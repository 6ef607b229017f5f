"""Fused packed-matmul path: kernel parity over awkward shapes / dtypes /
all Table 3 widths (2-D and batched-expert orientations), layer dispatch,
the fused backward (dx/dW grad parity vs. the materialized path), spec
normalization, and signedness round-trips."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.core.compress import CompressionPlan
from repro.core.formats import FLOAT_FORMATS
from repro.core.tensor_store import pack_tensor, pack_tree
from repro.kernels import ops as kops
from repro.kernels import ref as R
from repro.kernels.kv_decode import kv_decode
from repro.kernels.packed_matmul import packed_matmul, packed_matmul_batched
from repro.models import layers as L

ALL_WIDTHS = sorted(FLOAT_FORMATS)          # 8..32, incl. the AF32 identity


@pytest.fixture
def pallas_interpret_backend():
    kops.set_backend("pallas_interpret")
    yield
    kops.set_backend("jnp")


# -- kernel parity: fused vs unpack+einsum ------------------------------------

@pytest.mark.parametrize("bits", ALL_WIDTHS)
def test_fused_parity_all_widths(bits):
    m, k, n = 4, 64, 96
    rng = np.random.default_rng(bits)
    x = jnp.asarray((rng.standard_normal((m, k)) * 0.5).astype(np.float32))
    w = jnp.asarray((rng.standard_normal((k, n)) * 0.5).astype(np.float32))
    wp = R.pack_ref(w, bits)
    ref = R.packed_matmul_ref(x, wp, bits, n)
    got = packed_matmul(x, wp, bits, n, bm=8, bn=32, bk=32, interpret=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("mkn", [(3, 50, 33), (5, 96, 40), (7, 33, 96),
                                 (1, 37, 65)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_fused_nonmultiple_shapes(mkn, dtype):
    """Divisor-block selection + zero padding over shapes that divide by
    nothing MXU-shaped; bf16 inputs upcast in-kernel."""
    bits = 16
    m, k, n = mkn
    rng = np.random.default_rng(m + k + n)
    x = jnp.asarray((rng.standard_normal((m, k)) * 0.5)).astype(dtype)
    w = jnp.asarray((rng.standard_normal((k, n)) * 0.5).astype(np.float32))
    wp = R.pack_ref(w, bits)
    ref = R.packed_matmul_ref(x, wp, bits, n)
    got = packed_matmul(x, wp, bits, n, bm=8, bn=32, bk=32, interpret=True)
    assert got.shape == (m, n)
    assert got.dtype == dtype
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref), rtol=tol, atol=tol)


def test_fused_leading_batch_dims():
    bits, k, n = 16, 40, 64
    rng = np.random.default_rng(0)
    x = jnp.asarray((rng.standard_normal((2, 3, k)) * 0.5
                     ).astype(np.float32))
    w = jnp.asarray((rng.standard_normal((k, n)) * 0.5).astype(np.float32))
    wp = R.pack_ref(w, bits)
    got = packed_matmul(x, wp, bits, n, bm=8, bn=32, bk=32, interpret=True)
    assert got.shape == (2, 3, n)
    ref = R.packed_matmul_ref(x, wp, bits, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("bits", [8, 16, 28])
def test_fused_transpose_unembed_spec(bits):
    """x @ W.T with W (V, D) packed along D — the tied-unembed spec."""
    v, d = 48, 40
    rng = np.random.default_rng(bits)
    x = jnp.asarray((rng.standard_normal((2, 5, d)) * 0.5
                     ).astype(np.float32))
    w = jnp.asarray((rng.standard_normal((v, d)) * 0.5).astype(np.float32))
    wp = R.pack_ref(w, bits)
    got = packed_matmul(x, wp, bits, v, transpose=True,
                        bm=8, bn=16, bk=32, interpret=True)
    assert got.shape == (2, 5, v)
    ref = R.packed_matmul_ref(x, wp, bits, v, transpose=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


# -- batched-expert orientation (MoE banks) -----------------------------------

@pytest.mark.parametrize("bits", [8, 16, 28])
@pytest.mark.parametrize("transpose", [False, True])
def test_batched_parity_widths_and_orientations(bits, transpose):
    e, c, k, n = 3, 5, 64, 96
    rng = np.random.default_rng(bits + transpose)
    x = jnp.asarray((rng.standard_normal((e, c, k)) * 0.5
                     ).astype(np.float32))
    wshape = (e, n, k) if transpose else (e, k, n)
    w = jnp.asarray((rng.standard_normal(wshape) * 0.5).astype(np.float32))
    wp = R.pack_ref(w, bits)
    ref = R.packed_matmul_batched_ref(x, wp, bits, n, transpose)
    got = packed_matmul_batched(x, wp, bits, n, transpose=transpose,
                                bm=8, bn=32, bk=32, interpret=True)
    assert got.shape == (e, c, n)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("eckn", [(2, 3, 50, 33), (5, 1, 37, 65),
                                  (1, 7, 33, 96)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_batched_nonmultiple_shapes(eckn, dtype):
    """Expert banks with dims that divide by nothing MXU-shaped: divisor
    selection + zero-padding must hold per expert; bf16 upcasts in-kernel."""
    bits = 16
    e, c, k, n = eckn
    rng = np.random.default_rng(sum(eckn))
    x = jnp.asarray((rng.standard_normal((e, c, k)) * 0.5)).astype(dtype)
    w = jnp.asarray((rng.standard_normal((e, k, n)) * 0.5
                     ).astype(np.float32))
    wp = R.pack_ref(w, bits)
    ref = R.packed_matmul_batched_ref(x, wp, bits, n)
    got = packed_matmul_batched(x, wp, bits, n, bm=8, bn=32, bk=32,
                                interpret=True)
    assert got.shape == (e, c, n)
    assert got.dtype == dtype
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(ref), rtol=tol, atol=tol)


def test_expert_linear_dispatches_to_batched_kernel(monkeypatch):
    calls = []
    orig = kops.packed_matmul_batched

    def spy(*args, **kwargs):
        calls.append(True)
        return orig(*args, **kwargs)

    monkeypatch.setattr(kops, "packed_matmul_batched", spy)
    rng = np.random.default_rng(9)
    x = jnp.asarray(rng.standard_normal((4, 6, 32)).astype(np.float32))
    w = jnp.asarray((rng.standard_normal((4, 32, 64)) * 0.2
                     ).astype(np.float32))
    wb = pack_tensor(w, 16)
    got = L.expert_linear(x, wb)
    assert calls == [True]
    ref = L.expert_linear(x, wb, fallback=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_expert_linear_plain_and_4d_take_unpack_path(monkeypatch):
    """Plain banks and >= 4-D packed leaves must not touch the batched
    kernel — only per-layer 3-D float banks are fusable."""
    def boom(*a, **k):
        raise AssertionError("batched kernel must not be called")

    monkeypatch.setattr(kops, "packed_matmul_batched", boom)
    rng = np.random.default_rng(10)
    x = jnp.asarray(rng.standard_normal((2, 3, 32)).astype(np.float32))
    w = jnp.asarray((rng.standard_normal((2, 32, 64)) * 0.2
                     ).astype(np.float32))
    out = L.expert_linear(x, w)                   # plain array
    assert out.shape == (2, 3, 64)
    w4 = pack_tensor(jnp.asarray(
        (rng.standard_normal((2, 2, 32, 64)) * 0.2).astype(np.float32)), 16)
    assert not L._fusable_batched(w4)
    x4 = jnp.asarray(rng.standard_normal((2, 2, 3, 32)).astype(np.float32))
    out4 = L.expert_linear(x4, w4)            # materialized, never fused
    ref4 = jnp.einsum("...ck,...kn->...cn", x4, w4.unpack())
    assert out4.shape == (2, 2, 3, 64)
    np.testing.assert_allclose(np.asarray(out4), np.asarray(ref4),
                               rtol=1e-5, atol=1e-5)


# -- layer dispatch -----------------------------------------------------------

def test_linear_dispatches_to_fused_kernel(monkeypatch):
    calls = []
    orig = kops.packed_matmul

    def spy(*args, **kwargs):
        calls.append(kwargs.get("transpose", False))
        return orig(*args, **kwargs)

    monkeypatch.setattr(kops, "packed_matmul", spy)
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((2, 3, 64)).astype(np.float32))
    w = jnp.asarray((rng.standard_normal((64, 96)) * 0.2
                     ).astype(np.float32))
    wt = pack_tensor(w, 16)
    got = L.linear(x, wt)
    assert calls == [False]
    ref = L.linear(x, wt, fallback=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)

    head = pack_tensor(jnp.asarray(
        (rng.standard_normal((128, 64)) * 0.2).astype(np.float32)), 16)
    got_t = L.unembed(x, head, tied=True)
    assert calls == [False, True]
    ref_t = L.unembed(x, head, tied=True, fallback=True)
    np.testing.assert_allclose(np.asarray(got_t), np.asarray(ref_t),
                               rtol=1e-5, atol=1e-5)


def test_linear_fused_under_pallas_interpret(pallas_interpret_backend):
    """The dispatch survives the real kernel backend, not just the oracle."""
    rng = np.random.default_rng(2)
    x = jnp.asarray(rng.standard_normal((4, 64)).astype(np.float32))
    w = jnp.asarray((rng.standard_normal((64, 32)) * 0.2
                     ).astype(np.float32))
    wt = pack_tensor(w, 16)
    got = L.linear(x, wt)
    ref = L.linear(x, wt, fallback=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


def test_linear_grad_matches_fallback_path():
    """The fused forward carries a custom VJP whose backward is the
    materialized unpack path — grads wrt x must match it."""
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.standard_normal((2, 48)).astype(np.float32))
    wt = pack_tensor(jnp.asarray(
        (rng.standard_normal((48, 32)) * 0.2).astype(np.float32)), 16)

    g_fused = jax.grad(lambda x_: L.linear(x_, wt).sum())(x)
    g_ref = jax.grad(
        lambda x_: L.linear(x_, wt, fallback=True).astype(jnp.float32).sum()
    )(x)
    np.testing.assert_allclose(np.asarray(g_fused), np.asarray(g_ref),
                               rtol=1e-5, atol=1e-5)

    ht = pack_tensor(jnp.asarray(
        (rng.standard_normal((64, 48)) * 0.2).astype(np.float32)), 16)
    g_fused_t = jax.grad(lambda x_: L.unembed(x_, ht, tied=True).sum())(x)
    g_ref_t = jax.grad(
        lambda x_: L.unembed(x_, ht, tied=True,
                             fallback=True).astype(jnp.float32).sum())(x)
    np.testing.assert_allclose(np.asarray(g_fused_t), np.asarray(g_ref_t),
                               rtol=1e-5, atol=1e-5)


# -- fused backward: grad parity vs. the materialized path --------------------

@pytest.mark.parametrize("bits", ALL_WIDTHS)
def test_grad_parity_all_widths(bits):
    """dx through the fused backward (flipped-orientation kernel) must
    match the materialized unpack+einsum backward at every Table 3 width."""
    rng = np.random.default_rng(bits)
    x = jnp.asarray(rng.standard_normal((3, 48)).astype(np.float32))
    wt = pack_tensor(jnp.asarray(
        (rng.standard_normal((48, 64)) * 0.2).astype(np.float32)), bits)
    g_fused = jax.grad(lambda x_: (L.linear(x_, wt) ** 2).sum())(x)
    g_ref = jax.grad(
        lambda x_: (L.linear(x_, wt, fallback=True).astype(jnp.float32)
                    ** 2).sum())(x)
    np.testing.assert_allclose(np.asarray(g_fused), np.asarray(g_ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("mkn", [(3, 50, 33), (7, 33, 96), (1, 37, 65)])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_grad_parity_awkward_shapes_and_dtypes(mkn, dtype):
    m, k, n = mkn
    rng = np.random.default_rng(m + k + n)
    x = jnp.asarray((rng.standard_normal((m, k)) * 0.5)).astype(dtype)
    wt = pack_tensor(jnp.asarray(
        (rng.standard_normal((k, n)) * 0.2).astype(np.float32)), 16)
    g_fused = jax.grad(
        lambda x_: L.linear(x_, wt).astype(jnp.float32).sum())(x)
    g_ref = jax.grad(
        lambda x_: L.linear(x_, wt, fallback=True).astype(jnp.float32)
        .sum())(x)
    assert g_fused.dtype == dtype
    tol = 1e-5 if dtype == jnp.float32 else 2e-2
    np.testing.assert_allclose(np.asarray(g_fused, np.float32),
                               np.asarray(g_ref, np.float32),
                               rtol=tol, atol=tol)


@pytest.mark.parametrize("bits", [8, 16, 28])
def test_grad_parity_transpose_orientation(bits):
    """The tied-unembed (transpose) forward backs into the kernel's
    *normal* orientation for dx."""
    rng = np.random.default_rng(bits + 100)
    x = jnp.asarray(rng.standard_normal((2, 5, 40)).astype(np.float32))
    ht = pack_tensor(jnp.asarray(
        (rng.standard_normal((64, 40)) * 0.2).astype(np.float32)), bits)
    g_fused = jax.grad(
        lambda x_: (L.unembed(x_, ht, tied=True) ** 2).sum())(x)
    g_ref = jax.grad(
        lambda x_: (L.unembed(x_, ht, tied=True, fallback=True)
                    .astype(jnp.float32) ** 2).sum())(x)
    np.testing.assert_allclose(np.asarray(g_fused), np.asarray(g_ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("bits", [8, 16])
def test_grad_parity_batched_expert_bank(bits):
    """dx through the batched fused backward (per-expert transpose
    orientation) vs. the materialized einsum backward."""
    rng = np.random.default_rng(bits + 200)
    x = jnp.asarray(rng.standard_normal((3, 4, 40)).astype(np.float32))
    wb = pack_tensor(jnp.asarray(
        (rng.standard_normal((3, 40, 24)) * 0.2).astype(np.float32)), bits)
    g_fused = jax.grad(lambda x_: (L.expert_linear(x_, wb) ** 2).sum())(x)
    g_ref = jax.grad(
        lambda x_: (L.expert_linear(x_, wb, fallback=True)
                    .astype(jnp.float32) ** 2).sum())(x)
    np.testing.assert_allclose(np.asarray(g_fused), np.asarray(g_ref),
                               rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("transpose", [False, True])
@pytest.mark.parametrize("bits", [8, 16, 24])
def test_st_linear_dx_dw_parity(transpose, bits):
    """Straight-through packed training: fused dx/dW vs. the materialized
    straight-through reference must agree for both orientations. dW is
    accumulated from residuals alone (never decodes W), so it is exact."""
    rng = np.random.default_rng(bits + 7 * transpose)
    k, n = 40, 56
    x = jnp.asarray(rng.standard_normal((2, 3, k)).astype(np.float32))
    w = jnp.asarray((rng.standard_normal((n, k) if transpose else (k, n))
                     * 0.2).astype(np.float32))
    wt = pack_tensor(w, bits)
    wm = wt.unpack()                 # dense master copy

    def loss(x_, wm_, fb):
        return (L.st_linear(x_, wt, wm_, transpose=transpose,
                            fallback=fb) ** 2).sum()

    dx_f, dw_f = jax.grad(loss, argnums=(0, 1))(x, wm, False)
    dx_r, dw_r = jax.grad(loss, argnums=(0, 1))(x, wm, True)
    assert dw_f.shape == wm.shape
    np.testing.assert_allclose(np.asarray(dx_f), np.asarray(dx_r),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(dw_f), np.asarray(dw_r),
                               rtol=1e-5, atol=1e-5)


def test_packed_matmul_dw_matches_einsum():
    rng = np.random.default_rng(42)
    x = jnp.asarray(rng.standard_normal((2, 5, 16)).astype(np.float32))
    g = jnp.asarray(rng.standard_normal((2, 5, 24)).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(kops.packed_matmul_dw(x, g)),
        np.asarray(jnp.einsum("...k,...n->kn", x, g)), rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(
        np.asarray(kops.packed_matmul_dw(x, g, transpose=True)),
        np.asarray(jnp.einsum("...n,...k->nk", g, x)), rtol=1e-5, atol=1e-5)
    xe = jnp.asarray(rng.standard_normal((3, 4, 8)).astype(np.float32))
    ge = jnp.asarray(rng.standard_normal((3, 4, 6)).astype(np.float32))
    np.testing.assert_allclose(
        np.asarray(kops.packed_matmul_dw(xe, ge, batched=True)),
        np.asarray(jnp.einsum("eck,ecn->ekn", xe, ge)),
        rtol=1e-5, atol=1e-5)


# -- spec normalization + slow-path warning -----------------------------------

def test_whitespace_spec_still_fuses(monkeypatch):
    """einsum ignores spaces, so the dispatch must too — a whitespace
    variant of the plain contraction used to silently take the slow path."""
    calls = []
    orig = kops.packed_matmul

    def spy(*args, **kwargs):
        calls.append(True)
        return orig(*args, **kwargs)

    monkeypatch.setattr(kops, "packed_matmul", spy)
    rng = np.random.default_rng(11)
    x = jnp.asarray(rng.standard_normal((2, 48)).astype(np.float32))
    wt = pack_tensor(jnp.asarray(
        (rng.standard_normal((48, 32)) * 0.2).astype(np.float32)), 16)
    got = L.linear(x, wt, spec="...d, df -> ...f")
    assert calls == [True]
    ref = L.linear(x, wt, spec="...d,df->...f", fallback=True)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-5)


def test_unrecognized_spec_against_packed_weight_warns_once():
    rng = np.random.default_rng(12)
    x = jnp.asarray(rng.standard_normal((2, 32)).astype(np.float32))
    wt = pack_tensor(jnp.asarray(
        (rng.standard_normal((48, 32)) * 0.2).astype(np.float32)), 16)
    spec = "...z,yz->...y"                 # valid einsum, not fusable
    L._warn_unfused_spec.cache_clear()
    with warnings.catch_warnings(record=True) as rec:
        warnings.simplefilter("always")
        L.linear(x, wt, spec=spec)
        L.linear(x, wt, spec=spec)         # second call: cached, silent
    msgs = [w for w in rec if "materialized unpack path" in str(w.message)]
    assert len(msgs) == 1
    # plain (unpacked) weights never warn — nothing is lost there
    with warnings.catch_warnings(record=True) as rec2:
        warnings.simplefilter("always")
        L.linear(x, jnp.ones((48, 32), jnp.float32), spec=spec)
    assert not [w for w in rec2
                if "materialized unpack path" in str(w.message)]


def test_int_and_stacked_packed_fall_back(monkeypatch):
    """Int-kind packed weights and non-plain einsum specs must take the
    unpack path, never the fused kernel."""
    def boom(*a, **k):
        raise AssertionError("fused kernel must not be called")

    monkeypatch.setattr(kops, "packed_matmul", boom)
    x = jnp.ones((2, 32), jnp.float32)
    w_int = pack_tensor(jnp.arange(32 * 32, dtype=jnp.int32
                                   ).reshape(32, 32) % 100, 8,
                        signed=False, out_dtype=jnp.float32)
    out = L.linear(x, w_int)
    assert out.shape == (2, 32)

    # a float packed weight but a spec contracting the weight's *second*
    # axis: the fused kernel would compute the wrong product, so the
    # dispatch guard must route it to unpack+einsum
    rng = np.random.default_rng(5)
    wf = jnp.asarray((rng.standard_normal((48, 32)) * 0.2
                      ).astype(np.float32))
    wt = pack_tensor(wf, 16)
    got = L.linear(x, wt, spec="...a,ba->...b")
    ref = jnp.einsum("...a,ba->...b", x, wf.astype(jnp.float16
                                                   ).astype(jnp.float32))
    assert got.shape == (2, 48)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=5e-3, atol=5e-3)

    # all-same-letter spec is einsum diagonal scaling, not a matmul —
    # it must also bypass the fused kernel
    wd = pack_tensor(jnp.asarray((rng.standard_normal((32, 32)) * 0.2
                                  ).astype(np.float32)), 16)
    got_d = L.linear(x, wd, spec="...d,dd->...d")
    assert got_d.shape == (2, 32)


# -- signedness: pack_tree / CompressionPlan round-trips ----------------------

@pytest.mark.parametrize("bits", [4, 8, 12, 16])
def test_int_roundtrip_unsigned_top_bit(bits):
    """Unsigned tensors with the top bit set must not come back negative."""
    hi = (1 << bits) - 1
    vals = jnp.asarray(
        np.array([0, 1, hi // 2, hi - 1, hi] * 8, np.int32).reshape(8, 5))
    pt = pack_tensor(vals, bits, signed=False)
    back = np.asarray(pt.unpack())
    assert back.min() >= 0
    np.testing.assert_array_equal(back, np.asarray(vals))


@pytest.mark.parametrize("bits", [4, 8, 12, 16])
def test_int_roundtrip_signed(bits):
    lo, hi = -(1 << (bits - 1)), (1 << (bits - 1)) - 1
    vals = jnp.asarray(
        np.array([lo, lo + 1, -1, 0, 1, hi] * 4, np.int32).reshape(8, 3))
    pt = pack_tensor(vals, bits, signed=True)
    np.testing.assert_array_equal(np.asarray(pt.unpack()),
                                  np.asarray(vals))


@settings(max_examples=50)
@given(st.integers(1, 4), st.integers(0, 2 ** 16 - 1))
def test_int_roundtrip_property(nibbles, value):
    """Any value in [0, 2^bits) survives an unsigned round-trip; its
    two's-complement reinterpretation survives a signed one."""
    bits = 4 * nibbles
    value %= 1 << bits
    arr = jnp.full((4, 32), value, jnp.int32)
    back_u = int(np.asarray(pack_tensor(arr, bits, signed=False)
                            .unpack())[0, 0])
    assert back_u == value
    signed_val = value - (1 << bits) if value >= 1 << (bits - 1) else value
    arr_s = jnp.full((4, 32), signed_val, jnp.int32)
    back_s = int(np.asarray(pack_tensor(arr_s, bits, signed=True)
                            .unpack())[0, 0])
    assert back_s == signed_val


def test_pack_tree_threads_signedness_regression():
    """CompressionPlan.bits_of used to drop the signed flag, so pack_tree
    packed unsigned ranges as signed and [0, 255] came back negative."""
    plan = CompressionPlan(float_bits={},
                           int_bits={"x": (8, False), "y": (6, True)})
    tree = {
        "x": jnp.arange(256, dtype=jnp.int32).reshape(8, 32),   # top bit set
        "y": jnp.asarray(np.array([-17, 0, 15] * 32, np.int32
                                  ).reshape(3, 32)),
    }
    packed = pack_tree(tree, plan.bits_of)
    assert packed["x"].signed is False
    assert packed["x"].bits == 8
    assert packed["y"].signed is True
    assert packed["y"].bits == 8                 # 6 rounds up to a slice
    np.testing.assert_array_equal(np.asarray(packed["x"].unpack()),
                                  np.asarray(tree["x"]))
    np.testing.assert_array_equal(np.asarray(packed["y"].unpack()),
                                  np.asarray(tree["y"]))


# -- kv_decode degenerate mask ------------------------------------------------

def test_kv_decode_fully_masked_is_zero():
    """kv_len == 0 must give zeros, not the mean of stale cache rows."""
    b, h, hkv, d, s = 2, 4, 2, 32, 64
    rng = np.random.default_rng(7)
    q = jnp.asarray(rng.standard_normal((b, h, d)).astype(np.float32))
    # non-zero "stale garbage" in the cache
    k = jnp.asarray((rng.standard_normal((b, s, hkv * d)) + 3.0
                     ).astype(np.float32))
    v = jnp.asarray((rng.standard_normal((b, s, hkv * d)) + 3.0
                     ).astype(np.float32))
    kp, vp = R.pack_ref(k, 16), R.pack_ref(v, 16)
    lens = jnp.asarray(np.array([0, s], np.int32))
    got = np.asarray(kv_decode(q, kp, vp, lens, 16, hkv, block_s=32,
                               interpret=True))
    ref = np.asarray(R.kv_decode_ref(q, kp, vp, 16, hkv, lens))
    assert np.isfinite(got).all() and np.isfinite(ref).all()
    np.testing.assert_array_equal(got[0], np.zeros_like(got[0]))
    np.testing.assert_array_equal(ref[0], np.zeros_like(ref[0]))
    # the non-degenerate batch entry still matches the oracle
    np.testing.assert_allclose(got[1], ref[1], rtol=2e-5, atol=2e-5)
