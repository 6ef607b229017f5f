"""Per-kernel validation: Pallas (interpret mode) vs. pure-jnp oracle,
sweeping shapes, widths and dtypes per the deliverable spec. Pack,
unpack and the Value Converter / Truncator have one implementation (XLA,
through ``kernels.ops``); their cases pin it against the dense bitstream
and the scalar format codec, which share no code with the lane-tiled
layout."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import bitpack
from repro.core.formats import FLOAT_FORMATS, decode_float, encode_float
from repro.kernels import ops
from repro.kernels import ref as R
from repro.kernels.kv_decode import kv_decode
from repro.kernels.packed_matmul import packed_matmul

WIDTHS = [8, 12, 16, 20, 24, 28]


def _stream_rows(x, bits):
    """Each row's codes as one dense bitstream, tile by tile and lane by
    lane — the lane-tiled layout's definition, built from the scalar
    stream packer."""
    codes = np.asarray(encode_float(jnp.asarray(x), FLOAT_FORMATS[bits]))
    n = codes.shape[-1]
    g, wg = bitpack.lane_group(bits)
    lanes = bitpack.tile_lanes(n, bits)
    tiles = bitpack.packed_group_words(n, bits) // (wg * lanes)
    pad = np.zeros(codes.shape[:-1] + (tiles * g * lanes - n,), np.uint32)
    c = np.concatenate([codes, pad], -1).reshape(
        codes.shape[:-1] + (tiles, g, lanes)).swapaxes(-1, -2)
    rows = [np.asarray(bitpack.pack_stream(jnp.asarray(r.reshape(-1)),
                                           bits))
            for r in c.reshape((-1,) + c.shape[-3:])]
    w = np.stack(rows).reshape(codes.shape[:-1] + (tiles, lanes, wg))
    return w.swapaxes(-1, -2).reshape(codes.shape[:-1] + (-1,))


@pytest.mark.parametrize("bits", WIDTHS)
@pytest.mark.parametrize("shape", [(32, 128), (64, 256), (8, 32)])
def test_pack_unpack_vs_ref(bits, shape):
    rng = np.random.default_rng(bits)
    x = (rng.standard_normal(shape) * 4).astype(np.float32)
    got_p = ops.pack(jnp.asarray(x), bits)
    assert (np.asarray(got_p) == _stream_rows(x, bits)).all()
    got_u = ops.unpack(got_p, bits, shape[1])
    want = decode_float(encode_float(jnp.asarray(x), FLOAT_FORMATS[bits]),
                        FLOAT_FORMATS[bits])
    assert (np.asarray(got_u) == np.asarray(want)).all()


@pytest.mark.parametrize("bits", WIDTHS)
def test_unpack_bf16_output(bits):
    rng = np.random.default_rng(bits)
    x = rng.standard_normal((16, 64)).astype(np.float32)
    p = R.pack_ref(jnp.asarray(x), bits)
    got = ops.unpack(p, bits, 64, out_dtype=jnp.bfloat16)
    ref = R.unpack_ref(p, bits, 64).astype(jnp.bfloat16)
    assert got.dtype == jnp.bfloat16
    assert (np.asarray(got, np.float32) == np.asarray(ref, np.float32)).all()


@pytest.mark.parametrize("bits", [8, 16, 24])
def test_convert_truncate_vs_ref(bits):
    """The Truncator/Converter lanes against the scalar codec: AF16 is
    IEEE half, so numpy's own float16 rounding is the reference there."""
    rng = np.random.default_rng(bits)
    x = (rng.standard_normal((32, 64)) * 100).astype(np.float32)
    codes = R.truncate_ref(jnp.asarray(x), bits)
    dec = np.asarray(R.convert_ref(codes, bits))
    if bits == 16:
        assert (dec == x.astype(np.float16).astype(np.float32)).all()
    fmt = FLOAT_FORMATS[bits]
    normal = ((np.abs(x) >= 2.0 ** (1 - fmt.bias))
              & (np.abs(x) < fmt.max_finite))
    rel = np.abs(dec - x)[normal] / np.abs(x)[normal]
    assert normal.any()
    assert rel.max() <= 2.0 ** -(fmt.mantissa_bits + 1) * (1 + 1e-6)
    big = np.abs(x) > 2 * fmt.max_finite
    assert (np.isinf(dec[big]) & (np.sign(dec[big]) == np.sign(x[big]))).all()


@pytest.mark.parametrize("bits", [8, 12, 16])
@pytest.mark.parametrize("mkn", [(32, 64, 64), (64, 128, 96)])
def test_packed_matmul_vs_ref(bits, mkn):
    m, k, n = mkn
    rng = np.random.default_rng(bits + m)
    x = (rng.standard_normal((m, k)) * 0.3).astype(np.float32)
    w = (rng.standard_normal((k, n)) * 0.3).astype(np.float32)
    wp = R.pack_ref(jnp.asarray(w), bits)
    ref = R.packed_matmul_ref(jnp.asarray(x), wp, bits, n)
    got = packed_matmul(jnp.asarray(x), wp, bits, n, bm=16, bn=32, bk=32)
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=1e-5, atol=1e-4)


def test_packed_matmul_against_dense_matmul():
    """Fused kernel ~= dense matmul within format quantization error."""
    bits = 16
    rng = np.random.default_rng(0)
    x = (rng.standard_normal((32, 64)) * 0.3).astype(np.float32)
    w = (rng.standard_normal((64, 64)) * 0.3).astype(np.float32)
    wp = R.pack_ref(jnp.asarray(w), bits)
    got = packed_matmul(jnp.asarray(x), wp, bits, 64, bm=16, bn=32, bk=32)
    dense = x @ w.astype(np.float16).astype(np.float32)
    np.testing.assert_allclose(np.asarray(got), dense, rtol=1e-5,
                               atol=1e-4)


@pytest.mark.parametrize("bits", [8, 16])
@pytest.mark.parametrize("cfg", [
    dict(b=2, h=8, hkv=2, d=64, s=128, block_s=64),
    dict(b=1, h=4, hkv=4, d=32, s=256, block_s=128),
    dict(b=3, h=6, hkv=1, d=64, s=64, block_s=64),
])
def test_kv_decode_vs_ref(bits, cfg):
    rng = np.random.default_rng(bits)
    b, s, hkv, d = cfg["b"], cfg["s"], cfg["hkv"], cfg["d"]
    q = rng.standard_normal((b, cfg["h"], d)).astype(np.float32)
    k = (rng.standard_normal((b, s, hkv * d)) * 0.3).astype(np.float32)
    v = (rng.standard_normal((b, s, hkv * d)) * 0.3).astype(np.float32)
    kp = R.pack_ref(jnp.asarray(k), bits)
    vp = R.pack_ref(jnp.asarray(v), bits)
    lens = np.asarray(rng.integers(1, s + 1, b), np.int32)
    ref = R.kv_decode_ref(jnp.asarray(q), kp, vp, bits, hkv,
                          jnp.asarray(lens))
    got = kv_decode(jnp.asarray(q), kp, vp, jnp.asarray(lens), bits,
                    hkv, block_s=cfg["block_s"])
    np.testing.assert_allclose(np.asarray(got), np.asarray(ref),
                               rtol=2e-5, atol=2e-5)


def test_kernel_backend_dispatch():
    from repro.kernels import ops
    rng = np.random.default_rng(0)
    x = rng.standard_normal((8, 64)).astype(np.float32)
    ops.set_backend("jnp")
    a = ops.pack(jnp.asarray(x), 16)
    ops.set_backend("pallas_interpret")
    b = ops.pack(jnp.asarray(x), 16)
    ops.set_backend("jnp")
    assert (np.asarray(a) == np.asarray(b)).all()
