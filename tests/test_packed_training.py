"""Packed-master training: the STWeight straight-through tree, the
repack/staleness contract, checkpoint (codes, masters, plan) resume
parity, the packed-word sharding rule, and the take gather kernel."""
import dataclasses
import tempfile

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.compat import prng_key, tree_leaves, tree_map
from repro.configs import get_config
from repro.core.compress import uniform_plan, repack
from repro.core.formats import FLOAT_LADDER
from repro.core.tensor_store import (
    STWeight,
    is_packed,
    is_st,
    pack_tensor,
    st_tree,
    tree_bytes,
)
from repro.kernels import ref as R
from repro.kernels.take import take_rows
from repro.models import layers as L
from repro.optim import packed_staleness, repack_params
from repro.train import Trainer, TrainConfig


def _tiny_cfg(name="qwen3_8b"):
    return get_config(name).reduced()


def _pair(rng, shape, bits=16):
    w = jnp.asarray((rng.standard_normal(shape) * 0.3).astype(np.float32))
    return STWeight(pack_tensor(w, bits), w)


# -- STWeight layer dispatch --------------------------------------------------

@pytest.mark.parametrize("bits", [8, 12, 16])
def test_st_linear_forward_matches_packed_and_grads_master(bits):
    """Forward value comes from the codes (bit-identical to a bare
    PackedTensor weight); dW lands on the master and matches the
    materialized straight-through reference."""
    rng = np.random.default_rng(0)
    stw = _pair(rng, (64, 96), bits)
    x = jnp.asarray(rng.standard_normal((4, 64)).astype(np.float32))

    out_st = L.linear(x, stw)
    out_pk = L.linear(x, stw.packed)
    np.testing.assert_array_equal(np.asarray(out_st), np.asarray(out_pk))

    def loss_fused(m):
        return (L.linear(x, STWeight(stw.packed, m)) ** 2).sum()

    def loss_mat(m):
        return (L.linear(x, STWeight(stw.packed, m),
                         fallback=True) ** 2).sum()

    g_fused = jax.grad(loss_fused)(stw.master)
    g_mat = jax.grad(loss_mat)(stw.master)
    np.testing.assert_allclose(np.asarray(g_fused), np.asarray(g_mat),
                               rtol=1e-4, atol=1e-4)
    assert float(jnp.abs(g_fused).max()) > 0


def test_st_unembed_both_orientations_grad_master():
    rng = np.random.default_rng(1)
    x = jnp.asarray(rng.standard_normal((3, 64)).astype(np.float32))
    for tied, shape in ((True, (96, 64)), (False, (64, 96))):
        stw = _pair(rng, shape)
        out_st = L.unembed(x, stw, tied=tied)
        out_pk = L.unembed(x, stw.packed, tied=tied)
        np.testing.assert_array_equal(np.asarray(out_st),
                                      np.asarray(out_pk))
        g = jax.grad(lambda m: (L.unembed(
            x, STWeight(stw.packed, m), tied=tied) ** 2).sum())(stw.master)
        g_ref = jax.grad(lambda m: (L.unembed(
            x, STWeight(stw.packed, m), tied=tied,
            fallback=True) ** 2).sum())(stw.master)
        np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                                   rtol=1e-4, atol=1e-4)


def test_st_expert_linear_batched_fused_grad_master():
    rng = np.random.default_rng(2)
    stw = _pair(rng, (3, 64, 96))
    x = jnp.asarray(rng.standard_normal((3, 5, 64)).astype(np.float32))
    out_st = L.expert_linear(x, stw)
    out_pk = L.expert_linear(x, stw.packed)
    np.testing.assert_array_equal(np.asarray(out_st), np.asarray(out_pk))
    g = jax.grad(lambda m: (L.expert_linear(
        x, STWeight(stw.packed, m)) ** 2).sum())(stw.master)
    g_ref = jax.grad(lambda m: (L.expert_linear(
        x, STWeight(stw.packed, m), fallback=True) ** 2).sum())(stw.master)
    np.testing.assert_allclose(np.asarray(g), np.asarray(g_ref),
                               rtol=1e-4, atol=1e-4)


def test_st_embed_gather_forward_packed_grad_scatters_to_master():
    rng = np.random.default_rng(3)
    stw = _pair(rng, (32, 64))
    toks = jnp.asarray([[3, 3, 7], [0, 31, 7]], jnp.int32)
    out_st = L.embed(toks, stw)
    np.testing.assert_array_equal(
        np.asarray(out_st), np.asarray(L.embed(toks, stw.packed)))
    g = jax.grad(lambda m: (L.embed(
        toks, STWeight(stw.packed, m)) ** 2).sum())(stw.master)
    touched = np.unique(np.asarray(toks))
    mask = np.zeros(32, bool)
    mask[touched] = True
    gn = np.abs(np.asarray(g)).sum(-1)
    assert (gn[mask] > 0).all() and (gn[~mask] == 0).all()


def test_st_norm_scale_rides_materialized_straight_through():
    """Stacked norm scales packed by the plan decode straight-through:
    value from codes, tangent to the master — and slicing the stacked
    pair like the layer scan does yields per-layer STWeights."""
    rng = np.random.default_rng(4)
    stw = _pair(rng, (4, 64))          # stacked (L, d) scale
    x = jnp.asarray(rng.standard_normal((2, 64)).astype(np.float32))

    def slice_layer(pair, i):
        out = jax.tree_util.tree_map(lambda a: a[i], pair)
        assert is_st(out) and out.logical_shape == (64,)
        return out

    out = L.rms_norm(x, slice_layer(stw, 1))
    ref = L.rms_norm(x, slice_layer(stw, 1).packed)
    np.testing.assert_array_equal(np.asarray(out), np.asarray(ref))

    def loss(m):
        pair = STWeight(stw.packed, m)
        return (L.rms_norm(x, slice_layer(pair, 1)) ** 2).sum()

    g = jax.grad(loss)(stw.master)
    assert float(jnp.abs(g[1]).max()) > 0
    assert float(jnp.abs(g[0]).max()) == 0   # only layer 1 touched


def test_st_tree_pairs_planned_leaves_only():
    cfg = _tiny_cfg()
    from repro.models.lm import LM
    params = LM(cfg).init(prng_key(0))
    plan = uniform_plan(params, 16)
    packed = repack(params, plan)
    combined = st_tree(packed, params)
    flat_c = tree_leaves(combined, is_leaf=is_st)
    n_st = sum(is_st(l) for l in flat_c)
    n_packed = sum(is_packed(l)
                   for l in tree_leaves(packed, is_leaf=is_packed))
    assert n_st == n_packed > 0
    # unplanned riders come from the masters, not the packed mirror
    assert not any(is_packed(l) for l in flat_c if not is_st(l))


# -- repack / staleness -------------------------------------------------------

@given(st.sampled_from(FLOAT_LADDER[:-1]))
@settings(max_examples=6, deadline=None)
def test_repack_then_staleness_exactly_zero(bits):
    """Right after a repack, decode(codes) must equal a fresh qdq of the
    masters *exactly* — no residual drift."""
    rng = np.random.default_rng(bits)
    masters = {
        "w": jnp.asarray(rng.standard_normal((8, 64)).astype(np.float32)),
        "norm": jnp.asarray(
            rng.standard_normal((64,)).astype(np.float32)),
    }
    packed = {"w": pack_tensor(jnp.zeros((8, 64)), bits),
              "norm": masters["norm"]}
    stale0 = float(packed_staleness(packed, masters))
    assert stale0 > 0                      # zeros vs random masters
    repacked = repack_params(packed, masters)
    assert float(packed_staleness(repacked, masters)) == 0.0
    # drift the masters: staleness reappears and upper-bounds the drift
    drifted = {"w": masters["w"] + 0.25, "norm": masters["norm"]}
    assert float(packed_staleness(repacked, drifted)) > 0


def test_repack_every_zero_rejected():
    from repro.models.lm import LM
    from repro.optim import AdamWConfig
    from repro.train.loop import make_train_step
    cfg = _tiny_cfg()
    tc = TrainConfig(pack_params=True, repack_every=0)
    with pytest.raises(ValueError, match="repack_every"):
        make_train_step(LM(cfg), AdamWConfig(), tc)


def test_trainer_repack_every_staleness_contract():
    cfg = _tiny_cfg()
    tc = TrainConfig(steps=4, seq_len=32, global_batch=2, lr=1e-2,
                     log_every=1, pack_params=True, repack_every=2)
    m = Trainer(cfg, tc).run()
    stale = dict(m["staleness"])
    assert stale[1] == 0.0 and stale[3] == 0.0   # just repacked
    assert stale[0] > 0 or stale[2] > 0          # stale between repacks


# -- end-to-end training ------------------------------------------------------

def test_packed_master_loss_tracks_dense():
    cfg = _tiny_cfg()
    tc = TrainConfig(steps=3, seq_len=32, global_batch=2, lr=1e-3)
    dense = Trainer(cfg, tc).run()
    packed = Trainer(
        cfg, dataclasses.replace(tc, pack_params=True)).run()
    for d, p in zip(dense["losses"], packed["losses"]):
        assert abs(d - p) / abs(d) < 0.01, (dense["losses"],
                                            packed["losses"])


def test_packed_master_weight_stream_is_bits_over_32():
    cfg = _tiny_cfg()
    from repro.models.lm import LM
    params = LM(cfg).init(prng_key(0))
    packed = repack(params, uniform_plan(params, 16))
    pb, fb = tree_bytes(packed)
    # fwd + fused dx bwd each stream the packed words once
    assert 2 * pb <= 2 * (16 / 32) * fb * 1.02


@pytest.mark.parametrize("arch", ["deepseek_moe_16b", "whisper_small"])
def test_packed_master_other_families(arch):
    """MoE expert banks (batched ST kernel) and encdec (tied cross paths)
    train packed within tolerance."""
    cfg = _tiny_cfg(arch)
    tc = TrainConfig(steps=2, seq_len=32, global_batch=2, lr=1e-3)
    dense = Trainer(cfg, tc).run()
    packed = Trainer(
        cfg, dataclasses.replace(tc, pack_params=True)).run()
    rel = abs(dense["final_loss"] - packed["final_loss"]) / abs(
        dense["final_loss"])
    assert rel < 0.01, (dense["final_loss"], packed["final_loss"])


def test_packed_master_checkpoint_resume_bitwise():
    """save -> restore -> continue must be bitwise-equal to an
    uninterrupted run for 3 further steps (the (codes, masters, plan)
    triple round-trips exactly)."""
    cfg = _tiny_cfg()
    base = TrainConfig(steps=6, seq_len=32, global_batch=2, lr=1e-3,
                       checkpoint_every=3, pack_params=True,
                       repack_every=2)
    with tempfile.TemporaryDirectory() as d:
        m1 = Trainer(cfg, dataclasses.replace(
            base, checkpoint_dir=d)).run()
    with tempfile.TemporaryDirectory() as d:
        Trainer(cfg, dataclasses.replace(
            base, steps=3, checkpoint_dir=d)).run()
        m2 = Trainer(cfg, dataclasses.replace(
            base, checkpoint_dir=d)).run(resume=True)
    assert m2["losses"] == m1["losses"][3:]
    assert m2["last_step"] == 5


def test_packed_master_checkpoint_carries_plan():
    cfg = _tiny_cfg()
    with tempfile.TemporaryDirectory() as d:
        tc = TrainConfig(steps=2, seq_len=32, global_batch=2,
                         checkpoint_every=1, checkpoint_dir=d,
                         pack_params=True)
        tr = Trainer(cfg, tc)
        tr.run()
        step, tree, plan = tr.ckpt.restore(with_plan=True)
        assert plan is not None
        assert plan.float_bits == tr.plan.float_bits
        assert plan.int_bits == tr.plan.int_bits
        assert any(is_packed(l) for l in tree_leaves(
            tree["packed"], is_leaf=is_packed))
        # masters stay dense
        assert not any(is_packed(l) for l in tree_leaves(
            tree["masters"], is_leaf=is_packed))


# -- sharding: packed word arrays --------------------------------------------

def test_spec_for_packed_keeps_groups_intact():
    from jax.sharding import PartitionSpec as P
    from repro import compat
    from repro.distributed.sharding import spec_for_packed
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    with compat.mesh_context(mesh):
        # logical 1024 codes at AF16 = 4 tiles of 2 x 128 lanes: a 2-way
        # split lands on a tile boundary, each half keeps 128-lane tiles
        # AND matches the 512/512 logical split -> survives
        assert spec_for_packed(
            "blocks/attn/wq", (64, 1024), 16,
            axis_sizes={"data": 1, "model": 2}) == P(None, "model")
        # logical 128 codes at AF16 are one tile of 64-lane chunks: a
        # split would cut the tile even though the 64-word payload
        # divides evenly -> replicate
        assert spec_for_packed(
            "blocks/attn/wq", (64, 128), 16,
            axis_sizes={"data": 1, "model": 2}) == P(None, None)
        # logical 96 codes: one tile, 48 words -> replicate
        assert spec_for_packed(
            "blocks/attn/wq", (64, 96), 16,
            axis_sizes={"data": 1, "model": 2}) == P(None, None)
        # logical 1536 codes at AF8 = 3 tiles of 4 x 128 lanes: tile
        # boundaries cannot give the 768/768 logical split -> replicate
        assert spec_for_packed(
            "blocks/attn/wq", (64, 1536), 8,
            axis_sizes={"data": 1, "model": 2}) == P(None, None)
        # non-last axes keep the logical rules untouched
        assert spec_for_packed(
            "blocks/attn/wo", (128, 64), 16,
            axis_sizes={"data": 1, "model": 2}) == P("model", None)


def test_shard_leaf_uses_logical_spec_for_packed():
    from jax.sharding import PartitionSpec as P
    from repro import compat
    from repro.distributed.sharding import shard_leaf
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    rng = np.random.default_rng(0)
    w = jnp.asarray(rng.standard_normal((64, 1024)).astype(np.float32))
    pt = pack_tensor(w, 16)
    with compat.mesh_context(mesh):
        ns = shard_leaf("blocks/mlp/w_in", pt, mesh)
        assert ns.spec == P(None, "model")


# -- the take gather kernel ---------------------------------------------------

@pytest.mark.parametrize("bits", [8, 12, 16, 20, 24, 28, 32])
def test_take_kernel_parity_across_widths(bits):
    """Interpret-mode kernel vs. the jnp oracle, out-of-order and
    duplicated indices included."""
    rng = np.random.default_rng(bits)
    w = jnp.asarray((rng.standard_normal((40, 96)) * 0.3).astype(
        np.float32))
    wp = R.pack_ref(w, bits)
    idx = jnp.asarray([5, 3, 3, 39, 0, 17, 39], jnp.int32)
    got = take_rows(wp, idx, bits, 96, interpret=True)
    ref = R.take_rows_ref(wp, idx, bits, 96)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(ref))
    # and the oracle is the gather of the decoded table
    np.testing.assert_array_equal(
        np.asarray(ref),
        np.asarray(jnp.take(R.unpack_ref(wp, bits, 96), idx, 0)))


def test_take_kernel_int_kind():
    rng = np.random.default_rng(9)
    codes = jnp.asarray(rng.integers(-30, 30, (10, 64)), jnp.int32)
    pt = pack_tensor(codes, 8, signed=True)
    idx = jnp.asarray([9, 0, 4, 4], jnp.int32)
    got = take_rows(pt.data, idx, 8, 64, kind="int", signed=True,
                    out_dtype=jnp.int32, interpret=True)
    np.testing.assert_array_equal(
        np.asarray(got), np.asarray(jnp.take(pt.unpack(), idx, 0)))


def test_packed_tensor_take_dispatches_and_matches_oracle():
    """PackedTensor.take routes 2-D tables through kernels.ops and stays
    bit-identical to the materialized gather on the jnp backend."""
    rng = np.random.default_rng(11)
    w = jnp.asarray((rng.standard_normal((50, 64)) * 0.3).astype(
        np.float32))
    pt = pack_tensor(w, 12)
    idx = jnp.asarray([[49, 0], [7, 7]], jnp.int32)
    np.testing.assert_array_equal(
        np.asarray(pt.take(idx)),
        np.asarray(jnp.take(pt.unpack(), idx, axis=0)))
