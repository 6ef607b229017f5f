"""Substrate tests: optimizer (packed state), data determinism,
checkpoint atomicity/restore, watchdog, serving engine."""
import dataclasses
import os
import tempfile
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.configs import get_config
from repro.core.tensor_store import pack_tensor
from repro.data import SyntheticTokens
from repro.optim import AdamWConfig, adamw_init, adamw_update
from repro.serving import ServeEngine
from repro.train import Trainer, TrainConfig
from repro.train.watchdog import StragglerWatchdog


# -- optimizer ---------------------------------------------------------------

def _toy_params(key):
    k1, k2 = jax.random.split(key)
    return {
        "w": jax.random.normal(k1, (64, 64)) * 0.1,
        "b": jnp.zeros((64,)),
        "deep": {"u": jax.random.normal(k2, (32, 96)) * 0.1},
    }


def quad_loss(params, x):
    h = jnp.tanh(x @ params["w"]) + params["b"]
    return jnp.sum(h ** 2)


@pytest.mark.parametrize("m_bits,v_bits", [(None, None), (16, 16),
                                           (12, 16)])
def test_adamw_descends(m_bits, v_bits):
    cfg = AdamWConfig(lr=1e-2, m_bits=m_bits, v_bits=v_bits,
                      weight_decay=0.0)
    params = _toy_params(jax.random.PRNGKey(0))
    opt = adamw_init(params, cfg)
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 64))
    losses = []
    for _ in range(25):
        loss, grads = jax.value_and_grad(quad_loss)(params, x)
        params, opt = adamw_update(grads, opt, params, cfg)
        losses.append(float(loss))
    assert losses[-1] < 0.5 * losses[0]


def test_packed_opt_state_smaller():
    cfg = AdamWConfig(m_bits=16, v_bits=16)
    params = _toy_params(jax.random.PRNGKey(0))
    opt = adamw_init(params, cfg)
    f32_bytes = sum(
        int(np.prod(p.shape)) * 4
        for p in jax.tree_util.tree_leaves(params))
    packed_bytes = sum(
        int(np.prod(np.asarray(l).shape)) * np.asarray(l).dtype.itemsize
        for l in jax.tree_util.tree_leaves(opt["m"]))
    # 2-D leaves halve; small 1-D leaves stay f32
    assert packed_bytes < 0.6 * f32_bytes


def test_packed_vs_f32_trajectory_close():
    x = jax.random.normal(jax.random.PRNGKey(1), (16, 64))
    trajs = {}
    for name, (mb, vb) in {"f32": (None, None), "af16": (16, 16)}.items():
        cfg = AdamWConfig(lr=5e-3, m_bits=mb, v_bits=vb, weight_decay=0.0)
        params = _toy_params(jax.random.PRNGKey(0))
        opt = adamw_init(params, cfg)
        for _ in range(10):
            _, grads = jax.value_and_grad(quad_loss)(params, x)
            params, opt = adamw_update(grads, opt, params, cfg)
        trajs[name] = float(quad_loss(params, x))
    assert abs(trajs["af16"] - trajs["f32"]) / trajs["f32"] < 0.05


# -- data pipeline ------------------------------------------------------------

def test_data_restart_exact():
    a = SyntheticTokens(vocab_size=1000, seq_len=32, global_batch=4, seed=3)
    b1 = a.batch_at(7)
    b = SyntheticTokens(vocab_size=1000, seq_len=32, global_batch=4, seed=3)
    b2 = b.batch_at(7)
    assert (np.asarray(b1.tokens) == np.asarray(b2.tokens)).all()
    assert int(b1.tokens.max()) < 1000 and int(b1.tokens.min()) >= 0


def test_data_host_sharding_disjoint():
    hosts = [
        SyntheticTokens(vocab_size=100, seq_len=8, global_batch=8,
                        host_index=i, host_count=2)
        for i in range(2)
    ]
    b0, b1 = hosts[0].batch_at(0), hosts[1].batch_at(0)
    assert b0.tokens.shape == (4, 8)
    assert not (np.asarray(b0.tokens) == np.asarray(b1.tokens)).all()


# -- checkpointing --------------------------------------------------------------

def test_checkpoint_roundtrip_and_gc():
    with tempfile.TemporaryDirectory() as d:
        mgr = CheckpointManager(d, keep=2)
        tree = {
            "a": np.arange(12, dtype=np.float32).reshape(3, 4),
            "packed": pack_tensor(
                jnp.asarray(np.random.default_rng(0)
                            .standard_normal((4, 64)).astype(np.float32)),
                16),
            "nested": {"step": np.int32(5)},
        }
        for s in (1, 2, 3):
            mgr.save(s, tree)
        assert mgr.all_steps() == [2, 3]          # keep=2 gc'd step 1
        step, back = mgr.restore()
        assert step == 3
        assert (back["a"] == tree["a"]).all()
        assert (np.asarray(back["packed"].unpack())
                == np.asarray(tree["packed"].unpack())).all()


def test_checkpoint_tmp_gc():
    with tempfile.TemporaryDirectory() as d:
        os.makedirs(os.path.join(d, "step_000001.tmp-deadbeef"))
        CheckpointManager(d)                      # constructor gc's tmp
        assert not any(".tmp-" in n for n in os.listdir(d))


def test_trainer_checkpoint_restart_same_stream():
    cfg = get_config("qwen3_8b").reduced()
    with tempfile.TemporaryDirectory() as d:
        tc = TrainConfig(steps=6, seq_len=32, global_batch=2,
                         checkpoint_every=3, checkpoint_dir=d, lr=1e-3)
        m1 = Trainer(cfg, tc).run()
        tc2 = dataclasses.replace(tc, steps=8)
        m2 = Trainer(cfg, tc2).run(resume=True)
        assert m2["last_step"] == 7
        assert len(m2["losses"]) == 2             # only steps 6,7 re-run


# -- watchdog ----------------------------------------------------------------

def test_straggler_watchdog_flags():
    events = []
    wd = StragglerWatchdog(ratio=2.0, warmup_steps=3,
                           on_straggle=lambda s, t, b: events.append(s))
    for i in range(10):
        wd.observe(i, 0.1)
    assert wd.events == 0
    wd.observe(10, 0.5)                           # 5x baseline
    assert wd.events == 1 and events == [10]
    # baseline not polluted by the straggle
    assert wd.baseline < 0.12


# -- serving -------------------------------------------------------------------

def test_serving_continuous_batching():
    cfg = get_config("qwen3_8b").reduced()
    eng = ServeEngine(cfg, max_seq_len=32, max_slots=3)
    rids = [eng.submit([1, 2], max_new_tokens=4) for _ in range(5)]
    stats = eng.run_until_drained()
    assert all(len(eng.result(r)) == 4 for r in rids)
    assert stats["tokens"] == 20
    # more requests than slots => batching had to recycle
    assert stats["slots"] == 3


def test_residency_planner_monotone_in_bits():
    from repro.core.occupancy import decode_residency
    full = get_config("qwen3_8b")
    tp = 8                       # per-chip share on a TP=8 serving slice
    r16 = decode_residency(
        weight_bytes=full.n_params() * 2 // tp,
        kv_bytes_per_token=full.kv_bytes_per_token(16) // tp,
        seq_len=32768)
    r8 = decode_residency(
        weight_bytes=full.n_params() * 2 // tp,
        kv_bytes_per_token=full.kv_bytes_per_token(8) // tp,
        seq_len=32768)
    assert r16.max_sequences > 0
    assert r8.max_sequences >= 2 * r16.max_sequences - 1
    assert r8.arithmetic_intensity > r16.arithmetic_intensity


def test_chip_constants_keyed_by_device_kind():
    """The planner reads its chip from the device: v5e by kind, the
    reference v5e off-TPU, and an unknown TPU kind is an error."""
    import types

    from repro.core.occupancy import CHIPS, TPU_V5E, chip_for_device

    def dev(platform, kind):
        return types.SimpleNamespace(platform=platform, device_kind=kind)

    assert chip_for_device(dev("tpu", "TPU v5 lite")) is TPU_V5E
    assert chip_for_device(dev("cpu", "cpu")) is TPU_V5E
    assert all(c.source for c in CHIPS.values())
    with pytest.raises(KeyError, match="TPU v99"):
        chip_for_device(dev("tpu", "TPU v99"))


def test_engine_residency_reads_held_weight_bytes():
    """Slots are planned around the bytes the engine actually holds: at
    AF8 the packed weights are smaller, so at least as many slots fit."""
    cfg = get_config("qwen3_8b").reduced()
    e16 = ServeEngine(cfg, max_seq_len=32, pack_weights=True)
    cfg8 = dataclasses.replace(cfg, compression=dataclasses.replace(
        cfg.compression, weight_bits=8))
    e8 = ServeEngine(cfg8, max_seq_len=32, pack_weights=True)
    held16 = e16._pass_bytes["fused"] + e16._pass_bytes["dense"]
    held8 = e8._pass_bytes["fused"] + e8._pass_bytes["dense"]
    assert held8 < held16
    assert e8.residency.weight_bytes == held8
    assert e16.residency.weight_bytes == held16
