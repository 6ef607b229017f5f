"""Per-architecture smoke tests: reduced same-family config, one
forward/train step on CPU, shape + finiteness assertions, decode-vs-
prefill consistency, packed-vs-unpacked KV equivalence."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import ARCHS, get_config
from repro.models.config import NO_COMPRESSION
from repro.models.lm import LM

SMOKE_ARCHS = [a for a in ARCHS if a != "paper_native"]


def _batch(cfg, b=2, s=32):
    batch = {
        "tokens": jnp.arange(b * s, dtype=jnp.int32).reshape(b, s)
        % cfg.vocab_size,
        "labels": jnp.ones((b, s), jnp.int32),
    }
    if cfg.family == "vlm":
        batch["patch_embeds"] = 0.01 * jnp.ones(
            (b, cfg.num_image_tokens, cfg.d_model), jnp.float32)
    if cfg.family == "encdec":
        batch["frames"] = 0.01 * jnp.ones(
            (b, cfg.encoder_seq, cfg.d_model), jnp.float32)
    return batch


@pytest.mark.parametrize("arch", SMOKE_ARCHS)
def test_smoke_train_step(arch):
    cfg = get_config(arch).reduced()
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    batch = _batch(cfg)
    loss, grads = jax.value_and_grad(lm.loss)(params, batch)
    assert np.isfinite(float(loss))
    gnorm = sum(float(jnp.abs(g).sum())
                for g in jax.tree_util.tree_leaves(grads))
    assert np.isfinite(gnorm) and gnorm > 0


@pytest.mark.parametrize("arch", SMOKE_ARCHS)
def test_smoke_decode_step(arch):
    cfg = get_config(arch).reduced()
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    b = 2
    state = lm.init_decode_state(b, 16)
    if cfg.family == "encdec":
        state["clen"] = jnp.full((b,), cfg.encoder_seq, jnp.int32)
    logits, state2 = lm.decode_step(
        params, state, jnp.zeros((b, 1), jnp.int32))
    assert logits.shape == (b, 1, cfg.vocab_size)
    assert np.isfinite(np.asarray(logits, np.float32)).all()
    assert int(state2["len"][0]) == 1
    # a second step advances
    logits, state3 = lm.decode_step(
        params, state2, jnp.ones((b, 1), jnp.int32))
    assert int(state3["len"][0]) == 2


@pytest.mark.parametrize("arch", ["qwen3_8b", "recurrentgemma_9b"])
def test_decode_matches_prefill(arch):
    """Greedy decode over a short prompt must reproduce teacher-forced
    last-position logits (packed KV on — exercises the full read/write
    register-file path)."""
    cfg = get_config(arch).reduced()
    lm = LM(cfg)
    params = lm.init(jax.random.PRNGKey(0))
    b, s = 1, 8
    toks = (jnp.arange(s, dtype=jnp.int32)[None] * 7) % cfg.vocab_size

    logits_pref, _ = lm.prefill(params, {"tokens": toks})
    state = lm.init_decode_state(b, 16)
    logits_dec = None
    for i in range(s):
        logits_dec, state = lm.decode_step(params, state, toks[:, i:i + 1])
    a = np.asarray(logits_pref[0, -1], np.float32)
    bvec = np.asarray(logits_dec[0, 0], np.float32)
    # packed KV introduces AF16 rounding; compare top-1 and correlation
    assert a.argmax() == bvec.argmax()
    corr = np.corrcoef(a, bvec)[0, 1]
    assert corr > 0.99, corr


def test_packed_vs_unpacked_kv_close():
    cfg = get_config("qwen3_8b").reduced()
    cfg_nc = dataclasses.replace(cfg, compression=NO_COMPRESSION)
    lm_p, lm_n = LM(cfg), LM(cfg_nc)
    params = lm_p.init(jax.random.PRNGKey(0))
    b = 2
    sp = lm_p.init_decode_state(b, 16)
    sn = lm_n.init_decode_state(b, 16)
    toks = jnp.asarray([[3], [5]], jnp.int32)
    for _ in range(4):
        lp, sp = lm_p.decode_step(params, sp, toks)
        ln, sn = lm_n.decode_step(params, sn, toks)
    a = np.asarray(lp, np.float32)
    c = np.asarray(ln, np.float32)
    assert np.abs(a - c).max() / (np.abs(c).max() + 1e-9) < 0.05


@pytest.mark.parametrize("arch", SMOKE_ARCHS)
def test_param_count_matches_analytical(arch):
    cfg = get_config(arch).reduced()
    lm = LM(cfg)
    shapes = jax.eval_shape(lm.init, jax.random.PRNGKey(0))
    actual = sum(int(np.prod(l.shape))
                 for l in jax.tree_util.tree_leaves(shapes))
    expected = cfg.n_params()
    # analytical count ignores norms/small vectors: within 5%
    assert abs(actual - expected) / expected < 0.05, (actual, expected)


def test_input_specs_cover_all_shapes():
    from repro.models.config import ALL_SHAPES
    for arch in SMOKE_ARCHS:
        cfg = get_config(arch)
        lm = LM(cfg)
        for shape in ALL_SHAPES:
            specs = lm.input_specs(shape)
            assert "tokens" in specs
            for v in specs.values():
                assert isinstance(v, jax.ShapeDtypeStruct)


@pytest.mark.parametrize("arch", ["qwen3_8b", "deepseek_moe_16b",
                                  "falcon_mamba_7b", "recurrentgemma_9b",
                                  "whisper_small", "paligemma_3b"])
def test_init_packed_matches_repacked_init(arch):
    """Building leaf by leaf (what lets a full-width model come up packed)
    gives ``init``'s tree and values (to f32 rounding: each leaf's init
    runs fused under jit), and its packed leaves are exactly those values
    packed afterwards."""
    from repro.core.compress import repack, uniform_plan

    lm = LM(get_config(arch).reduced())
    key = jax.random.PRNGKey(3)
    dense = lm.init_packed(key)
    want_l, want_t = jax.tree_util.tree_flatten(lm.init(key))
    got_l, got_t = jax.tree_util.tree_flatten(dense)
    assert got_t == want_t
    for a, b in zip(got_l, want_l):
        assert a.shape == b.shape and a.dtype == b.dtype
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=1e-6, atol=1e-9)
    plan = uniform_plan(dense, 8)
    got_l, got_t = jax.tree_util.tree_flatten(lm.init_packed(key, plan))
    want_l, want_t = jax.tree_util.tree_flatten(repack(dense, plan))
    assert got_t == want_t
    for a, b in zip(got_l, want_l):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
