"""Speculative serving: plan derivation, repack, packed embed gather,
multi-token verify/prefill, and the engine-level exactness property —
greedy speculative output must be token-for-token identical to the plain
engine across prompt lengths, k, draft widths, and families."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from _hypothesis_compat import given, settings, st

from repro.compat import prng_key
from repro.configs import get_config
from repro.core.compress import CompressionPlan, derive_plan, repack, \
    uniform_plan
from repro.core.formats import FLOAT_LADDER
from repro.core.tensor_store import (
    is_packed,
    pack_tensor,
    repack_tensor,
    tree_bytes,
)
from repro.models import layers as L
from repro.models.lm import LM
from repro.serving import ServeEngine, SpeculativeEngine, resolve_draft_bits


def _tiny_cfg(name="qwen3_8b"):
    return get_config(name).reduced()


# -- plan derivation ----------------------------------------------------------

def test_derive_plan_steps_down_ladder_and_floors():
    plan = CompressionPlan(
        float_bits={"a": 16, "b": 8, "c": 32},
        int_bits={"i": (12, False)},
    )
    d = derive_plan(plan, 4)
    assert d.float_bits == {"a": 12, "b": 8, "c": 28}
    assert d.int_bits == {"i": (12, False)}       # ints never narrow
    d2 = derive_plan(plan, 8)
    assert d2.float_bits == {"a": 8, "b": 8, "c": 24}
    # delta 0 keeps every width
    assert derive_plan(plan, 0).float_bits == plan.float_bits
    with pytest.raises(ValueError):
        derive_plan(plan, -4)


def test_uniform_plan_targets_matmul_leaves_only():
    tree = {
        "w": jnp.ones((8, 64), jnp.float32),
        "norm": jnp.ones((64,), jnp.float32),
        "idx": jnp.ones((8, 64), jnp.int32),
    }
    plan = uniform_plan(tree, 16)
    assert plan.float_bits == {"w": 16}
    assert uniform_plan(tree, 32).float_bits == {}


def test_repack_tensor_reencodes_values():
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 64)).astype(np.float32))
    p16 = pack_tensor(x, 16)
    p12 = repack_tensor(p16, 12)
    assert p12.bits == 12
    # definition: decode current codes, encode at the new width
    ref = pack_tensor(p16.unpack(), 12)
    assert jnp.array_equal(p12.data, ref.data)
    assert jnp.array_equal(p12.unpack(), ref.unpack())
    assert repack_tensor(p16, 16) is p16          # no-op fast path


def test_repack_tree_handles_packed_and_plain_leaves():
    rng = np.random.default_rng(1)
    tree = {
        "packed": pack_tensor(
            jnp.asarray(rng.standard_normal((4, 64)).astype(np.float32)),
            16),
        "plain": jnp.asarray(
            rng.standard_normal((4, 64)).astype(np.float32)),
        "norm": jnp.ones((64,), jnp.float32),     # not in the plan
    }
    plan = CompressionPlan(float_bits={"packed": 12, "plain": 12},
                           int_bits={})
    out = repack(tree, plan)
    assert out["packed"].bits == 12 and out["plain"].bits == 12
    assert out["norm"] is tree["norm"]
    packed_b, logical_b = tree_bytes(out)
    assert packed_b < logical_b


def test_derive_plan_at_floor_is_distinct_but_equal():
    """Deriving from a plan already at the AF8 floor must hand back a
    *new* plan equal in content — never an alias of the source's mutable
    dicts (a tuner revising one plan must not rewrite the other)."""
    plan = CompressionPlan(float_bits={"a": 8, "b": 8},
                           int_bits={"i": (12, False)}, tune_evals=3)
    for delta in (0, 4, 8):
        d = derive_plan(plan, delta)
        assert d == plan                      # every width already floored
        assert d is not plan
        assert d.float_bits is not plan.float_bits
        assert d.int_bits is not plan.int_bits
        d.float_bits["a"] = 32                # mutating the derived plan…
        d.int_bits["i"] = (4, True)
        assert plan.float_bits["a"] == 8      # …never touches the source
        assert plan.int_bits["i"] == (12, False)


@settings(max_examples=25)
@given(st.sampled_from((8, 12, 16, 20, 24, 28)), st.integers(0, 3))
def test_derive_plan_distinctness_property(bits, steps):
    """Any chain of derivations shares no mutable state with its source
    and is idempotent once it reaches the floor."""
    plan = CompressionPlan(float_bits={"w": bits}, int_bits={})
    cur = plan
    for _ in range(steps):
        nxt = derive_plan(cur, 4)
        assert nxt.float_bits is not cur.float_bits
        assert nxt.float_bits["w"] <= cur.float_bits["w"]
        cur = nxt
    floored = derive_plan(CompressionPlan(float_bits={"w": 8}, int_bits={}),
                          4)
    assert floored.float_bits == {"w": 8}


@settings(max_examples=25)
@given(st.sampled_from((8, 12, 16, 20, 24, 28)),
       st.sampled_from((8, 12, 16, 20, 24, 28)),
       st.sampled_from((8, 12, 16, 20, 24, 28)),
       st.sampled_from((4, 8)))
def test_derive_plan_mixed_widths_step_per_leaf(wa, wb, wc, delta):
    """A calibrated *mixed*-width plan derives per leaf: every float leaf
    steps down the ladder by its own delta (snapped, floored at AF8),
    int streams never narrow, and order between leaves is preserved —
    a narrower leaf never ends up wider than a wider one."""
    from repro.core.formats import ladder_snap
    plan = CompressionPlan(
        float_bits={"a": wa, "b": wb, "c": wc},
        int_bits={"inputs/tokens": (9, False), "inputs/len": (7, False)},
    )
    d = derive_plan(plan, delta)
    for k in ("a", "b", "c"):
        assert d.float_bits[k] == ladder_snap(plan.float_bits[k] - delta)
        assert d.float_bits[k] >= FLOAT_LADDER[0]          # AF8 floor
        assert d.float_bits[k] <= plan.float_bits[k]
    # monotone: leaf ordering survives derivation
    for x in ("a", "b", "c"):
        for y in ("a", "b", "c"):
            if plan.float_bits[x] <= plan.float_bits[y]:
                assert d.float_bits[x] <= d.float_bits[y]
    assert d.int_bits == plan.int_bits                     # never narrow
    assert d.int_bits is not plan.int_bits


@settings(max_examples=25)
@given(st.sampled_from((8, 12, 16, 20, 24, 28, 32)),
       st.sampled_from((8, 12, 16, 20, 24, 28, 32)),
       st.sampled_from((0, 4, 8)))
def test_derive_plan_kv_family_roundtrip(kv0, kv1, delta):
    """The three plan families derive independently: ``kv_bits`` entries
    always step exactly one Table 3 rung down regardless of the weight
    delta, never below AF8, ints are untouched — and the derived plan
    survives the JSON codec round-trip with all three families intact."""
    import json as _json
    from repro.core.formats import ladder_snap
    plan = CompressionPlan(
        float_bits={"w": 16},
        int_bits={"inputs/tokens": (9, False)},
        kv_bits={"kv/layer_0": kv0, "kv/layer_1": kv1},
    )
    d = derive_plan(plan, delta)
    for key, src in plan.kv_bits.items():
        # one rung down irrespective of delta (the draft-KV ladder
        # contract), floored at AF8
        assert d.kv_bits[key] == ladder_snap(src, below=True)
        assert d.kv_bits[key] >= FLOAT_LADDER[0]
        assert d.kv_bits[key] < src or src == FLOAT_LADDER[0]
        assert d.kv_bits[key] in FLOAT_LADDER
    assert d.float_bits["w"] == ladder_snap(16 - delta)    # own delta
    assert d.int_bits == plan.int_bits                     # never narrow
    assert d.kv_bits is not plan.kv_bits                   # fresh dict
    # JSON round-trip: codec carries the kv family losslessly
    back = CompressionPlan.from_jsonable(
        _json.loads(_json.dumps(d.to_jsonable())))
    assert back.kv_bits == d.kv_bits
    assert back.float_bits == d.float_bits
    assert back.int_bits == d.int_bits
    # deriving the round-tripped plan again equals deriving the original
    # twice: the codec is transparent to the ladder walk
    assert derive_plan(back, delta).kv_bits == \
        derive_plan(d, delta).kv_bits


@settings(max_examples=15)
@given(st.sampled_from((8, 12, 16, 20)), st.sampled_from((8, 12, 16, 20)))
def test_repack_mixed_plan_idempotent_at_width(wa, wb):
    """Repacking a tree already at a mixed plan's widths is a no-op per
    leaf (identical objects, zero re-encode error), and int streams in
    the plan never touch float param leaves."""
    rng = np.random.default_rng(wa * 32 + wb)
    tree = {
        "a": jnp.asarray(rng.standard_normal((4, 32)).astype(np.float32)),
        "b": jnp.asarray(rng.standard_normal((8, 16)).astype(np.float32)),
        "norm": jnp.ones((16,), jnp.float32),
    }
    plan = CompressionPlan(
        float_bits={"a": wa, "b": wb},
        int_bits={"inputs/tokens": (9, False)},   # stream key: no leaf
    )
    once = repack(tree, plan)
    assert once["a"].bits == wa and once["b"].bits == wb
    assert once["norm"] is tree["norm"]
    twice = repack(once, plan)
    assert twice["a"] is once["a"]                # at-width: identical
    assert twice["b"] is once["b"]
    # deriving then repacking steps each leaf to its own rung
    d = derive_plan(plan, 4)
    stepped = repack(once, d)
    assert stepped["a"].bits == d.float_bits["a"]
    assert stepped["b"].bits == d.float_bits["b"]


@settings(max_examples=25)
@given(st.sampled_from((8, 12, 16, 20, 24, 28)))
def test_repack_at_width_is_noop_property(bits):
    """Repacking at the leaf's current width must return the identical
    object — no decode->encode round trip, hence zero error accumulation
    no matter how often the same plan is applied."""
    rng = np.random.default_rng(bits)
    leaf = pack_tensor(
        jnp.asarray(rng.standard_normal((4, 64)).astype(np.float32)), bits)
    tree = {"w": leaf, "other": jnp.ones((2, 32), jnp.float32)}
    plan = CompressionPlan(float_bits={"w": bits}, int_bits={})
    out1 = repack(tree, plan)
    assert out1["w"] is leaf                      # byte-identical, free
    assert out1["other"] is tree["other"]         # unnamed: untouched
    # and through a real round trip: width change then back is stable
    down = repack_tensor(leaf, 8)
    up_down = repack_tensor(repack_tensor(down, 8), 8)
    assert up_down is down


# -- packed embed gather (satellite: ROADMAP open item) -----------------------

@pytest.mark.parametrize("bits", [8, 12, 16, 20])
def test_packed_embed_gather_parity(bits):
    rng = np.random.default_rng(2)
    table = jnp.asarray(rng.standard_normal((96, 64)).astype(np.float32))
    pt = pack_tensor(table, bits)
    toks = jnp.asarray(rng.integers(0, 96, (3, 5)), jnp.int32)
    got = L.embed(toks, pt)
    ref = jnp.take(pt.unpack(), toks, axis=0)     # materialized path
    assert got.shape == (3, 5, 64)
    assert jnp.array_equal(got, ref)              # same codes, same decode
    # 1-D index vector too
    v = jnp.asarray([0, 95, 7], jnp.int32)
    assert jnp.array_equal(L.embed(v, pt), jnp.take(pt.unpack(), v, 0))


def test_packed_take_int_kind():
    rng = np.random.default_rng(3)
    x = jnp.asarray(rng.integers(-100, 100, (32, 64)), jnp.int32)
    pt = pack_tensor(x, 8, kind="int", signed=True)
    idx = jnp.asarray([5, 0, 31], jnp.int32)
    assert jnp.array_equal(pt.take(idx), jnp.take(pt.unpack(), idx, 0))


def test_packed_take_requires_row_axis():
    pt = pack_tensor(jnp.ones((64,), jnp.float32), 16)
    with pytest.raises(ValueError):
        pt.take(jnp.asarray([0]))


# -- multi-token decode: verify_step / prefill_step ---------------------------

def test_verify_step_matches_sequential_decode_bitwise():
    cfg = _tiny_cfg()
    lm = LM(cfg)
    params = lm.init(prng_key(0))
    B, S, T = 3, 32, 5
    rng = np.random.default_rng(4)
    toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (B, T)), jnp.int32)

    step = jax.jit(lm.decode_step)
    st_seq = lm.init_decode_state(B, S)
    outs = []
    for i in range(T):
        lg, st_seq = step(params, st_seq, toks[:, i:i + 1])
        outs.append(lg[:, 0])
    seq = jnp.stack(outs, 1)

    vl, st_v = jax.jit(lm.verify_step)(params, lm.init_decode_state(B, S),
                                       toks)
    assert jnp.array_equal(seq, vl)
    assert jnp.array_equal(st_seq["len"], st_v["len"])
    assert jnp.array_equal(st_seq["kv"]["k"], st_v["kv"]["k"])


# One-pass prefill (dense, vlm) and the scanned decode compute the same
# K/V with different summation orders: B·C-row matmuls and a C-query
# softmax against B-row matmuls and one-query softmaxes. The tiny
# configs run float32 activations, so that moves a value by about 1e-7
# of itself, and a stored AF16 (e5m10) cache word can only cross one
# rounding boundary: one ulp, 2^-10 of its binade. Two ulps leave room
# for a flipped word feeding the next layer; 2^-24 is AF16's least
# subnormal step.
KV_RTOL = 2.0 ** -9
KV_ATOL = 2.0 ** -24


def _kv_floats(cfg, words):
    """Cache rows (..., kv_row_width) -> their float values."""
    from repro.kernels.ref import unpack_ref
    bits = cfg.compression.kv_bits
    n = cfg.n_kv_heads * cfg.resolved_head_dim
    return np.asarray(unpack_ref(jnp.asarray(words), bits, n)
                      if bits else words, np.float32)


def _assert_kv_close(cfg, got, want):
    np.testing.assert_allclose(_kv_floats(cfg, got), _kv_floats(cfg, want),
                               rtol=KV_RTOL, atol=KV_ATOL)


@pytest.mark.parametrize("arch,one_pass", [("qwen3_8b", True),
                                           ("deepseek_moe_16b", False),
                                           ("whisper_small", False)])
def test_prefill_step_chunked_matches_sequential(arch, one_pass):
    """Valid rows of a prefilled slot against the same tokens fed one
    decode step at a time: bitwise where prefill scans the decode step
    (moe, encdec), within KV_RTOL where it is one forward."""
    cfg = _tiny_cfg(arch)
    lm = LM(cfg)
    assert lm.one_pass_prefill is one_pass
    params = lm.init(prng_key(0))
    B, S, C = 2, 32, 6
    rng = np.random.default_rng(5)
    toks = np.zeros((B, C), np.int32)
    n_valid = np.asarray([4, 0], np.int32)        # slot 1 rides along idle
    toks[0, :4] = rng.integers(1, cfg.vocab_size, 4)

    st = lm.prefill_step(params, lm.init_decode_state(B, S),
                         jnp.asarray(toks), jnp.asarray(n_valid))
    assert np.asarray(st["len"]).tolist() == [4, 0]

    st_ref = lm.init_decode_state(B, S)
    for i in range(4):
        _, st_ref = lm.decode_step(params, st_ref,
                                   jnp.asarray(toks[:, i:i + 1]))
    # valid rows of the prefilled slot match the sequential feed
    for key in ("k", "v"):
        got = np.asarray(st["kv"][key])[:, 0, :4]
        want = np.asarray(st_ref["kv"][key])[:, 0, :4]
        if one_pass:
            _assert_kv_close(cfg, got, want)
        else:
            assert np.array_equal(got, want), key


def _paged_state(lm, B, S, page, seed=0):
    """A paged state whose table maps each slot onto shuffled pages, so
    the indirection is not the identity."""
    mp = S // page
    st = lm.init_paged_decode_state(B, S, page, B * mp)
    ids = np.random.default_rng(seed).permutation(B * mp) + 1
    return dict(st, table=jnp.asarray(ids.reshape(B, mp), jnp.int32))


def _view_rows(state, key, slot, n):
    """Rows 0..n-1 of ``slot`` in layer order, dense or paged."""
    kv = np.asarray(state["kv"][key])
    if "table" not in state:
        return kv[:, slot, :n]
    tab = np.asarray(state["table"])[slot]
    rows = kv[:, tab].reshape(kv.shape[0], -1, kv.shape[-1])
    return rows[:, :n]


def _prefill_chunks(lm, params, state, prompts, chunk):
    """Prefill every prompt in ``chunk``-wide calls, as the engine does."""
    step = jax.jit(lm.prefill_step)
    B = len(prompts)
    done = [0] * B
    while any(d < len(p) for d, p in zip(done, prompts)):
        toks = np.zeros((B, chunk), np.int32)
        n_valid = np.zeros((B,), np.int32)
        for b, p in enumerate(prompts):
            take = min(chunk, len(p) - done[b])
            toks[b, :take] = p[done[b]:done[b] + take]
            n_valid[b] = take
            done[b] += take
        state = step(params, state, jnp.asarray(toks), jnp.asarray(n_valid))
    return state


@pytest.mark.parametrize("packed", [False, True])
def test_one_pass_prefill_matches_scanned_decode(packed):
    """Multi-chunk prompts of unequal lengths through the one-pass
    prefill, dense and paged: K/V within KV_RTOL of the scanned decode
    over the same tokens, and the paged pool bitwise equal to the dense
    cache (both attend to the same view)."""
    cfg = _tiny_cfg()
    lm = LM(cfg)
    params = lm.init(prng_key(1))
    if packed:
        params = repack(params, uniform_plan(params, 8))
    B, S, page = 3, 32, 8
    rng = np.random.default_rng(6)
    prompts = [list(rng.integers(1, cfg.vocab_size, n)) for n in (21, 9, 0)]
    dense = _prefill_chunks(lm, params, lm.init_decode_state(B, S),
                            prompts, 8)
    paged = _prefill_chunks(lm, params, _paged_state(lm, B, S, page),
                            prompts, 8)
    T = max(len(p) for p in prompts)
    full = np.zeros((B, T), np.int32)
    for b, p in enumerate(prompts):
        full[b, :len(p)] = p
    _, ref = jax.jit(lm.verify_step)(params, lm.init_decode_state(B, S),
                                     jnp.asarray(full))
    for st in (dense, paged):
        assert np.asarray(st["len"]).tolist() == [21, 9, 0]
    for key in ("k", "v"):
        for b, p in enumerate(prompts):
            got = _view_rows(dense, key, b, len(p))
            assert np.array_equal(_view_rows(paged, key, b, len(p)), got)
            _assert_kv_close(cfg, got, _view_rows(ref, key, b, len(p)))


def test_prefill_chunk_width_keeps_greedy_tokens():
    """A prompt prefilled in chunks of 4 against chunks of 16: K/V within
    KV_RTOL, and the engine's greedy tokens the same."""
    cfg = _tiny_cfg()
    lm = LM(cfg)
    params = lm.init(prng_key(2))
    rng = np.random.default_rng(7)
    prompts = [list(rng.integers(1, cfg.vocab_size, n)) for n in (16, 11)]
    narrow = _prefill_chunks(lm, params, lm.init_decode_state(2, 32),
                             prompts, 4)
    wide = _prefill_chunks(lm, params, lm.init_decode_state(2, 32),
                           prompts, 16)
    for key in ("k", "v"):
        for b, p in enumerate(prompts):
            _assert_kv_close(cfg, _view_rows(narrow, key, b, len(p)),
                             _view_rows(wide, key, b, len(p)))

    mix = [list(rng.integers(1, cfg.vocab_size, n))
           for n in (0, 3, 15, 16, 17, 40)]
    outs = []
    for chunk in (4, 16):
        eng = ServeEngine(cfg, max_seq_len=64, max_slots=3,
                          prefill_chunk=chunk)
        rids = [eng.submit(p, max_new_tokens=6) for p in mix]
        eng.run_until_drained()
        outs.append([eng.result(r) for r in rids])
    assert outs[0] == outs[1]


@pytest.mark.parametrize("layout", ["dense", "paged"])
def test_prefill_idle_slots_keep_len_and_rows(layout):
    """A slot that rides along with n_valid = 0 keeps its length and its
    valid rows byte for byte, also where the chunk's padding would run
    past the end of its cache."""
    cfg = _tiny_cfg()
    lm = LM(cfg)
    params = lm.init(prng_key(3))
    B, S, page = 2, 16, 8
    st = (lm.init_decode_state(B, S) if layout == "dense"
          else _paged_state(lm, B, S, page))
    rng = np.random.default_rng(8)
    step = jax.jit(lm.prefill_step)
    toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (B, 16)), jnp.int32)
    st = step(params, st, toks, jnp.asarray([3, 14], jnp.int32))
    before = {key: _view_rows(st, key, 1, 14) for key in ("k", "v")}
    toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (B, 8)), jnp.int32)
    st = step(params, st, toks, jnp.asarray([8, 0], jnp.int32))
    assert np.asarray(st["len"]).tolist() == [11, 14]
    for key in ("k", "v"):
        assert np.array_equal(_view_rows(st, key, 1, 14), before[key])


def test_rollback_is_length_reset_and_gated_by_family():
    cfg = _tiny_cfg()
    lm = LM(cfg)
    st = lm.init_decode_state(2, 16)
    st = lm.rollback_decode_state(dict(st, len=jnp.asarray([5, 7])),
                                  np.asarray([2, 7]))
    assert np.asarray(st["len"]).tolist() == [2, 7]
    ssm = LM(_tiny_cfg("falcon_mamba_7b"))
    assert not ssm.supports_rollback
    with pytest.raises(ValueError):
        ssm.rollback_decode_state(ssm.init_decode_state(1, 8), [0])


# -- the exactness property ---------------------------------------------------

def _drain_pair(cfg, prompts, max_new, k, draft_bits=None,
                pack_weights=False, slots=3, seq=128):
    base = ServeEngine(cfg, max_seq_len=seq, max_slots=slots,
                       pack_weights=pack_weights)
    rb = [base.submit(p, max_new_tokens=max_new) for p in prompts]
    base.run_until_drained()
    spec = SpeculativeEngine(cfg, max_seq_len=seq, max_slots=slots, k=k,
                             draft_bits=draft_bits,
                             pack_weights=pack_weights)
    rs = [spec.submit(p, max_new_tokens=max_new) for p in prompts]
    spec.run_until_drained()
    return base, rb, spec, rs


def _prompt_mix(cfg):
    """Empty, short, chunk-boundary and multi-chunk prompt lengths."""
    rng = np.random.default_rng(11)
    lens = [0, 1, 3, 15, 16, 17, 40]
    return [list(rng.integers(1, cfg.vocab_size, n)) for n in lens]


@pytest.mark.parametrize("k,draft_bits", [(1, None), (2, 8), (4, None)])
def test_greedy_speculative_exactness(k, draft_bits):
    cfg = _tiny_cfg()
    prompts = _prompt_mix(cfg)
    base, rb, spec, rs = _drain_pair(cfg, prompts, 8, k, draft_bits)
    for a, b in zip(rb, rs):
        assert base.result(a) == spec.result(b), (k, draft_bits)
    # speculation must not need more ticks than one-token-per-tick decode
    assert spec.ticks <= base.ticks
    assert 0 < spec.accepted <= spec.proposed


def test_greedy_speculative_exactness_packed_target():
    cfg = _tiny_cfg()
    prompts = _prompt_mix(cfg)[:4]
    base, rb, spec, rs = _drain_pair(cfg, prompts, 6, 2, pack_weights=True)
    for a, b in zip(rb, rs):
        assert base.result(a) == spec.result(b)
    # two packed widths of the same structure run concurrently
    assert spec.draft_weight_read_bytes < spec.weight_read_bytes


@pytest.mark.parametrize("arch", ["deepseek_moe_16b", "whisper_small"])
def test_greedy_speculative_exactness_other_families(arch):
    cfg = _tiny_cfg(arch)
    rng = np.random.default_rng(13)
    prompts = [list(rng.integers(1, cfg.vocab_size, n))
               for n in (0, 2, 9)]
    base, rb, spec, rs = _drain_pair(cfg, prompts, 4, 2, slots=2, seq=64)
    for a, b in zip(rb, rs):
        assert base.result(a) == spec.result(b)


@pytest.mark.parametrize("k", [1, 3])
def test_greedy_speculative_paged_matches_dense(k):
    """Paged-vs-dense token exactness across the prompt-length mix and
    k: the page-table indirection must be invisible to the draft/verify/
    rollback cycle (both caches page through one shared table)."""
    cfg = _tiny_cfg()
    prompts = _prompt_mix(cfg)
    dense = SpeculativeEngine(cfg, max_seq_len=128, max_slots=3, k=k)
    rd = [dense.submit(p, max_new_tokens=6) for p in prompts]
    dense.run_until_drained()
    paged = SpeculativeEngine(cfg, max_seq_len=128, max_slots=3, k=k,
                              paged=True, kv_page_size=16)
    rp = [paged.submit(p, max_new_tokens=6) for p in prompts]
    stats = paged.run_until_drained()
    for a, b in zip(rd, rp):
        assert dense.result(a) == paged.result(b), k
    # rollback trim kept pool usage at committed length: fully drained
    assert paged.pool.used == 0 and paged.pool.reserved == 0
    assert stats["pool_peak_utilization"] > 0


def test_speculative_refuses_recurrent_families():
    with pytest.raises(ValueError, match="roll"):
        SpeculativeEngine(_tiny_cfg("falcon_mamba_7b"), max_seq_len=32,
                          max_slots=2)


def test_speculative_rejects_non_narrowing_draft():
    with pytest.raises(ValueError, match="narrower"):
        SpeculativeEngine(_tiny_cfg(), max_seq_len=32, max_slots=2,
                          draft_bits=16)


def test_off_ladder_draft_bits_snaps_before_reporting():
    """An off-ladder width must snap down to a Table 3 rung and report
    the width the weights are actually packed at."""
    spec = SpeculativeEngine(_tiny_cfg(), max_seq_len=32, max_slots=2,
                             draft_bits=14)
    assert spec.draft_bits == 12
    packed_bits = {l.bits for l in jax.tree_util.tree_leaves(
        spec.draft_params, is_leaf=is_packed) if is_packed(l)}
    assert packed_bits == {12}


def test_submit_refuses_requests_without_kv_headroom():
    """Appends past max_seq_len would clamp and overwrite the last valid
    KV row — both engines must refuse at submit time, the speculative one
    accounting for its k rolled-back rows at the peak."""
    cfg = _tiny_cfg()
    base = ServeEngine(cfg, max_seq_len=32, max_slots=2)
    base.submit([1] * 25, max_new_tokens=8)       # 25+8-1 = 32: fits
    with pytest.raises(ValueError, match="KV rows"):
        base.submit([1] * 26, max_new_tokens=8)   # 33 rows: refused
    spec = SpeculativeEngine(cfg, max_seq_len=32, max_slots=2, k=4)
    spec.submit([1] * 21, max_new_tokens=8)       # 21+8-1+4 = 32: fits
    with pytest.raises(ValueError, match="headroom"):
        spec.submit([1] * 25, max_new_tokens=8)   # fits plain, not spec


def test_recurrent_families_accept_long_prompts():
    """O(1)-state families have no KV rows to overflow — the headroom
    check must not refuse prompts longer than max_seq_len there."""
    eng = ServeEngine(_tiny_cfg("falcon_mamba_7b"), max_seq_len=16,
                      max_slots=2)
    rid = eng.submit([1] * 40, max_new_tokens=3)
    eng.run_until_drained()
    assert len(eng.result(rid)) == 3


def test_resolve_draft_bits_knob_and_ladder_default():
    cfg = _tiny_cfg()
    assert resolve_draft_bits(cfg) == 12          # config knob (qwen3)
    comp = dataclasses.replace(cfg.compression, draft_weight_bits=None)
    assert resolve_draft_bits(
        dataclasses.replace(cfg, compression=comp)) == 12  # ladder step
    comp8 = dataclasses.replace(cfg.compression, draft_weight_bits=None,
                                weight_bits=8)
    assert resolve_draft_bits(
        dataclasses.replace(cfg, compression=comp8)) == FLOAT_LADDER[0]


def test_resolve_draft_kv_bits_knob_and_ladder_default():
    from repro.serving import resolve_draft_kv_bits
    cfg = _tiny_cfg()                                  # kv_bits=16
    assert resolve_draft_kv_bits(cfg) == 12            # one rung below
    comp = dataclasses.replace(cfg.compression, draft_kv_bits=8)
    assert resolve_draft_kv_bits(
        dataclasses.replace(cfg, compression=comp)) == 8   # knob wins
    dense = dataclasses.replace(cfg.compression, kv_bits=None)
    assert resolve_draft_kv_bits(
        dataclasses.replace(cfg, compression=dense)) is None  # mirror


def test_draft_kv_cache_is_narrower_and_greedy_exact():
    """The draft's KV rows pack at draft_kv_bits (fewer uint32 words per
    row than the target's), and greedy outputs stay token-for-token
    identical to the plain engine — quality moved into the acceptance
    rate, not the output."""
    cfg = _tiny_cfg()
    base = ServeEngine(cfg, max_seq_len=64, max_slots=2)
    rb = [base.submit([1, 2, 3], max_new_tokens=5) for _ in range(3)]
    base.run_until_drained()
    spec = SpeculativeEngine(cfg, max_seq_len=64, max_slots=2, k=2)
    assert spec.draft_kv_bits == 12
    tgt_words = spec.state["kv"]["k"].shape[-1]
    drf_words = spec.draft_state["kv"]["k"].shape[-1]
    assert drf_words < tgt_words                     # 12/32 vs 16/32
    assert spec.draft_kv_bytes_per_token < cfg.kv_bytes_per_token()
    rs = [spec.submit([1, 2, 3], max_new_tokens=5) for _ in range(3)]
    spec.run_until_drained()
    assert all(base.result(a) == spec.result(b) for a, b in zip(rb, rs))
    stats_kv = spec.run_until_drained()
    assert stats_kv["draft_kv_bits"] == 12


def test_draft_kv_bits_override_and_mirror():
    cfg = _tiny_cfg()
    spec8 = SpeculativeEngine(cfg, max_seq_len=64, max_slots=2, k=2,
                              draft_kv_bits=8)
    assert spec8.draft_kv_bits == 8
    dense = dataclasses.replace(
        cfg, compression=dataclasses.replace(cfg.compression,
                                             kv_bits=None))
    mirror = SpeculativeEngine(dense, max_seq_len=64, max_slots=2, k=2)
    assert mirror.draft_kv_bits is None
    assert (mirror.draft_state["kv"]["k"].dtype
            == mirror.state["kv"]["k"].dtype)


def test_draft_kv_bits_rejects_wider_than_target():
    cfg = _tiny_cfg()                                  # kv_bits=16
    comp8 = dataclasses.replace(cfg.compression, kv_bits=8)
    with pytest.raises(ValueError, match="must not be wider"):
        SpeculativeEngine(
            dataclasses.replace(cfg, compression=comp8),
            max_seq_len=64, max_slots=2, k=2, draft_kv_bits=16)
    # equal = explicit mirror, allowed
    eq = SpeculativeEngine(cfg, max_seq_len=64, max_slots=2, k=2,
                           draft_kv_bits=16)
    assert eq.draft_kv_bits == 16


def test_kv_bits_accounting_single_accessor():
    """ServeEngine's residency maths and ModelConfig.kv_bytes_per_token
    resolve the packed width through one accessor, so a default change
    cannot skew the bytes accounting between them."""
    cfg = _tiny_cfg()
    assert cfg.resolved_kv_bits == (cfg.compression.kv_bits or 16)
    dense = dataclasses.replace(
        cfg, compression=dataclasses.replace(cfg.compression,
                                             kv_bits=None))
    assert dense.resolved_kv_bits == 16
    # kv_bytes_per_token() with no argument == with the resolved width
    assert cfg.kv_bytes_per_token() == cfg.kv_bytes_per_token(
        cfg.resolved_kv_bits)
    assert dense.kv_bytes_per_token() == dense.kv_bytes_per_token(16)


def test_per_request_acceptance_stats():
    cfg = _tiny_cfg()
    spec = SpeculativeEngine(cfg, max_seq_len=64, max_slots=2, k=2)
    rid = spec.submit([1, 2, 3], max_new_tokens=6)
    req = spec._active[rid]
    spec.run_until_drained()
    assert req.draft_proposed > 0
    assert 0 <= req.draft_accepted <= req.draft_proposed
    assert spec.proposed >= req.draft_proposed
    assert 0.0 <= spec.acceptance_rate <= 1.0


def test_sampled_speculation_completes():
    """Rejection sampling commits 1..k+1 tokens per tick and drains."""
    cfg = _tiny_cfg()
    spec = SpeculativeEngine(cfg, max_seq_len=64, max_slots=2, k=2,
                             greedy=False)
    rids = [spec.submit([1 + i], max_new_tokens=5) for i in range(4)]
    spec.run_until_drained()
    assert all(len(spec.result(r)) == 5 for r in rids)


def test_engine_queues_are_deques_and_fifo():
    import collections
    cfg = _tiny_cfg()
    eng = ServeEngine(cfg, max_seq_len=32, max_slots=2)
    assert isinstance(eng._queue, collections.deque)
    assert isinstance(eng._free, collections.deque)
    rids = [eng.submit([1], max_new_tokens=1) for _ in range(6)]
    admitted_order = []
    seen = set()
    while eng._queue or eng._active:
        for rid in eng._active:
            if rid not in seen:
                seen.add(rid)
                admitted_order.append(rid)
        eng.step()
    assert admitted_order == sorted(admitted_order)  # FIFO admission
    assert all(eng.result(r) is not None for r in rids)
