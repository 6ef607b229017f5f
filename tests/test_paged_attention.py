"""Fused paged attention: kernel-vs-oracle parity (interpret mode),
page-boundary edge cases, scrap-page isolation, and engine-level
three-way token exactness across {dense, paged+gather, paged+fused}."""
import dataclasses

import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ref as kref
from repro.kernels.paged_attention import paged_attention
from repro.serving import ServeEngine, SpeculativeEngine

HKV, D, H = 2, 32, 4          # hkv*d multiple of 32: exact group packing


def _pools(rng, n_pages, page, bits):
    """Physical pools filled with *encoded real values* (random words
    can decode to NaN codes, and 0 * NaN poisons the masked rows)."""
    kf = rng.standard_normal((n_pages, page, HKV * D)).astype(np.float32)
    vf = rng.standard_normal((n_pages, page, HKV * D)).astype(np.float32)
    if bits:
        return (kref.pack_ref(jnp.asarray(kf), bits),
                kref.pack_ref(jnp.asarray(vf), bits))
    return jnp.asarray(kf), jnp.asarray(vf)


def _case(rng, page, bits, lens):
    b, mp = len(lens), max(1, -(-max(lens) // page))
    n_pages = 1 + b * mp
    pk, pv = _pools(rng, n_pages, page, bits)
    q = jnp.asarray(rng.standard_normal((b, H, D)), jnp.float32)
    ids = rng.permutation(np.arange(1, n_pages))[: b * mp]
    table = np.asarray(ids, np.int32).reshape(b, mp)
    # entries past each row's live pages point at the scrap page, as the
    # engine leaves unallocated tail entries
    for i, ln in enumerate(lens):
        table[i, -(-ln // page):] = 0
    return q, pk, pv, jnp.asarray(table), jnp.asarray(lens, jnp.int32)


@pytest.mark.parametrize("bits", [0, 8, 16])
@pytest.mark.parametrize("page", [4, 8])
def test_kernel_matches_oracle_interpret(bits, page):
    """The Pallas kernel (interpret mode — the real lowering, on CPU)
    against the gather-materialize oracle, across packed widths and page
    sizes, over boundary lengths: 0 (dead slot), 1, page-1, exactly one
    page, a partial tail page, and every page full."""
    rng = np.random.default_rng(7 * page + bits)
    lens = [0, 1, page - 1, page, page + 1, 3 * page]
    q, pk, pv, table, kv_len = _case(rng, page, bits, lens)
    got = paged_attention(q, pk, pv, table, kv_len, bits, HKV,
                          interpret=True)
    want = kref.paged_attention_ref(q, pk, pv, table, kv_len, bits, HKV)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("bits", [0, 8])
def test_scrap_page_never_leaks(bits):
    """Poisoning the scrap page (large rows that stay *finite* after
    encoding — an AF8-saturated inf, like NaN, would break the
    exact-zero-weight argument: 0 x inf = NaN in the v contraction) must
    not move the fused output by a single bit: dead table entries and
    masked tail rows carry exactly zero softmax weight."""
    page = 4
    rng = np.random.default_rng(3)
    lens = [1, page, 2 * page - 1]
    q, pk, pv, table, kv_len = _case(rng, page, bits, lens)
    if bits:
        poison = kref.pack_ref(
            jnp.full((1, page, HKV * D), 10.0, jnp.float32), bits)
        assert np.isfinite(np.asarray(kref.unpack_ref(
            poison, bits, HKV * D))).all()
    else:
        poison = jnp.full((1, page, HKV * D), 1e4, jnp.float32)
    pk_p = pk.at[0].set(poison[0])
    pv_p = pv.at[0].set(poison[0])
    clean = paged_attention(q, pk, pv, table, kv_len, bits, HKV,
                            interpret=True)
    dirty = paged_attention(q, pk_p, pv_p, table, kv_len, bits, HKV,
                            interpret=True)
    assert (np.asarray(clean) == np.asarray(dirty)).all()
    ref_clean = kref.paged_attention_ref(q, pk, pv, table, kv_len,
                                         bits, HKV)
    ref_dirty = kref.paged_attention_ref(q, pk_p, pv_p, table, kv_len,
                                         bits, HKV)
    assert (np.asarray(ref_clean) == np.asarray(ref_dirty)).all()


# -- engine-level three-way exactness ----------------------------------------

def _tiny_cfg(name="qwen3_8b", kv_bits=None):
    cfg = get_config(name).reduced()
    if kv_bits is not None:
        cfg = dataclasses.replace(
            cfg, compression=dataclasses.replace(
                cfg.compression, kv_bits=kv_bits))
    return cfg


def _prompt_mix(cfg, lens=(0, 1, 3, 7, 8, 9, 20)):
    rng = np.random.default_rng(11)
    return [list(rng.integers(1, cfg.vocab_size, n)) for n in lens]


def _drain(eng, prompts, max_new=6):
    rids = [eng.submit(list(p), max_new_tokens=max_new) for p in prompts]
    stats = eng.run_until_drained()
    return [eng.result(r) for r in rids], stats


@pytest.mark.parametrize("kv_bits", [None, 8])
def test_engine_three_way_exact(kv_bits):
    """Greedy tokens bitwise identical across {dense, paged+gather,
    paged+fused}: the fused kernel appends the identical packed words to
    the identical physical rows the gather+scatter round-trip writes,
    and the jnp fused path runs the oracle's exact math."""
    cfg = _tiny_cfg(kv_bits=kv_bits)
    prompts = _prompt_mix(cfg)
    dense, _ = _drain(ServeEngine(cfg, max_seq_len=32, max_slots=3),
                      prompts)
    kw = dict(max_seq_len=32, max_slots=3, paged=True, kv_page_size=8)
    gather, _ = _drain(ServeEngine(cfg, paged_attn=False, **kw), prompts)
    fused, stats = _drain(ServeEngine(cfg, paged_attn=True, **kw),
                          prompts)
    assert dense == gather == fused
    assert 0 < stats["kv_pages_read"] < stats["kv_pages_read_dense_equiv"]


def test_engine_three_way_exact_encdec():
    cfg = _tiny_cfg("whisper_small")
    prompts = _prompt_mix(cfg, lens=(0, 2, 9))
    kw = dict(max_seq_len=32, max_slots=2, paged=True, kv_page_size=8)
    dense, _ = _drain(ServeEngine(cfg, max_seq_len=32, max_slots=2),
                      prompts, max_new=4)
    gather, _ = _drain(ServeEngine(cfg, paged_attn=False, **kw),
                       prompts, max_new=4)
    fused, _ = _drain(ServeEngine(cfg, paged_attn=True, **kw),
                      prompts, max_new=4)
    assert dense == gather == fused


def test_engine_three_way_exact_mixed_widths():
    """Width-segmented KV (kv_layer_bits (16, 8, 8, ...)): each segment
    decodes at its own width inside the fused kernel."""
    from repro.core.compress import CompressionPlan
    cfg = _tiny_cfg()
    n_kv = cfg.n_kv_layers
    widths = [16] + [8] * (n_kv - 1)
    plan = CompressionPlan(
        float_bits={}, int_bits={},
        kv_bits={f"kv/layer_{i}": b for i, b in enumerate(widths)})
    prompts = _prompt_mix(cfg, lens=(0, 3, 9))
    kw = dict(max_seq_len=32, max_slots=3, paged=True, kv_page_size=8,
              plan=plan)
    dense, _ = _drain(ServeEngine(cfg, max_seq_len=32, max_slots=3,
                                  plan=plan), prompts)
    gather, _ = _drain(ServeEngine(cfg, paged_attn=False, **kw), prompts)
    fused, _ = _drain(ServeEngine(cfg, paged_attn=True, **kw), prompts)
    assert dense == gather == fused


def test_speculative_three_way_exact():
    """The speculative verify walks k+1 positions through the same
    tables, and the post-tick rollback trims speculated tail rows —
    fused greedy outputs still match the plain engine bit-for-bit."""
    cfg = _tiny_cfg(kv_bits=8)
    prompts = _prompt_mix(cfg, lens=(0, 1, 5, 9))
    plain, _ = _drain(ServeEngine(cfg, max_seq_len=40, max_slots=3),
                      prompts)
    kw = dict(max_seq_len=40, max_slots=3, k=3, paged=True,
              kv_page_size=4)
    gather, _ = _drain(SpeculativeEngine(cfg, paged_attn=False, **kw),
                       prompts)
    fused, stats = _drain(SpeculativeEngine(cfg, paged_attn=True, **kw),
                          prompts)
    assert plain == gather == fused
    assert stats["kv_pages_read"] > 0


# -- device-resident table: dirty-row H2D discipline --------------------------

def test_table_uploads_only_dirty_ticks():
    """Steady decode mutates no table rows, so most jitted calls run
    with zero H2D table traffic; the uploads that do fire ship dirty
    rows (bytes well under calls x full-table)."""
    cfg = _tiny_cfg()
    eng = ServeEngine(cfg, max_seq_len=32, max_slots=3, paged=True,
                      kv_page_size=4)
    _, stats = _drain(eng, _prompt_mix(cfg, lens=(0, 2, 5)), max_new=8)
    calls = stats["decode_calls"] + stats["prefill_calls"]
    full_table = eng.n_slots * (32 // 4) * 4          # int32 bytes
    assert 0 < stats["table_uploads"] < calls
    assert stats["table_rows_uploaded"] > 0
    assert stats["table_upload_bytes"] < calls * full_table
    # lazy-sync invariant: rows not marked dirty agree between the
    # device table and the host shadow (finish-time eviction dirties
    # rows after the last jitted call, so those may legitimately lag
    # until the next tick pushes them)
    dev = np.asarray(eng.state["table"])
    clean_rows = [s for s in range(eng.n_slots)
                  if s not in eng._dirty_rows]
    assert (dev[clean_rows] == eng._table[clean_rows]).all()


def test_paged_decode_trace_dispatches_fused():
    """Tracing decode_step over a paged state must record the fused
    paged-attention dispatch and never the gather-materialize oracle
    (the PR 9 lint gate's contract, unit-sized)."""
    import jax

    from repro.compat import prng_key
    from repro.kernels import ops as kops
    from repro.models.lm import LM

    cfg = _tiny_cfg(kv_bits=8)
    lm = LM(cfg)
    params = lm.init(prng_key(0))
    state = lm.init_paged_decode_state(2, 32, 8, 8, abstract=True)
    n = len(kops.DISPATCH_RECORDS)
    jax.make_jaxpr(lm.decode_step)(
        params, state, jnp.zeros((2, 1), jnp.int32))
    new = list(kops.DISPATCH_RECORDS)[n:]
    assert any(r.op == "paged_attention" and r.path == "fused_paged"
               for r in new)
    assert not any(r.op == "gather_kv_pages" for r in new)
