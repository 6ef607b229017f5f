"""Paged-KV serving bench: per-request KV bytes, over-commit, sharing.

The paper's capacity argument, applied to the serving cache: a fixed
physical file (the ``KVPagePool``) serves more logical state when each
request holds only the pages its *actual* length needs, instead of one
dense ``max_seq_len`` region per slot. Three measurements per config:

  * **KV bytes per request, dense vs paged** — dense always provisions
    ``max_seq_len`` rows; paged provisions ``pages_peak * page_size``
    rows, so short requests stop paying for the worst case;
  * **over-commit under a mixed-length workload** — with the pool sized
    *below* slots x pages-per-sequence, the engine must still admit more
    concurrent residents than the pool could hold as dense regions
    (peak residents > pool_pages / pages_per_seq), token-exactly;
  * **prefix-hit rate on a shared system prompt** — identical prompt
    prefixes dedup page-for-page through the chain-key registry.

Since the fused paged-attention path landed, the paged leg attends
straight through the device-resident page table; a fourth measurement
pair covers it:

  * **fused vs gather vs dense, token-exact three ways** — the same
    traffic through ``paged_attn=True`` (fused), ``paged_attn=False``
    (gather-materialize oracle) and the dense engine must emit
    identical greedy tokens;
  * **H2D table traffic and pages read** — the device-resident table
    means clean ticks skip the upload entirely and dirty ticks ship
    only dirty rows (``table_upload_bytes`` well under calls x full
    table), while ``kv_pages_read`` scales with pages actually live,
    not slots x max_pages (the dense-equivalent figure).

Greedy outputs are asserted identical to the dense engine in-bench for
both traffics — an ERROR row (and CI failure) on any divergence. An
interpret-mode Pallas-kernel parity probe rides along so the real
kernel lowering (not just the jnp oracle) is exercised on CPU CI.
Writes ``BENCH_serving_paged.json`` for CI to archive and returns the
usual ``(name, us, derived)`` CSV rows.
"""
from __future__ import annotations

import json
from typing import List, Tuple

import numpy as np

ARTIFACT = "BENCH_serving_paged.json"
CONFIGS = ("qwen3_8b", "phi3_medium_14b")
SEQ = 64
PAGE = 8
SLOTS = 6
MAX_NEW = 8
N_REQUESTS = 12
SYSTEM_PROMPT_LEN = 24


def _mixed_prompts(cfg, rng) -> List[List[int]]:
    return [list(rng.integers(1, cfg.vocab_size, int(n)))
            for n in rng.integers(0, 25, N_REQUESTS)]


def _shared_prompts(cfg, rng) -> List[List[int]]:
    system = list(rng.integers(1, cfg.vocab_size, SYSTEM_PROMPT_LEN))
    return [system + list(rng.integers(1, cfg.vocab_size, int(n)))
            for n in rng.integers(1, 9, N_REQUESTS)]


def _drain_tracked(eng, prompts):
    """Submit, drain via step(), return (results, stats, requests,
    peak concurrent residents)."""
    rids = [eng.submit(p, max_new_tokens=MAX_NEW) for p in prompts]
    reqs = {r.rid: r for r in list(eng._queue) + list(
        eng._active.values())}
    peak = len(eng._active)
    while eng._queue or eng._active:
        eng.step()
        peak = max(peak, len(eng._active))
    stats = eng.run_until_drained()        # drained: stats only
    return [eng.result(r) for r in rids], stats, reqs, peak


def _mixed_kv_leg(cfg, name: str, prompts) -> dict:
    """Serve the same traffic with a mixed per-layer KV plan (layer 0 at
    the config width, later layers one Table 3 rung down) through both
    engines; dense and paged must stay token-exact against each other,
    and the per-token KV accounting must come in under the uniform
    figure."""
    from repro.core.compress import CompressionPlan
    from repro.core.formats import ladder_snap
    from repro.serving import ServeEngine

    base = cfg.resolved_kv_bits
    n_kv = cfg.n_kv_layers
    widths = [base] + [ladder_snap(base, below=True)] * (n_kv - 1)
    plan = CompressionPlan(
        float_bits={}, int_bits={},
        kv_bits={f"kv/layer_{i}": b for i, b in enumerate(widths)})

    dense = ServeEngine(cfg, max_seq_len=SEQ, max_slots=SLOTS, plan=plan)
    dres, _, _, _ = _drain_tracked(dense, prompts)
    paged = ServeEngine(cfg, max_seq_len=SEQ, max_slots=SLOTS,
                        paged=True, kv_page_size=PAGE, plan=plan)
    pres, _, _, _ = _drain_tracked(paged, prompts)
    if dres != pres:
        raise AssertionError(
            f"{name}: paged output diverged from the dense engine "
            "under a mixed per-layer KV plan")
    mixed_kvb = dense.cfg.kv_bytes_per_token()
    uniform_kvb = cfg.kv_bytes_per_token()
    if n_kv > 1 and not mixed_kvb < uniform_kvb:
        raise AssertionError(
            f"{name}: mixed KV plan {widths} did not shrink "
            f"kv_bytes_per_token ({mixed_kvb} vs uniform {uniform_kvb})")
    return {
        "mixed_kv_layer_bits": list(dense.cfg.resolved_kv_layer_bits),
        "mixed_kv_bytes_per_token": mixed_kvb,
        "mixed_greedy_exact": dres == pres,
    }


def _kernel_parity_probe() -> dict:
    """Run the actual Pallas paged-attention kernel in interpret mode
    against the jnp oracle on one packed case — proof the kernel
    lowering itself (not just the dispatch-layer oracle CPU CI
    otherwise runs) computes the fused program."""
    import jax.numpy as jnp

    from repro.kernels import ref as kref
    from repro.kernels.paged_attention import paged_attention

    bits, d, page, hkv, h, b, mp = 8, 32, 4, 2, 4, 3, 3
    n_pages = 1 + b * mp
    rng = np.random.default_rng(5)
    k_pool, v_pool = (kref.pack_ref(jnp.asarray(
        rng.standard_normal((n_pages, page, hkv * d)), jnp.float32), bits)
        for _ in range(2))
    q = jnp.asarray(rng.standard_normal((b, h, d)), jnp.float32)
    table = jnp.asarray(
        rng.permutation(np.arange(1, n_pages))[: b * mp].reshape(b, mp),
        jnp.int32)
    kv_len = jnp.asarray([1, page, b * page - 1], jnp.int32)
    got = paged_attention(q, k_pool, v_pool, table, kv_len, bits, hkv,
                          interpret=True)
    want = kref.paged_attention_ref(q, k_pool, v_pool, table, kv_len,
                                    bits, hkv)
    err = float(jnp.max(jnp.abs(got - want)))
    if err > 2e-5:
        raise AssertionError(
            f"interpret-mode paged-attention kernel diverged from the "
            f"oracle (max abs err {err:.2e})")
    return {"kernel_interpret_parity": True,
            "kernel_interpret_max_abs_err": err}


def bench_serving_paged() -> List[Tuple[str, float, str]]:
    from repro.configs import get_config
    from repro.serving import ServeEngine

    rows: List[Tuple[str, float, str]] = []
    artifact = {"bench": "serving_paged", "max_seq_len": SEQ,
                "kv_page_size": PAGE, "slots": SLOTS, "configs": []}
    pages_per_seq = SEQ // PAGE
    # pool deliberately below slots x pages/seq: dense regions would only
    # fit pool_pages / pages_per_seq residents
    pool_pages = (SLOTS * pages_per_seq) // 2

    for name in CONFIGS:
        cfg = get_config(name).reduced()
        kvb = cfg.kv_bytes_per_token()
        rng = np.random.default_rng(23)
        mixed = _mixed_prompts(cfg, rng)

        dense = ServeEngine(cfg, max_seq_len=SEQ, max_slots=SLOTS)
        dres, dstats, _, _ = _drain_tracked(dense, mixed)
        paged = ServeEngine(cfg, max_seq_len=SEQ, max_slots=SLOTS,
                            paged=True, kv_page_size=PAGE,
                            kv_pool_pages=pool_pages)
        pres, pstats, reqs, peak_live = _drain_tracked(paged, mixed)
        gather = ServeEngine(cfg, max_seq_len=SEQ, max_slots=SLOTS,
                             paged=True, kv_page_size=PAGE,
                             kv_pool_pages=pool_pages, paged_attn=False)
        gres, gstats, _, _ = _drain_tracked(gather, mixed)
        if not (dres == pres == gres):
            raise AssertionError(
                f"{name}: greedy outputs diverged across "
                "{dense, paged+fused, paged+gather} "
                "(mixed-length workload)")

        # device-resident table: uploads fire only on dirty ticks and
        # ship dirty rows, never one full table per jitted call
        calls = pstats["decode_calls"] + pstats["prefill_calls"]
        full_table_bytes = SLOTS * pages_per_seq * 4
        if not pstats["table_uploads"] < calls:
            raise AssertionError(
                f"{name}: {pstats['table_uploads']} table uploads over "
                f"{calls} jitted calls — clean ticks are not skipping "
                "the H2D transfer")
        if not pstats["table_upload_bytes"] < calls * full_table_bytes:
            raise AssertionError(
                f"{name}: H2D table traffic "
                f"{pstats['table_upload_bytes']} B is no better than "
                f"re-uploading the full table every call "
                f"({calls} x {full_table_bytes} B)")
        # fused KV reads scale with pages actually live, not the
        # slots x max_pages dense-equivalent walk
        if not 0 < pstats["kv_pages_read"] \
                < pstats["kv_pages_read_dense_equiv"]:
            raise AssertionError(
                f"{name}: fused path read {pstats['kv_pages_read']} "
                f"pages vs dense-equivalent "
                f"{pstats['kv_pages_read_dense_equiv']}")
        if gstats["kv_pages_read"] != 0:
            raise AssertionError(
                f"{name}: gather oracle accrued kv_pages_read "
                f"({gstats['kv_pages_read']}) — the counter must track "
                "only the fused path")

        dense_capacity = pool_pages // pages_per_seq
        if peak_live <= dense_capacity:
            raise AssertionError(
                f"{name}: paged engine admitted only {peak_live} "
                f"concurrent residents — no better than the {pool_pages} "
                f"pages held as dense regions ({dense_capacity})")

        # per-request KV bytes: dense strands max_seq_len rows per slot;
        # paged holds pages_peak actual pages
        dense_bytes = SEQ * kvb
        paged_bytes = [r.pages_peak * PAGE * kvb for r in reqs.values()]
        scaling = max(paged_bytes) > min(paged_bytes)  # length-dependent
        if not all(b <= dense_bytes for b in paged_bytes):
            raise AssertionError(
                f"{name}: a paged request provisioned more KV bytes "
                "than the dense worst case")

        shared = _shared_prompts(cfg, rng)
        dense2 = ServeEngine(cfg, max_seq_len=SEQ, max_slots=SLOTS)
        dres2, _, _, _ = _drain_tracked(dense2, shared)
        paged2 = ServeEngine(cfg, max_seq_len=SEQ, max_slots=SLOTS,
                             paged=True, kv_page_size=PAGE)
        pres2, sstats, _, _ = _drain_tracked(paged2, shared)
        if dres2 != pres2:
            raise AssertionError(
                f"{name}: paged output diverged from the dense engine "
                "under greedy decoding (shared-prefix workload)")
        hit_rate = sstats["prefix_hit_rate"]
        if not hit_rate > 0:
            raise AssertionError(
                f"{name}: shared system prompt produced no prefix hits")

        mean_paged = sum(paged_bytes) / len(paged_bytes)
        rows.append((
            f"serving_paged.{name}",
            pstats["wall_s"] * 1e6 / max(pstats["ticks"], 1),
            f"peak_residents={peak_live};dense_equiv_capacity="
            f"{dense_capacity};mean_kv_bytes_per_request={mean_paged:.0f};"
            f"dense_kv_bytes_per_request={dense_bytes};"
            f"pool_peak_utilization={pstats['pool_peak_utilization']:.2f};"
            f"prefix_hit_rate={hit_rate:.2f};"
            f"pages_read={pstats['kv_pages_read']};"
            f"dense_equiv_pages={pstats['kv_pages_read_dense_equiv']};"
            f"table_upload_bytes={pstats['table_upload_bytes']}",
        ))
        # mixed per-layer KV widths (the static-analysis plan family):
        # install a two-width plan through ServeEngine(plan=) and assert
        # the paged engine still matches dense token-exactly while the
        # per-row accounting drops below the uniform figure
        mixed_kv = _mixed_kv_leg(cfg, name, mixed)

        artifact["configs"].append({
            "config": name,
            "kv_bits": cfg.resolved_kv_bits,
            "kv_layer_bits": list(cfg.resolved_kv_layer_bits),
            "kv_bytes_per_token": kvb,
            **mixed_kv,
            "pool_pages": pool_pages,
            "pages_per_seq": pages_per_seq,
            "greedy_exact_mixed": dres == pres,
            "greedy_exact_gather": dres == gres,
            "greedy_exact_shared": dres2 == pres2,
            "paged_attn_fused": pstats["paged_attn"],
            "kv_pages_read": pstats["kv_pages_read"],
            "kv_pages_read_dense_equiv":
                pstats["kv_pages_read_dense_equiv"],
            "kv_pages_read_bytes": pstats["kv_pages_read_bytes"],
            "table_uploads": pstats["table_uploads"],
            "table_upload_bytes": pstats["table_upload_bytes"],
            "table_rows_uploaded": pstats["table_rows_uploaded"],
            "jitted_calls": calls,
            "full_table_bytes": full_table_bytes,
            "peak_concurrent_residents": peak_live,
            "dense_equivalent_capacity": dense_capacity,
            "overcommit": peak_live > dense_capacity,
            "dense_kv_bytes_per_request": dense_bytes,
            "paged_kv_bytes_per_request": sorted(paged_bytes),
            "paged_bytes_scale_with_length": scaling,
            "pool_utilization_final": pstats["pool_utilization"],
            "pool_peak_utilization": pstats["pool_peak_utilization"],
            "prefix_hit_rate": hit_rate,
            "prefix_hits": sstats["prefix_hits"],
            "prefix_queries": sstats["prefix_queries"],
            "ticks_dense": dstats["ticks"],
            "ticks_paged": pstats["ticks"],
        })

    artifact.update(_kernel_parity_probe())
    with open(ARTIFACT, "w") as f:
        json.dump(artifact, f, indent=2)
    rows.append(("serving_paged.artifact", 0.0, ARTIFACT))
    return rows
