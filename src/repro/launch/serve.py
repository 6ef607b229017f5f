"""Production serving launcher: batched decode with packed KV.

    python -m repro.launch.serve --arch qwen3_8b --requests 64 \
        [--kv-bits 8] [--max-seq-len 2048] [--reduced] \
        [--speculative 4] [--draft-bits 12] [--adaptive] \
        [--paged] [--kv-page-size 16] [--kv-pool-pages N] \
        [--paged-attn | --gather-attn] \
        [--pack-weights] [--plan plan.json | --calibrate] \
        [--save-plan plan.json]

Sizes the slot count from the residency planner (the Table 1 occupancy
calculator for chips), runs continuous batching until the request queue
drains, and reports occupancy + throughput. ``--speculative k`` swaps in
the narrow-draft self-speculative stepper: a draft repacked one ladder
step down proposes k tokens per tick, the full-width model verifies them
in one call — emitted tokens are unchanged, ticks drop by the acceptance
rate; ``--adaptive`` lets the DraftController retune (draft width, k)
from live acceptance. ``--paged`` swaps the per-slot dense KV regions
for the block-granular ``KVPagePool``: per-request page tables, pages
sized by ``--kv-page-size``, admission over-commits slots against a
pool of ``--kv-pool-pages`` pages (default slots x pages/sequence —
no over-commit), and identical prompt prefixes share refcounted pages. ``--plan plan.json`` packs weights at a calibrated
per-leaf mixed-width plan; ``--calibrate`` runs the calibration pass
(``core.calibrate``) in-process first, gated by ``--quality-kind`` /
``--quality-threshold``, and ``--save-plan`` writes the plan JSON for
later ``--plan`` runs (and for ``repro.tuning.calibrate``, the offline
driver).
"""
from __future__ import annotations

import argparse
import dataclasses

import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", required=True)
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--max-new-tokens", type=int, default=8)
    ap.add_argument("--max-seq-len", type=int, default=64)
    ap.add_argument("--kv-bits", type=int, default=None)
    ap.add_argument("--slots", type=int, default=None)
    ap.add_argument("--reduced", action="store_true",
                    help="serve the config's tiny same-family variant "
                         "(CPU smoke runs); default is the published "
                         "widths")
    ap.add_argument("--speculative", type=int, default=0, metavar="K",
                    help="draft K tokens/tick through the narrow plan; "
                         "0 = plain engine")
    ap.add_argument("--draft-bits", type=int, default=None,
                    help="draft weight width (default: config knob, else "
                         "one Table 3 step below weight_bits)")
    ap.add_argument("--paged", action="store_true",
                    help="page the KV cache: per-request page tables "
                         "over a shared KVPagePool instead of one dense "
                         "max-seq-len region per slot")
    ap.add_argument("--kv-page-size", type=int, default=16,
                    help="KV rows per page (must divide --max-seq-len)")
    ap.add_argument("--kv-pool-pages", type=int, default=None,
                    help="physical pool pages (default: slots x "
                         "pages/sequence; smaller over-commits slots "
                         "against the pool)")
    ap.add_argument("--paged-attn", dest="paged_attn",
                    action="store_true", default=True,
                    help="paged only: attend straight through the "
                         "device-resident page table (fused paged "
                         "attention, the default)")
    ap.add_argument("--gather-attn", dest="paged_attn",
                    action="store_false",
                    help="paged only: demote to the gather-materialize "
                         "oracle (dense per-sequence view each step)")
    ap.add_argument("--pack-weights", action="store_true",
                    help="pack target weights at the planned width")
    ap.add_argument("--adaptive", action="store_true",
                    help="speculative only: retune (draft width, k) from "
                         "live acceptance (DraftController)")
    ap.add_argument("--plan", default=None, metavar="PLAN_JSON",
                    help="pack weights at this calibrated per-leaf plan")
    ap.add_argument("--calibrate", action="store_true",
                    help="run the calibration pass first and serve the "
                         "tuned mixed-width plan")
    ap.add_argument("--save-plan", default=None, metavar="OUT_JSON",
                    help="write the served plan (from --plan/--calibrate) "
                         "to this file")
    ap.add_argument("--quality-kind", default="loss_delta",
                    choices=["loss_delta", "deviation"],
                    help="--calibrate acceptance metric")
    ap.add_argument("--quality-threshold", type=float, default=0.05,
                    help="--calibrate acceptance threshold (nats / %%)")
    ap.add_argument("--calib-batches", type=int, default=2)
    ap.add_argument("--metrics-out", default=None, metavar="JSONL",
                    help="stream structured telemetry (spans, events, "
                         "serve.metrics snapshots) to this JSONL file")
    ap.add_argument("--metrics-interval", type=int, default=0,
                    metavar="N",
                    help="emit a serve.metrics snapshot every N ticks "
                         "(0: only the final one at drain)")
    args = ap.parse_args()

    from repro import compat, obs
    from repro.configs import get_config
    from repro.core.compress import CompressionPlan
    from repro.serving import ServeEngine, SpeculativeEngine

    compat.enable_compile_cache()
    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    if args.kv_bits:
        cfg = dataclasses.replace(
            cfg, compression=dataclasses.replace(
                cfg.compression, kv_bits=args.kv_bits))

    plan = None
    if args.plan and args.calibrate:
        raise SystemExit("--plan and --calibrate are exclusive")
    if args.plan:
        plan = CompressionPlan.load(args.plan)
        print(f"loaded plan {args.plan}: {len(plan.float_bits)} float "
              f"leaves, {len(plan.int_bits)} int streams, "
              f"{len(plan.kv_bits)} KV layers")
    elif args.calibrate:
        from repro.core.calibrate import calibrate
        from repro.core.quality import QualitySpec
        res = calibrate(
            cfg, QualitySpec(args.quality_kind, args.quality_threshold),
            n_batches=args.calib_batches, max_seq_len=args.max_seq_len)
        plan = res.plan
        print(f"calibrated {cfg.name}: mean float bits "
              f"{res.mean_float_bits:.1f} (uniform {res.uniform_bits}), "
              f"{args.quality_kind}={res.metric:.4g} "
              f"(gate {args.quality_threshold})")
    if args.save_plan:
        if plan is None:
            raise SystemExit("--save-plan needs --plan or --calibrate")
        plan.save(args.save_plan)
        print(f"wrote plan to {args.save_plan}")

    tracer = None
    if args.metrics_out:
        tracer = obs.Tracer()
        tracer.set_sink(args.metrics_out)
    paged_kw = dict(paged=args.paged, kv_page_size=args.kv_page_size,
                    kv_pool_pages=args.kv_pool_pages,
                    paged_attn=args.paged_attn, tracer=tracer,
                    metrics_interval=args.metrics_interval)
    if args.speculative:
        eng = SpeculativeEngine(
            cfg, max_seq_len=args.max_seq_len,
            max_slots=args.slots or 4, k=args.speculative,
            draft_bits=args.draft_bits, pack_weights=args.pack_weights,
            plan=plan, adaptive=args.adaptive, **paged_kw)
    else:
        eng = ServeEngine(cfg, max_seq_len=args.max_seq_len,
                          max_slots=args.slots or 4,
                          pack_weights=args.pack_weights, plan=plan,
                          **paged_kw)
    rng = np.random.default_rng(0)
    rids = [
        eng.submit(list(rng.integers(1, cfg.vocab_size, 4)),
                   max_new_tokens=args.max_new_tokens)
        for _ in range(args.requests)
    ]
    stats = eng.run_until_drained()
    done = sum(1 for r in rids if eng.result(r) is not None)
    print(f"completed {done}/{len(rids)} requests; "
          f"{stats['tokens']} tokens in {stats['ticks']} ticks; "
          f"slots={stats['slots']}; "
          f"planner max sequences (full-scale)="
          f"{stats['residency_max_sequences']}")
    if args.paged:
        print(f"paged: page_size={stats['kv_page_size']} "
              f"pool_pages={stats['kv_pool_pages']} "
              f"pool_peak_utilization="
              f"{stats['pool_peak_utilization']:.2f} "
              f"prefix_hit_rate={stats['prefix_hit_rate']:.2f}")
        print(f"paged-attn: fused={stats['paged_attn']} "
              f"pages_read={stats['kv_pages_read']} "
              f"(dense-equiv {stats['kv_pages_read_dense_equiv']}) "
              f"table_rows_uploaded={stats['table_rows_uploaded']} "
              f"table_upload_bytes={stats['table_upload_bytes']}")
    if args.speculative:
        print(f"speculative: k={stats['k']} draft_bits={stats['draft_bits']} "
              f"acceptance={stats['acceptance_rate']:.3f} "
              f"committed/tick={stats['committed_per_tick']:.2f} "
              f"draft_weight_bytes={eng.draft_weight_read_bytes} "
              f"target_weight_bytes={eng.weight_read_bytes}")
        if args.adaptive:
            print(f"adaptive: retunes={stats['retunes']} "
                  f"post_retune_acceptance="
                  f"{stats['post_retune_acceptance']:.3f}")
    if tracer is not None:
        tracer.close()
        print(f"wrote telemetry to {args.metrics_out}")
        print(obs.console_summary(obs.REGISTRY))


if __name__ == "__main__":
    main()
