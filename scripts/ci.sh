#!/usr/bin/env bash
# CPU-only CI: tier-1 suite + 8-device distributed smoke.
#
#   scripts/ci.sh            # everything
#   scripts/ci.sh --smoke    # just the 8-device mesh-matrix smoke
#
# Fails on any collection error (the explicit --collect-only pass turns
# a silently-skipped broken module into a hard failure) and on any
# mesh-matrix cell, so a regression in the compat mesh path is caught
# without hardware. This is the CPU suite; `python chip_smoke.py` is the
# check on a TPU.
set -euo pipefail
cd "$(dirname "$0")/.."

export PYTHONPATH="src${PYTHONPATH:+:$PYTHONPATH}"

smoke_only=false
[[ "${1:-}" == "--smoke" ]] && smoke_only=true

if ! $smoke_only; then
    echo "== collection check =="
    python -m pytest -q --collect-only >/dev/null

    echo "== tier-1 suite =="
    # the mesh matrix runs as the explicit smoke step below; deselect
    # its pytest twin so CI doesn't pay the slowest stage twice
    python -m pytest -x -q \
        --deselect tests/test_distributed.py::test_dryrun_mesh_matrix

    echo "== benchmark smoke (micro + perf + packed path + speculative + serving paged + train packed + calibration) =="
    # packed_path runs the fused kernel in Pallas interpret mode for the
    # parity rows (2-D and batched-expert orientations), benchmarks the
    # MoE expert-bank chain and one train step (forward + fused backward
    # weight stream), and (re)writes BENCH_packed_path.json as a CI
    # artifact;
    # speculative drains the same traffic through the plain and the
    # narrow-draft engines (narrow draft KV included), asserts greedy
    # outputs identical, and writes BENCH_speculative.json (acceptance
    # rate + bytes/committed token, target/draft KV split);
    # train_packed runs the Trainer in packed-master mode vs. the dense
    # baseline, asserts loss parity within the plan width's tolerance,
    # the 2 x bits/32 train-step weight stream and the repack_every
    # staleness contract, and writes BENCH_train_packed.json;
    # serving_paged drains mixed-length and shared-prefix traffic through
    # the dense engine and BOTH paged attention paths (fused
    # through-the-table + gather-materialize oracle), asserts greedy
    # outputs identical three ways, that an undersized pool still
    # over-commits (peak residents beat the pool's dense-region
    # capacity) with per-request KV bytes scaling with actual length,
    # that the device-resident table ships only dirty rows (uploads <
    # jitted calls, bytes << calls x full table) while fused KV reads
    # scale with live pages (< the slots x max_pages dense-equivalent),
    # runs the paged-attention Pallas kernel in interpret mode against
    # its oracle (the fused parity smoke), and writes
    # BENCH_serving_paged.json;
    # calibration runs the static-analysis calibration pass on two zoo
    # configs (asserting the tuned mixed-width plan beats uniform at the
    # same quality gate) plus the adaptive draft controller (asserting
    # stablelm's acceptance recovers to >= 0.5), and writes
    # BENCH_calibration.json.
    # Artifacts are removed first so a stale copy can't mask a bench that
    # stopped writing them. The CSV is always echoed — even when run.py
    # exits nonzero — so the rows that did succeed reach the CI log;
    # ERROR: rows or a nonzero exit fail the build.
    rm -f BENCH_packed_path.json BENCH_speculative.json \
        BENCH_serving_paged.json BENCH_train_packed.json \
        BENCH_calibration.json
    set +e
    bench_csv=$(python -m benchmarks.run \
        --only micro,perf,packed_path,speculative,serving_paged,train_packed,calibration)
    bench_rc=$?
    set -e
    printf '%s\n' "$bench_csv"
    if [ "$bench_rc" -ne 0 ] \
        || printf '%s\n' "$bench_csv" | grep -q "ERROR:"; then
        echo "benchmark smoke failed: ERROR rows present" >&2
        exit 1
    fi
    test -f BENCH_packed_path.json || {
        echo "BENCH_packed_path.json artifact missing" >&2; exit 1; }
    test -f BENCH_speculative.json || {
        echo "BENCH_speculative.json artifact missing" >&2; exit 1; }
    test -f BENCH_serving_paged.json || {
        echo "BENCH_serving_paged.json artifact missing" >&2; exit 1; }
    test -f BENCH_train_packed.json || {
        echo "BENCH_train_packed.json artifact missing" >&2; exit 1; }
    test -f BENCH_calibration.json || {
        echo "BENCH_calibration.json artifact missing" >&2; exit 1; }

    echo "== static-analysis lint gate (packed-path auditor) =="
    # The four-pass auditor (repro.analysis) over two zoo configs: the
    # traced entry points (now including a paged decode state, which
    # must dispatch onto the fused paged-attention kernel — any
    # gather_kv_pages record in that trace is an error) must prove every
    # planned leaf fused, the default plan must be sound against the
    # derived range proofs, and the sharding/donation invariants must
    # hold. Reports are archived
    # (BENCH_lint_<arch>.json) and schema-validated. Then the two
    # negative legs: a seeded-broken plan fixture and a seeded unfused
    # dispatch must BOTH fail with a nonzero exit — a gate that cannot
    # fail proves nothing.
    rm -f BENCH_lint_qwen3_8b.json BENCH_lint_deepseek_moe_16b.json
    python -m repro.analysis.lint --arch qwen3_8b --reduced \
        --out BENCH_lint_qwen3_8b.json
    python -m repro.analysis.lint --arch deepseek_moe_16b --reduced \
        --out BENCH_lint_deepseek_moe_16b.json
    python -m repro.obs.validate --lint \
        BENCH_lint_qwen3_8b.json BENCH_lint_deepseek_moe_16b.json
    if python -m repro.analysis.lint --arch qwen3_8b --reduced \
        --plan tests/fixtures/broken_plan.json >/dev/null 2>&1; then
        echo "lint gate failed: broken plan fixture passed the lint" >&2
        exit 1
    fi
    if python -m repro.analysis.lint --arch qwen3_8b --reduced \
        --inject-fallback >/dev/null 2>&1; then
        echo "lint gate failed: seeded unfused dispatch passed" >&2
        exit 1
    fi

    echo "== instrumented serve smoke (telemetry stream) =="
    # A short paged speculative serve with --metrics-out, then the
    # stream is validated against the schema contract (exact key set of
    # the final serve.metrics event, span/event record shape, and the
    # fused-bytes-vs-analytic-bits/32 parity within 1%). The validator
    # fails on an empty or malformed stream; the JSONL is archived
    # beside the BENCH_*.json artifacts.
    rm -f BENCH_serve_metrics.jsonl
    python -m repro.launch.serve --arch qwen3_8b --reduced \
        --requests 8 --max-new-tokens 4 --max-seq-len 64 \
        --speculative 2 --paged --paged-attn --pack-weights \
        --metrics-out BENCH_serve_metrics.jsonl --metrics-interval 4
    python -m repro.obs.validate BENCH_serve_metrics.jsonl
fi

echo "== 8-device distributed smoke (mesh matrix) =="
XLA_FLAGS="--xla_force_host_platform_device_count=8" \
    python -m repro.launch.dryrun --mesh-matrix

echo "CI green"
