"""Benchmark harness: one function per paper table/figure + framework
benches. Prints ``name,us_per_call,derived`` CSV rows.

    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run --only fig9,roofline

Every ``BENCH_*.json`` artifact a selected bench (re)writes gets a
``telemetry`` key stamped in afterwards: the backend support matrix and
the full ``obs.REGISTRY`` snapshot at the end of the run — so an
archived artifact records which kernel paths actually dispatched and
what the byte counters read when it was produced.
"""
from __future__ import annotations

import argparse
import glob
import json
import os
import sys
import time
import traceback


def _stamp_telemetry(t_start: float) -> int:
    """Embed {support_matrix, metrics} into every BENCH_*.json this run
    touched (mtime >= t_start). Artifacts from earlier runs are left
    alone — their telemetry described *their* run."""
    from repro import compat, obs
    telemetry = {
        "support_matrix": compat.support_matrix(),
        "metrics": obs.REGISTRY.snapshot(),
    }
    stamped = 0
    for path in sorted(glob.glob("BENCH_*.json")):
        if os.path.getmtime(path) < t_start:
            continue
        try:
            with open(path) as f:
                art = json.load(f)
        except (OSError, json.JSONDecodeError):
            continue
        if not isinstance(art, dict):
            continue
        art["telemetry"] = telemetry
        with open(path, "w") as f:
            json.dump(art, f, indent=1, default=str)
        stamped += 1
    return stamped


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated bench name filter")
    args = ap.parse_args()

    from repro import compat
    compat.enable_compile_cache()

    from benchmarks.calibration import bench_calibration
    from benchmarks.micro import bench_micro
    from benchmarks.packed_path import bench_packed_path
    from benchmarks.paper_suite import (
        bench_area_table,
        bench_fig9_pressure,
        bench_fig10_occupancy,
        bench_fig11_ipc,
        bench_fig12_writeback,
        bench_table1,
    )
    from benchmarks.perf_cells import bench_perf
    from benchmarks.roofline import bench_roofline
    from benchmarks.serving_paged import bench_serving_paged
    from benchmarks.serving_residency import bench_residency
    from benchmarks.speculative import bench_speculative
    from benchmarks.train_packed import bench_train_packed

    benches = {
        "table1": bench_table1,
        "fig9": bench_fig9_pressure,
        "fig10": bench_fig10_occupancy,
        "fig11": bench_fig11_ipc,
        "fig12": bench_fig12_writeback,
        "area": bench_area_table,
        "micro": bench_micro,
        "packed_path": bench_packed_path,
        "residency": bench_residency,
        "perf": bench_perf,
        "roofline": bench_roofline,
        "speculative": bench_speculative,
        "serving_paged": bench_serving_paged,
        "train_packed": bench_train_packed,
        "calibration": bench_calibration,
    }
    selected = (set(args.only.split(",")) if args.only else set(benches))

    print("name,us_per_call,derived")
    t_start = time.time()
    failed = 0
    for name, fn in benches.items():
        if name not in selected:
            continue
        try:
            for row_name, us, derived in fn():
                print(f"{row_name},{us:.1f},{derived}", flush=True)
        except Exception as e:  # noqa: BLE001
            failed += 1
            print(f"{name},0.0,ERROR:{type(e).__name__}:{e}", flush=True)
            traceback.print_exc(file=sys.stderr)
    stamped = _stamp_telemetry(t_start)
    if stamped:
        print(f"telemetry,0.0,stamped:{stamped}", flush=True)
    if failed:
        raise SystemExit(f"{failed} benchmark group(s) failed")


if __name__ == "__main__":
    main()
