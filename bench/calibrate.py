"""Readings that a cell's correctness limit is set from, on the chip.

    python bench/calibrate.py --workload <cell> --seconds <s> \
        --seeds 11,12,13 [--control 3]

In one process, serves the cell once per seed as ``bench/run.py`` does
and prints one JSON line per seed: whether it was correct under the
configured limit, the widest logit gap of its served tokens under the
reference (the program's reading), and, for the first ``--control``
seeds, the widest gap of the tokens that the reference computed one
precision step lower puts first at the same positions (the control's
reading) and whether the control, judged by the configured limit as a
run is, comes out not correct, as it has to. The limit lies above the
largest program reading and below the smallest control reading
(``PERF.md`` gives both).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path[:0] = [os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"),
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]

from bench import cell as cells  # noqa: E402
from bench import run as bench_run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control", type=int, default=0)
    args = ap.parse_args(argv)
    cell = cells.resolve(args.workload)
    bench_run.use_compile_cache()
    for i, seed in enumerate(int(s) for s in args.seeds.split(",")):
        res = bench_run.run_cell(cell, seed, args.seconds, False,
                                 control=i < args.control)
        line = {"seed": seed, "correct": res["correct"],
                "logit_gap": res["checked"]["logit_gap"]["value"],
                "tokens": res["checked"]["tokens_compared"]["value"],
                "control_correct": res.get("control", {}).get("correct"),
                "control_logit_gap": res.get("control", {}).get(
                    "checked", {}).get("logit_gap", {}).get("value"),
                "metrics": {k: v["value"]
                            for k, v in res["metrics"].items()}}
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
