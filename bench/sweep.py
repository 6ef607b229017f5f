"""Find the knee of an open-loop cell once, on the chip: the highest
arrival rate at which the engine keeps up, with no backlog that grows
through the window.

    python bench/sweep.py --workload qwen3_8b.chat --seconds 40 \
        --rates 0.2,0.3,0.4,0.5,0.6,0.8 [--seed N]

One process builds the cell's engine once, then offers the cell's mix at
each rate for ``--seconds`` and drains it before the next. For each rate
it prints one JSON line: requests offered and served a first token, the
waiting backlog (requests due without a first token) averaged over the
middle and the last third of the window, the median time to first token
in the first and the last third, and output tokens per second. A rate
keeps up when its backlog does not grow by more than half a request from
the middle to the last third; the knee is the highest rate that keeps
up with every lower rate keeping up too. The cell's traffic file then
takes 0.8 of the knee. (The medians of time to first token are printed
beside it but not judged: over the three or four requests of a third
they swing both ways at rates that keep up.)
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
from bench import load  # noqa: E402
from bench import run as bench_run  # noqa: E402
from bench.client import Client  # noqa: E402


def backlog(reqs, t0: float, lo: float, hi: float, step: float = 0.5):
    """Mean number of requests due but without a first token, sampled
    every ``step`` seconds of [lo, hi) after ``t0``."""
    samples = []
    t = lo
    while t < hi:
        samples.append(sum(
            1 for r in reqs if r.due <= t
            and not (r.token_times and r.token_times[0] - t0 <= t)))
        t += step
    return sum(samples) / max(len(samples), 1)


def ttft(reqs, t0: float, t1: float, lo: float, hi: float):
    waits = [(r.token_times[0] if r.token_times else t1) - (t0 + r.due)
             for r in reqs if lo <= r.due < hi]
    return load.percentile(waits, 50) if waits else None


def one_rate(engine, cell, rate: float, seed: int, seconds: float):
    traffic = dict(cell.traffic, rate_rps=rate)
    gen = cells.load_module("generators", traffic["kind"], cell.bench)
    source = gen.make(traffic, seed, seconds,
                      cell.config["model"]["vocab_size"])
    client = Client(engine, source)
    client.window(seconds)
    t0, t1 = client.t0, client.t1
    reqs = source.requests
    third = seconds / 3
    out = {
        "rate_rps": rate, "seconds": seconds,
        "offered": sum(1 for r in reqs if r.due <= seconds),
        "first_token": sum(1 for r in reqs if r.token_times
                           and r.token_times[0] <= t1),
        "backlog_mid": backlog(reqs, t0, third, 2 * third),
        "backlog_last": backlog(reqs, t0, 2 * third, seconds),
        "ttft_p50_first_s": ttft(reqs, t0, t1, 0, third),
        "ttft_p50_last_s": ttft(reqs, t0, t1, 2 * third, seconds),
        "output_tokens_per_s": sum(
            1 for r in reqs for t in r.token_times if t0 <= t <= t1)
        / (t1 - t0),
    }
    out["keeps_up"] = bool(out["backlog_last"] <= out["backlog_mid"] + 0.5)
    while client.inflight:           # drain before the next rate
        client.step()
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=40.0)
    ap.add_argument("--rates", required=True)
    ap.add_argument("--seed", type=int, default=20260101)
    args = ap.parse_args(argv)
    cell = cells.resolve(args.workload)
    bench_run.use_compile_cache()
    from repro import obs

    bench_run.check_device(cell.chips, bench_run.load_peaks())
    engine = bench_run.build_engine(cell, args.seed, obs.Tracer())
    bench_run.warm(engine)
    knee, failed = None, False
    for rate in sorted(float(r) for r in args.rates.split(",")):
        res = one_rate(engine, cell, rate, args.seed, args.seconds)
        print(json.dumps(res), flush=True)
        failed = failed or not res["keeps_up"]
        if not failed:
            knee = rate
    print(json.dumps({"knee_rps": knee,
                      "cell_rate_rps": None if knee is None else 0.8 * knee}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
