"""Roofline share of a prefill chunk: the least time the chunk needs
(one weight pass, the chunk's operations and KV pages; ``bench/work``)
over the mean device time of a ``prefill_step`` execution, for the
chunks in the traced stretch."""

from bench import trace as tr
from bench.record import share


def read(run):
    t = run.trace
    calls = run.traced_calls("prefill")
    if t is None or not calls:
        return None
    pred = lambda n: "prefill_step" in n
    dev = tr.matched_s(t.modules, t.window, pred, "prefill_step program")
    mean_dev = dev / max(tr.count(t.modules, t.window, pred), 1)
    return share(run.least_sum(calls, "step") / len(calls), mean_dev)
