"""Roofline share of the decode step: the least time its shapes need
(weights once, live KV pages, the step's operations; ``bench/work``)
over the mean device time of a ``decode_step`` execution, for the calls
in the traced stretch."""

from bench import trace as tr
from bench.record import share


def read(run):
    t = run.trace
    calls = run.traced_calls("decode")
    if t is None or not calls:
        return None
    pred = lambda n: "decode_step" in n and "prefill" not in n
    dev = tr.matched_s(t.modules, t.window, pred, "decode_step program")
    mean_dev = dev / max(tr.count(t.modules, t.window, pred), 1)
    return share(run.least_sum(calls, "step") / len(calls), mean_dev)
