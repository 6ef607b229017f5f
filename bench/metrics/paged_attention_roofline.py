"""Roofline share of the paged-attention kernel: the least time of the
attention work of every call in the traced stretch (the distinct live
KV pages, the scores and sums of the real tokens) over the device time
of the kernel's executions."""

from bench import trace as tr
from bench.record import share

KERNEL = "paged_attention"


def read(run):
    t = run.trace
    calls = run.traced_calls()
    if t is None or not calls:
        return None
    dev = tr.matched_s(t.ops, t.window,
                       lambda n: tr.base(n) == KERNEL, KERNEL)
    return share(run.least_sum(calls, "paged_attention"), dev)
