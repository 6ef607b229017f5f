"""Roofline share of the packed-weight matmul kernel: the least time of
the matmul work of every call in the traced stretch (weights once per
call at the packed width, the matmul operations of the real tokens)
over the device time of the kernel's executions."""

from bench import trace as tr
from bench.record import share

KERNEL = "packed_matmul"


def read(run):
    t = run.trace
    calls = run.traced_calls()
    if t is None or not calls:
        return None
    dev = tr.matched_s(t.ops, t.window,
                       lambda n: tr.base(n) == KERNEL, KERNEL)
    return share(run.least_sum(calls, "packed_matmul"), dev)
