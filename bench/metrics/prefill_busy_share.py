"""Share of the device's busy time in the traced stretch that went to
``prefill_step`` programs."""

from bench import trace as tr
from bench.record import share


def read(run):
    t = run.trace
    if t is None or not run.traced_calls("prefill"):
        return None
    pre = tr.matched_s(t.modules, t.window, lambda n: "prefill_step" in n,
                       "prefill_step program")
    return share(pre, tr.busy_s(t))
