"""Share of the traced stretch in which no operation ran on the device."""

from bench import trace as tr


def read(run):
    t = run.trace
    if t is None or t.window_s <= 0:
        return None
    return 100.0 * (1.0 - tr.busy_s(t) / t.window_s)
