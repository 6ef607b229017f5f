"""Median wait before admission over every request due in the window:
from the moment it was due to the engine's ``admitted_at`` stamp on it
(the part of the time to first token spent before the request holds a
slot, including any time the client could not submit it). A request not
admitted by the window's end counts as the window's end, as in
``ttft_p50_s``. Nothing where the engine stamps no admission."""

from bench.load import percentile


def read(run):
    waits = []
    for r in run.requests:
        if r.in_setup or r.due > run.window_s:
            continue
        at = run.t1
        if r.handle is not None:
            if not hasattr(r.handle, "admitted_at"):
                return None
            at = r.handle.admitted_at or run.t1
        waits.append(min(at, run.t1) - (run.t0 + r.due))
    return percentile(waits, 50) if waits else None
