"""Median time to first token over every request due in the window: from
the moment it was due (not when it was sent, so a stalled generator
counts) to the ``step()`` that returned its first token. A request with
no token by the window's end counts as the window's end."""

from bench.load import percentile


def read(run):
    waits = []
    for r in run.requests:
        if r.in_setup or r.due > run.window_s:
            continue
        first = r.token_times[0] if r.token_times else run.t1
        waits.append(min(first, run.t1) - (run.t0 + r.due))
    return percentile(waits, 50) if waits else None
