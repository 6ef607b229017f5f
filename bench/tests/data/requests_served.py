"""Requests that received at least one token in the window: a metric a
test adds as a new file, to show that the harness finds it by name."""


def read(run):
    return float(sum(1 for r in run.requests
                     if any(run.in_window(t) for t in r.token_times)))
