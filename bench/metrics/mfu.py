"""Model FLOPs utilization over the traced stretch: the operations of
every token the engine processed in it (prompt and output tokens
through the matmul parameters, plus attention; ``bench/work``) over the
stretch's length times the chip's peak bf16 rate. The stretch and not
the whole window, because starting and stopping the profiler takes
seconds of the window in which the engine runs nothing."""

from bench.record import share


def read(run):
    calls = run.traced_calls()
    if run.trace is None or not calls:
        return None
    s = run.sizes
    flops = sum(run.work.model_flops(s, c.rows) for c in calls)
    return share(flops, run.trace.window_s * run.peaks["bf16_flops_per_s"])
