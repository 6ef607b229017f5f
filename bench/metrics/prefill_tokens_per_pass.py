"""Prompt tokens ingested per pass over the weights in prefill, over the
window (engine counters: ``prefill_tokens`` and
``prefill_weight_passes``, which counts every position a chunk's scan
streams the weights for, padding included). Nothing where the engine
keeps no such counters or prefilled nothing."""


def read(run):
    if "prefill_weight_passes" not in run.counters["end"]:
        return None
    passes = run.delta("prefill_weight_passes")
    return run.delta("prefill_tokens") / passes if passes else None
