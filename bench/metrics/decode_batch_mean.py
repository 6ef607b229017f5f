"""Tokens returned per decode call over the window (engine counters:
``tokens`` and ``decode_calls``)."""


def read(run):
    calls = run.delta("decode_calls")
    return run.delta("tokens") / calls if calls else None
