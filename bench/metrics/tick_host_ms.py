"""Host time of a serving tick outside its wait for the device: the
engine's running totals of its host phases (``host_<phase>_s`` in
``metrics_snapshot()``: prepare, table, dispatch, commit and admit, but
not wait) over the window, per tick. Admissions that ``submit()`` ran
between ticks count too. Nothing where the engine keeps no such totals
or ran no tick."""

PHASES = ("prepare", "table", "dispatch", "commit", "admit")


def read(run):
    keys = [f"host_{p}_s" for p in PHASES]
    if not all(k in run.counters["end"] for k in keys):
        return None
    ticks = run.delta("ticks")
    if not ticks:
        return None
    return 1e3 * sum(run.delta(k) for k in keys) / ticks
