"""A profiler trace of part of the window, reduced to plain intervals.

The harness brackets a stretch of the window with ``jax.profiler`` and a
host span ``bench.window``; ``load`` reads the ``.xplane.pb`` written
with ``jax.profiler.ProfileData`` into a :class:`Trace` of three
interval lists on one clock (nanoseconds):

* ``ops``      operations that ran on the first TPU device ("XLA Ops"),
  named as the compiled program names its instructions (``packed_matmul.78``,
  ``fusion.12``, ``while.3``); a loop's instruction spans the operations
  of its body, which the line holds as well;
* ``modules``  executions of whole compiled programs ("XLA Modules");
* ``host``     the host spans of the engine's tracer (``serve.*``) and
  the harness (``bench.*``).

The reductions below read only these lists, so a trace recorded on the
chip (``run.py --save-trace``) can be kept as a small JSON file and
reduced again in a test.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Callable, Dict, List, Sequence, Tuple

Interval = Tuple[str, int, int]          # (name, start_ns, end_ns)
WINDOW_SPAN = "bench.window"
HOST_PREFIXES = ("serve.", "bench.")


@dataclasses.dataclass
class Trace:
    window: Tuple[int, int]
    ops: List[Interval]
    modules: List[Interval]
    host: List[Interval]

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def to_json(self) -> Dict:
        return dataclasses.asdict(self)


def load(trace_dir: str) -> Trace:
    """Read the newest ``.xplane.pb`` under ``trace_dir``."""
    from jax.profiler import ProfileData

    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no profiler trace under {trace_dir}")
    data = ProfileData.from_file(files[-1])
    ops: List[Interval] = []
    modules: List[Interval] = []
    host: List[Interval] = []
    device = sorted((p for p in data.planes
                     if p.name.startswith("/device:TPU:")),
                    key=lambda p: p.name)
    if not device:
        raise ValueError("the trace holds no TPU device plane: "
                         f"{[p.name for p in data.planes]}")
    for line in device[0].lines:
        dest = {"XLA Ops": ops, "XLA Modules": modules}.get(line.name)
        if dest is None:
            continue
        for ev in line.events:
            dest.append((short(ev.name), int(ev.start_ns), int(ev.end_ns)))
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith(HOST_PREFIXES):
                    host.append((ev.name, int(ev.start_ns), int(ev.end_ns)))
    win = [h for h in host if h[0] == WINDOW_SPAN]
    if not ops or not win:
        raise ValueError(
            f"no device ops or no {WINDOW_SPAN} span; lines: "
            f"{[(p.name, [ln.name for ln in p.lines]) for p in data.planes]}")
    return Trace(window=(win[0][1], win[0][2]), ops=sorted(ops, key=_start),
                 modules=sorted(modules, key=_start),
                 host=sorted(host, key=_start))


def short(name: str) -> str:
    """``%packed_matmul.78 = bf16[16,24576] custom-call(...)`` (an
    instruction's text, as the TPU trace names its ops) becomes
    ``packed_matmul.78``."""
    return name.split(" = ", 1)[0].lstrip("%")


def base(name: str) -> str:
    """An instruction's name without its number: ``packed_matmul``."""
    return re.sub(r"\.\d+$", "", name)


def _start(iv: Interval) -> int:
    return iv[1]


def leaves(ivs: Sequence[Interval]) -> List[Interval]:
    """The intervals that hold no other: a loop's instruction goes, the
    operations of its body stay."""
    order = sorted(ivs, key=lambda iv: (iv[1], -iv[2]))
    parent = [False] * len(order)
    stack: List[int] = []
    for i, (_, s, e) in enumerate(order):
        while stack and order[stack[-1]][2] <= s:
            stack.pop()
        if stack and e <= order[stack[-1]][2]:
            parent[stack[-1]] = True
        stack.append(i)
    return [iv for iv, p in zip(order, parent) if not p]


def trimmed(trace: Trace, seconds: float) -> Trace:
    """The first ``seconds`` of the traced stretch, as a small fixture."""
    lo = trace.window[0]
    win = (lo, min(trace.window[1], lo + int(seconds * 1e9)))
    return Trace(window=win, ops=clip(trace.ops, win),
                 modules=clip(trace.modules, win),
                 host=clip(trace.host, win))


def clip(ivs: Sequence[Interval], window: Tuple[int, int]
         ) -> List[Interval]:
    lo, hi = window
    return [(n, max(s, lo), min(e, hi)) for n, s, e in ivs
            if e > lo and s < hi]


def merged(ivs: Sequence[Interval], window: Tuple[int, int]
           ) -> List[Tuple[int, int]]:
    """The union of the intervals inside ``window``, as disjoint spans."""
    out: List[List[int]] = []
    for _, s, e in sorted(clip(ivs, window), key=_start):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def busy_s(trace: Trace) -> float:
    """Seconds of the window in which some operation ran on the device."""
    return sum(e - s for s, e in merged(trace.ops, trace.window)) * 1e-9


def time_s(ivs: Sequence[Interval], window: Tuple[int, int],
           pred: Callable[[str], bool]) -> float:
    """Seconds the intervals whose name satisfies ``pred`` took, within
    the window (summed, not unioned: they do not overlap on one line)."""
    return sum(e - s for n, s, e in clip(ivs, window) if pred(n)) * 1e-9


def matched_s(ivs: Sequence[Interval], window: Tuple[int, int],
              pred: Callable[[str], bool], what: str) -> float:
    """:func:`time_s` of a kernel or program that the engine is known to
    have run in the window: where no name matches, the reader's name for
    it is wrong, so this raises with the names the trace does hold."""
    t = time_s(ivs, window, pred)
    if t <= 0:
        names = sorted({n for n, _, _ in clip(ivs, window)})
        raise LookupError(f"no {what} in the traced stretch; "
                          f"{len(names)} names seen: {names[:60]}")
    return t


def count(ivs: Sequence[Interval], window: Tuple[int, int],
          pred: Callable[[str], bool]) -> int:
    """Intervals whose name satisfies ``pred`` that start in the window."""
    lo, hi = window
    return sum(1 for n, s, _ in ivs if pred(n) and lo <= s < hi)


def top_ops(trace: Trace, n: int = 10) -> List[List]:
    """The ``n`` operations that took most device time, loops' bodies
    counted by their operations and not again by the loop."""
    tot: Dict[str, int] = collections.Counter()
    for name, s, e in leaves(clip(trace.ops, trace.window)):
        tot[name] += e - s
    return [[name, ns * 1e-9] for name, ns in tot.most_common(n)]


def idle_gaps(trace: Trace, n: int = 10) -> List[List]:
    """The ``n`` longest stretches with nothing on the device, each named
    by the innermost host span that covers its middle (``idle`` if
    none)."""
    lo, hi = trace.window
    busy = merged(trace.ops, trace.window)
    gaps, prev = [], lo
    for s, e in busy:
        if s > prev:
            gaps.append((prev, s))
        prev = max(prev, e)
    if hi > prev:
        gaps.append((prev, hi))
    spans = [h for h in trace.host if h[0] != WINDOW_SPAN]
    out = []
    for s, e in sorted(gaps, key=lambda g: g[0] - g[1])[:n]:
        mid = (s + e) // 2
        cover = [h for h in spans if h[1] <= mid < h[2]]
        label = (min(cover, key=lambda h: h[2] - h[1])[0] if cover
                 else "idle")
        out.append([label, (e - s) * 1e-9])
    return out
