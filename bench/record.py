"""What one run saw, as the metric readers get it, and the arithmetic
they share.

A reader is ``bench/metrics/<name>.py`` with ``read(run) -> float | None``;
``None`` means it found nothing to read, and the harness leaves the
metric out of the result line.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

from bench.load import Req
from bench.trace import Trace


@dataclasses.dataclass
class Call:
    """One jitted step the engine ran in the window."""

    kind: str                          # "decode" | "prefill"
    rows: List[Tuple[int, int]]        # (new tokens, cache length after)
    traced: bool = False               # inside the profiled stretch


@dataclasses.dataclass
class Run:
    cell: Any                          # bench.cell.Cell
    seed: int
    seconds: float
    setup_s: float
    t0: float                          # window, perf_counter seconds
    t1: float
    requests: List[Req]
    counters: Dict[str, Dict[str, Any]]   # engine snapshots: start, end
    peaks: Dict[str, Any]
    work: Any                          # bench/work/<work>.py module
    calls: List[Call] = dataclasses.field(default_factory=list)
    trace: Optional[Trace] = None

    @property
    def window_s(self) -> float:
        return self.t1 - self.t0

    @property
    def sizes(self):
        return self.work.sizes(self.cell.config)

    def delta(self, key: str) -> float:
        return self.counters["end"][key] - self.counters["start"][key]

    def in_window(self, t: float) -> bool:
        return self.t0 <= t <= self.t1

    def least_s(self, flops: float, nbytes: float) -> float:
        """The least time the chip could take: compute or memory bound."""
        return max(flops / self.peaks["bf16_flops_per_s"],
                   nbytes / self.peaks["hbm_bytes_per_s"])

    def traced_calls(self, kind: Optional[str] = None) -> List[Call]:
        return [c for c in self.calls
                if c.traced and (kind is None or c.kind == kind)]

    def least_sum(self, calls: Sequence[Call], part: str) -> float:
        s = self.sizes
        return sum(self.least_s(*self.work.call_work(s, c.rows)[part])
                   for c in calls if c.rows)


def share(num: float, den: float) -> Optional[float]:
    """``num / den`` in percent, or nothing where there is no base."""
    if den <= 0:
        return None
    return 100.0 * num / den
