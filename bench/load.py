"""Requests and the length and gap distributions that traffic mixes name.

Every seed gets the same multiset of sizes and gaps, in another order:
values are read off a distribution at the fixed quantiles
``(i + 0.5) / n`` and shuffled by the seed. So two seeds offer the same
work, and a run's spread is the system's, not the draw's. The seed also
picks the prompt tokens.
"""
from __future__ import annotations

import dataclasses
import math
import statistics
from typing import Any, Dict, List, Optional

import numpy as np


@dataclasses.dataclass
class Req:
    """One request the harness offers, and what became of it."""

    prompt: List[int]
    max_new: int
    due: float = 0.0                  # seconds after the window opens
    session: int = -1
    rid: Optional[int] = None
    handle: Any = None                # the engine's request object
    token_times: List[float] = dataclasses.field(default_factory=list)
    in_setup: bool = False

    @property
    def tokens(self) -> List[int]:
        """The tokens the engine has returned so far."""
        return [] if self.handle is None else list(self.handle.output)

    @property
    def done(self) -> bool:
        return len(self.token_times) >= self.max_new


def rng(seed: int, stream: int) -> np.random.Generator:
    """An independent generator per purpose (sizes, order, tokens)."""
    return np.random.default_rng([int(seed) & (2**63 - 1), stream])


def quantiles(n: int) -> np.ndarray:
    return (np.arange(n) + 0.5) / n


def lengths(spec: Dict[str, Any], n: int, gen: np.random.Generator
            ) -> List[int]:
    """``n`` integer lengths of ``spec`` in an order drawn from ``gen``."""
    q = quantiles(n)
    kind = spec["dist"]
    if kind == "lognormal":
        z = np.array([statistics.NormalDist().inv_cdf(x) for x in q])
        vals = np.rint(spec["median"] * np.exp(spec["sigma"] * z))
    elif kind == "uniform":
        lo, hi = spec["min"], spec["max"]
        vals = lo + np.floor(q * (hi - lo + 1))
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    vals = np.clip(vals, spec["min"], spec["max"]).astype(int)
    return [int(v) for v in gen.permutation(vals)]


def poisson_gaps(rate: float, seconds: float, gen: np.random.Generator
                 ) -> List[float]:
    """Gaps of ``round(rate * seconds)`` arrivals: exponential quantiles,
    scaled so that the last arrival falls inside the window and the mean
    rate is ``rate``, shuffled by ``gen``."""
    n = max(int(round(rate * seconds)), 1)
    g = -np.log1p(-quantiles(n))
    g *= seconds * n / (n + 1) / g.sum()
    return [float(x) for x in gen.permutation(g)]


def prompt_tokens(n: int, vocab: int, gen: np.random.Generator
                  ) -> List[int]:
    """Token ids 1..vocab-1 (0 is the engine's BOS)."""
    return [int(t) for t in gen.integers(1, vocab, n)]


def percentile(values: List[float], pct: float) -> float:
    """The ``pct`` percentile, linear between order statistics."""
    vals = sorted(values)
    if not vals:
        raise ValueError("percentile of nothing")
    pos = (len(vals) - 1) * pct / 100.0
    lo = math.floor(pos)
    hi = min(lo + 1, len(vals) - 1)
    return vals[lo] + (vals[hi] - vals[lo]) * (pos - lo)
