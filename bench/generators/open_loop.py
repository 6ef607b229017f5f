"""Open loop: independent users send on a schedule, whatever the
system's state, so a slow system builds a queue.

Parameters (``bench/traffic/<name>.json``): ``rate_rps``, and the length
distributions ``prompt`` and ``output`` (see ``bench/load.py``). The
schedule holds ``round(rate_rps * seconds)`` requests, all due inside
the window. With ``order_seed``, the sizes and gaps come in the order
that seed draws, the same for every run; the run's seed then draws only
the prompt tokens (and, through the engine, the weights). A mix of a few
requests a window needs it: there the order alone moves a median wait
by more than the system's own run-to-run noise.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional

from bench import load


class OpenLoop:
    def __init__(self, params: Dict[str, Any], seed: int, seconds: float,
                 vocab: int):
        order = params.get("order_seed", seed)
        gaps = load.poisson_gaps(params["rate_rps"], seconds,
                                 load.rng(order, 1))
        n = len(gaps)
        prompts = load.lengths(params["prompt"], n, load.rng(order, 2))
        outputs = load.lengths(params["output"], n, load.rng(order, 3))
        toks = load.rng(seed, 4)
        t = 0.0
        self.requests: List[load.Req] = []
        for gap, p, m in zip(gaps, prompts, outputs):
            t += gap
            self.requests.append(load.Req(
                prompt=load.prompt_tokens(p, vocab, toks), max_new=m,
                due=t))
        self._next = 0

    def initial(self) -> List[load.Req]:
        return []

    def due(self, t: float) -> List[load.Req]:
        out = []
        while (self._next < len(self.requests)
               and self.requests[self._next].due <= t):
            out.append(self.requests[self._next])
            self._next += 1
        return out

    def next_due(self) -> Optional[float]:
        if self._next < len(self.requests):
            return self.requests[self._next].due
        return None

    def finished(self, req: load.Req, t: float) -> None:
        pass


def make(params, seed, seconds, vocab) -> OpenLoop:
    return OpenLoop(params, seed, seconds, vocab)
