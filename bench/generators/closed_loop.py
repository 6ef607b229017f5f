"""Closed loop: a fixed number of sessions, each of which sends its next
request as soon as its previous answer is complete.

Parameters (``bench/traffic/<name>.json``): ``sessions``,
``requests_per_session`` (how many each session may send; the sizes of
all of them are drawn as one multiset), and the length distributions
``prompt`` and ``output``. The first request of every session is
submitted during set-up, so the window opens on a full batch.
"""
from __future__ import annotations

import collections
from typing import Any, Deque, Dict, List, Optional

from bench import load


class ClosedLoop:
    def __init__(self, params: Dict[str, Any], seed: int, seconds: float,
                 vocab: int):
        s, k = params["sessions"], params["requests_per_session"]
        prompts = load.lengths(params["prompt"], s * k, load.rng(seed, 2))
        outputs = load.lengths(params["output"], s * k, load.rng(seed, 3))
        toks = load.rng(seed, 4)
        self._queues: List[Deque[load.Req]] = []
        for i in range(s):
            q = collections.deque()
            for j in range(k):
                n = i * k + j
                q.append(load.Req(
                    prompt=load.prompt_tokens(prompts[n], vocab, toks),
                    max_new=outputs[n], session=i))
            self._queues.append(q)
        self.requests: List[load.Req] = []
        self._ready: Deque[load.Req] = collections.deque()

    def _take(self, session: int, t: float) -> Optional[load.Req]:
        q = self._queues[session]
        if not q:
            return None
        req = q.popleft()
        req.due = t
        self.requests.append(req)
        return req

    def initial(self) -> List[load.Req]:
        out = []
        for i in range(len(self._queues)):
            req = self._take(i, 0.0)
            req.in_setup = True
            out.append(req)
        return out

    def due(self, t: float) -> List[load.Req]:
        out = list(self._ready)
        self._ready.clear()
        return out

    def next_due(self) -> Optional[float]:
        return self._ready[0].due if self._ready else None

    def finished(self, req: load.Req, t: float) -> None:
        nxt = self._take(req.session, t)
        if nxt is not None:
            self._ready.append(nxt)


def make(params, seed, seconds, vocab) -> ClosedLoop:
    return ClosedLoop(params, seed, seconds, vocab)
