"""Offer a traffic source's requests to the engine and record when each
token comes back.

The engine is driven only through ``submit()`` and ``step()``. What a
request has received is read from the engine's own request object after
each step (``Request.output``); a token is timed when the ``step()``
that returned it returns.
"""
from __future__ import annotations

import time
from typing import Callable, List, Optional

from bench.load import Req


def engine_request(engine, rid: int):
    """The engine's record of request ``rid``: resident or queued."""
    req = engine._active.get(rid)
    if req is None:
        req = next((r for r in engine._queue if r.rid == rid), None)
    if req is None:
        raise KeyError(f"request {rid} is neither resident nor queued")
    return req


class Client:
    def __init__(self, engine, source, clock: Callable[[], float]
                 = time.perf_counter):
        self.engine = engine
        self.source = source
        self.clock = clock
        self.inflight: List[Req] = []
        self.failed = 0
        self.attempted = 0
        self.t0: Optional[float] = None
        self.t1: Optional[float] = None

    def submit(self, req: Req) -> None:
        self.attempted += 1
        try:
            req.rid = self.engine.submit(req.prompt, req.max_new)
        except ValueError:
            self.failed += 1
            return
        req.handle = engine_request(self.engine, req.rid)
        self.inflight.append(req)

    def step(self) -> None:
        self.engine.step()
        now = self.clock()
        rel = now - (self.t0 if self.t0 is not None else now)
        still = []
        for req in self.inflight:
            n = len(req.handle.output)
            req.token_times.extend([now] * (n - len(req.token_times)))
            if req.done:
                self.source.finished(req, rel)
            else:
                still.append(req)
        self.inflight = still

    def setup(self) -> None:
        """Submit the source's set-up requests and serve them until each
        has been prefilled (their first token is left for the window)."""
        for req in self.source.initial():
            self.submit(req)

    def window(self, seconds: float,
               hook: Optional[Callable[["Client", float], None]] = None
               ) -> None:
        """Serve for ``seconds``: submit what falls due, step while
        anything is in flight, sleep until the next arrival otherwise.
        ``hook(client, elapsed)`` runs between iterations (tracing)."""
        self.t0 = self.clock()
        end = self.t0 + seconds
        while True:
            now = self.clock()
            if now >= end:
                break
            if hook is not None:
                hook(self, now - self.t0)
            for req in self.source.due(now - self.t0):
                self.submit(req)
            if self.inflight:
                self.step()
                continue
            nxt = self.source.next_due()
            wake = end if nxt is None else min(end, self.t0 + nxt)
            time.sleep(max(wake - self.clock(), 0.0))
        self.t1 = self.clock()
