"""Structured tracing: span/event records, ring-buffered, JSONL-sinkable.

A ``Tracer`` is the event half of the observability layer (the numeric
half is ``repro.obs.registry``). It records two shapes:

* **events** — instant records: ``tracer.event("serve.retune", tick=12,
  from_bits=8, to_bits=12)``;
* **spans** — timed records via context manager: ``with
  tracer.span("serve.decode", tick=n): ...`` stamps ``dur_s`` on exit.

Every record is a flat dict ``{"kind", "name", "ts", ("dur_s",)
"attrs"}``, with ``ts`` from ``time.time()`` (wall, for cross-process
alignment) and span durations from ``time.perf_counter()``. Records land
in a bounded in-memory ring (cheap enough for per-tick hot paths) and,
when a sink is attached, stream to a JSONL file one record per line —
the exchange format the launchers' ``--metrics-out`` flag exposes and
``repro.obs.schema.validate_metrics_jsonl`` checks.

``annotate=True`` additionally opens a ``jax.profiler.TraceAnnotation``
for every span so spans line up with XLA activity in a profiler trace;
it is feature-detected and silently off when unavailable (the module
itself never imports jax at import time — the obs layer stays
dependency-free).

``watch_gc()`` records every garbage collection of the process as a
``GC_SPAN`` span (``generation``, ``collected``) and keeps running totals
of the collections and the seconds they paused the interpreter
(``gc_collections``, ``gc_pause_ns``), whether or not anyone reads the
ring.
"""
from __future__ import annotations

import collections
import contextlib
import gc
import json
import threading
import time
import weakref
from typing import Any, Deque, Dict, IO, List, Optional, Union


def _json_default(o: Any) -> Any:
    """Coerce numpy scalars / arrays and other strays to JSON."""
    try:
        if hasattr(o, "item") and not hasattr(o, "__len__"):
            return o.item()
        if hasattr(o, "tolist"):
            return o.tolist()
        return float(o)
    except Exception:
        return str(o)


#: the span name of one garbage collection (``Tracer.watch_gc``)
GC_SPAN = "serve.gc"


class _GcHook:
    """The ``gc.callbacks`` entry of one tracer. It holds the tracer
    weakly, so a tracer that is gone leaves a hook that does nothing
    (and that the next ``watch_gc`` call removes)."""

    def __init__(self, tracer: "Tracer"):
        self.tracer = weakref.ref(tracer)
        self._t0 = 0
        self._ts = 0.0
        self._ann = None

    def __call__(self, phase: str, info: Dict[str, Any]) -> None:
        tracer = self.tracer()
        if tracer is None:
            return
        if phase == "start":
            self._ts = time.time()
            if tracer._annotation is not None:
                self._ann = tracer._annotation(GC_SPAN)
                self._ann.__enter__()
            self._t0 = time.perf_counter_ns()
            return
        dur = time.perf_counter_ns() - self._t0
        if self._ann is not None:
            self._ann.__exit__(None, None, None)
            self._ann = None
        tracer.gc_pause_ns += dur
        tracer.gc_collections += 1
        tracer._emit({"kind": "span", "name": GC_SPAN, "ts": self._ts,
                      "dur_s": dur * 1e-9,
                      "attrs": {"generation": info.get("generation"),
                                "collected": info.get("collected")}})


class Tracer:
    """Ring-buffered span/event recorder with an optional JSONL sink."""

    def __init__(self, ring_capacity: int = 4096,
                 sink: Union[None, str, IO[str]] = None,
                 annotate: bool = False):
        self._ring: Deque[Dict[str, Any]] = collections.deque(
            maxlen=ring_capacity)
        # re-entrant: a garbage collection can start inside _emit, and
        # the gc hook emits its own record from the same thread
        self._lock = threading.RLock()
        self._sink: Optional[IO[str]] = None
        self._owns_sink = False
        self.dropped = 0          # records emitted after the sink failed
        self.gc_collections = 0   # running totals kept by watch_gc
        self.gc_pause_ns = 0
        self._gc_hook: Optional[_GcHook] = None
        self._annotation = None
        if annotate:
            try:
                from jax.profiler import TraceAnnotation
                self._annotation = TraceAnnotation
            except Exception:
                self._annotation = None
        if sink is not None:
            self.set_sink(sink)

    # -- sink management ------------------------------------------------------
    def set_sink(self, sink: Union[str, IO[str]]) -> None:
        """Attach a JSONL sink: a path (opened/truncated, line-buffered)
        or an already-open text file object."""
        self.close()
        if isinstance(sink, str):
            self._sink = open(sink, "w", buffering=1)
            self._owns_sink = True
        else:
            self._sink = sink
            self._owns_sink = False

    def flush(self) -> None:
        with self._lock:
            if self._sink is not None:
                self._sink.flush()

    def close(self) -> None:
        with self._lock:
            if self._sink is not None and self._owns_sink:
                self._sink.close()
            self._sink = None
            self._owns_sink = False

    # -- recording ------------------------------------------------------------
    def _emit(self, rec: Dict[str, Any]) -> None:
        with self._lock:
            self._ring.append(rec)
            if self._sink is not None:
                try:
                    self._sink.write(
                        json.dumps(rec, default=_json_default) + "\n")
                except Exception:
                    self.dropped += 1

    def event(self, name: str, **attrs: Any) -> Dict[str, Any]:
        rec = {"kind": "event", "name": name, "ts": time.time(),
               "attrs": attrs}
        self._emit(rec)
        return rec

    @contextlib.contextmanager
    def span(self, name: str, **attrs: Any):
        """Timed record; mutate the yielded dict to attach late attrs:

            with tracer.span("serve.decode", tick=n) as sp:
                ...
                sp["emitted"] = emitted
        """
        live: Dict[str, Any] = dict(attrs)
        ann = (self._annotation(name) if self._annotation is not None
               else contextlib.nullcontext())
        t0 = time.perf_counter()
        ts = time.time()
        with ann:
            yield live
        self._emit({"kind": "span", "name": name, "ts": ts,
                    "dur_s": time.perf_counter() - t0, "attrs": live})

    def watch_gc(self) -> None:
        """Record each garbage collection from now on as a ``GC_SPAN``
        span and add it to ``gc_collections`` / ``gc_pause_ns``. One hook per
        tracer: later calls do nothing."""
        if self._gc_hook is not None:
            return
        gc.callbacks[:] = [cb for cb in gc.callbacks
                           if not (isinstance(cb, _GcHook)
                                   and cb.tracer() is None)]
        self._gc_hook = _GcHook(self)
        gc.callbacks.append(self._gc_hook)

    # -- inspection -----------------------------------------------------------
    def events(self, name: Optional[str] = None) -> List[Dict[str, Any]]:
        """Ring contents (oldest first), optionally filtered by name."""
        with self._lock:
            recs = list(self._ring)
        if name is None:
            return recs
        return [r for r in recs if r["name"] == name]

    def clear(self) -> None:
        with self._lock:
            self._ring.clear()
