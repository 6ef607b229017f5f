"""The serving tick as the engine times it: the three readers of its
counters on a toy run on the CPU (and nothing from a program that keeps
no such counters), and the account of traces recorded on a TPU v5e by
``run.py --save-trace``."""
import json
import statistics
import types

import pytest

from bench import cell as cells
from bench import run as bench_run
from bench import trace as tr
from bench.client import Client
from bench.record import Call, Run
from bench.tests.tiny import DATA, make_bench

READERS = ("tick_host_ms", "prefill_tokens_per_pass", "ttft_wait_p50_s")
PHASES = ("prepare", "table", "dispatch", "wait", "commit", "admit")
GAP_S = 0.5e-3     # the least device-idle gap a phase span has to name


@pytest.fixture(scope="module")
def toy_run(tmp_path_factory):
    from repro import obs

    tmp = tmp_path_factory.mktemp("bench")
    bench = make_bench(tmp)
    cell = cells.resolve("tiny.chat", cells.load_manifest(tmp), bench)
    seed, seconds = 2718281828459, 2.0
    engine = bench_run.build_engine(cell, seed, obs.Tracer())
    bench_run.warm(engine)
    gen = cells.load_module("generators", cell.traffic["kind"], bench)
    source = gen.make(cell.traffic, seed, seconds,
                      cell.config["model"]["vocab_size"])
    client = Client(engine, source)
    client.setup()
    snap0 = engine.metrics_snapshot()
    client.window(seconds)
    snap1 = engine.metrics_snapshot()
    return Run(cell=cell, seed=seed, seconds=seconds, setup_s=0.0,
               t0=client.t0, t1=client.t1, requests=source.requests,
               counters={"start": snap0, "end": snap1}, peaks={},
               work=None)


def _recorded(name):
    """A trace kept by ``run.py --save-trace``, as the run that made it:
    its cell, and the calls of the kept stretch."""
    rec = json.loads((DATA / f"v5e_{name}.json").read_text())
    t = rec["trace"]
    trace = tr.Trace(window=tuple(t["window"]),
                     **{k: [tuple(iv) for iv in t[k]]
                        for k in ("ops", "modules", "host")})
    cell = cells.resolve(rec["cell"])
    peaks = json.loads((cells.BENCH / "peaks.json").read_text())
    return Run(cell=cell, seed=0, seconds=1.0, setup_s=1.0, t0=0.0, t1=1.0,
               requests=[], counters={}, peaks=peaks["TPU v5 lite"],
               work=cells.load_module("work", cell.config["work"]),
               calls=[Call(kind, [tuple(r) for r in rows], True)
                      for kind, rows in rec["calls"]],
               trace=trace)


def _read(name, run):
    return cells.load_module("metrics", name).read(run)


@pytest.mark.parametrize("name", READERS)
def test_reader_on_a_toy_run(toy_run, name):
    value = _read(name, toy_run)
    assert value is not None and value > 0, (name, value)
    if name == "prefill_tokens_per_pass":
        # each chunk pads to a power of two: at most one token a pass
        assert value <= 1.0
    if name == "ttft_wait_p50_s":
        assert value <= toy_run.window_s


@pytest.mark.parametrize("name", READERS)
def test_reader_finds_nothing_in_an_older_program(toy_run, name):
    """Snapshots without the new counters and requests without the
    admission stamp, as a program before them gives: nothing to read."""
    old = {k: v for k, v in toy_run.counters["end"].items()
           if not k.startswith(("host_", "prefill_tokens",
                                "prefill_weight"))}
    run = Run(**{**vars(toy_run), "counters": {"start": old, "end": old},
                 "requests": [
                     types.SimpleNamespace(**{**vars(r), "handle":
                                              types.SimpleNamespace(
                                                  output=[])})
                     for r in toy_run.requests]})
    assert _read(name, run) is None


def test_tick_account_of_the_toy_run(toy_run):
    """The phase totals of the window, per tick: all six phases kept, no
    more than the window holds, and ``tick_host_ms`` all but ``wait``."""
    ticks = toy_run.delta("ticks")
    assert ticks > 0
    phase_ms = {p: 1e3 * toy_run.delta(f"host_{p}_s") / ticks
                for p in PHASES}
    assert all(v >= 0 for v in phase_ms.values())
    assert phase_ms["wait"] > 0
    assert sum(phase_ms.values()) <= 1e3 * toy_run.window_s / ticks
    assert _read("tick_host_ms", toy_run) == pytest.approx(
        sum(phase_ms.values()) - phase_ms["wait"])


def _trace_account(t):
    """The decode programs of a trace's window, the period between their
    starts, and the host-device skew: the device start of each decode
    program less the opening of the ``serve.decode`` span that enqueued
    it, the n-th program paired with the n-th span. It measures the
    clocks' offset only where the device is idle when the step is
    enqueued."""
    lo, hi = t.window
    dec = [m for m in t.modules
           if "decode_step" in m[0] and "prefill" not in m[0]
           and lo <= m[1] < hi]
    opens = [h[1] for h in t.host
             if h[0] == "serve.decode" and lo <= h[1] < hi]
    return {"decode_programs": len(dec),
            "decode_device_ms": statistics.mean(
                (e - s) * 1e-6 for _, s, e in dec),
            "decode_period_ms": statistics.median(
                (b[1] - a[1]) * 1e-6 for a, b in zip(dec, dec[1:])),
            "skew_least_us": min(m[1] - o for m, o in zip(dec, opens)) * 1e-3}


def test_trace_account_of_a_v5e_decode():
    """Two granite decode steps: the second program starts 62.3 us before
    the ``serve.decode`` span that enqueued it opens on the host clock."""
    t = _recorded("granite_decode").trace
    acc = _trace_account(t)
    assert acc["decode_programs"] == 2
    assert acc["decode_device_ms"] == pytest.approx(59.5, abs=0.1)
    assert acc["decode_period_ms"] == pytest.approx(63.29, abs=0.01)
    assert acc["skew_least_us"] == pytest.approx(-62.337, abs=1e-3)
    gaps = [sec for _, sec in tr.idle_gaps(t, n=len(t.ops) + 1)
            if sec >= GAP_S]
    assert len(gaps) >= 2


# the spans of a tick's phases (and what runs inside them)
PHASE_SPANS = {"serve.prepare", "serve.h2d_table", "serve.decode",
               "serve.wait", "serve.commit", "serve.admission",
               "serve.prefill", "serve.gc"}


def test_v5e_ticks_idle_gaps_are_named_by_phases():
    """Two whole granite ticks traced on a v5e: every device-idle gap of
    0.5 ms or more falls in a phase of the tick or a collection, never in
    bare ``serve.tick`` or outside every span."""
    t = _recorded("granite_ticks").trace
    ticks_ = [h for h in t.host if h[0] == "serve.tick"]
    assert len(ticks_) == 2
    for _, s, e in ticks_:
        inside = sorted(n for n, hs, he in t.host
                        if s <= hs and he <= e and n != "serve.tick")
        assert inside == sorted(PHASE_SPANS - {"serve.prefill",
                                               "serve.gc"})
    gaps = [(label, sec) for label, sec in tr.idle_gaps(t, n=len(t.ops))
            if sec >= GAP_S]
    assert len(gaps) >= 2
    for label, sec in gaps:
        assert label in PHASE_SPANS, (label, sec)


def test_v5e_ticks_kernels_keep_their_names():
    """The kernels named by ``pallas_call(name=...)`` are found in the
    chip's trace by the readers, and read as two decode steps of the
    same cell recorded before the names were fixed."""
    run, old = _recorded("granite_ticks"), _recorded("granite_decode")
    assert len(run.calls) == len(old.calls) == 2
    for name, rel in (("decode_step_mfu_roofline", 0.02),
                      ("packed_matmul_roofline", 0.02),
                      ("paged_attention_roofline", 0.1)):
        assert _read(name, run) == pytest.approx(_read(name, old),
                                                 rel=rel), name
