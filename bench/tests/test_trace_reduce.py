"""The reduction from a profiler trace to per-layer metrics: on a hand-made
trace whose answers are known, and on short stretches of traces recorded
on a TPU v5e (``data/v5e_*.json``, written by ``run.py --save-trace``)."""
import json

import pytest

from bench import cell as cells
from bench import trace as tr
from bench.record import Call, Run
from bench.tests.tiny import DATA

MS = 1_000_000


def _hand_trace():
    # a 100 ms window: a decode program 0-20 ms (two kernels inside), a
    # prefill program 30-70 ms, nothing 70-100 ms while the host steps
    ops = [("packed_matmul.1", 0, 8 * MS), ("fusion.1", 8 * MS, 12 * MS),
           ("paged_attention.1", 12 * MS, 20 * MS),
           ("while.1", 30 * MS, 70 * MS),          # the body's loop
           ("packed_matmul.2", 30 * MS, 60 * MS),
           ("fusion.2", 60 * MS, 70 * MS)]
    modules = [("jit_decode_step(1)", 0, 20 * MS),
               ("jit_prefill_step(2)", 30 * MS, 70 * MS)]
    host = [(tr.WINDOW_SPAN, 0, 100 * MS), ("serve.tick", 70 * MS, 99 * MS),
            ("serve.decode", 72 * MS, 98 * MS)]
    return tr.Trace(window=(0, 100 * MS), ops=ops, modules=modules,
                    host=host)


def test_busy_idle_and_gaps():
    t = _hand_trace()
    assert t.window_s == pytest.approx(0.1)
    assert tr.busy_s(t) == pytest.approx(0.060)
    gaps = tr.idle_gaps(t)
    assert gaps[0] == ["serve.decode", pytest.approx(0.030)]
    assert gaps[1] == ["idle", pytest.approx(0.010)]
    top = dict((n, s) for n, s in tr.top_ops(t))
    assert top["packed_matmul.2"] == pytest.approx(0.030)
    assert "while.1" not in top


def test_clipping_to_the_window():
    t = _hand_trace()
    t.window = (10 * MS, 40 * MS)
    assert tr.busy_s(t) == pytest.approx(0.020)
    assert tr.count(t.modules, t.window, lambda n: "prefill" in n) == 1
    assert tr.time_s(t.modules, t.window,
                     lambda n: "prefill" in n) == pytest.approx(0.010)


def _run_over(trace, calls, cell="qwen3_8b.chat"):
    c = cells.resolve(cell)
    with open(cells.BENCH / "peaks.json") as f:
        peaks = json.load(f)["TPU v5 lite"]
    return Run(cell=c, seed=0, seconds=1.0, setup_s=1.0, t0=0.0, t1=1.0,
               requests=[], counters={}, peaks=peaks,
               work=cells.load_module("work", c.config["work"]),
               calls=calls, trace=trace)


def _read(name, run):
    return cells.load_module("metrics", name).read(run)


def test_hand_trace_metrics():
    run = _run_over(_hand_trace(), [Call("decode", [(1, 100)] * 4, True),
                                    Call("prefill", [(16, 48)], True)])
    assert _read("device_idle_share", run) == pytest.approx(40.0)
    assert _read("prefill_busy_share", run) == pytest.approx(200 / 3)
    w = run.work.call_work(run.sizes, [(1, 100)] * 4)["step"]
    want = 100 * run.least_s(*w) / 0.020
    assert _read("decode_step_mfu_roofline", run) == pytest.approx(want)
    flops = sum(run.work.model_flops(run.sizes, c.rows) for c in run.calls)
    assert _read("mfu", run) == pytest.approx(
        100 * flops / (0.1 * run.peaks["bf16_flops_per_s"]))
    # calls were traced but no op bears the kernel's name: the reader's
    # name is wrong, and it says which names it saw
    run.trace.ops = [o for o in run.trace.ops if "paged" not in o[0]]
    with pytest.raises(LookupError, match="fusion.1"):
        _read("paged_attention_roofline", run)
    # no calls in the traced stretch: nothing to read
    run.calls = []
    assert _read("paged_attention_roofline", run) is None


def _recorded(name):
    rec = json.loads((DATA / f"v5e_{name}.json").read_text())
    t = rec["trace"]
    trace = tr.Trace(window=tuple(t["window"]),
                     **{k: [tuple(iv) for iv in t[k]]
                        for k in ("ops", "modules", "host")})
    calls = [Call(kind, [tuple(r) for r in rows], True)
             for kind, rows in rec["calls"]]
    return _run_over(trace, calls, rec["cell"])


# what each recorded stretch holds, and the readers that must find their
# kernels and programs in it by name
RECORDED = {
    "granite_decode": ("decode", ("decode_step_mfu_roofline",
                                  "packed_matmul_roofline",
                                  "paged_attention_roofline")),
    "qwen_prefill": ("prefill", ("prefill_busy_share",
                                 "prefill_mfu_roofline",
                                 "packed_matmul_roofline",
                                 "paged_attention_roofline")),
}


@pytest.mark.parametrize("name", sorted(RECORDED))
def test_recorded_v5e_trace(name):
    kind, readers = RECORDED[name]
    run = _recorded(name)
    t = run.trace
    assert {c.kind for c in run.calls} == {kind}
    assert 0 < tr.busy_s(t) <= t.window_s
    assert 0 <= _read("device_idle_share", run) < 100
    # a loop's instruction spans its body: only the body's ops are ranked
    top = [n for n, _ in tr.top_ops(t)]
    assert top and not any(tr.base(n) == "while" for n in top)
    for reader in readers:
        value = _read(reader, run)
        assert value is not None and 0 < value <= 100, (reader, value)


def test_recorded_decode_step_shares():
    """Two whole decode steps of granite_34b.long_gen at 16 slots: the
    step's share and its kernels' shares as the chip run read them."""
    run = _recorded("granite_decode")
    (n_steps,) = {tr.count(run.trace.modules, run.trace.window,
                           lambda n: "decode_step" in n)}
    assert n_steps == len(run.calls) == 2
    step = _read("decode_step_mfu_roofline", run)
    pmm = _read("packed_matmul_roofline", run)
    attn = _read("paged_attention_roofline", run)
    # a step streams ~8.6 GB of AF8 weights: ~10.5 ms at 819 GB/s
    assert 12 < step < 25
    assert step < pmm < 60
    assert attn < 5
    # 16 tokens a step through 8.6 G matmul parameters, in 125 ms
    assert 1 < _read("mfu", run) < 3
