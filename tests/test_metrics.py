"""Observability layer: registry/tracer/exporter units, the schema
stability contract (exact snapshot and drain key sets per engine mode),
mid-run snapshot purity, the byte-accounting parity invariant (live
counters vs. the analytic bits/32 model, and vs. the packed-path bench
artifact), and the JSONL stream validator end-to-end."""
import gc
import json
import math
import os
import re

import numpy as np
import pytest

from repro import obs
from repro.configs import get_config
from repro.obs.registry import MetricsRegistry
from repro.obs.schema import (
    BYTE_TOLERANCE,
    TRAIN_FINAL_KEYS,
    check_byte_parity,
    drain_keys,
    snapshot_keys,
    validate_metrics_jsonl,
)
from repro.obs.trace import GC_SPAN, Tracer
from repro.serving import ServeEngine, SpeculativeEngine
from repro.serving.engine import TICK_PHASES


def _tiny_cfg(name="qwen3_8b"):
    return get_config(name).reduced()


def _drain_engine(eng, n_requests=4, prompt_len=4, max_new=4, seed=0):
    cfg = eng.cfg
    rng = np.random.default_rng(seed)
    rids = [
        eng.submit(list(rng.integers(1, cfg.vocab_size, prompt_len)),
                   max_new_tokens=max_new)
        for _ in range(n_requests)
    ]
    stats = eng.run_until_drained()
    return rids, stats


# -- registry -----------------------------------------------------------------

def test_counter_monotone_and_labeled():
    reg = MetricsRegistry()
    c = reg.counter("requests_total", "help text")
    c.inc()
    c.inc(3, path="fused")
    c.inc(2, path="fused")
    assert c.value() == 1
    assert c.value(path="fused") == 5
    with pytest.raises(ValueError):
        c.inc(-1)


def test_registration_idempotent_but_type_checked():
    reg = MetricsRegistry()
    a = reg.counter("x_total")
    assert reg.counter("x_total") is a
    with pytest.raises(ValueError):
        reg.gauge("x_total")


def test_histogram_cumulative_buckets():
    reg = MetricsRegistry()
    h = reg.histogram("lat_seconds", buckets=(0.1, 1.0, 10.0))
    for v in (0.05, 0.5, 5.0, 50.0):
        h.observe(v)
    st = h.stats()
    assert st["buckets"] == [1, 2, 3]      # cumulative
    assert st["count"] == 4
    assert st["sum"] == pytest.approx(55.55)


_SAMPLE_RE = re.compile(
    r'^[a-zA-Z_:][a-zA-Z0-9_:]*'
    r'(\{[a-zA-Z_][a-zA-Z0-9_]*="[^"]*"'
    r'(,[a-zA-Z_][a-zA-Z0-9_]*="[^"]*")*\})? '
    r'(NaN|[+-]?Inf|[0-9eE.+-]+)$')


def test_expose_is_valid_prometheus_text():
    reg = MetricsRegistry()
    reg.counter("a_total", "with \"quotes\" and\nnewline").inc(
        2, op="matmul", path="fused")
    reg.gauge("b_ratio").set(0.25)
    reg.histogram("c_seconds", buckets=(0.5, 1.0)).observe(0.7)
    text = reg.expose()
    names_typed = set()
    for line in text.strip().split("\n"):
        if line.startswith("# HELP "):
            continue
        if line.startswith("# TYPE "):
            _, _, name, kind = line.split(" ")
            assert kind in ("counter", "gauge", "histogram")
            names_typed.add(name)
            continue
        assert _SAMPLE_RE.match(line), f"bad sample line: {line!r}"
    assert names_typed == {"a_total", "b_ratio", "c_seconds"}
    # histogram layout: every bucket + the implicit +Inf + sum + count
    assert 'c_seconds_bucket{le="0.5"} 0' in text
    assert 'c_seconds_bucket{le="1"} 1' in text
    assert 'c_seconds_bucket{le="+Inf"} 1' in text
    assert "c_seconds_sum 0.7" in text
    assert "c_seconds_count 1" in text


def test_snapshot_is_json_ready():
    reg = MetricsRegistry()
    reg.counter("k_total").inc(7, op="pack")
    snap = json.loads(json.dumps(reg.snapshot()))
    assert snap["k_total"]["type"] == "counter"
    assert snap["k_total"]["series"][0]["value"] == 7


# -- tracer -------------------------------------------------------------------

def test_tracer_ring_and_span_duration():
    t = Tracer(ring_capacity=3)
    for i in range(5):
        t.event("e", i=i)
    recs = t.events("e")
    assert len(recs) == 3                       # ring bounded
    assert [r["attrs"]["i"] for r in recs] == [2, 3, 4]
    with t.span("s", tick=1) as sp:
        sp["late"] = "attr"
    rec = t.events("s")[0]
    assert rec["kind"] == "span" and rec["dur_s"] >= 0
    assert rec["attrs"] == {"tick": 1, "late": "attr"}


def test_tracer_jsonl_sink_coerces_numpy(tmp_path):
    path = str(tmp_path / "t.jsonl")
    t = Tracer(sink=path)
    t.event("e", a=np.int64(3), b=np.float32(0.5), c=np.arange(2))
    t.close()
    recs = list(obs.read_jsonl(path))
    assert recs[0]["attrs"] == {"a": 3, "b": 0.5, "c": [0, 1]}


def test_read_jsonl_rejects_malformed(tmp_path):
    path = tmp_path / "bad.jsonl"
    path.write_text('{"kind": "event"}\nnot json\n')
    with pytest.raises(ValueError):
        list(obs.read_jsonl(str(path)))


def test_console_summary_renders_all_metrics():
    reg = MetricsRegistry()
    reg.counter("hits_total").inc(3, op="x")
    reg.histogram("lat_seconds").observe(0.01)
    out = obs.console_summary(reg)
    assert "hits_total" in out and "lat_seconds" in out


# -- schema stability (satellite: exact key sets per engine mode) -------------

def test_snapshot_and_drain_schema_plain():
    eng = ServeEngine(_tiny_cfg(), max_seq_len=64, max_slots=2)
    assert set(eng.metrics_snapshot()) == snapshot_keys()
    _, stats = _drain_engine(eng)
    assert set(stats) == drain_keys()


def test_snapshot_and_drain_schema_paged():
    eng = ServeEngine(_tiny_cfg(), max_seq_len=64, max_slots=2,
                      paged=True, kv_page_size=8)
    assert set(eng.metrics_snapshot()) == snapshot_keys(paged=True)
    _, stats = _drain_engine(eng)
    assert set(stats) == drain_keys(paged=True)


def test_snapshot_and_drain_schema_speculative():
    eng = SpeculativeEngine(_tiny_cfg(), max_seq_len=64, max_slots=2,
                            k=2, pack_weights=True, paged=True,
                            kv_page_size=8, adaptive=True)
    assert set(eng.metrics_snapshot()) == snapshot_keys(
        paged=True, speculative=True)
    _, stats = _drain_engine(eng)
    assert set(stats) == drain_keys(paged=True, speculative=True,
                                    adaptive=True)


def test_drain_reuses_snapshot_counters():
    tracer = Tracer()
    eng = ServeEngine(_tiny_cfg(), max_seq_len=64, max_slots=2,
                      tracer=tracer)
    _, stats = _drain_engine(eng)
    snap = eng.metrics_snapshot()
    for key, val in snap.items():
        if key.startswith("gc_"):
            continue      # process-wide: a collection may fall between
        assert stats[key] == val, key
    assert stats["wall_s"] > 0
    # a dense prefill chunk is one forward: one pass over the weights
    assert stats["prefill_calls"] > 0
    assert stats["weight_passes"] == (stats["decode_calls"]
                                      + stats["prefill_calls"])


# -- the serving tick: host phases, request stamps, true weight passes -------

_PHASE_SPANS = ("serve.prepare", "serve.decode", "serve.wait",
                "serve.commit", "serve.admission")


def test_tick_phases_sum_to_the_tick():
    """The running totals of the host phases grow, in each tick, by that
    tick's ``serve.tick`` span; each phase is a span of its own inside
    it; the engine waits on the device in every tick."""
    tracer = Tracer(ring_capacity=1 << 16)
    eng = ServeEngine(_tiny_cfg(), max_seq_len=64, max_slots=2,
                      tracer=tracer)
    rng = np.random.default_rng(0)
    for n in (5, 9, 3):          # the third waits: admitted by a tick
        eng.submit(list(rng.integers(1, eng.cfg.vocab_size, n)),
                   max_new_tokens=4)

    def host():
        snap = eng.metrics_snapshot()
        return {p: snap[f"host_{p}_s"] for p in TICK_PHASES}

    grew = []
    while eng._queue or eng._active:
        tracer.clear()
        before = host()
        eng.step()
        after = host()
        grew.append({p: after[p] - before[p] for p in TICK_PHASES})
        (tick,) = tracer.events("serve.tick")
        assert sum(grew[-1].values()) == pytest.approx(tick["dur_s"],
                                                       rel=0.05)
        spans = [r for r in tracer.events() if r["name"] in _PHASE_SPANS]
        assert sorted(r["name"] for r in spans) == sorted(_PHASE_SPANS)
        assert sum(r["dur_s"] for r in spans) <= tick["dur_s"]
        (wait,) = tracer.events("serve.wait")
        assert wait["dur_s"] > 0
    assert len(grew) > 2
    assert all(g["wait"] > 0 for g in grew)


def test_request_stamps_are_ordered():
    tracer = Tracer()
    eng = ServeEngine(_tiny_cfg(), max_seq_len=64, max_slots=2,
                      tracer=tracer)
    rng = np.random.default_rng(1)
    for n in (4, 6, 2):
        eng.submit(list(rng.integers(1, eng.cfg.vocab_size, n)),
                   max_new_tokens=3)
    reqs = list(eng._active.values()) + list(eng._queue)
    eng.run_until_drained()
    for r in reqs:
        assert 0 < r.submitted_at <= r.admitted_at <= r.first_token_at \
            <= r.finished_at
    recs = {rec["attrs"]["rid"]: rec["attrs"]
            for rec in tracer.events("serve.request")}
    assert sorted(recs) == sorted(r.rid for r in reqs)
    for r in reqs:
        a = recs[r.rid]
        assert a["queue_wait_s"] == r.admitted_at - r.submitted_at
        assert a["ttft_s"] == r.first_token_at - r.submitted_at
        assert a["e2e_s"] == r.finished_at - r.submitted_at
        assert (a["prompt_len"], a["output_len"]) == (len(r.prompt), 3)


@pytest.mark.parametrize("arch,speculative", [("qwen3_8b", False),
                                               ("qwen3_8b", True),
                                               ("deepseek_moe_16b", False)])
def test_prefill_weight_passes_count_scanned_positions(arch, speculative):
    """A dense prefill chunk is one forward, one pass over the weights
    (the speculative draft mirrors it); a moe chunk scans the decode
    step, one pass per position, padding included. The byte counters
    follow the pass count."""
    tracer = Tracer()
    kw = dict(max_seq_len=64, max_slots=2, pack_weights=True,
              prefill_chunk=8, tracer=tracer)
    eng = (SpeculativeEngine(_tiny_cfg(arch), k=2, **kw) if speculative
           else ServeEngine(_tiny_cfg(arch), **kw))
    lens = (5, 20, 1)
    rng = np.random.default_rng(2)
    for n in lens:
        eng.submit(list(rng.integers(1, eng.cfg.vocab_size, n)),
                   max_new_tokens=2)
    stats = eng.run_until_drained()
    chunks = [r["attrs"]["chunk"] for r in tracer.events("serve.prefill")]
    # every prompt token but the last is prefilled; chunks pad to 2^k
    assert stats["prefill_tokens"] == sum(n - 1 for n in lens)
    assert stats["prefill_calls"] == len(chunks)
    assert sum(chunks) > len(chunks)
    one_pass = eng.lm.one_pass_prefill
    assert one_pass == (arch == "qwen3_8b")
    assert stats["prefill_weight_passes"] == (len(chunks) if one_pass
                                              else sum(chunks))
    if speculative:
        assert stats["draft_weight_passes"] == (
            stats["spec_ticks"] * (eng.k + 1) + len(chunks))
        assert stats["weight_passes"] == (
            stats["spec_ticks"] * (eng.k + 1)
            + stats["prefill_weight_passes"])
        assert check_byte_parity(stats, "draft_") == []
    else:
        assert stats["weight_passes"] == (stats["decode_calls"]
                                          + stats["prefill_weight_passes"])
    assert check_byte_parity(stats) == []


def test_gc_collect_yields_one_span():
    tracer = Tracer()
    tracer.watch_gc()
    tracer.watch_gc()                      # one hook per tracer
    gc.disable()
    try:
        before = (len(tracer.events(GC_SPAN)), tracer.gc_collections,
                  tracer.gc_pause_ns)
        gc.collect()
        recs = tracer.events(GC_SPAN)
    finally:
        gc.enable()
    assert len(recs) == before[0] + 1
    assert recs[-1]["kind"] == "span"
    assert recs[-1]["attrs"]["generation"] == 2
    assert recs[-1]["dur_s"] >= 0
    assert tracer.gc_collections == before[1] + 1
    assert tracer.gc_pause_ns > before[2]


# -- snapshot purity (satellite: callable mid-run without mutation) -----------

def test_midrun_snapshot_does_not_perturb_outputs():
    def run(snapshot_every_step):
        eng = SpeculativeEngine(
            _tiny_cfg(), max_seq_len=64, max_slots=2, k=2,
            pack_weights=True, paged=True, kv_page_size=8,
            sample_seed=7)
        cfg = eng.cfg
        rng = np.random.default_rng(3)
        rids = [eng.submit(list(rng.integers(1, cfg.vocab_size, 4)),
                           max_new_tokens=4) for _ in range(4)]
        while eng._queue or eng._active:
            eng.step()
            if snapshot_every_step:
                eng.metrics_snapshot()
        return [eng.result(r) for r in rids]

    assert run(True) == run(False)


# -- byte accounting (the paper's saving as a live counter) -------------------

def test_byte_parity_fused_vs_analytic_model():
    k = 2
    eng = SpeculativeEngine(_tiny_cfg(), max_seq_len=64, max_slots=2,
                            k=k, pack_weights=True)
    _, stats = _drain_engine(eng)
    assert check_byte_parity(stats) == []
    assert check_byte_parity(stats, "draft_") == []
    # a verify streams the target's weights k+1 times; the draft mirrors
    # every prefill chunk at the same scan width
    for prefix in ("", "draft_"):
        assert stats[f"{prefix}weight_passes"] == (
            stats["spec_ticks"] * (k + 1) + stats["prefill_weight_passes"])
    # and the tolerance is doing work: the counters are real bytes,
    # the model has no lane-tile padding, so they differ but < 1%
    want = stats["weight_passes"] * stats["fused_analytic_bytes_per_pass"]
    got = stats["weight_read_bytes_fused"]
    assert got >= want
    assert abs(got - want) / want <= BYTE_TOLERANCE


def test_dense_engine_has_zero_fused_bytes():
    eng = ServeEngine(_tiny_cfg(), max_seq_len=64, max_slots=2)
    _, stats = _drain_engine(eng)
    assert stats["weight_read_bytes_fused"] == 0
    assert stats["fused_analytic_bytes_per_pass"] == 0
    assert stats["weight_read_bytes_dense"] > 0
    assert check_byte_parity(stats) == []


def test_byte_ratio_matches_packed_path_artifact():
    """Counter-vs-artifact parity: the engine's live fused/f32 per-pass
    ratio must agree with BENCH_packed_path.json's bytes_ratio_vs_f32
    for the same config — both are bits/32 plus group padding."""
    art_path = "BENCH_packed_path.json"
    if not os.path.exists(art_path):
        pytest.skip("BENCH_packed_path.json not present (run benchmarks)")
    with open(art_path) as f:
        art = json.load(f)
    by_cfg = {c["config"]: c for c in art.get("configs", [])}
    if "qwen3_8b" not in by_cfg:
        pytest.skip("artifact lacks qwen3_8b row")
    eng = ServeEngine(_tiny_cfg("qwen3_8b"), max_seq_len=64, max_slots=2,
                      pack_weights=True)
    snap = eng.metrics_snapshot()
    ratio = (snap["fused_bytes_per_pass"]
             / snap["fused_f32_bytes_per_pass"])
    assert ratio == pytest.approx(
        by_cfg["qwen3_8b"]["bytes_ratio_vs_f32"], abs=0.02)


# -- pool / retune / dispatch telemetry ---------------------------------------

def test_pool_event_counters_balance_at_drain():
    eng = ServeEngine(_tiny_cfg(), max_seq_len=64, max_slots=2,
                      paged=True, kv_page_size=8)
    _, stats = _drain_engine(eng, n_requests=6)
    assert stats["pool_alloc_total"] > 0
    # every alloc/retain share is freed once the queue drains
    assert stats["pool_free_total"] == (
        stats["pool_alloc_total"] + stats["pool_retain_total"])
    assert stats["pool_reserve_total"] >= stats["pool_release_total"]
    assert stats["pool_pages_used"] == 0
    assert stats["table_uploads"] > 0
    assert stats["table_upload_bytes"] > 0


def test_retune_events_surface_through_tracer():
    tracer = Tracer()
    eng = SpeculativeEngine(
        _tiny_cfg("stablelm_12b"), max_seq_len=64, max_slots=2, k=2,
        pack_weights=True, adaptive=True, tracer=tracer)
    eng.controller.min_proposals = 4     # retune quickly in a short run
    _, stats = _drain_engine(eng, n_requests=4, max_new=8)
    if not stats["retunes"]:
        pytest.skip("no retune fired in this short run")
    recs = tracer.events("serve.retune")
    assert len(recs) == stats["retunes"]
    for rec, ev in zip(recs, stats["retune_events"]):
        assert rec["attrs"] == ev
        assert {"tick", "action", "from_bits", "to_bits", "from_k",
                "to_k", "ewma"} <= set(rec["attrs"])


def test_kernel_dispatch_counters_record_paths():
    import jax.numpy as jnp
    from repro.kernels import ops as kops
    base = obs.REGISTRY.counter("kernel_dispatch_total")
    before = base.value(op="packed_matmul", path="fused")
    x = jnp.ones((2, 32), jnp.float32)
    w = jnp.ones((32, 16), jnp.float32)
    from repro.core.tensor_store import pack_tensor
    pt = pack_tensor(np.asarray(w), 8)
    kops.packed_matmul(x, jnp.asarray(pt.data), 8, 16)
    assert base.value(op="packed_matmul", path="fused") == before + 1
    pb = obs.REGISTRY.counter("kernel_dispatch_packed_bytes")
    assert pb.value(op="packed_matmul", path="fused") > 0


# -- JSONL stream validation (the acceptance-criterion path) ------------------

def test_metrics_jsonl_stream_validates_end_to_end(tmp_path):
    path = str(tmp_path / "serve.jsonl")
    tracer = Tracer(sink=path)
    eng = SpeculativeEngine(
        _tiny_cfg(), max_seq_len=64, max_slots=2, k=2,
        pack_weights=True, paged=True, kv_page_size=8,
        tracer=tracer, metrics_interval=2)
    _, stats = _drain_engine(eng)
    tracer.close()
    counts, errors = validate_metrics_jsonl(path)
    assert errors == []
    assert counts["records"] > 0
    assert counts["metrics_events"] >= 2     # periodic + final
    assert counts["spans"] > 0
    # the final serve.metrics event is the drain snapshot
    final = [r for r in obs.read_jsonl(path)
             if r["name"] == "serve.metrics"][-1]
    assert final["attrs"]["ticks"] == stats["ticks"]
    assert final["attrs"]["weight_read_bytes_fused"] == \
        stats["weight_read_bytes_fused"]


def test_validator_rejects_empty_and_malformed(tmp_path):
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    _, errors = validate_metrics_jsonl(str(empty))
    assert errors and "empty" in errors[0]

    bad = tmp_path / "bad.jsonl"
    bad.write_text("this is not json\n")
    _, errors = validate_metrics_jsonl(str(bad))
    assert any("malformed" in e for e in errors)

    no_metrics = tmp_path / "nm.jsonl"
    no_metrics.write_text(json.dumps(
        {"kind": "event", "name": "serve.admit", "ts": 0.0,
         "attrs": {}}) + "\n")
    _, errors = validate_metrics_jsonl(str(no_metrics))
    assert any("no serve.metrics" in e for e in errors)


def test_train_stream_validates(tmp_path):
    from repro.train import Trainer, TrainConfig
    path = str(tmp_path / "train.jsonl")
    tc = TrainConfig(steps=3, seq_len=32, global_batch=2,
                     pack_params=True, repack_every=2, log_every=2,
                     metrics_out=path)
    metrics = Trainer(_tiny_cfg(), tc).run()
    counts, errors = validate_metrics_jsonl(path)
    assert errors == []
    assert counts["metrics_events"] == 1
    assert TRAIN_FINAL_KEYS <= set(metrics)
    assert metrics["weight_passes"] == 2 * metrics["steps_completed"]
    assert metrics["repacks"] == 1       # steps 0..2: repack after step 1
    assert check_byte_parity(metrics) == []
    steps = [r for r in obs.read_jsonl(path) if r["name"] == "train.step"]
    assert [s["attrs"]["step"] for s in steps] == [0, 1, 2]
    assert all(math.isfinite(s["attrs"]["loss"]) for s in steps)
