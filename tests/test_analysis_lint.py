"""The whole-program packed-path auditor (``repro.analysis``).

Covers the four passes end-to-end at smoke scale: activation-width
inference (per-layer KV widths from proven float bounds), the dispatch
lint (clean on the real entry points, failing on a seeded unfused
dispatch), plan soundness against the broken fixture, the
sharding/donation lints, the CLI exit-code contract, the report schema
validator, and the acceptance criterion — statically inferred per-layer
KV widths loading through ``ServeEngine(plan=)`` bitwise-identically to
the constant-``kv_bits`` baseline at equal widths.
"""
import dataclasses
import json
import math
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

sys.path.insert(0, os.path.dirname(__file__))
from _hypothesis_compat import given, settings, st  # noqa: E402

from repro import compat, obs
from repro.analysis.activations import (
    FloatRangeAnalysis,
    infer_kv_widths,
    width_for_bound,
)
from repro.analysis.dispatch import lint_dispatch
from repro.analysis.lint import main as lint_main
from repro.analysis.lint import run_lint
from repro.analysis.report import Finding, LintReport
from repro.analysis.sharding_lint import (
    donation_hazards,
    lint_donation,
    lint_sharding,
)
from repro.analysis.soundness import lint_plan
from repro.configs import get_config
from repro.core.compress import CompressionPlan
from repro.core.formats import FLOAT_FORMATS, FLOAT_LADDER
from repro.core.range_analysis import Interval, analyze
from repro.obs.schema import validate_lint_report

FIXTURE = os.path.join(os.path.dirname(__file__), "fixtures",
                       "broken_plan.json")


@pytest.fixture(scope="module")
def dense_cfg():
    return get_config("qwen3_8b").reduced()


@pytest.fixture(scope="module")
def dense_params(dense_cfg):
    from repro.models.lm import LM
    return LM(dense_cfg).init(compat.prng_key(0))


# ---------------------------------------------------------------- pass 1

def test_infer_kv_widths_dense(dense_cfg, dense_params):
    kv_bits, kv_bounds, findings = infer_kv_widths(
        dense_cfg, params=dense_params)
    assert set(kv_bits) == {f"kv/layer_{i}"
                            for i in range(dense_cfg.n_kv_layers)}
    for key, bits in kv_bits.items():
        assert bits in FLOAT_FORMATS
        # the width must actually clear the proven bound
        assert FLOAT_FORMATS[bits].max_finite >= kv_bounds[key]
        # floored at the config width by default
        assert bits >= dense_cfg.resolved_kv_bits
    assert all(math.isfinite(b) for b in kv_bounds.values())
    assert not [f for f in findings if f.severity == "error"]


def test_infer_kv_widths_ssm_out_of_domain():
    cfg = get_config("falcon_mamba_7b").reduced()
    kv_bits, kv_bounds, findings = infer_kv_widths(cfg)
    assert kv_bits == {} and kv_bounds == {}
    assert any("outside the per-layer KV width domain" in f.message
               for f in findings)


def test_width_for_bound_ladder():
    assert width_for_bound(float("inf")) == 32
    assert width_for_bound(float("nan")) == 32
    # AF8 max_finite is ~15.5: a tiny bound fits the narrowest rung
    assert width_for_bound(1.0) == FLOAT_LADDER[0]
    # the floor is honored even when the bound would fit narrower
    assert width_for_bound(1.0, floor_bits=16) == 16
    # monotone: wider bounds never map to narrower formats
    widths = [width_for_bound(b) for b in (1.0, 1e2, 1e4, 1e8, 1e30)]
    assert widths == sorted(widths)
    for b in (1.0, 255.0, 6e4, 1e10):
        w = width_for_bound(b)
        if w in FLOAT_FORMATS:
            assert FLOAT_FORMATS[w].max_finite >= b


# ------------------------------------- float interval transfer properties

def _out_interval(fn, args, ranges):
    """Run FloatRangeAnalysis over fn's jaxpr with seeded input ranges."""
    closed = jax.make_jaxpr(fn)(*args)
    ra = FloatRangeAnalysis()
    for v, itv in zip(closed.jaxpr.invars, ranges):
        ra._write(v, itv)
    for v in closed.jaxpr.constvars:
        ra._write(v, Interval.top())
    for eqn in closed.jaxpr.eqns:
        ra._transfer(eqn)
    return ra._read(closed.jaxpr.outvars[0])


@settings(max_examples=25)
@given(st.floats(min_value=-50.0, max_value=50.0, allow_nan=False,
                 allow_infinity=False),
       st.floats(min_value=0.0, max_value=50.0, allow_nan=False,
                 allow_infinity=False))
def test_float_transfer_soundness(center, radius):
    """The abstract output contains every concrete output for inputs
    drawn inside the seeded interval (transcendental + matmul chain)."""
    lo, hi = center - radius, center + radius

    def f(x, w):
        h = jnp.tanh(x) @ w
        return h + jnp.sqrt(jnp.abs(h) + 1.0)

    x = jnp.zeros((2, 4), jnp.float32)
    w = jnp.zeros((4, 3), jnp.float32)
    itv = _out_interval(f, (x, w), [Interval(lo, hi),
                                    Interval(-2.0, 2.0)])
    rng = np.random.default_rng(0)
    xs = rng.uniform(lo, hi, (2, 4)).astype(np.float32)
    ws = rng.uniform(-2.0, 2.0, (4, 3)).astype(np.float32)
    out = np.asarray(f(jnp.asarray(xs), jnp.asarray(ws)))
    assert itv.lo <= float(out.min()) + 1e-5
    assert itv.hi >= float(out.max()) - 1e-5


@settings(max_examples=20)
@given(st.integers(min_value=1, max_value=40),
       st.floats(min_value=0.0, max_value=4.0, allow_nan=False,
                 allow_infinity=False))
def test_scan_widening_converges(n_steps, mag):
    """A growing scan carry must reach a sound fixpoint (possibly top)
    in bounded iterations — widening is what guarantees termination."""
    def f(x):
        def body(c, _):
            return c + x, c
        c, _ = jax.lax.scan(body, x, None, length=n_steps)
        return c

    itv = _out_interval(f, (jnp.float32(0.0),),
                        [Interval(-mag, mag)])
    # sound: the true output is n_steps+1 copies of x summed
    true_hi = (n_steps + 1) * mag
    assert itv.hi >= true_hi - 1e-6
    assert itv.lo <= -true_hi + 1e-6


@settings(max_examples=20)
@given(st.floats(min_value=0.5, max_value=30.0, allow_nan=False,
                 allow_infinity=False))
def test_while_widening_converges(mag):
    """A monotone while-loop accumulator widens to a sound (here: top-
    side unbounded) interval instead of looping forever."""
    def f(x):
        def cond(c):
            return c[0] < 100.0
        def body(c):
            return (c[0] + x,)
        return jax.lax.while_loop(cond, body, (x,))[0]

    itv = _out_interval(f, (jnp.float32(1.0),), [Interval(0.5, mag)])
    # the loop adds x until >= 100: any sound bound must cover 100+mag
    assert itv.hi >= 100.0 or math.isinf(itv.hi)
    assert itv.lo <= 0.5 + 1e-6


@settings(max_examples=20)
@given(st.floats(min_value=-20.0, max_value=20.0, allow_nan=False,
                 allow_infinity=False),
       st.floats(min_value=0.0, max_value=20.0, allow_nan=False,
                 allow_infinity=False))
def test_cond_union(center, radius)  :
    """A lax.cond output is the union of its branch intervals."""
    lo, hi = center - radius, center + radius

    def f(p, x):
        return jax.lax.cond(p, lambda v: v * 2.0,
                            lambda v: v - 100.0, x)

    itv = _out_interval(f, (jnp.bool_(True), jnp.float32(0.0)),
                        [Interval(0, 1), Interval(lo, hi)])
    # branch 1: [2lo, 2hi] (sign-dependent corners); branch 2: shift
    b1 = [2 * lo, 2 * hi]
    assert itv.lo <= min(min(b1), lo - 100.0) + 1e-6
    assert itv.hi >= max(max(b1), hi - 100.0) - 1e-6


def test_interval_edges():
    with pytest.raises(ValueError):
        Interval(1.0, 0.0)        # empty interval is a construction error
    top = Interval.top()
    assert math.isinf(top.lo) and math.isinf(top.hi)
    # exp through the float transfer: top -> [0, inf], never negative
    itv = _out_interval(lambda x: jnp.exp(x), (jnp.float32(0.0),), [top])
    assert itv.lo >= 0.0 and math.isinf(itv.hi)
    # rsqrt of a zero-crossing interval makes no claim (top), not a crash
    itv = _out_interval(lambda x: jax.lax.rsqrt(x), (jnp.float32(1.0),),
                        [Interval(-1.0, 1.0)])
    assert math.isinf(itv.hi)
    # division by a zero-crossing interval is top
    itv = _out_interval(lambda x: 1.0 / x, (jnp.float32(1.0),),
                        [Interval(-1.0, 1.0)])
    assert math.isinf(itv.lo) and math.isinf(itv.hi)


# ---------------------------------------------------------------- pass 2

def test_dispatch_lint_clean(dense_cfg, dense_params):
    findings, traced = lint_dispatch(dense_cfg, params=dense_params)
    assert set(traced) == {"decode_step", "paged_decode_step",
                           "prefill_step", "verify_step", "train_loss"}
    assert not [f for f in findings if f.severity == "error"]


def test_dispatch_lint_one_pass_prefill_paged(dense_cfg, monkeypatch):
    """The one-pass prefill_step over a paged state, traced with a packed
    plan in place of the linter's own entry points: no fallback, no
    planned leaf unpacked wholesale, and a fused proof for every planned
    leaf. Prefill has no head, so the config ties its embeddings: every
    planned leaf is then one the prefill reads."""
    import repro.analysis.dispatch as dispatch
    from repro.models.lm import LM

    cfg = dataclasses.replace(dense_cfg, tie_embeddings=True)
    lm = LM(cfg)
    assert lm.one_pass_prefill

    def trace_paged_prefill(cfg_, packed, masters, batch_size=1,
                            seq_len=32):
        state = lm.init_paged_decode_state(batch_size, seq_len, 8,
                                           batch_size * 4, abstract=True)
        jax.make_jaxpr(lm.prefill_step)(
            packed, state, jnp.zeros((batch_size, 4), jnp.int32),
            jnp.full((batch_size,), 4, jnp.int32))
        return ["paged_prefill_step"], []

    monkeypatch.setattr(dispatch, "trace_entry_points", trace_paged_prefill)
    findings, traced = lint_dispatch(cfg,
                                     params=lm.init(compat.prng_key(0)))
    assert traced == ["paged_prefill_step"]
    assert not [f for f in findings if f.severity != "info"], findings
    assert any("proven fused" in f.message for f in findings)


def test_dispatch_lint_catches_seeded_fallback(dense_cfg, dense_params):
    from repro.analysis.lint import _inject_fallback
    findings, _ = lint_dispatch(
        dense_cfg, params=dense_params,
        extra_trace=lambda: _inject_fallback(dense_cfg, dense_params))
    errs = [f for f in findings if f.severity == "error"]
    assert errs, "seeded unfused dispatch must produce an error finding"
    assert any("fell off the fused path" in f.message for f in errs)
    # the finding names the offending spec and candidate leaves
    assert any(f.detail.get("spec") for f in errs)


def test_fallback_records_are_structured(dense_cfg, dense_params):
    """models/layers records leaf shape + normalized spec + width +
    reason on every unrecognized-spec dispatch (satellite a)."""
    from repro.core.compress import repack, uniform_plan
    from repro.kernels import ops as kops
    from repro.models import layers as L

    packed = repack(dense_params,
                    uniform_plan(dense_params,
                                 dense_cfg.resolved_weight_bits))
    w = jax.tree_util.tree_map(lambda a: a[0],
                               packed["blocks"]["attn"]["wq"])
    before = len(kops.FALLBACK_RECORDS)
    counter = obs.REGISTRY.counter(
        "kernel_fallback_total", "Packed operands that fell off the "
        "fused path (trace-time).")
    c_before = counter.value(op="linear", reason="unrecognized_spec")
    jax.make_jaxpr(lambda x: L.linear(x, w, spec="...b, ab -> ...a"))(
        jnp.zeros((1, w.logical_shape[0]), jnp.float32))
    recs = list(kops.FALLBACK_RECORDS)[before:]
    assert len(recs) == 1
    rec = recs[0]
    assert rec.op == "linear"
    assert rec.spec == "...b,ab->...a"         # whitespace-normalized
    assert tuple(rec.shape) == tuple(w.logical_shape)
    assert rec.bits == dense_cfg.resolved_weight_bits
    assert rec.reason == "unrecognized_spec"
    assert counter.value(op="linear",
                         reason="unrecognized_spec") == c_before + 1


# ---------------------------------------------------------------- pass 3

def test_plan_soundness_broken_fixture(dense_cfg, dense_params):
    plan = CompressionPlan.load(FIXTURE)
    findings = lint_plan(dense_cfg, plan, params=dense_params,
                         max_seq_len=64)
    errs = {f.path: f for f in findings if f.severity == "error"}
    assert "inputs/tokens" in errs           # 4 bits vs proven 9
    assert "silent clipping" in errs["inputs/tokens"].message
    assert "embed" in errs                   # 13 bits is off-ladder
    assert "kv/layer_0" in errs              # off-ladder KV width
    assert "kv/layer_99" in errs             # out-of-range layer


def test_plan_soundness_clean_default(dense_cfg, dense_params):
    from repro.core.compress import uniform_plan
    plan = uniform_plan(dense_params, dense_cfg.resolved_weight_bits)
    findings = lint_plan(dense_cfg, plan, params=dense_params,
                         max_seq_len=64)
    assert not [f for f in findings if f.severity == "error"]


def test_plan_soundness_kv_overflow(dense_cfg, dense_params):
    plan = CompressionPlan(float_bits={}, int_bits={},
                           kv_bits={"kv/layer_0": 8})
    findings = lint_plan(dense_cfg, plan, params=dense_params,
                         max_seq_len=64,
                         kv_bounds={"kv/layer_0": 1000.0})
    errs = [f for f in findings if f.severity == "error"]
    assert any("KV overflow" in f.message for f in errs)


# ---------------------------------------------------------------- pass 4

def test_sharding_lint_clean(dense_cfg, dense_params):
    findings = lint_sharding(dense_cfg, params=dense_params)
    assert not [f for f in findings if f.severity == "error"]


def test_donation_lint_clean(dense_cfg, dense_params):
    findings = lint_donation(dense_cfg, params=dense_params)
    assert not [f for f in findings if f.severity == "warning"]


def test_donation_hazard_detected():
    """A hand-built read-after-overwrite is flagged by the jaxpr walk."""
    def f(buf, upd):
        b2 = jax.lax.dynamic_update_slice(buf, upd, (0,))
        return b2 + buf[0]                   # reads buf after overwrite

    closed = jax.make_jaxpr(f)(jnp.zeros((4,), jnp.float32),
                               jnp.ones((1,), jnp.float32))
    donated = {closed.jaxpr.invars[0]: "state/buf"}
    hazards = donation_hazards(closed.jaxpr, donated)
    assert "state/buf" in hazards
    w_idx, r_idx, _ = hazards["state/buf"]
    assert w_idx < r_idx


# ---------------------------------------------------- report + CLI + CI

def test_report_schema_roundtrip(tmp_path):
    rep = LintReport(arch="x", passes=["dispatch"])
    rep.extend([Finding(check="dispatch", severity="info", message="ok"),
                Finding(check="dispatch", severity="error", message="bad",
                        path="embed")])
    p = str(tmp_path / "report.json")
    rep.save(p)
    counts, errors = validate_lint_report(p)
    assert errors == []
    assert counts == {"findings": 2, "errors": 1, "warnings": 0,
                      "infos": 1}
    obj = json.load(open(p))
    assert obj["clean"] is False
    assert obj["counters"] == {"dispatch/info": 1, "dispatch/error": 1}


def test_report_validator_catches_inconsistency(tmp_path):
    rep = LintReport(arch="x", passes=["dispatch"])
    rep.extend([Finding(check="dispatch", severity="error", message="b")])
    obj = rep.to_jsonable()
    obj["clean"] = True                      # lie about the verdict
    p = str(tmp_path / "bad.json")
    json.dump(obj, open(p, "w"))
    _, errors = validate_lint_report(p)
    assert any("clean=True" in e for e in errors)


def test_report_mirrors_obs_counters():
    counter = obs.REGISTRY.counter(
        "lint_findings_total",
        "Static-analysis lint findings by check and severity.")
    before = counter.value(check="dispatch", severity="error")
    rep = LintReport(arch="x")
    rep.extend([Finding(check="dispatch", severity="error", message="b")])
    rep.mirror_to_obs()
    assert counter.value(check="dispatch",
                         severity="error") == before + 1


def test_finding_rejects_unknown_severity():
    with pytest.raises(ValueError):
        Finding(check="x", severity="fatal", message="no such level")


def test_cli_clean_and_emits_kv_plan(tmp_path):
    out = str(tmp_path / "report.json")
    kv_out = str(tmp_path / "kv_plan.json")
    rc = lint_main(["--arch", "qwen3_8b", "--reduced", "--out", out,
                    "--emit-kv-plan", kv_out])
    assert rc == 0
    _, errors = validate_lint_report(out)
    assert errors == []
    plan = CompressionPlan.load(kv_out)
    cfg = get_config("qwen3_8b").reduced()
    assert set(plan.kv_bits) == {f"kv/layer_{i}"
                                 for i in range(cfg.n_kv_layers)}


def test_cli_broken_plan_fails():
    rc = lint_main(["--arch", "qwen3_8b", "--reduced",
                    "--plan", FIXTURE])
    assert rc == 1


def test_cli_injected_fallback_fails():
    rc = lint_main(["--arch", "qwen3_8b", "--reduced",
                    "--inject-fallback"])
    assert rc == 1


# ------------------------------------------- acceptance: plan -> serving

def test_inferred_kv_plan_serves_bitwise_identical(dense_cfg):
    """Statically inferred per-layer KV widths load through
    ``ServeEngine(plan=)``; at equal widths the traced program is the
    legacy one, so greedy outputs are bitwise-identical to the
    constant-``kv_bits`` baseline."""
    from repro.serving import ServeEngine

    report = run_lint(dense_cfg, "qwen3_8b")
    assert report.clean
    plan = CompressionPlan(float_bits={}, int_bits={},
                           kv_bits=dict(report.kv_bits))
    prompts = [[3, 5, 7], [11, 13], [17, 19, 23, 29]]

    base = ServeEngine(dense_cfg, max_seq_len=32, max_slots=2)
    rids = [base.submit(p, max_new_tokens=4) for p in prompts]
    base.run_until_drained()
    want = [base.result(r) for r in rids]

    eng = ServeEngine(dense_cfg, max_seq_len=32, max_slots=2, plan=plan)
    rids = [eng.submit(p, max_new_tokens=4) for p in prompts]
    eng.run_until_drained()
    got = [eng.result(r) for r in rids]
    # the inferred widths equal the config width at smoke scale, so
    # this is the bitwise-identity leg (not merely closeness)
    assert all(b == dense_cfg.resolved_kv_bits
               for b in plan.kv_bits.values())
    assert got == want
