"""Whole-program static analysis over traced jaxprs — the paper's
compiler flow (Section 4) promoted from a calibration-time tool to a
correctness gate.

Four passes over the real entry points (``LM.decode_step`` /
``prefill_step`` / ``verify_step``, the packed-master train body):

1. **activation range/precision inference** (``activations``): float
   magnitude bounds through the transformer body -> per-layer KV-cache
   widths, emitted as ``CompressionPlan.kv_bits`` entries;
2. **packed-dispatch lint** (``dispatch``): every planned float leaf
   must hit a fused kernel — fallbacks reported with spec/shape;
3. **plan-soundness verifier** (``soundness``): plan int widths vs.
   range-analysis proofs (silent-clipping detection), float widths vs.
   the Table 3 ladder and overflow thresholds;
4. **sharding/donation lints** (``sharding_lint``): the whole-tile
   packed-axis rule and donated-buffer read-after-overwrite.

CLI: ``python -m repro.analysis.lint --arch X [--plan plan.json]
[--out report.json]`` — nonzero exit on error findings; wired into
``scripts/ci.sh`` as a gate over the zoo configs.
"""
from repro.analysis.activations import (
    FloatRangeAnalysis,
    infer_kv_widths,
    width_for_bound,
)
from repro.analysis.dispatch import lint_dispatch
from repro.analysis.report import Finding, LintReport
from repro.analysis.sharding_lint import lint_donation, lint_sharding
from repro.analysis.soundness import lint_plan

__all__ = [
    "Finding",
    "FloatRangeAnalysis",
    "LintReport",
    "infer_kv_widths",
    "lint_dispatch",
    "lint_donation",
    "lint_plan",
    "lint_sharding",
    "width_for_bound",
]
