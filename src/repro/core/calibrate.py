"""Model calibration: the static-analysis flow aimed at a whole LM.

This is the deployment-side closing of the paper's loop (Fig. 7, lifted
from one traced kernel to a model): run N sample batches through the
model and collect per-leaf evidence —

* **integer streams** get exact widths from the jaxpr range analysis,
  seeded by ``ModelConfig`` bounds via ``range_analysis.input_specs``
  (token ids < vocab, positions < max_seq_len, expert ids < n_experts) —
  the launch-knowledge metadata of Section 4.2, derived rather than
  asserted;
* **float parameter leaves** get the largest-footprint-first fixpoint
  search of ``precision_tuning.tune_tensors`` (Section 4.1, Angerd et
  al. 2017) at tensor granularity, acceptance gated by a ``QualitySpec``
  (typically ``loss_delta``: max |Δloss| in nats over the calibration
  batches).

The output is a per-leaf mixed-width ``CompressionPlan`` that serving
(``launch/serve.py --calibrate`` / ``--plan``), packed-master training
(``TrainConfig.plan_path``), and draft derivation (``derive_plan``) all
consume — every width in the system becomes an analysis output instead
of a CLI constant. Integer widths live under ``inputs/...`` keys: they
describe the token/position/routing streams, never parameter leaves, so
``repack`` over the plan leaves params untouched while the widths still
round-trip through the JSON codec and the bytes accounting.

Quality is only guaranteed for inputs resembling the calibration batches
— the paper says the same of its tuning samples.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.compress import CompressionPlan, path_str, uniform_plan
from repro.core.formats import FLOAT_LADDER
from repro.core.precision_tuning import quantize_dequantize, tune_tensors
from repro.core.quality import QualitySpec
from repro.core.range_analysis import analyze, input_specs


def derive_int_bits(cfg, max_seq_len: int) -> Dict[str, Tuple[int, bool]]:
    """Exact integer widths for the model's input streams, *derived* by
    running the interval analysis over a traced stream function seeded
    with ``input_specs(cfg, max_seq_len)``. Keys are ``inputs/<name>``
    so they can never collide with parameter paths."""
    specs = input_specs(cfg, max_seq_len)
    names = list(specs)
    examples = [jnp.zeros((4,), jnp.int32) for _ in names]
    ranges = [specs[n] for n in names]

    def stream(*vals):
        env = dict(zip(names, vals))
        outs = []
        for n in names:
            v = env[n]
            if n == "positions":
                # the decode-step successor position, clamped in-bounds —
                # exercises the add/min transfer instead of identity
                v = jnp.minimum(v + 1, max_seq_len - 1)
            outs.append(v)
        return tuple(outs)

    report = analyze(stream, *examples, input_ranges=ranges)
    out: Dict[str, Tuple[int, bool]] = {}
    for n, itv in zip(names, report.out_intervals):
        b = itv.bits()
        if b:
            out["inputs/" + n] = b
    return out


def _extra_inputs(cfg, batch_size: int) -> Dict[str, jnp.ndarray]:
    """Family-specific zero riders the LM batch dict expects."""
    extra: Dict[str, jnp.ndarray] = {}
    if cfg.family == "vlm":
        extra["patch_embeds"] = jnp.zeros(
            (batch_size, cfg.num_image_tokens, cfg.d_model), jnp.float32)
    if cfg.family == "encdec":
        extra["frames"] = jnp.zeros(
            (batch_size, cfg.encoder_seq, cfg.d_model), jnp.float32)
    return extra


def float_leaves(
    params: Any, min_ndim: int = 2
) -> Dict[str, jnp.ndarray]:
    """The tunable float tensors of a param tree, keyed by ``path_str``
    (the same keys ``uniform_plan`` / ``repack`` use)."""
    tensors: Dict[str, jnp.ndarray] = {}

    def visit(path, leaf):
        if (jnp.issubdtype(leaf.dtype, jnp.floating)
                and getattr(leaf, "ndim", 0) >= min_ndim):
            tensors[path_str(path)] = leaf

    jax.tree_util.tree_map_with_path(visit, params)
    return tensors


@dataclasses.dataclass
class CalibrationResult:
    """A tuned plan plus the evidence that justifies it."""

    cfg_name: str
    plan: CompressionPlan
    quality: QualitySpec
    ref_loss: float               # mean reference loss over the batches
    metric: float                 # achieved quality metric at the plan
    uniform_metric: float         # same metric for the uniform baseline
    mean_float_bits: float        # size-weighted, 32s included
    uniform_bits: int             # the width the plan competes against
    footprint_ratio: float        # plan bytes / f32 bytes (float leaves)
    uniform_ratio: float          # uniform-plan bytes / f32 bytes
    tune_evals: int
    n_batches: int
    batch_size: int
    seq_len: int

    @property
    def accepted(self) -> bool:
        """The tuned plan sits inside the quality gate."""
        th = self.quality.threshold
        if self.quality.kind == "ssim":
            return self.metric >= th - 1e-6
        return self.metric <= th + 1e-9

    @property
    def beats_uniform(self) -> bool:
        """Strictly narrower mean float width than the uniform plan."""
        return self.mean_float_bits < self.uniform_bits

    def summary(self) -> Dict[str, Any]:
        return {
            "config": self.cfg_name,
            "quality_kind": self.quality.kind,
            "quality_threshold": self.quality.threshold,
            "ref_loss": self.ref_loss,
            "metric": self.metric,
            "uniform_metric": self.uniform_metric,
            "mean_float_bits": self.mean_float_bits,
            "uniform_bits": self.uniform_bits,
            "footprint_ratio": self.footprint_ratio,
            "uniform_ratio": self.uniform_ratio,
            "tune_evals": self.tune_evals,
            "n_float_leaves": len(self.plan.float_bits),
            "n_int_streams": len(self.plan.int_bits),
            "accepted": self.accepted,
            "beats_uniform": self.beats_uniform,
        }


def calibrate(
    cfg,
    quality: QualitySpec,
    *,
    n_batches: int = 2,
    batch_size: int = 2,
    seq_len: int = 16,
    seed: int = 0,
    params: Optional[Any] = None,
    ladder: Sequence[int] = FLOAT_LADDER,
    min_ndim: int = 2,
    max_seq_len: Optional[int] = None,
) -> CalibrationResult:
    """Run the calibration pass on one ``ModelConfig``.

    Floats: each ``ndim >= min_ndim`` float leaf is a tuning group; the
    search quantizes candidates through the Table 3 ladder and judges
    the *stacked per-batch losses* against the reference run via
    ``quality``. Ints: widths from ``derive_int_bits``. ``params=None``
    initializes fresh parameters from ``seed`` (what the tuner sees is
    what serving packs, as long as the caller passes the same params it
    will deploy — pass the checkpoint's params for a trained model)."""
    from repro.compat import jit, prng_key
    from repro.data import SyntheticTokens
    from repro.models.lm import LM

    lm = LM(cfg)
    if params is None:
        params = lm.init(prng_key(seed))
    data = SyntheticTokens(
        vocab_size=cfg.vocab_size, seq_len=seq_len,
        global_batch=batch_size, seed=seed,
    )
    extra = _extra_inputs(cfg, batch_size)
    batches = [data.batch_at(i).as_dict(dict(extra))
               for i in range(n_batches)]

    tensors = float_leaves(params, min_ndim)
    sizes = {k: int(np.prod(np.asarray(v).shape or (1,)))
             for k, v in tensors.items()}
    loss_fn = jit(lm.loss)

    def apply_fn(quantized: Dict[str, jnp.ndarray]) -> jnp.ndarray:
        def splice(path, leaf):
            return quantized.get(path_str(path), leaf)
        spliced = jax.tree_util.tree_map_with_path(splice, params)
        return jnp.stack([loss_fn(spliced, b) for b in batches])

    ref = apply_fn(tensors)
    tuned = tune_tensors(apply_fn, tensors, quality, ladder, reference=ref)

    wbits = cfg.resolved_weight_bits
    plan = CompressionPlan(
        float_bits={k: b for k, b in tuned.formats.items() if b < 32},
        int_bits=derive_int_bits(cfg, max_seq_len or seq_len),
        tune_evals=tuned.evaluations,
    )

    def metric_at(widths: Dict[str, int]) -> float:
        q = {k: quantize_dequantize(v, widths.get(k, 32))
             for k, v in tensors.items()}
        return quality.metric(ref, apply_fn(q))

    return CalibrationResult(
        cfg_name=cfg.name,
        plan=plan,
        quality=quality,
        ref_loss=float(jnp.mean(ref)),
        metric=metric_at(tuned.formats),
        uniform_metric=metric_at({k: wbits for k in tensors}),
        mean_float_bits=tuned.mean_bits(sizes),
        uniform_bits=wbits,
        footprint_ratio=plan.footprint_ratio(tensors),
        uniform_ratio=uniform_plan(
            params, wbits, min_ndim).footprint_ratio(tensors),
        tune_evals=tuned.evaluations,
        n_batches=n_batches,
        batch_size=batch_size,
        seq_len=seq_len,
    )
