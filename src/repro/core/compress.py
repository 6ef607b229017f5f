"""End-to-end static compression flow (Fig. 7).

GPU granularity (the paper's evaluation pipeline, used by the Fig. 9/10/11
benchmark reproductions):

    trace kernel -> integer range analysis (Section 4.2)
                 -> float precision tuning vs. quality threshold (4.1)
                 -> liveness over the SSA program
                 -> slice allocation + indirection table (4.3)
    => register pressure before/after, occupancy, IPC model inputs.

Tensor granularity (the framework's deployment path):

    model + sample batch -> per-tensor precision tuning
                         -> integer width assignment from ranges
    => a CompressionPlan consumed by the packed store / optimizer / KV
       cache and by the serving residency planner.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.allocator import Allocation, Operand, SliceAllocator
from repro.core.formats import ladder_snap, round_bits_to_slice
from repro.core.precision_tuning import (
    QuantizedKernel,
    TuneResult,
    tune_kernel,
    tune_tensors,
)
from repro.core.quality import QualitySpec
from repro.core.range_analysis import Interval, RangeAnalysis, _is_int


# ---------------------------------------------------------------------------
# GPU granularity: per-SSA-value compression of a traced kernel
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class KernelCompression:
    """Everything Fig. 7 produces for one kernel."""

    name: str
    allocation: Allocation           # packed
    baseline_pressure: int           # 32-bit registers, liveness-aware
    packed_pressure: int
    float_formats: Dict[int, int]    # vid -> bits
    int_bits: Dict[str, Tuple[int, bool]]
    tune_evals: int
    operands: List[Operand] = dataclasses.field(default_factory=list)

    def repressure(self, use_ints: bool, use_floats: bool,
                   prefer_contiguous: bool = False) -> int:
        """Register pressure with only one side of the framework active
        (Fig. 9's isolated bars), liveness preserved."""
        ops = [
            dataclasses.replace(
                o,
                bits=o.bits if (o.is_float and use_floats)
                or ((not o.is_float) and use_ints) else 32,
            )
            for o in self.operands
        ]
        return SliceAllocator(
            prefer_contiguous=prefer_contiguous
        ).allocate(ops).register_pressure

    @property
    def pressure_reduction(self) -> float:
        return 1.0 - self.packed_pressure / max(self.baseline_pressure, 1)


def _liveness(jaxpr) -> Dict[Any, Tuple[int, int]]:
    """[def_point, last_use) for every var; inputs defined at -1."""
    from jax.extend import core as jcore

    def is_var(a) -> bool:
        return not isinstance(a, jcore.Literal)

    live: Dict[Any, Tuple[int, int]] = {}
    for i, v in enumerate(jaxpr.invars):
        live[v] = (0, 1)
    for v in jaxpr.constvars:
        live[v] = (0, 1)
    for t, eqn in enumerate(jaxpr.eqns, start=1):
        for v in eqn.outvars:
            live[v] = (t, t + 1)
        for a in eqn.invars:
            if is_var(a) and a in live:
                d, _ = live[a]
                live[a] = (d, t + 1)
    end = len(jaxpr.eqns) + 1
    for v in jaxpr.outvars:
        if is_var(v) and v in live:
            d, _ = live[v]
            live[v] = (d, end)
    return live


def compress_kernel(
    name: str,
    fn: Callable,
    samples: Sequence[Tuple],
    quality: QualitySpec,
    input_ranges: Optional[Sequence[Optional[Interval]]] = None,
    prefer_contiguous: bool = False,
) -> KernelCompression:
    """Run the full static framework on one traced kernel."""
    qk = QuantizedKernel(fn, *samples[0])
    jaxpr = qk.closed.jaxpr

    # 1. integer ranges (Section 4.2)
    ra = RangeAnalysis()
    ranges = list(input_ranges or [])
    for i, v in enumerate(jaxpr.invars):
        itv = ranges[i] if i < len(ranges) and ranges[i] else Interval.top()
        ra._write(v, itv)
    for v in jaxpr.constvars:
        ra._write(v, Interval.top())
    for eqn in jaxpr.eqns:
        ra._transfer(eqn)

    # 2. float precision tuning (Section 4.1)
    tuned = tune_kernel(qk, samples, quality)

    # 3. liveness + operands
    live = _liveness(jaxpr)
    operands: List[Operand] = []
    int_bits: Dict[str, Tuple[int, bool]] = {}
    vid_of = qk._var_vid
    idx = 0
    for var, (start, end) in live.items():
        aval = getattr(var, "aval", None)
        if aval is None or not hasattr(aval, "dtype"):
            continue
        oname = f"v{idx}"
        idx += 1
        if np.issubdtype(aval.dtype, np.floating):
            bits = tuned.formats.get(vid_of.get(var, -1), 32)
            operands.append(Operand(
                name=oname, bits=bits, is_float=True, signed=True,
                start=start, end=end,
            ))
        elif _is_int(aval) or np.issubdtype(aval.dtype, np.bool_):
            itv = ra.env.get(var, Interval.top())
            b = itv.bits()
            bits, signed = b if b else (32, True)
            bits = min(bits, 32)
            int_bits[oname] = (bits, signed)
            operands.append(Operand(
                name=oname, bits=bits, is_float=False, signed=signed,
                start=start, end=end,
            ))

    # 4. slice allocation (Section 4.3)
    alloc = SliceAllocator(prefer_contiguous=prefer_contiguous).allocate(
        operands
    )
    return KernelCompression(
        name=name,
        allocation=alloc,
        baseline_pressure=alloc.baseline_pressure,
        packed_pressure=alloc.register_pressure,
        float_formats=dict(tuned.formats),
        int_bits=int_bits,
        tune_evals=tuned.evaluations,
        operands=operands,
    )


# ---------------------------------------------------------------------------
# Tensor granularity: the framework deployment plan
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class CompressionPlan:
    """Per-tensor widths consumed by the packed store.

    ``float_bits``: leaf-path string -> Table 3 width.
    ``int_bits``:   leaf-path string -> (bits rounded to slices, signed).
    ``kv_bits``:    ``"kv/layer_{i}"`` -> Table 3 width for that layer's
    KV-cache rows — the activation-width family emitted by the static
    analysis pass (``repro.analysis``), consumed by
    ``init_decode_state`` / paged pool allocation and the serving bytes
    accounting. A separate namespace from the weight families: KV widths
    describe runtime activations, not stored leaves, so ``bits_of``
    never consults them.
    """

    float_bits: Dict[str, int]
    int_bits: Dict[str, Tuple[int, bool]]
    tune_evals: int = 0
    kv_bits: Dict[str, int] = dataclasses.field(default_factory=dict)

    def bits_of(self, path: Tuple[Any, ...], leaf):
        """Packing spec for one leaf: a bare width for floats, a
        ``(width, signed)`` pair for ints (signedness from range
        analysis must survive to ``pack_tensor``, or unsigned tensors
        with the top bit set sign-extend to negatives on unpack), or
        ``None`` to leave the leaf unpacked."""
        key = path_str(path)
        if key in self.float_bits:
            return self.float_bits[key]
        if key in self.int_bits:
            bits, signed = self.int_bits[key]
            return round_bits_to_slice(bits), signed
        return None

    def footprint_ratio(self, tensors: Dict[str, jnp.ndarray]) -> float:
        """Packed bytes / f32 bytes over the planned tensors."""
        num = 0.0
        den = 0.0
        for k, v in tensors.items():
            n = float(np.prod(np.asarray(v).shape or (1,)))
            bits = self.float_bits.get(
                k, round_bits_to_slice(self.int_bits.get(k, (32, True))[0])
                if k in self.int_bits else 32
            )
            num += n * bits
            den += n * 32
        return num / max(den, 1.0)

    def mean_float_bits(
        self, sizes: Optional[Dict[str, int]] = None
    ) -> float:
        """Mean width over the float leaves — size-weighted when per-leaf
        element counts are supplied (the honest footprint number: one
        large embedding at AF8 should dominate a dozen tiny heads at
        AF24), plain mean otherwise. 32.0 for an empty plan."""
        if not self.float_bits:
            return 32.0
        if sizes:
            num = sum(b * sizes.get(k, 1)
                      for k, b in self.float_bits.items())
            den = sum(sizes.get(k, 1) for k in self.float_bits)
            return num / max(den, 1)
        return sum(self.float_bits.values()) / len(self.float_bits)

    def kv_layer_widths(self, n_layers: int, default: int) -> Tuple[int, ...]:
        """Per-layer KV widths as a dense tuple: ``kv_bits["kv/layer_i"]``
        where present, ``default`` (normally the config's uniform width)
        for layers the plan does not name."""
        return tuple(
            int(self.kv_bits.get(f"kv/layer_{i}", default))
            for i in range(n_layers)
        )

    # -- JSON codec (plan files + checkpoint manifests) ------------------

    def to_jsonable(self) -> Dict[str, Any]:
        """Schema v1: ``{"version", "float_bits": {path: bits},
        "int_bits": {path: [bits, signed]}, "tune_evals"}``. Keys are
        stable ``path_str`` strings, sorted so the file diffs cleanly."""
        return {
            "version": 1,
            "float_bits": {k: int(v) for k, v in
                           sorted(self.float_bits.items())},
            "int_bits": {k: [int(b), bool(s)] for k, (b, s) in
                         sorted(self.int_bits.items())},
            "tune_evals": int(self.tune_evals),
            "kv_bits": {k: int(v) for k, v in
                        sorted(self.kv_bits.items())},
        }

    @classmethod
    def from_jsonable(cls, obj: Dict[str, Any]) -> "CompressionPlan":
        """Inverse of ``to_jsonable``; tolerates a missing ``version``
        (pre-codec checkpoint manifests carried the same shape bare)."""
        version = obj.get("version", 1)
        if version != 1:
            raise ValueError(f"unknown CompressionPlan schema v{version}")
        return cls(
            float_bits={k: int(v) for k, v in
                        obj.get("float_bits", {}).items()},
            int_bits={k: (int(v[0]), bool(v[1])) for k, v in
                      obj.get("int_bits", {}).items()},
            tune_evals=int(obj.get("tune_evals", 0)),
            kv_bits={k: int(v) for k, v in
                     obj.get("kv_bits", {}).items()},
        )

    def save(self, path: str) -> None:
        import json
        with open(path, "w") as f:
            json.dump(self.to_jsonable(), f, indent=2, sort_keys=True)
            f.write("\n")

    @classmethod
    def load(cls, path: str) -> "CompressionPlan":
        import json
        with open(path) as f:
            return cls.from_jsonable(json.load(f))


def path_str(path: Tuple[Any, ...]) -> str:
    parts = []
    for p in path:
        if hasattr(p, "key"):
            parts.append(str(p.key))
        elif hasattr(p, "idx"):
            parts.append(str(p.idx))
        else:
            parts.append(str(p))
    return "/".join(parts)


def plan_tensors(
    apply_fn: Callable[[Dict[str, jnp.ndarray]], Any],
    tensors: Dict[str, jnp.ndarray],
    quality: QualitySpec,
    int_ranges: Optional[Dict[str, Interval]] = None,
) -> CompressionPlan:
    """Tensor-level plan: tune floats, width ints from supplied ranges."""
    tuned = tune_tensors(apply_fn, tensors, quality)
    int_bits: Dict[str, Tuple[int, bool]] = {}
    for k, v in tensors.items():
        if np.issubdtype(np.asarray(v).dtype, np.integer):
            itv = (int_ranges or {}).get(k)
            if itv is None:
                arr = np.asarray(v)
                itv = Interval(float(arr.min()), float(arr.max()))
            b = itv.bits()
            if b:
                int_bits[k] = b
    return CompressionPlan(
        float_bits={k: b for k, b in tuned.formats.items() if b < 32},
        int_bits=int_bits,
        tune_evals=tuned.evaluations,
    )


# ---------------------------------------------------------------------------
# Plan derivation + repacking (the speculative-serving draft ladder)
# ---------------------------------------------------------------------------

def uniform_plan(tree: Any, bits: int, min_ndim: int = 2) -> CompressionPlan:
    """A trivial plan assigning one Table 3 width to every float leaf with
    ``ndim >= min_ndim`` (matmul weights / embedding tables; unstacked
    norms and biases stay at the compute dtype — layer-stacked (L, d)
    norm scales ride along deliberately, they decode on the cheap
    materialized path). MoE expert banks are covered the same way: a
    (E, d, f) bank — or the layer-stacked (L, E, d, f) leaf — packs along
    its last axis and dispatches onto the batched fused kernel at decode
    time. Used where a tuned plan is not available but the config pins a
    deployment width (``weight_bits``)."""
    from repro.core.tensor_store import is_packed

    float_bits: Dict[str, int] = {}
    if bits is None or bits >= 32:
        return CompressionPlan(float_bits={}, int_bits={})

    def visit(path, leaf):
        if is_packed(leaf):
            if leaf.kind == "float":
                float_bits[path_str(path)] = bits
            return
        if (jnp.issubdtype(leaf.dtype, jnp.floating)
                and getattr(leaf, "ndim", 0) >= min_ndim):
            float_bits[path_str(path)] = bits

    jax.tree_util.tree_map_with_path(
        visit, tree, is_leaf=is_packed)
    return CompressionPlan(float_bits=float_bits, int_bits={})


def derive_plan(plan: CompressionPlan, delta_bits: int = 4) -> CompressionPlan:
    """Derive the *draft* plan: every float leaf steps ``delta_bits`` down
    the Table 3 ladder (snapped to the widest rung <= width - delta_bits,
    floored at the narrowest rung) without re-running precision tuning.
    The three families step independently: weight floats by
    ``delta_bits``; per-layer ``kv_bits`` entries always one rung down
    (the draft-KV ladder contract, matching the scalar
    ``resolve_draft_kv_bits`` default) and never below AF8 — that is the
    narrowest Table 3 rung, so ``ladder_snap``'s floor enforces it;
    integer widths come from range analysis and are exact — narrowing
    them would corrupt values, so they are carried over unchanged.

    The result never aliases the source plan's mutable state: even when
    every leaf is already at the AF8 floor (or ``delta_bits == 0``) the
    derived plan is a distinct-but-equal object with fresh dicts, so
    mutating one plan (e.g. a tuner revising the target) cannot silently
    rewrite the other's widths."""
    if delta_bits < 0:
        raise ValueError(f"delta_bits must be >= 0, got {delta_bits}")
    new_floats: Dict[str, int] = {
        key: ladder_snap(bits - delta_bits)
        for key, bits in plan.float_bits.items()
    }
    new_kv: Dict[str, int] = {
        key: ladder_snap(bits, below=True)
        for key, bits in plan.kv_bits.items()
    }
    return CompressionPlan(
        float_bits=new_floats,
        int_bits=dict(plan.int_bits),
        tune_evals=plan.tune_evals,
        kv_bits=new_kv,
    )


def repack(tree: Any, plan: CompressionPlan) -> Any:
    """Re-encode a (partially packed) pytree at ``plan``'s widths.

    ``PackedTensor`` leaves are re-encoded value-by-value (decode at the
    current width, encode at the plan width) — no re-tuning, which is what
    makes draft derivation cheap; plain leaves the plan names are packed
    outright; leaves the plan does not name pass through untouched (packed
    leaves keep their current width). A packed leaf already *at* the plan
    width is returned as-is (``repack_tensor``'s no-op fast path): the
    decode→encode round trip is skipped entirely, so repeatedly applying
    the same plan accumulates zero re-encoding error and costs nothing.
    This is how the draft model of the speculative server derives a
    second, narrower packed width over the same weight structure."""
    from repro.core.tensor_store import is_packed, pack_tensor, repack_tensor

    def _one(path, leaf):
        spec = plan.bits_of(path, leaf)
        if spec is None:
            return leaf
        bits, signed = spec if isinstance(spec, tuple) else (spec, True)
        if is_packed(leaf):
            return repack_tensor(leaf, bits)
        if bits is None or bits >= 32:
            return leaf
        return pack_tensor(leaf, bits, signed=signed)

    return jax.tree_util.tree_map_with_path(_one, tree, is_leaf=is_packed)
