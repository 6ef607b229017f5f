"""Pass 4: sharding & donation lints over the distributed metadata.

Two whole-program invariants that today only hold by convention:

* **whole-tile packed axis** — a packed payload may shard its last
  (word) axis only when every shard is a whole number of lane tiles at
  the axis' own lane width (``core.bitpack.shard_aligned``); anything
  else hands two devices halves of one tile's shift/or network
  (``distributed.sharding.spec_for_packed`` docstring has the full
  argument). The lint re-derives the expected rule per planned leaf at
  several tensor-parallel degrees and reports any spec that keeps a
  misaligned shard (error) — plus perf notes (info) where a hot leaf's
  packed axis must replicate because the logical width is
  tile-misaligned.
* **donated-buffer read-after-overwrite** — ``decode_step`` donates the
  decode state (serving jits with ``donate_argnums``); a donated invar
  that is overwritten (fed to an in-place-shaped op: a
  ``dynamic_update_slice``/``scatter`` destination, a scan/while carry)
  and then *read by a later equation* is only correct while XLA chooses
  not to alias — a silent performance cliff or, under aliasing, a
  stale read. Reported as warnings (some double-uses are
  stale-by-design, e.g. rollback paths).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp
from jax.extend import core as jcore

from repro import compat
from repro.analysis.report import Finding
from repro.core import bitpack
from repro.core.compress import path_str, repack, uniform_plan
from repro.core.tensor_store import is_packed
from repro.distributed.sharding import _spec_shards, spec_for, spec_for_packed

_TP_DEGREES = (2, 4, 8)


def lint_sharding(cfg, plan=None, params: Optional[Dict] = None,
                  ) -> List[Finding]:
    """Check the whole-tile rule for every planned leaf at each TP
    degree, using the ``axis_sizes`` override (no mesh needed)."""
    findings: List[Finding] = []
    if params is None:
        from repro.models.lm import LM
        params = LM(cfg).init(compat.prng_key(0))
    if plan is None or not plan.float_bits:
        plan = uniform_plan(params, cfg.resolved_weight_bits)
    packed = repack(params, plan)

    leaves: List[Tuple[str, Tuple[int, ...], int]] = []

    def visit(path, leaf):
        if is_packed(leaf):
            leaves.append((path_str(path), tuple(leaf.logical_shape),
                           leaf.bits))

    jax.tree_util.tree_map_with_path(visit, packed, is_leaf=is_packed)

    n_checked = 0
    for path, logical, bits in sorted(leaves):
        base = tuple(spec_for(path, logical))
        base_last = base[-1] if len(base) == len(logical) and base else None
        dropped_at: List[int] = []
        for tp in _TP_DEGREES:
            sizes = {"model": tp, "data": 1}
            spec = tuple(spec_for_packed(path, logical, bits,
                                         axis_sizes=sizes))
            n_checked += 1
            last = spec[-1] if spec else None
            if last is not None:
                shards = _spec_shards(last, sizes)
                if not bitpack.shard_aligned(logical[-1], bits, shards):
                    findings.append(Finding(
                        check="sharding", severity="error", path=path,
                        message=(
                            f"whole-tile violation: packed axis of "
                            f"{path} (logical last dim {logical[-1]}, "
                            f"AF{bits}) sharded {shards}-way over "
                            f"{last!r} — a lane tile would straddle "
                            f"devices"),
                        detail={"logical_shape": list(logical),
                                "tp": tp, "entry": str(last)},
                    ))
            elif base_last is not None and _spec_shards(
                    base_last, sizes) > 1:
                dropped_at.append(tp)
        if dropped_at and len(dropped_at) == len(_TP_DEGREES):
            findings.append(Finding(
                check="sharding", severity="info", path=path,
                message=(
                    f"perf: packed axis of {path} (logical last dim "
                    f"{logical[-1]}) replicates at every TP degree "
                    f"{_TP_DEGREES} — the logical width does not split "
                    f"into whole lane tiles, so the packed leaf "
                    "cannot tensor-parallelize its hot axis"),
                detail={"logical_shape": list(logical),
                        "degrees": dropped_at},
            ))
    if all(f.severity == "info" for f in findings):
        findings.append(Finding(
            check="sharding", severity="info",
            message=(
                f"whole-tile rule holds for {len(leaves)} packed "
                f"leaves x {len(_TP_DEGREES)} TP degrees "
                f"({n_checked} specs checked)"),
        ))
    return findings


def _overwrite_positions(eqn) -> Tuple[int, ...]:
    """Invar positions this equation treats as an in-place destination
    (under donation, XLA may alias these buffers)."""
    name = eqn.primitive.name
    if name == "dynamic_update_slice":
        return (0,)
    if name.startswith("scatter"):
        return (0,)
    if name == "scan":
        nc = eqn.params["num_consts"]
        return tuple(range(nc, nc + eqn.params["num_carry"]))
    if name == "while":
        nc = eqn.params["cond_nconsts"] + eqn.params["body_nconsts"]
        return tuple(range(nc, len(eqn.invars)))
    return ()


def donation_hazards(jaxpr, donated: Dict) -> Dict[str, Tuple[int, int, str]]:
    """Walk a jaxpr's equations in order: for each donated invar (a
    ``{var: name}`` map), record the first overwrite-shaped use, then
    flag any read by a *later* equation. Returns
    ``{name: (overwrite_eqn, read_eqn, reader_primitive)}``."""
    overwritten_at: Dict[object, int] = {}
    hazards: Dict[str, Tuple[int, int, str]] = {}
    for idx, eqn in enumerate(jaxpr.eqns):
        ow = set(_overwrite_positions(eqn))
        for pos, v in enumerate(eqn.invars):
            if isinstance(v, jcore.Literal) or v not in donated:
                continue
            if v in overwritten_at and idx > overwritten_at[v]:
                name = donated[v]
                if name not in hazards:
                    hazards[name] = (overwritten_at[v], idx,
                                     eqn.primitive.name)
            if pos in ow and v not in overwritten_at:
                overwritten_at[v] = idx
    return hazards


def lint_donation(cfg, params: Optional[Dict] = None, batch_size: int = 1,
                  seq_len: int = 32) -> List[Finding]:
    """Walk ``decode_step``'s top-level jaxpr: every donated state invar
    that is read by an equation *after* its overwrite-shaped use is a
    read-after-overwrite hazard."""
    from repro.models.lm import LM
    lm = LM(cfg)
    findings: List[Finding] = []
    if params is None:
        params = lm.init(compat.prng_key(0))
    state = lm.init_decode_state(batch_size, seq_len, abstract=True)
    tokens = jnp.zeros((batch_size, 1), jnp.int32)
    try:
        closed = jax.make_jaxpr(lm.decode_step)(params, state, tokens)
    except Exception as e:                     # noqa: BLE001
        findings.append(Finding(
            check="donation", severity="warning",
            message=f"tracing decode_step failed: "
                    f"{type(e).__name__}: {e}"))
        return findings
    jaxpr = closed.jaxpr

    n_params = len(jax.tree_util.tree_leaves(params))
    flat_state = jax.tree_util.tree_leaves(state)
    state_paths = [path_str(p) for p, _ in
                   jax.tree_util.tree_flatten_with_path(state)[0]]
    donated = {}
    for i, v in enumerate(jaxpr.invars[n_params:n_params + len(flat_state)]):
        donated[v] = state_paths[i] if i < len(state_paths) else f"state[{i}]"

    hazards = donation_hazards(jaxpr, donated)
    for name, (w_idx, r_idx, prim) in sorted(hazards.items()):
        findings.append(Finding(
            check="donation", severity="warning", path=name,
            message=(
                f"donated state leaf {name} is overwritten at eqn "
                f"{w_idx} and read again at eqn {r_idx} ({prim}) — "
                "under donate_argnums aliasing this read can observe "
                "the overwritten buffer"),
            detail={"overwrite_eqn": w_idx, "read_eqn": r_idx,
                    "reader": prim},
        ))
    if not findings:
        findings.append(Finding(
            check="donation", severity="info",
            message=(
                f"no donated-buffer read-after-overwrite in decode_step "
                f"({len(donated)} donated state leaves, "
                f"{len(jaxpr.eqns)} equations)"),
        ))
    return findings
