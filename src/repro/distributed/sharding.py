"""Sharding rules: logical axis names -> mesh PartitionSpecs.

The production mesh axes are ("data", "model") single-pod and
("pod", "data", "model") multi-pod; data-parallel state shards over
("pod", "data") jointly. Rules map parameter-path regexes to specs, so the
same model code serves TP (replicated weights across DP) and ZeRO
(weights sharded over DP) modes. ``constrain`` is a mesh-aware
``with_sharding_constraint`` that degrades to a no-op outside any mesh.

All mesh-context queries go through ``repro.compat`` — the one layer
that knows how this jax exposes the current mesh.
"""
from __future__ import annotations

import re
from typing import Any, Dict, Optional, Sequence, Tuple

from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.compat import (
    current_mesh_axis_names,
    current_mesh_axis_sizes,
    with_sharding_constraint,
)

DATA_AXES = ("pod", "data")        # DP shards over both when present


def resolve_axes(axes: Sequence[Any]) -> P:
    """Translate logical axis entries to a PartitionSpec valid for the
    current mesh: "data" expands to ("pod", "data") on multi-pod meshes;
    axis names absent from the mesh drop to None (replicated)."""
    names = current_mesh_axis_names()
    out = []
    for ax in axes:
        if ax is None:
            out.append(None)
        elif ax == "data":
            present = tuple(a for a in DATA_AXES if a in names)
            out.append(present if present else None)
        elif isinstance(ax, (tuple, list)):
            present = tuple(a for a in ax if a in names)
            out.append(present if present else None)
        else:
            out.append(ax if ax in names else None)
    return P(*out)


def drop_indivisible(spec: P, shape: Tuple[int, ...],
                     axis_sizes: Optional[Dict[str, int]] = None) -> P:
    """Replicate any dimension whose size doesn't divide its shard count —
    jit in_shardings (unlike sharding constraints) reject uneven shards.
    The fallbacks are always small tensors (odd vocabs, batch=1 decode).
    ``axis_sizes`` overrides the current-mesh query (unit-testable
    without a multi-device mesh)."""
    sizes = (axis_sizes if axis_sizes is not None
             else current_mesh_axis_sizes())
    out = []
    for dim, ax in enumerate(tuple(spec) + (None,) * (len(shape) - len(spec))):
        if ax is None:
            out.append(None)
            continue
        names = ax if isinstance(ax, tuple) else (ax,)
        shards = 1
        for n in names:
            shards *= sizes.get(n, 1)
        out.append(ax if shape[dim] % shards == 0 else None)
    return P(*out)


def constrain(x, axes: Sequence[Any]):
    """with_sharding_constraint against logical axes; no-op outside any
    mesh.  Inside a mesh, errors propagate: the old blanket
    ``except: return x`` turned every bad spec into a silently
    replicated tensor — a sharded run that compiles and trains but
    holds full copies everywhere looks healthy until it OOMs at scale.
    The one benign mismatch (rank) is checked explicitly so the error
    names the offending spec."""
    names = current_mesh_axis_names()
    if not names:
        return x
    spec = resolve_axes(axes)
    ndim = getattr(x, "ndim", None)
    if ndim is not None and len(spec) > ndim:
        raise ValueError(
            f"constrain: spec {spec} (rank {len(spec)}) does not fit "
            f"tensor of shape {getattr(x, 'shape', ())}")
    return with_sharding_constraint(x, spec)


# ---------------------------------------------------------------------------
# Parameter sharding rules
# ---------------------------------------------------------------------------
# Pattern -> logical axes per dimension, aligned to the *trailing*
# dimensions of the parameter (so stacked (L, ...) scan params reuse the
# rules of their unstacked forms; leading unmatched dims are replicated,
# or sharded over DP in zero mode).
#
# Packed weights: PackedTensor payloads have the same rank with the last
# axis scaled by bits/32 — the rules apply unchanged because sharding of
# a group-aligned packed axis is proportional.

TP_RULES: Tuple[Tuple[str, Tuple[Any, ...]], ...] = (
    # attention projections: shard heads/ff over model
    (r"\bwq\b", (None, "model")),          # (d, H*hd)
    (r"\bwk\b", (None, "model")),
    (r"\bwv\b", (None, "model")),
    (r"\bwo\b", ("model", None)),          # (H*hd, d)
    # MLPs: column- then row-parallel
    (r"\bw_in\b|\bw_gate\b", (None, "model")),
    (r"\bw_out\b", ("model", None)),
    # MoE experts: expert-parallel over model
    (r"\bexperts\b.*\b(w_in|w_gate)\b", ("model", None, None)),
    (r"\bexperts\b.*\bw_out\b", ("model", None, None)),
    (r"\brouter\b", (None, None)),
    # embeddings: vocab over model
    (r"\bembed\b", ("model", None)),
    (r"\blm_head\b", (None, "model")),
    # mamba / rglru projections
    (r"\bin_proj\b", (None, "model")),
    (r"\bout_proj\b", ("model", None)),
    (r"\bconv_w\b", ("model", None)),
    (r"\bx_proj\b", ("model", None)),
    (r"\bdt_proj\b", (None, "model")),
    (r"\ba_param\b", ("model", None)),
    (r"\b(dt_bias|conv_b|d_param)\b", ("model",)),
    (r"\brg_(a|wr|wi)\b", ("model",)),
    (r"\brg_(gate_w|in_w)\b", (None, "model")),
    (r"\brg_out\b", ("model", None)),
    # norms / small vectors: replicated
    (r"\b(norm|scale|bias|ln)\w*\b", (None,)),
)


def spec_for(path: str, shape: Tuple[int, ...], mode: str = "tp") -> P:
    """PartitionSpec for a parameter path under the given mode."""
    axes: Optional[Tuple[Any, ...]] = None
    for pat, a in TP_RULES:
        if re.search(pat, path):
            axes = a
            break
    rank = len(shape)
    if axes is None:
        spec = [None] * rank
    else:
        spec = [None] * (rank - len(axes)) + list(axes)[:rank]
    if mode == "zero":
        # ZeRO: additionally shard a free dim over DP — the first dim the
        # DP degree divides (the layer stack when L divides, else e.g.
        # the expert dim: arctic's L=35 doesn't divide 16 but E=128 does).
        sizes = current_mesh_axis_sizes()
        dp = 1
        for a in DATA_AXES:
            dp *= sizes.get(a, 1)
        for d in range(rank):
            if spec[d] is None and dp > 1 and shape[d] % dp == 0 \
                    and shape[d] >= dp:
                spec[d] = "data"
                break
    return drop_indivisible(resolve_axes(spec), shape)


def _spec_shards(entry, sizes: Dict[str, int]) -> int:
    names = entry if isinstance(entry, tuple) else (entry,)
    shards = 1
    for n in names:
        shards *= sizes.get(n, 1)
    return shards


def spec_for_packed(path: str, logical_shape: Tuple[int, ...],
                    bits: int, mode: str = "tp",
                    axis_sizes: Optional[Dict[str, int]] = None) -> P:
    """PartitionSpec for a packed uint32 word array, consistent with the
    *logical* tensor's spec.

    The payload has the logical rank with the last axis rescaled to
    lane-tiled words (``core.bitpack``), so leading dims take the logical
    rules verbatim. The packed (last) axis is the subtle one: an even
    word split that lands mid-tile would hand two devices halves of one
    tile's shift/or network — checking word divisibility alone is wrong
    (e.g. 96 codes at AF16 = 48 words split 2 ways is 24 words each, but
    the whole axis is one tile). A split on a tile boundary is still
    wrong when the last tile carries padding (misaligned with the even
    logical split every logical-spec consumer assumes), or when a shard
    is short enough that it would lay its codes out on narrower lanes.
    The axis may shard only when ``bitpack.shard_aligned`` holds;
    otherwise it drops to replicated.

    ``axis_sizes`` overrides the current-mesh query for the tile check
    (unit-testable without a multi-device mesh, like
    ``drop_indivisible``)."""
    from repro.core import bitpack

    spec = spec_for(path, logical_shape, mode)
    rank = len(logical_shape)
    entries = list(tuple(spec)) + [None] * (rank - len(tuple(spec)))
    if rank and entries[-1] is not None:
        sizes = (axis_sizes if axis_sizes is not None
                 else current_mesh_axis_sizes())
        shards = _spec_shards(entries[-1], sizes)
        if not bitpack.shard_aligned(logical_shape[-1], bits, shards):
            entries[-1] = None
    return P(*entries)


def shard_leaf(path: str, leaf, mesh: Mesh, mode: str = "tp"):
    """NamedSharding for one (possibly packed) parameter leaf. Packed
    leaves shard by their *logical* spec with the lane-tiled word axis
    kept intact (``spec_for_packed``) — never by raw payload shape, which
    can split a tile across devices."""
    from repro.core.tensor_store import PackedTensor
    if isinstance(leaf, PackedTensor):
        return NamedSharding(mesh, spec_for_packed(
            path, leaf.logical_shape, leaf.bits, mode))
    return NamedSharding(mesh, spec_for(path, leaf.shape, mode))
