"""PackedTensor / PackedStore: the register file at tensor granularity.

A ``PackedTensor`` is the framework's architectural-register analogue: a
logical float or integer tensor stored as a dense uint32 bitstream in the
lane-tiled layout of ``repro.core.bitpack`` with a statically assigned
bitwidth (from precision tuning / range analysis). It is a pytree node, so
packed state flows through jit/pjit/grad machinery and can be sharded;
the packed (last) axis shards whenever every shard is whole lane tiles
(``bitpack.shard_aligned``).

A ``PackedStore`` is the indirection table analogue for a whole state
pytree: per-leaf format metadata + packed payloads, with helpers to pack /
unpack / estimate footprints. Packing policy (which leaves get which
width) comes from the static analysis framework (``repro.core.compress``).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitpack
from repro.core.formats import (
    FLOAT_FORMATS,
    FloatFormat,
    decode_float,
    decode_int,
    encode_float,
    encode_int,
)


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class PackedTensor:
    """A tensor packed along its last axis at ``bits`` per element."""

    data: jnp.ndarray                # uint32 (..., packed_group_words)
    bits: int                        # total bits per element (mult of 4)
    kind: str                        # "float" | "int"
    signed: bool                     # int decode extension mode
    logical_shape: Tuple[int, ...]   # unpacked shape (pack axis last)
    out_dtype: Any                   # dtype returned by unpack()

    def tree_flatten(self):
        return (self.data,), (
            self.bits, self.kind, self.signed, self.logical_shape,
            self.out_dtype,
        )

    @classmethod
    def tree_unflatten(cls, aux, children):
        bits, kind, signed, logical_shape, out_dtype = aux
        data = children[0]
        # Leading (unpacked) dims follow the payload: transforms that
        # slice or stack the data leaf — lax.scan over stacked layer
        # weights, vmap batching — rebuild the node with a reshaped
        # payload, so reconcile everything but the packed (last) axis
        # from it. A stacked (L, d, f) weight scanned over L then yields
        # per-layer 2-D PackedTensors that take the fused matmul path.
        shp = getattr(data, "shape", None)
        if shp is not None and tuple(shp[:-1]) != tuple(logical_shape[:-1]):
            logical_shape = tuple(shp[:-1]) + (logical_shape[-1],)
        return cls(data, bits, kind, signed, logical_shape, out_dtype)

    # -- the Value Extractor + Converter path --------------------------------
    def unpack(self) -> jnp.ndarray:
        n = self.logical_shape[-1]
        codes = bitpack.unpack_groups(self.data, self.bits, n)
        if self.kind == "float":
            fmt = FLOAT_FORMATS[self.bits]
            x = decode_float(codes, fmt)
            out = x.astype(self.out_dtype)
        else:
            out = decode_int(codes, self.bits, self.signed).astype(
                self.out_dtype
            )
        return out.reshape(self.logical_shape)

    def take(self, indices: jnp.ndarray) -> jnp.ndarray:
        """Gather logical rows (leading-axis entries) from the packed
        payload and decode *only those rows* — the packed ``embed`` path.

        ``indices`` indexes axis 0 of a >= 2-D packed tensor; the gather
        runs on the uint32 words (bits/32 of the f32 gather traffic), and
        the Value Extractor / Converter only ever sees the gathered rows
        instead of materializing the whole table (important when the table
        is a 150k-row vocabulary and the gather wants a handful).

        Dispatches through ``kernels.ops.take_rows`` — the Pallas
        gather-decode kernel for 2-D payloads on TPU (rows DMA'd by
        scalar-prefetched index, decoded in VMEM), the jnp oracle
        elsewhere (higher-rank payloads always take the oracle)."""
        if len(self.logical_shape) < 2:
            raise ValueError(
                f"take() needs a leading row axis; shape {self.logical_shape}"
            )
        from repro.kernels import ops as kops

        n = self.logical_shape[-1]
        idx_shape = tuple(jnp.shape(indices))
        flat = jnp.asarray(indices).reshape(-1)
        out = kops.take_rows(self.data, flat, self.bits, n,
                             kind=self.kind, signed=self.signed,
                             out_dtype=self.out_dtype)
        return out.reshape(idx_shape + self.logical_shape[1:])

    @property
    def nbytes_packed(self) -> int:
        return int(np.prod(self.data.shape)) * 4

    @property
    def nbytes_logical_f32(self) -> int:
        return int(np.prod(self.logical_shape)) * 4

    @property
    def compression_ratio(self) -> float:
        return 32.0 / self.bits


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class STWeight:
    """A straight-through training weight: packed codes + dense master.

    The packed-master training mode carries every planned parameter as
    this pair — the forward always computes from ``packed`` (the deployed
    codes, exactly what serving streams), while gradients flow to
    ``master``, the dense copy the optimizer owns. ``models.layers``
    dispatches it everywhere a weight can appear: the fused matmul paths
    route through ``st_linear``-style custom_vjps (dW from residuals,
    never decoding W) and the materialized paths (norms, fallbacks) use
    the straight-through decode ``unpack(packed) + (master - sg(master))``.

    Both children are pytree leaves, so stacked (L, ...) pairs slice
    per-layer through ``lax.scan`` exactly like bare ``PackedTensor``
    leaves (the payload's leading dims reconcile on unflatten)."""

    packed: PackedTensor
    master: jnp.ndarray

    def tree_flatten(self):
        return (self.packed, self.master), None

    @classmethod
    def tree_unflatten(cls, aux, children):
        return cls(*children)

    @property
    def logical_shape(self) -> Tuple[int, ...]:
        return self.packed.logical_shape


def is_st(x) -> bool:
    return isinstance(x, STWeight)


def st_tree(packed_tree: Any, master_tree: Any) -> Any:
    """Zip a (partially) packed tree with its dense masters: every
    ``PackedTensor`` leaf pairs into an ``STWeight``; unplanned leaves
    come from the master tree (the packed tree's dense mirror copies are
    carried only so the two trees stay congruent). This is the parameter
    tree the packed-master train step runs the model on — values from
    the codes, tangents to the masters."""
    return jax.tree_util.tree_map(
        lambda pk, m: STWeight(pk, m) if is_packed(pk) else m,
        packed_tree, master_tree, is_leaf=is_packed,
    )


# -- the Value Truncator path -------------------------------------------------
def pack_tensor(
    x: jnp.ndarray,
    bits: int,
    kind: Optional[str] = None,
    signed: bool = True,
    out_dtype: Optional[Any] = None,
) -> PackedTensor:
    x = jnp.asarray(x)
    if kind is None:
        kind = "float" if jnp.issubdtype(x.dtype, jnp.floating) else "int"
    out_dtype = out_dtype or x.dtype
    if kind == "float":
        codes = encode_float(x.astype(jnp.float32), FLOAT_FORMATS[bits])
    else:
        codes = encode_int(x.astype(jnp.int32), bits, signed)
    data = bitpack.pack_groups(codes, bits)
    return PackedTensor(
        data=data,
        bits=bits,
        kind=kind,
        signed=signed,
        logical_shape=tuple(x.shape),
        out_dtype=out_dtype,
    )


def repack_tensor(pt: PackedTensor, bits: int) -> PackedTensor:
    """Re-encode a ``PackedTensor`` at a different width *without*
    re-tuning: decode the stored codes to values, encode at ``bits``.
    Same kind/signedness/out_dtype; this is the ladder step that derives
    the speculative draft's weights from the already-packed target."""
    if bits == pt.bits:
        return pt
    return pack_tensor(
        pt.unpack(), bits, kind=pt.kind, signed=pt.signed,
        out_dtype=pt.out_dtype,
    )


def packed_shape(shape: Tuple[int, ...], bits: int) -> Tuple[int, ...]:
    """Shape of the packed payload for a logical ``shape`` at ``bits``."""
    return tuple(shape[:-1]) + (bitpack.packed_group_words(shape[-1], bits),)


def packed_spec(shape: Tuple[int, ...], bits: int) -> jax.ShapeDtypeStruct:
    return jax.ShapeDtypeStruct(packed_shape(shape, bits), jnp.uint32)


# ---------------------------------------------------------------------------
# Store-level helpers (pytrees of PackedTensor / plain arrays)
# ---------------------------------------------------------------------------

def is_packed(x) -> bool:
    return isinstance(x, PackedTensor)


def pack_tree(
    tree: Any,
    bits_of: Callable[[Tuple[Any, ...], jnp.ndarray], Any],
) -> Any:
    """Pack every leaf for which ``bits_of(path, leaf)`` returns a width —
    either a bare ``bits`` int or a ``(bits, signed)`` pair (int leaves
    must carry the signedness decided by range analysis through to
    ``pack_tensor``; a bare width defaults to signed). Leaves mapped to
    None stay unpacked (e.g. norms, small biases)."""

    def _maybe_pack(path, leaf):
        spec = bits_of(path, leaf)
        if spec is None:
            return leaf
        bits, signed = spec if isinstance(spec, tuple) else (spec, True)
        if bits is None or bits >= 32:
            return leaf
        return pack_tensor(leaf, bits, signed=signed)

    return jax.tree_util.tree_map_with_path(_maybe_pack, tree)


def unpack_tree(tree: Any) -> Any:
    """Unpack every PackedTensor leaf (identity on plain arrays)."""
    return jax.tree_util.tree_map(
        lambda l: l.unpack() if is_packed(l) else l,
        tree,
        is_leaf=is_packed,
    )


def tree_bytes(tree: Any) -> Tuple[int, int]:
    """(packed_bytes, logical_f32_bytes) over a (partially) packed tree."""
    packed = 0
    logical = 0
    for leaf in jax.tree_util.tree_leaves(tree, is_leaf=is_packed):
        if is_packed(leaf):
            packed += leaf.nbytes_packed
            logical += leaf.nbytes_logical_f32
        else:
            n = int(np.prod(leaf.shape)) if hasattr(leaf, "shape") else 0
            b = n * np.dtype(leaf.dtype).itemsize
            packed += b
            logical += n * 4
    return packed, logical


def weight_pass_bytes(tree: Any) -> Dict[str, int]:
    """Byte cost of streaming every weight of ``tree`` once, split by
    path: ``fused`` (packed leaves, the bytes the fused kernels read),
    ``fused_f32`` (what those leaves would cost dense f32),
    ``analytic`` (the paper's bits/32 model summed per leaf — no
    lane-tile padding, the reference the live telemetry byte counters
    are held to within ``obs.schema.BYTE_TOLERANCE``), and ``dense``
    (plain leaves: norms, biases, unpacked weights)."""
    fused = fused_f32 = analytic = dense = 0
    for leaf in jax.tree_util.tree_leaves(tree, is_leaf=is_packed):
        if is_packed(leaf):
            fused += leaf.nbytes_packed
            fused_f32 += leaf.nbytes_logical_f32
            analytic += leaf.nbytes_logical_f32 * leaf.bits // 32
        elif hasattr(leaf, "shape"):
            n = int(np.prod(leaf.shape))
            dense += n * np.dtype(leaf.dtype).itemsize
    return {"fused": fused, "fused_f32": fused_f32,
            "analytic": analytic, "dense": dense}
