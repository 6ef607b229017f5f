"""Pallas TPU kernel: fused unpack + matmul (the hot path).

The paper's register file keeps operands compressed in SRAM and expands
them on the way to the execution units. The TPU analogue keeps weights
compressed in HBM and expands tiles in VMEM on the way to the MXU:

    HBM:  x tile (bm x bk)  +  packed w tile (bk x bn*bits/32 words)
    VMEM: decode w tile -> (bk x bn) f32, MXU dot, accumulate f32
    HBM:  out tile (bm x bn)

so the *unpacked* weights never touch HBM — weight-read bytes drop by
bits/32, which is exactly the paper's bytes-per-operand saving. Without
this fusion, XLA materializes the decoded weights and the memory roofline
term gets worse, not better (see EXPERIMENTS.md section Perf).

This is the kernel ``models.layers.linear`` / ``unembed`` dispatch onto
for 2-D float-format ``PackedTensor`` weights (via ``kernels.ops``), so
it accepts everything the model stack throws at it:

  * arbitrary leading/batch dims on ``x`` (flattened onto M);
  * ``transpose=True`` for contraction over the *packed* axis — the
    ``unembed`` tied-head spec ``"...d,vd->...v"`` where the table is
    packed along d. The normal orientation covers every ``linear`` spec
    (``"...d,df->...f"``, ``"...f,fd->...d"``, ...), all of which are the
    same last-axis x first-axis contraction;
  * bf16 or f32 ``x`` (tiles upcast to f32 on the VPU; the MXU dot
    accumulates f32; output is ``out_dtype``, defaulting to ``x.dtype``);
  * non-multiple M/N/K: an unpacked axis no longer than its target is one
    full-dim block; a longer one picks the largest divisor block <= the
    target that is a multiple of 8 (sublane axes) or 128 (lane axes);
    when no divisor is MXU-viable (best divisor under 1/8 of the target —
    e.g. a prime dim) the axis is zero-padded up to a block multiple
    instead. Padded x rows/cols are zeros, so padding never changes the
    contraction; outputs are sliced back to logical shape.

Grid is (M/bm, N/bn, K/bk) with the K dimension innermost ("arbitrary"
semantics) accumulating into a VMEM f32 scratch. Packed-axis blocks are
whole tiles of ``bitpack``'s lane-tiled layout (a multiple of 128 words
at real widths), so the in-kernel unpack is whole-vreg shifts and masks
(``bitpack.unpack_block``) and the packed axis never pads: its words
already cover whole tiles, and tile padding decodes to +0.0.

``interpret=None`` resolves through ``repro.compat.pallas``: compiled on
a real TPU, interpret (Python validation) everywhere else.

``packed_matmul_batched`` is the same fusion with a leading expert axis:
the grid gains an expert dimension and every (x, w, out) block carries an
expert coordinate, so stacked MoE expert banks ``(E, K, N)`` stream their
packed words per expert exactly like dense 2-D weights — this is what
``models.blocks.moe_ffn`` dispatches 3-D float ``PackedTensor`` banks
onto, including per-layer banks yielded by the stacked-layer ``lax.scan``.

Both kernels also serve the *training backward*: ``models.layers`` wraps
them in ``custom_vjp``s whose dx is the same kernel with the orientation
flipped (dx = g @ Wᵀ contracts over the packed axis of a normal-orientation
weight and vice versa), so the backward streams packed words too instead
of materializing W (weight-read bytes drop by bits/32 in training as
well). dW never reads W at all — it accumulates from the (x, g) residuals
(``kernels.ops.packed_matmul_dw``).
"""
from __future__ import annotations

import functools
import math
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.compat.pallas import pallas_interpret_default
from repro.core import bitpack
from repro.core.formats import FLOAT_FORMATS, decode_float

DEFAULT_BM = 128
DEFAULT_BN = 512
DEFAULT_BK = 512
SUBLANES = 8


def _plan_axis(dim: int, target: int, align: int) -> Tuple[int, int]:
    """Choose (block, padded_dim) for one unpacked grid axis.

    An axis no longer than ``target`` is one full-dim block (always a
    legal TPU block). Otherwise prefer the largest divisor of ``dim`` that
    is <= ``target`` and a multiple of ``align`` (8 sublanes or 128
    lanes, whichever the axis lands on); if the best such divisor is under
    1/8 of the target (no MXU-viable divisor, e.g. a large prime dim),
    fall back to an aligned ``target``-sized block and zero-pad the axis
    up to a multiple of it.
    """
    if dim <= target:
        return dim, dim
    cap = max(align, target - target % align)
    best = 0
    for cand in range(cap, align - 1, -align):
        if dim % cand == 0:
            best = cand
            break
    if best and best * 8 >= cap:
        return best, dim
    return cap, -(-dim // cap) * cap


def _plan_packed(codes: int, target: int, tile: int) -> int:
    """Block length along the packed axis: the largest whole number of
    tiles that divides the (tile-padded) axis and is <= ``target`` — at
    least one tile. The packed axis never pads: its words already cover
    whole tiles."""
    tiles = codes // tile
    per = max(1, min(tiles, target // tile))
    while tiles % per:
        per -= 1
    return per * tile


def _pad_to(x: jnp.ndarray, axis: int, size: int) -> jnp.ndarray:
    pad = size - x.shape[axis]
    if pad <= 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _pmm_kernel(x_ref, w_ref, o_ref, acc_ref, *, bits: int, lanes: int,
                k_steps: int, transpose: bool, batched: bool):
    k = pl.program_id(3 if batched else 2)
    sel = (0,) if batched else ()

    @pl.when(k == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    codes = bitpack.unpack_block(w_ref[sel], bits, lanes)
    w = decode_float(codes, FLOAT_FORMATS[bits])   # (bk, bn) | (bn, bk)
    x = x_ref[sel].astype(jnp.float32)             # (bm, bk)
    if transpose:
        acc_ref[...] += jax.lax.dot_general(
            x, w, (((1,), (1,)), ((), ())),         # x @ w.T
            preferred_element_type=jnp.float32,
        )
    else:
        acc_ref[...] += jnp.dot(x, w, preferred_element_type=jnp.float32)

    @pl.when(k == k_steps - 1)
    def _flush():
        o_ref[sel] = acc_ref[...].astype(o_ref.dtype)


def _packed_call(x3, w3, bits, n, transpose, bm, bn, bk, out_dtype,
                 interpret):
    """The fused call both entry points share: x3 (M, K) with w3
    (K, Nw) — or (N, Kw) when ``transpose`` — for the 2-D kernel, and the
    same with a leading expert axis on all three for the batched one."""
    from jax.experimental.pallas import tpu as pltpu

    batched = x3.ndim == 3
    m, kdim = x3.shape[-2:]
    packed_len = kdim if transpose else n
    lanes = bitpack.tile_lanes(packed_len, bits)
    tile = bitpack.tile_codes(packed_len, bits)
    tile_words = bitpack.lane_group(bits)[1] * lanes
    assert w3.shape[-1] == bitpack.packed_group_words(packed_len, bits), (
        w3.shape, packed_len, bits)
    codes_pad = w3.shape[-1] // tile_words * tile

    def words_of(codes):
        return codes // tile * tile_words

    if transpose:
        # W logical (N, K) packed along K: contraction over the packed
        # axis, so K blocks cut on tile boundaries; N lands on the lanes
        # of the output and the sublanes of the weight block.
        assert w3.shape[-2] == n, (w3.shape, n)
        bn_, n_pad = _plan_axis(n, bn, 128)
        bk_ = _plan_packed(codes_pad, bk, tile)
        k_pad = codes_pad
        w3 = _pad_to(w3, w3.ndim - 2, n_pad)
        w_block = (bn_, words_of(bk_))
        w_index = lambda j, k: (j, k)
    else:
        # W logical (K, N) packed along N: output blocks cut on tile
        # boundaries; K lands on the lanes of x and the sublanes of W.
        assert w3.shape[-2] == kdim, (w3.shape, kdim)
        bn_ = _plan_packed(codes_pad, bn, tile)
        n_pad = codes_pad
        bk_, k_pad = _plan_axis(kdim, bk, 128)
        w3 = _pad_to(w3, w3.ndim - 2, k_pad)
        w_block = (bk_, words_of(bn_))
        w_index = lambda j, k: (k, j)

    bm_, m_pad = _plan_axis(m, bm, SUBLANES)
    x3 = _pad_to(_pad_to(x3, x3.ndim - 1, k_pad), x3.ndim - 2, m_pad)
    k_steps = k_pad // bk_
    grid = (m_pad // bm_, n_pad // bn_, k_steps)
    kernel = functools.partial(_pmm_kernel, bits=bits, lanes=lanes,
                               k_steps=k_steps, transpose=transpose,
                               batched=batched)
    if batched:
        e = x3.shape[0]
        grid = (e,) + grid
        in_specs = [
            pl.BlockSpec((1, bm_, bk_), lambda e_, i, j, k: (e_, i, k)),
            pl.BlockSpec((1,) + w_block,
                         lambda e_, i, j, k: (e_,) + w_index(j, k)),
        ]
        out_spec = pl.BlockSpec((1, bm_, bn_),
                                lambda e_, i, j, k: (e_, i, j))
        out_shape = (e, m_pad, n_pad)
    else:
        in_specs = [pl.BlockSpec((bm_, bk_), lambda i, j, k: (i, k)),
                    pl.BlockSpec(w_block, lambda i, j, k: w_index(j, k))]
        out_spec = pl.BlockSpec((bm_, bn_), lambda i, j, k: (i, j))
        out_shape = (m_pad, n_pad)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=in_specs,
        out_specs=out_spec,
        out_shape=jax.ShapeDtypeStruct(out_shape, out_dtype),
        scratch_shapes=[pltpu.VMEM((bm_, bn_), jnp.float32)],
        interpret=interpret,
        name="packed_matmul",
    )(x3, w3)
    return out[..., :m, :n]


@functools.partial(
    jax.jit,
    static_argnames=("bits", "n", "transpose", "bm", "bn", "bk",
                     "out_dtype", "interpret"),
)
def packed_matmul(
    x: jnp.ndarray,            # (..., K) f32/bf16
    w_packed: jnp.ndarray,     # (K, packed_group_words(N)) uint32, or
                               # (N, packed_group_words(K)) when transpose
    bits: int,
    n: int,                    # logical output features N
    transpose: bool = False,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    out_dtype=None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """x @ W (or x @ W.T when ``transpose``) without materializing W.

    ``bm``/``bn``/``bk`` are block-size *targets*; the actual blocks come
    from ``_plan_axis`` / ``_plan_packed``. ``n`` is the logical output
    width — packed columns beyond it (tile padding) decode to zero and
    are sliced off.
    """
    interpret = pallas_interpret_default(interpret)
    out_dtype = out_dtype or x.dtype
    assert w_packed.ndim == 2, "packed weights are 2-D (pack axis last)"
    assert bits in FLOAT_FORMATS, f"no float format with {bits} bits"
    lead = x.shape[:-1]
    m = math.prod(lead) if lead else 1
    out = _packed_call(x.reshape(m, x.shape[-1]), w_packed, bits, n,
                       transpose, bm, bn, bk, out_dtype, interpret)
    return out.reshape(lead + (n,))


# ---------------------------------------------------------------------------
# Batched-expert orientation: grid over a leading expert axis
# ---------------------------------------------------------------------------

@functools.partial(
    jax.jit,
    static_argnames=("bits", "n", "transpose", "bm", "bn", "bk",
                     "out_dtype", "interpret"),
)
def packed_matmul_batched(
    x: jnp.ndarray,            # (E, C, K) f32/bf16
    w_packed: jnp.ndarray,     # (E, K, packed_group_words(N)) uint32, or
                               # (E, N, packed_group_words(K)) if transpose
    bits: int,
    n: int,                    # logical output features N (per expert)
    transpose: bool = False,
    bm: int = DEFAULT_BM,
    bn: int = DEFAULT_BN,
    bk: int = DEFAULT_BK,
    out_dtype=None,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Per-expert ``x[e] @ W[e]`` (or ``x[e] @ W[e].T``) without
    materializing any expert's weights.

    The grid is (E, C/bm, N/bn, K/bk) — the expert axis leads, K stays
    innermost for the scratch accumulation — and each block spec carries
    the expert coordinate, so an expert's packed words expand in VMEM only
    while that expert's grid slice is resident. Block planning per expert
    is identical to the 2-D kernel; experts share one plan since the bank
    is homogeneous.
    """
    interpret = pallas_interpret_default(interpret)
    out_dtype = out_dtype or x.dtype
    assert w_packed.ndim == 3, "expert banks are 3-D (pack axis last)"
    assert bits in FLOAT_FORMATS, f"no float format with {bits} bits"
    assert x.ndim == 3 and x.shape[0] == w_packed.shape[0], (
        x.shape, w_packed.shape)
    return _packed_call(x, w_packed, bits, n, transpose, bm, bn, bk,
                        out_dtype, interpret)
