"""Dense bitstream packing of narrow codes into 32-bit words.

This is the storage substrate of the proposed register file: operands of
``w`` bits (w a multiple of the 4-bit slice size, 4..32) are laid out
back-to-back in a pool of 32-bit physical words. A single operand may
straddle a word boundary — the paper's "architectural register split into
two physical registers" (Section 4.3) — in which case reads fetch two
words and OR the parts together, exactly like the extended collector
unit's 1024-bit OR gate (Section 3.2.4).

All routines are vectorized jnp (scatter-add for pack, double-gather + OR
for unpack) so they jit/lower on any backend; the Pallas kernels reuse the
same arithmetic with VMEM tiling.
"""
from __future__ import annotations

import math
from typing import Tuple

import jax.numpy as jnp
import numpy as np

from repro.core.formats import SLICE_BITS

_U32 = jnp.uint32


def packed_words(n: int, width: int) -> int:
    """Number of 32-bit words to store ``n`` codes of ``width`` bits."""
    _check_width(width)
    return -(-n * width // 32)


def _check_width(width: int) -> None:
    if not (1 <= width <= 32) or width % SLICE_BITS != 0:
        raise ValueError(
            f"width must be a multiple of {SLICE_BITS} in [4, 32], got {width}"
        )


def _width_mask(width: int) -> np.uint32:
    return np.uint32(0xFFFFFFFF) if width == 32 else np.uint32((1 << width) - 1)


def pack_stream(codes: jnp.ndarray, width: int) -> jnp.ndarray:
    """Pack flat uint32 ``codes`` (low ``width`` bits valid) densely.

    Returns a uint32 array of ``packed_words(len(codes), width)`` words.
    Element ``i`` occupies bits ``[i*width, (i+1)*width)`` of the stream,
    little-endian within each word (bit 0 of word 0 is stream bit 0).
    """
    _check_width(width)
    codes = jnp.asarray(codes, _U32).reshape(-1) & _width_mask(width)
    n = codes.shape[0]
    n_words = packed_words(n, width)
    if width == 32:
        return codes

    start = jnp.arange(n, dtype=_U32) * np.uint32(width)
    word_lo = (start >> np.uint32(5)).astype(jnp.int32)
    off = start & np.uint32(31)

    lo_part = codes << off
    # Portion spilling into the next word. off+width <= 63 so the shift
    # (32 - off) is in [1, 31] whenever a spill exists (off > 0 required
    # for a spill since width <= 32).
    spill = (off + np.uint32(width)) > np.uint32(32)
    safe_shift = jnp.where(off > 0, np.uint32(32) - off, np.uint32(1))
    hi_part = jnp.where(spill, codes >> safe_shift, np.uint32(0))

    out = jnp.zeros((n_words + 1,), _U32)  # +1 slack for the last spill
    # Bit ranges never overlap, so add == bitwise OR here.
    out = out.at[word_lo].add(lo_part, mode="drop")
    out = out.at[word_lo + 1].add(hi_part, mode="drop")
    return out[:n_words]


def unpack_stream(packed: jnp.ndarray, width: int, n: int) -> jnp.ndarray:
    """Inverse of :func:`pack_stream`: extract ``n`` codes of ``width`` bits.

    This is the Value Extractor data path (Fig. 3): gather the word(s)
    holding each operand, shift-align, OR the two parts, mask.
    """
    _check_width(width)
    packed = jnp.asarray(packed, _U32).reshape(-1)
    if width == 32:
        return packed[:n]

    start = jnp.arange(n, dtype=_U32) * np.uint32(width)
    word_lo = (start >> np.uint32(5)).astype(jnp.int32)
    off = start & np.uint32(31)

    lo_word = packed[word_lo]
    hi_idx = jnp.minimum(word_lo + 1, packed.shape[0] - 1)
    hi_word = packed[hi_idx]

    spill = (off + np.uint32(width)) > np.uint32(32)
    safe_shift = jnp.where(off > 0, np.uint32(32) - off, np.uint32(1))
    code = (lo_word >> off) | jnp.where(
        spill, hi_word << safe_shift, np.uint32(0)
    )
    return code & _width_mask(width)


def stream_bits(n: int, width: int) -> int:
    """Total payload bits of a stream (before word rounding)."""
    _check_width(width)
    return n * width


# ---------------------------------------------------------------------------
# Lane-tiled layout: the TPU-tileable packing used by the tensor store
# ---------------------------------------------------------------------------
# A ``width``-bit code shares 32-bit words with its neighbours in groups of
# ``g = 32 / gcd(width, 32)`` codes, which fill exactly ``wg = g*width/32``
# words (AF8: 4 codes -> 1 word, AF12: 8 -> 3, AF16: 2 -> 1). Along the
# packed (last) axis, codes are cut into chunks of ``L`` lanes (``L`` = 128
# for every axis of at least ``g`` x 128 codes, the full axis / g below
# that); ``g`` consecutive code chunks form a *tile* that packs into ``wg``
# consecutive word chunks. Lane ``l`` of a tile's word chunks holds lane
# ``l`` of its code chunks as a dense little-endian stream — the paper's
# slice-packed register, one per lane.
#
# The point of the layout is what unpacking it needs: every code chunk is
# a whole-vreg shift/or/mask of one or two word chunks at the *same* lanes,
# so a Mosaic kernel unpacks an (8k, 128) tile with no reshape of the
# minor dim and no lane movement at all. Every shift is a Python constant
# (the static mux selects of the TVE), so XLA fuses it too. The axis pads
# to whole tiles; an axis of at most ``g`` x 128 codes pads only to a
# multiple of 32, exactly like the dense stream.

GROUP = 32
LANES = 128
# Name of this layout in persisted payloads: a checkpoint whose packed
# leaves carry another (or no) layout tag was packed differently and must
# not be decoded with these shifts.
LAYOUT = "lane-tiled-128"


def lane_group(width: int) -> Tuple[int, int]:
    """(codes, words) of one lane group: the fewest ``width``-bit codes
    that fill whole 32-bit words, and those words."""
    _check_width(width)
    g = 32 // math.gcd(width, 32)
    return g, g * width // 32


def tile_lanes(n: int, width: int) -> int:
    """Lanes per chunk ``L`` for a packed axis of ``n`` codes."""
    g, _ = lane_group(width)
    n32 = -(-n // GROUP) * GROUP
    if g == 1:                      # 32-bit codes: the identity layout
        return n32
    return min(LANES, n32 // g)


def tile_codes(n: int, width: int) -> int:
    """Codes per tile — the packed-axis alignment of every kernel block."""
    return lane_group(width)[0] * tile_lanes(n, width)


def packed_group_words(n: int, width: int) -> int:
    """Packed last-dim length for ``n`` codes (padded to whole tiles)."""
    g, wg = lane_group(width)
    lanes = tile_lanes(n, width)
    tiles = -(-n // (g * lanes))
    return tiles * wg * lanes


def shard_aligned(n: int, width: int, shards: int) -> bool:
    """Whether a packed axis of ``n`` codes splits ``shards`` ways into
    equal runs of whole tiles that keep the axis' own lane width — the
    condition for every shard to be a self-contained lane-tiled array
    whose logical slice matches the logical tensor's even split."""
    if shards <= 1:
        return True
    if n % shards:
        return False
    local = n // shards
    return (local % tile_codes(n, width) == 0
            and tile_lanes(local, width) == tile_lanes(n, width))


def _plane_terms(width: int):
    """For code plane ``j`` of a lane group: (first word, bit offset,
    spills into the next word). The one schedule both the XLA and the
    in-kernel unpack read, so they cannot disagree on the layout."""
    g, _ = lane_group(width)
    return tuple((j * width // 32, j * width % 32,
                  j * width % 32 + width > 32) for j in range(g))


def _extract(lo, hi, off: int, spill: bool, width: int):
    code = lo >> np.uint32(off)
    if spill:
        code = code | (hi << np.uint32(32 - off))
    return code & _width_mask(width)


def pack_groups(codes: jnp.ndarray, width: int) -> jnp.ndarray:
    """Pack codes (..., N) -> words (..., packed_group_words(N)), lane-tiled."""
    g, wg = lane_group(width)
    codes = jnp.asarray(codes, _U32) & _width_mask(width)
    lead, n = codes.shape[:-1], codes.shape[-1]
    lanes = tile_lanes(n, width)
    tiles = -(-n // (g * lanes))
    pad = tiles * g * lanes - n
    if pad:
        codes = jnp.concatenate(
            [codes, jnp.zeros(lead + (pad,), _U32)], axis=-1)
    c = codes.reshape(lead + (tiles, g, lanes))
    words = []
    for w in range(wg):
        acc = None
        for j, (w0, off, spill) in enumerate(_plane_terms(width)):
            if w0 == w:                       # low part lands here
                part = c[..., j, :] << np.uint32(off)
            elif w0 == w - 1 and spill:       # spill part
                part = c[..., j, :] >> np.uint32(32 - off)
            else:
                continue
            acc = part if acc is None else acc | part
        words.append(acc)
    out = jnp.stack(words, axis=-2)           # (..., tiles, wg, lanes)
    return out.reshape(lead + (tiles * wg * lanes,))


def unpack_groups(words: jnp.ndarray, width: int, n: int) -> jnp.ndarray:
    """Inverse of :func:`pack_groups`: words (..., W) -> codes (..., n)."""
    g, wg = lane_group(width)
    words = jnp.asarray(words, _U32)
    lead = words.shape[:-1]
    lanes = tile_lanes(n, width)
    tiles = words.shape[-1] // (wg * lanes)
    w = words.reshape(lead + (tiles, wg, lanes))
    cols = [_extract(w[..., w0, :], w[..., w0 + 1, :] if spill else None,
                     off, spill, width)
            for w0, off, spill in _plane_terms(width)]
    out = jnp.stack(cols, axis=-2)            # (..., tiles, g, lanes)
    return out.reshape(lead + (tiles * g * lanes,))[..., :n]


def unpack_chunk(words, width: int, lanes: int, chunk: int):
    """Codes ``[chunk*lanes, (chunk+1)*lanes)`` of a block of packed words
    — the in-kernel unpack. ``words`` (a value or a ref) holds whole tiles
    of ``lanes``-wide chunks along its last axis; the result comes from
    static, chunk-aligned lane slices of one or two word chunks, shifted,
    or-ed and masked as whole vregs (no reshape, no lane movement)."""
    g, wg = lane_group(width)
    t, j = divmod(chunk, g)
    w0, off, spill = _plane_terms(width)[j]
    base = (t * wg + w0) * lanes
    lo = words[..., base:base + lanes]
    hi = words[..., base + lanes:base + 2 * lanes] if spill else None
    return _extract(lo, hi, off, spill, width)


def unpack_block(words, width: int, lanes: int):
    """Every code of a block of whole tiles, chunk by chunk
    (:func:`unpack_chunk` concatenated along lanes)."""
    g, wg = lane_group(width)
    chunks = words.shape[-1] // (wg * lanes) * g
    parts = [unpack_chunk(words, width, lanes, c) for c in range(chunks)]
    return parts[0] if len(parts) == 1 else jnp.concatenate(parts, axis=-1)
