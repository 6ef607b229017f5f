"""Pallas TPU kernel: single-token attention decode over a packed KV cache.

Decode is the workload where the paper's occupancy argument lands on TPU:
step time is dominated by streaming the KV cache from HBM, so packing KV
at the statically tuned width cuts the dominant roofline term by bits/32
*and* lets proportionally more sequences stay resident (serving
"occupancy", see core/occupancy.decode_residency).

The cache keeps one token's K (or V) row for every KV head as one packed
row, ``(B, S, packed_group_words(Hkv * D))``, so a sequence chunk is a
``(block_s, words)`` tile whose minor dim is whole 128-lane chunks at
real widths. One grid step processes one sequence and one chunk for all
KV heads: each head's codes are whole lane chunks of the tile
(``kv_head_codes``), unpacked in VMEM (Value Extractor path), and the
chunk's contribution to the online softmax is accumulated in f32 VMEM
scratch (running max / normalizer / weighted values — flash-decoding
style). The valid lengths arrive on the scalar-prefetch path (SMEM), and
the final grid step normalizes and writes the (Hkv, group, D) output.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.compat.pallas import pallas_interpret_default
from repro.core import bitpack
from repro.core.formats import FLOAT_FORMATS, decode_float

DEFAULT_BLOCK_S = 512
NEG_INF = -1e30


def kv_head_rows(kv, h: int, d: int, bits: int, hkv: int) -> jnp.ndarray:
    """Head ``h``'s (rows, D) f32 K/V values out of a block of packed
    cache rows (``bits`` > 0) or dense rows (``bits`` == 0). At real
    widths a head is whole lane chunks of the tile, so only its own chunks
    unpack; tiny test widths whose heads split a chunk unpack the block
    and slice."""
    if not bits:
        return kv[:, h * d:(h + 1) * d].astype(jnp.float32)
    lanes = bitpack.tile_lanes(hkv * d, bits)
    if d % lanes == 0:
        per = d // lanes
        parts = [bitpack.unpack_chunk(kv, bits, lanes, c)
                 for c in range(h * per, (h + 1) * per)]
        codes = parts[0] if per == 1 else jnp.concatenate(parts, axis=-1)
    else:
        codes = bitpack.unpack_block(kv, bits, lanes)[:, h * d:(h + 1) * d]
    return decode_float(codes, FLOAT_FORMATS[bits])


def online_softmax_step(q_ref, k, v, h: int, m_ref, l_ref, acc_ref,
                        valid) -> None:
    """Fold one KV chunk of head ``h`` into the running (m, l, acc)."""
    d = q_ref.shape[-1]
    q = q_ref[0, h].astype(jnp.float32)                   # (G, D)
    logits = jax.lax.dot_general(
        q, k, (((1,), (1,)), ((), ())),                   # q @ k.T
        preferred_element_type=jnp.float32) * (1.0 / (d ** 0.5))
    logits = jnp.where(valid, logits, NEG_INF)            # (G, rows)
    m_prev = m_ref[h]                                     # (G, 1)
    m_new = jnp.maximum(m_prev, logits.max(-1, keepdims=True))
    scale = jnp.exp(m_prev - m_new)
    p = jnp.exp(logits - m_new)
    l_ref[h] = l_ref[h] * scale + p.sum(-1, keepdims=True)
    acc_ref[h] = acc_ref[h] * scale + jnp.dot(
        p, v, preferred_element_type=jnp.float32)
    m_ref[h] = m_new


def flush_heads(o_ref, m_ref, l_ref, acc_ref) -> None:
    """Normalize and write every head. A fully masked sequence
    (kv_len == 0) leaves m == NEG_INF and l with garbage mass: guard the
    normalizer and emit zeros for those rows."""
    empty = m_ref[...] <= NEG_INF * 0.5                   # (Hkv, G, 1)
    l_safe = jnp.maximum(l_ref[...], 1e-30)
    out = jnp.where(empty, 0.0, acc_ref[...] / l_safe)
    o_ref[0] = out.astype(o_ref.dtype)


def _kv_decode_kernel(len_ref, q_ref, k_ref, v_ref, o_ref,
                      m_ref, l_ref, acc_ref,
                      *, bits: int, hkv: int, block_s: int, s_steps: int):
    b, s_idx = pl.program_id(0), pl.program_id(1)
    d = q_ref.shape[-1]

    @pl.when(s_idx == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # chunks wholly past the valid length contribute nothing: skip them
    @pl.when(s_idx * block_s < len_ref[b])
    def _accumulate():
        kw, vw = k_ref[0], v_ref[0]                       # (block_s, W)
        pos = s_idx * block_s + jax.lax.broadcasted_iota(
            jnp.int32, (q_ref.shape[2], block_s), 1)
        valid = pos < len_ref[b]
        for h in range(hkv):
            online_softmax_step(q_ref,
                                kv_head_rows(kw, h, d, bits, hkv),
                                kv_head_rows(vw, h, d, bits, hkv),
                                h, m_ref, l_ref, acc_ref, valid)

    @pl.when(s_idx == s_steps - 1)
    def _flush():
        flush_heads(o_ref, m_ref, l_ref, acc_ref)


@functools.partial(
    jax.jit,
    static_argnames=("bits", "hkv", "block_s", "interpret"),
)
def kv_decode(
    q: jnp.ndarray,            # (B, H, D)
    k_packed: jnp.ndarray,     # (B, S, packed_group_words(Hkv*D)) uint32
    v_packed: jnp.ndarray,     # (B, S, packed_group_words(Hkv*D)) uint32
    kv_len: jnp.ndarray,       # (B,) int32 valid lengths
    bits: int,
    hkv: int,
    block_s: int = DEFAULT_BLOCK_S,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    from jax.experimental.pallas import tpu as pltpu

    interpret = pallas_interpret_default(interpret)
    b, h, dim = q.shape
    s, words = k_packed.shape[1], k_packed.shape[2]
    group = h // hkv
    block_s = min(block_s, s)
    assert s % block_s == 0
    s_steps = s // block_s

    # (B, Hkv, G, D) view of q: one block holds every head's group
    qg = q.reshape(b, hkv, group, dim)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=(b, s_steps),
        in_specs=[
            pl.BlockSpec((1, hkv, group, dim),
                         lambda ib, js, lens: (ib, 0, 0, 0)),
            pl.BlockSpec((1, block_s, words),
                         lambda ib, js, lens: (ib, js, 0)),
            pl.BlockSpec((1, block_s, words),
                         lambda ib, js, lens: (ib, js, 0)),
        ],
        out_specs=pl.BlockSpec((1, hkv, group, dim),
                               lambda ib, js, lens: (ib, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hkv, group, 1), jnp.float32),
            pltpu.VMEM((hkv, group, 1), jnp.float32),
            pltpu.VMEM((hkv, group, dim), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_kv_decode_kernel, bits=bits, hkv=hkv,
                          block_s=block_s, s_steps=s_steps),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, dim), q.dtype),
        interpret=interpret,
    )(kv_len.astype(jnp.int32), qg, k_packed, v_packed)
    return out.reshape(b, h, dim)
