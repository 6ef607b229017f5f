"""Pallas TPU kernel: fused paged-attention decode through the page table.

The paged serving path used to gather every request's pages into a dense
``(B, S, Hkv, W)`` view before ``kv_decode`` could run — a full
materialized copy of the logical cache per step, exactly the decompressed
shadow copy the paper's register file avoids. This kernel attends
*through* the block table instead:

    SMEM: the per-slot page-id table and valid lengths arrive on the
          scalar-prefetch path (``PrefetchScalarGridSpec``), so the
          BlockSpec index_map can steer each grid step's DMA;
    HBM:  one physical page — ``(page, words)`` packed rows holding every
          KV head, so the block's minor dims are whole (8, 128) tiles —
          per grid step, fetched straight from the pool row the table
          names; the dense gather copy never exists;
    VMEM: whole-vreg shift/or unpack of each head's lane chunks
          (``kv_decode.kv_head_rows``) + Value Converter, then the page's
          contribution to the online softmax (flash-decoding style
          m/l/acc scratch, shared with ``kv_decode``).

Pages past a sequence's live length all map to the scrap page 0, and
consecutive grid steps with an unchanged block index skip the re-DMA —
so HBM traffic per sequence is the pages actually live, not
``max_pages``. Dead-page grid steps also skip the softmax update
entirely (``pl.when``); the tail of a partially filled page is masked by
position exactly as the dense kernel masks beyond ``kv_len``.

``bits=0`` runs the same grid over an unpacked (dense-dtype) pool, so
every serving width shares one kernel. The jnp oracle is
``ref.paged_attention_ref`` (gather through the table + the dense
kernels' exact math), which is also the ``fallback=`` escape hatch in
``kernels.ops.paged_attention``.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.compat.pallas import pallas_interpret_default
from repro.kernels.kv_decode import (
    NEG_INF,
    flush_heads,
    kv_head_rows,
    online_softmax_step,
)


def _paged_attn_kernel(tab_ref, len_ref, q_ref, k_ref, v_ref, o_ref,
                       m_ref, l_ref, acc_ref,
                       *, bits: int, hkv: int, page: int, max_pages: int):
    b, j = pl.program_id(0), pl.program_id(1)
    d = q_ref.shape[-1]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    length = len_ref[b]

    # Dead pages (page_start >= length) sit on the scrap page; skip their
    # softmax contribution outright — the revisit-elision above already
    # skipped their DMA.
    @pl.when(j * page < length)
    def _accumulate():
        kw, vw = k_ref[0], v_ref[0]                       # (page, W)
        # mask the partially-filled tail page beyond the live length
        pos = j * page + jax.lax.broadcasted_iota(
            jnp.int32, (q_ref.shape[2], page), 1)
        valid = pos < length
        for h in range(hkv):
            online_softmax_step(q_ref,
                                kv_head_rows(kw, h, d, bits, hkv),
                                kv_head_rows(vw, h, d, bits, hkv),
                                h, m_ref, l_ref, acc_ref, valid)

    @pl.when(j == max_pages - 1)
    def _flush():
        flush_heads(o_ref, m_ref, l_ref, acc_ref)


@functools.partial(
    jax.jit, static_argnames=("bits", "hkv", "interpret")
)
def paged_attention(
    q: jnp.ndarray,          # (B, H, D) one new token
    k_pool: jnp.ndarray,     # (P+1, page, packed_group_words(Hkv*D))
    v_pool: jnp.ndarray,     #   uint32, or (P+1, page, Hkv*D) when bits=0
    table: jnp.ndarray,      # (B, max_pages) int32 physical page ids
    kv_len: jnp.ndarray,     # (B,) valid lengths
    bits: int,
    hkv: int,
    interpret: Optional[bool] = None,
) -> jnp.ndarray:
    """Attend one token per sequence straight through the page table.

    One grid step owns one (batch, table-slot) pair and every KV head;
    the slot's physical page id is scalar-prefetched into the DMA
    index_map, so only the pages the table names ever leave HBM.
    """
    from jax.experimental.pallas import tpu as pltpu

    interpret = pallas_interpret_default(interpret)
    b, h, dim = q.shape
    page, words = k_pool.shape[1], k_pool.shape[2]
    group = h // hkv
    max_pages = table.shape[1]

    qg = q.reshape(b, hkv, group, dim)
    flat_table = table.reshape(-1).astype(jnp.int32)      # (B * mp,)

    def page_index(ib, jp, tab, lens):
        return (tab[ib * max_pages + jp], 0, 0)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, max_pages),
        in_specs=[
            pl.BlockSpec((1, hkv, group, dim),
                         lambda ib, jp, tab, lens: (ib, 0, 0, 0)),
            pl.BlockSpec((1, page, words), page_index),
            pl.BlockSpec((1, page, words), page_index),
        ],
        out_specs=pl.BlockSpec((1, hkv, group, dim),
                               lambda ib, jp, tab, lens: (ib, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((hkv, group, 1), jnp.float32),
            pltpu.VMEM((hkv, group, 1), jnp.float32),
            pltpu.VMEM((hkv, group, dim), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        functools.partial(_paged_attn_kernel, bits=bits, hkv=hkv, page=page,
                          max_pages=max_pages),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, group, dim), q.dtype),
        interpret=interpret,
        name="paged_attention",
    )(flat_table, kv_len.astype(jnp.int32), qg, k_pool, v_pool)
    return out.reshape(b, h, dim)
