"""Attention: chunked-flash training path + cached decode path.

The training/prefill path is an online-softmax double-scan (q chunks x kv
chunks) in pure JAX so peak memory is O(S * chunk) instead of O(S^2) —
required for the 32k prefill cells to fit HBM at compile time. Causal,
local-window (recurrentgemma / whisper-free) and full (encoder / cross)
masks share one implementation.

The decode path scores one new token against a (possibly packed) KV
cache; with packing, HBM traffic per step drops by bits/32 — the
register-file insight applied to the dominant decode term. GQA is
grouped: q heads are folded onto their kv head before the scan.
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitpack
from repro.core.tensor_store import is_packed
from repro.distributed.sharding import constrain
from repro.kernels import ops as kops

NEG_INF = -1e30


def _chunk_mask(q_pos, k_pos, causal: bool, window: int, prefix: int = 0):
    """(Sq_blk, Sk_blk) boolean validity mask. ``prefix`` marks a fully
    visible (bidirectional) leading segment — the VLM image tokens."""
    m = jnp.ones((q_pos.shape[0], k_pos.shape[0]), bool)
    if causal:
        c = q_pos[:, None] >= k_pos[None, :]
        if prefix:
            c |= k_pos[None, :] < prefix
        m &= c
    if window:
        m &= q_pos[:, None] - k_pos[None, :] < window
    return m


@functools.partial(
    jax.jit,
    static_argnames=("causal", "window", "prefix", "q_chunk", "kv_chunk"),
)
def flash_attention(
    q: jnp.ndarray,            # (B, Sq, H, D)
    k: jnp.ndarray,            # (B, Sk, Hkv, D)
    v: jnp.ndarray,            # (B, Sk, Hkv, D)
    causal: bool = True,
    window: int = 0,
    prefix: int = 0,
    q_chunk: int = 1024,
    kv_chunk: int = 1024,
) -> jnp.ndarray:
    b, sq, h, d = q.shape
    sk, hkv = k.shape[1], k.shape[2]
    g = h // hkv
    scale = 1.0 / np.sqrt(d)

    def _divisor_chunk(target: int, s: int) -> int:
        c = min(target, s)
        while s % c:              # largest divisor <= target (trace-time)
            c -= 1
        return c

    q_chunk = _divisor_chunk(q_chunk, sq)
    kv_chunk = _divisor_chunk(kv_chunk, sk)
    nq, nk = sq // q_chunk, sk // kv_chunk

    # (B, Hkv, G, nq, qc, D) queries; (B, Hkv, nk, kc, D) keys/values
    qs = q.reshape(b, nq, q_chunk, hkv, g, d).transpose(0, 3, 4, 1, 2, 5)
    ks = k.reshape(b, nk, kv_chunk, hkv, d).transpose(0, 3, 1, 2, 4)
    vs = v.reshape(b, nk, kv_chunk, hkv, d).transpose(0, 3, 1, 2, 4)

    def per_q_chunk(qi, qc):
        # qc: (B, Hkv, G, qc, D)
        q_pos = qi * q_chunk + jnp.arange(q_chunk)

        def kv_step(carry, inputs):
            acc, m_run, l_run = carry
            ki, kc, vc = inputs
            k_pos = ki * kv_chunk + jnp.arange(kv_chunk)
            # MXU-style: bf16 operands, f32 accumulation. Keeping q/k/v in
            # the compute dtype (instead of upcasting) halves the dot-input
            # traffic AND makes every cotangent crossing a TP boundary
            # bf16 — the f32 activation all-reduces were the dominant
            # collective (EXPERIMENTS.md section Perf, iteration 2).
            logits = jnp.einsum(
                "bkgqd,bkcd->bkgqc", qc, kc,
                preferred_element_type=jnp.float32,
            ) * scale
            mask = _chunk_mask(q_pos, k_pos, causal, window, prefix)
            logits = jnp.where(mask[None, None, None], logits, NEG_INF)
            m_new = jnp.maximum(m_run, logits.max(-1, keepdims=True))
            r = jnp.exp(m_run - m_new)
            p = jnp.exp(logits - m_new)
            acc = acc * r + jnp.einsum(
                "bkgqc,bkcd->bkgqd", p.astype(vc.dtype), vc,
                preferred_element_type=jnp.float32,
            )
            l_run = l_run * r + p.sum(-1, keepdims=True)
            return (acc, m_new, l_run), None

        acc0 = jnp.zeros((b, hkv, g, q_chunk, d), jnp.float32)
        m0 = jnp.full((b, hkv, g, q_chunk, 1), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, hkv, g, q_chunk, 1), jnp.float32)
        (acc, _, l), _ = jax.lax.scan(
            kv_step, (acc0, m0, l0),
            (jnp.arange(nk), jnp.moveaxis(ks, 2, 0), jnp.moveaxis(vs, 2, 0)),
        )
        return acc / jnp.maximum(l, 1e-30)

    out = jax.lax.map(
        lambda args: per_q_chunk(*args),
        (jnp.arange(nq), jnp.moveaxis(qs, 3, 0)),
    )                                       # (nq, B, Hkv, G, qc, D)
    out = out.transpose(1, 0, 4, 2, 3, 5).reshape(b, sq, h, d)
    return out.astype(q.dtype)


def kv_row_width(n_kv_heads: int, head_dim: int,
                 kv_bits: Optional[int] = None) -> int:
    """Minor dim of a KV cache row: one token's K (or V) for every KV
    head — packed words along the flattened (Hkv * D) axis when
    ``kv_bits`` packs, else Hkv * D dense values. One packed row per
    token keeps the cache's minor dim whole 128-lane tiles at real widths
    (a per-head row of 64 words would half-fill every tile)."""
    n = n_kv_heads * head_dim
    return bitpack.packed_group_words(n, kv_bits) if kv_bits else n


def decode_attention(
    q: jnp.ndarray,                        # (B, H, D) one new token
    k_cache, v_cache,                      # (B, S, kv_row_width) rows
    kv_len: jnp.ndarray,                   # (B,) valid lengths
    kv_bits: Optional[int],
    hkv: int,                              # KV heads per row
) -> jnp.ndarray:
    """Score one token against the cache (packed path = kernel dispatch).

    Packed caches are raw uint32 word arrays (scan-sliceable); ``kv_bits``
    is the static format width from the compression plan.
    """
    if is_packed(k_cache):
        kv_bits, k_cache, v_cache = (
            k_cache.bits, k_cache.data, v_cache.data
        )
    if kv_bits:
        return kops.kv_decode(q, k_cache, v_cache, kv_len, kv_bits, hkv)
    b, h, d = q.shape
    s = k_cache.shape[1]
    g = h // hkv
    qg = q.reshape(b, hkv, g, d).astype(jnp.float32)
    logits = jnp.einsum(
        "bkgd,bskd->bkgs", qg,
        k_cache.reshape(b, s, hkv, d).astype(jnp.float32)
    ) / np.sqrt(d)
    mask = jnp.arange(s)[None, None, None, :] < kv_len[:, None, None, None]
    logits = jnp.where(mask, logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p,
                     v_cache.reshape(b, s, hkv, d).astype(jnp.float32))
    return out.reshape(b, h, d).astype(q.dtype)


def paged_decode_attention(
    q: jnp.ndarray,                        # (B, H, D) one new token
    k_pool, v_pool,                        # (P+1, page, kv_row_width) pools
    table: jnp.ndarray,                    # (B, max_pages) int32 page ids
    kv_len: jnp.ndarray,                   # (B,) valid lengths
    kv_bits: Optional[int],
    hkv: int,
    fallback: bool = False,
) -> jnp.ndarray:
    """Score one token straight through the page table — the fused paged
    serving path. Only the pages the table names are read; the dense
    gathered view of ``gather_kv_pages`` never materializes.
    ``fallback=True`` demotes to the gather-materialize oracle (recorded
    as such for the dispatch linter)."""
    return kops.paged_attention(q, k_pool, v_pool, table, kv_len,
                                kv_bits or 0, hkv, fallback=fallback)


def gather_kv_pages(pool: jnp.ndarray, table: jnp.ndarray) -> jnp.ndarray:
    """Materialize a per-sequence cache view from the page pool.

    pool: (P, page, W) one layer's physical pages (page 0 = scrap);
    table: (B, max_pages) int32 physical page ids (0 where unallocated).
    Returns (B, max_pages*page, W) — logical row ``p`` of sequence
    ``b`` is pool row (table[b, p // page], p % page). Rows gathered
    through unallocated (scrap) entries are garbage, but they only ever
    sit at positions >= the sequence's valid length, where attention
    masks them — the same dead-row contract the dense cache relies on.

    In decode this is the *demoted* paged path: the fused kernel
    (``kernels.paged_attention``) attends through the table without ever
    building this view, so the dispatch record here lets the static
    linter prove a fused-configured decode trace never materialized the
    gather. The one-pass prefill reads this view in both paged modes
    (its C queries have no fused kernel), which keeps paged prefill
    bitwise equal to dense prefill.
    """
    kops.record_dispatch("gather_kv_pages", "materialized",
                         pool.size * pool.dtype.itemsize,
                         shape=pool.shape)
    g = jnp.take(pool, table, axis=0)          # (B, mp, page, W)
    b, mp, pg = g.shape[0], g.shape[1], g.shape[2]
    return g.reshape((b, mp * pg) + g.shape[3:])


def _kv_rows(x_new, kv_bits):
    """K or V of shape (..., Hkv, D) -> cache rows (..., kv_row_width)
    (``kops.pack`` over the flattened heads when the cache packs)."""
    row = x_new.reshape(x_new.shape[:-2] + (-1,))
    return kops.pack(row.astype(jnp.float32), kv_bits) if kv_bits else row


def append_kv_pool_rows(k_pool, v_pool, k_new, v_new, table, kv_len,
                        kv_bits: Optional[int] = None):
    """Persist C tokens' K/V rows, (B, C, Hkv, D), straight to their
    physical pages at positions ``kv_len[b] + i`` — the paged append of
    decode (C = 1) and of the one-pass prefill. Rows pack exactly as
    ``update_kv_cache`` packs them (same ``_kv_rows``), so the pool holds
    bit-identical words whether a row arrived here or through the
    gather-view + ``scatter_kv_row`` round-trip. Out-of-range positions
    (dead slots, padding past the cache) clamp onto the last table entry
    (the scrap page where unallocated), mirroring ``scatter_kv_row``."""
    return (_scatter_pool_rows(k_pool, _kv_rows(k_new, kv_bits), table,
                               kv_len),
            _scatter_pool_rows(v_pool, _kv_rows(v_new, kv_bits), table,
                               kv_len))


def _scatter_pool_rows(pool, rows, table, kv_len):
    """Write row i of sequence b, ``rows[b, i]``, at pool position
    (table[b, p // page], p % page) with p = kv_len[b] + i."""
    page = pool.shape[1]
    mp = table.shape[1]
    pos = jnp.minimum(kv_len[:, None] + jnp.arange(rows.shape[1]),
                      mp * page - 1)                         # (B, C)
    ids = jnp.take_along_axis(table, pos // page, axis=1)
    phys = (ids * page + pos % page).reshape(-1)
    flat = pool.reshape((-1,) + pool.shape[2:])
    flat = flat.at[phys].set(
        rows.reshape((-1,) + rows.shape[2:]).astype(flat.dtype))
    return flat.reshape(pool.shape)


def append_kv_cache_rows(k_cache, v_cache, k_new, v_new, kv_len,
                         kv_bits: Optional[int] = None):
    """Dense-cache twin of :func:`append_kv_pool_rows`: C tokens' K/V,
    (B, C, Hkv, D), packed as decode packs them, written at cache rows
    ``kv_len[b] + i``. Rows past the end of the cache are dropped, so a
    chunk's padding never clamps back onto a valid row."""
    return (_set_cache_rows(k_cache, _kv_rows(k_new, kv_bits), kv_len),
            _set_cache_rows(v_cache, _kv_rows(v_new, kv_bits), kv_len))


def _set_cache_rows(cache, rows, kv_len):
    pos = kv_len[:, None] + jnp.arange(rows.shape[1])      # (B, C)
    b = jnp.arange(cache.shape[0])[:, None]
    return cache.at[b, pos].set(rows.astype(cache.dtype), mode="drop")


def prefill_attention(
    q: jnp.ndarray,                        # (B, C, H, D) new tokens
    k_cache, v_cache,                      # (B, S, kv_row_width) rows
    kv_len: jnp.ndarray,                   # (B,) length before the chunk
    kv_bits: Optional[int],
    hkv: int,
) -> jnp.ndarray:
    """Score C new tokens per sequence against a cache view that already
    holds their own rows: query i sits at position ``kv_len + i`` and
    sees keys at positions up to and including its own. Packed rows are
    unpacked in XLA (``kops.unpack``, the oracles' decode) and scored in
    float32 like the jnp path of :func:`decode_attention`, so every key a
    query sees went through the cache's format, as in decode."""
    b, c, h, d = q.shape
    s = k_cache.shape[1]
    g = h // hkv

    def rows(cache):
        x = (kops.unpack(cache, kv_bits, hkv * d) if kv_bits
             else cache.astype(jnp.float32))
        return x.reshape(b, s, hkv, d)

    qg = q.reshape(b, c, hkv, g, d).astype(jnp.float32)
    logits = jnp.einsum("bckgd,bskd->bkgcs", qg, rows(k_cache)) / np.sqrt(d)
    last = kv_len[:, None] + jnp.arange(c)                   # (B, C)
    mask = jnp.arange(s)[None, None, :] <= last[:, :, None]  # (B, C, S)
    logits = jnp.where(mask[:, None, None], logits, NEG_INF)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgcs,bskd->bckgd", p, rows(v_cache))
    return out.reshape(b, c, h, d).astype(q.dtype)


def update_kv_cache(k_cache, v_cache, k_new, v_new, kv_len,
                    kv_bits: Optional[int] = None):
    """Insert one token's K/V at position kv_len per sequence.

    Packed caches (uint32 words) update whole rows: one token's
    (Hkv, D) K packs to one row of words — a masked writeback of whole
    words, so no read-modify-write of neighbours (Section 3.2.6
    analogue).
    """
    k_cache = _dus_rows(k_cache, _kv_rows(k_new, kv_bits)[:, None], kv_len)
    v_cache = _dus_rows(v_cache, _kv_rows(v_new, kv_bits)[:, None], kv_len)
    return k_cache, v_cache


def _dus_rows(cache, row, kv_len):
    """Per-batch dynamic_update_slice at row kv_len[b]."""
    def upd(c, r, l):
        start = (l,) + (0,) * (c.ndim - 1)
        return jax.lax.dynamic_update_slice(c, r.astype(c.dtype), start)
    return jax.vmap(upd)(cache, row, kv_len)
