"""Pure-jnp oracles for every Pallas kernel in this package.

These are the executable specifications: each kernel's test sweeps shapes,
dtypes and Table 3 widths and asserts allclose (or exact equality for the
bit-manipulation paths) against these functions.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import bitpack
from repro.core.formats import (
    FLOAT_FORMATS,
    decode_float,
    decode_int,
    encode_float,
)


def unpack_ref(packed: jnp.ndarray, bits: int, n: int,
               out_dtype=jnp.float32) -> jnp.ndarray:
    """Value Extractor + Converter: packed words -> floats (last axis n)."""
    codes = bitpack.unpack_groups(packed, bits, n)
    return decode_float(codes, FLOAT_FORMATS[bits]).astype(out_dtype)


def take_rows_ref(packed: jnp.ndarray, indices: jnp.ndarray, bits: int,
                  n: int, kind: str = "float", signed: bool = True,
                  out_dtype=jnp.float32) -> jnp.ndarray:
    """Gather rows of packed words, decode only the gathered rows — the
    packed ``embed`` path. packed (R, packed_group_words(n)) uint32,
    indices (B,) -> (B, n). The Pallas kernel DMAs the 8-row slab that
    holds each scalar-prefetched index; this oracle is the same gather in
    XLA."""
    rows = jnp.take(packed, indices, axis=0)
    codes = bitpack.unpack_groups(rows, bits, n)
    if kind == "float":
        out = decode_float(codes, FLOAT_FORMATS[bits])
    else:
        out = decode_int(codes, bits, signed)
    return out.astype(out_dtype)


def pack_ref(x: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Value Truncator: floats -> packed words along the last axis."""
    codes = encode_float(jnp.asarray(x, jnp.float32), FLOAT_FORMATS[bits])
    return bitpack.pack_groups(codes, bits)


def convert_ref(code: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Value Converter: one narrow-float code lane -> f32 lane."""
    return decode_float(code, FLOAT_FORMATS[bits])


def truncate_ref(x: jnp.ndarray, bits: int) -> jnp.ndarray:
    """Truncation step of the Value Truncator: f32 lane -> code lane."""
    return encode_float(jnp.asarray(x, jnp.float32), FLOAT_FORMATS[bits])


def packed_matmul_ref(x: jnp.ndarray, w_packed: jnp.ndarray, bits: int,
                      n: int, transpose: bool = False) -> jnp.ndarray:
    """x @ unpack(w): x (..., K) f32/bf16; w_packed (K, n*bits/32) uint32,
    or (n, K*bits/32) when ``transpose`` (contraction over the packed
    axis — the ``unembed`` tied-head orientation)."""
    if transpose:
        w = unpack_ref(w_packed, bits, x.shape[-1], jnp.float32)  # (N, K)
        return jnp.einsum("...k,nk->...n", x.astype(jnp.float32), w,
                          preferred_element_type=jnp.float32)
    w = unpack_ref(w_packed, bits, n, jnp.float32)                # (K, N)
    return jnp.einsum("...k,kn->...n", x.astype(jnp.float32), w,
                      preferred_element_type=jnp.float32)


def packed_matmul_batched_ref(x: jnp.ndarray, w_packed: jnp.ndarray,
                              bits: int, n: int,
                              transpose: bool = False) -> jnp.ndarray:
    """Per-expert ``x[e] @ unpack(w[e])``: x (E, C, K) f32/bf16; w_packed
    (E, K, n*bits/32) uint32, or (E, n, K*bits/32) when ``transpose``
    (contraction over the packed axis) — the MoE expert-bank orientation."""
    if transpose:
        w = unpack_ref(w_packed, bits, x.shape[-1], jnp.float32)  # (E, N, K)
        return jnp.einsum("eck,enk->ecn", x.astype(jnp.float32), w,
                          preferred_element_type=jnp.float32)
    w = unpack_ref(w_packed, bits, n, jnp.float32)                # (E, K, N)
    return jnp.einsum("eck,ekn->ecn", x.astype(jnp.float32), w,
                      preferred_element_type=jnp.float32)


def packed_matmul_dw_ref(x: jnp.ndarray, g: jnp.ndarray,
                         transpose: bool = False,
                         batched: bool = False) -> jnp.ndarray:
    """Weight cotangent of the fused matmul, accumulated *packed-aware*:
    dW never reads W at all — it contracts the saved input against the
    upstream cotangent, so no decode happens on this grad either.

    Normal orientation (out = x @ W, W (K, N)): dW = xᵀ g, laid out
    (K, N). Transpose orientation (out = x @ Wᵀ, W (N, K)): dW = gᵀ x,
    laid out (N, K). Leading batch dims of x/g are summed; with
    ``batched`` the leading axis is the expert axis and is kept
    (per-expert accumulation over the capacity axis)."""
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    if batched:
        if transpose:
            return jnp.einsum("ecn,eck->enk", gf, xf,
                              preferred_element_type=jnp.float32)
        return jnp.einsum("eck,ecn->ekn", xf, gf,
                          preferred_element_type=jnp.float32)
    if transpose:
        return jnp.einsum("...n,...k->nk", gf, xf,
                          preferred_element_type=jnp.float32)
    return jnp.einsum("...k,...n->kn", xf, gf,
                      preferred_element_type=jnp.float32)


def kv_decode_ref(
    q: jnp.ndarray,           # (B, H, D)
    k_packed: jnp.ndarray,    # (B, S, packed_group_words(Hkv*D)) uint32
    v_packed: jnp.ndarray,    # (B, S, packed_group_words(Hkv*D)) uint32
    bits: int,
    hkv: int,
    kv_len: jnp.ndarray | None = None,   # (B,) valid lengths, else full S
) -> jnp.ndarray:
    """Single-token attention decode over a packed KV cache (one packed
    row per token holding every KV head)."""
    b, h, dim = q.shape
    s = k_packed.shape[1]
    group = h // hkv
    k = unpack_ref(k_packed, bits, hkv * dim).reshape(b, s, hkv, dim)
    v = unpack_ref(v_packed, bits, hkv * dim).reshape(b, s, hkv, dim)
    qg = q.reshape(b, hkv, group, dim).astype(jnp.float32)
    logits = jnp.einsum("bkgd,bskd->bkgs", qg, k) / jnp.sqrt(float(dim))
    if kv_len is not None:
        mask = jnp.arange(s)[None, None, None, :] < kv_len[:, None, None, None]
        logits = jnp.where(mask, logits, -jnp.inf)
    # Fully masked rows (kv_len == 0) have max == -inf; anchor them at 0
    # and guard the normalizer so they emit zeros instead of NaN — the
    # same degenerate case the Pallas kernel masks at flush time.
    mx = logits.max(-1, keepdims=True)
    mx = jnp.where(jnp.isfinite(mx), mx, 0.0)
    p = jnp.exp(logits - mx)
    p = p / jnp.maximum(p.sum(-1, keepdims=True), 1e-30)
    out = jnp.einsum("bkgs,bskd->bkgd", p, v)
    return out.reshape(b, h, dim).astype(q.dtype)


def paged_attention_ref(
    q: jnp.ndarray,          # (B, H, D) one new token
    k_pool: jnp.ndarray,     # (P+1, page, packed_group_words(Hkv*D))
    v_pool: jnp.ndarray,     #   uint32, or (P+1, page, Hkv*D) when bits=0
    table: jnp.ndarray,      # (B, max_pages) int32 physical page ids
    kv_len: jnp.ndarray,     # (B,) valid lengths
    bits: int,
    hkv: int,
) -> jnp.ndarray:
    """Fused paged-attention oracle: gather the pages the table names
    into the dense per-sequence view, then run the dense kernels' exact
    math on it. This IS the pre-fused gather-materialize program
    (``models.attention.gather_kv_pages`` + ``kv_decode_ref`` / the dense
    softmax), which is what makes fused-vs-gather parity checkable down
    to the bit on the jnp backend. Rows gathered through scrap entries
    sit at positions >= ``kv_len`` where the mask zeroes their softmax
    weight exactly, so scrap garbage never leaks into the output."""

    def gather(pool):
        g = jnp.take(pool, table, axis=0)     # (B, mp, page, wd)
        b_, mp, pg = g.shape[0], g.shape[1], g.shape[2]
        return g.reshape((b_, mp * pg) + g.shape[3:])

    kc, vc = gather(k_pool), gather(v_pool)
    if bits:
        return kv_decode_ref(q, kc, vc, bits, hkv, kv_len)
    return dense_decode_ref(q, kc, vc, kv_len, hkv)


def dense_decode_ref(q: jnp.ndarray, k_cache: jnp.ndarray,
                     v_cache: jnp.ndarray, kv_len: jnp.ndarray,
                     hkv: int) -> jnp.ndarray:
    """One-token attention over a dense-dtype cache (B, S, Hkv*D): the
    exact ``models.attention.decode_attention`` program for unpacked KV.
    kv_len == 0 rows (dead slots; live slots always append before they
    attend) emit zeros like the kernel's flush guard, instead of the
    garbage-mean a fully NEG_INF-masked softmax produces. For live rows
    the select passes the identical value through bit-for-bit."""
    b, h, dim = q.shape
    s = k_cache.shape[1]
    group = h // hkv
    kc = k_cache.reshape(b, s, hkv, dim)
    vc = v_cache.reshape(b, s, hkv, dim)
    qg = q.reshape(b, hkv, group, dim).astype(jnp.float32)
    logits = jnp.einsum(
        "bkgd,bskd->bkgs", qg, kc.astype(jnp.float32)
    ) / np.sqrt(dim)
    mask = jnp.arange(s)[None, None, None, :] < kv_len[:, None, None, None]
    logits = jnp.where(mask, logits, -1e30)
    p = jax.nn.softmax(logits, axis=-1)
    out = jnp.einsum("bkgs,bskd->bkgd", p, vc.astype(jnp.float32))
    out = jnp.where((kv_len == 0)[:, None, None], 0.0,
                    out.reshape(b, h, dim))
    return out.astype(q.dtype)
