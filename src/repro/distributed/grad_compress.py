"""Compressed data-parallel gradient reduction (beyond-paper optimization).

The dominant collective in data-parallel training is the gradient
all-reduce: 2 * (D-1)/D * N * 4 bytes per step at f32. Packing gradient
lanes into a Table 3 format before they cross ICI scales the wire bytes by
bits/32 — the register-file insight applied to the interconnect.

Implementation: a **ring reduce-scatter over encoded lanes** followed by
an all-gather of the reduced codes, built from ``jax.lax.ppermute`` inside
``shard_map`` (manual over the DP axis, auto over everything else):

    hop h:  send my running chunk c-h as codes -> neighbour decodes,
            adds its local contribution, re-encodes.

Per-hop requantization noise is bounded by the format's epsilon and is
absorbed by **error feedback**: each device keeps the residual between its
local f32 contribution and what it actually transmitted, and adds it to
the next step's gradient. (EF-SGD, Karimireddy et al. 2019 — the standard
fix; the paper's own quality-threshold framing justifies the width.)

Wire bytes per step: 2 * (D-1)/D * N * bits/8  (vs. 8*(D-1)/D*N at f32).
"""
from __future__ import annotations

import functools
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.compat import axis_size, tree_flatten, tree_map
from repro.core import bitpack
from repro.core.formats import FLOAT_FORMATS, decode_float, encode_float


def _encode(x: jnp.ndarray, bits: int) -> jnp.ndarray:
    return bitpack.pack_groups(
        encode_float(x, FLOAT_FORMATS[bits]), bits
    )


def _decode(words: jnp.ndarray, bits: int, n: int) -> jnp.ndarray:
    return decode_float(
        bitpack.unpack_groups(words, bits, n), FLOAT_FORMATS[bits]
    )


def ring_reduce_codes(
    x: jnp.ndarray,             # (D*chunk,) local f32 contribution
    axis_name: str,
    bits: int,
) -> jnp.ndarray:
    """All-reduce(sum) of ``x`` over ``axis_name`` moving only codes.

    Call inside shard_map with the DP axis manual. Requires len(x) to be
    divisible by D*32. Each chunk packs (and decodes) on its own, so
    chunks travel as self-contained lane-tiled payloads.
    """
    d = axis_size(axis_name)
    idx = jax.lax.axis_index(axis_name)
    n = x.shape[0]
    chunk = n // d
    xc = x.reshape(d, chunk)

    perm = [(i, (i + 1) % d) for i in range(d)]

    # Reduce-scatter: after D-1 hops, device i holds the full sum of
    # chunk (i+1) mod d. Accumulation happens in f32; only codes travel.
    def hop(h, acc_chunk):
        # acc_chunk: the running partial sum this device forwards.
        codes = _encode(acc_chunk, bits)
        codes = jax.lax.ppermute(codes, axis_name, perm)
        received = _decode(codes, bits, chunk)
        # chunk index this device must now contribute to:
        ci = (idx - h + d - 1) % d
        return received + jax.lax.dynamic_index_in_dim(
            xc, ci, axis=0, keepdims=False
        )

    acc = jax.lax.dynamic_index_in_dim(xc, idx, axis=0, keepdims=False)
    for h in range(d - 1):
        acc = hop(h, acc)
    # acc now equals sum over devices of chunk (idx+1) mod d.
    own_chunk_idx = (idx + 1) % d

    # All-gather of reduced codes (one more ring pass of D-1 hops).
    my_codes = _encode(acc, bits)
    gathered = [(own_chunk_idx, my_codes)]
    cur_idx, cur = own_chunk_idx, my_codes
    for _ in range(d - 1):
        cur = jax.lax.ppermute(cur, axis_name, perm)
        cur_idx = (cur_idx - 1) % d
        gathered.append((cur_idx, cur))

    # Reassemble in chunk order. Chunk ids differ per device (traced), so
    # scatter via one-hot sum (d is small and static).
    words = my_codes.shape[0]
    out = jnp.zeros((d, words), jnp.uint32)
    for ci, codes in gathered:
        onehot = (jnp.arange(d) == ci).astype(jnp.uint32)[:, None]
        out = out + onehot * codes[None, :]
    return _decode(out, bits, chunk).reshape(-1)


def compressed_psum(
    x: jnp.ndarray, axis_name: str, bits: Optional[int]
) -> jnp.ndarray:
    """Drop-in psum: exact f32 psum when bits is None/32."""
    if not bits or bits >= 32:
        return jax.lax.psum(x, axis_name)
    d = axis_size(axis_name)
    n = x.size
    quantum = d * bitpack.GROUP
    pad = (-n) % quantum
    flat = x.astype(jnp.float32).reshape(-1)
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    out = ring_reduce_codes(flat, axis_name, bits)
    return out[:n].reshape(x.shape)


def apply_error_feedback(
    grads, residual, bits: Optional[int]
) -> Tuple[object, object]:
    """g' = g + residual; residual' = g' - qdq(g'). Per-leaf f32."""
    if not bits or bits >= 32:
        return grads, residual

    fmt = FLOAT_FORMATS[bits]

    def leaf(g, r):
        gf = g.astype(jnp.float32) + r
        q = decode_float(encode_float(gf, fmt), fmt)
        return q.astype(g.dtype), gf - q

    flat_g, treedef = tree_flatten(grads)
    flat_r = treedef.flatten_up_to(residual)
    pairs = [leaf(g, r) for g, r in zip(flat_g, flat_r)]
    return (treedef.unflatten([p[0] for p in pairs]),
            treedef.unflatten([p[1] for p in pairs]))


def init_error_feedback(params):
    return tree_map(lambda p: jnp.zeros(p.shape, jnp.float32), params)
