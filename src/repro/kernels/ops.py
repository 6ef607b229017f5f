"""Unified kernel dispatch: Pallas TPU kernels vs. the jnp reference path.

Models call these wrappers; a single ``KernelBackend`` switch selects
between the fused Pallas kernels (TPU, or interpret-mode validation) and
the pure-jnp oracle (used for the multi-device dry-run, where XLA lowers
the same bit arithmetic on any backend). The numerics are identical by
construction — the kernels reuse the oracle's bit manipulation.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Optional, Tuple

import jax
import jax.numpy as jnp

from repro import compat, obs
from repro.kernels import ref as _ref


@dataclasses.dataclass(frozen=True)
class KernelBackend:
    """``mode``: "auto" (feature-detect at first use), "jnp" (XLA
    everywhere), "pallas_interpret" (CPU validation), or "pallas" (real
    TPU). "auto" resolves through ``repro.compat.pallas`` — compiled
    Pallas when a TPU backend is present, the jnp oracle otherwise —
    lazily, so importing this module never initializes the JAX backend
    (multi-host launchers must be able to call
    ``jax.distributed.initialize`` after importing repro modules)."""

    mode: str = "auto"

    @property
    def resolved_mode(self) -> str:
        if self.mode == "auto":
            return compat.default_kernel_mode()
        return self.mode

    @property
    def use_pallas(self) -> bool:
        return self.resolved_mode in ("pallas", "pallas_interpret")

    @property
    def interpret(self) -> bool:
        return self.resolved_mode != "pallas"


BACKEND = KernelBackend()


def set_backend(mode: str) -> None:
    global BACKEND
    BACKEND = KernelBackend(mode)


@dataclasses.dataclass(frozen=True)
class DispatchRecord:
    """One structured trace-time dispatch (or fallback) decision.

    The static linter (``repro.analysis``) queries these to prove every
    planned leaf hit a fused kernel — and, for fallbacks, to report
    *which* spec/shape fell off the fused path instead of today's
    warn-once. The leaf path is not known at the kernel call site (the
    models pass bare arrays); records carry the logical shape/width so
    the linter resolves candidate leaf paths by matching them against
    the plan."""

    op: str                               # packed_matmul | unpack | ...
    path: str                             # fused | materialized | ...
    shape: Tuple[int, ...] = ()           # logical operand shape
    bits: int = 0                         # packed width (0 = unpacked)
    spec: str = ""                        # normalized einsum spec, if any
    reason: str = ""                      # fallbacks: why it fell off


# Bounded trace-time record streams. Like the dispatch counters these
# grow only when a new program is traced (cached jit re-executions do not
# re-dispatch), but deques keep a long-lived process with many traced
# shapes bounded anyway. The linter snapshots + diffs them around its
# own tracing, so a maxlen eviction can only drop *other* programs'
# records, never the ones inside the lint window.
DISPATCH_RECORDS: collections.deque = collections.deque(maxlen=4096)
FALLBACK_RECORDS: collections.deque = collections.deque(maxlen=4096)


def record_fallback(op: str, spec: str = "", shape: Tuple[int, ...] = (),
                    bits: int = 0, reason: str = "") -> None:
    """A packed operand leaving the fused path: structurally recorded
    (queryable by the linter) and counted with a reason label."""
    FALLBACK_RECORDS.append(DispatchRecord(
        op=op, path="fallback", shape=tuple(int(s) for s in shape),
        bits=int(bits), spec=spec, reason=reason))
    obs.REGISTRY.counter(
        "kernel_fallback_total",
        "Packed operands that fell off the fused path (trace-time).",
    ).inc(1, op=op, reason=reason or "unknown")


def record_dispatch(op: str, path: str, packed_bytes: int = 0,
                    shape: Tuple[int, ...] = (), bits: int = 0) -> None:
    """Dispatch telemetry: one count (and the packed operand's analytic
    weight-read bytes) per *dispatch decision*, labeled by path — fused,
    fused_batched, materialized, fallback, take, kv_decode. These
    increment at **trace time**: under jit a cached trace re-executes
    without re-dispatching, so the counters report which path each
    compiled program took (the bench-only fused-vs-materialized split as
    a live metric), while per-execution byte accounting lives with the
    callers that count executions (ServeEngine/Trainer)."""
    DISPATCH_RECORDS.append(DispatchRecord(
        op=op, path=path, shape=tuple(int(s) for s in shape),
        bits=int(bits)))
    obs.REGISTRY.counter(
        "kernel_dispatch_total",
        "Kernel dispatch decisions by op and path (trace-time).",
    ).inc(1, op=op, path=path)
    if packed_bytes:
        obs.REGISTRY.counter(
            "kernel_dispatch_packed_bytes",
            "Analytic packed weight-read bytes per dispatch (trace-time).",
        ).inc(int(packed_bytes), op=op, path=path)


def unpack(packed, bits: int, n: int, out_dtype=jnp.float32):
    """Materialized unpack + decode. XLA is the only implementation: the
    lane-tiled shifts fuse into the consumer, where a standalone kernel
    would only add an HBM round trip of the decoded tensor."""
    record_dispatch("unpack", "materialized", packed.size * 4,
                    shape=packed.shape[:-1] + (n,), bits=bits)
    return _ref.unpack_ref(packed, bits, n, out_dtype)


def pack(x, bits: int):
    """Encode + pack along the last axis (the KV append and the weight
    packer). XLA only, for the same reason as :func:`unpack`."""
    record_dispatch("pack", "encode")
    return _ref.pack_ref(x, bits)


def take_rows(packed, indices, bits: int, n: int, kind: str = "float",
              signed: bool = True, out_dtype=jnp.float32):
    """Gather rows of a 2-D packed payload by index and decode only the
    gathered rows (the packed ``embed`` path). On the Pallas backends each
    row is DMA'd by a scalar-prefetched index and decoded in VMEM; the
    jnp oracle is the same gather+decode in XLA."""
    record_dispatch("take_rows", "take",
                    shape=packed.shape[:-1] + (n,), bits=bits)
    if BACKEND.use_pallas and packed.ndim == 2 and indices.ndim == 1:
        from repro.kernels.take import take_rows as _k
        return _k(packed, indices, bits, n, kind=kind, signed=signed,
                  out_dtype=out_dtype, interpret=BACKEND.interpret)
    return _ref.take_rows_ref(packed, indices, bits, n, kind, signed,
                              out_dtype)


def packed_matmul(x, w_packed, bits: int, n: int, transpose: bool = False):
    """Fused unpack+matmul (the models' packed-weight hot path). The
    kernel flattens leading batch dims itself; ``transpose`` selects
    contraction over the packed axis (tied ``unembed``)."""
    record_dispatch("packed_matmul", "fused", w_packed.size * 4,
                    shape=w_packed.shape, bits=bits)
    if BACKEND.use_pallas:
        from repro.kernels.packed_matmul import packed_matmul as _k
        return _k(x, w_packed, bits, n, transpose=transpose,
                  interpret=BACKEND.interpret)
    return _ref.packed_matmul_ref(x, w_packed, bits, n, transpose)


def packed_matmul_batched(x, w_packed, bits: int, n: int,
                          transpose: bool = False):
    """Fused unpack+matmul over a leading expert axis (the MoE expert-bank
    hot path): x (E, C, K), w_packed (E, K, n*bits/32) uint32 (or
    (E, n, K*bits/32) when ``transpose``) -> (E, C, n)."""
    record_dispatch("packed_matmul_batched", "fused_batched",
                    w_packed.size * 4, shape=w_packed.shape, bits=bits)
    if BACKEND.use_pallas:
        from repro.kernels.packed_matmul import (
            packed_matmul_batched as _k,
        )
        return _k(x, w_packed, bits, n, transpose=transpose,
                  interpret=BACKEND.interpret)
    return _ref.packed_matmul_batched_ref(x, w_packed, bits, n, transpose)


def packed_matmul_dw(x, g, transpose: bool = False, batched: bool = False):
    """Weight cotangent of the fused matmul, from residuals alone. No
    Pallas kernel exists (or is needed): there is no packed operand to
    stream — dW contracts the saved x against the upstream cotangent g
    without ever touching W, so XLA's plain dot is already the fused
    form. This is the backward's "packed-aware" accumulation: the only
    weight bytes a train step reads are the packed words the forward and
    the dx kernels stream."""
    return _ref.packed_matmul_dw_ref(x, g, transpose, batched)


def kv_decode(q, k_packed, v_packed, kv_len, bits: int, hkv: int):
    """One-token attention over packed KV caches (B, S, words): one
    packed row per token holds all ``hkv`` heads."""
    record_dispatch("kv_decode", "kv_decode",
                    (k_packed.size + v_packed.size) * 4,
                    shape=k_packed.shape, bits=bits)
    if BACKEND.use_pallas:
        from repro.kernels.kv_decode import kv_decode as _k
        return _k(q, k_packed, v_packed, kv_len, bits, hkv,
                  interpret=BACKEND.interpret)
    return _ref.kv_decode_ref(q, k_packed, v_packed, bits, hkv, kv_len)


def paged_attention(q, k_pool, v_pool, table, kv_len, bits: int, hkv: int,
                    fallback: bool = False):
    """Attend one token straight through the page table (the fused paged
    serving hot path): pools (P+1, page, words) of packed rows holding
    all ``hkv`` heads (or (P+1, page, Hkv*D) dense rows when ``bits`` is
    0), table (B, max_pages) int32 page ids. Only the pages the table
    names leave HBM — the dense gathered view never materializes.
    ``fallback=True`` is the parity escape hatch: it runs the
    gather-materialize oracle instead and records itself as such, so the
    dispatch linter can tell a deliberate oracle run from a fused path
    that silently de-fused. ``packed_bytes`` stays 0 on the fused
    record: bytes-read scale with pages actually live, which only the
    serving layer knows (``kv_pages_read`` counters), not the pool
    size."""
    if fallback:
        record_dispatch("paged_attention", "materialized",
                        shape=k_pool.shape, bits=bits)
        return _ref.paged_attention_ref(q, k_pool, v_pool, table, kv_len,
                                        bits, hkv)
    record_dispatch("paged_attention", "fused_paged",
                    shape=k_pool.shape, bits=bits)
    if BACKEND.use_pallas:
        from repro.kernels.paged_attention import paged_attention as _k
        return _k(q, k_pool, v_pool, table, kv_len, bits, hkv,
                  interpret=BACKEND.interpret)
    return _ref.paged_attention_ref(q, k_pool, v_pool, table, kv_len,
                                    bits, hkv)
