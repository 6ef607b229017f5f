"""Microbenchmarks of the compression data paths (wall-clock on CPU).

Times the jnp reference path under jit (what the dry-run lowers) and
derives effective pack/unpack GB/s — the Value Extractor/Truncator
bandwidth analogue. Pallas interpret mode is correctness-only (Python
interpreter speed), so it is excluded from timing.
"""
from __future__ import annotations

import time
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ref as R


def _time(fn, *args, iters: int = 20) -> float:
    fn(*args)[0].block_until_ready() if isinstance(fn(*args), tuple) else \
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters


def bench_micro() -> List[Tuple[str, float, str]]:
    rows = []
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((256, 4096)).astype(np.float32))
    n_bytes = x.size * 4
    for bits in (8, 16, 24):
        packf = jax.jit(lambda a, b=bits: R.pack_ref(a, b))
        us = _time(packf, x) * 1e6
        rows.append((
            f"micro.pack_af{bits}", us,
            f"{n_bytes / (us * 1e-6) / 1e9:.2f}GB/s",
        ))
        packed = packf(x)
        unpackf = jax.jit(
            lambda p, b=bits: R.unpack_ref(p, b, 4096))
        us = _time(unpackf, packed) * 1e6
        rows.append((
            f"micro.unpack_af{bits}", us,
            f"{n_bytes / (us * 1e-6) / 1e9:.2f}GB/s",
        ))

    # fused packed matmul vs dense (f32) matmul, per Table 3 width, with
    # the per-call weight-read bytes (the bits/32 saving the fused kernel
    # realizes on hardware)
    m, k, n = 128, 1024, 1024
    a = jnp.asarray(rng.standard_normal((m, k)).astype(np.float32))
    w = jnp.asarray(rng.standard_normal((k, n)).astype(np.float32) * 0.1)
    dense = jax.jit(lambda a_, w_: a_ @ w_)
    us_d = _time(dense, a, w) * 1e6
    for bits in (8, 16, 24):
        wp = R.pack_ref(w, bits)
        pmm = jax.jit(
            lambda a_, p_, b=bits: R.packed_matmul_ref(a_, p_, b, n))
        us_p = _time(pmm, a, wp) * 1e6
        rows.append((
            f"micro.packed_matmul_af{bits}", us_p,
            f"dense_ratio={us_p / us_d:.2f};wbytes={wp.size * 4}",
        ))
    rows.append(("micro.dense_matmul_f32", us_d, f"wbytes={w.size * 4}"))

    # transposed orientation (the tied-unembed spec: contract over the
    # packed axis), same geometry
    wt = jnp.asarray(rng.standard_normal((n, k)).astype(np.float32) * 0.1)
    for bits in (8, 16):
        wtp = R.pack_ref(wt, bits)
        pmmt = jax.jit(
            lambda a_, p_, b=bits: R.packed_matmul_ref(a_, p_, b, n, True))
        us_t = _time(pmmt, a, wtp) * 1e6
        rows.append((
            f"micro.packed_matmul_t_af{bits}", us_t,
            f"dense_ratio={us_t / us_d:.2f};wbytes={wtp.size * 4}",
        ))

    # packed KV decode step vs unpacked
    b, h, hkv, d, s = 4, 16, 4, 128, 2048
    q = jnp.asarray(rng.standard_normal((b, h, d)).astype(np.float32))
    kc = jnp.asarray(
        rng.standard_normal((b, s, hkv * d)).astype(np.float32) * 0.3)
    vc = jnp.asarray(
        rng.standard_normal((b, s, hkv * d)).astype(np.float32) * 0.3)
    lens = jnp.full((b,), s, jnp.int32)
    kp, vp = R.pack_ref(kc, 16), R.pack_ref(vc, 16)
    f_packed = jax.jit(
        lambda q_, k_, v_, l_: R.kv_decode_ref(q_, k_, v_, 16, hkv, l_))
    us_pk = _time(f_packed, q, kp, vp, lens) * 1e6
    rows.append(("micro.kv_decode_packed16", us_pk,
                 f"kv_bytes={kp.size * 4 * 2}"))
    return rows
