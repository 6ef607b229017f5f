#!/usr/bin/env python3
"""Chip smoke: the serving main path, end to end, on one TPU.

    python chip_smoke.py               # one chip: phases (a)-(d)
    python chip_smoke.py --four-chips  # the sharded train step, 2x2 mesh

One process, no children. Phases, in order:

  (a) device   — platform, device kind and count, the resolved kernel
                 backend and the compile-cache path; fails unless the
                 platform is ``tpu`` and kernels resolve to compiled Pallas.
  (b) kernels  — every main-path Pallas kernel at qwen3_8b's published
                 shapes against its jnp oracle (the oracle's matmuls at
                 full f32 precision); prints max abs / rel error.
  (c) serve    — ``ServeEngine`` for qwen3_8b at published widths, AF8
                 packed weights, AF16 paged KV through fused paged
                 attention, 4 slots, 8 greedy requests with seeded prompts
                 of 64-700 tokens (chunked prefill crosses page
                 boundaries), 32 new tokens each. Every request completes,
                 every token is inside the vocabulary, the traced decode
                 dispatches onto the fused kernels and nothing falls back.
                 The wall time printed is a smoke timing, not a benchmark.
  (d) backends — one decode step on the served params and state, under
                 the Pallas backend and under the jnp oracle: logits agree
                 within tolerance and every slot's argmax matches.

``--four-chips`` runs only the train step ``launch/train.py`` runs in its
default (dense-param) mode, sharded 2 data x 2 model over four chips, on
qwen3_8b cut to one layer, and compares its per-step losses with the
same steps on one chip. Both runs keep AdamW moments in f32 and leave
gradient compression off: with the config's packed moments, or with its
error-feedback buffers, the one-chip reference does not fit at any depth
(see ``four_chip_config``).

The last line of standard output is one JSON object,
``{"ok": true, "device": {...}}``, printed only when every phase passed.
Any failure exits non-zero, and there is no CPU path: without a TPU the
script stops in phase (a). The phase functions take the config, sizes
and interpret flag as arguments so the CPU tests can drive (b)-(d) at
``.reduced()`` size in Pallas interpret mode.
"""
from __future__ import annotations

import argparse
import dataclasses
import functools
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro import compat  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.kernels import ref as kref  # noqa: E402
from repro.kernels.kv_decode import kv_decode  # noqa: E402
from repro.kernels.packed_matmul import (  # noqa: E402
    packed_matmul,
    packed_matmul_batched,
)
from repro.kernels.paged_attention import paged_attention  # noqa: E402
from repro.kernels.take import take_rows  # noqa: E402

# max |kernel - oracle| / max |oracle|: the kernels accumulate in f32 in
# another order than the oracle, and the chip's MXU may take f32 operands
# in bf16 passes; a layout or decode bug is O(1).
KERNEL_TOL = 1e-2
# the same ratio over a whole decode step's logits (36 layers of it)
LOGIT_TOL = 5e-2
# four-chip vs one-chip loss, as tests/test_distributed.py holds it
LOSS_REL_TOL = 5e-3


def log(msg: str) -> None:
    print(msg, flush=True)


class PhaseError(RuntimeError):
    """A phase's check failed."""


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise PhaseError(msg)


def device_line(count: int) -> dict:
    dev = jax.devices()[0]
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": count}


# --------------------------------------------------------------------- (a)
def phase_device(cache_dir: str) -> dict:
    devs = jax.devices()
    backend = kops.BACKEND.resolved_mode
    info = {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs), "kernel_backend": backend,
            "compile_cache": cache_dir}
    log(f"(a) device: {json.dumps(info)}")
    _check(devs[0].platform == "tpu",
           f"no TPU: JAX found {devs[0].platform!r} devices")
    _check(backend == "pallas",
           f"kernel backend resolved to {backend!r}, not compiled pallas")
    return info


# --------------------------------------------------------------------- (b)
def _packed(key, shape, scale, bits):
    """Seeded normal values packed at ``bits`` (dense when 0), made in one
    jitted call: eager ops at vocabulary size would keep every
    intermediate of the encode alive at once."""
    def make(k):
        x = jax.random.normal(k, shape, jnp.float32) * scale
        return kref.pack_ref(x, bits) if bits else x
    return jax.jit(make)(key)


def _oracle(fn, *args, **static):
    """``fn(*args, **static)`` jitted, its matmuls at full f32 precision."""
    with jax.default_matmul_precision("float32"):
        return jax.jit(functools.partial(fn, **static))(*args)


def _rel_err(got, want) -> tuple:
    got = np.asarray(got, np.float32)
    want = np.asarray(want, np.float32)
    err = float(np.abs(got - want).max())
    return err, err / max(float(np.abs(want).max()), 1e-30)


def phase_kernels(cfg, interpret: bool = False, slots: int = 4,
                  seq: int = 2048, page: int = 16, seed: int = 0) -> dict:
    """Each main-path kernel at ``cfg``'s widths against its oracle.
    Returns {case: (max_abs, max_rel)}; raises past tolerance."""
    d, ff, vocab = cfg.d_model, cfg.d_ff, cfg.vocab_size
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.resolved_head_dim
    dt = cfg.compute_dtype
    keys = iter(jax.random.split(jax.random.PRNGKey(seed), 64))
    results = {}

    def record(name, got, want, exact=False):
        jax.block_until_ready(got)
        err, rel = _rel_err(got, want)
        results[name] = (err, rel)
        log(f"(b) {name}: max_abs={err:.3e} max_rel={rel:.3e}")
        _check(np.isfinite(np.asarray(got, np.float32)).all(),
               f"{name}: non-finite kernel output")
        _check(err == 0.0 if exact else rel <= KERNEL_TOL,
               f"{name}: error {rel:.3e} past tolerance")

    x = _packed(next(keys), (slots, d), 1.0, 0).astype(dt)
    for bits in (8, 12, 16):
        w = _packed(next(keys), (d, ff), d ** -0.5, bits)
        got = packed_matmul(x, w, bits, ff, interpret=interpret)
        want = _oracle(kref.packed_matmul_ref, x, w, bits=bits, n=ff)
        record(f"packed_matmul AF{bits} {d}x{ff}", got, want)
        del w
        table = _packed(next(keys), (vocab, d), 0.02, bits)
        got = packed_matmul(x, table, bits, vocab, transpose=True,
                            interpret=interpret)
        want = _oracle(kref.packed_matmul_ref, x, table, bits=bits,
                       n=vocab, transpose=True)
        record(f"packed_matmul^T AF{bits} {vocab}x{d}", got, want)
        if bits in (8, 16):
            idx = jax.random.randint(next(keys), (slots * 16,), 0, vocab)
            got = take_rows(table, idx, bits, d, out_dtype=dt,
                            interpret=interpret)
            want = _oracle(kref.take_rows_ref, table, idx, bits=bits, n=d,
                           out_dtype=dt)
            record(f"take_rows AF{bits} {vocab}x{d}", got, want,
                   exact=True)
        del table

    experts, eff = 8, max(ff // 8, 32)
    xe = _packed(next(keys), (experts, slots, d), 1.0, 0).astype(dt)
    for bits in (8, 16):
        bank = _packed(next(keys), (experts, d, eff), d ** -0.5, bits)
        got = packed_matmul_batched(xe, bank, bits, eff,
                                    interpret=interpret)
        want = _oracle(kref.packed_matmul_batched_ref, xe, bank, bits=bits,
                       n=eff)
        record(f"packed_matmul_batched AF{bits} {experts}x{d}x{eff}",
               got, want)

    q = _packed(next(keys), (slots, h, hd), 1.0, 0).astype(dt)
    lens = jax.random.randint(next(keys), (slots,), 1, seq + 1)
    n_pages = slots * seq // page
    table = (1 + jax.random.permutation(next(keys), n_pages)).reshape(
        slots, seq // page).astype(jnp.int32)
    for bits in (0, 8, 16):
        pools = [_packed(next(keys), (n_pages + 1, page, hkv * hd), 1.0,
                         bits) for _ in range(2)]
        if not bits:
            pools = [p.astype(dt) for p in pools]
        got = paged_attention(q, *pools, table, lens, bits, hkv,
                              interpret=interpret)
        want = _oracle(kref.paged_attention_ref, q, *pools, table, lens,
                       bits=bits, hkv=hkv)
        record(f"paged_attention AF{bits or 'dense'} "
               f"pool {n_pages + 1}x{page}", got, want)
        if bits:
            cache = [p[1:].reshape(slots, seq, -1) for p in pools]
            got = kv_decode(q, *cache, lens, bits, hkv,
                            interpret=interpret)
            want = _oracle(kref.kv_decode_ref, q, *cache, bits=bits,
                           hkv=hkv, kv_len=lens)
            record(f"kv_decode AF{bits} {slots}x{seq}", got, want)
    return results


# --------------------------------------------------------------------- (c)
def serve_config(cfg):
    """AF8 packed weights; KV stays at the config's width."""
    return dataclasses.replace(cfg, compression=dataclasses.replace(
        cfg.compression, weight_bits=8))


def phase_serve(cfg, n_requests: int = 8, prompt_range=(64, 700),
                new_tokens: int = 32, max_seq_len: int = 1024,
                page: int = 16, slots: int = 4, seed: int = 0):
    """Serve ``n_requests`` greedy requests through the paged, fused,
    AF8-packed engine. Returns (engine, report); raises on a failed
    check."""
    from repro.serving import ServeEngine

    rec0 = len(kops.DISPATCH_RECORDS)
    fb0 = len(kops.FALLBACK_RECORDS)
    t0 = time.perf_counter()
    eng = ServeEngine(serve_config(cfg), max_seq_len=max_seq_len,
                      max_slots=slots, pack_weights=True, paged=True,
                      kv_page_size=page, paged_attn=True, greedy=True,
                      sample_seed=seed)
    jax.block_until_ready(eng.params)
    init_s = time.perf_counter() - t0
    log(f"(c) engine up in {init_s:.1f}s: {eng.n_slots} slots, "
        f"{eng.weight_read_bytes / 2**30:.3f} GiB packed weights")
    rng = np.random.default_rng(seed)
    lens = rng.integers(prompt_range[0], prompt_range[1] + 1, n_requests)
    rids = [eng.submit(rng.integers(1, cfg.vocab_size, n).tolist(),
                       max_new_tokens=new_tokens) for n in lens]
    t1 = time.perf_counter()
    stats = eng.run_until_drained()
    wall = time.perf_counter() - t1
    outs = [eng.result(r) for r in rids]
    _check(all(o is not None and len(o) == new_tokens for o in outs),
           f"not every request completed: {[o and len(o) for o in outs]}")
    _check(all(0 <= t < cfg.vocab_size for o in outs for t in o),
           "a token outside the vocabulary")
    paths = {(r.op, r.path)
             for r in list(kops.DISPATCH_RECORDS)[rec0:]}
    want = {("packed_matmul", "fused"), ("paged_attention", "fused_paged"),
            ("take_rows", "take")}
    _check(want <= paths, f"decode missed fused paths: {want - paths}")
    # the one-pass prefill reads the gathered view by design; decode's
    # own trace must not
    rec1 = len(kops.DISPATCH_RECORDS)
    jax.make_jaxpr(eng.lm.decode_step)(
        eng.params, eng.state, jnp.zeros((eng.n_slots, 1), jnp.int32))
    _check(all(r.op != "gather_kv_pages"
               for r in list(kops.DISPATCH_RECORDS)[rec1:]),
           "paged decode materialized the gathered cache")
    fallbacks = list(kops.FALLBACK_RECORDS)[fb0:]
    _check(not fallbacks, f"fallback records: {fallbacks[:3]}")
    stats_mem = jax.devices()[0].memory_stats() or {}
    report = {
        "requests": n_requests, "prompt_lens": [int(n) for n in lens],
        "tokens": stats["tokens"], "ticks": stats["ticks"],
        "prefill_calls": stats["prefill_calls"],
        "decode_calls": stats["decode_calls"],
        "smoke_wall_s": wall, "init_s": init_s,
        "peak_bytes_in_use": stats_mem.get("peak_bytes_in_use"),
        "dispatch_paths": sorted(f"{o}/{p}" for o, p in paths),
    }
    log(f"(c) serve: {json.dumps(report)} (smoke timing, not a benchmark)")
    return eng, report


# --------------------------------------------------------------------- (d)
def phase_backends(eng, n_prompts: int = 4, prompt_range=(40, 200),
                   seed: int = 1) -> dict:
    """One decode step on the engine's params and a freshly prefilled
    state, under the engine's Pallas backend and under the jnp oracle."""
    cfg = eng.cfg
    rng = np.random.default_rng(seed)
    for n in rng.integers(prompt_range[0], prompt_range[1] + 1, n_prompts):
        eng.submit(rng.integers(1, cfg.vocab_size, int(n)).tolist(),
                   max_new_tokens=1)
    toks = jnp.asarray(rng.integers(1, cfg.vocab_size, (eng.n_slots, 1)),
                       jnp.int32)
    mode = kops.BACKEND.mode
    try:
        logits_k, _ = jax.jit(eng.lm.decode_step)(eng.params, eng.state,
                                                  toks)
        kops.set_backend("jnp")
        with jax.default_matmul_precision("float32"):
            logits_r, _ = jax.jit(
                lambda p, s, t: eng.lm.decode_step(p, s, t))(
                    eng.params, eng.state, toks)
    finally:
        kops.set_backend(mode)
    err, rel = _rel_err(logits_k, logits_r)
    am_k = np.asarray(jnp.argmax(logits_k[:, 0], -1))
    am_r = np.asarray(jnp.argmax(logits_r[:, 0], -1))
    report = {"max_abs": err, "max_rel": rel,
              "argmax_kernel": am_k.tolist(), "argmax_oracle": am_r.tolist()}
    log(f"(d) backends: {json.dumps(report)}")
    _check(np.isfinite(np.asarray(logits_k, np.float32)).all(),
           "non-finite logits")
    _check(rel <= LOGIT_TOL, f"logits differ by {rel:.3e}")
    _check((am_k == am_r).all(), "argmax differs between backends")
    eng.run_until_drained()
    return report


# ------------------------------------------------------------- four chips
def train_losses(cfg, devices, mesh_shape, steps: int = 3,
                 seq_len: int = 128, global_batch: int = 8,
                 grad_compress_bits=None) -> list:
    """Per-step losses of the dense-param train step ``launch/train.py``
    runs (``train.loop.make_train_step``), with params, optimizer state
    and batch sharded over a (data, model) mesh of ``devices``."""
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.data import SyntheticTokens
    from repro.distributed.grad_compress import init_error_feedback
    from repro.launch.steps import _batch_shardings, _tree_shardings
    from repro.models.lm import LM
    from repro.optim import AdamWConfig, adamw_init
    from repro.train.loop import TrainConfig, make_train_step

    tc = TrainConfig(steps=steps, seq_len=seq_len, global_batch=global_batch,
                     grad_compress_bits=grad_compress_bits)
    lm = LM(cfg)
    comp = cfg.compression
    ocfg = AdamWConfig(lr=tc.lr, m_bits=comp.opt_m_bits,
                       v_bits=comp.opt_v_bits)
    data = SyntheticTokens(vocab_size=cfg.vocab_size, seq_len=seq_len,
                           global_batch=global_batch, seed=tc.seed)
    mesh = compat.make_mesh(mesh_shape, ("data", "model"), devices=devices)
    key = compat.prng_key(tc.seed)
    with compat.mesh_context(mesh):
        shapes = jax.eval_shape(lm.init, key)
        p_sh = _tree_shardings(shapes, mesh, "tp")
        o_sh = _tree_shardings(
            jax.eval_shape(lambda p: adamw_init(p, ocfg), shapes), mesh,
            "tp")
        rep = NamedSharding(mesh, P())
        ef_sh = p_sh if grad_compress_bits else rep
        feed0 = data.batch_at(0).as_dict()
        b_sh = _batch_shardings(
            {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
             for k, v in feed0.items()}, mesh)
        params = jax.jit(lm.init, out_shardings=p_sh)(key)
        opt = jax.jit(lambda p: adamw_init(p, ocfg), out_shardings=o_sh)(
            params)
        ef = (jax.jit(init_error_feedback, out_shardings=ef_sh)(params)
              if grad_compress_bits else jnp.zeros((), jnp.float32))
        step_fn = jax.jit(make_train_step(lm, ocfg, tc),
                          in_shardings=(p_sh, o_sh, ef_sh, b_sh, rep),
                          out_shardings=(p_sh, o_sh, ef_sh, rep),
                          donate_argnums=(0, 1, 2))
        losses = []
        for step in range(steps):
            feed = jax.device_put(data.batch_at(step).as_dict(), b_sh)
            params, opt, ef, loss = step_fn(params, opt, ef, feed,
                                            jnp.int32(step))
            losses.append(float(loss))
    return losses


FOUR_CHIP_LAYERS = 1   # qwen3_8b cut in depth only: what one chip holds


def four_chip_config(cfg, layers: int = FOUR_CHIP_LAYERS):
    """qwen3_8b at published widths, ``layers`` deep, with f32 AdamW
    moments. The embedding and head alone hold 1.24 B parameters; with
    the config's AF16-packed moments one layer needs 16.2 GiB on a v5e
    (their decode/encode temporaries outgrow the bytes packing saves),
    so the one-chip reference would not fit at any depth."""
    return dataclasses.replace(
        cfg, n_layers=layers, compression=dataclasses.replace(
            cfg.compression, opt_m_bits=None, opt_v_bits=None))


def phase_four_chips(cfg, layers: int = FOUR_CHIP_LAYERS,
                     steps: int = 3) -> dict:
    devs = jax.devices()
    _check(devs[0].platform == "tpu" and len(devs) >= 4,
           f"--four-chips needs 4 TPU chips, found {len(devs)} "
           f"{devs[0].platform!r} devices")
    cut = four_chip_config(cfg, layers)
    t0 = time.perf_counter()
    four = train_losses(cut, devs[:4], (2, 2), steps)
    t1 = time.perf_counter()
    one = train_losses(cut, devs[:1], (1, 1), steps)
    t2 = time.perf_counter()
    rel = [abs(a - b) / abs(b) for a, b in zip(four, one)]
    report = {"layers": layers, "steps": steps, "losses_2x2": four,
              "losses_1chip": one, "rel": rel,
              "wall_s_2x2": t1 - t0, "wall_s_1chip": t2 - t1}
    log(f"four-chips: {json.dumps(report)}")
    _check(all(np.isfinite(four + one)), "non-finite loss")
    _check(max(rel) < LOSS_REL_TOL,
           f"2x2 vs one-chip losses differ by {max(rel):.3e}")
    return report


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="only the sharded train step on a 2x2 mesh of "
                         "four chips vs. the same steps on one chip")
    args = ap.parse_args()
    cache_dir = compat.enable_compile_cache()
    cfg = get_config("qwen3_8b")
    info = phase_device(cache_dir)
    if args.four_chips:
        phase_four_chips(cfg)
        count = 4
    else:
        phase_kernels(cfg)
        eng, _ = phase_serve(cfg)
        phase_backends(eng)
        count = info["count"]
    print(json.dumps({"ok": True, "device": device_line(count)}),
          flush=True)


if __name__ == "__main__":
    try:
        main()
    except PhaseError as e:
        print(f"chip smoke FAILED: {e}", file=sys.stderr, flush=True)
        sys.exit(1)
