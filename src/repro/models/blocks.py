"""Per-family transformer blocks: init + train apply + single-token decode.

Every block follows the same convention:
  * ``init_*(rng, cfg) -> params dict`` (unstacked; the LM stacks L copies
    for scan),
  * ``*_apply(params, x, ...) -> x`` for train/prefill,
  * ``*_decode(params, x, state, ...) -> (x, state)`` for one token.
Weights may be PackedTensor leaves — ``layers.linear`` dispatches.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import constrain
from repro.models import layers as L
from repro.models.attention import (
    append_kv_cache_rows,
    append_kv_pool_rows,
    decode_attention,
    flash_attention,
    gather_kv_pages,
    paged_decode_attention,
    prefill_attention,
    update_kv_cache,
)
from repro.models.config import ModelConfig


def _split(rng, n):
    return jax.random.split(rng, n)


# ---------------------------------------------------------------------------
# Dense attention block (phi3 / granite / stablelm / qwen3 / whisper / vlm)
# ---------------------------------------------------------------------------

def init_attention(rng, cfg: ModelConfig, cross: bool = False) -> Dict:
    d, hd = cfg.d_model, cfg.resolved_head_dim
    h, hkv = cfg.n_heads, cfg.n_kv_heads
    dt = cfg.dtype
    ks = _split(rng, 6)
    p = {
        "wq": L.init_dense(ks[0], (d, h * hd), dtype=dt),
        "wk": L.init_dense(ks[1], (d, hkv * hd), dtype=dt),
        "wv": L.init_dense(ks[2], (d, hkv * hd), dtype=dt),
        "wo": L.init_dense(ks[3], (h * hd, d), dtype=dt),
        "ln": jnp.zeros((d,), dt),
    }
    if cfg.qk_norm:
        p["q_norm"] = jnp.zeros((hd,), dt)
        p["k_norm"] = jnp.zeros((hd,), dt)
    return p


def attention_apply(
    p: Dict, x: jnp.ndarray, cfg: ModelConfig,
    positions: jnp.ndarray,
    causal: bool = True, window: int = 0, prefix: int = 0,
    kv_source: Optional[jnp.ndarray] = None,
    use_rope: bool = True,
) -> jnp.ndarray:
    b, s, d = x.shape
    hd, h, hkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    xn = L.rms_norm(x, p["ln"])
    src = xn if kv_source is None else kv_source
    q = L.linear(xn, p["wq"]).reshape(b, s, h, hd)
    k = L.linear(src, p["wk"]).reshape(b, src.shape[1], hkv, hd)
    v = L.linear(src, p["wv"]).reshape(b, src.shape[1], hkv, hd)
    if cfg.qk_norm:
        q = L.rms_norm(q, p["q_norm"])
        k = L.rms_norm(k, p["k_norm"])
    if use_rope and kv_source is None:
        q = L.rope(q, positions, cfg.rope_theta)
        k = L.rope(k, positions, cfg.rope_theta)
    # q shards its (many) heads over 'model'; k/v heads are few (GQA) and
    # small — replicating them avoids the mixed (heads x head_dim)
    # sharding that forced SPMD resharding copies/permutes every layer
    # when n_kv_heads < model-axis size (EXPERIMENTS.md Perf, iter. 3).
    q = constrain(q, ("data", None, "model", None))
    k = constrain(k, ("data", None, None, None))
    v = constrain(v, ("data", None, None, None))
    o = flash_attention(
        q, k, v, causal=causal and kv_source is None, window=window,
    )
    if prefix:
        # bidirectional prefix (VLM): rerun mask-free over prefix handled
        # in flash via window=0/causal handled by caller-level mask; the
        # caller passes prefix through `causal_prefix` wrapper below.
        pass
    return x + L.linear(o.reshape(b, s, h * hd), p["wo"], "...f,fd->...d")


def attention_decode(
    p: Dict, x: jnp.ndarray, cfg: ModelConfig,
    state: Dict, positions: jnp.ndarray,
    window: int = 0, cross: bool = False,
    kv_bits_override: Optional[int] = None,
) -> Tuple[jnp.ndarray, Dict]:
    """x: (B, 1, d). state: {k, v, len} (self) or {ck, cv, clen} (cross).

    ``kv_bits_override`` pins the packed-KV width for this call — the
    width-segmented decode path passes each segment's static width so
    mixed per-layer plans (``CompressionConfig.kv_layer_bits``) pack each
    layer run at its own rung; ``None`` reads the uniform config knob."""
    b, _, d = x.shape
    hd, h, hkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    xn = L.rms_norm(x, p["ln"])
    q = L.linear(xn, p["wq"]).reshape(b, 1, h, hd)
    if cfg.qk_norm:
        q = L.rms_norm(q, p["q_norm"])
    kv_bits = (kv_bits_override if kv_bits_override is not None
               else cfg.compression.kv_bits)
    if cross:
        o = decode_attention(
            q[:, 0], state["ck"], state["cv"], state["clen"], kv_bits, hkv
        )
        return x + L.linear(o.reshape(b, 1, h * hd), p["wo"],
                            "...f,fd->...d"), state
    k = L.linear(xn, p["wk"]).reshape(b, 1, hkv, hd)
    v = L.linear(xn, p["wv"]).reshape(b, 1, hkv, hd)
    if cfg.qk_norm:
        k = L.rms_norm(k, p["k_norm"])
    q = L.rope(q, positions, cfg.rope_theta)
    k = L.rope(k, positions, cfg.rope_theta)
    slot = state["len"] if not window else state["len"] % window
    kc, vc = update_kv_cache(state["k"], state["v"], k[:, 0], v[:, 0], slot,
                             kv_bits)
    eff_len = state["len"] + 1
    if window:
        eff_len = jnp.minimum(eff_len, window)
    o = decode_attention(q[:, 0], kc, vc, eff_len, kv_bits, hkv)
    state = dict(state, k=kc, v=vc)
    return x + L.linear(o.reshape(b, 1, h * hd), p["wo"],
                        "...f,fd->...d"), state


def attention_decode_paged(
    p: Dict, x: jnp.ndarray, cfg: ModelConfig,
    kv: Dict, table: jnp.ndarray, kv_len: jnp.ndarray,
    positions: jnp.ndarray,
    kv_bits_override: Optional[int] = None,
    oracle: bool = False,
) -> Tuple[jnp.ndarray, Dict]:
    """Fused-paged twin of :func:`attention_decode` (self-attention
    only): the same q/k/v/rope program, but the new row persists straight
    to its physical page (``append_kv_pool_rows``) and attention walks the
    pool through the table (``kernels.paged_attention``) — the dense
    gathered view never materializes. ``kv`` is one layer's pool slice
    ``{"k", "v"}`` of shape (P+1, page, kv_row_width). ``oracle=True`` routes
    the attention through the gather-materialize reference instead (the
    linter-visible parity escape hatch)."""
    b, _, d = x.shape
    hd, h, hkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    xn = L.rms_norm(x, p["ln"])
    q = L.linear(xn, p["wq"]).reshape(b, 1, h, hd)
    if cfg.qk_norm:
        q = L.rms_norm(q, p["q_norm"])
    kv_bits = (kv_bits_override if kv_bits_override is not None
               else cfg.compression.kv_bits)
    k = L.linear(xn, p["wk"]).reshape(b, 1, hkv, hd)
    v = L.linear(xn, p["wv"]).reshape(b, 1, hkv, hd)
    if cfg.qk_norm:
        k = L.rms_norm(k, p["k_norm"])
    q = L.rope(q, positions, cfg.rope_theta)
    k = L.rope(k, positions, cfg.rope_theta)
    kc, vc = append_kv_pool_rows(kv["k"], kv["v"], k, v,
                                 table, kv_len, kv_bits)
    o = paged_decode_attention(q[:, 0], kc, vc, table, kv_len + 1,
                               kv_bits, hkv, fallback=oracle)
    return x + L.linear(o.reshape(b, 1, h * hd), p["wo"],
                        "...f,fd->...d"), {"k": kc, "v": vc}


def attention_prefill(
    p: Dict, x: jnp.ndarray, cfg: ModelConfig,
    kv: Dict, table: Optional[jnp.ndarray], kv_len: jnp.ndarray,
    positions: jnp.ndarray,
    kv_bits_override: Optional[int] = None,
) -> Tuple[jnp.ndarray, Dict]:
    """Multi-token twin of :func:`attention_decode` for a prompt chunk:
    x (B, C, d) at ``positions`` = ``kv_len[:, None] + arange(C)``. The
    q/k/v projections see all B·C rows at once; the C new K/V rows are
    written into ``kv`` — one layer's dense (B, S, row) cache when
    ``table`` is None, else its page pool through the table — packed as
    the decode append packs them; then the C queries attend to the cache
    view after the write, dense or gathered (``gather_kv_pages``)."""
    b, c, _ = x.shape
    hd, h, hkv = cfg.resolved_head_dim, cfg.n_heads, cfg.n_kv_heads
    xn = L.rms_norm(x, p["ln"])
    q = L.linear(xn, p["wq"]).reshape(b, c, h, hd)
    k = L.linear(xn, p["wk"]).reshape(b, c, hkv, hd)
    v = L.linear(xn, p["wv"]).reshape(b, c, hkv, hd)
    if cfg.qk_norm:
        q = L.rms_norm(q, p["q_norm"])
        k = L.rms_norm(k, p["k_norm"])
    q = L.rope(q, positions, cfg.rope_theta)
    k = L.rope(k, positions, cfg.rope_theta)
    kv_bits = (kv_bits_override if kv_bits_override is not None
               else cfg.compression.kv_bits)
    if table is None:
        kc, vc = append_kv_cache_rows(kv["k"], kv["v"], k, v, kv_len,
                                      kv_bits)
        k_view, v_view = kc, vc
    else:
        kc, vc = append_kv_pool_rows(kv["k"], kv["v"], k, v, table,
                                     kv_len, kv_bits)
        k_view, v_view = gather_kv_pages(kc, table), gather_kv_pages(
            vc, table)
    o = prefill_attention(q, k_view, v_view, kv_len, kv_bits, hkv)
    return x + L.linear(o.reshape(b, c, h * hd), p["wo"],
                        "...f,fd->...d"), {"k": kc, "v": vc}


# ---------------------------------------------------------------------------
# MLP block
# ---------------------------------------------------------------------------

def init_mlp(rng, cfg: ModelConfig, d_ff: Optional[int] = None) -> Dict:
    d, f, dt = cfg.d_model, d_ff or cfg.d_ff, cfg.dtype
    ks = _split(rng, 3)
    p = {
        "w_in": L.init_dense(ks[0], (d, f), dtype=dt),
        "w_out": L.init_dense(ks[1], (f, d), dtype=dt),
        "ln": jnp.zeros((d,), dt),
    }
    if cfg.gated_mlp:
        p["w_gate"] = L.init_dense(ks[2], (d, f), dtype=dt)
    return p


def mlp_apply(p: Dict, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    xn = L.rms_norm(x, p["ln"])
    return x + L.mlp(xn, p["w_in"], p.get("w_gate"), p["w_out"],
                     cfg.gated_mlp)


# ---------------------------------------------------------------------------
# MoE block (deepseek-moe / arctic)
# ---------------------------------------------------------------------------

def init_moe(rng, cfg: ModelConfig) -> Dict:
    d, f, dt = cfg.d_model, cfg.moe_d_ff, cfg.dtype
    e = cfg.n_experts
    ks = _split(rng, 8)
    p = {
        "router": L.init_dense(ks[0], (d, e), scale=0.02, dtype="float32"),
        "experts": {
            "w_in": L.init_dense(ks[1], (e, d, f), dtype=dt),
            "w_gate": L.init_dense(ks[2], (e, d, f), dtype=dt),
            "w_out": L.init_dense(ks[3], (e, f, d), dtype=dt),
        },
        "ln": jnp.zeros((d,), dt),
    }
    if cfg.n_shared_experts:
        fs = f * cfg.n_shared_experts
        p["shared"] = {
            "w_in": L.init_dense(ks[4], (d, fs), dtype=dt),
            "w_gate": L.init_dense(ks[5], (d, fs), dtype=dt),
            "w_out": L.init_dense(ks[6], (fs, d), dtype=dt),
        }
    if cfg.dense_residual:
        p["residual"] = {
            "w_in": L.init_dense(ks[7], (d, cfg.d_ff), dtype=dt),
            "w_gate": L.init_dense(ks[4], (d, cfg.d_ff), dtype=dt),
            "w_out": L.init_dense(ks[5], (cfg.d_ff, d), dtype=dt),
        }
    return p


def moe_ffn(p: Dict, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    """Scatter-based top-k dispatch with per-expert capacity (GShard-style,
    memory O(T*k*d)); experts shard over 'model' (EP). Router indices are
    narrow integers — range analysis sizes them at ceil(log2 E) bits."""
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.experts_per_token
    t = b * s
    xf = x.reshape(t, d)
    gates = jax.nn.softmax(
        L.linear(xf.astype(jnp.float32), p["router"]), axis=-1
    )
    top_w, top_i = jax.lax.top_k(gates, k)            # (t, k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    cap = int(np.ceil(t * k / e * cfg.capacity_factor))
    flat_e = top_i.reshape(-1)                        # (t*k,) int in [0, e)
    onehot = jax.nn.one_hot(flat_e, e, dtype=jnp.int32)
    pos = jnp.cumsum(onehot, axis=0) - onehot
    pos_in_e = (pos * onehot).sum(-1)                 # rank within expert
    keep = pos_in_e < cap
    slot = jnp.where(keep, flat_e * cap + pos_in_e, e * cap)

    x_rep = jnp.repeat(xf, k, axis=0)                 # (t*k, d)
    buf = jnp.zeros((e * cap + 1, d), x.dtype).at[slot].add(x_rep)
    ein = buf[: e * cap].reshape(e, cap, d)
    # shard capacity over DP as well as experts over model: per-device
    # expert compute/memory then scales down with the DP degree instead
    # of every DP replica processing the full global capacity
    # (EXPERIMENTS.md Perf, deepseek iteration)
    ein = constrain(ein, ("model", "data", None))

    # expert banks dispatch through expert_linear: 3-D float PackedTensor
    # leaves (incl. per-layer banks sliced out of a stacked (L, E, d, f)
    # leaf by the decode scan) hit the batched fused kernel — packed words
    # stream per expert, the decoded bank never materializes in HBM
    we = p["experts"]
    h = L.expert_linear(ein, we["w_in"])
    g = L.expert_linear(ein, we["w_gate"])
    h = jax.nn.silu(g) * h
    h = constrain(h, ("model", "data", None))
    eout = L.expert_linear(h, we["w_out"])
    eout = constrain(eout, ("model", "data", None))

    flat_out = jnp.concatenate(
        [eout.reshape(e * cap, d), jnp.zeros((1, d), eout.dtype)], 0
    )
    y_rep = flat_out[slot] * (
        top_w.reshape(-1)[:, None].astype(x.dtype)
        * keep[:, None].astype(x.dtype)
    )
    y = y_rep.reshape(t, k, d).sum(1)
    return y.reshape(b, s, d)


def moe_apply(p: Dict, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    xn = L.rms_norm(x, p["ln"])
    y = moe_ffn(p, xn, cfg)
    if "shared" in p:
        sp = p["shared"]
        y = y + L.mlp(xn, sp["w_in"], sp.get("w_gate"), sp["w_out"], True)
    if "residual" in p:
        rp = p["residual"]
        y = y + L.mlp(xn, rp["w_in"], rp.get("w_gate"), rp["w_out"], True)
    return x + y


# ---------------------------------------------------------------------------
# Mamba-1 block (falcon-mamba)
# ---------------------------------------------------------------------------

def init_mamba(rng, cfg: ModelConfig) -> Dict:
    d, di, n = cfg.d_model, cfg.d_inner, cfg.ssm_state
    dtr, dt = cfg.resolved_dt_rank, cfg.dtype
    ks = _split(rng, 6)
    a_init = jnp.log(
        jnp.tile(jnp.arange(1, n + 1, dtype=jnp.float32)[None], (di, 1))
    )
    return {
        "in_proj": L.init_dense(ks[0], (d, 2 * di), dtype=dt),
        "conv_w": L.init_dense(ks[1], (di, cfg.d_conv), dtype=dt),
        "conv_b": jnp.zeros((di,), dt),
        "x_proj": L.init_dense(ks[2], (di, dtr + 2 * n), dtype=dt),
        "dt_proj": L.init_dense(ks[3], (dtr, di), dtype=dt),
        "dt_bias": jnp.zeros((di,), "float32"),
        "a_param": a_init,                      # A = -exp(a_param), f32
        "d_param": jnp.ones((di,), "float32"),
        "out_proj": L.init_dense(ks[4], (di, d), dtype=dt),
        "ln": jnp.zeros((d,), dt),
    }


def _causal_conv(x: jnp.ndarray, w, b, width: int) -> jnp.ndarray:
    """Depthwise causal conv over (B, S, C)."""
    wq = L.unpack_maybe(w, x.dtype)                   # (C, width)
    pad = jnp.pad(x, ((0, 0), (width - 1, 0), (0, 0)))
    out = sum(
        pad[:, i: i + x.shape[1], :] * wq[:, i][None, None, :]
        for i in range(width)
    )
    return out + L.unpack_maybe(b, x.dtype)[None, None, :]


def _ssm_params(p, xc, cfg):
    dtr, n = cfg.resolved_dt_rank, cfg.ssm_state
    bcdt = L.linear(xc, p["x_proj"], "...c,cf->...f")
    dt_r, bm, cm = jnp.split(bcdt, [dtr, dtr + n], axis=-1)
    dt_full = L.linear(dt_r, p["dt_proj"], "...r,rc->...c")
    dt = jax.nn.softplus(
        dt_full.astype(jnp.float32)
        + L.unpack_maybe(p["dt_bias"], jnp.float32)
    )
    a = -jnp.exp(L.unpack_maybe(p["a_param"], jnp.float32))  # (di, n)
    return dt, bm.astype(jnp.float32), cm.astype(jnp.float32), a


def mamba_apply(p: Dict, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    b, s, d = x.shape
    di, n = cfg.d_inner, cfg.ssm_state
    xn = L.rms_norm(x, p["ln"])
    xz = L.linear(xn, p["in_proj"])
    xi, z = jnp.split(xz, 2, axis=-1)
    xi = constrain(xi, ("data", None, "model"))
    xc = jax.nn.silu(_causal_conv(xi, p["conv_w"], p["conv_b"], cfg.d_conv))
    dt, bm, cm, a = _ssm_params(p, xc, cfg)

    xcf = xc.astype(jnp.float32)

    def step(h, inputs):
        dt_t, b_t, c_t, x_t = inputs                # (B,di),(B,n),(B,n),(B,di)
        da = jnp.exp(dt_t[..., None] * a[None])     # (B, di, n)
        h = da * h + (dt_t * x_t)[..., None] * b_t[:, None, :]
        y = jnp.einsum("bcn,bn->bc", h, c_t)
        return h, y

    # Chunked selective scan: checkpoint at time-chunk boundaries so the
    # backward pass stores h only every ``chunk`` steps (the per-step h is
    # (B, d_inner, N) — unchunked, 4k steps of residuals would dwarf HBM).
    chunk = min(256, s)
    while s % chunk:
        chunk //= 2
    n_chunks = s // chunk

    xs_all = (jnp.moveaxis(dt, 1, 0), jnp.moveaxis(bm, 1, 0),
              jnp.moveaxis(cm, 1, 0), jnp.moveaxis(xcf, 1, 0))
    xs_chunked = jax.tree_util.tree_map(
        lambda t: t.reshape((n_chunks, chunk) + t.shape[1:]), xs_all)

    @jax.checkpoint
    def chunk_body(h, xs):
        return jax.lax.scan(step, h, xs)

    h0 = jnp.zeros((b, di, n), jnp.float32)
    _, ys = jax.lax.scan(chunk_body, h0, xs_chunked)
    ys = ys.reshape((s,) + ys.shape[2:])            # (S, B, di)
    y = jnp.moveaxis(ys, 0, 1) + xcf * L.unpack_maybe(
        p["d_param"], jnp.float32
    )
    y = (y.astype(x.dtype)) * jax.nn.silu(z)
    return x + L.linear(y, p["out_proj"], "...c,cd->...d")


def mamba_decode(p: Dict, x: jnp.ndarray, cfg: ModelConfig,
                 state: Dict) -> Tuple[jnp.ndarray, Dict]:
    """state: conv (B, d_conv-1, di) trailing inputs; ssm (B, di, n)."""
    b, _, d = x.shape
    xn = L.rms_norm(x, p["ln"])
    xz = L.linear(xn, p["in_proj"])
    xi, z = jnp.split(xz, 2, axis=-1)               # (B, 1, di)
    hist = jnp.concatenate([state["conv"], xi], axis=1)  # (B, d_conv, di)
    w = L.unpack_maybe(p["conv_w"], x.dtype)        # (di, width)
    xc = jnp.einsum("bwc,cw->bc", hist, w) + L.unpack_maybe(
        p["conv_b"], x.dtype
    )
    xc = jax.nn.silu(xc)[:, None, :]                # (B, 1, di)
    dt, bm, cm, a = _ssm_params(p, xc, cfg)
    dt_t, b_t, c_t = dt[:, 0], bm[:, 0], cm[:, 0]
    xcf = xc[:, 0].astype(jnp.float32)
    da = jnp.exp(dt_t[..., None] * a[None])
    h = da * state["ssm"] + (dt_t * xcf)[..., None] * b_t[:, None, :]
    y = jnp.einsum("bcn,bn->bc", h, c_t) + xcf * L.unpack_maybe(
        p["d_param"], jnp.float32
    )
    y = y[:, None, :].astype(x.dtype) * jax.nn.silu(z)
    out = x + L.linear(y, p["out_proj"], "...c,cd->...d")
    return out, dict(state, conv=hist[:, 1:], ssm=h)


# ---------------------------------------------------------------------------
# RG-LRU recurrent block (recurrentgemma)
# ---------------------------------------------------------------------------

_RG_C = 8.0                                          # Griffin's fixed power


def init_rglru(rng, cfg: ModelConfig) -> Dict:
    d, lw, dt = cfg.d_model, cfg.lru_width or cfg.d_model, cfg.dtype
    ks = _split(rng, 5)
    return {
        "rg_in_w": L.init_dense(ks[0], (d, lw), dtype=dt),
        "rg_gate_w": L.init_dense(ks[1], (d, lw), dtype=dt),
        "conv_w": L.init_dense(ks[2], (lw, cfg.d_conv), dtype=dt),
        "conv_b": jnp.zeros((lw,), dt),
        "rg_a": jnp.full((lw,), -1.5, "float32"),    # sigmoid ~ 0.18
        "rg_wr": jnp.zeros((lw,), "float32"),        # diagonal gates
        "rg_wi": jnp.zeros((lw,), "float32"),
        "rg_out": L.init_dense(ks[3], (lw, d), dtype=dt),
        "ln": jnp.zeros((d,), dt),
    }


def _rglru_gates(p, xc):
    xf = xc.astype(jnp.float32)
    r = jax.nn.sigmoid(xf * L.unpack_maybe(p["rg_wr"], jnp.float32))
    i = jax.nn.sigmoid(xf * L.unpack_maybe(p["rg_wi"], jnp.float32))
    log_a = -_RG_C * r * jax.nn.softplus(
        L.unpack_maybe(p["rg_a"], jnp.float32)
    )
    a = jnp.exp(log_a)
    mult = jnp.sqrt(jnp.maximum(1.0 - a * a, 1e-12))
    return a, mult * i * xf


def rglru_apply(p: Dict, x: jnp.ndarray, cfg: ModelConfig) -> jnp.ndarray:
    b, s, d = x.shape
    xn = L.rms_norm(x, p["ln"])
    xi = L.linear(xn, p["rg_in_w"])
    gate = jax.nn.gelu(L.linear(xn, p["rg_gate_w"]))
    xc = _causal_conv(xi, p["conv_w"], p["conv_b"], cfg.d_conv)
    a, bx = _rglru_gates(p, xc)

    def step(h, inputs):
        a_t, b_t = inputs
        h = a_t * h + b_t
        return h, h

    h0 = jnp.zeros((b, xi.shape[-1]), jnp.float32)
    _, hs = jax.lax.scan(
        step, h0, (jnp.moveaxis(a, 1, 0), jnp.moveaxis(bx, 1, 0))
    )
    y = jnp.moveaxis(hs, 0, 1).astype(x.dtype) * gate
    return x + L.linear(y, p["rg_out"], "...c,cd->...d")


def rglru_decode(p: Dict, x: jnp.ndarray, cfg: ModelConfig,
                 state: Dict) -> Tuple[jnp.ndarray, Dict]:
    """state: conv (B, d_conv-1, lw); h (B, lw)."""
    xn = L.rms_norm(x, p["ln"])
    xi = L.linear(xn, p["rg_in_w"])                  # (B, 1, lw)
    gate = jax.nn.gelu(L.linear(xn, p["rg_gate_w"]))
    hist = jnp.concatenate([state["conv"], xi], axis=1)
    w = L.unpack_maybe(p["conv_w"], x.dtype)
    xc = (jnp.einsum("bwc,cw->bc", hist, w)
          + L.unpack_maybe(p["conv_b"], x.dtype))[:, None, :]
    a, bx = _rglru_gates(p, xc)
    h = a[:, 0] * state["h"] + bx[:, 0]
    y = h[:, None, :].astype(x.dtype) * gate
    out = x + L.linear(y, p["rg_out"], "...c,cd->...d")
    return out, dict(state, conv=hist[:, 1:], h=h)
