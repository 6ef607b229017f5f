"""Plain float32 reference of a dense decoder as the benchmark's
configurations state it, and the comparison's precision-stepped control.

Independent of the program: it imports nothing from ``repro``. It draws
the same weights the engine serves by following the engine's documented
initializer from the same key (per-leaf ``jax.random`` keys split as the
parameter tree is built, ``normal * 1/sqrt(fan_in)`` — ``0.02`` for the
embedding — stored in bfloat16), and rounds them to the configured
weight format (AF8: one sign, three exponent and four mantissa bits,
IEEE-like with subnormals, round to nearest even). Keys and values are
rounded to the configured KV format (AF16: IEEE half). Everything else
runs in float32 at the highest matmul precision.

The block: pre-norm RMSNorm (``x * rsqrt(mean(x^2) + eps) * (1 + w)``,
norm weights zero), queries, keys and values without bias, RMSNorm over
each head of q and k where ``qk_norm``, rotary positions (rotate-half,
``theta ** (-i / half)``), causal grouped-query attention, SwiGLU
(``silu(x W_gate) * x W_in``) or tanh-GELU MLP, a final RMSNorm and an
untied head.

Memory: one layer's weights exist at a time; the embedding and the head
are generated whole (they cannot be drawn row by row), used, and freed.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Sequence

import jax
import jax.numpy as jnp
import numpy as np

# the precision one step below what the configurations state: bfloat16
# activations become float8 (e4m3), AF16 keys and values float8 too
CONTROL_DTYPE = jnp.float8_e4m3fn


def weight_key(seed: int) -> jax.Array:
    """The key the served weights are drawn from, for a ``--seed``."""
    return jax.random.PRNGKey(int(seed) % (2**31 - 1))


def round_af(x: jnp.ndarray, exp_bits: int, man_bits: int) -> jnp.ndarray:
    """Round float32 ``x`` to the nearest value of a sign/exp/mantissa
    float format with subnormals (ties to even; no overflow handling is
    needed at the magnitudes drawn here, and it is checked)."""
    bias = 2 ** (exp_bits - 1) - 1
    min_exp = 1 - bias
    _, e = jnp.frexp(jnp.where(x != 0, x, 1.0))   # |x| = m * 2**e, m in [.5, 1)
    e = jnp.maximum(e.astype(jnp.float32) - 1, min_exp)
    step = jnp.exp2(e - man_bits)
    y = jnp.round(x / step) * step          # jnp.round: half to even
    max_finite = (2.0 - 2.0 ** -man_bits) * 2.0 ** (2 ** exp_bits - 2 - bias)
    return jnp.where(jnp.abs(y) > max_finite, jnp.inf * jnp.sign(y), y)


_FORMATS = {8: (3, 4), 12: (4, 7), 16: (5, 10)}


def _served(x32: jnp.ndarray, bits: int) -> jnp.ndarray:
    """A drawn float32 weight as served: stored in bfloat16, then packed
    at ``bits``."""
    xb = x32.astype(jnp.bfloat16).astype(jnp.float32)
    return round_af(xb, *_FORMATS[bits])


def _dense(key, shape, scale=None):
    fan_in = shape[-2] if len(shape) >= 2 else shape[-1]
    s = scale if scale is not None else 1.0 / np.sqrt(fan_in)
    return jax.random.normal(key, shape, jnp.float32) * s


@functools.partial(jax.jit, static_argnames=("m", "bits"))
def _layer_weights(root, layer, m: "Model", bits: int) -> Dict[str, Any]:
    r = jax.random.split(root, 8)
    ka = jax.random.split(r[2], m.n_layers)[layer]
    km = jax.random.split(r[3], m.n_layers)[layer]
    ks = jax.random.split(ka, 6)
    d, hd = m.d_model, m.head_dim
    w = {
        "wq": _dense(ks[0], (d, m.n_heads * hd)),
        "wk": _dense(ks[1], (d, m.n_kv_heads * hd)),
        "wv": _dense(ks[2], (d, m.n_kv_heads * hd)),
        "wo": _dense(ks[3], (m.n_heads * hd, d)),
    }
    km3 = jax.random.split(km, 3)
    w["w_in"] = _dense(km3[0], (d, m.d_ff))
    w["w_out"] = _dense(km3[1], (m.d_ff, d))
    if m.gated_mlp:
        w["w_gate"] = _dense(km3[2], (d, m.d_ff))
    return {k: _served(v, bits) for k, v in w.items()}


@functools.partial(jax.jit, static_argnames=("m", "bits"))
def _embed_rows(root, tokens, m: "Model", bits: int):
    r = jax.random.split(root, 8)
    table = _served(_dense(r[0], (m.vocab_size, m.d_model), 0.02), bits)
    return jnp.take(table, tokens, axis=0)


@functools.partial(jax.jit, static_argnames=("m", "bits"))
def _head(root, m: "Model", bits: int):
    r = jax.random.split(root, 8)
    return _served(_dense(r[1], (m.d_model, m.vocab_size)), bits)


class Model:
    """Sizes of a configuration file's ``model`` block (hashable, so it
    can be a static argument)."""

    def __init__(self, conf: Dict[str, Any]):
        mm = conf["model"]
        self.n_layers = mm["n_layers"]
        self.d_model = mm["d_model"]
        self.n_heads = mm["n_heads"]
        self.n_kv_heads = mm["n_kv_heads"]
        self.head_dim = mm["head_dim"]
        self.d_ff = mm["d_ff"]
        self.vocab_size = mm["vocab_size"]
        self.gated_mlp = mm["gated_mlp"]
        self.qk_norm = mm["qk_norm"]
        self.rope_theta = mm["rope_theta"]
        self.eps = mm["rms_norm_eps"]
        if mm.get("tie_embeddings"):
            raise ValueError("the reference holds an untied head only")
        self._key = tuple(sorted(mm.items()))

    def __hash__(self):
        return hash(self._key)

    def __eq__(self, other):
        return isinstance(other, Model) and self._key == other._key


def _rms(x, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)


def _rope(x, pos, theta):
    half = x.shape[-1] // 2
    freqs = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def _act_round(x, control: bool):
    return x.astype(CONTROL_DTYPE).astype(jnp.float32) if control else x


def _kv_round(x, control: bool, kv_bits: int):
    if control:
        return x.astype(CONTROL_DTYPE).astype(jnp.float32)
    return round_af(x, *_FORMATS[kv_bits])


@functools.partial(jax.jit, static_argnames=("m", "kv_bits", "control"))
def _layer(x, w, m: Model, kv_bits: int, control: bool):
    """One block over (B, S, d) hidden states, causal from position 0."""
    b, s, d = x.shape
    h, hkv, hd = m.n_heads, m.n_kv_heads, m.head_dim
    pos = jnp.arange(s)
    a = lambda t: _act_round(t, control)
    xn = a(_rms(x, m.eps))
    q = a(xn @ w["wq"]).reshape(b, s, h, hd)
    k = a(xn @ w["wk"]).reshape(b, s, hkv, hd)
    v = a(xn @ w["wv"]).reshape(b, s, hkv, hd)
    if m.qk_norm:
        q, k = a(_rms(q, m.eps)), a(_rms(k, m.eps))
    q = jax.vmap(lambda t: _rope(t, pos, m.rope_theta))(q)
    k = jax.vmap(lambda t: _rope(t, pos, m.rope_theta))(k)
    k, v = _kv_round(k, control, kv_bits), _kv_round(v, control, kv_bits)
    g = h // hkv
    mask = pos[:, None] >= pos[None, :]

    def one(qb, kb, vb):              # (S, H, D), (S, Hkv, D) one sequence
        qg = qb.reshape(s, hkv, g, hd)
        sc = jnp.einsum("qkgd,ckd->kgqc", qg, kb) / np.sqrt(hd)
        sc = jnp.where(mask, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        o = jnp.einsum("kgqc,ckd->qkgd", p, vb)
        return o.reshape(s, h * hd)

    o = jax.lax.map(lambda t: one(*t), (q, k, v))
    x = a(x + a(a(o) @ w["wo"]))
    xn = a(_rms(x, m.eps))
    up = a(xn @ w["w_in"])
    if m.gated_mlp:
        hid = jax.nn.silu(a(xn @ w["w_gate"])) * up
    else:
        hid = jax.nn.gelu(up, approximate=True)
    return a(x + a(a(hid) @ w["w_out"]))


@functools.partial(jax.jit, static_argnames=("m", "control", "rows"))
def _gaps(x, head, score_tokens, m: Model, control: bool, rows: int):
    """For every position: the reference's best logit minus the logit of
    each token in ``score_tokens`` (T, B, S), and the argmax itself."""
    b, s, d = x.shape
    xf = _act_round(_rms(x, m.eps), control).reshape(b * s, d)
    toks = score_tokens.reshape(score_tokens.shape[0], b * s)

    def block(i):
        xs = jax.lax.dynamic_slice_in_dim(xf, i * rows, rows, 0)
        lg = xs @ head                                   # (rows, V)
        best = lg.max(-1)
        ts = jax.lax.dynamic_slice_in_dim(toks, i * rows, rows, 1)
        picked = jnp.take_along_axis(lg[None], ts[..., None], -1)[..., 0]
        return best[None] - picked, jnp.argmax(lg, -1).astype(jnp.int32)

    gaps, top = jax.lax.map(block, jnp.arange(b * s // rows))
    gaps = jnp.moveaxis(gaps, 0, 1).reshape(toks.shape[0], b, s)
    return gaps, top.reshape(b, s)


def forward_gaps(conf: Dict[str, Any], seed: int, inputs: np.ndarray,
                 score_tokens: np.ndarray, control: bool = False):
    """Run the model over ``inputs`` (B, S) token ids and return
    ``(gaps (T, B, S), argmax (B, S))`` for ``score_tokens`` (T, B, S):
    at each position, how far each scored token's logit lies below the
    best logit. ``control=True`` computes in the stepped-down precision
    (float8 activations and KV) instead of float32."""
    m = Model(conf)
    bits, kv_bits = conf["weight_bits"], conf["kv_bits"]
    root = weight_key(seed)
    with jax.default_matmul_precision("highest"):
        x = _embed_rows(root, jnp.asarray(inputs), m, bits)
        x = _act_round(x, control)
        for layer in range(m.n_layers):
            w = _layer_weights(root, layer, m, bits)
            x = _layer(x, w, m, kv_bits, control)
            del w
        head = _head(root, m, bits)
        b, s = inputs.shape
        rows = max(r for r in range(1, 513) if (b * s) % r == 0)
        gaps, top = _gaps(x, head, jnp.asarray(score_tokens), m, control,
                          rows)
        del head
    return np.asarray(gaps), np.asarray(top)


def pack_sequences(seqs: Sequence[Dict[str, List[int]]], length: int):
    """Pad ``[{"prompt", "served"}]`` into the (B, S) input ids, the
    (B, S) served token expected at each position (−1 where nothing was
    served) — the logit at position i predicts the token at i + 1."""
    b = len(seqs)
    inputs = np.zeros((b, length), np.int32)
    served = np.full((b, length), -1, np.int32)
    for i, sq in enumerate(seqs):
        p, out = sq["prompt"], sq["served"]
        full = list(p) + list(out[:-1])
        if len(full) > length:
            raise ValueError(f"sequence of {len(full)} > {length}")
        inputs[i, :len(full)] = full
        served[i, len(p) - 1:len(p) - 1 + len(out)] = out
    return inputs, served
