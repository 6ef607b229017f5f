"""AdamW with statically compressed optimizer state.

The paper packs *registers*; the training-side analogue with the largest
footprint is optimizer state: Adam's first/second moments are 8 bytes per
parameter in f32. With the static plan's widths (AF16 moments by default,
AF12 under the "high quality" threshold for m), the at-rest footprint
drops by 2-2.7x. Moments are stored packed (uint32 payloads), unpacked at
the top of the update (Value Extractor path), updated in f32, and
re-truncated (Value Truncator path) — with an optional error-feedback
residual so truncation noise doesn't bias the moment EMA.

All of it is jnp, so the whole update jits and shards; packed payloads
shard exactly like their logical tensors (lane-tiled layout).
"""
from __future__ import annotations

import dataclasses
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import compat

from repro.core import bitpack
from repro.core.formats import FLOAT_FORMATS, decode_float, encode_float
from repro.core.tensor_store import is_packed, pack_tensor


@dataclasses.dataclass(frozen=True)
class AdamWConfig:
    lr: float = 3e-4
    b1: float = 0.9
    b2: float = 0.95
    eps: float = 1e-8
    weight_decay: float = 0.1
    grad_clip: float = 1.0
    m_bits: Optional[int] = None       # Table 3 width for the 1st moment
    v_bits: Optional[int] = None       # ... 2nd moment


def cosine_schedule(step, base_lr: float, warmup: int, total: int):
    warm = base_lr * (step + 1) / max(warmup, 1)
    t = jnp.clip((step - warmup) / max(total - warmup, 1), 0.0, 1.0)
    cos = base_lr * 0.5 * (1.0 + jnp.cos(jnp.pi * t))
    return jnp.where(step < warmup, warm, cos)


def _qdq(x: jnp.ndarray, bits: Optional[int]) -> jnp.ndarray:
    if not bits or bits >= 32:
        return x
    fmt = FLOAT_FORMATS[bits]
    return decode_float(encode_float(x, fmt), fmt)


def _pack_moment(x: jnp.ndarray, bits: Optional[int]):
    """f32 moment -> packed uint32 payload.

    Packs along the *last* axis, preserving rank, so the payload inherits
    the parameter's PartitionSpec verbatim (lane-tiled words scale the
    last dim by bits/32) — no resharding collectives appear around the
    optimizer. Scalars/vectors stay f32 (packing overhead > payload)."""
    if not bits or bits >= 32 or x.ndim < 2:
        return x
    codes = encode_float(x, FLOAT_FORMATS[bits])
    return bitpack.pack_groups(codes, bits)


def _unpack_moment(payload, shape, bits: Optional[int]) -> jnp.ndarray:
    if not bits or bits >= 32 or len(shape) < 2:
        return payload
    codes = bitpack.unpack_groups(payload, bits, shape[-1])
    return decode_float(codes, FLOAT_FORMATS[bits])


def adamw_init(params, cfg: AdamWConfig) -> Dict[str, Any]:
    """The second moment is stored in the sqrt domain when packed: grad^2
    values underflow AF16's e5 exponent range (observed as optimizer
    divergence — see EXPERIMENTS.md section Paper-validation), while
    sqrt(v) halves the needed exponent range and round-trips safely. This
    is the paper's own per-value format-fitting discipline applied to the
    moment's distribution."""
    def zeros_packed(p, bits):
        z = jnp.zeros(p.shape, jnp.float32)
        return _pack_moment(z, bits)

    return {
        "m": compat.tree_map(
            lambda p: zeros_packed(p, cfg.m_bits), params),
        "v": compat.tree_map(          # holds sqrt(v) when packed
            lambda p: zeros_packed(p, cfg.v_bits), params),
        "count": jnp.zeros((), jnp.int32),
    }


def adamw_update(
    grads, opt_state, params, cfg: AdamWConfig,
    lr: Optional[jnp.ndarray] = None,
) -> Tuple[Any, Dict[str, Any]]:
    """Returns (new_params, new_opt_state). Global-norm clip + AdamW."""
    count = opt_state["count"] + 1
    lr = cfg.lr if lr is None else lr

    gnorm = jnp.sqrt(sum(
        jnp.sum(jnp.square(g.astype(jnp.float32)))
        for g in compat.tree_leaves(grads)
    ))
    scale = jnp.minimum(1.0, cfg.grad_clip / jnp.maximum(gnorm, 1e-12))

    b1c = 1.0 - cfg.b1 ** count.astype(jnp.float32)
    b2c = 1.0 - cfg.b2 ** count.astype(jnp.float32)

    v_packed = bool(cfg.v_bits) and cfg.v_bits < 32

    def upd(p, g, m_pk, v_pk):
        g = g.astype(jnp.float32) * scale
        m = _unpack_moment(m_pk, p.shape, cfg.m_bits)
        v = _unpack_moment(v_pk, p.shape, cfg.v_bits)
        if v_packed:
            v = v * v                       # stored as sqrt(v)
        m = cfg.b1 * m + (1.0 - cfg.b1) * g
        v = cfg.b2 * v + (1.0 - cfg.b2) * g * g
        mh = m / b1c
        vh = v / b2c
        step = mh / (jnp.sqrt(vh) + cfg.eps)
        pf = p.astype(jnp.float32)
        pf = pf - lr * (step + cfg.weight_decay * pf)
        return (
            pf.astype(p.dtype),
            _pack_moment(m, cfg.m_bits),
            _pack_moment(jnp.sqrt(v) if v_packed else v, cfg.v_bits),
        )

    flat_p, treedef = compat.tree_flatten(params)
    flat_g = treedef.flatten_up_to(grads)
    flat_m = treedef.flatten_up_to(opt_state["m"])
    flat_v = treedef.flatten_up_to(opt_state["v"])
    out = [upd(p, g, m, v)
           for p, g, m, v in zip(flat_p, flat_g, flat_m, flat_v)]
    new_p = treedef.unflatten([o[0] for o in out])
    new_m = treedef.unflatten([o[1] for o in out])
    new_v = treedef.unflatten([o[2] for o in out])
    return new_p, {"m": new_m, "v": new_v, "count": count}


# ---------------------------------------------------------------------------
# Packed-master training: the codes <-> masters re-encode step
# ---------------------------------------------------------------------------

def repack_params(packed, masters):
    """Re-encode every planned leaf of ``packed`` from its dense master
    at the leaf's existing width (Value Truncator path, all jnp — jits
    inside the train step). Unplanned leaves mirror the master straight
    through, keeping the two trees congruent. This is the deploy step of
    packed-master training: the codes the next forward streams are the
    freshly truncated masters."""
    def _one(pk, m):
        if is_packed(pk):
            return pack_tensor(m, pk.bits, kind=pk.kind, signed=pk.signed,
                               out_dtype=pk.out_dtype)
        return m

    return compat.tree_map(_one, packed, masters, is_leaf=is_packed)


def packed_staleness(packed, masters):
    """Max |decode(stored codes) - decode(encode(master))| over planned
    leaves: how far the deployed codes have drifted from what a fresh
    re-encode of the masters would store. Exactly 0.0 right after a
    repack step; grows between repacks when ``repack_every > 1`` (the
    knob trades re-encode cost against training on stale codes)."""
    out = jnp.float32(0.0)
    flat_p = compat.tree_leaves(packed, is_leaf=is_packed)
    flat_m = compat.tree_leaves(masters)
    for pk, m in zip(flat_p, flat_m):
        if not is_packed(pk) or pk.kind != "float":
            continue
        fmt = FLOAT_FORMATS[pk.bits]
        fresh = decode_float(
            encode_float(jnp.asarray(m, jnp.float32), fmt), fmt
        ).astype(pk.out_dtype)
        cur = pk.unpack()
        out = jnp.maximum(out, jnp.max(jnp.abs(
            cur.astype(jnp.float32) - fresh.astype(jnp.float32))))
    return out
