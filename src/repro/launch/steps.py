"""Sharded program builders for train / prefill / decode.

``build_programs(cfg, shape, mode)`` returns a ``Program``: the jitted
step with in/out shardings bound, plus ShapeDtypeStruct input specs —
everything the dry-run needs to ``.lower().compile()`` without touching
device memory, and everything the real launcher needs to run.

Sharding summary (axes: pod/data = DP, model = TP/EP):
  params      rule-matched per path (distributed/sharding.py); `zero`
              mode additionally shards the leading stack dim over DP
  opt state   moments inherit their parameter's spec (packed payloads
              scale the last dim only)
  batch       (B, S) -> (("pod","data"), None)
  KV cache    (L, B, S, H, D) -> B over DP, S over model (uniform across
              families incl. MQA where the head dim is unshardable)
  ssm state   d_inner over model
  logits      vocab over model
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.compat import (jit, mesh_context, path_str, prng_key,
                          tree_map_with_path)
from repro.distributed.sharding import (drop_indivisible,
                                        resolve_axes, shard_leaf)
from repro.models.config import ModelConfig, ShapeConfig
from repro.models.lm import LM
from repro.optim import AdamWConfig, adamw_init, adamw_update, cosine_schedule


@dataclasses.dataclass
class Program:
    name: str
    fn: Callable                  # jit-wrapped, shardings bound
    in_specs: Tuple               # ShapeDtypeStructs (positional)
    lm: LM

    def lower(self):
        return self.fn.lower(*self.in_specs)


def _tree_shardings(tree, mesh: Mesh, mode: str):
    from repro.core.tensor_store import is_packed

    def leaf_spec(path, leaf):
        # packed leaves shard by their logical spec with the lane-tiled
        # word axis kept intact (distributed.sharding.spec_for_packed)
        return shard_leaf(path_str(path), leaf, mesh, mode)
    return tree_map_with_path(leaf_spec, tree, is_leaf=is_packed)


def _batch_shardings(specs: Dict, mesh: Mesh) -> Dict:
    with mesh_context(mesh):
        out = {}
        for k, v in specs.items():
            if v.ndim >= 1:
                spec = resolve_axes(("data",) + (None,) * (v.ndim - 1))
                out[k] = NamedSharding(
                    mesh, drop_indivisible(spec, v.shape)
                )
            else:
                out[k] = NamedSharding(mesh, P())
        return out


def _state_shardings(state, mesh: Mesh) -> Any:
    """Decode-state shardings by key family (see module docstring)."""
    def leaf_spec(path, leaf):
        keys = [str(getattr(p, "key", "")) for p in path]
        nd = leaf.ndim
        with mesh_context(mesh):
            if "len" in keys or "clen" in keys or nd <= 1:
                return NamedSharding(mesh, P())
            def ns(axes):
                return NamedSharding(
                    mesh, drop_indivisible(resolve_axes(axes), leaf.shape))
            if "k" in keys or "v" in keys:          # (L,B,S,W)
                return ns((None, "data", "model") + (None,) * (nd - 3))
            if "ck" in keys or "cv" in keys:        # (L,B,Se,W)
                return ns((None, "data") + (None,) * (nd - 2))
            if "ssm" in keys:                       # (L,B,di,N)
                return ns((None, "data", "model", None))
            if "conv" in keys:                      # (...,B,w,di|lw)
                return ns((None,) * (nd - 3) + ("data", None, "model"))
            if "h" in keys:                         # (...,B,lw)
                return ns((None,) * (nd - 2) + ("data", "model"))
            return NamedSharding(mesh, P())
    return tree_map_with_path(leaf_spec, state)


def build_programs(
    cfg: ModelConfig,
    shape: ShapeConfig,
    mesh: Mesh,
    mode: str = "tp",
    opt_cfg: Optional[AdamWConfig] = None,
) -> Program:
    lm = LM(cfg)
    rng_spec = prng_key(0)
    abstract_params = jax.eval_shape(lm.init, rng_spec)
    with mesh_context(mesh):
        p_shard = _tree_shardings(abstract_params, mesh, mode)
    input_specs = lm.input_specs(shape)
    b_shard = _batch_shardings(input_specs, mesh)

    if shape.kind == "train":
        comp = cfg.compression
        ocfg = opt_cfg or AdamWConfig(
            m_bits=comp.opt_m_bits, v_bits=comp.opt_v_bits
        )
        abstract_opt = jax.eval_shape(
            functools.partial(adamw_init, cfg=ocfg), abstract_params
        )
        with mesh_context(mesh):
            o_shard = _tree_shardings(abstract_opt, mesh, mode)
            rep = NamedSharding(mesh, P())

        def train_step(params, opt_state, batch, step):
            loss, grads = jax.value_and_grad(lm.loss)(params, batch)
            lr = cosine_schedule(step, 3e-4, 100, 10000)
            params, opt_state = adamw_update(
                grads, opt_state, params, ocfg, lr
            )
            return params, opt_state, loss

        fn = jit(
            train_step,
            in_shardings=(p_shard, o_shard, b_shard, rep),
            out_shardings=(p_shard, o_shard, rep),
            donate_argnums=(0, 1),
        )
        specs = (abstract_params, abstract_opt, input_specs,
                 jax.ShapeDtypeStruct((), jnp.int32))
        return Program(f"{cfg.name}:{shape.name}:train", fn, specs, lm)

    if shape.kind == "prefill":
        with mesh_context(mesh):
            lshape = (shape.global_batch, 1, cfg.vocab_size)
            out_shard = (
                NamedSharding(mesh, drop_indivisible(
                    resolve_axes(("data", None, "model")), lshape)),
                NamedSharding(mesh, P()),
            )

        def prefill_step(params, batch):
            return lm.prefill(params, batch)

        fn = jit(
            prefill_step,
            in_shardings=(p_shard, b_shard),
            out_shardings=out_shard,
        )
        return Program(
            f"{cfg.name}:{shape.name}:prefill", fn,
            (abstract_params, input_specs), lm,
        )

    # decode: one new token against seq_len of persistent state
    abstract_state = lm.init_decode_state(
        shape.global_batch, _state_seq_len(cfg, shape), abstract=True
    )
    s_shard = _state_shardings(abstract_state, mesh)
    with mesh_context(mesh):
        lshape = (shape.global_batch, 1, cfg.vocab_size)
        logits_shard = NamedSharding(
            mesh, drop_indivisible(
                resolve_axes(("data", None, "model")), lshape))

    def serve_step(params, state, tokens):
        return lm.decode_step(params, state, tokens)

    fn = jit(
        serve_step,
        in_shardings=(p_shard, s_shard, b_shard["tokens"]),
        out_shardings=(logits_shard, s_shard),
        donate_argnums=(1,),
    )
    return Program(
        f"{cfg.name}:{shape.name}:decode", fn,
        (abstract_params, abstract_state, input_specs["tokens"]), lm,
    )


def _state_seq_len(cfg: ModelConfig, shape: ShapeConfig) -> int:
    """KV length the decode state must hold (window-capped for hybrids)."""
    return shape.seq_len
