"""Compile each benchmark configuration's ``decode_step`` and
``prefill_step`` for a described TPU v5e, at its cell's slots and
``max_seq_len``, and print the compiler's memory analysis.

No chip is needed: the programs are lowered from shapes alone, with the
Pallas kernels compiled by Mosaic as they would be on the chip. A
configuration that does not fit one chip fails here.

    JAX_PLATFORMS=cpu python bench/aot.py [config ...]
"""
from __future__ import annotations

import json
import os
import sys

sys.path[:0] = [os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "src"),
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))]
os.environ.setdefault("TPU_LOG_DIR", "disabled")


def compile_config(conf, one_chip, chunk: int = 16):
    import jax
    import jax.numpy as jnp

    from bench.cell import model_config
    from repro.core.compress import uniform_plan
    from repro.models.lm import LM

    cfg = model_config(conf)
    lm = LM(cfg, paged_attn=True)
    key = jax.ShapeDtypeStruct((2,), jnp.uint32)
    plan = uniform_plan(jax.eval_shape(lm.init, key), cfg.resolved_weight_bits)
    params = jax.eval_shape(lambda k: lm.init_packed(k, plan), key)
    slots, seq = conf["max_slots"], conf["max_seq_len"]
    page = conf["kv_page_size"]
    state = lm.init_paged_decode_state(slots, seq, page,
                                       slots * (seq // page), abstract=True)

    def place(tree):
        return jax.tree.map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,
                                           sharding=one_chip), tree)

    params, state = place(params), place(state)
    tok1 = place(jax.ShapeDtypeStruct((slots, 1), jnp.int32))
    tokc = place(jax.ShapeDtypeStruct((slots, chunk), jnp.int32))
    nval = place(jax.ShapeDtypeStruct((slots,), jnp.int32))
    out = {}
    for name, fn, args in (
            ("decode_step", lm.decode_step, (params, state, tok1)),
            ("prefill_step", lm.prefill_step, (params, state, tokc, nval))):
        compiled = jax.jit(fn, donate_argnums=(1,)).lower(*args).compile()
        ma = compiled.memory_analysis()
        out[name] = {
            "argument_bytes": ma.argument_size_in_bytes,
            "output_bytes": ma.output_size_in_bytes,
            "alias_bytes": ma.alias_size_in_bytes,
            "temp_bytes": ma.temp_size_in_bytes,
            "generated_code_bytes": ma.generated_code_size_in_bytes,
            "tpu_custom_calls": compiled.as_text().count("tpu_custom_call"),
        }
    return out


def main(argv) -> int:
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from bench.cell import BENCH
    from repro.kernels import ops as kops

    jax.config.update("jax_enable_compilation_cache", False)
    kops.set_backend("pallas")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one_chip = SingleDeviceSharding(topo.devices[0])
    names = argv or sorted(p.stem for p in (BENCH / "configs").glob("*.json"))
    for name in names:
        with open(BENCH / "configs" / f"{name}.json") as f:
            conf = json.load(f)
        print(json.dumps({"config": name, "slots": conf["max_slots"],
                          "max_seq_len": conf["max_seq_len"],
                          **compile_config(conf, one_chip)}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
