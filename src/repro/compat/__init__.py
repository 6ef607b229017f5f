"""The JAX substrate seam.

Everything above the packing kernels talks to JAX through this package
instead of scattered ``jax.*`` attribute lookups: mesh construction and
queries (``meshes``), ``shard_map`` (``shardmap``), PRNG and donation
helpers (``prng``), pytree utilities (``trees``), the Pallas execution
mode (``pallas``) and the persistent compilation cache (``cache``). The
supported generation is JAX 0.9; when an API moves, the change lands
here and not in every consumer. ``support_matrix()`` reports what the
run-time choices resolved to (the dry-run prints it, benchmarks embed
it).

The paper's framing applies directly: the register-file work survives
hardware generations because the compression seam lives in one
dedicated layer, not in every consumer.  Same move here — this package
is the only place allowed to mention ``jax.shard_map``,
``get_abstract_mesh`` or ``jax._src.mesh``.
"""
from __future__ import annotations

import jax

from repro.compat.pallas import (
    default_kernel_mode,
    on_tpu,
    pallas_interpret_default,
)
from repro.compat.cache import enable_compile_cache
from repro.compat.meshes import (
    current_mesh,
    current_mesh_axis_names,
    current_mesh_axis_sizes,
    make_mesh,
    mesh_context,
    with_sharding_constraint,
)
from repro.compat.prng import jit, prng_fold_in, prng_key, prng_split
from repro.compat.shardmap import axis_size, shard_map
from repro.compat.trees import (
    path_str,
    tree_flatten,
    tree_flatten_with_path,
    tree_leaves,
    tree_map,
    tree_map_with_path,
    tree_structure,
    tree_unflatten,
)

__all__ = [
    "current_mesh",
    "current_mesh_axis_names",
    "current_mesh_axis_sizes",
    "make_mesh",
    "mesh_context",
    "with_sharding_constraint",
    "shard_map",
    "axis_size",
    "jit",
    "prng_key",
    "prng_split",
    "prng_fold_in",
    "path_str",
    "tree_flatten",
    "tree_flatten_with_path",
    "tree_leaves",
    "tree_map",
    "tree_map_with_path",
    "tree_structure",
    "tree_unflatten",
    "support_matrix",
    "on_tpu",
    "default_kernel_mode",
    "pallas_interpret_default",
    "enable_compile_cache",
]


def support_matrix() -> dict:
    """The JAX version and what the run-time choices resolved to here:
    the Pallas execution mode and the kernel backend."""
    return {
        "jax": jax.__version__,
        "pallas": "interpret" if pallas_interpret_default() else "compiled",
        "kernel_mode": default_kernel_mode(),
    }
