"""``shard_map`` through the seam.

Callers use the neutral ``check_replication`` flag; the seam maps it onto
``jax.shard_map``'s ``check_vma`` so a future rename lands here.
"""
from __future__ import annotations

from typing import Callable

import jax


def shard_map(f: Callable, *, mesh, in_specs, out_specs,
              check_replication: bool = True) -> Callable:
    """Map ``f`` over mesh shards with manual collectives.

    ``check_replication=False`` disables the out-spec replication check
    (``check_vma``) — needed for programs whose replication the checker
    cannot prove, e.g. the masked psum that ends the pipeline schedule.
    """
    return jax.shard_map(f, mesh=mesh, in_specs=in_specs,
                         out_specs=out_specs, check_vma=check_replication)


def axis_size(axis_name) -> int:
    """Static size of a named (manual) mesh axis, inside shard_map —
    usable in Python control flow."""
    return jax.lax.axis_size(axis_name)
