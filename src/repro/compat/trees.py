"""Pytree utilities through the seam.

The stack imports its tree functions from here (the ``jax.tree``
namespace), so a future rename lands in one file.
"""
from __future__ import annotations

import jax

tree_map = jax.tree.map
tree_leaves = jax.tree.leaves
tree_flatten = jax.tree.flatten
tree_unflatten = jax.tree.unflatten
tree_structure = jax.tree.structure
tree_map_with_path = jax.tree.map_with_path
tree_flatten_with_path = jax.tree.flatten_with_path


def path_str(path) -> str:
    """Render a tree path as 'a/b/0/c' — the canonical form the sharding
    rules match against (dict keys and sequence indices alike)."""
    return "/".join(str(getattr(p, "key", getattr(p, "idx", p)))
                    for p in path)
