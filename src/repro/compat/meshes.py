"""Mesh construction and mesh-context queries.

Meshes are built with ``Auto`` axis types: the model stack annotates
activations with ``with_sharding_constraint`` against a bare
``PartitionSpec`` and lets GSPMD propagate the rest, which the
``Explicit`` default of ``jax.make_mesh`` refuses. The active mesh is
the ``AbstractMesh`` that ``jax.set_mesh`` installs — visible at trace
time (inside jit) and eagerly. This module is the single place that
decides both.
"""
from __future__ import annotations

import contextlib
from typing import Dict, Optional, Sequence, Tuple

import jax
from jax.sharding import AxisType, Mesh


def current_mesh():
    """The ``AbstractMesh`` made current via :func:`mesh_context`, else
    None. Valid at trace time (inside jit) and eagerly."""
    m = jax.sharding.get_abstract_mesh()
    return m if m.axis_names else None


def current_mesh_axis_names() -> Tuple[str, ...]:
    m = current_mesh()
    return tuple(m.axis_names) if m is not None else ()


def current_mesh_axis_sizes() -> Dict[str, int]:
    m = current_mesh()
    if m is None:
        return {}
    return dict(zip(m.axis_names, (int(s) for s in m.axis_sizes)))


def make_mesh(axis_shapes: Sequence[int],
              axis_names: Sequence[str],
              devices=None) -> Mesh:
    """``jax.make_mesh`` with every axis ``Auto`` (GSPMD-propagated)."""
    kwargs = {} if devices is None else {"devices": devices}
    return jax.make_mesh(tuple(axis_shapes), tuple(axis_names),
                         axis_types=(AxisType.Auto,) * len(axis_names),
                         **kwargs)


@contextlib.contextmanager
def mesh_context(mesh: Optional[Mesh]):
    """Make ``mesh`` current for sharding queries (``jax.set_mesh``).
    ``None`` is a no-op so callers can thread an optional mesh."""
    if mesh is None:
        yield None
        return
    with jax.set_mesh(mesh):
        yield mesh


def with_sharding_constraint(x, spec):
    """Annotate ``x`` with a sharding; resolved against the current mesh.

    Routed through the seam so a future rename lands in exactly one
    file.
    """
    return jax.lax.with_sharding_constraint(x, spec)
