"""The version-portability seam: mesh-context queries in/out of a mesh
and under jit, the shard_map dispatch, and the sharding-rule edge cases
(batch=1 decode, odd vocab) that ride on it.  Single-device — the
multi-device faces run in test_distributed.py subprocesses."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import PartitionSpec as P

from repro import compat
from repro.distributed.sharding import (
    constrain,
    drop_indivisible,
    resolve_axes,
    spec_for,
)


def test_support_matrix_reports_resolved_paths():
    sm = compat.support_matrix()
    assert sm["jax"] == jax.__version__
    assert sm["pallas"] in ("compiled", "interpret")
    assert sm["kernel_mode"] in ("pallas", "jnp")


def test_axis_queries_outside_any_mesh():
    assert compat.current_mesh() is None
    assert compat.current_mesh_axis_names() == ()
    assert compat.current_mesh_axis_sizes() == {}


def test_axis_queries_inside_mesh():
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    with compat.mesh_context(mesh):
        assert compat.current_mesh_axis_names() == ("data", "model")
        assert compat.current_mesh_axis_sizes() == {"data": 1, "model": 1}
    # context restored on exit
    assert compat.current_mesh_axis_names() == ()


def test_axis_queries_under_jit():
    mesh = compat.make_mesh((1,), ("data",))
    seen = []

    def f(x):
        seen.append(compat.current_mesh_axis_names())
        return constrain(x, ("data", None))

    with compat.mesh_context(mesh):
        y = jax.jit(f)(jnp.ones((2, 3)))
    np.testing.assert_array_equal(np.asarray(y), np.ones((2, 3)))
    assert seen and seen[0] == ("data",)      # mesh visible at trace time


def test_resolve_axes_multipod_expansion():
    mesh = compat.make_mesh((1, 1, 1), ("pod", "data", "model"))
    with compat.mesh_context(mesh):
        # "data" expands to joint DP over ("pod", "data")
        assert resolve_axes(("data", None)) == P(("pod", "data"), None)
        # unknown axis names replicate rather than error
        assert resolve_axes(("stage", "model")) == P(None, "model")
    # outside any mesh every axis replicates
    assert resolve_axes(("data", "model")) == P(None, None)


def test_drop_indivisible_batch1_decode():
    # uneven batch=1 decode on a 2x8x4 pod mesh: the DP axes (pod*data
    # = 16) cannot divide batch 1 -> replicated; the vocab dim still
    # shards over model
    sizes = {"pod": 2, "data": 8, "model": 4}
    spec = P(("pod", "data"), None, "model")
    shape = (1, 1, 1024)
    assert drop_indivisible(spec, shape, axis_sizes=sizes) == \
        P(None, None, "model")
    # odd vocab additionally drops the model axis
    assert drop_indivisible(spec, (1, 1, 1023), axis_sizes=sizes) == \
        P(None, None, None)
    # divisible batch keeps the joint DP axes
    assert drop_indivisible(spec, (16, 1, 1024), axis_sizes=sizes) == \
        P(("pod", "data"), None, "model")
    # spec shorter than rank: trailing dims replicate, no IndexError
    assert drop_indivisible(P("model"), (8, 3), axis_sizes=sizes) == \
        P("model", None)


def test_constrain_noop_outside_mesh():
    x = jnp.ones((4, 4))
    assert constrain(x, ("data", "model")) is x


def test_constrain_rank_mismatch_raises():
    # real spec errors must surface — the old blanket except silently
    # replicated the tensor instead
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    with compat.mesh_context(mesh):
        with pytest.raises(ValueError, match="constrain"):
            constrain(jnp.ones((4,)), ("data", None, "model"))


def test_spec_for_matches_rules_inside_mesh():
    mesh = compat.make_mesh((1, 1), ("data", "model"))
    with compat.mesh_context(mesh):
        assert spec_for("blocks/attn/wq", (64, 64)) == P(None, "model")
        assert spec_for("blocks/attn/wo", (64, 64)) == P("model", None)
        # stacked (L, ...) scan params align rules to trailing dims
        assert spec_for("blocks/mlp/w_in", (4, 64, 64)) == \
            P(None, None, "model")


def test_shard_map_seam_runs_under_jit():
    mesh = compat.make_mesh((1,), ("data",))
    g = compat.shard_map(
        lambda x: jax.lax.psum(x, "data"),
        mesh=mesh, in_specs=P("data"), out_specs=P("data"),
        check_replication=False,
    )
    out = jax.jit(g)(jnp.arange(4.0))
    np.testing.assert_allclose(np.asarray(out), np.arange(4.0))


def test_axis_size_inside_shard_map():
    mesh = compat.make_mesh((1,), ("data",))
    sizes = []

    def f(x):
        sizes.append(int(compat.axis_size("data")))
        return x

    compat.shard_map(f, mesh=mesh, in_specs=P(None),
                     out_specs=P(None))(jnp.zeros((2,)))
    assert sizes == [1]


def test_prng_helpers_are_raw_keys():
    k = compat.prng_key(0)
    assert k.dtype == jnp.uint32         # raw keys, not typed keys
    k1, k2 = compat.prng_split(k)
    assert not np.array_equal(np.asarray(k1), np.asarray(k2))
    f = compat.prng_fold_in(k, 7)
    assert f.shape == k.shape


def test_compat_jit_donation():
    @compat.jit(donate_argnums=(0,))
    def f(x):
        return x + 1

    assert float(f(jnp.float32(1.0))) == 2.0


def test_tree_helpers_roundtrip():
    tree = {"a": jnp.zeros((2,)), "b": [jnp.ones((1,)), 3.0]}
    leaves, treedef = compat.tree_flatten(tree)
    assert compat.tree_unflatten(treedef, leaves)["a"].shape == (2,)
    doubled = compat.tree_map(lambda x: x * 2, tree)
    assert float(doubled["b"][1]) == 6.0
    paths = []
    compat.tree_map_with_path(
        lambda p, x: paths.append(compat.path_str(p)), tree)
    assert "a" in paths and "b/0" in paths
