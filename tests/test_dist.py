"""repro.dist unit tests: mesh context nesting, no-op safety, axis
resolution on 1D/2D/3D meshes, and pspec factories for param / optimizer /
batch / cache trees (fsdp on and off).

The suite runs on 8 forced CPU devices (see conftest.py)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.dist import context as dctx
from repro.dist import partitioning as part
from repro.launch.mesh import make_mesh


def mesh2d(data=4, model=2):
    return make_mesh((data, model), ("data", "model"))


# ---------------------------------------------------------------------------
# context
# ---------------------------------------------------------------------------

def test_no_mesh_is_total_noop():
    assert dctx.current_mesh() is None
    assert dctx.dp_axes() == ()
    assert dctx.tp_axis() is None
    x = jnp.ones((4, 4))
    assert dctx.shard(x, "data", "model") is x
    assert dctx.shard_batch_dim(x) is x


def test_use_mesh_nesting_restores_outer():
    outer, inner = mesh2d(4, 2), make_mesh((8,), ("data",))
    with dctx.use_mesh(outer):
        assert dctx.current_mesh() is outer
        assert dctx.dp_axes() == ("data",)
        assert dctx.tp_axis() == "model"
        with dctx.use_mesh(inner):
            assert dctx.current_mesh() is inner
            assert dctx.dp_axes() == ("data",)
            assert dctx.tp_axis() is None
        assert dctx.current_mesh() is outer
        assert dctx.tp_axis() == "model"
    assert dctx.current_mesh() is None


def test_use_mesh_restores_on_exception():
    with pytest.raises(RuntimeError):
        with dctx.use_mesh(mesh2d()):
            raise RuntimeError("boom")
    assert dctx.current_mesh() is None


@pytest.mark.parametrize("shape,axes,want_dp,want_tp", [
    ((8,), ("data",), ("data",), None),
    ((8,), ("model",), (), "model"),
    ((4, 2), ("data", "model"), ("data",), "model"),
    ((2, 2, 2), ("pod", "data", "model"), ("pod", "data"), "model"),
])
def test_axis_resolution(shape, axes, want_dp, want_tp):
    with dctx.use_mesh(make_mesh(shape, axes)):
        assert dctx.dp_axes() == want_dp
        assert dctx.tp_axis() == want_tp


def test_dp_axes_override_dp_only_policy():
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    with dctx.use_mesh(mesh, dp_axes=("pod", "data", "model")):
        assert dctx.dp_axes() == ("pod", "data", "model")
        assert dctx.tp_axis() is None
    with pytest.raises(ValueError):
        with dctx.use_mesh(mesh, dp_axes=("nope",)):
            pass


def test_shard_applies_constraint_in_jit():
    mesh = mesh2d(4, 2)

    @jax.jit
    def f(x):
        return dctx.shard(x, "data", "model")

    with dctx.use_mesh(mesh):
        y = f(jnp.ones((8, 4)))
    assert y.sharding.spec == P("data", "model")


def test_shard_drops_non_dividing_axes():
    mesh = mesh2d(4, 2)
    with dctx.use_mesh(mesh):
        # 6 % 4 != 0 -> data axis dropped; 4 % 2 == 0 -> model kept
        y = dctx.shard(jnp.ones((6, 4)), "data", "model")
        assert y.sharding.spec == P(None, "model")
        # nothing shardable -> identity (no constraint inserted)
        x = jnp.ones((3, 3))
        assert dctx.shard(x, "data", "model") is x


def test_shard_batch_dim_uses_all_dp_axes():
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    with dctx.use_mesh(mesh):
        y = dctx.shard_batch_dim(jnp.ones((8, 3)))
        assert y.sharding.spec == P(("pod", "data"), None)


def test_mesh_axes_for_foreign_mesh():
    active = mesh2d(4, 2)
    other = make_mesh((2, 2, 2), ("pod", "data", "model"))
    with dctx.use_mesh(active, dp_axes=("data", "model")):
        assert dctx.mesh_axes(active) == (("data", "model"), None)
        assert dctx.mesh_axes(other) == (("pod", "data"), "model")


# ---------------------------------------------------------------------------
# partitioning
# ---------------------------------------------------------------------------

def _shapes(tree):
    return jax.tree.map(lambda s: jax.ShapeDtypeStruct(s, jnp.float32), tree,
                        is_leaf=lambda x: isinstance(x, tuple))


PARAMS = _shapes({
    "embed": (1024, 64),            # vocab x d
    "norm": (64,),
    "blocks": {"w_in": (4, 64, 256), "w_out": (4, 256, 64)},
})


def test_param_pspecs_tp_picks_largest_dim_late_ties():
    mesh = mesh2d(4, 2)
    specs = part.param_pspecs(PARAMS, mesh, fsdp=False)
    assert specs["embed"] == P("model", None)          # vocab largest
    assert specs["norm"] == P("model")                 # 64 % 2 == 0
    assert specs["blocks"]["w_in"] == P(None, None, "model")
    assert specs["blocks"]["w_out"] == P(None, "model", None)


def test_param_pspecs_fsdp_adds_data_axis():
    mesh = mesh2d(4, 2)
    specs = part.param_pspecs(PARAMS, mesh, fsdp=True)
    assert specs["embed"] == P("model", "data")
    assert specs["blocks"]["w_in"] == P(None, "data", "model")
    assert specs["blocks"]["w_out"] == P(None, "model", "data")
    # fsdp=False leaves "data" out everywhere
    flat = jax.tree.leaves(part.param_pspecs(PARAMS, mesh, fsdp=False))
    assert all("data" not in [a for e in sp if e for a in
               ((e,) if isinstance(e, str) else e)] for sp in flat)


def test_param_pspecs_tp_off_replicates_model_axis():
    mesh = mesh2d(4, 2)
    specs = part.param_pspecs(PARAMS, mesh, fsdp=False, tp=False)
    assert all(sp == P(*([None] * len(sp)))
               for sp in jax.tree.leaves(specs))


def test_opt_state_pspecs_mirror_params_and_factored_stats():
    from repro.optim.adamw import AdamWConfig, init_state

    mesh = mesh2d(4, 2)
    cfg = AdamWConfig(factored=True, factored_min_dim=64)
    ostate = jax.eval_shape(lambda: init_state(cfg, PARAMS))
    p_part = part.param_pspecs(PARAMS, mesh, fsdp=True)
    o_part = part.opt_state_pspecs(PARAMS, p_part, ostate, mesh)
    assert o_part["step"] == P()
    leaves = o_part["leaves"]
    assert leaves["embed"]["m"] == p_part["embed"]
    # embed (1024, 64) factored: vr (1024,) keeps dim-0 spec, vc (64,) dim-1
    assert leaves["embed"]["vr"] == P("model")
    assert leaves["embed"]["vc"] == P("data")
    assert leaves["norm"]["v"] == p_part["norm"]
    # structures line up exactly with the real state tree
    assert (jax.tree.structure(o_part["leaves"])
            == jax.tree.structure(ostate["leaves"]))


def test_batch_pspecs_shard_leading_dim():
    mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
    batch = _shapes({"tokens": (16, 32), "labels": (16, 32)})
    with dctx.use_mesh(mesh):
        specs = part.batch_pspecs(batch, mesh)
    assert specs["tokens"] == P(("pod", "data"), None)
    # non-dividing batch replicates
    odd = _shapes({"tokens": (3, 32)})
    with dctx.use_mesh(mesh):
        assert part.batch_pspecs(odd, mesh)["tokens"] == P(None, None)


def test_cache_pspecs_batch_and_head_dims():
    mesh = mesh2d(4, 2)
    caches = _shapes({
        "kv": (6, 8, 128, 2, 16),    # (ns, batch, cap, hkv, hd)
        "ssm": (6, 8, 64, 16),       # (ns, batch, d_inner, d_state)
        "m": (6, 8, 4),
    })
    specs = part.cache_pspecs(caches, mesh)
    assert specs["kv"] == P(None, "data", None, "model", None)
    assert specs["ssm"] == P(None, "data", "model", None)  # d_inner on -2
    assert specs["m"] == P(None, "data", None)


def test_tree_shardings_wraps_every_spec():
    mesh = mesh2d(4, 2)
    specs = part.param_pspecs(PARAMS, mesh)
    sh = part.tree_shardings(specs, mesh)
    flat = jax.tree.leaves(sh, is_leaf=lambda x: isinstance(x, NamedSharding))
    assert len(flat) == len(jax.tree.leaves(PARAMS))
    assert all(isinstance(s, NamedSharding) and s.mesh is mesh for s in flat)


def test_sharded_matmul_matches_single_device():
    """End-to-end numeric check: same result with and without a mesh.

    Integer-valued inputs make every partial sum exact in float32, so the
    check is equality whatever order the CPU's per-shard dot kernels sum
    in (random normals left 2e-6 rounding differences on some hosts)."""
    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.integers(-8, 9, size=(8, 64)).astype(np.float32))
    w = jnp.asarray(rng.integers(-8, 9, size=(64, 32)).astype(np.float32))

    def f(x, w):
        x = dctx.shard_batch_dim(x)
        y = x @ w
        return dctx.shard(y, dctx.dp_axes(), dctx.tp_axis())

    # The active mesh is read at *trace* time, so jit separately per context.
    want = np.asarray(jax.jit(f)(x, w))
    with dctx.use_mesh(mesh2d(4, 2)):
        got = np.asarray(jax.jit(f)(x, w))
    np.testing.assert_array_equal(got, want)
