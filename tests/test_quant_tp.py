"""Tensor-parallel ``quant_tp`` execution mode.

The load-bearing claims, mirrored from the kernel docstring:

1. both shard_map splits (column- and row-parallel) and the non-divisible
   padding path reproduce the single-rank "quant" result bit-for-bit at
   the int8/int32 level (jit-vs-jit identical; eager references differ
   only by fusion-order ulps in the final float rescale);
2. the mode threads end to end — prefill, scalar decode, and the serving
   runtime's slot decode through contiguous *and* block-paged pools —
   without retracing, and greedy tokens match the single-rank path
   exactly;
3. the straight-through ``custom_vjp`` makes it train under shard_map;
4. dispatch goes through the one engine registry ("quant_tp" backend +
   MODES entry), and the mode degrades to "quant" outside a mesh.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist import context as dctx
from repro.dist import partitioning as dpart
from repro.kernels.quant_matmul import (quant_linear, tp_quant_linear,
                                        tp_split, tp_tile_shape)
from repro.launch.mesh import make_host_mesh, make_mesh
from repro.models import model_lib as M
from repro.models.layers import linear
from repro.pim import engine
from repro.serving import Scheduler, ServingConfig


@pytest.fixture(scope="module")
def tp8():
    return make_mesh((8,), ("model",))


def _rand(rng, *shape):
    return jnp.asarray(rng.normal(size=shape).astype(np.float32))


# --------------------------------------------------------------------------
# kernel: split selection + bit-exactness vs single-rank quant
# --------------------------------------------------------------------------

def test_tp_split_matches_param_placement():
    """The tile split must follow the dim param_pspecs shards, so weight
    shards are local to their rank's tile."""
    assert dpart.tp_shard_dim((64, 128), 8) == 1
    assert dpart.tp_shard_dim((128, 64), 8) == 0
    assert dpart.tp_shard_dim((64, 64), 8) == 1      # tie -> later (col)
    assert dpart.tp_shard_dim((60, 52), 8) == -1
    assert tp_split((64, 128), 8) == "col"
    assert tp_split((128, 64), 8) == "row"
    assert tp_split((60, 52), 8) == "col"            # pad fallback
    assert tp_tile_shape((64, 128), 8) == (64, 16)
    assert tp_tile_shape((128, 64), 8) == (16, 64)
    assert tp_tile_shape((60, 52), 8) == (60, 7)     # 52 -> 56 padded / 8


@pytest.mark.parametrize("m,k,n", [
    (4, 64, 128),    # column-parallel
    (4, 128, 64),    # row-parallel (psum over int32 partials)
    (5, 60, 52),     # neither dim divides: zero-pad N, slice back
    (3, 33, 56),     # K odd, N divides
    (2, 8, 8),       # single-block tiles
])
def test_kernel_bit_exact_vs_single_rank(tp8, m, k, n):
    rng = np.random.default_rng(m * 100 + n)
    x, w = _rand(rng, m, k), _rand(rng, k, n)
    ref = np.asarray(jax.jit(lambda a, b: quant_linear(a, b))(x, w))
    with dctx.use_mesh(tp8):
        got = np.asarray(jax.jit(lambda a, b: tp_quant_linear(a, b))(x, w))
    np.testing.assert_array_equal(got, ref)


def test_kernel_leading_batch_dims(tp8):
    rng = np.random.default_rng(0)
    x, w = _rand(rng, 2, 3, 64), _rand(rng, 64, 32)
    ref = np.asarray(jax.jit(lambda a, b: quant_linear(a, b))(x, w))
    with dctx.use_mesh(tp8):
        got = np.asarray(jax.jit(lambda a, b: tp_quant_linear(a, b))(x, w))
    assert got.shape == (2, 3, 32)
    np.testing.assert_array_equal(got, ref)


def test_without_mesh_degrades_to_quant_exactly():
    rng = np.random.default_rng(1)
    x, w = _rand(rng, 4, 16), _rand(rng, 16, 24)
    np.testing.assert_array_equal(np.asarray(tp_quant_linear(x, w)),
                                  np.asarray(quant_linear(x, w)))


def test_data_model_mesh(tp8):
    """On a (data, model) mesh the tile shards only over "model"."""
    mesh = make_host_mesh(model=2)
    rng = np.random.default_rng(2)
    x, w = _rand(rng, 8, 64), _rand(rng, 64, 32)
    ref = np.asarray(jax.jit(lambda a, b: quant_linear(a, b))(x, w))
    with dctx.use_mesh(mesh):
        got = np.asarray(jax.jit(lambda a, b: tp_quant_linear(a, b))(x, w))
    np.testing.assert_array_equal(got, ref)


# --------------------------------------------------------------------------
# engine registry dispatch
# --------------------------------------------------------------------------

def test_backend_kinds_guard_execute_state():
    """quant_tp is a linear-kind backend: the state-executor entry point
    must reject it loudly instead of feeding microcode to a GEMM."""
    assert engine.backend_kind("quant_tp") == "linear"
    assert engine.backend_kind("scan") == "state"
    with pytest.raises(ValueError, match="linear lowering"):
        engine.execute_state(np.zeros((1, 8, 1), np.uint32),
                             np.zeros((2, 4), np.int32), backend="quant_tp")
    with pytest.raises(ValueError, match="unknown backend"):
        engine.backend_kind("does-not-exist")


def test_engine_registry_dispatch(tp8):
    assert "quant_tp" in engine.MODES
    assert "quant_tp" in engine.backends()
    fn = engine.get_backend("quant_tp")
    rng = np.random.default_rng(3)
    x, w = _rand(rng, 4, 64), _rand(rng, 64, 32)
    ref = np.asarray(jax.jit(lambda a, b: quant_linear(a, b))(x, w))
    with dctx.use_mesh(tp8):
        # the registry entry, the layers.linear mode dispatch, and the
        # ambient-mode context all land on the same tile
        got_reg = np.asarray(jax.jit(fn)(x, w))
        got_lin = np.asarray(jax.jit(
            lambda a, b: linear(a, b, mode="quant_tp"))(x, w))
        with engine.mode("quant_tp"):
            got_amb = np.asarray(jax.jit(lambda a, b: linear(a, b))(x, w))
    np.testing.assert_array_equal(got_reg, ref)
    np.testing.assert_array_equal(got_lin, ref)
    np.testing.assert_array_equal(got_amb, ref)


# --------------------------------------------------------------------------
# grads: straight-through estimator under shard_map
# --------------------------------------------------------------------------

def test_grad_straight_through_under_shard_map(tp8):
    rng = np.random.default_rng(4)
    x, w = _rand(rng, 4, 16), _rand(rng, 16, 24)

    def loss(w_):
        return jnp.sum(tp_quant_linear(x, w_) ** 2)

    with dctx.use_mesh(tp8):
        val, grad = jax.jit(jax.value_and_grad(loss))(w)
        y = np.asarray(jax.jit(lambda a, b: tp_quant_linear(a, b))(x, w))
    # d/dw sum(y^2) with the quantized forward treated as x @ w
    ref = np.asarray(x).T @ (2 * y)
    assert np.isfinite(float(val))
    np.testing.assert_allclose(np.asarray(grad), ref, rtol=1e-5)


def test_trains_through_loss_fn(tp8, small_model_config):
    """cfg.pim_mode="quant_tp" reaches a jitted value_and_grad loss."""
    cfg = small_model_config.scaled(n_layers=1, pattern=("ad",),
                                    loss_chunk=8, pim_mode="quant_tp")
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 8)),
                              jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (1, 8)),
                              jnp.int32),
    }
    with dctx.use_mesh(tp8):
        loss, grads = jax.jit(jax.value_and_grad(
            lambda p: M.loss_fn(p, batch, cfg)))(params)
    assert np.isfinite(float(loss))
    gnorm = float(jnp.sqrt(sum(jnp.sum(g.astype(jnp.float32) ** 2)
                               for g in jax.tree.leaves(grads))))
    assert np.isfinite(gnorm) and gnorm > 0


# --------------------------------------------------------------------------
# model threading: prefill + decode vs single-rank quant
# --------------------------------------------------------------------------

def test_prefill_and_decode_match_quant(tp8, small_model_config):
    cfg_q = small_model_config.scaled(pim_mode="quant")
    cfg_tp = cfg_q.scaled(pim_mode="quant_tp")
    params = M.init_params(cfg_q, jax.random.PRNGKey(1))
    rng = np.random.default_rng(1)
    toks = rng.integers(0, cfg_q.vocab_size, (2, 9))
    batch = {"tokens": jnp.asarray(toks[:, :8], jnp.int32)}
    nxt = jnp.asarray(toks[:, 8:9], jnp.int32)

    lg_q, c_q = jax.jit(lambda p, b: M.prefill(p, b, cfg_q))(params, batch)
    _, d_q, _ = jax.jit(
        lambda p, t, c: M.decode_step(p, t, jnp.int32(8), c, cfg_q))(
        params, nxt, c_q)
    with dctx.use_mesh(tp8):
        lg_t, c_t = jax.jit(lambda p, b: M.prefill(p, b, cfg_tp))(params,
                                                                  batch)
        _, d_t, _ = jax.jit(
            lambda p, t, c: M.decode_step(p, t, jnp.int32(8), c, cfg_tp))(
            params, nxt, c_t)
    # per-token outputs within ulp-fusion noise of the single-rank quant
    # path (the int accumulation is identical; only the float rescale and
    # downstream norm/attention fusion orders can differ across programs)
    scale = np.abs(np.asarray(lg_q)).max()
    assert np.abs(np.asarray(lg_t) - np.asarray(lg_q)).max() < 1e-4 * scale
    dscale = np.abs(np.asarray(d_q)).max()
    assert np.abs(np.asarray(d_t) - np.asarray(d_q)).max() < 1e-4 * dscale


@pytest.mark.parametrize("paged", [False, True])
def test_serving_matches_quant_both_pools(small_model_config, paged):
    """Continuous-batching decode under the 8-device (data, model) mesh:
    greedy tokens identical to the meshless single-rank quant scheduler
    through the contiguous and block-paged pools, one decode trace."""
    cfg_q = small_model_config.scaled(pim_mode="quant")
    cfg_tp = cfg_q.scaled(pim_mode="quant_tp")
    params = M.init_params(cfg_q, jax.random.PRNGKey(0))
    prompts = [([1, 2, 3, 4, 5], 6), ([9, 8], 4), ([3, 1, 4, 1, 5, 9], 5)]

    s_q = Scheduler(params, cfg_q,
                    ServingConfig(max_batch=2, prompt_bucket=8,
                                  paged=paged, block_size=8))
    rids_q = [s_q.submit(p, n) for p, n in prompts]
    out_q = s_q.run()

    mesh = make_host_mesh(model=2)
    with dctx.use_mesh(mesh):
        s_t = Scheduler(params, cfg_tp,
                        ServingConfig(max_batch=2, prompt_bucket=8,
                                      paged=paged, block_size=8), mesh=mesh)
        rids_t = [s_t.submit(p, n) for p, n in prompts]
        out_t = s_t.run()
    assert s_t.decode_traces == 1
    for ra, rb in zip(rids_q, rids_t):
        np.testing.assert_array_equal(out_q[ra], out_t[rb])


def test_placed_weights_serve_quant_tp_like_quant(small_model_config):
    """``serve.place_params`` splits the weights over the "model" axis
    (none stays whole on the device it was made on), and quant_tp served
    from them through ``serve_trace`` emits single-rank quant's tokens."""
    import time

    from repro.launch.serve import place_params, serve_trace
    from repro.serving import synthetic_requests

    cfg_q = small_model_config.scaled(pim_mode="quant")
    params = M.init_params(cfg_q, jax.random.PRNGKey(0))
    mesh = make_mesh((4,), ("model",))
    placed = place_params(params, cfg_q, mesh)
    leaves = jax.tree_util.tree_leaves(placed)
    assert {len(a.sharding.device_set) for a in leaves} == {4}
    assert any(a.addressable_shards[0].data.shape != a.shape for a in leaves)

    def serve(p, cfg, mesh=None):
        reqs = synthetic_requests(3, vocab_size=cfg.vocab_size,
                                  prompt_lens=(4, 8), max_new_tokens=4,
                                  seed=0, start_time=time.monotonic())
        out, s = serve_trace(p, cfg, reqs, max_batch=2, prompt_bucket=8,
                             mesh=mesh, paged=True, block_size=8)
        assert s["decode_traces"] == 1
        return [out[r.rid] for r in reqs]

    with dctx.use_mesh(mesh):
        got = serve(placed, cfg_q.scaled(pim_mode="quant_tp"), mesh)
    for a, b in zip(got, serve(params, cfg_q)):
        np.testing.assert_array_equal(a, b)


# --------------------------------------------------------------------------
# pspec plumbing for the sharded leaves
# --------------------------------------------------------------------------

def test_cache_pspecs_scale_leaves_follow_their_kv_heads():
    """Quantized-KV scale leaves get "model" on their *last* (head) dim,
    staying aligned with the (…, heads, hd) values they rescale."""
    from jax.sharding import PartitionSpec as P

    mesh = make_host_mesh(model=2)
    caches = {
        "k": jax.ShapeDtypeStruct((6, 8, 128, 2, 16), jnp.int8),
        "k_scale": jax.ShapeDtypeStruct((6, 8, 128, 2), jnp.float32),
    }
    specs = dpart.cache_pspecs(caches, mesh)
    assert specs["k"] == P(None, "data", None, "model", None)
    assert specs["k_scale"] == P(None, "data", None, "model")
