"""The Pallas kernels compile for a described TPU v5e at serving widths.

Nothing here runs on a chip: the TPU compiler, which is installed with
``libtpu``, compiles for a ``v5e:2x2`` topology that is described, not
attached.  That catches what interpret mode cannot — tiles the chip's
layout refuses, fast memory over the limit — at about two seconds per
kernel.  The topology is described inside a module fixture (never at
import), so every pytest-xdist worker collects the same tests and only
the worker that runs this file loads the TPU library.  The persistent
compilation cache is off around these compiles: an entry written for a
described chip cannot be read back without one.
"""
import math
import os
import re

import pytest

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import SingleDeviceSharding

from repro.kernels.crossbar_exec.crossbar_exec import crossbar_exec
from repro.kernels.quant_matmul.quant_matmul import quant_matmul_int
from repro.kernels.quant_matmul.tp import _block


@pytest.fixture(scope="module")
def v5e_2x2():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return topo.devices


@pytest.fixture(scope="module")
def one_chip(v5e_2x2):
    return SingleDeviceSharding(v5e_2x2[0])


@pytest.fixture(scope="module", autouse=True)
def _no_persistent_cache():
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(shape, dtype, sharding):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


# (M, K, N) and the block edges the serving path uses at that shape:
# single-rank quant keeps the kernel's defaults; a quant_tp rank clamps
# them to its shard with tp._block (qwen1.5-0.5b w_out, K=2816 split 4 ways)
_QUANT_CASES = {
    "quant_decode_8x1024x2816": ((8, 1024, 2816), {}),
    "quant_tp_rank_8x704x1024": ((8, 704, 1024),
                                 dict(bm=_block(8, 128), bk=_block(704, 512),
                                      bn=_block(1024, 128))),
}


@pytest.mark.parametrize("case", list(_QUANT_CASES))
def test_quant_matmul_compiles_for_v5e(one_chip, case):
    (m, k, n), blocks = _QUANT_CASES[case]
    compiled = quant_matmul_int.lower(
        _spec((m, k), jnp.int8, one_chip), _spec((k, n), jnp.int8, one_chip),
        interpret=False, **blocks).compile()
    # the benchmark's quant_matmul_roofline finds the kernel's device time
    # by this prefix of the instruction's name: a rename must fail here,
    # not silence the metric
    names = re.findall(
        r'^\s*(?:ROOT )?%(\S+) = .*custom_call_target="tpu_custom_call"',
        compiled.as_text(), re.M)
    assert names and all(n.startswith("quant_matmul_int") for n in names)


def test_crossbar_exec_compiles_for_v5e_on_a_real_program(one_chip):
    from repro.pim import engine

    dot = engine.compile_dot(8, 8)              # n_cols 1024 MultPIM dot
    compiled = crossbar_exec.lower(
        _spec((1, dot.n_cols, 128), jnp.uint32, one_chip),
        _spec(dot.microcode.shape, jnp.int32, one_chip),
        interpret=False).compile()
    assert "tpu_custom_call" in compiled.as_text()


@pytest.mark.parametrize("kv", ["bf16", "int8"])
def test_paged_decode_step_writes_pool_in_place_on_v5e(one_chip, kv):
    """The scheduler's paged decode step, compiled for the chip, updates
    the donated pool in place.  Its K/V rows are 256 wide here, as the
    served configurations' are 640-1024: the TPU lays out an array whose
    last dim is under 128 with another dim minor, and a pool stored as
    (..., hkv, hd) with hd = 64 was copied to another layout and back
    every step (``model.fold_kv_rows``).  The int8 scales keep such a
    narrow last dim, so only the values are checked there."""
    from paged_hlo import pool_sized_moves, unaliased_pool_leaves

    import repro.configs as configs
    from repro.models import model_lib as M

    cfg = configs.get("qwen1.5-0.5b").smoke().scaled(
        n_layers=2, d_model=256, n_heads=4, n_kv_heads=4, head_dim=64,
        dtype="bfloat16", pim_mode="xla", kv_cache_dtype=kv)
    b, bs = 4, 16
    bps = cfg.max_seq_len // bs

    def on_chip(tree):
        return jax.tree.map(lambda s: _spec(s.shape, s.dtype, one_chip), tree,
                            is_leaf=lambda x: isinstance(x,
                                                         jax.ShapeDtypeStruct))

    params = on_chip(jax.eval_shape(
        lambda: M.init_params(cfg, jax.random.PRNGKey(0))))
    # more blocks than hd: the dim such a layout would make minor
    caches = on_chip(M.paged_cache_specs(cfg, b, cfg.max_seq_len, 257, bs))

    def serve_decode(p, tokens, pos, active, caches, tables):
        return M.decode_step_slots(p, tokens, pos, active, caches, cfg,
                                   block_tables=tables)

    text = M.serve_jit(serve_decode, donate="caches").lower(
        params, _spec((b, 1), jnp.int32, one_chip),
        _spec((b,), jnp.int32, one_chip), _spec((b,), jnp.bool_, one_chip),
        caches, _spec((b, bps), jnp.int32, one_chip)).compile().as_text()
    assert unaliased_pool_leaves(text, caches) == 0
    values = {i: {k: c[k] for k in M.FOLDED_KV_KEYS}
              for i, c in caches.items()}
    bad = pool_sized_moves(text, values)
    assert not bad, "pool-sized ops in the decode step:\n" + "\n".join(bad)


#: ``name = shape opcode(`` of a cross-chip op in HLO text; ``shape`` is one
#: array or a tuple of them
_COLLECTIVE = re.compile(
    r"^\s*(?:ROOT )?%?[\w.\-]+ = (.*?) (all-reduce|all-gather|reduce-scatter"
    r"|collective-permute|all-to-all)(?:-start)?\(", re.M)


def _largest_collectives(text):
    """(elements, line) of each cross-chip op, largest first; an op on a
    tuple counts its largest array."""
    out = []
    for m in _COLLECTIVE.finditer(text):
        dims = re.findall(r"\w+\[([\d,]*)\]", m.group(1))
        n = max((math.prod(int(d) for d in ds.split(",") if d)
                 for ds in dims), default=0)
        out.append((n, m.group(0).strip()[:160]))
    return sorted(out, reverse=True)


@pytest.mark.parametrize("kv_heads,head_dim", [(8, 64), (1, 128)],
                         ids=["heads_split", "mqa"])
def test_paged_decode_step_attends_within_a_chip_on_v5e_2x2(
        v5e_2x2, kv_heads, head_dim):
    """The scheduler's paged decode step under ``quant_tp`` with "model"
    over four chips, compiled for them: no cross-chip op carries the
    scores or the gathered K/V view of a layer, and each chip updates its
    part of the pool in place.  With eight KV heads each chip holds two
    heads' part of every (hkv * hd) row, 128 wide, and attends with them
    alone; with one KV head (MQA, hd 128 as granite-20b's) "model" cannot
    split the rows between heads, so the pool is replicated
    (``dist.cache_pspecs``) and each chip reads it whole."""
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from paged_hlo import pool_sized_moves, unaliased_pool_leaves

    import repro.configs as configs
    from repro.dist import context as dctx
    from repro.dist import partitioning as dpart
    from repro.models import model_lib as M
    from repro.models.layers import as_shapes

    cfg = configs.get("qwen1.5-0.5b").smoke().scaled(
        n_layers=2, d_model=512, n_heads=8, n_kv_heads=kv_heads,
        head_dim=head_dim, dtype="bfloat16", pim_mode="quant_tp",
        max_seq_len=1024)
    b, bs = 4, 16
    bps = cfg.max_seq_len // bs
    mesh = Mesh(np.array(v5e_2x2).reshape(1, 4), ("data", "model"))

    def placed(shapes, specs):
        return jax.tree.map(
            lambda s, p: _spec(s.shape, s.dtype, NamedSharding(mesh, p)),
            shapes, specs,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))

    def replicated(shape, dtype):
        return _spec(shape, dtype, NamedSharding(mesh, PartitionSpec()))

    def serve_decode(p, tokens, pos, active, caches, tables):
        return M.decode_step_slots(p, tokens, pos, active, caches, cfg,
                                   block_tables=tables)

    with dctx.use_mesh(mesh):
        pshapes = as_shapes(M.param_specs(cfg))
        params = placed(pshapes, dpart.param_pspecs(pshapes, mesh))
        cshapes = M.paged_cache_specs(cfg, b, cfg.max_seq_len, 257, bs)
        caches = placed(cshapes, dpart.cache_pspecs(
            cshapes, mesh, batch_over_dp=False,
            row_heads={k: kv_heads for k in M.FOLDED_KV_KEYS}))
        text = M.serve_jit(serve_decode, donate="caches").lower(
            params, replicated((b, 1), jnp.int32),
            replicated((b,), jnp.int32), replicated((b,), jnp.bool_),
            caches, replicated((b, bps), jnp.int32)).compile().as_text()

    assert unaliased_pool_leaves(text, caches) == 0
    bad = pool_sized_moves(text, caches)
    assert not bad, "pool-sized ops in the decode step:\n" + "\n".join(bad)
    # a layer's scores hold b * n_heads * lcap values, its view b * lcap *
    # hkv * hd: both far above the activations and logits the linears'
    # collectives move (b * d_ff at most here)
    scores = b * cfg.n_heads * cfg.max_seq_len
    big = [line for n, line in _largest_collectives(text) if n >= scores]
    assert not big, "scores or views cross chips:\n" + "\n".join(big)
