"""Checks that need a four-device mesh, each in a process of its own with
four host CPU devices, whatever topology the test process has:

    python four_devices.py weights
    python four_devices.py calls
    python four_devices.py run <dir> [no_exchange]

Each prints one JSON object as its last line of standard output (``run``
prints the benchmark's own result line).  ``launch`` starts one from a
test.
"""
import json
import os
import subprocess
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
SRC = os.path.join(os.path.dirname(os.path.dirname(BENCH)), "src")
SEED = 2 ** 32 + 4444
ARCHS = ("qwen1.5-0.5b", "h2o-danube-1.8b", "granite-moe-1b-a400m",
         "granite-20b")


def launch(*args, timeout=600):
    """Run this file with ``args`` on four host CPU devices."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    return subprocess.run([sys.executable, os.path.abspath(__file__),
                           *args], env=env, capture_output=True, text=True,
                          timeout=timeout)


def _mesh():
    import jax

    from repro.runtime.fault_tolerance import ElasticMesh

    assert len(jax.devices()) == 4, jax.devices()
    return ElasticMesh(model_parallel=4).make()


def weights():
    """Per registry entry: whether the tree made under the program's
    shardings equals the one made on one device, leaf by leaf, and whether
    each leaf's sharding is the one ``param_pspecs`` gives it."""
    import jax
    import numpy as np

    import repro.configs as configs
    from repro.dist.partitioning import param_pspecs
    from repro.models import model_lib as M
    from repro.models.layers import as_shapes
    from servebench import weights as W

    mesh = _mesh()
    out = {}
    for arch in ARCHS:
        cfg = configs.get(arch).smoke()
        one = W.make_params(cfg, SEED)
        tp = W.make_params(cfg, SEED, mesh=mesh)
        specs = param_pspecs(as_shapes(M.param_specs(cfg)), mesh)
        flat_one = jax.tree_util.tree_leaves_with_path(one)
        flat_tp = jax.tree_util.tree_leaves(tp)
        flat_specs = jax.tree_util.tree_leaves(
            specs, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec))
        out[arch] = {
            "leaves": len(flat_tp),
            "unequal": [W.path_name(p) for (p, a), b in zip(flat_one, flat_tp)
                        if not np.array_equal(np.asarray(a), np.asarray(b))],
            "misplaced": [W.path_name(p) for (p, _), b, s in
                          zip(flat_one, flat_tp, flat_specs)
                          if b.sharding.spec != s or b.sharding.mesh != mesh],
            "split": sum(any(e is not None for e in b.sharding.spec)
                         for b in flat_tp),
            "devices": sorted({len(b.sharding.device_set) for b in flat_tp}),
        }
    return out


def calls():
    """(M, K, N) -> calls of the ``quant_matmul_int`` jit in the program's
    ``quant_tp`` decode step and prefill over a model=4 mesh (each a
    shard_map tile, traced once for all four chips), beside the count
    ``flops.kernel_calls`` gives at ``r = 4``."""
    import jax
    import jax.numpy as jnp

    import repro.configs as configs
    from repro.dist import context as dctx
    from repro.models import model_lib as M
    from repro.models.layers import as_shapes
    from servebench import flops
    from servebench.window import StepRecord
    from test_bench_flops import B, BUCKET, _calls

    mesh = _mesh()
    out = {}
    for arch in ARCHS[:2] + ARCHS[3:]:
        cfg = configs.get(arch).smoke().scaled(
            pim_mode="quant_tp", n_layers=3, max_seq_len=64)
        params = as_shapes(M.param_specs(cfg))
        nb = B * -(-cfg.max_seq_len // 8) + 1
        caches = M.paged_cache_specs(cfg, B, cfg.max_seq_len, nb, 8)
        bps = cfg.window_ring_blocks(8) or -(-cfg.max_seq_len // 8)
        with dctx.use_mesh(mesh):
            dec = jax.make_jaxpr(
                lambda p, t, pos, a, c, tb: M.decode_step_slots(
                    p, t, pos, a, c, cfg, block_tables=tb))(
                params, jax.ShapeDtypeStruct((B, 1), jnp.int32),
                jax.ShapeDtypeStruct((B,), jnp.int32),
                jax.ShapeDtypeStruct((B,), jnp.bool_), caches,
                jax.ShapeDtypeStruct((B, bps), jnp.int32))
            rows = flops.bucket(20, BUCKET, cfg.sliding_window)
            pre = jax.make_jaxpr(lambda p, t, li: M.prefill(
                p, {"tokens": t}, cfg, last_index=li))(
                params, jax.ShapeDtypeStruct((1, rows), jnp.int32),
                jax.ShapeDtypeStruct((1,), jnp.int32))
        found = {}
        for name, jaxpr, step in (
                ("decode", dec, StepRecord(0, 1, [], decode_rows=2)),
                ("prefill", pre, StepRecord(0, 1, [20], decode_rows=0))):
            got, _ = _calls(jaxpr.jaxpr)
            want = flops.kernel_calls([step], cfg, B, BUCKET, r=4)
            found[name] = {"traced": sorted([list(k), v]
                                            for k, v in got.items()),
                           "counted": sorted([list(k), v]
                                             for k, v in want.items())}
        out[arch] = found
    return out


def run(root, fault=None):
    """A tiny ``quant_tp`` cell over a model=4 mesh through the whole
    harness; ``no_exchange`` leaves out the sum across chips of the
    row-split linears' partial products."""
    import jax

    from servebench import run as R
    from servebench import testing

    if fault == "no_exchange":
        jax.lax.psum = lambda x, axis_name, **kw: x
    layout = testing.smoke_layout(root, testing.TINY_QWEN, loop="open",
                                  chips=4, pim_mode="quant_tp",
                                  mesh={"model": 4})
    _mesh()
    return R.main(["--workload", "tiny-open", "--seed", str(SEED),
                   "--seconds", "1", "--trace", "0"],
                  started=time.monotonic(), layout=layout,
                  require_chip=False)


if __name__ == "__main__":
    sys.path[:0] = [BENCH, HERE, SRC]
    what = sys.argv[1]
    if what == "run":
        sys.exit(run(*sys.argv[2:]))
    print(json.dumps({"weights": weights, "calls": calls}[what]()))
