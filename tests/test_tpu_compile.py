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
import os
import re

import pytest

import jax
import jax.numpy as jnp
from jax.sharding import SingleDeviceSharding

from repro.kernels.crossbar_exec.crossbar_exec import crossbar_exec
from repro.kernels.quant_matmul.quant_matmul import quant_matmul_int
from repro.kernels.quant_matmul.tp import _block


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


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
