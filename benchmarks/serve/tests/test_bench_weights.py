"""The weight filler: every leaf of every registry entry gets a value, any
one layer of a stacked leaf is remade alone exactly, and a tree made under
the program's shardings over a four-device mesh holds the same values, each
leaf placed as ``param_pspecs`` places it."""
import os
import sys
import zlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, HERE, os.path.join(
    os.path.dirname(os.path.dirname(BENCH)), "src")]

import four_devices  # noqa: E402
import repro.configs as configs  # noqa: E402
from servebench import weights  # noqa: E402

SEED = 2 ** 32 + 31


@pytest.mark.parametrize("arch", configs.ARCH_NAMES)
def test_every_registry_entry_is_filled(arch):
    cfg = configs.get(arch).smoke()
    params = weights.make_params(cfg, SEED)
    key = weights.seed_key(SEED)
    # as a reference remakes a layer: jitted, the layer an argument; one
    # stacked leaf of each rank
    remake = jax.jit(weights.leaf_value, static_argnums=(1, 2, 3))
    ranks = set()
    for path, leaf in jax.tree_util.tree_leaves_with_path(params):
        name = weights.path_name(path)
        assert bool(jnp.all(jnp.isfinite(leaf))), name
        assert float(jnp.std(leaf.astype(jnp.float32))) > 0, name
        if weights.stacked(name) and leaf.ndim not in ranks:
            ranks.add(leaf.ndim)
            last = leaf.shape[0] - 1
            one = remake(key, name, leaf.shape[1:], leaf.dtype, last)
            assert jnp.array_equal(one, leaf[last]), name


def test_rules_for_vectors_stacks_and_other_norms():
    key = weights.seed_key(SEED)
    z = np.asarray(jax.random.normal(
        jax.random.fold_in(jax.random.fold_in(key, zlib.crc32(
            b"blocks/0/conv_b") & 0x7FFFFFFF), 2), (128,), jnp.float32))
    got = weights.leaf_value(key, "blocks/0/conv_b", (128,), jnp.float32, 2)
    np.testing.assert_array_equal(np.asarray(got), weights.SMALL * z)
    norm = weights.leaf_value(key, "encoder/enc_norm", (4096,), jnp.float32)
    assert abs(float(jnp.mean(norm)) - 1.0) < 0.01
    experts = weights.leaf_value(key, "blocks/1/w1", (8, 256, 64),
                                 jnp.float32, 0)
    # N(0, 1) / sqrt(K) for each of the E matrices
    assert float(jnp.std(experts)) == pytest.approx(256 ** -0.5, rel=0.02)
    assert weights.stacked("encoder/blocks/0/wq")
    assert not weights.stacked("encoder/enc_norm")


@pytest.fixture(scope="module")
def sharded():
    import json

    p = four_devices.launch("weights")
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("arch", four_devices.ARCHS)
def test_sharded_weights_equal_one_device_weights(sharded, arch):
    got = sharded[arch]
    assert got["unequal"] == [] and got["misplaced"] == []
    assert got["devices"] == [4]        # no leaf lives on one chip alone
    assert got["split"] > got["leaves"] // 2
