"""Random weights from ``--seed``, made by the benchmark and not the program.

The program only gives the layout (``param_specs``: leaf names, shapes and
served dtypes) and, on a mesh, its placement.  Each leaf is filled from a
key derived from the seed and the leaf's path, and each layer of a stacked
leaf (any leaf under a ``blocks`` group: ``blocks/<i>/...``,
``encoder/blocks/<i>/...``; dim 0 counts the layers) from that key folded
with the layer index, so :mod:`reference` can remake any one layer alone
with the same values, without touching what the program holds.

Fill by leaf name, the first rule that matches (shapes are one layer's):

* ``norm*`` / ``final_norm``: ``1 + 0.1 N(0, 1)``;
* ``bq`` / ``bk`` / ``bv`` (QKV bias): ``0.5 N(0, 1)``;
* ``embed`` / ``lm_head`` ``(V, d)``: ``N(0, 1) / sqrt(d)`` over the
  vocabulary's ``V`` rows, and zeros in any rows the program pads on;
* every ``(K, N)`` matrix: ``N(0, 1) / sqrt(K)``;
* any other ``*_norm`` (``enc_norm``): as a norm, ``1 + 0.1 N(0, 1)``;
* a ``(E, K, N)`` stack (experts, or heads with a matrix each):
  ``N(0, 1) / sqrt(K)`` for each of its ``E`` matrices;
* any other vector (biases, conv biases, skips): ``SMALL N(0, 1)``,
  ``SMALL = 0.1``.

On a mesh the tree is made under the shardings the program gives its
parameters (``param_pspecs``, as ``launch.serve.place_params`` lays them),
so no leaf is ever whole on one chip; the values are the same.
"""
from __future__ import annotations

import math
import zlib

import jax
import jax.numpy as jnp

STACKED = "blocks"
TABLES = ("embed", "lm_head")
SMALL = 0.1


def seed_key(seed: int):
    """A PRNG key from a seed of up to 64 bits."""
    k = jax.random.PRNGKey(0)
    k = jax.random.fold_in(k, seed & 0xFFFFFFFF)
    return jax.random.fold_in(k, (seed >> 32) & 0xFFFFFFFF)


def path_name(path) -> str:
    return "/".join(str(getattr(p, "key", p)) for p in path)


def leaf_value(key, path: str, shape, dtype, layer=None):
    """One leaf (or, with ``layer``, one layer's slice of a stacked leaf)
    in its served dtype."""
    k = jax.random.fold_in(key, zlib.crc32(path.encode()) & 0x7FFFFFFF)
    if layer is not None:
        k = jax.random.fold_in(k, layer)
    z = jax.random.normal(k, shape, jnp.float32)
    name = path.rsplit("/", 1)[-1]
    if name.startswith("norm") or name == "final_norm":
        v = 1.0 + 0.1 * z
    elif name in ("bq", "bk", "bv"):
        v = 0.5 * z
    elif name in ("embed", "lm_head"):
        v = z / math.sqrt(shape[-1])
    elif len(shape) == 2:
        v = z / math.sqrt(shape[0])
    elif name.endswith("_norm"):
        v = 1.0 + 0.1 * z
    elif len(shape) == 3:
        v = z / math.sqrt(shape[1])
    elif len(shape) == 1:
        v = SMALL * z
    else:
        raise ValueError(f"no fill rule for leaf {path} {shape}")
    return v.astype(dtype)


def stacked(name: str) -> bool:
    """Whether leaf ``name`` stacks its layers on dim 0."""
    return STACKED in name.split("/")[:-1]


def make_params(cfg, seed: int, mesh=None):
    """The whole parameter tree in one jitted call (compiled once for every
    seed: the key is an argument): on the default device, or over ``mesh``
    in the program's own parameter shardings."""
    from repro.models import model_lib as M
    from repro.models.layers import as_shapes

    shapes = as_shapes(M.param_specs(cfg))
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        out = []
        for path, s in flat:
            name = path_name(path)
            if name in TABLES:
                # the vocabulary's rows; the program's padding rows are 0
                rows = leaf_value(key, name, (cfg.vocab_size,) + s.shape[1:],
                                  s.dtype)
                out.append(jnp.pad(rows, ((0, s.shape[0] - cfg.vocab_size),
                                          (0, 0))))
            elif stacked(name):
                layers = jnp.arange(s.shape[0])
                out.append(jax.vmap(lambda i, n=name, s=s: leaf_value(
                    key, n, s.shape[1:], s.dtype, i))(layers))
            else:
                out.append(leaf_value(key, name, s.shape, s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    if mesh is None:
        return jax.jit(build)(seed_key(seed))
    from repro.dist.partitioning import param_pspecs, tree_shardings

    # out_shardings=None would mean replicated: pass it only on a mesh
    return jax.jit(build, out_shardings=tree_shardings(
        param_pspecs(shapes, mesh), mesh))(seed_key(seed))
