"""PartitionSpec factories for the dry-run / train / serve entry points.

All factories are **shape-driven**: they walk trees of
``jax.ShapeDtypeStruct`` (or concrete arrays) and assign mesh axes per
leaf, keeping every assignment divisible — a spec produced here always
compiles, on any mesh, at any model size.

Placement rules (see ``repro.dist.__doc__`` for the axis conventions):

* **params** — the largest dim divisible by the ``"model"`` axis is
  tensor-parallel (ties pick the later dim: column-parallel for square
  ``(d, ff)`` weights); with ``fsdp=True`` the largest *remaining* dim
  divisible by ``"data"`` is ZeRO-3 sharded (ties pick the earlier dim).
* **optimizer state** — mirrors the param spec; Adafactor row/col
  statistics inherit the surviving dims of their param's spec.
* **batches** — leading (batch) dim over the data-parallel axes.
* **decode caches** — dim 1 (batch; dim 0 is the stacked-layer axis) over
  the data-parallel axes, and the head dim (-2) of rank>=4 leaves over
  ``"model"``.
"""
from __future__ import annotations

from typing import Any, Dict, Optional

import jax
from jax.sharding import NamedSharding, PartitionSpec

from repro.dist import context as dctx

__all__ = ["param_pspecs", "opt_state_pspecs", "batch_pspecs",
           "cache_pspecs", "tree_shardings", "tp_shard_dim",
           "replica_slices"]

FSDP_AXIS = "data"


def _axis_size(mesh, axis: Optional[str]) -> int:
    return mesh.shape[axis] if axis and axis in mesh.axis_names else 1


def _is_shape_leaf(x) -> bool:
    return hasattr(x, "shape") and hasattr(x, "dtype")


def _pick_dim(shape, divisor: int, taken, *, prefer_late: bool) -> int:
    """Index of the largest dim divisible by ``divisor`` (excluding
    ``taken``), or -1.  Ties resolve to the later/earlier dim."""
    best, best_size = -1, 0
    dims = range(len(shape))
    for i in (reversed(dims) if prefer_late else dims):
        if i in taken or shape[i] % divisor or shape[i] < divisor:
            continue
        if shape[i] > best_size:
            best, best_size = i, shape[i]
    return best


def tp_shard_dim(shape, tp_size: int) -> int:
    """The dim index ``param_pspecs`` puts on the ``"model"`` axis, or -1.

    Largest dim divisible by ``tp_size``; ties resolve to the *later* dim
    (column-parallel for square weights).  The ``quant_tp`` execution mode
    (``repro.kernels.quant_matmul.tp``) keys its shard_map split off the
    same rule, so a weight's tile split always matches the layout
    ``param_pspecs`` gave it — no resharding at dispatch.
    """
    return _pick_dim(shape, tp_size, set(), prefer_late=True)


def _param_spec(shape, mesh, tp_ax: Optional[str], fsdp_ax: Optional[str]
                ) -> PartitionSpec:
    entries = [None] * len(shape)
    taken = set()
    tp_size = _axis_size(mesh, tp_ax)
    if tp_size > 1:
        i = tp_shard_dim(shape, tp_size)
        if i >= 0:
            entries[i] = tp_ax
            taken.add(i)
    fsdp_size = _axis_size(mesh, fsdp_ax)
    if fsdp_size > 1:
        i = _pick_dim(shape, fsdp_size, taken, prefer_late=False)
        if i >= 0:
            entries[i] = fsdp_ax
            taken.add(i)
    return PartitionSpec(*entries)


def param_pspecs(pshapes, mesh, *, fsdp: bool = False, tp: bool = True):
    """PartitionSpec tree for a param tree. ``tp=False`` keeps weights off
    the "model" axis (dp-only policy); ``fsdp=True`` additionally shards
    over "data" (ZeRO-3)."""
    _, tp_ax = dctx.mesh_axes(mesh)
    tp_ax = tp_ax if tp else None
    fsdp_ax = FSDP_AXIS if fsdp else None
    return jax.tree.map(
        lambda s: _param_spec(s.shape, mesh, tp_ax, fsdp_ax),
        pshapes, is_leaf=_is_shape_leaf)


def opt_state_pspecs(pshapes, param_part, opt_state, mesh):
    """Specs for ``optim.adamw`` state: moments mirror their param's spec;
    factored row/col stats keep the spec entries of their surviving dims;
    the step counter replicates."""
    flat_shapes, tdef = jax.tree.flatten(pshapes, is_leaf=_is_shape_leaf)
    flat_specs = tdef.flatten_up_to(param_part)
    flat_state = tdef.flatten_up_to(opt_state["leaves"])

    def leaf(spec: PartitionSpec, st: Dict[str, Any]) -> Dict[str, Any]:
        e = tuple(spec)
        out: Dict[str, Any] = {"m": spec}
        if "v" in st:
            out["v"] = spec
        else:  # Adafactor: vr = shape[:-1], vc = shape[:-2] + shape[-1:]
            out["vr"] = PartitionSpec(*e[:-1])
            out["vc"] = PartitionSpec(*(e[:-2] + e[-1:]))
        return out

    leaves = [leaf(sp, st) for sp, st in zip(flat_specs, flat_state)]
    return {"step": PartitionSpec(),
            "leaves": jax.tree.unflatten(tdef, leaves)}


def batch_pspecs(batch, mesh):
    """Input batches: leading dim over the data-parallel axes (dropped when
    the global batch does not divide), everything else replicated."""
    dp, _ = dctx.mesh_axes(mesh)
    return jax.tree.map(
        lambda s: dctx.pspec_for(mesh, s.shape, dp),
        batch, is_leaf=_is_shape_leaf)


def cache_pspecs(caches, mesh, *, batch_over_dp: bool = True,
                 row_heads: Optional[Dict[str, int]] = None):
    """Decode caches ``(n_super, batch, ...)``: batch dim over DP axes, the
    head dim (-2) of rank>=4 leaves over the "model" axis.

    ``batch_over_dp=False`` keeps the batch (slot) dim replicated while
    heads still ride "model" — the serving cache pool's placement:
    continuous batching scatters arbitrary slots on admit/evict, and a
    DP-sharded slot dim would turn every single-slot update into
    cross-device traffic.

    Block-paged pools (``serving.PagedCachePool``) reuse the same factory:
    their attention leaves are ``(n_super, num_blocks, block, ...)``, so
    dim 1 is the *block* dim — it must stay replicated for the same reason
    slots do (any slot touches any block), hence paged pools always pass
    ``batch_over_dp=False``.  Their ``k``/``v`` hold each token as one
    ``heads * hd`` row with the heads major (``model.fold_kv_rows``);
    ``row_heads`` names such leaves and their head count.  "model" splits
    a row only where it divides the heads, so each shard holds whole
    heads and the decode attention stays local to it
    (``attention.decode_attention_rows``); an MQA/GQA pool with fewer
    heads than ranks stays replicated, as a ``(heads, hd)`` leaf whose
    heads do not divide does.  The block *table* itself is a tiny
    replicated int32 array and never gets a spec here.

    Quantized-KV *scale* leaves (``k_scale``/``v_scale``) are the KV leaf
    minus its trailing head-dim axis — ``(n_super, batch, cap, heads)`` —
    so their head dim is *last*, not ``-2``: they get ``tp`` on ``-1`` to
    stay aligned with the ``(…, heads, hd)`` values they rescale (putting
    ``tp`` on ``-2`` would shard the *sequence* dim of the scales against
    the head-sharded values and force a gather per decode step).
    """
    dp, tp_ax = dctx.mesh_axes(mesh)

    def leaf(path, s):
        nd = len(s.shape)
        entries = [None] * nd
        if nd >= 2 and batch_over_dp:
            entries[1] = dp
        name = str(getattr(path[-1], "key", path[-1])) if path else ""
        if name in (row_heads or {}):
            entries[-1] = dctx.head_axis(row_heads[name], mesh)
        elif nd >= 4 and tp_ax:
            entries[-1 if name.endswith("_scale") else -2] = tp_ax
        return dctx.pspec_for(mesh, s.shape, *entries)

    return jax.tree_util.tree_map_with_path(leaf, caches,
                                            is_leaf=_is_shape_leaf)


def replica_slices(n_replicas: int, devices=None):
    """Disjoint contiguous device slices for a data-parallel replica fleet.

    The serving router gives each replica its own slice (its own mesh, KV
    pool, prefix trie); contiguity keeps each replica's model-parallel
    collectives on neighbouring devices, matching how
    ``ElasticMesh.make`` reshapes a device list.  ``n_replicas`` must
    divide the device count — a ragged fleet would hand replicas unequal
    capacity and poison the scaling benchmark.
    """
    devices = list(devices if devices is not None else jax.devices())
    n = len(devices)
    if n_replicas < 1 or n % n_replicas:
        raise ValueError(
            f"{n_replicas} replicas cannot evenly split {n} devices")
    per = n // n_replicas
    return [devices[i * per:(i + 1) * per] for i in range(n_replicas)]


def tree_shardings(spec_tree, mesh):
    """PartitionSpec tree -> NamedSharding tree on ``mesh``."""
    return jax.tree.map(
        lambda p: NamedSharding(mesh, p), spec_tree,
        is_leaf=lambda x: isinstance(x, PartitionSpec))
