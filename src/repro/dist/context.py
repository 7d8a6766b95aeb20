"""Active-mesh context and sharding-constraint helpers.

The model stack never receives a mesh argument; it asks this module.  A
``use_mesh`` block pushes ``(mesh, dp_axes, tp_axis)`` onto a thread-local
stack; everything sharding-related (``shard``, ``shard_batch_dim``, the
``partitioning`` factories) resolves against the top of that stack and
degrades to a no-op when it is empty.  See ``repro.dist.__doc__`` for the
axis conventions.
"""
from __future__ import annotations

import contextlib
import threading
from typing import Optional, Sequence, Tuple, Union

import jax
from jax.sharding import NamedSharding, PartitionSpec

__all__ = ["use_mesh", "current_mesh", "mesh_axes", "dp_axes", "tp_axis",
           "head_axis", "shard", "pin", "shard_batch_dim"]

TP_AXIS = "model"

# One spec entry: None (replicated), an axis name, or a tuple of axis names.
AxisEntry = Union[None, str, Sequence[str]]


class _Stack(threading.local):
    def __init__(self):
        self.frames = []  # [(mesh, dp_axes: tuple, tp_axis: str | None)]


_stack = _Stack()


def _resolve_axes(mesh, dp_override: Optional[Sequence[str]] = None
                  ) -> Tuple[Tuple[str, ...], Optional[str]]:
    """Split mesh axes into (data-parallel tuple, tensor-parallel axis)."""
    names = tuple(mesh.axis_names)
    if dp_override is None:
        dp = tuple(a for a in names if a != TP_AXIS)
        tp = TP_AXIS if TP_AXIS in names else None
    else:
        unknown = set(dp_override) - set(names)
        if unknown:
            raise ValueError(f"dp_axes {sorted(unknown)} not in mesh axes "
                             f"{names}")
        dp = tuple(a for a in names if a in dp_override)
        tp = TP_AXIS if TP_AXIS in names and TP_AXIS not in dp else None
    return dp, tp


@contextlib.contextmanager
def use_mesh(mesh, *, dp_axes: Optional[Sequence[str]] = None):
    """Make ``mesh`` the active mesh for the enclosed block (re-entrant).

    ``dp_axes`` overrides which axes count as data-parallel; by default all
    axes except ``"model"``.  Passing every axis (the dry-run's ``dp_only``
    policy) leaves ``tp_axis() is None`` and fully replicates weights.

    The active mesh is read at **trace** time and is not part of jax's jit
    cache key: a function jitted and first called under one context will be
    replayed with that context's shardings on later calls.  Create the jit
    wrapper inside the ``use_mesh`` block (as ``launch/dryrun.py`` does),
    one per (mesh, dp_axes) policy.
    """
    frame = (mesh,) + _resolve_axes(mesh, dp_axes)
    _stack.frames.append(frame)
    try:
        with mesh:
            yield mesh
    finally:
        _stack.frames.pop()


def current_mesh():
    """The innermost ``use_mesh`` mesh, or None outside any."""
    return _stack.frames[-1][0] if _stack.frames else None


def mesh_axes(mesh=None) -> Tuple[Tuple[str, ...], Optional[str]]:
    """(dp_axes, tp_axis) for ``mesh`` (default: the active mesh).

    For the active mesh this honours the ``use_mesh(dp_axes=...)`` override;
    for any other mesh it applies the default split.
    """
    if _stack.frames and (mesh is None or mesh is _stack.frames[-1][0]):
        return _stack.frames[-1][1], _stack.frames[-1][2]
    if mesh is None:
        return (), None
    return _resolve_axes(mesh)


def dp_axes() -> Tuple[str, ...]:
    """Data-parallel axis names of the active mesh (``()`` outside one)."""
    return _stack.frames[-1][1] if _stack.frames else ()


def tp_axis() -> Optional[str]:
    """Tensor-parallel axis name of the active mesh, or None."""
    return _stack.frames[-1][2] if _stack.frames else None


def head_axis(heads: int, mesh=None) -> Optional[str]:
    """The tensor-parallel axis of ``mesh`` (default: the active one) where
    it splits ``heads`` into whole heads per shard, else None.

    A paged KV pool stores each token as one ``heads * hd`` row; "model"
    may split the row only between heads, so the decode attention stays
    local to a shard.  Fewer heads than ranks (MQA) leaves it replicated.
    """
    _, tp = mesh_axes(mesh)
    if tp is None:
        return None
    size = (mesh if mesh is not None else current_mesh()).shape[tp]
    return tp if size > 1 and heads % size == 0 else None


def _normalize_entry(mesh, dim_size: int, entry: AxisEntry):
    """Drop axes that are absent or do not divide ``dim_size``."""
    if entry is None:
        return None
    axes = (entry,) if isinstance(entry, str) else tuple(entry)
    axes = tuple(a for a in axes if a in mesh.axis_names
                 and mesh.shape[a] > 1)
    size = 1
    for a in axes:
        size *= mesh.shape[a]
    if not axes or dim_size % size:
        return None
    return axes[0] if len(axes) == 1 else axes


def pspec_for(mesh, shape: Sequence[int], *entries: AxisEntry
              ) -> PartitionSpec:
    """A PartitionSpec for ``shape`` keeping only valid, dividing entries."""
    if len(entries) > len(shape):
        raise ValueError(f"{len(entries)} spec entries for rank-{len(shape)} "
                         f"array")
    return PartitionSpec(*(
        _normalize_entry(mesh, d, e)
        for d, e in zip(shape, tuple(entries) + (None,) * (len(shape)
                                                           - len(entries)))))


def shard(x, *entries: AxisEntry):
    """Constrain ``x``'s sharding (one entry per leading dim; missing
    trailing entries replicate).  No-op outside ``use_mesh``; axes that do
    not divide the dimension are silently dropped, so callers never need
    divisibility checks."""
    mesh = current_mesh()
    if mesh is None:
        return x
    spec = pspec_for(mesh, x.shape, *entries)
    if all(e is None for e in spec):
        return x
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def pin(x, *entries: AxisEntry):
    """``shard`` that also imposes a placement replicated on every dim.

    Sharding propagation may otherwise give a loop carry or an output
    another placement than the argument it came from, and a donated
    buffer can then not be updated in place."""
    mesh = current_mesh()
    if mesh is None:
        return x
    return jax.lax.with_sharding_constraint(
        x, NamedSharding(mesh, pspec_for(mesh, x.shape, *entries)))


def shard_batch_dim(x, dim: int = 0):
    """Shard dimension ``dim`` over the data-parallel axes, rest replicated."""
    entries: list = [None] * (dim + 1)
    entries[dim] = dp_axes()
    return shard(x, *entries)
