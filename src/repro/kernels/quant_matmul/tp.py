"""Tensor-parallel int8 quantized linear: shard_map tiles over "model".

The mesh-level analogue of PartitionPIM's crossbar partitioning: one
logical GEMM is split into per-rank int8 tiles, each rank driving its own
Pallas ``quant_matmul_int`` over only its weight shard — partitions
multiply parallelism, exactly the paper's move, with the JAX mesh's
"model" axis as the partition dimension.

Split selection mirrors ``dist.partitioning.param_pspecs`` (via
:func:`dist.partitioning.tp_shard_dim`), so the tile split always matches
the layout the weight already lives in:

* **column-parallel** (output dim sharded) — each rank quantizes its own
  ``(K, N/R)`` shard per output column and emits its slice of the result;
  no collective.  Per-column weight scales make this *bit-identical* to
  the single-rank "quant" path: sharding columns cannot change any
  column's scale.  Non-divisible output dims zero-pad to ``R`` columns
  (padding can't perturb any real column's quantization) and slice back.
* **row-parallel** (inner dim sharded) — activation rows and weight
  columns are quantized against *global* amax (per-shard max + an exact
  ``pmax`` over "model", bit-identical to the single-rank reduction), each
  rank computes an int32 partial GEMM over its ``K/R`` slice, and a
  ``psum`` combines them.  Integer accumulation is associative, so the
  cross-rank reduce is bit-deterministic — the whole row-parallel path is
  bit-identical to single-rank "quant" too.  (Row-parallel is only ever
  *chosen* when K divides R — a non-divisible weight always routes to the
  column split, whose N-pad is always possible.)

On meshes that also carry data-parallel axes, the token (row) dim of the
activations shards over them whenever it divides — each data rank's tile
runs only its slice of the batch — and falls back to replication when it
doesn't (the tiny-decode case), mirroring ``moe_ffn``'s policy.

Each rank's tile clamps the Pallas block geometry to its (padded) shard —
the per-rank kernel iterates a grid sized for ``1/R`` of the weight, not
for the full matrix — while keeping the MXU-default caps, so shrinking
shards actually shrink per-rank work.

Differentiation is a straight-through ``custom_vjp`` (forward: the
sharded int8 tiles; backward: the ideal float matmul), the same QAT
convention as ``engine.sim_linear`` — so ``pim_mode="quant_tp"`` trains,
and the backward einsums are plain GSPMD ops that reduce-scatter /
all-reduce as the sharding dictates.

Outside any mesh (or at model=1) :func:`tp_quant_linear` degrades to the
single-rank ``quant_linear`` exactly, so "quant_tp" is always safe to pin
in a config.
"""
from __future__ import annotations

import functools
from typing import List, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist import context as dctx
from repro.dist.partitioning import tp_shard_dim
from repro.kernels.quant_matmul.ops import quant_linear, quantize_sym
from repro.kernels.quant_matmul.quant_matmul import quant_matmul_int

__all__ = ["tp_quant_linear", "tp_split", "tp_tile_shape", "tile_summary"]


def _block(dim: int, cap: int) -> int:
    """Per-rank Pallas block edge: the shard dim padded to the int8 lane
    multiple (8), capped at the MXU-default block edge."""
    return min(cap, -(-dim // 8) * 8)


def tp_split(w_shape: Tuple[int, int], r: int) -> str:
    """``"col"`` | ``"row"``: which dim of ``(K, N)`` the tile shards.

    Follows ``partitioning.tp_shard_dim`` (largest divisible dim, ties to
    the later = column-parallel) so the split matches where
    ``param_pspecs`` put the weight.  When neither dim divides ``r`` the
    tile goes column-parallel and zero-pads N — always possible."""
    return "row" if tp_shard_dim(w_shape, r) == 0 else "col"


def tp_tile_shape(w_shape: Tuple[int, int], r: int) -> Tuple[int, int]:
    """The per-rank weight tile ``(K_loc, N_loc)`` (after pad) for ``r``
    ranks — what each rank's Pallas kernel actually sees."""
    k, n = w_shape
    if tp_split(w_shape, r) == "row":
        return (-(-k // r), n)
    return (k, -(-(n + (-n) % r) // r))


def tile_summary(cfg, r: int) -> List[str]:
    """Human-readable per-rank tile lines for a config's core projections.

    One source of truth for the shapes the tiles actually shard — the
    serving CLI's ``[tp]`` echo and the benchmark's tile rows both render
    from here, so they can never drift from :func:`tp_split` /
    :func:`tp_tile_shape`."""
    d, ff, h = cfg.d_model, cfg.d_ff, cfg.n_heads * cfg.hd
    return [
        f"{nm} {shp}->{tp_split(shp, r)} {tp_tile_shape(shp, r)}"
        for nm, shp in (("wq", (d, h)), ("w_in", (d, ff)),
                        ("w_out", (ff, d)))
    ]


def _dp_split(mesh, m: int):
    """(dp spec entry for the token dim, local token count): the data axes
    when they divide ``m`` (each data rank tiles only its batch slice),
    else replicate — the same fallback ``moe_ffn`` uses for tiny decodes."""
    dp = tuple(a for a in dctx.dp_axes() if a in mesh.axis_names
               and mesh.shape[a] > 1)
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    if not dp or m % dp_size:
        return None, m
    return (dp if len(dp) > 1 else dp[0]), m // dp_size


def _tp_forward(split: str, bits: int, x2, w):
    mesh = dctx.current_mesh()
    ax = dctx.tp_axis()
    r = mesh.shape[ax]
    m, k = x2.shape
    n = w.shape[1]
    dp, m_loc = _dp_split(mesh, m)

    if split == "col":
        pn = (-n) % r
        wp = jnp.pad(w, ((0, 0), (0, pn))) if pn else w
        bm, bk, bn = (_block(m_loc, 128), _block(k, 512),
                      _block((n + pn) // r, 128))

        def tile(xl, wl):
            # per-shard scales: quantize_sym's weight scales are per output
            # column, so each rank's local scales ARE the global ones (and
            # activation rows quantize independently, so a dp token split
            # changes nothing either)
            xq, xs = quantize_sym(xl, axis=1, bits=bits)
            wq, ws = quantize_sym(wl, axis=0, bits=bits)
            acc = quant_matmul_int(xq, wq, bm=bm, bn=bn, bk=bk)
            return acc.astype(jnp.float32) * xs[:, None] * ws[None, :]

        y = jax.shard_map(tile, mesh=mesh,
                          in_specs=(P(dp, None), P(None, ax)),
                          out_specs=P(dp, ax), check_vma=False)(x2, wp)
        return y[:, :n] if pn else y

    # row-parallel: only chosen when K % r == 0 (see tp_split), so the
    # inner dim never needs padding here
    bm, bk, bn = (_block(m_loc, 128), _block(k // r, 512), _block(n, 128))

    def tile(xl, wl):
        # global ranges from per-shard amax: max is exact, so pmax yields
        # bit-identical scales to the single-rank full-axis reduction
        # (activation rows are local to their dp rank; only K is pmax'd)
        xa = jax.lax.pmax(jnp.max(jnp.abs(xl), axis=1, keepdims=True), ax)
        wa = jax.lax.pmax(jnp.max(jnp.abs(wl), axis=0, keepdims=True), ax)
        xq, xs = quantize_sym(xl, axis=1, bits=bits, amax=xa)
        wq, ws = quantize_sym(wl, axis=0, bits=bits, amax=wa)
        # int32 partial tiles; integer psum is associative => exact
        acc = jax.lax.psum(quant_matmul_int(xq, wq, bm=bm, bn=bn, bk=bk), ax)
        return acc.astype(jnp.float32) * xs[:, None] * ws[None, :]

    return jax.shard_map(tile, mesh=mesh, in_specs=(P(dp, ax), P(ax, None)),
                         out_specs=P(dp, None), check_vma=False)(x2, w)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 1))
def _tp_mm(split: str, bits: int, x2, w):
    return _tp_forward(split, bits, x2, w)


def _tp_mm_fwd(split, bits, x2, w):
    return _tp_forward(split, bits, x2, w), (x2, w)


def _tp_mm_bwd(split, bits, res, g):
    # straight-through estimator: forward is the sharded int8 tiles,
    # backward differentiates the ideal float matmul (QAT convention,
    # matching engine.sim_linear); GSPMD shards the einsums
    x2, w = res
    gx = jnp.einsum("mn,kn->mk", g, w.astype(g.dtype)).astype(x2.dtype)
    gw = jnp.einsum("mk,mn->kn", x2.astype(g.dtype), g).astype(w.dtype)
    return gx, gw


_tp_mm.defvjp(_tp_mm_fwd, _tp_mm_bwd)


def tp_quant_linear(x, w, bits: int = 8):
    """``x @ w`` via per-rank int8 Pallas tiles over the "model" axis.

    ``x``: (..., K) float; ``w``: (K, N).  Reads the active mesh at trace
    time (like every ``dist`` helper); outside a mesh — or when the mesh
    has no tensor axis, or it has size 1 — this is exactly the single-rank
    ``quant_linear``, and *with* a mesh the result is bit-identical to it
    (see module docstring for why both splits preserve the quantization).
    """
    mesh = dctx.current_mesh()
    ax = dctx.tp_axis()
    if mesh is None or ax is None or mesh.shape[ax] <= 1:
        return quant_linear(x, w.astype(jnp.float32), bits=bits)
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    split = tp_split(w.shape, mesh.shape[ax])
    y = _tp_mm(split, bits, x2, w.astype(jnp.float32))
    return y.reshape(*lead, w.shape[-1]).astype(x.dtype)
