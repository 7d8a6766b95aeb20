"""Pallas TPU kernel: int8 x int8 -> int32 blocked matmul.

The TPU-side analogue of PartitionPIM's fixed-point arithmetic: ``PIMLinear``
quantizes weights/activations to N-bit integers exactly as the crossbar
stores them, and this kernel is the MXU path for that representation
(``mode="quant"``), with per-row/per-column scales applied by the wrapper.

Block geometry: (bm, bk) x (bk, bn) -> (bm, bn), all MXU-aligned multiples
of 128 (int8 native on v5e).  K is the innermost grid axis; the int32
accumulator lives in the output block, zeroed at k==0 — the canonical
revisiting-output pattern, VMEM footprint bm*bk + bk*bn (int8) + bm*bn
(int32) = 160 KiB at the default 128/512/128 blocking.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

__all__ = ["quant_matmul_int"]


def _kernel(x_ref, w_ref, o_ref):
    @pl.when(pl.program_id(2) == 0)
    def _():
        o_ref[...] = jnp.zeros_like(o_ref)

    o_ref[...] += jax.lax.dot_general(
        x_ref[...], w_ref[...],
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    )


@functools.partial(jax.jit,
                   static_argnames=("bm", "bn", "bk", "interpret"))
def quant_matmul_int(x: jnp.ndarray, w: jnp.ndarray, bm: int = 128,
                     bn: int = 128, bk: int = 512,
                     interpret: Optional[bool] = None) -> jnp.ndarray:
    """(M, K) int8 @ (K, N) int8 -> (M, N) int32, zero-padded to blocks.

    ``interpret=None`` follows the platform (:func:`interpret_mode`)."""
    m, k = x.shape
    k2, n = w.shape
    assert k == k2
    pm, pn, pk = (-m) % bm, (-n) % bn, (-k) % bk
    if pm or pk:
        x = jnp.pad(x, ((0, pm), (0, pk)))
    if pk or pn:
        w = jnp.pad(w, ((0, pk), (0, pn)))
    mp, kp = x.shape
    _, np_ = w.shape
    # the kernel's own name, which the compiled custom call carries
    # (``%quant_matmul_int_kernel.1``) whatever jit encloses it; a profile
    # finds the kernel by its ``quant_matmul_int`` prefix.  Not the jit's
    # own name: a jaxpr would then hold two equations of that name.
    out = pl.pallas_call(
        _kernel,
        grid=(mp // bm, np_ // bn, kp // bk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((mp, np_), jnp.int32),
        interpret=interpret_mode(interpret),
        name="quant_matmul_int_kernel",
    )(x, w)
    return out[:m, :n]
