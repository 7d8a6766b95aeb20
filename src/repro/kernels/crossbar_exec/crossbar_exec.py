"""Pallas TPU kernel: crossbar microcode executor.

TPU adaptation of stateful-logic simulation (DESIGN.md §2): crossbar state is
``(C, n, W)`` uint32 (n bitlines x W row-words); the kernel tiles
``(crossbar, row-word)`` blocks into VMEM and streams the *entire* microcode
program over the resident tile.  Arithmetic intensity therefore scales with
program length G: HBM traffic is one read + one write of the state per
program, instead of per gate — the same insight that makes partitions pay on
the memristive side (amortize the expensive resource over many gates).

Block geometry: (1, n, Wt).  The row-word axis (last, 128-lane) is the
vector axis; bitlines live on the sublane axis, so a gate's column gather /
scatter is a sublane-dynamic, lane-contiguous VMEM access.  For n=1024,
Wt=128: 512 KiB per tile, MXU unused (pure VPU kernel).  The (G, 4)
microcode block sits in VMEM too, lane-padded to G*512 B (~9.5 MB for an
8-term MultPIM dot, G=18656): SMEM would hold it unpadded but caps at
1 MiB, which a single 32-bit multiplier program (G=11074) already
exceeds.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import interpret_mode

__all__ = ["crossbar_exec"]


def _kernel(mc_ref, state_ref, out_ref):
    out_ref[...] = state_ref[...]
    n_ops = mc_ref.shape[0]

    def body(g, carry):
        op = mc_ref[pl.ds(g, 1), :]
        code, ia, ib, dst = op[0, 0], op[0, 1], op[0, 2], op[0, 3]
        a = out_ref[:, pl.ds(ia, 1), :]
        b = out_ref[:, pl.ds(ib, 1), :]
        nor = ~(a | b)
        res = jnp.where(
            code == 0, ~jnp.zeros_like(a),
            jnp.where(code == 1, ~a,
                      jnp.where(code == 2, nor,
                                jnp.where(code == 3, a | b,
                                          jnp.where(code == 4, ~(a & b),
                                                    a & b)))))
        out_ref[:, pl.ds(dst, 1), :] = res
        return carry

    jax.lax.fori_loop(0, n_ops, body, 0)


@functools.partial(jax.jit, static_argnames=("w_tile", "interpret"))
def crossbar_exec(state: jnp.ndarray, microcode: jnp.ndarray,
                  w_tile: int = 128,
                  interpret: Optional[bool] = None) -> jnp.ndarray:
    """Run microcode (G, 4) over state (C, n, W); tiles (1, n, w_tile).

    ``interpret=None`` follows the platform (:func:`interpret_mode`)."""
    c, n, w = state.shape
    pad = (-w) % w_tile
    if pad:
        state = jnp.pad(state, ((0, 0), (0, 0), (0, pad)))
    wp = state.shape[2]
    grid = (c, wp // w_tile)
    out = pl.pallas_call(
        _kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec(microcode.shape, lambda i, j: (0, 0)),
            pl.BlockSpec((1, n, w_tile), lambda i, j: (i, 0, j)),
        ],
        out_specs=pl.BlockSpec((1, n, w_tile), lambda i, j: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((c, n, wp), jnp.uint32),
        interpret=interpret_mode(interpret),
    )(jnp.asarray(microcode, jnp.int32), state)
    return out[:, :, :w] if pad else out
