"""Public entry point for the crossbar executor kernel."""
from __future__ import annotations

import jax.numpy as jnp

from repro.kernels.crossbar_exec.crossbar_exec import crossbar_exec
from repro.kernels.crossbar_exec.ref import crossbar_exec_ref

__all__ = ["run_program", "crossbar_exec", "crossbar_exec_ref"]


def run_program(state: jnp.ndarray, microcode, backend: str = "jnp",
                w_tile: int = 128) -> jnp.ndarray:
    """Execute a Program's microcode on crossbar state.

    Thin shim over the ``repro.pim.engine`` backend registry — ``"jnp"``
    (alias ``"scan"``, the lax.scan oracle), ``"unrolled"`` (static-index
    variant), or ``"pallas"`` (the VMEM-tiled Pallas kernel: compiled on a
    TPU, interpreted on the CPU backend — ``kernels.interpret_mode``);
    ``engine.register_backend`` extends the set without touching call
    sites.
    """
    from repro.pim import engine

    return engine.execute_state(state, microcode, backend=backend,
                                w_tile=w_tile)
