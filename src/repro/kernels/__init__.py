"""Pallas kernels.

Whether a kernel runs compiled or under the Pallas interpreter is decided
in one place, :func:`interpret_mode`, from the platform: the interpreter
on the CPU backend (tests, laptops), a compiled Mosaic kernel everywhere
else — so an accelerator the kernels do not support fails loudly at
compile time instead of quietly running the interpreter.
"""
from __future__ import annotations

from typing import Optional

import jax

__all__ = ["interpret_mode"]


def interpret_mode(interpret: Optional[bool] = None) -> bool:
    """The ``interpret`` flag for ``pl.pallas_call``: an explicit request
    wins (compile tests force ``False`` for a described chip), else
    interpret exactly when the default backend is the CPU."""
    if interpret is not None:
        return interpret
    return jax.default_backend() == "cpu"
