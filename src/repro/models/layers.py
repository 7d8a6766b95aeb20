"""Primitive layers: norms, rotary embeddings, activations, linears, embed.

Parameters are plain nested dicts of jnp arrays.  Every layer has a
``*_specs`` companion producing ShapeDtypeStructs so the full-size configs
can be lowered without allocating (the dry-run path), and ``init_*``
initializers used by the smoke tests / real training.

:func:`linear` is the paper integration point.  How it lowers is selected
through ``repro.pim.engine`` — there is no process-wide global:

* ``"xla"``      — plain einsum (default);
* ``"quant"``    — the int8 Pallas kernel (fixed-point arithmetic, the TPU
  analogue of the crossbar's integer representation);
* ``"quant_tp"`` — the same int8 arithmetic as per-rank Pallas tiles
  ``shard_map``-ped over the mesh "model" axis (the paper's partition
  parallelism at mesh level; ``engine.get_backend("quant_tp")``) — falls
  back to (and is bit-identical with) ``"quant"`` when no tensor axis is
  active;
* ``"pim_sim"``  — the actual MultPIM gate programs on the bit-accurate
  crossbar simulator, via ``engine.sim_linear``'s ``jax.pure_callback``
  route, so it traces under ``jax.jit`` (tiny shapes; examples/tests).

Selection is either ambient — ``with pim.engine.mode("quant"): ...`` wrapped
around the *trace* — or threaded explicitly: ``linear(x, w, mode=...)``,
normally fed from ``ModelConfig.pim_mode`` by the model stack.  An explicit
mode wins over the ambient context.
"""
from __future__ import annotations

import math
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp

Params = Dict[str, Any]


# --------------------------------------------------------------------------
# spec / init plumbing
# --------------------------------------------------------------------------

class Spec(jax.ShapeDtypeStruct):
    """ShapeDtypeStruct + init kind ('normal', 'zeros', 'ones', 'scaled')."""

    def __init__(self, shape, dtype, init: str = "normal", scale: float = 1.0):
        super().__init__(shape, dtype)
        self.init = init
        self.scale = scale


def materialize(specs, key) -> Params:
    """Instantiate a spec tree into real parameters."""
    leaves, treedef = jax.tree.flatten(
        specs, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    keys = jax.random.split(key, len(leaves))
    out = []
    for k, s in zip(keys, leaves):
        init = getattr(s, "init", "normal")
        scale = getattr(s, "scale", 1.0)
        if init == "zeros":
            out.append(jnp.zeros(s.shape, s.dtype))
        elif init == "ones":
            out.append(jnp.ones(s.shape, s.dtype))
        elif init == "alog":
            # S4/Mamba A initialization: A = -(1..d_state) per channel
            ds = s.shape[-1]
            a = jnp.broadcast_to(jnp.arange(1, ds + 1, dtype=jnp.float32),
                                 s.shape)
            out.append(jnp.log(a).astype(s.dtype))
        else:
            fan_in = s.shape[-2] if len(s.shape) >= 2 else s.shape[-1]
            std = scale / math.sqrt(max(1, fan_in))
            out.append((jax.random.normal(k, s.shape, jnp.float32) * std
                        ).astype(s.dtype))
    return jax.tree.unflatten(treedef, out)


def as_shapes(specs):
    """Strip init metadata -> plain ShapeDtypeStructs (for jit.lower)."""
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype),
        specs, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))


# --------------------------------------------------------------------------
# norms / activations / rope
# --------------------------------------------------------------------------

def rms_norm(x, weight, eps: float):
    # statistics in f32; the (big) elementwise multiply stays in x.dtype so a
    # pending TP all-reduce on x is materialized in bf16, not pushed past an
    # f32 upcast (halves the collective wire bytes — see EXPERIMENTS §Perf)
    xf = x.astype(jnp.float32)
    scale = jax.lax.rsqrt(jnp.mean(xf * xf, axis=-1, keepdims=True) + eps)
    return x * (scale.astype(x.dtype) * weight.astype(x.dtype))


def activation(name: str):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[name]


def rope_freqs(hd: int, theta: float):
    return 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))


def apply_rope(x, positions, theta: float):
    """x: (B, S, H, Dh), positions: (B, S) or (S,)."""
    hd = x.shape[-1]
    freqs = rope_freqs(hd, theta)  # (Dh/2,)
    ang = positions[..., None].astype(jnp.float32) * freqs  # (B, S, Dh/2)
    cos = jnp.cos(ang)[..., None, :]  # (B, S, 1, Dh/2)
    sin = jnp.sin(ang)[..., None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], axis=-1)
    return out.astype(x.dtype)


# --------------------------------------------------------------------------
# linear / embedding (with PIM modes)
# --------------------------------------------------------------------------

def linear(x, w, b=None, *, mode: Optional[str] = None):
    """``x @ w (+ b)`` lowered per the active PIM mode.

    ``mode=None`` reads the ambient ``pim.engine.mode(...)`` context at
    trace time; an explicit ``mode`` (e.g. ``ModelConfig.pim_mode`` threaded
    by the model stack) takes precedence.
    """
    from repro.pim import engine

    mode = engine.resolve_mode(mode)
    if mode == "quant":
        from repro.kernels.quant_matmul import quant_linear

        y = quant_linear(x, w.astype(jnp.float32))
    elif mode == "quant_tp":
        y = engine.get_backend("quant_tp")(x, w.astype(jnp.float32))
    elif mode == "pim_sim":
        y = engine.sim_linear(x, w)
    else:
        y = jnp.einsum("...k,kn->...n", x, w.astype(x.dtype))
    if b is not None:
        y = y + b.astype(y.dtype)
    return y


def embed_lookup(table, ids):
    return jnp.take(table, ids, axis=0)


def unembed(x, table, chunk: Optional[int] = None):
    """Logits = x @ table.T (table: (V, d)).

    ``chunk`` bounds the vocab-axis working set: the table is consumed in
    ``chunk``-row slices, so the compute-dtype upcast of the table (and the
    einsum intermediate) peaks at ``chunk x d`` instead of ``V x d``.  The
    loss path threads ``ModelConfig.unembed_chunk`` here.  ``None`` (or a
    chunk >= V) is the single full-width einsum.

    A single row runs as two: XLA (CPU and TPU alike) strength-reduces a
    one-row product to multiply+reduce, which sums in another order than
    the matmul any wider batch gets, so a max_batch=1 server would emit
    other tokens than the same request batched with others.
    """
    V = table.shape[0]
    if math.prod(x.shape[:-1]) == 1:
        x2 = x.reshape(1, x.shape[-1])
        return unembed(jnp.concatenate([x2, x2]), table,
                       chunk)[:1].reshape(*x.shape[:-1], V)
    if chunk is None or chunk <= 0 or chunk >= V:
        return jnp.einsum("...d,vd->...v", x, table.astype(x.dtype))
    parts = [
        jnp.einsum("...d,vd->...v", x, table[v:v + chunk].astype(x.dtype))
        for v in range(0, V, chunk)
    ]
    return jnp.concatenate(parts, axis=-1)
