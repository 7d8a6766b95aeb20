"""Top-k MoE with expert parallelism over the "model" mesh axis.

Design (DESIGN.md §4): activations are data-parallel over ("pod","data") and
*replicated* along "model"; experts are sharded over "model".  Inside a
``shard_map`` each model-rank processes only the token-assignments that
route to its local experts (gather into fixed-capacity buffers -> dense
expert FFN -> scatter-add), then one ``psum`` over "model" combines the
per-rank partial outputs — the same collective volume as a tensor-parallel
FFN all-reduce, with zero dispatch FLOPs (no one-hot einsum: dispatch is a
gather/scatter, so HLO FLOPs stay at 6*N_active*D and the roofline
MODEL_FLOPS/HLO_FLOPs ratio stays honest).

Capacity: each expert accepts ``ceil(T*k/E * capacity_factor)`` tokens per
rank-shard; overflow tokens are dropped for that expert (standard practice;
the router's other choices still serve them).
"""
from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.dist import context as dctx
from repro.models.layers import activation


def _local_moe(x, top_ids, top_w, w1, w2, w3, *, n_experts_global: int,
               e_base: int, capacity: int, act_name: str):
    """Per-rank expert compute. x: (T, d); w1: (E_loc, d, f) ...

    Returns this rank's partial output (T, d) (sum over its experts).
    """
    t, d = x.shape
    e_loc = w1.shape[0]
    out = jnp.zeros((t + 1, d), jnp.float32)  # +1 trash row for drops
    act = activation(act_name)
    for e in range(e_loc):
        ge = e_base + e
        hit = (top_ids == ge)                      # (T, k)
        tok_w = (hit * top_w).sum(-1)              # (T,)
        any_hit = hit.any(-1)
        slot = jnp.cumsum(any_hit) - 1             # (T,) position per hit
        slot = jnp.where(any_hit & (slot < capacity), slot, capacity)
        buf = jnp.zeros((capacity + 1, d), x.dtype).at[slot].set(
            jnp.where(any_hit[:, None], x, 0))
        tok_of_slot = jnp.full((capacity + 1,), t, jnp.int32).at[slot].set(
            jnp.arange(t, dtype=jnp.int32))
        h = act(buf @ w1[e].astype(x.dtype))
        if w3 is not None:
            h = h * (buf @ w3[e].astype(x.dtype))
        y = (h @ w2[e].astype(x.dtype)).astype(jnp.float32)
        gathered_w = jnp.where(tok_of_slot < t, tok_w[jnp.minimum(tok_of_slot,
                                                                  t - 1)], 0.0)
        out = out.at[tok_of_slot].add(y * gathered_w[:, None])
    return out[:t]


def moe_ffn(x, params: Dict, cfg) -> jnp.ndarray:
    """x: (B, S, d) -> (B, S, d). params: router (d, E), w1/w2/w3 (E, d, f)."""
    b, s, d = x.shape
    e = cfg.n_experts
    k = cfg.top_k
    xf = x.reshape(b * s, d)
    logits = (xf.astype(jnp.float32) @ params["router"].astype(jnp.float32))
    top_w, top_ids = jax.lax.top_k(logits, k)
    top_w = jax.nn.softmax(top_w, axis=-1)

    mesh = dctx.current_mesh()
    gated = "w3" in params

    def _cap(t_tokens):
        # capacity per expert; floor of 8 (and never above t) so tiny decode
        # batches are never dropped
        return min(t_tokens,
                   max(int(-(-t_tokens * k * cfg.capacity_factor // e)), 8))

    tp_ax = dctx.mesh_axes(mesh)[1] if mesh is not None else None
    # ZeRO-3 expert weights keep the shard_map path relevant even at
    # model=1: the weights stay 'data'-sharded and gather on use.
    zero3 = (mesh is not None and cfg.moe_fsdp_gather
             and "data" in mesh.axis_names and mesh.shape["data"] > 1)
    if mesh is None or tp_ax is None or (mesh.shape[tp_ax] == 1 and not zero3):
        cap = _cap(b * s)
        out = _local_moe(xf, top_ids, top_w, params["w1"], params["w2"],
                         params.get("w3"), n_experts_global=e, e_base=0,
                         capacity=cap, act_name=cfg.activation)
        return out.astype(x.dtype).reshape(b, s, d)

    dp, tp = dctx.mesh_axes(mesh)
    tp_size = mesh.shape[tp]
    dp_size = 1
    for a in dp:
        dp_size *= mesh.shape[a]
    if (b * s) % dp_size:
        dp = ()          # batch-1 decode: replicate tokens across data axes
        dp_size = 1
    t_loc = (b * s) // dp_size
    e_loc = e // tp_size
    cap = _cap(t_loc)

    # ZeRO-3 expert weights: keep them 'data'-sharded inside the shard_map
    # and all_gather on use — the gather's transpose is a reduce-scatter of
    # the expert grads (vs a full all-reduce when experts enter replicated).
    fsdp_gather = zero3

    def ranked(xl, idl, wl, w1, w2, w3):
        rank = jax.lax.axis_index(tp)
        if fsdp_gather:
            w1 = jax.lax.all_gather(w1, "data", axis=1, tiled=True)
            w3 = jax.lax.all_gather(w3, "data", axis=1, tiled=True)
            w2 = jax.lax.all_gather(w2, "data", axis=2, tiled=True)
        part = _local_moe(xl, idl, wl, w1, w2, w3, n_experts_global=e,
                          e_base=rank * e_loc, capacity=cap,
                          act_name=cfg.activation)
        # psum in the compute dtype: halves the dominant wire term (≤16
        # partials; the f32 accumulation inside _local_moe already absorbed
        # the long sums)
        return jax.lax.psum(part.astype(xl.dtype), tp)

    if not gated:
        raise ValueError("MoE experts are gated (SwiGLU) in all configs")
    w13_spec = P(tp, "data", None) if fsdp_gather else P(tp, None, None)
    w2_spec = P(tp, None, "data") if fsdp_gather else P(tp, None, None)
    out = jax.shard_map(
        ranked, mesh=mesh,
        in_specs=(P(dp, None), P(dp, None), P(dp, None),
                  w13_spec, w2_spec, w13_spec),
        out_specs=P(dp, None),
        check_vma=False,
    )(xf, top_ids, top_w, params["w1"], params["w2"], params["w3"])
    return out.astype(x.dtype).reshape(b, s, d)
