"""Model assembly: parameter specs, train forward, prefill, decode.

The layer stack is ``lax.scan`` over super-blocks (stacked params) so HLO
size is O(|pattern|), not O(n_layers) — this is what keeps the 480B-config
dry-run compiles tractable.  Each block kind returns ``(x, cache_out)``;
caches are scanned alongside (prefill emits them, decode threads them).
"""
from __future__ import annotations

import contextlib
import functools
from typing import Any, Dict

import jax
import jax.numpy as jnp

from repro.dist import context as dctx
from repro.models import attention as attn
from repro.models.config import ModelConfig
from repro.models.layers import (Spec, activation, apply_rope, embed_lookup,
                                 linear, materialize, rms_norm, unembed)
from repro.models.moe import moe_ffn
from repro.models.ssm import mamba_mixer
from repro.models.xlstm import mlstm_block, slstm_block

Params = Dict[str, Any]


# ==========================================================================
# parameter specs
# ==========================================================================

def _attn_specs(cfg: ModelConfig, dt) -> Dict[str, Spec]:
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    s = {
        "norm1": Spec((d,), jnp.float32, "ones"),
        "wq": Spec((d, h * hd), dt),
        "wk": Spec((d, hkv * hd), dt),
        "wv": Spec((d, hkv * hd), dt),
        "wo": Spec((h * hd, d), dt),
    }
    if cfg.qkv_bias:
        s.update(bq=Spec((h * hd,), jnp.float32, "zeros"),
                 bk=Spec((hkv * hd,), jnp.float32, "zeros"),
                 bv=Spec((hkv * hd,), jnp.float32, "zeros"))
    return s


def _mlp_specs(cfg: ModelConfig, dt) -> Dict[str, Spec]:
    d, ff = cfg.d_model, cfg.d_ff
    s = {
        "norm2": Spec((d,), jnp.float32, "ones"),
        "w_in": Spec((d, ff), dt),
        "w_out": Spec((ff, d), dt),
    }
    if cfg.gated_mlp:
        s["w_gate"] = Spec((d, ff), dt)
    return s


def _moe_specs(cfg: ModelConfig, dt) -> Dict[str, Spec]:
    d, e, f = cfg.d_model, cfg.n_experts, cfg.moe_d_ff or cfg.d_ff
    return {
        "norm_moe": Spec((d,), jnp.float32, "ones"),
        "router": Spec((d, e), jnp.float32),
        "w1": Spec((e, d, f), dt),
        "w2": Spec((e, f, d), dt),
        "w3": Spec((e, d, f), dt),
    }


def _xattn_specs(cfg: ModelConfig, dt) -> Dict[str, Spec]:
    d, h, hkv, hd = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd
    return {
        "normx": Spec((d,), jnp.float32, "ones"),
        "xwq": Spec((d, h * hd), dt),
        "xwk": Spec((d, hkv * hd), dt),
        "xwv": Spec((d, hkv * hd), dt),
        "xwo": Spec((h * hd, d), dt),
    }


def _mamba_specs(cfg: ModelConfig, dt) -> Dict[str, Spec]:
    d, di, ds, dc, dtr = (cfg.d_model, cfg.d_inner, cfg.mamba_d_state,
                          cfg.mamba_d_conv, cfg.dt_rank)
    return {
        "norm_m": Spec((d,), jnp.float32, "ones"),
        "in_proj": Spec((d, 2 * di), dt),
        "conv_w": Spec((dc, di), jnp.float32, "normal", 0.5),
        "conv_b": Spec((di,), jnp.float32, "zeros"),
        "x_proj": Spec((di, dtr + 2 * ds), dt),
        "dt_proj": Spec((dtr, di), jnp.float32, "normal", 0.5),
        "dt_bias": Spec((di,), jnp.float32, "zeros"),
        "a_log": Spec((di, ds), jnp.float32, "alog"),
        "d_skip": Spec((di,), jnp.float32, "ones"),
        "out_proj": Spec((di, d), dt),
    }


def _mlstm_specs(cfg: ModelConfig, dt) -> Dict[str, Spec]:
    d = cfg.d_model
    nh = cfg.n_heads
    p = int(cfg.xlstm_proj_factor * d)
    p -= p % nh
    dh = p // nh
    return {
        "norm_x": Spec((d,), jnp.float32, "ones"),
        "up_proj": Spec((d, 2 * p), dt),
        # block-diagonal per-head projections (as in the xLSTM reference)
        "wq": Spec((nh, dh, dh), dt),
        "wk": Spec((nh, dh, dh), dt),
        "wv": Spec((nh, dh, dh), dt),
        "w_gates": Spec((p, 2 * nh), jnp.float32, "normal", 0.5),
        "down_proj": Spec((p, d), dt),
    }


def _slstm_specs(cfg: ModelConfig, dt) -> Dict[str, Spec]:
    d = cfg.d_model
    nh = cfg.n_heads
    p = int(cfg.xlstm_proj_factor * d)
    p -= p % nh
    dh = p // nh
    return {
        "norm_x": Spec((d,), jnp.float32, "ones"),
        "up_proj": Spec((d, 2 * p), dt),
        "wz": Spec((nh, dh, dh), dt),
        "w_gates": Spec((p, 3 * nh), jnp.float32, "normal", 0.5),
        "down_proj": Spec((p, d), dt),
    }


def _block_specs(kind: str, cfg: ModelConfig, dt) -> Dict[str, Spec]:
    s: Dict[str, Spec] = {}
    if kind in ("ad", "ae", "ar", "adx", "enc"):
        s.update(_attn_specs(cfg, dt))
    if kind in ("ad", "adx", "enc", "md", "ar"):
        s.update(_mlp_specs(cfg, dt))
    if kind in ("ae", "ar", "me"):
        s.update(_moe_specs(cfg, dt))
    if kind == "adx":
        s.update(_xattn_specs(cfg, dt))
    if kind in ("md", "me"):
        s.update(_mamba_specs(cfg, dt))
    if kind == "xm":
        s.update(_mlstm_specs(cfg, dt))
    if kind == "xs":
        s.update(_slstm_specs(cfg, dt))
    return s


def _stack_specs(specs: Dict[str, Spec], n: int) -> Dict[str, Spec]:
    return {k: Spec((n,) + v.shape, v.dtype, getattr(v, "init", "normal"),
                    getattr(v, "scale", 1.0)) for k, v in specs.items()}


def param_specs(cfg: ModelConfig) -> Params:
    dt = cfg.compute_dtype
    v = cfg.padded_vocab
    d = cfg.d_model
    out: Params = {
        "embed": Spec((v, d), dt, "normal"),
        "final_norm": Spec((d,), jnp.float32, "ones"),
        "blocks": {
            str(i): _stack_specs(_block_specs(kind, cfg, dt), cfg.n_super)
            for i, kind in enumerate(cfg.pattern)
        },
    }
    if not cfg.tie_embeddings:
        out["lm_head"] = Spec((v, d), dt)
    if cfg.is_encoder_decoder:
        out["encoder"] = {
            "blocks": {"0": _stack_specs(_block_specs("enc", cfg, dt),
                                         cfg.n_encoder_layers)},
            "enc_norm": Spec((d,), jnp.float32, "ones"),
        }
    if cfg.vision_dim:
        out["vision_proj"] = Spec((cfg.vision_dim, d), dt)
    return out


def init_params(cfg: ModelConfig, key) -> Params:
    return materialize(param_specs(cfg), key)


def param_count(cfg: ModelConfig) -> int:
    import numpy as np

    leaves = jax.tree.leaves(
        param_specs(cfg), is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    return int(sum(np.prod(l.shape) for l in leaves))


# ==========================================================================
# block application
# ==========================================================================

def _quantize_kv(x):
    """(B, S, H, D) -> (int8 values, f32 per-(B,S,H) scales)."""
    xf = x.astype(jnp.float32)
    amax = jnp.max(jnp.abs(xf), axis=-1)
    scale = jnp.maximum(amax, 1e-6) / 127.0
    q = jnp.clip(jnp.round(xf / scale[..., None]), -127, 127).astype(jnp.int8)
    return q, scale


def _dequantize_kv(q, scale, dtype):
    """int8 K/V and their per-(token, head) scales -> ``dtype``.  ``q`` is
    (..., hkv, hd) with ``scale`` (..., hkv), or (..., hkv * hd) rows as
    the paged pool stores them (``fold_kv_rows``), where a row's hd values
    of one head share that head's scale."""
    if q.ndim == scale.ndim:
        scale = jnp.repeat(scale, q.shape[-1] // scale.shape[-1], axis=-1)
    else:
        scale = scale[..., None]
    return (q.astype(jnp.float32) * scale).astype(dtype)


def _self_attention(x, p, cfg: ModelConfig, positions, mode, cache, pos,
                    causal=True, block_tables=None, segment_ids=None,
                    layer=None):
    b, s, d = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    quant = cfg.kv_cache_dtype == "int8"
    hh = rms_norm(x, p["norm1"], cfg.norm_eps)
    q = linear(hh, p["wq"], p.get("bq")).reshape(b, s, h, hd)
    k = linear(hh, p["wk"], p.get("bk")).reshape(b, s, hkv, hd)
    v = linear(hh, p["wv"], p.get("bv")).reshape(b, s, hkv, hd)
    if causal:  # rope only on the causal (decoder) stacks
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    new_cache = None
    if mode == "decode" and block_tables is not None:
        # Block-paged cache: ``cache`` holds the *whole* paged pool, every
        # layer's (n_super, num_blocks, block, ...) physical store shared
        # by every slot (``paged_cache_specs``), and ``layer`` picks this
        # layer's part; ``block_tables`` (B, blocks_per_slot) maps each
        # slot's logical blocks to physical ones (sentinel entries point at
        # the reserved trash block 0, which no reader unmasks).  Logical
        # index: the absolute position, or — under a sliding window — the
        # position modulo the block-rounded ring capacity.  The step's rows
        # are written into the pool in place (the pool rides the layer
        # scan's carry, see ``_decoder_stack``), then the layer gathers
        # its blocks back, and the whole pool is returned.
        bs_blk = cache["k"].shape[2]
        lcap = block_tables.shape[1] * bs_blk
        r = jnp.arange(lcap)
        widx, blk, off = paged_write_targets(cfg, pos, s, block_tables,
                                             bs_blk)
        if s > 1:
            # Speculative verify run: query i's mask stops at its own row
            # pos+i — (B, s, lcap) per-query validity.
            valid = r[None, None, :] <= widx[:, :, None]
        elif cfg.sliding_window:
            ring = cfg.window_ring_blocks(bs_blk) * bs_blk
            _, in_ring = ring_slot_positions(pos[:, None], r[None, :],
                                             ring, cfg.sliding_window)
            valid = (r[None, :] < ring) & in_ring
        else:
            valid = r[None, :] <= pos[:, None]

        if quant:
            kq, ks = _quantize_kv(k)
            vq, vs = _quantize_kv(v)
            rows = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
        else:
            rows = {"k": k, "v": v}
        new_cache, views = {}, {}
        heads_ax = dctx.head_axis(hkv)
        for key, new in rows.items():
            new = fold_kv_rows(key, new.astype(cache[key].dtype))
            new_cache[key], views[key] = paged_append(
                cache[key], layer, new, blk, off, block_tables, heads_ax)
        if quant:
            for key in ("k", "v"):
                views[key] = _dequantize_kv(views[key], views[key + "_scale"],
                                            cfg.compute_dtype)
        out = attn.decode_attention_rows(q, views["k"], views["v"], valid)
    elif mode == "decode":
        cap = cache["k"].shape[1]
        per_slot = jnp.ndim(pos) == 1  # continuous batching: (B,) positions

        if s > 1:
            # Speculative verify run over the contiguous pool: rows land at
            # their *unwrapped* absolute indices, with out-of-capacity
            # writes dropped — wrapping (the `% cap` ring below) would let a
            # past-the-budget garbage row overwrite live early rows that
            # rollback still needs.
            if not per_slot:
                raise ValueError("multi-position decode needs per-slot "
                                 "(B,) positions")
            if cfg.sliding_window:
                raise ValueError("multi-position decode (speculative verify)"
                                 " does not support sliding_window")
            idx = pos[:, None] + jnp.arange(s)             # (B, s)
            bidx = jnp.arange(b)[:, None]

            def put(c, new):
                return c.at[bidx, idx].set(new.astype(c.dtype), mode="drop")
        elif per_slot:
            # each slot writes its token at its own cache index: modulo the
            # ring for sliding windows, else the absolute position with
            # out-of-capacity writes dropped — the speculative draft pass
            # steps this path past a slot's last row, and the unconditional
            # `% cap` wrap would land that garbage on live row 0 (the same
            # hazard the s > 1 run above drops)
            bidx = jnp.arange(b)
            if cfg.sliding_window:
                idx = pos % cap

                def put(c, new):
                    return c.at[bidx, idx].set(new[:, 0].astype(c.dtype))
            else:
                idx = pos

                def put(c, new):
                    return c.at[bidx, idx].set(new[:, 0].astype(c.dtype),
                                               mode="drop")
        else:
            idx = pos % cap

            def put(c, new):
                start = (0, idx) + (0,) * (new.ndim - 2)
                return jax.lax.dynamic_update_slice(c, new.astype(c.dtype),
                                                    start)

        if quant:
            kq, ks = _quantize_kv(k)
            vq, vs = _quantize_kv(v)
            kc, vc = put(cache["k"], kq), put(cache["v"], vq)
            ksc, vsc = put(cache["k_scale"], ks), put(cache["v_scale"], vs)
            k_full = _dequantize_kv(kc, ksc, cfg.compute_dtype)
            v_full = _dequantize_kv(vc, vsc, cfg.compute_dtype)
            new_cache = {"k": kc, "v": vc, "k_scale": ksc, "v_scale": vsc}
        else:
            kc, vc = put(cache["k"], k), put(cache["v"], v)
            k_full, v_full = kc, vc
            new_cache = {"k": kc, "v": vc}
        if s > 1:
            valid = jnp.arange(cap)[None, None, :] <= idx[:, :, None]
            out = attn.decode_attention(q, k_full, v_full, valid=valid)
        else:
            cache_len = jnp.minimum(pos + 1, cap)
            if per_slot:
                cache_len = cache_len[:, None]  # (B, 1): per-slot mask rows
            out = attn.decode_attention(q, k_full, v_full, cache_len)
    else:
        window = cfg.sliding_window if causal else None
        attn_fn = attn.flash_attention if cfg.flash_attention \
            else attn.direct_attention
        if mode == "prefill" and cache is not None:
            # Prefix-resume prefill (prefix caching): ``cache`` holds the
            # shared prompt prefix KV — dense, post-RoPE, positions
            # 0..m-1, gathered by ``PagedCachePool.read_prefix`` — and the
            # caller shifted ``positions`` by m, so q/k here are already
            # rotated at absolute positions m..m+s-1.  Queries attend
            # concat(prefix, tail) with ``q_offset=m``; the emitted cache
            # is the *tail only*, unpadded — the paged pool scatters it at
            # block offset m (``assign_tail``) without touching the shared
            # prefix blocks.
            m_len = cache["k"].shape[1]
            if quant:
                pk = _dequantize_kv(cache["k"], cache["k_scale"],
                                    cfg.compute_dtype)
                pv = _dequantize_kv(cache["v"], cache["v_scale"],
                                    cfg.compute_dtype)
            else:
                pk, pv = cache["k"].astype(k.dtype), cache["v"].astype(v.dtype)
            out = attn_fn(q, jnp.concatenate([pk, k], axis=1),
                          jnp.concatenate([pv, v], axis=1),
                          causal=causal, window=window, q_offset=m_len)
            if quant:
                kq, ks = _quantize_kv(k)
                vq, vs = _quantize_kv(v)
                new_cache = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
            else:
                new_cache = {"k": k.astype(cfg.compute_dtype),
                             "v": v.astype(cfg.compute_dtype)}
            y = linear(out.reshape(b, s, h * hd), p["wo"])
            return x + y, new_cache
        if segment_ids is not None:
            # Packed prefill: several prompts share one (1, L) stream.
            # ``positions`` is the per-token position vector (restarting at
            # 0 per segment; it already drove RoPE above) and the segment
            # mask keeps attention block-diagonal.  The emitted cache is
            # the *raw packed* k/v — per-segment ``start`` offsets in the
            # pool's assign closure unpack it, so no ring roll or headroom
            # padding here (windowed packing is gated to plen <= window,
            # where ring layout == dense layout).
            out = attn_fn(q, k, v, causal=causal, window=window,
                          segment_ids=segment_ids, positions=positions)
            if quant:
                kq, ks = _quantize_kv(k)
                vq, vs = _quantize_kv(v)
                new_cache = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
            else:
                new_cache = {"k": k.astype(cfg.compute_dtype),
                             "v": v.astype(cfg.compute_dtype)}
            y = linear(out.reshape(b, s, h * hd), p["wo"])
            return x + y, new_cache
        out = attn_fn(q, k, v, causal=causal, window=window)
        if mode == "prefill":
            if cfg.sliding_window:
                # ring capacity: the window, with decode headroom padded for
                # prompts shorter than it (a ring of only min(s, window)
                # entries would wrap early and forget keys still inside the
                # window); capped at max_seq_len like the dense branch.
                cap = min(cfg.sliding_window, max(s, cfg.max_seq_len))
                if s < cap:
                    pad = ((0, 0), (0, cap - s), (0, 0), (0, 0))
                    kc, vc = jnp.pad(k, pad), jnp.pad(v, pad)
                else:
                    # ring alignment: decode writes position p at index
                    # p % cap, so position (s-cap+r) must sit at index
                    # (s-cap+r) % cap.
                    shift = (s - cap) % cap if cap else 0
                    kc = jnp.roll(k[:, -cap:], shift, axis=1) if shift \
                        else k[:, -cap:]
                    vc = jnp.roll(v[:, -cap:], shift, axis=1) if shift \
                        else v[:, -cap:]
            else:
                # full cache with decode headroom up to max_seq_len
                cap = max(s, cfg.max_seq_len)
                pad = ((0, 0), (0, cap - s), (0, 0), (0, 0))
                kc = jnp.pad(k, pad) if cap > s else k
                vc = jnp.pad(v, pad) if cap > s else v
            if quant:
                kq, ks = _quantize_kv(kc)
                vq, vs = _quantize_kv(vc)
                new_cache = {"k": kq, "v": vq, "k_scale": ks, "v_scale": vs}
            else:
                new_cache = {"k": kc.astype(cfg.compute_dtype),
                             "v": vc.astype(cfg.compute_dtype)}
    y = linear(out.reshape(b, s, h * hd), p["wo"])
    return x + y, new_cache


def _cross_attention(x, p, cfg: ModelConfig, memory, mode, cache):
    b, s, d = x.shape
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    hh = rms_norm(x, p["normx"], cfg.norm_eps)
    q = linear(hh, p["xwq"]).reshape(b, s, h, hd)
    if mode == "decode":
        k, v = cache["xk"], cache["xv"]
        new_cache = cache
    else:
        sk = memory.shape[1]
        k = linear(memory, p["xwk"]).reshape(b, sk, hkv, hd)
        v = linear(memory, p["xwv"]).reshape(b, sk, hkv, hd)
        new_cache = ({"xk": k.astype(cfg.compute_dtype),
                      "xv": v.astype(cfg.compute_dtype)}
                     if mode == "prefill" else None)
    out = attn.cross_attention(q, k, v)
    y = linear(out.reshape(b, s, h * hd), p["xwo"])
    return x + y, new_cache


def _dense_ffn(hh, p, cfg: ModelConfig):
    act = activation(cfg.activation)
    h = act(linear(hh, p["w_in"]))
    if cfg.gated_mlp:
        h = h * linear(hh, p["w_gate"])
    return linear(h, p["w_out"])


def _mlp(x, p, cfg: ModelConfig):
    hh = rms_norm(x, p["norm2"], cfg.norm_eps)
    return x + _dense_ffn(hh, p, cfg)


def _moe(x, p, cfg: ModelConfig, dense_residual: bool):
    hh = rms_norm(x, p["norm_moe"], cfg.norm_eps)
    y = moe_ffn(hh, p, cfg)
    if dense_residual:  # arctic: parallel dense MLP on the same input
        y = y + _dense_ffn(hh, p, cfg)
    return x + y


def _mamba(x, p, cfg: ModelConfig, mode, cache):
    hh = rms_norm(x, p["norm_m"], cfg.norm_eps)
    state = (cache["ssm"], cache["conv"]) if mode == "decode" else None
    y, (ssm, conv) = mamba_mixer(hh, p, cfg, state)
    new_cache = None
    if mode in ("prefill", "decode"):
        new_cache = {"ssm": ssm.astype(jnp.float32),
                     "conv": conv.astype(cfg.compute_dtype)}
    return x + y, new_cache


def _xlstm(x, p, cfg: ModelConfig, mode, cache, kind):
    hh = rms_norm(x, p["norm_x"], cfg.norm_eps)
    fn = mlstm_block if kind == "xm" else slstm_block
    state = ((cache["c"], cache["n"], cache["m"]) if mode == "decode" else None)
    y, (c, n, m) = fn(hh, p, cfg, state)
    new_cache = None
    if mode in ("prefill", "decode"):
        new_cache = {"c": c.astype(jnp.float32), "n": n.astype(jnp.float32),
                     "m": m.astype(jnp.float32)}
    return x + y, new_cache


def apply_block(kind: str, x, p, cfg: ModelConfig, *, positions, mode,
                cache=None, pos=None, memory=None, block_tables=None,
                segment_ids=None, layer=None):
    """Returns (x, cache_out or None).  In a paged decode step ``cache``
    holds the whole paged pool and ``layer`` this super-block's index
    into it (see ``_self_attention``)."""
    out_cache = {}
    if kind in ("ad", "ae", "ar", "adx", "enc"):
        x, c = _self_attention(x, p, cfg, positions, mode, cache, pos,
                               causal=(kind != "enc"),
                               block_tables=block_tables,
                               segment_ids=segment_ids, layer=layer)
        if c:
            out_cache.update(c)
    if kind == "adx":
        x, c = _cross_attention(x, p, cfg, memory, mode, cache)
        if c:
            out_cache.update({k2: v for k2, v in c.items()
                              if k2 in ("xk", "xv")})
    if kind in ("md", "me"):
        x, c = _mamba(x, p, cfg, mode, cache)
        if c:
            out_cache.update(c)
    if kind in ("xm", "xs"):
        x, c = _xlstm(x, p, cfg, mode, cache, kind)
        if c:
            out_cache.update(c)
    if kind in ("ad", "adx", "enc", "md"):
        x = _mlp(x, p, cfg)
    if kind == "ae":
        x = _moe(x, p, cfg, dense_residual=False)
    if kind == "ar":
        x = _moe(x, p, cfg, dense_residual=True)
    if kind == "me":
        x = _moe(x, p, cfg, dense_residual=False)
    x = dctx.shard(x, dctx.dp_axes(), None, None)  # pin residual stream to DP
    return x, (out_cache or None)


# ==========================================================================
# stacks
# ==========================================================================

def _decoder_stack(params, x, cfg: ModelConfig, *, positions, mode,
                   caches=None, pos=None, memory=None, block_tables=None,
                   segment_ids=None):
    """Scan over super-blocks. caches: dict pos->stacked cache (or None).
    ``block_tables`` is shared by every layer (one slot->physical-block map
    for the whole paged pool), so it rides the closure, not the scan.

    A paged decode step's pool (its ``PAGED_KV_KEYS`` leaves) rides the
    scan's carry whole: each layer writes the step's rows into it in
    place, at its own layer index, and reads its blocks back from it.
    Scanning the pool as ``xs``/``ys`` instead slices each layer out and
    writes it back whole, which costs a copy of the pool every step even
    when the caller donates it.  Every other leaf (recurrent state, the
    contiguous pool) rides ``xs``/``ys``."""
    pools = {}
    if mode == "decode" and block_tables is not None and caches:
        pools = {i: {key: leaf for key, leaf in c.items()
                     if key in PAGED_KV_KEYS} for i, c in caches.items()}
        pools = {i: c for i, c in pools.items() if c}
        caches = {i: {key: leaf for key, leaf in c.items()
                      if key not in PAGED_KV_KEYS} for i, c in caches.items()}

    def body(carry, layer_inputs):
        x, pools = carry
        pools = dict(pools)
        pslices, cslices, layer = layer_inputs
        new_caches = {}
        for i, kind in enumerate(cfg.pattern):
            cslice = cslices.get(str(i)) if cslices else None
            if str(i) in pools:
                cslice = {**(cslice or {}), **pools[str(i)]}
            x, c = apply_block(kind, x, pslices[str(i)], cfg,
                               positions=positions, mode=mode, cache=cslice,
                               pos=pos, memory=memory,
                               block_tables=block_tables,
                               segment_ids=segment_ids, layer=layer)
            if c is not None:
                if str(i) in pools:
                    pools[str(i)] = {key: c.pop(key) for key in pools[str(i)]}
                new_caches[str(i)] = c
        return (x, pools), (new_caches or None)

    if cfg.remat and mode == "train":
        body = jax.checkpoint(body, prevent_cse=False)

    xs = (params["blocks"], caches if caches is not None
          else {str(i): None for i in range(len(cfg.pattern))},
          jnp.arange(cfg.n_super))
    if cfg.scan_layers:
        (x, pools), new_caches = jax.lax.scan(body, (x, pools), xs)
    else:
        # unrolled (dry-run mode: XLA cost analysis counts while-loop
        # bodies once, so roofline cells lower with the stack unrolled)
        per_super = []
        for i in range(cfg.n_super):
            sl = jax.tree.map(lambda a: a[i], xs)
            (x, pools), c = body((x, pools), sl)
            per_super.append(c)
        if any(c is not None for c in per_super):
            new_caches = jax.tree.map(lambda *ls: jnp.stack(ls), *per_super)
        else:
            new_caches = None
    for i, c in pools.items():
        new_caches[i].update(c)
    return x, new_caches


def _encode(params, frames, cfg: ModelConfig):
    x = frames.astype(cfg.compute_dtype)

    def body(xc, pslice):
        x, _ = apply_block("enc", xc, pslice["0"], cfg,
                           positions=jnp.arange(xc.shape[1]), mode="train")
        return x, None

    if cfg.scan_layers:
        x, _ = jax.lax.scan(body, x, params["encoder"]["blocks"])
    else:
        for i in range(cfg.n_encoder_layers):
            x, _ = body(x, jax.tree.map(lambda a: a[i],
                                        params["encoder"]["blocks"]))
    return rms_norm(x, params["encoder"]["enc_norm"], cfg.norm_eps)


def _memory(params, batch, cfg: ModelConfig):
    if cfg.is_encoder_decoder:
        return _encode(params, batch["frames"], cfg)
    if cfg.vision_dim:
        return batch["patches"].astype(cfg.compute_dtype) @ params[
            "vision_proj"].astype(cfg.compute_dtype)
    return None


# ==========================================================================
# entry points: loss / prefill / decode
# ==========================================================================

def _embed_in(params, tokens, cfg: ModelConfig):
    x = embed_lookup(params["embed"], tokens).astype(cfg.compute_dtype)
    if cfg.name.startswith("gemma"):
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return dctx.shard_batch_dim(x)


def _unembed_table(params, cfg):
    return params.get("lm_head", params["embed"])


def _vocab_logits(logits, cfg):
    """f32 serving logits over the real vocabulary: the table's padding
    rows (``cfg.padded_vocab``) are never a token, so greedy picks and the
    scheduler's argmax cannot emit an id past ``cfg.vocab_size``."""
    return logits[..., :cfg.vocab_size].astype(jnp.float32)


def serve_jit(fn, donate=()):
    """``jax.jit`` for the serving entry points (prefill, decode, verify).

    Excess precision is off: with it on, XLA may drop a bf16 rounding
    between fused ops, and whether it does depends on the batch's shape —
    on a TPU v5e a request's ``quant`` logits differed between a batch of
    one and a batch of four.  Rounding after every op keeps each row's
    result its own, whatever else shares the step.

    ``donate`` names arguments whose buffers the program may reuse for its
    outputs (``jax.jit``'s ``donate_argnames``): the decode steps donate
    the KV pool, which they return updated, so its rows are written in
    place; the caller must not touch the arrays it passed afterwards."""
    return jax.jit(fn, donate_argnames=donate,
                   compiler_options={"xla_allow_excess_precision": False})


def _pim_ctx(cfg: ModelConfig):
    """Thread ``cfg.pim_mode`` into the trace (MaxText-style config
    threading): every ``linear`` below the entry point resolves against it.
    ``None`` defers to the caller's ambient ``pim.engine.mode`` context.

    Every entry point — ``loss_fn``, ``prefill``, ``decode_step``, and the
    serving runtime's jitted ``decode_step_slots`` (contiguous *and*
    block-paged) — wraps its trace in this context, so a mode like
    ``"quant_tp"`` reaches the linears inside the ``lax.scan`` layer stack
    end to end; its shard_map tiles read the active mesh at the same trace
    time the ``dist`` sharding constraints do."""
    if cfg.pim_mode is None:
        return contextlib.nullcontext()
    from repro.pim import engine

    return engine.mode(cfg.pim_mode)


def loss_fn(params, batch, cfg: ModelConfig):
    """Mean next-token cross entropy (chunked over tokens)."""
    with _pim_ctx(cfg):
        return _loss_fn(params, batch, cfg)


def _loss_fn(params, batch, cfg: ModelConfig):
    tokens = batch["tokens"]
    labels = batch["labels"]
    x = _embed_in(params, tokens, cfg)
    memory = _memory(params, batch, cfg)
    positions = jnp.arange(tokens.shape[1])
    x, _ = _decoder_stack(params, x, cfg, positions=positions, mode="train",
                          memory=memory)
    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    table = _unembed_table(params, cfg)

    flat_x = x.reshape(-1, cfg.d_model)
    flat_y = labels.reshape(-1)
    n_tok = flat_x.shape[0]
    chunk = cfg.loss_chunk if n_tok % cfg.loss_chunk == 0 else n_tok

    dp = dctx.dp_axes()

    @functools.partial(jax.checkpoint, prevent_cse=False)  # don't keep logits
    def chunk_nll(args):
        xc, yc = args
        xc = dctx.shard(xc, dp, None)
        logits = unembed(xc, table,
                         chunk=cfg.unembed_chunk or None).astype(jnp.float32)
        logits = dctx.shard(logits, dp, dctx.tp_axis())  # tokens x vocab
        m = jax.lax.stop_gradient(logits.max(axis=-1, keepdims=True))
        lse = jnp.log(jnp.sum(jnp.exp(logits - m), axis=-1)) + m[:, 0]
        # gold logit via mask-sum: fuses elementwise over the vocab shard
        # (take_along_axis would gather across the "model"-sharded axis)
        idx = jax.lax.broadcasted_iota(jnp.int32, logits.shape, 1)
        gold = jnp.sum(jnp.where(idx == yc[:, None], logits, 0.0), axis=-1)
        return jnp.sum(lse - gold)

    xs = (flat_x.reshape(-1, chunk, cfg.d_model), flat_y.reshape(-1, chunk))
    if cfg.scan_layers:
        nll = jax.lax.map(chunk_nll, xs)
    else:
        n_chunks = n_tok // chunk
        nll = jnp.stack([chunk_nll(jax.tree.map(lambda a: a[i], xs))
                         for i in range(n_chunks)])
    return nll.sum() / n_tok


def prefill(params, batch, cfg: ModelConfig, last_index=None, prefix=None):
    """Forward the prompt; return (last-token logits, caches).

    ``last_index`` — optional (B,) int32 index of each request's last real
    prompt token, for right-padded (bucketed) prompts: logits are read
    there instead of at ``S - 1``.  Causal masking makes every position
    <= ``last_index`` independent of the padding, so bucketed prefill is
    exact for *full-attention* stacks only: recurrent blocks (Mamba/xLSTM)
    fold the padding into their state (serve those unbucketed), and a
    sliding-window cache keeps pad KV inside its ring once the padded
    length exceeds the window — the serving scheduler buckets windowed
    prompts only while ``padded <= window`` and enforces the rest.

    ``prefix`` — optional mapped-prefix KV tree (``{layer: {"k": (ns, 1,
    m, ...), ...}}`` per super-block, as returned by
    ``PagedCachePool.read_prefix``): ``batch["tokens"]`` is then the
    *divergent tail* of the prompt, resumed at absolute position ``m`` —
    positions/RoPE shift by ``m``, attention reads concat(prefix, tail)
    keys, and the returned caches hold the tail only (the paged pool
    scatters them at block offset ``m``).  ``m`` must be block-aligned
    and positive; full-attention stacks only (the caller gates recurrent
    and MoE configs, whose state/KV is not prefix-separable).
    """
    with _pim_ctx(cfg):
        tokens = batch["tokens"]
        x = _embed_in(params, tokens, cfg)
        memory = _memory(params, batch, cfg)
        off = 0
        if prefix is not None:
            off = jax.tree.leaves(prefix)[0].shape[2]
        positions = off + jnp.arange(tokens.shape[1])
        x, caches = _decoder_stack(params, x, cfg, positions=positions,
                                   mode="prefill", memory=memory,
                                   caches=prefix)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        if last_index is None:
            xl = x[:, -1]
        else:
            xl = jnp.take_along_axis(
                x, last_index[:, None, None].astype(jnp.int32), axis=1)[:, 0]
        logits = unembed(xl, _unembed_table(params, cfg))
        return _vocab_logits(logits, cfg), caches


def prefill_packed(params, tokens, positions, segment_ids, last_index,
                   cfg: ModelConfig):
    """Prefill several prompts packed into one (1, L) token stream.

    ``positions`` (L,) int32 restarts at 0 for each prompt (driving RoPE
    and the causal/window masks), ``segment_ids`` (L,) int32 keeps
    attention block-diagonal — one prompt's tokens never attend to
    another's, so each segment's logits and KV are bit-identical to its
    own unpacked ``prefill`` (padding carries segment id -1 and position
    0, which no real segment matches).  ``last_index`` (K,) int32 indexes
    each segment's final prompt token in the stream; K is fixed (the
    scheduler passes ``max_batch``, padding unused entries with 0) so a
    short burst never retraces on burst size.  Returns ``((K, V) logits,
    packed caches)`` — cache leaves keep the raw packed (1, L) stream
    layout; ``PagedCachePool.admit(start=)`` unpacks per segment.

    Full-attention stacks only (same gate as the prefix-resume path:
    recurrent state folds segments together, MoE routing is
    batch-coupled); windowed configs only for segments ``<= window``.
    """
    with _pim_ctx(cfg):
        x = _embed_in(params, tokens, cfg)
        x, caches = _decoder_stack(params, x, cfg, positions=positions,
                                   mode="prefill",
                                   segment_ids=segment_ids)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        xl = x[0, last_index.astype(jnp.int32)]     # (K, d)
        table = _unembed_table(params, cfg)
        # one (1, d) unembed per segment: a (K, d) matmul picks a different
        # reduction order than the (1, d) row the unpacked prefill runs,
        # and bit-exactness vs unpacked is the packed path's contract
        logits = jnp.concatenate(
            [unembed(xl[i:i + 1], table) for i in range(xl.shape[0])], axis=0)
        return _vocab_logits(logits, cfg), caches


def decode_step(params, token, pos, caches, cfg: ModelConfig):
    """One greedy decode step. token: (B, 1) int32; pos: scalar int32."""
    with _pim_ctx(cfg):
        x = _embed_in(params, token, cfg)
        positions = jnp.full((1,), pos, jnp.int32)
        x, new_caches = _decoder_stack(params, x, cfg, positions=positions,
                                       mode="decode", caches=caches, pos=pos)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = _vocab_logits(
            unembed(x[:, -1], _unembed_table(params, cfg)), cfg)
        next_tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        return next_tok, logits, new_caches


def decode_step_slots(params, tokens, pos, active, caches, cfg: ModelConfig,
                      block_tables=None):
    """One decode step over a slot batch (continuous batching).

    ``tokens``: (B, 1) int32 current token per slot; ``pos``: (B,) int32
    absolute position of that token per slot; ``active``: (B,) bool slot
    occupancy.  Shapes are fixed at ``B = max_batch``, so one jitted step
    serves a churning request mix without ever recompiling — slots attend
    only up to their own ``pos`` (per-slot ``cache_len`` masks), and
    finished/empty slots keep computing on stale state.  An inactive slot
    writes its (garbage) KV at ``pos[b] % cap`` of its *own* cache rows,
    which other slots never read and which prefill-on-admit fully
    overwrites; its emitted token is pinned to 0 by the active mask.

    ``block_tables`` (B, blocks_per_slot) int32 switches the attention
    leaves to the block-paged layout (``paged_cache_specs``): reads gather
    the slot's blocks, writes land at the slot's current block/offset, and
    an inactive slot's all-sentinel row routes its garbage write to the
    trash block.  Its shape is fixed, so block churn never recompiles.
    """
    with _pim_ctx(cfg):
        x = _embed_in(params, tokens, cfg)
        x, new_caches = _decoder_stack(params, x, cfg,
                                       positions=pos[:, None],
                                       mode="decode", caches=caches, pos=pos,
                                       block_tables=block_tables)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        logits = _vocab_logits(
            unembed(x[:, -1], _unembed_table(params, cfg)), cfg)
        next_tok = jnp.argmax(logits, axis=-1)[:, None].astype(jnp.int32)
        next_tok = jnp.where(active[:, None], next_tok, 0)
        return next_tok, logits, new_caches


def decode_run_slots(params, tokens, pos, active, caches, cfg: ModelConfig,
                     block_tables=None):
    """Verify a run of ``S`` candidate tokens per slot in one decode step.

    The speculative-decoding verify pass: ``tokens`` (B, S) int32 holds,
    per slot, the current token followed by ``S - 1`` drafted tokens;
    ``pos`` (B,) int32 is the absolute position of ``tokens[:, 0]``.
    Token ``i`` is fed at position ``pos + i``, its KV row written at that
    logical index (overwriting whatever the drafting pass left there), and
    its greedy continuation read out — the returned ``verify_tok`` (B, S)
    int32 is ``argmax(logits[:, i])`` for every ``i``.  The caller accepts
    the longest prefix where ``verify_tok[:, i] == tokens[:, i + 1]``
    (pure integer comparison; greedy decode makes acceptance exact) and
    rewinds ``pos`` past the rejected tail — the rejected rows hold
    garbage KV, but every mask in this stack is position-gated
    (``row <= query pos``), so a garbage row is always overwritten by the
    next run before any query can see it.

    Bit-exactness contract: with ``S = 1`` this is ``decode_step_slots``;
    for any ``S``, row ``i``'s hidden state equals the plain decode step's
    at the same position with the same fed prefix, because every linear
    lowering in the engine quantizes per activation row and the unembed
    below runs one (B, d) matmul per position (same reduction order as the
    single-token step — a batched (B*S, d) unembed would pick a different
    one).  Shapes are fixed at (B, S), so acceptance-length churn never
    retraces.  Not supported: sliding-window rings (rollback would need
    rows the ring already overwrote), recurrent blocks and MoE routing
    (state/capacity couple positions; the scheduler gates these).
    """
    with _pim_ctx(cfg):
        x = _embed_in(params, tokens, cfg)
        run = jnp.arange(tokens.shape[1], dtype=jnp.int32)
        x, new_caches = _decoder_stack(params, x, cfg,
                                       positions=pos[:, None] + run[None, :],
                                       mode="decode", caches=caches, pos=pos,
                                       block_tables=block_tables)
        x = rms_norm(x, params["final_norm"], cfg.norm_eps)
        table = _unembed_table(params, cfg)
        # one (B, d) unembed per run position: the prefill_packed precedent
        # — a (B*S, d) matmul picks a different reduction order than the
        # (B, d) rows the plain decode step runs, and bit-exactness vs
        # non-speculative decode is the verify pass's whole contract
        logits = _vocab_logits(jnp.stack([unembed(x[:, i], table)
                                          for i in range(tokens.shape[1])],
                                         axis=1), cfg)
        verify_tok = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        verify_tok = jnp.where(active[:, None], verify_tok, 0)
        return verify_tok, logits, new_caches


# ==========================================================================
# cache specs (for the dry-run)
# ==========================================================================

#: Attention-KV leaf names eligible for block paging: these carry a token
#: (sequence) dim and grow with context.  Everything else in the decode
#: cache tree — recurrent state (ssm/conv/c/n/m) and cross-attention
#: memory (xk/xv) — is fixed-size per slot and stays slot-indexed.
PAGED_KV_KEYS = ("k", "v", "k_scale", "v_scale")
#: The paged leaves stored as (hkv * hd) rows (``fold_kv_rows``).
FOLDED_KV_KEYS = ("k", "v")


def fold_kv_rows(key: str, x):
    """A K/V leaf's entries as the paged pool stores them: ``k`` and ``v``
    fold their trailing (hkv, hd) into one row of hkv * hd values, scales
    keep their (hkv,).  A TPU lays an array whose last dim is narrower
    than its 128 lanes out with another dim minor — for a (..., block,
    hkv, 64) pool, the block dim — and neither the decode step's row
    writes nor its block gathers run in place on that layout: each step
    then copied the pool to another layout and back.  A row of hkv * hd
    (1024 for qwen1.5-0.5b, 640 for h2o-danube-1.8b) keeps the pool in
    the plain row-major layout, and the decode step's attention reads the
    rows as they are (``attention.decode_attention_rows``)."""
    return x.reshape(x.shape[:-2] + (-1,)) if key in FOLDED_KV_KEYS else x


def unfold_kv_rows(key: str, x, cfg: ModelConfig):
    """Inverse of ``fold_kv_rows``: (..., hkv * hd) rows -> (..., hkv, hd)."""
    if key in FOLDED_KV_KEYS:
        return x.reshape(x.shape[:-1] + (cfg.n_kv_heads, cfg.hd))
    return x


def paged_append(pool, layer, rows, blk, off, block_tables, heads_ax=None):
    """Write one layer's new rows into a paged pool leaf, then read that
    layer's blocks of every slot back: ``(pool, view)``.

    ``pool`` (n_super, num_blocks, block, ...); ``rows`` (B, s, ...) land
    at the physical blocks ``blk`` and offsets ``off`` (B, s) of
    ``paged_write_targets``, at the layer index ``layer``; ``view`` is
    (B, lcap, ...) in each slot's logical order.  Both index the whole
    pool, so no layer-sized slice of it is made, and the write is in place
    where the pool is a loop carry of a jit that donates it.  Under a mesh
    the rows are placed as ``dist.cache_pspecs`` placed the pool, their
    last dim over ``heads_ax`` (``dist.context.head_axis``): rows split
    otherwise (an MQA projection's over "model") would pull the pool to
    their placement, and the donated buffer could not hold the result."""
    rows = dctx.pin(rows, *([None] * (rows.ndim - 1)), heads_ax)
    pool = pool.at[layer, blk, off].set(rows)
    b = block_tables.shape[0]
    lcap = block_tables.shape[1] * pool.shape[2]
    view = pool[layer, block_tables].reshape((b, lcap) + pool.shape[3:])
    return pool, view


def paged_write_targets(cfg: ModelConfig, pos, s: int, block_tables,
                        block_size: int):
    """Where a paged decode step writes its ``s`` new K/V rows per slot.

    Returns ``(widx, blk, off)``, each (B, s) int32: the logical row
    (``pos + i``, or under a sliding window the position modulo the
    block-rounded ring), and the physical block and offset it lands at.
    A row at or past the slot's logical capacity goes to the trash block
    0: the speculative draft pass drives single-token steps up to
    ``draft_k - 2`` rows past a slot's last reserved position, where the
    clamped table lookup would resolve to the slot's *last real block*
    and overwrite a committed row the verify step never rewrites.  Rows
    past the slot's reservation but inside ``lcap`` hit sentinel table
    entries, which point at the trash block too.
    """
    b = block_tables.shape[0]
    lcap = block_tables.shape[1] * block_size
    slots = jnp.arange(b)[:, None]
    if s > 1 and cfg.sliding_window:
        # rolling back a rejected draft would need ring rows the run's own
        # writes already destroyed
        raise ValueError("multi-position decode (speculative verify)"
                         " does not support sliding_window")
    if cfg.sliding_window:
        ring = cfg.window_ring_blocks(block_size) * block_size
        widx = (pos % ring)[:, None]
        blk = block_tables[slots, widx // block_size]
    else:
        widx = pos[:, None] + jnp.arange(s)
        blk = block_tables[slots, jnp.minimum(widx, lcap - 1) // block_size]
        blk = jnp.where(widx < lcap, blk, 0)
    return widx, blk, widx % block_size


def ring_slot_positions(last_pos, r, ring: int, window: int):
    """Sliding-window ring congruence, shared by writeback and readback.

    For ring index ``r`` (broadcastable against ``last_pos``), returns
    ``(p_r, valid)``: the newest absolute position ``<= last_pos`` with
    ``p_r % ring == r``, and whether that position exists and is still
    inside the attention window.  The paged decode path (reading a slot's
    block ring at position ``last_pos``) and the pool's admit conversion
    (laying out a ``plen``-token prefill, ``last_pos = plen - 1``) must
    agree on this bit-for-bit — keep both on this helper.
    """
    p_r = last_pos - ((last_pos - r) % ring)
    return p_r, (p_r >= 0) & (p_r > last_pos - window)


def paged_cache_specs(cfg: ModelConfig, batch: int, seq_len: int,
                      num_blocks: int, block_size: int) -> Dict:
    """``cache_specs`` with the attention-KV leaves re-laid as block pools.

    Each ``PAGED_KV_KEYS`` leaf becomes a ``(n_super, num_blocks,
    block_size, ...)`` physical store shared by every slot (block 0 is the
    pool's reserved sentinel/trash block); the per-slot token capacity
    moves into the block table, not the array shapes.  ``k`` and ``v``
    store each token as one (hkv * hd) row (``fold_kv_rows``).
    Non-attention leaves keep their ``(n_super, batch, ...)`` slot layout.
    """
    out = cache_specs(cfg, batch, seq_len)
    for c in out.values():
        for key in PAGED_KV_KEYS:
            if key in c:
                s = c[key]
                # (ns, batch, cap, ...) -> (ns, num_blocks, block, ...)
                row = s.shape[3:]
                if key in FOLDED_KV_KEYS:
                    row = (row[0] * row[1],)
                c[key] = jax.ShapeDtypeStruct(
                    (s.shape[0], num_blocks, block_size) + row, s.dtype)
    return out


def cache_specs(cfg: ModelConfig, batch: int, seq_len: int) -> Dict:
    """ShapeDtypeStructs of the decode caches for a given shape cell."""
    dt = cfg.compute_dtype
    ns = cfg.n_super
    h, hkv, hd = cfg.n_heads, cfg.n_kv_heads, cfg.hd
    out: Dict[str, Any] = {}
    for i, kind in enumerate(cfg.pattern):
        c: Dict[str, Any] = {}
        if kind in ("ad", "ae", "ar", "adx"):
            cap = min(seq_len, cfg.sliding_window) if cfg.sliding_window \
                else seq_len
            kv_dt = jnp.int8 if cfg.kv_cache_dtype == "int8" else dt
            c["k"] = jax.ShapeDtypeStruct((ns, batch, cap, hkv, hd), kv_dt)
            c["v"] = jax.ShapeDtypeStruct((ns, batch, cap, hkv, hd), kv_dt)
            if cfg.kv_cache_dtype == "int8":
                c["k_scale"] = jax.ShapeDtypeStruct((ns, batch, cap, hkv),
                                                    jnp.float32)
                c["v_scale"] = jax.ShapeDtypeStruct((ns, batch, cap, hkv),
                                                    jnp.float32)
        if kind == "adx":
            p = cfg.n_patches or (seq_len // cfg.audio_frames_div)
            c["xk"] = jax.ShapeDtypeStruct((ns, batch, p, hkv, hd), dt)
            c["xv"] = jax.ShapeDtypeStruct((ns, batch, p, hkv, hd), dt)
        if kind in ("md", "me"):
            c["ssm"] = jax.ShapeDtypeStruct(
                (ns, batch, cfg.d_inner, cfg.mamba_d_state), jnp.float32)
            c["conv"] = jax.ShapeDtypeStruct(
                (ns, batch, cfg.mamba_d_conv - 1, cfg.d_inner), dt)
        if kind in ("xm", "xs"):
            p = int(cfg.xlstm_proj_factor * cfg.d_model)
            p -= p % cfg.n_heads
            dh = p // cfg.n_heads
            if kind == "xm":
                c["c"] = jax.ShapeDtypeStruct(
                    (ns, batch, cfg.n_heads, dh, dh), jnp.float32)
            else:
                c["c"] = jax.ShapeDtypeStruct(
                    (ns, batch, cfg.n_heads, dh), jnp.float32)
            c["n"] = jax.ShapeDtypeStruct(
                (ns, batch, cfg.n_heads) + ((dh,) if kind == "xm" else ()),
                jnp.float32)
            c["m"] = jax.ShapeDtypeStruct((ns, batch, cfg.n_heads), jnp.float32)
        if c:
            out[str(i)] = c
    return out
