"""Attention: GQA/MQA, sliding windows, cross-attention, KV-cache decode.

Training/prefill use a pure-jnp flash implementation (two-level ``lax.scan``
over query/key blocks with an online softmax): memory is O(Bq*Bk) per step
instead of O(S^2), which is what lets the 32k-prefill cells fit the dry-run
memory budget; XLA counts the same FLOPs as monolithic attention.
"""
from __future__ import annotations

import functools
import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.dist import context as dctx

NEG_INF = -1e30


def _repeat_kv(k, n_rep: int):
    if n_rep == 1:
        return k
    b, s, h, d = k.shape
    return jnp.broadcast_to(k[:, :, :, None, :], (b, s, h, n_rep, d)
                            ).reshape(b, s, h * n_rep, d)


def direct_attention(q, k, v, *, causal: bool, window: Optional[int] = None,
                     q_offset: int = 0,
                     segment_ids: Optional[jnp.ndarray] = None,
                     positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Materialized-scores attention (exact HLO flop accounting; used by the
    dry-run cost lowering — memory comes from the flash lowering).

    ``positions``/``segment_ids``: packed-prefill support.  ``positions``
    (S,) replaces the arange-derived q/k positions (requires Sq == Sk —
    q and k cover the same packed token stream); ``segment_ids`` (S,)
    adds a block-diagonal mask so tokens never attend across segments.
    """
    b, sq, h, d = q.shape
    _, sk, hkv, _ = k.shape
    k = _repeat_kv(k, h // hkv)
    v = _repeat_kv(v, h // hkv)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) / math.sqrt(d)
    if positions is not None:
        if sq != sk:
            raise ValueError("positions requires Sq == Sk (packed prefill)")
        q_pos = k_pos = jnp.asarray(positions, jnp.int32)
    else:
        q_pos = q_offset + jnp.arange(sq)
        k_pos = jnp.arange(sk)
    mask = jnp.ones((sq, sk), bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if window is not None:
        mask &= q_pos[:, None] - k_pos[None, :] < window
    if segment_ids is not None:
        seg = jnp.asarray(segment_ids, jnp.int32)
        mask &= seg[:, None] == seg[None, :]
    s = jnp.where(mask[None, None], s, NEG_INF)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def flash_attention(q, k, v, *, causal: bool, window: Optional[int] = None,
                    q_offset: int = 0, block_q: int = 512,
                    block_k: int = 512,
                    segment_ids: Optional[jnp.ndarray] = None,
                    positions: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """q: (B, Sq, H, D), k/v: (B, Sk, Hkv, D) -> (B, Sq, H, D).

    ``q_offset``: absolute position of q[0] (for prefill continuation).
    ``window``: sliding-window radius (attend to keys in (pos-window, pos]).
    ``positions``/``segment_ids``: packed-prefill support — ``positions``
    (S,) replaces the arange-derived positions for both q and k (requires
    Sq == Sk), ``segment_ids`` (S,) masks cross-segment pairs.
    """
    b, sq, h, d = q.shape
    _, sk, hkv, _ = k.shape
    n_rep = h // hkv
    k = _repeat_kv(k, n_rep)
    v = _repeat_kv(v, n_rep)

    pad_q = (-sq) % block_q
    pad_k = (-sk) % block_k
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    if pad_k:
        k = jnp.pad(k, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_k), (0, 0), (0, 0)))
    sq_p, sk_p = q.shape[1], k.shape[1]
    nq, nk = sq_p // block_q, sk_p // block_k

    # (nq, B, H, Bq, D) etc — scan over leading axis; batch on DP, heads on TP
    dp = dctx.dp_axes()
    qb = q.reshape(b, nq, block_q, h, d).transpose(1, 0, 3, 2, 4)
    kb = k.reshape(b, nk, block_k, h, d).transpose(1, 0, 3, 2, 4)
    vb = v.reshape(b, nk, block_k, h, d).transpose(1, 0, 3, 2, 4)
    tp = dctx.tp_axis()
    qb = dctx.shard(qb, None, dp, tp, None, None)
    kb = dctx.shard(kb, None, dp, tp, None, None)
    vb = dctx.shard(vb, None, dp, tp, None, None)
    scale = 1.0 / math.sqrt(d)

    # Per-block position/segment vectors.  Default path derives positions
    # from block indices (identical masks to an arange over the stream);
    # the packed path scans explicit per-token vectors instead.
    if positions is not None:
        if sq != sk:
            raise ValueError("positions requires Sq == Sk (packed prefill)")
        posv = jnp.asarray(positions, jnp.int32)
        q_posb = jnp.pad(posv, (0, pad_q)).reshape(nq, block_q)
        k_posb = jnp.pad(posv, (0, pad_k)).reshape(nk, block_k)
    else:
        q_posb = (q_offset + jnp.arange(sq_p, dtype=jnp.int32)
                  ).reshape(nq, block_q)
        k_posb = jnp.arange(sk_p, dtype=jnp.int32).reshape(nk, block_k)
    use_seg = segment_ids is not None
    if use_seg:
        segv = jnp.asarray(segment_ids, jnp.int32)
        q_segb = jnp.pad(segv, (0, pad_q), constant_values=-1
                         ).reshape(nq, block_q)
        k_segb = jnp.pad(segv, (0, pad_k), constant_values=-1
                         ).reshape(nk, block_k)

    k_idx_base = jnp.arange(block_k)

    @functools.partial(jax.checkpoint, prevent_cse=False)
    def q_step(_, qi_q):
        if use_seg:
            qblk, q_pos, q_seg = qi_q
        else:
            qblk, q_pos = qi_q
            q_seg = None

        @functools.partial(jax.checkpoint, prevent_cse=False)
        def k_step(carry, ki_kv):
            m, l, acc = carry
            if use_seg:
                ki, kblk, vblk, k_pos, k_seg = ki_kv
            else:
                ki, kblk, vblk, k_pos = ki_kv
            s = jnp.einsum("bhqd,bhkd->bhqk", qblk, kblk,
                           preferred_element_type=jnp.float32) * scale
            mask = jnp.ones((block_q, block_k), bool)
            if causal:
                mask &= q_pos[:, None] >= k_pos[None, :]
            if window is not None:
                mask &= q_pos[:, None] - k_pos[None, :] < window
            if use_seg:
                mask &= q_seg[:, None] == k_seg[None, :]
            mask &= (ki * block_k + k_idx_base < sk)[None, :]  # kv padding
            s = jnp.where(mask[None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = l * corr + p.sum(-1)
            acc_new = acc * corr[..., None] + jnp.einsum(
                "bhqk,bhkd->bhqd", p.astype(vblk.dtype), vblk,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((b, h, block_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((b, h, block_q), jnp.float32)
        a0 = jnp.zeros((b, h, block_q, d), jnp.float32)
        k_xs = ((jnp.arange(nk), kb, vb, k_posb, k_segb) if use_seg
                else (jnp.arange(nk), kb, vb, k_posb))
        (m, l, acc), _ = jax.lax.scan(k_step, (m0, l0, a0), k_xs)
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return None, out.astype(q.dtype)

    q_xs = (qb, q_posb, q_segb) if use_seg else (qb, q_posb)
    _, ob = jax.lax.scan(q_step, None, q_xs)
    out = ob.transpose(1, 0, 3, 2, 4).reshape(b, sq_p, h, d)
    return out[:, :sq]


def decode_attention(q, k_cache, v_cache, cache_len=None, *,
                     window: Optional[int] = None,
                     valid: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Decode-step attention: q (B, Sq, H, D) over cache (B, S, Hkv, D).

    The key mask comes from ``cache_len`` (prefix semantics: indices below
    it are live, optionally window-clipped) or, for non-contiguous cache
    layouts, from an explicit ``valid`` (B, S) boolean mask — the paged
    pool's gather path computes per-logical-index validity (ring wraparound,
    unallocated sentinel blocks) that a single prefix length can't express.

    ``Sq`` is 1 for the plain decode step.  A speculative verify run feeds
    ``Sq > 1`` consecutive positions with a per-query ``valid`` (B, Sq, S)
    mask — query ``i`` may only see cache rows at positions ``<= pos + i``,
    which keeps the run causal and masks the rows the run itself just wrote
    for *later* queries.  The ``Sq = 1`` trace is unchanged by the
    generalization (same reshapes, same einsums, same broadcast mask).

    A batch of one runs as two rows, for the reason ``layers.unembed``
    gives: XLA lowers the size-1 batch to another reduction order, and a
    row's output must not depend on how many rows share the step.
    """
    if q.shape[0] == 1:
        def dup(a):
            return a if a is None or jnp.ndim(a) == 0 else \
                jnp.concatenate([a, a])

        return decode_attention(dup(q), dup(k_cache), dup(v_cache),
                                dup(cache_len), window=window,
                                valid=dup(valid))[:1]
    b, sq, h, d = q.shape
    _, s, hkv, _ = k_cache.shape
    n_rep = h // hkv
    qg = q.reshape(b, sq, hkv, n_rep, d)
    scores = jnp.einsum("bqhgd,bshd->bhgqs", qg, k_cache,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(d)
    if valid is not None:
        mask = valid
    else:
        pos = jnp.arange(s)
        mask = pos[None, :] < cache_len  # (B?, S) — cache_len scalar or (B,)
        if window is not None:
            mask = mask & (pos[None, :] > cache_len - 1 - window)
    p = _decode_probs(scores, mask, v_cache.dtype)
    out = jnp.einsum("bhgqs,bshd->bqhgd", p, v_cache,
                     preferred_element_type=jnp.float32)
    return out.reshape(b, sq, h, d).astype(q.dtype)


def _decode_probs(scores, mask, dtype):
    """Softmax of decode scores (B, Hkv, G, Sq, S) over the keys ``mask``
    (B, S) or (B, Sq, S) lets through, in ``dtype``."""
    b, s = scores.shape[0], scores.shape[-1]
    if mask.ndim == 3:          # per-query validity (B, Sq, S)
        mask5 = mask[:, None, None, :, :]
    else:
        mask5 = jnp.broadcast_to(mask, (b, s))[:, None, None, None, :]
    scores = jnp.where(mask5, scores, NEG_INF)
    return jax.nn.softmax(scores, axis=-1).astype(dtype)


def decode_attention_rows(q, k_rows, v_rows, valid) -> jnp.ndarray:
    """``decode_attention`` over a cache that holds each position as one
    row of ``Hkv * D`` values: q (B, Sq, H, D), k_rows / v_rows (B, S,
    Hkv * D), ``valid`` (B, S) or per query (B, Sq, S).

    The paged pool keeps its K/V rows whole (``model.fold_kv_rows``), and
    splitting the gathered rows into (Hkv, D) for per-head contractions
    makes a TPU lay the whole gathered cache out again, as D (64, 80) is
    narrower than its 128 lanes.  Here each query head contracts the whole
    row, its entries outside the head's own KV head zero, and each head
    keeps its own D columns of the output row: the same sums over the same
    products, at the cost of multiplications by zero, which are cheap
    next to reading the cache.  Only the order in which the accumulation
    adds a score's D products may differ from ``decode_attention``'s.
    A batch of one runs as two rows, as there.

    Under a "model" axis that splits the KV heads, the rows arrive split
    between whole heads (``dist.cache_pspecs``), and each shard attends
    with its own query heads over its own part of every row: a score sums
    over its head's D entries alone, so contracting a whole sharded row
    would add partial scores across chips."""
    ax = dctx.head_axis(k_rows.shape[-1] // q.shape[-1])
    if ax is not None:
        mesh, dp = dctx.current_mesh(), dctx.dp_axes()
        heads = dctx.pspec_for(mesh, q.shape, dp, None, ax)
        rows = dctx.pspec_for(mesh, k_rows.shape, dp, None, ax)
        return jax.shard_map(
            _attend_rows, mesh=mesh,
            in_specs=(heads, rows, rows, dctx.pspec_for(mesh, valid.shape,
                                                        dp)),
            out_specs=heads, check_vma=False)(q, k_rows, v_rows, valid)
    return _attend_rows(q, k_rows, v_rows, valid)


def _attend_rows(q, k_rows, v_rows, valid):
    """``decode_attention_rows`` over the heads and rows it is given."""
    if q.shape[0] == 1:
        def dup(a):
            return jnp.concatenate([a, a])

        return _attend_rows(dup(q), dup(k_rows), dup(v_rows),
                            dup(valid))[:1]
    b, sq, h, d = q.shape
    hkv = k_rows.shape[-1] // d
    n_rep = h // hkv
    # own[g, 0, k, 0]: query group g reads KV head k
    own = (jnp.arange(hkv)[:, None] == jnp.arange(hkv)[None, :]
           )[:, None, :, None]
    q_rows = jnp.where(own, q.reshape(b, sq, hkv, n_rep, 1, d), 0)
    q_rows = q_rows.reshape(b, sq, hkv, n_rep, hkv * d)
    scores = jnp.einsum("bqhgc,bsc->bhgqs", q_rows, k_rows,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(d)
    p = _decode_probs(scores, valid, v_rows.dtype)
    out = jnp.einsum("bhgqs,bsc->bhgqc", p, v_rows,
                     preferred_element_type=jnp.float32)
    out = out.reshape(b, hkv, n_rep, sq, hkv, d)
    out = jnp.sum(jnp.where(own[:, :, None], out, 0), axis=4)
    return out.transpose(0, 3, 1, 2, 4).reshape(b, sq, h, d).astype(q.dtype)


def cross_attention(q, k, v) -> jnp.ndarray:
    """Full (non-causal) attention onto a small memory (patches / frames)."""
    b, sq, h, d = q.shape
    _, sk, hkv, _ = k.shape
    k = _repeat_kv(k, h // hkv)
    v = _repeat_kv(v, h // hkv)
    s = jnp.einsum("bqhd,bkhd->bhqk", q, k,
                   preferred_element_type=jnp.float32) / math.sqrt(d)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bkhd->bqhd", p, v,
                      preferred_element_type=jnp.float32).astype(q.dtype)
