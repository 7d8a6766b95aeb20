"""Plain float32 reference of the served dense decoder.

Straight ``jax.numpy`` under ``jax.default_matmul_precision("highest")``,
written from the published architecture and imports nothing of the
program: a pre-norm decoder (RMSNorm), rotary embeddings (rotate-half,
``rope_theta``), grouped-query attention with an optional sliding window
(a query at ``i`` sees keys ``j`` with ``i - window < j <= i``), optional
QKV bias, a SiLU-gated MLP (``act(x W_in) * (x W_gate)``, then
``W_out``), a final RMSNorm and a tied or separate output table.

Every linear runs the ``quant`` lowering's arithmetic, written here on its
own: symmetric ``bits``-bit quantization with ``qmax = 2**(bits-1) - 1``,
one scale per activation row and one per weight column (``absmax / qmax``,
at least ``1e-8``), round half to even, an exact integer product, then both
scales.  ``bits=4`` is the control: the same model one step below the
int8 that the cell's configuration states.  The output table is a plain
float32 product, as the program's unembedding is not a quantized linear.

Weights are remade here from the seed, one layer at a time, by
:mod:`servebench.weights`; nothing the program made is read.  Sequences
run together as one padded batch, layer by layer, and attention in blocks
of queries, so a whole cell's sample fits beside nothing else on the chip.
"""
from __future__ import annotations

import dataclasses
import functools
import math
from typing import Dict, Optional

import jax
import jax.numpy as jnp
import numpy as np

from servebench.weights import leaf_value, seed_key

Q_BLOCK = 512


@dataclasses.dataclass(frozen=True)
class Arch:
    d: int
    heads: int
    kv_heads: int
    head_dim: int
    ff: int
    vocab: int
    layers: int
    theta: float
    eps: float
    window: Optional[int]
    tied: bool
    qkv_bias: bool
    dtype: str

    @classmethod
    def from_config(cls, c: Dict) -> "Arch":
        window = c.get("sliding_window")
        if c.get("use_sliding_window") is False:
            window = None
        if c.get("hidden_act", "silu") != "silu":
            raise ValueError("the reference covers SiLU-gated MLPs only")
        return cls(d=c["hidden_size"], heads=c["num_attention_heads"],
                   kv_heads=c["num_key_value_heads"],
                   head_dim=c.get("head_dim") or (c["hidden_size"]
                                                  // c["num_attention_heads"]),
                   ff=c["intermediate_size"], vocab=c["vocab_size"],
                   layers=c["num_hidden_layers"],
                   theta=float(c.get("rope_theta", 10000.0)),
                   eps=float(c.get("rms_norm_eps", 1e-6)), window=window,
                   tied=bool(c.get("tie_word_embeddings", False)),
                   qkv_bias=bool(c.get("qkv_bias", False)),
                   dtype=c.get("torch_dtype", "bfloat16"))


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def quant_linear(x, w, bits: int):
    """``x @ w`` through symmetric integer quantization (rows of ``x``,
    columns of ``w``), exact integer accumulation."""
    qmax = 2 ** (bits - 1) - 1
    lead = x.shape[:-1]
    x2 = x.reshape(-1, x.shape[-1])
    xs = jnp.maximum(jnp.max(jnp.abs(x2), 1, keepdims=True), 1e-8) / qmax
    ws = jnp.maximum(jnp.max(jnp.abs(w), 0, keepdims=True), 1e-8) / qmax
    xq = jnp.clip(jnp.round(x2 / xs), -qmax, qmax).astype(jnp.int8)
    wq = jnp.clip(jnp.round(w / ws), -qmax, qmax).astype(jnp.int8)
    acc = jax.lax.dot_general(xq, wq, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.int32)
    y = acc.astype(jnp.float32) * xs * ws
    return y.reshape(*lead, w.shape[1])


def rope(x, theta):
    """x: (N, T, H, D), rotate-half convention at positions 0..T-1."""
    d = x.shape[-1]
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    ang = jnp.arange(x.shape[1], dtype=jnp.float32)[:, None] * inv
    cos = jnp.cos(jnp.concatenate([ang, ang], -1))[None, :, None, :]
    sin = jnp.sin(jnp.concatenate([ang, ang], -1))[None, :, None, :]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], -1) * sin


def attention(q, k, v, window):
    """Causal (optionally windowed) GQA; q: (N, T, H, D), k/v: (N, T, Hk,
    D); queries in blocks of ``Q_BLOCK``."""
    n, t, h, dd = q.shape
    hk = k.shape[2]
    g = h // hk
    keys = jnp.arange(t)
    outs = []
    for s in range(0, t, Q_BLOCK):
        qb = q[:, s:s + Q_BLOCK].reshape(n, -1, hk, g, dd)
        qi = jnp.arange(s, s + qb.shape[1])
        sc = jnp.einsum("nqkgd,nskd->nkgqs", qb, k) / math.sqrt(dd)
        ok = keys[None, :] <= qi[:, None]
        if window:
            ok &= qi[:, None] - keys[None, :] < window
        sc = jnp.where(ok[None, None, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, -1)
        outs.append(jnp.einsum("nkgqs,nskd->nqkgd", p, v).reshape(
            n, -1, h, dd))
    return jnp.concatenate(outs, 1)


class Reference:
    """The float32 model for one seed, at ``bits``-bit linears."""

    def __init__(self, config: Dict, seed: int, bits: int = 8):
        self.a = Arch.from_config(config)
        self.key = seed_key(seed)
        self.bits = bits
        self._dt = jnp.bfloat16 if self.a.dtype == "bfloat16" \
            else jnp.float32

    def _leaf(self, name, shape, dtype, layer=None):
        return leaf_value(self.key, name, shape, dtype, layer).astype(
            jnp.float32)

    @functools.partial(jax.jit, static_argnums=(0,))
    def _layer_weights(self, layer):
        a, dt = self.a, self._dt
        qd, kd = a.heads * a.head_dim, a.kv_heads * a.head_dim
        shapes = {"norm1": ((a.d,), jnp.float32), "wq": ((a.d, qd), dt),
                  "wk": ((a.d, kd), dt), "wv": ((a.d, kd), dt),
                  "wo": ((qd, a.d), dt), "norm2": ((a.d,), jnp.float32),
                  "w_in": ((a.d, a.ff), dt), "w_gate": ((a.d, a.ff), dt),
                  "w_out": ((a.ff, a.d), dt)}
        if a.qkv_bias:
            shapes.update(bq=((qd,), jnp.float32), bk=((kd,), jnp.float32),
                          bv=((kd,), jnp.float32))
        return {k: self._leaf(f"blocks/0/{k}", s, t, layer)
                for k, (s, t) in shapes.items()}

    @functools.partial(jax.jit, static_argnums=(0, 1))
    def _table(self, name):
        return self._leaf(name, (self.a.vocab, self.a.d), self._dt)

    @functools.partial(jax.jit, static_argnums=(0,))
    def _block(self, x, w):
        a, bits = self.a, self.bits
        n, t, _ = x.shape
        h = rms_norm(x, w["norm1"], a.eps)
        q = quant_linear(h, w["wq"], bits)
        k = quant_linear(h, w["wk"], bits)
        v = quant_linear(h, w["wv"], bits)
        if a.qkv_bias:
            q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
        q = rope(q.reshape(n, t, a.heads, a.head_dim), a.theta)
        k = rope(k.reshape(n, t, a.kv_heads, a.head_dim), a.theta)
        v = v.reshape(n, t, a.kv_heads, a.head_dim)
        o = attention(q, k, v, a.window).reshape(n, t, -1)
        x = x + quant_linear(o, w["wo"], bits)
        h = rms_norm(x, w["norm2"], a.eps)
        f = jax.nn.silu(quant_linear(h, w["w_in"], bits)) \
            * quant_linear(h, w["w_gate"], bits)
        return x + quant_linear(f, w["w_out"], bits)

    def hidden(self, tokens: np.ndarray):
        """Final-norm hidden states (N, T, d) of a padded token batch."""
        with jax.default_matmul_precision("highest"):
            x = jnp.take(self._table("embed"), jnp.asarray(tokens), axis=0)
            for layer in range(self.a.layers):
                x = self._block(x, self._layer_weights(layer))
            final = self._leaf("final_norm", (self.a.d,), jnp.float32)
            return rms_norm(x, final, self.a.eps)

    def output_table(self):
        return self._table("embed" if self.a.tied else "lm_head")


@jax.jit
def _logits(rows, table):
    with jax.default_matmul_precision("highest"):
        return rows @ table.T


def row_logits(rows, table, chunk: int = 256):
    """Yield ``(start, logits)`` for blocks of ``chunk`` rows."""
    for s in range(0, rows.shape[0], chunk):
        blk = rows[s:s + chunk]
        pad = chunk - blk.shape[0]
        if pad:
            blk = jnp.pad(blk, ((0, pad), (0, 0)))
        yield s, _logits(blk, table)[:chunk - pad]
