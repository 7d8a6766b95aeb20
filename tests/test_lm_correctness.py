"""Semantic invariants of the LM stack: decode==forward, SWA ring buffers,
MoE routing equivalence, flash==direct attention."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.models import model_lib as M
from repro.models.attention import direct_attention, flash_attention


def test_flash_matches_direct():
    rng = np.random.default_rng(0)
    q = jnp.asarray(rng.normal(size=(2, 100, 4, 16)).astype(np.float32))
    k = jnp.asarray(rng.normal(size=(2, 100, 2, 16)).astype(np.float32))
    v = jnp.asarray(rng.normal(size=(2, 100, 2, 16)).astype(np.float32))
    for window in (None, 17):
        a = flash_attention(q, k, v, causal=True, window=window,
                            block_q=32, block_k=32)
        b = direct_attention(q, k, v, causal=True, window=window)
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-4, atol=2e-5)


@pytest.mark.parametrize("name", ["qwen1.5-0.5b", "h2o-danube-1.8b",
                                  "jamba-v0.1-52b", "xlstm-1.3b",
                                  "llama-3.2-vision-11b"])
def test_decode_matches_forward(name):
    """prefill(x[:L]) + decode step == forward(x[:L+1]) last-token logits.

    capacity_factor is raised so MoE archs drop no tokens in either path
    (capacity drops are legitimate forward/decode divergence otherwise)."""
    cfg = C.get(name).smoke().scaled(capacity_factor=16.0)
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(1)
    L = 24
    toks = rng.integers(0, cfg.vocab_size, (2, L + 1))
    batch = {"tokens": jnp.asarray(toks[:, :L], jnp.int32)}
    if cfg.vision_dim:
        batch["patches"] = jnp.asarray(
            rng.normal(size=(2, cfg.n_patches, cfg.vision_dim)), jnp.float32)

    _, caches = M.prefill(params, batch, cfg)
    nxt = jnp.asarray(toks[:, L:L + 1], jnp.int32)
    _, logits_dec, _ = M.decode_step(params, nxt, jnp.int32(L), caches, cfg)

    batch_full = dict(batch, tokens=jnp.asarray(toks, jnp.int32))
    x = M._embed_in(params, batch_full["tokens"], cfg)
    memory = M._memory(params, batch_full, cfg)
    x, _ = M._decoder_stack(params, x, cfg,
                            positions=jnp.arange(L + 1), mode="train",
                            memory=memory)
    from repro.models.layers import rms_norm, unembed

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    logits_fwd = unembed(x[:, -1], M._unembed_table(params, cfg))
    logits_fwd = logits_fwd[:, :cfg.vocab_size]   # serving drops padding
    np.testing.assert_allclose(np.asarray(logits_dec),
                               np.asarray(logits_fwd), rtol=2e-3, atol=2e-3)


def test_swa_ring_buffer_long_decode():
    """Decoding past the window capacity must equal full-context SWA."""
    cfg = C.get("h2o-danube-1.8b").smoke()  # window 16
    params = M.init_params(cfg, jax.random.PRNGKey(2))
    rng = np.random.default_rng(2)
    total = 40  # > 2x window
    toks = rng.integers(0, cfg.vocab_size, (1, total))
    # path A: prefill 24, decode the rest step by step
    _, caches = M.prefill(params,
                          {"tokens": jnp.asarray(toks[:, :24], jnp.int32)}, cfg)
    logits = None
    for pos in range(24, total):
        tok = jnp.asarray(toks[:, pos:pos + 1], jnp.int32)
        _, logits, caches = M.decode_step(params, tok, jnp.int32(pos),
                                          caches, cfg)
    # path B: single forward over all tokens
    x = M._embed_in(params, jnp.asarray(toks, jnp.int32), cfg)
    x, _ = M._decoder_stack(params, x, cfg, positions=jnp.arange(total),
                            mode="train")
    from repro.models.layers import rms_norm, unembed

    x = rms_norm(x, params["final_norm"], cfg.norm_eps)
    want = unembed(x[:, -1],
                   M._unembed_table(params, cfg))[:, :cfg.vocab_size]
    np.testing.assert_allclose(np.asarray(logits), np.asarray(want),
                               rtol=2e-3, atol=2e-3)


def test_moe_routing_matches_dense_reference():
    """With ample capacity, the gather/scatter MoE equals the brute-force
    per-token expert sum."""
    from repro.models.moe import moe_ffn

    cfg = C.get("granite-moe-1b-a400m").smoke().scaled(capacity_factor=8.0)
    rng = np.random.default_rng(3)
    b, s, d = 2, 8, cfg.d_model
    e, f = cfg.n_experts, cfg.moe_d_ff
    x = jnp.asarray(rng.normal(size=(b, s, d)).astype(np.float32))
    params = {
        "router": jnp.asarray(rng.normal(size=(d, e)).astype(np.float32)),
        "w1": jnp.asarray(rng.normal(size=(e, d, f)).astype(np.float32)) * 0.1,
        "w2": jnp.asarray(rng.normal(size=(e, f, d)).astype(np.float32)) * 0.1,
        "w3": jnp.asarray(rng.normal(size=(e, d, f)).astype(np.float32)) * 0.1,
    }
    got = moe_ffn(x, params, cfg)

    xf = np.asarray(x).reshape(-1, d)
    logits = xf @ np.asarray(params["router"])
    top = np.argsort(-logits, axis=1)[:, :cfg.top_k]
    ref = np.zeros_like(xf)
    for t in range(xf.shape[0]):
        ws = np.exp(logits[t, top[t]] - logits[t, top[t]].max())
        ws = ws / ws.sum()
        for j, eid in enumerate(top[t]):
            h = (xf[t] @ np.asarray(params["w1"][eid]))
            h = h / (1 + np.exp(-h))  # silu
            h = h * (xf[t] @ np.asarray(params["w3"][eid]))
            ref[t] += ws[j] * (h @ np.asarray(params["w2"][eid]))
    np.testing.assert_allclose(np.asarray(got).reshape(-1, d), ref,
                               rtol=2e-3, atol=2e-3)


def test_mamba_state_continuity():
    """Mamba prefill state must continue exactly into decode."""
    from repro.models.ssm import mamba_mixer

    cfg = C.get("jamba-v0.1-52b").smoke()
    rng = np.random.default_rng(4)
    d = cfg.d_model
    params = M.init_params(cfg, jax.random.PRNGKey(4))
    p = jax.tree.map(lambda a: a[0], params["blocks"]["0"])  # first md block
    x = jnp.asarray(rng.normal(size=(1, 12, d)).astype(np.float32))
    y_full, _ = mamba_mixer(x, p, cfg, None)
    y_a, st = mamba_mixer(x[:, :8], p, cfg, None)
    y_b, _ = mamba_mixer(x[:, 8:], p, cfg, st)
    np.testing.assert_allclose(np.asarray(y_full[:, 8:]), np.asarray(y_b),
                               rtol=2e-3, atol=2e-4)


def test_int8_kv_cache_close_to_full_precision():
    """Quantized KV cache (serving optimization) stays within ~1% of bf16."""
    cfg = C.get("qwen1.5-0.5b").smoke().scaled(kv_cache_dtype="int8")
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(1)
    L = 24
    toks = rng.integers(0, cfg.vocab_size, (2, L + 1))
    batch = {"tokens": jnp.asarray(toks[:, :L], jnp.int32)}
    _, caches = M.prefill(params, batch, cfg)
    nxt = jnp.asarray(toks[:, L:L + 1], jnp.int32)
    _, lg_q, _ = M.decode_step(params, nxt, jnp.int32(L), caches, cfg)
    cfg2 = cfg.scaled(kv_cache_dtype="bf16")
    _, caches2 = M.prefill(params, batch, cfg2)
    _, lg_f, _ = M.decode_step(params, nxt, jnp.int32(L), caches2, cfg2)
    rel = np.abs(np.asarray(lg_q) - np.asarray(lg_f)).max() / (
        np.abs(np.asarray(lg_f)).max() + 1e-9)
    assert rel < 0.05
