"""Per-architecture smoke tests: reduced config, one loss/prefill/decode step
on CPU, asserting output shapes and finiteness (assigned-arch deliverable)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.models import model_lib as M

B, S = 2, 32


def _batch(cfg, rng):
    batch = {
        "tokens": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)),
                              jnp.int32),
        "labels": jnp.asarray(rng.integers(0, cfg.vocab_size, (B, S)),
                              jnp.int32),
    }
    if cfg.is_encoder_decoder:
        batch["frames"] = jnp.asarray(
            rng.normal(size=(B, S // cfg.audio_frames_div, cfg.d_model)),
            jnp.float32)
    if cfg.vision_dim:
        batch["patches"] = jnp.asarray(
            rng.normal(size=(B, cfg.n_patches, cfg.vision_dim)), jnp.float32)
    return batch


@pytest.mark.parametrize("name", C.ARCH_NAMES)
def test_smoke_loss_prefill_decode(name):
    cfg = C.get(name).smoke()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    rng = np.random.default_rng(0)
    batch = _batch(cfg, rng)

    loss = jax.jit(lambda p, b: M.loss_fn(p, b, cfg))(params, batch)
    assert np.isfinite(float(loss))
    assert 0.0 < float(loss) < 20.0

    logits, caches = jax.jit(lambda p, b: M.prefill(p, b, cfg))(params, batch)
    assert logits.shape == (B, cfg.vocab_size)   # padding rows dropped
    assert np.isfinite(np.asarray(logits)).all()

    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    nt, lg, caches2 = jax.jit(
        lambda p, t, pos, c: M.decode_step(p, t, pos, c, cfg))(
        params, tok, jnp.int32(S), caches)
    assert nt.shape == (B, 1)
    assert np.isfinite(np.asarray(lg)).all()
    assert jax.tree.structure(caches) == jax.tree.structure(caches2)


@pytest.mark.parametrize("name", C.ARCH_NAMES)
def test_full_config_param_specs(name):
    """The FULL configs are exercised shape-only (dry-run covers lowering)."""
    cfg = C.get(name)
    n = M.param_count(cfg)
    assert n > 1e8
    specs = M.param_specs(cfg)
    leaves = jax.tree.leaves(
        specs, is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
    assert all(len(l.shape) >= 1 for l in leaves)
    # vocab padding keeps the model-axis shardable
    assert cfg.padded_vocab % 16 == 0


def test_assigned_cell_matrix():
    """40 cells total; long_500k skips exactly the pure-full-attention archs."""
    from repro.models.config import SHAPES

    cells = [(a, s.name, C.get(a).runnable(s)[0])
             for a in C.ARCH_NAMES for s in SHAPES]
    assert len(cells) == 40
    skipped = {(a, s) for a, s, ok in cells if not ok}
    assert skipped == {
        ("granite-20b", "long_500k"), ("gemma-7b", "long_500k"),
        ("qwen1.5-0.5b", "long_500k"), ("granite-moe-1b-a400m", "long_500k"),
        ("arctic-480b", "long_500k"), ("seamless-m4t-medium", "long_500k"),
        ("llama-3.2-vision-11b", "long_500k"),
    }
