"""The float32 reference against the program: prefill, then decode
through the paged cache via ``Scheduler``, for a qwen-shaped model (MHA
groups of 2, QKV bias, tied table) and a danube-shaped one (untied head, a
16-token sliding window that the requests run past)."""
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(os.path.dirname(BENCH)),
                                    "src")]

from reference.model import Reference, quant_linear, row_logits  # noqa
from servebench import check, spec, testing, weights  # noqa: E402

SEED = 2 ** 33 + 5
F32 = {"torch_dtype": "float32"}


def _cell(tmp_path, config):
    layout = testing.smoke_layout(str(tmp_path), config, loop="closed")
    return spec.load_cell("tiny-closed", layout)


def _serve(cell, prompts, new_tokens):
    from repro.serving import Scheduler

    cfg = spec.model_config(cell)
    params = weights.make_params(cfg, SEED)
    sched = Scheduler(params, cfg, spec.serving_config(cell))
    rids = [sched.submit(p, new_tokens) for p in prompts]
    out = sched.run()
    return cfg, params, [np.asarray(out[r]) for r in rids]


@pytest.mark.parametrize("config", [testing.TINY_QWEN,
                                    testing.TINY_DANUBE])
def test_prefill_logits_match(tmp_path, config):
    from repro.models import model_lib as M

    cell = _cell(tmp_path, dict(config, **F32))
    cfg = spec.model_config(cell)
    params = weights.make_params(cfg, SEED)
    toks = np.random.default_rng(0).integers(0, cfg.vocab_size, (1, 14))
    got, _ = M.prefill(params, {"tokens": jnp.asarray(toks)}, cfg)
    ref = Reference(cell.config, SEED)
    h = ref.hidden(np.pad(toks, ((0, 0), (0, 2))))[:, 13]
    (_, want), = row_logits(h, ref.output_table())
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-3, rtol=0)


@pytest.mark.parametrize("config", [testing.TINY_QWEN,
                                    testing.TINY_DANUBE])
def test_served_tokens_match_through_the_paged_cache(tmp_path, config):
    cell = _cell(tmp_path, dict(config, **F32))
    rng = np.random.default_rng(1)
    prompts = [rng.integers(0, 277, n).astype(np.int32) for n in (7, 16, 20)]
    cfg, _, served = _serve(cell, prompts, 12)
    longest = max(len(p) + len(s) for p, s in zip(prompts, served))
    if cfg.sliding_window:
        assert longest > cfg.sliding_window      # the ring wrapped
    nums = check.compare(cell.config, SEED, list(zip(prompts, served)))
    assert nums["served_tokens"] == 36
    assert nums["widest_gap"] < 1e-3


def test_quantized_linear_is_symmetric_int8():
    x = jnp.array([[1.0, -2.0, 0.5], [0.0, 0.0, 0.0]])
    w = jnp.array([[1.0, 0.0], [0.0, -4.0], [2.0, 1.0]])
    got = quant_linear(x, w, 8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(x @ w),
                               atol=0.05)
    assert np.all(np.asarray(got[1]) == 0)
    # 2 bits, qmax 1: row scale 2 gives x -> [0.5, -1, 0.25] -> [0, -1, 0]
    # (half rounds to even); column scales 2 and 4 give w -> [[0, 0], [0,
    # -1], [1, 0]]; the integer product [0, 1] times 2 x [2, 4] is [0, 8]
    np.testing.assert_array_equal(np.asarray(quant_linear(x, w, 2))[0],
                                  [0.0, 8.0])


def test_weights_are_remade_layer_by_layer(tmp_path):
    cell = _cell(tmp_path, testing.TINY_DANUBE)
    cfg = spec.model_config(cell)
    params = weights.make_params(cfg, SEED)
    key = weights.seed_key(SEED)
    wq = params["blocks"]["0"]["wq"]
    for layer in range(cfg.n_super):
        one = weights.leaf_value(key, "blocks/0/wq", wq.shape[1:], wq.dtype,
                                 layer)
        assert jnp.array_equal(one, wq[layer])
    head = params["lm_head"]
    assert jnp.array_equal(
        weights.leaf_value(key, "lm_head", (cfg.vocab_size, cfg.d_model),
                           head.dtype), head[:cfg.vocab_size])
    assert not jnp.any(head[cfg.vocab_size:])
    other = weights.make_params(cfg, SEED + 1)["blocks"]["0"]["wq"]
    assert not jnp.array_equal(other, wq)
    assert jax.tree.structure(params) == jax.tree.structure(
        weights.make_params(cfg, 3))
