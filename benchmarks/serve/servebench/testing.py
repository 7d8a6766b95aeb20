"""Small cells for the CPU tests, assembled from files by name only.

``smoke_layout`` writes a ``BENCHMARK.json``, a configuration, a traffic
mix and a cell into a directory, exactly as a later cell would be added,
and returns the :class:`~servebench.spec.Layout` that finds them.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Optional

from servebench.spec import Layout

E2E = [
    {"name": "ttft_p50_ms", "unit": "ms", "better": "lower", "bound": 0.1,
     "source": "host_clock", "workloads": ["tiny-open"]},
    {"name": "itl_p95_ms", "unit": "ms", "better": "lower", "bound": 0.1,
     "source": "host_clock", "workloads": ["tiny-open"]},
    {"name": "tokens_per_s", "unit": "tokens/s", "better": "higher",
     "bound": 0.1, "source": "host_clock", "workloads": ["tiny-closed"]},
    {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.25,
     "source": "host_clock"},
]
PER_LAYER = [
    {"name": "queue_wait_p50_ms", "unit": "ms", "better": "lower",
     "source": "program_counter", "layer": "scheduler",
     "moves": "ttft_p50_ms", "workloads": ["tiny-open"]},
    {"name": "decode_step_ms.open", "unit": "ms", "better": "lower",
     "source": "host_clock", "layer": "model step", "moves": "itl_p95_ms",
     "workloads": ["tiny-open"]},
    {"name": "prefill_step_share", "unit": "%", "better": "lower",
     "source": "host_clock", "layer": "scheduler",
     "moves": "tokens_per_s", "workloads": ["tiny-closed"]},
]

#: a qwen-shaped model at a CPU-test size: GQA, QKV bias, tied table
TINY_QWEN = {
    "name": "tiny-qwen", "source": "test", "registry": "qwen1.5-0.5b",
    "hidden_size": 64, "intermediate_size": 128, "num_hidden_layers": 2,
    "num_attention_heads": 4, "num_key_value_heads": 2, "head_dim": 16,
    "vocab_size": 277, "rope_theta": 1000000.0, "rms_norm_eps": 1e-06,
    "hidden_act": "silu", "tie_word_embeddings": True,
    "use_sliding_window": False, "torch_dtype": "bfloat16",
    "qkv_bias": True, "reduced": [],
}
#: a danube-shaped one: untied head, no bias, a 16-token sliding window
TINY_DANUBE = dict(TINY_QWEN, name="tiny-danube",
                   registry="h2o-danube-1.8b", tie_word_embeddings=False,
                   qkv_bias=False, use_sliding_window=True,
                   sliding_window=16)


def smoke_layout(root: str, config: Dict, loop: str = "open",
                 chips: int = 1, pim_mode: str = "quant",
                 mesh: Optional[Dict[str, int]] = None) -> Layout:
    """Write a one-config, one-cell benchmark under ``root``; the cell is
    ``tiny-open`` or ``tiny-closed`` by ``loop``, on ``chips`` devices, its
    linears lowered by ``pim_mode``, over ``mesh`` where given.  Prompts of
    4-24 tokens and answers of 2-12 fit a 48-token horizon; a 16-token
    window wraps."""
    bench_dir = os.path.join(root, "bench")
    for sub in ("configs", "traffic", "cells"):
        os.makedirs(os.path.join(bench_dir, sub), exist_ok=True)
    cell = f"tiny-{loop}"
    mix = {"loop": loop, "strata": 16,
           "prompt_len": {"dist": "lognormal", "median": 12, "sigma": 0.5,
                          "min": 4, "max": 24},
           "output_len": {"dist": "lognormal", "median": 6, "sigma": 0.5,
                          "min": 2, "max": 12}}
    if loop == "open":
        mix.update(rate_per_s=12.0)
    else:
        mix.update(concurrency=6)
    files = {
        "configs/tiny.json": config,
        f"traffic/tiny-{loop}.json": mix,
        f"cells/{cell}.json": {
            "pim_mode": pim_mode, "max_seq_len": 48,
            "serving": {"max_batch": 4, "prompt_bucket": 16, "paged": True,
                        "block_size": 8},
            "check": {"sample": 4, "widest_gap": 0.5}},
    }
    if mesh is not None:
        files[f"cells/{cell}.json"]["mesh"] = mesh
    for rel, obj in files.items():
        with open(os.path.join(bench_dir, rel), "w") as f:
            json.dump(obj, f)
    bench = {
        "command": ["python3", "bench/serve_bench.py"], "paths": ["bench"],
        "run_seconds": 2,
        "configs": [{"name": "tiny", "source": "test",
                     "file": "bench/configs/tiny.json", "reduced": [],
                     "why": "test"}],
        "workloads": [{"name": cell, "config": "tiny",
                       "traffic": f"tiny-{loop}", "chips": chips,
                       "why": "test"}],
        "end_to_end": E2E, "per_layer": PER_LAYER,
    }
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)
    return Layout(root=root, bench_dir=bench_dir)
