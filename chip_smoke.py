#!/usr/bin/env python3
"""Smoke run of the serving path on a TPU.

Drives the system once through the entry points a user calls, at the full
published width of qwen1.5-0.5b (24 layers, d_model 1024, vocab 151936,
random weights from a seed), and checks what comes out:

1. device line: platform, device kind and count, jax/jaxlib/libtpu
   versions, the compile-cache directory (informational);
2. both Pallas kernels compiled (``tpu_custom_call`` in their HLO) and
   bit-exact against their references: ``quant_matmul_int`` at the
   model's projection widths, ``crossbar_exec`` on a real MultPIM dot
   program;
3. ``launch.serve.serve_trace`` over the paged pool, batch 4, eight
   requests of 32-128 prompt tokens and 16 new tokens each, in ``xla``
   and in ``quant`` (the compiled int8 Pallas path): every request
   finishes in-vocabulary with one decode compile, and ``quant`` tokens
   equal the same requests served at ``max_batch=1``;
4. ``pim_sim`` at the smoke width: the ``jax.pure_callback`` host route
   called from a TPU program;
5. ``launch.train.main``: three full-width steps, batch 2, seq 128,
   finite loss.

``--four-chips`` runs only the multi-chip path: the same serve phase under
``quant_tp`` with the "model" axis over four chips, against single-rank
``quant`` on one chip, and requires identical tokens.

Every phase runs in this one process (a chip belongs to one process).  The
last line of stdout is ``{"ok": true, "device": {...}}`` only when every
phase passed; without a TPU, or when any phase fails, the script prints no
such line and exits non-zero.  Times it prints are one-run smoke readings
with compilation included, not benchmarks.

Run from the repository root:  python3 chip_smoke.py [--four-chips]
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time
import traceback

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

ARCH = "qwen1.5-0.5b"
SEED = 0
PROMPT_LENS = (32, 64, 96, 128)
N_REQUESTS = 8
GEN = 16
# (M, K, N) int8 GEMMs of a qwen1.5-0.5b step: decode (M=8) and prefill
# (M=512) tiles of the d_model -> {d_ff, 3*d_model} projections, and the
# d_ff -> d_model down projection
QUANT_SHAPES = ((8, 1024, 2816), (512, 1024, 2816), (8, 1024, 3072),
                (512, 1024, 3072), (8, 2816, 1024))


def log(msg: str) -> None:
    print(msg, flush=True)


def require(cond: bool, what: str) -> None:
    if not cond:
        raise RuntimeError(what)


def _has_kernel(jitted, *args, **kw) -> bool:
    return "tpu_custom_call" in jitted.lower(*args, **kw).compile().as_text()


def phase_device(jax) -> None:
    from importlib.metadata import PackageNotFoundError, version

    from repro.runtime.compile_cache import enable_compile_cache

    cache = enable_compile_cache()
    d = jax.devices()[0]
    try:
        libtpu = version("libtpu")
    except PackageNotFoundError:
        libtpu = "not installed"
    log(f"[device] {d.platform} {d.device_kind!r} x{len(jax.devices())} | "
        f"jax {jax.__version__} jaxlib {version('jaxlib')} libtpu {libtpu} "
        f"| compile cache {cache}")


def phase_kernels() -> None:
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels.crossbar_exec import crossbar_exec, crossbar_exec_ref
    from repro.kernels.quant_matmul import (quant_matmul_int,
                                            quant_matmul_int_ref)
    from repro.pim import engine
    from repro.pim import executor as ex

    rng = np.random.default_rng(SEED)
    for m, k, n in QUANT_SHAPES:
        x = jnp.asarray(rng.integers(-127, 128, (m, k), dtype=np.int8))
        w = jnp.asarray(rng.integers(-127, 128, (k, n), dtype=np.int8))
        require(_has_kernel(quant_matmul_int, x, w),
                f"quant_matmul_int {m}x{k}x{n}: no tpu_custom_call")
        got = np.asarray(quant_matmul_int(x, w))
        require(np.array_equal(got, np.asarray(quant_matmul_int_ref(x, w))),
                f"quant_matmul_int {m}x{k}x{n} differs from the reference")
        log(f"[kernels] quant_matmul_int {m}x{k}x{n}: compiled, exact")

    k_terms, rows = 8, 4096        # 4096 rows = 128 words: one lane tile
    dot = engine.compile_dot(k_terms, 8)
    x = rng.integers(0, 256, (rows, k_terms), dtype=np.uint64)
    w = rng.integers(0, 256, (rows, k_terms), dtype=np.uint64)
    state = ex.blank_state(1, dot.n_cols, rows)
    for i in range(k_terms):
        state = ex.write_numbers(state, dot.x_cols[i], x[None, :, i])
        state = ex.write_numbers(state, dot.w_cols[i], w[None, :, i])
    mc = jnp.asarray(dot.microcode, jnp.int32)
    require(_has_kernel(crossbar_exec, state, mc),
            "crossbar_exec: no tpu_custom_call")
    got = np.asarray(crossbar_exec(state, mc))
    require(np.array_equal(got, np.asarray(crossbar_exec_ref(state, mc))),
            "crossbar_exec differs from crossbar_exec_ref")
    acc = ex.read_numbers(got, dot.acc_cols, rows)[0]
    require(np.array_equal(acc, (x * w).sum(axis=1)),
            "crossbar_exec dot products are wrong")
    log(f"[kernels] crossbar_exec MultPIM dot K={k_terms} n_cols={dot.n_cols}"
        f" G={mc.shape[0]} over {rows} rows: compiled, bit-exact")


def _serve(params, cfg, label: str, *, max_batch: int, mesh=None,
           n: int = N_REQUESTS, prompt_lens=PROMPT_LENS, gen: int = GEN,
           bucket: int = 32):
    """Serve a seeded synthetic trace through ``serve_trace``; check it and
    return the generated tokens in request order."""
    import numpy as np

    from repro.launch.serve import serve_trace
    from repro.serving import synthetic_requests

    reqs = synthetic_requests(n, vocab_size=cfg.vocab_size,
                              prompt_lens=prompt_lens, max_new_tokens=gen,
                              seed=SEED, start_time=time.monotonic())
    t0 = time.perf_counter()
    results, s = serve_trace(params, cfg, reqs, max_batch=max_batch,
                             prompt_bucket=bucket, mesh=mesh, paged=True)
    wall = time.perf_counter() - t0
    toks = [np.asarray(results[r.rid]) for r in reqs]
    require(s["n_finished"] == n, f"{label}: {s['n_finished']}/{n} finished")
    require(all(len(t) == gen for t in toks),
            f"{label}: generation lengths {[len(t) for t in toks]}")
    require(all(((t >= 0) & (t < cfg.vocab_size)).all() for t in toks),
            f"{label}: a token outside the vocabulary")
    require(s["decode_traces"] == 1,
            f"{label}: {s['decode_traces']} decode traces, expected 1")
    log(f"[serve] {label}: {n}/{n} requests, {s['total_tokens']} tokens, "
        f"1 decode trace | smoke reading, one run, compiles included, not a "
        f"benchmark: TTFT mean {s['mean_ttft_s'] * 1e3:.1f} ms, TPOT mean "
        f"{s['mean_tpot_s'] * 1e3:.2f} ms, wall {wall:.1f} s")
    return toks


def _full_cfg():
    import repro.configs as configs

    return configs.get(ARCH).scaled(max_seq_len=max(PROMPT_LENS) + GEN)


def phase_serve(jax) -> None:
    import numpy as np

    from repro.models import model_lib as M

    cfg = _full_cfg()
    params = M.init_params(cfg, jax.random.PRNGKey(SEED))
    _serve(params, cfg.scaled(pim_mode="xla"), f"{ARCH} xla batch 4",
           max_batch=4)
    quant = cfg.scaled(pim_mode="quant")
    batched = _serve(params, quant, f"{ARCH} quant batch 4", max_batch=4)
    alone = _serve(params, quant, f"{ARCH} quant batch 1", max_batch=1)
    require(all(np.array_equal(a, b) for a, b in zip(batched, alone)),
            "quant tokens at batch 4 differ from batch 1")
    log("[serve] quant batch 4 tokens == batch 1 tokens")


def phase_pim_sim(jax) -> None:
    import repro.configs as configs
    from repro.models import model_lib as M
    from repro.pim import engine

    cfg = configs.get(ARCH).smoke().scaled(pim_mode="pim_sim",
                                           max_seq_len=16 + 4)
    params = M.init_params(cfg, jax.random.PRNGKey(SEED))
    _serve(params, cfg, f"{ARCH} smoke pim_sim batch 2", max_batch=2, n=2,
           prompt_lens=(8, 16), gen=4, bucket=8)
    info = engine.cache_info()
    require(info.exec_uploads > 0, "pim_sim never reached the crossbar "
            "simulator")
    log(f"[pim_sim] host callback ran: {info.exec_uploads} crossbar uploads, "
        f"{info.exec_hits} weight-stationary hits")


def phase_train() -> None:
    import math

    from repro.launch import train

    losses = train.main(["--arch", ARCH, "--steps", "3", "--batch", "2",
                         "--seq", "128", "--log-every", "1"])
    require(len(losses) == 3 and all(math.isfinite(v) for v in losses),
            f"train losses {losses}")
    log(f"[train] {ARCH} 3 steps: losses {[round(v, 4) for v in losses]}")


def phase_four_chips(jax) -> None:
    import numpy as np

    from repro.dist import context as dctx
    from repro.kernels.quant_matmul.tp import tile_summary
    from repro.launch.serve import place_params
    from repro.models import model_lib as M
    from repro.runtime.fault_tolerance import ElasticMesh

    require(len(jax.devices()) == 4,
            f"--four-chips needs 4 devices, found {len(jax.devices())}")
    cfg = _full_cfg()
    mesh = ElasticMesh(model_parallel=4).make()
    require(mesh.shape["model"] == 4, f"mesh {dict(mesh.shape)}")
    for line in tile_summary(cfg, 4):
        log(f"[tp] {line} x4 ranks")
    params = M.init_params(cfg, jax.random.PRNGKey(SEED))
    tp_params = place_params(params, cfg, mesh)
    with dctx.use_mesh(mesh):
        tp = _serve(tp_params, cfg.scaled(pim_mode="quant_tp"),
                    f"{ARCH} quant_tp model=4 batch 4", max_batch=4,
                    mesh=mesh)
    spans = {len(leaf.sharding.device_set)
             for leaf in jax.tree_util.tree_leaves(tp_params)}
    require(spans == {4}, f"weights span {spans} devices, expected 4")
    held = [(d.memory_stats() or {}).get("bytes_in_use")
            for d in jax.devices()]
    log(f"[tp] weights placed over all 4 devices; bytes in use per device "
        f"after quant_tp: {held}")
    single = _serve(params, cfg.scaled(pim_mode="quant"),
                    f"{ARCH} quant single-rank batch 4", max_batch=4)
    require(all(np.array_equal(a, b) for a, b in zip(tp, single)),
            "quant_tp tokens at model=4 differ from single-rank quant")
    log("[tp] quant_tp model=4 tokens == single-rank quant tokens")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only quant_tp over a 4-chip mesh against "
                         "single-rank quant")
    args = ap.parse_args(argv)

    import jax

    d = jax.devices()[0]
    if d.platform != "tpu":
        print(f"chip_smoke: needs a TPU, found {d.platform!r}",
              file=sys.stderr)
        return 1
    phase_device(jax)
    if args.four_chips:
        phases = [("four_chips", lambda: phase_four_chips(jax))]
    else:
        phases = [("kernels", phase_kernels),
                  ("serve", lambda: phase_serve(jax)),
                  ("pim_sim", lambda: phase_pim_sim(jax)),
                  ("train", phase_train)]
    failed = []
    for name, run in phases:
        t0 = time.perf_counter()
        try:
            run()
        except Exception:
            traceback.print_exc()
            failed.append(name)
            log(f"[{name}] FAILED after {time.perf_counter() - t0:.1f} s")
            continue
        log(f"[{name}] passed in {time.perf_counter() - t0:.1f} s")
    if failed:
        print(f"chip_smoke: failed phases {failed}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
