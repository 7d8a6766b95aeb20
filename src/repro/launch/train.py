"""End-to-end trainer: data -> jit'd train step -> checkpoint/resume.

Runs any registered arch at any scale (``--smoke`` for the reduced config,
``--preset 100m`` etc. for CPU-trainable sizes).  Fault tolerance: periodic
atomic checkpoints, auto-resume (``--resume``), stateless data indexing so
the token stream continues exactly where the failed run left off;
``--fail-at-step`` injects a crash for the restart test.  On multi-device
runs the mesh comes from ``ElasticMesh`` (degrades gracefully to whatever
devices are alive); single-device runs skip mesh machinery entirely.
"""
from __future__ import annotations

import argparse
import contextlib
import functools
import json
import time

import jax
import jax.numpy as jnp

import repro.configs as configs
from repro.data.pipeline import AudioStub, SyntheticLM, VisionStub
from repro.dist import context as dctx
from repro.dist import partitioning as dpart
from repro.models import model_lib as M
from repro.models.layers import as_shapes
from repro.optim.adamw import AdamWConfig, apply_updates, init_state
from repro.pim import engine
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.fault_tolerance import (CheckpointManager, ElasticMesh,
                                           StragglerMonitor)

PRESETS = {
    # (d_model, n_layers_mult, heads, kv, d_ff) scaled same-family configs
    "tiny": dict(d_model=64, n_heads=4, n_kv_heads=2, d_ff=128,
                 vocab_size=512, pad_vocab_multiple=8),
    "20m": dict(d_model=256, n_heads=8, n_kv_heads=4, d_ff=1024,
                vocab_size=4096, pad_vocab_multiple=64),
    "100m": dict(d_model=640, n_heads=10, n_kv_heads=5, d_ff=2560,
                 vocab_size=8192, pad_vocab_multiple=64),
}


def build_cfg(args):
    cfg = configs.get(args.arch)
    if args.smoke:
        cfg = cfg.smoke()
    if args.preset:
        kw = dict(PRESETS[args.preset])
        kw.update(n_layers=max(len(cfg.pattern), args.layers or 4),
                  dtype="float32", remat=False, loss_chunk=1 << 30)
        if cfg.n_experts:
            kw.update(n_experts=8, top_k=2, moe_d_ff=kw["d_ff"] // 4)
        if cfg.n_encoder_layers:
            kw.update(n_encoder_layers=2)
        if cfg.vision_dim:
            kw.update(vision_dim=64, n_patches=16)
        if cfg.family == "ssm":
            kw.update(n_kv_heads=kw["n_heads"])
        cfg = cfg.scaled(**kw)
    return cfg


def main(argv=None):
    """Train from command-line arguments (``argv``, default ``sys.argv``);
    returns the per-step losses."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--preset", choices=list(PRESETS), default=None)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    ap.add_argument("--lr", type=float, default=3e-4)
    ap.add_argument("--ckpt-dir", default=None)
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--resume", action="store_true")
    ap.add_argument("--fail-at-step", type=int, default=None)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--log-every", type=int, default=10)
    ap.add_argument("--metrics-out", default=None)
    ap.add_argument("--model-parallel", type=int, default=1,
                    help="tensor-parallel degree on multi-device runs "
                         "(degraded automatically if devices don't divide)")
    ap.add_argument("--pim-mode", choices=list(engine.MODES), default=None,
                    help="repro.pim.engine lowering for every linear "
                         "(threaded through ModelConfig.pim_mode); quant_tp "
                         "shards int8 tiles over the 'model' axis and "
                         "trains via its straight-through custom_vjp")
    ap.add_argument("--autotune", action="store_true",
                    help="before training, print the partition autotuner's "
                         "cost-model report for every linear shape in the "
                         "model (picked configuration vs the engine "
                         "default; no timed trials)")
    args = ap.parse_args(argv)
    enable_compile_cache()

    # Single-device runs skip mesh machinery entirely; multi-device runs get
    # the largest valid (pod, data, model) mesh from whatever is alive.
    mesh = None
    mesh_ctx = contextlib.nullcontext()
    if jax.device_count() > 1:
        mesh = ElasticMesh(model_parallel=args.model_parallel).make()
        print(f"[mesh] {dict(mesh.shape)} over {mesh.size} devices")
        mesh_ctx = dctx.use_mesh(mesh)

    cfg = build_cfg(args)
    if args.pim_mode:
        cfg = cfg.scaled(pim_mode=args.pim_mode)
    if args.autotune:
        # cost-model report: for every distinct linear shape in the model,
        # what configuration would the partition autotuner pick, and what
        # does the cost model predict it buys over the engine default?
        # Pure prediction (trials=0) — nothing here touches the simulator.
        from repro.pim import autotune

        mode = cfg.pim_mode or "raw"
        tokens = args.batch * args.seq
        shapes = sorted({tuple(map(int, s.shape)) for s in
                         jax.tree_util.tree_leaves(
                             as_shapes(M.param_specs(cfg)))
                         if len(s.shape) == 2})
        print(f"[autotune] cost-model report, {len(shapes)} linear "
              f"shape(s) at {tokens} tokens ({mode}):")
        for k_dim, o in shapes:
            plan = autotune.autotune(k_dim, 8, (tokens, o), mode, trials=0)
            dflt = autotune.default_plan(k_dim, 8, (tokens, o), mode)
            gain = dflt.predicted_us / max(plan.predicted_us, 1e-9)
            print(f"[autotune]   K={k_dim:5d} O={o:5d} -> "
                  f"model={plan.model} n_cols={plan.n_cols} "
                  f"chunk={plan.chunk} ({gain:.2f}x vs default predicted)")
    ocfg = AdamWConfig(lr_peak=args.lr, warmup_steps=max(args.steps // 20, 5),
                       total_steps=args.steps)
    data = SyntheticLM(cfg.vocab_size, args.seq, args.batch, seed=args.seed)
    audio = AudioStub(cfg.d_model, args.seq // cfg.audio_frames_div) \
        if cfg.is_encoder_decoder else None
    vision = VisionStub(cfg.vision_dim, cfg.n_patches) if cfg.vision_dim \
        else None

    if mesh is not None:
        # ZeRO-3 init: jit the initializers under fsdp=True out-shardings so
        # parameters and optimizer state materialize directly onto their
        # shards — host/device memory is bounded by the *sharded* model size,
        # never the replicated one.
        pshapes = as_shapes(M.param_specs(cfg))
        p_part = dpart.param_pspecs(pshapes, mesh, fsdp=True)
        p_shard = dpart.tree_shardings(p_part, mesh)
        params = jax.jit(lambda k: M.init_params(cfg, k),
                         out_shardings=p_shard)(jax.random.PRNGKey(args.seed))
        o_part = dpart.opt_state_pspecs(
            pshapes, p_part, jax.eval_shape(lambda: init_state(ocfg, pshapes)),
            mesh)
        o_shard = dpart.tree_shardings(o_part, mesh)
        opt_state = jax.jit(lambda p: init_state(ocfg, p),
                            out_shardings=o_shard)(params)
    else:
        params = M.init_params(cfg, jax.random.PRNGKey(args.seed))
        opt_state = init_state(ocfg, params)
    start_step = 0
    manager = None
    if args.ckpt_dir:
        manager = CheckpointManager(args.ckpt_dir, every_steps=args.ckpt_every)
        if args.resume:
            step, tree, meta = manager.resume({"p": params, "o": opt_state})
            if step is not None:
                params, opt_state = tree["p"], tree["o"]
                start_step = step
                print(f"[resume] restored step {step}")

    # donate params/opt_state through apply_updates: the updated trees alias
    # the old buffers, so a ZeRO-3 run never holds two copies of the state
    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def train_step(params, opt_state, batch):
        loss, grads = jax.value_and_grad(
            lambda p: M.loss_fn(p, batch, cfg))(params)
        params, opt_state, metrics = apply_updates(ocfg, params, grads,
                                                   opt_state)
        return params, opt_state, loss, metrics

    monitor = StragglerMonitor()
    losses = []
    metrics_f = open(args.metrics_out, "a") if args.metrics_out else None
    # The active mesh is read at trace time, so the whole stepping loop sits
    # inside the context; the in-model sharding constraints do the rest.
    with mesh_ctx:
        for step in range(start_step, args.steps):
            if args.fail_at_step is not None and step == args.fail_at_step:
                raise RuntimeError(f"injected failure at step {step}")
            t0 = time.time()
            batch = {k: jnp.asarray(v) for k, v in data.batch_at(step).items()}
            if audio:
                batch["frames"] = jnp.asarray(audio.batch_at(step, args.batch))
            if vision:
                batch["patches"] = jnp.asarray(
                    vision.batch_at(step, args.batch))
            params, opt_state, loss, metrics = train_step(params, opt_state,
                                                          batch)
            loss = float(loss)
            losses.append(loss)
            dt = time.time() - t0
            slow = monitor.record(dt)
            if step % args.log_every == 0 or step == args.steps - 1:
                print(f"step {step:5d} loss {loss:.4f} "
                      f"lr {float(metrics['lr']):.2e} "
                      f"gnorm {float(metrics['grad_norm']):.2f} {dt*1e3:.0f}ms"
                      + (" [straggler]" if slow else ""))
            if metrics_f:
                metrics_f.write(json.dumps({"step": step, "loss": loss,
                                            "dt_s": dt}) + "\n")
            if manager:
                manager.maybe_save(step + 1, {"p": params, "o": opt_state},
                                   metadata={"arch": cfg.name,
                                             "seq": args.seq,
                                             "batch": args.batch})
    if manager:
        manager.save(args.steps, {"p": params, "o": opt_state},
                     metadata={"arch": cfg.name, "final": True})
    if metrics_f:
        metrics_f.close()
    if losses:
        print(f"final loss {losses[-1]:.4f} (first {losses[0]:.4f})")
    else:  # --resume on an already-finished run: nothing left to step
        print(f"nothing to do: resumed at step {start_step} of {args.steps}")
    return losses


if __name__ == "__main__":
    main()
