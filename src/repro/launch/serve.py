"""Serving driver: a thin CLI over the ``repro.serving`` runtime.

Replays a synthetic (Poisson-arrival) request trace through the
continuous-batching scheduler: a fixed ``--batch``-slot decode batch whose
finished slots are backfilled from the FIFO admission queue, prefill on
admit (bucketed prompts), a persistent slot-indexed KV-cache pool, and one
jitted decode step that never recompiles as requests churn.  Prints
per-request TTFT/TPOT and aggregate tokens/sec; ``--sequential`` runs the
same trace one-request-at-a-time (a max_batch=1 scheduler) for an A/B
throughput comparison.  ``--paged`` swaps in the block-paged KV pool
(``--block-size`` / ``--num-blocks``): long-tail prompts reserve only
their own block need instead of worst-case slots, and sliding-window
architectures — which page unconditionally — serve as rings over their
block lists.  ``--prefix-cache`` (implies paged) attaches the trie prefix
index with copy-on-write sharing — pair it with ``--shared-prefix N`` to
give every synthetic prompt one N-token system prompt and watch warm
admits skip its prefill entirely.  ``--prefill-chunk N`` +
``--step-token-budget B`` interleave long-prompt prefill with decode
steps (chunked prefill: no step runs more than ``B`` prefill tokens, so
decode TPOT jitter stays bounded under long-prompt bursts), and
``--packed-prefill`` batches short queued prompts into one segment-masked
prefill call; the ``[chunked]`` line echoes p99 TPOT and chunk/pack
counters, and generations stay bit-identical to whole prefill.
``--speculative`` turns on self-speculative decoding: ``--draft-mode``
(default ``quant``) drafts ``--draft-k - 1`` tokens per round and the
serving mode verifies the whole run in one batched step; greedy
acceptance keeps generations bit-identical to plain decode in every
mode, and the ``[spec]`` line echoes acceptance counters.

**Multi-replica router** (``--replicas N``): instead of one scheduler,
``N`` independent engines — each its own device slice, mesh, KV pool,
and prefix trie — behind one ``serving.Router`` that owns the global
admission queue (``--queue-policy fifo|sjf``) and dispatches per request
with ``--router-policy``: ``round_robin``, ``least_loaded`` (fewest
queued+active, most free KV blocks), or ``prefix_affinity`` (leading
block-run hash pins repeat/system prefixes to the replica whose trie
holds them).  ``--kill-replica R:S`` injects a failure — replica ``R``
dies after router step ``S``, its in-flight requests drain back to the
front of the global queue (original arrival kept, ``n_migrations``
bumped) and it respawns over its surviving devices; migrated requests
restart from their prompt, so greedy outputs are bit-identical to an
undisturbed run.  Throughput is reported on the fleet clock (a round
costs its slowest replica — see ``serving.router``); the ``[router]``
line echoes the policy, per-replica tok/s, rebalanced requests, and
restarts.

CPU-runnable with ``--smoke``/``--preset``.  On multi-device runs the
driver enters the ``ElasticMesh`` (same policy as ``launch/train.py``);
the cache pool keeps its slot dim replicated while attention heads shard
over "model" (``dist.cache_pspecs(batch_over_dp=False)``), so admits stay
single-slot writes and steady-state decode never gathers the caches.
``--pim-mode`` threads a ``repro.pim.engine`` lowering mode through the
config (e.g. ``pim_sim`` decodes on the bit-accurate crossbar simulator,
whose persistent ``ExecutionSession`` uploads crossbar state once per
artifact and streams only operand columns per token; ``quant_tp`` decodes
on per-rank int8 Pallas tiles shard_mapped over the mesh "model" axis —
pair it with ``--model-parallel``).  ``--autotune`` switches the
``repro.pim.autotune`` planner on: under ``pim_sim`` the scheduler's
warmup plans every linear shape at the decode batch bucket (partition
model x crossbar geometry x chunking x backend, cost-model-scored, timed
tie-break) and decode runs the picks; ``--autotune-table PATH`` persists
the picks as JSON (format documented in ``benchmarks/check.py``) so the
next run reloads them instead of re-searching.  The ``[autotune]`` line
echoes table size, hit/miss/trial counters, and an example pick.
"""
from __future__ import annotations

import argparse
import contextlib
import os
import time

import jax

from repro.dist import context as dctx
from repro.dist import partitioning as dpart
from repro.launch.train import PRESETS, build_cfg
from repro.models import model_lib as M
from repro.models.layers import as_shapes
from repro.pim import autotune, engine
from repro.runtime.compile_cache import enable_compile_cache
from repro.runtime.fault_tolerance import ElasticMesh
from repro.serving import (FailurePlan, Router, RouterConfig, Scheduler,
                           ServingConfig, synthetic_requests)
from repro.serving.router import ROUTER_POLICIES


def place_params(params, cfg, mesh):
    """Lay ``params`` out over ``mesh`` as ``param_pspecs`` does (each
    weight split on the "model" axis the way quant_tp's tiles expect), so
    no step reshards the weights from the device they were made on."""
    return jax.device_put(params, dpart.tree_shardings(
        dpart.param_pspecs(as_shapes(M.param_specs(cfg)), mesh), mesh))


def serve_trace(params, cfg, requests, *, max_batch: int, prompt_bucket: int,
                mesh=None, paged: bool = False, block_size: int = 16,
                num_blocks=None, prefix_cache: bool = False,
                queue_policy: str = "fifo", autotune: bool = False,
                autotune_trials: int = 1, prefill_chunk=None,
                step_token_budget=None, packed_prefill: bool = False,
                speculative: bool = False, draft_mode: str = "quant",
                draft_k: int = 4):
    """Run a request trace through the scheduler; returns (results, summary)."""
    scfg = ServingConfig(max_batch=max_batch, prompt_bucket=prompt_bucket,
                         paged=paged, block_size=block_size,
                         num_blocks=num_blocks, prefix_cache=prefix_cache,
                         queue_policy=queue_policy, autotune=autotune,
                         autotune_trials=autotune_trials,
                         prefill_chunk=prefill_chunk,
                         step_token_budget=step_token_budget,
                         packed_prefill=packed_prefill,
                         speculative=speculative, draft_mode=draft_mode,
                         draft_k=draft_k)
    sched = Scheduler(params, cfg, scfg, mesh=mesh)
    for req in requests:
        sched.submit_request(req)
    results = sched.run()
    summary = sched.metrics.summary()
    summary["decode_traces"] = sched.decode_traces
    return results, summary


def serve_fleet(params, cfg, requests, *, scfg: ServingConfig,
                rcfg: RouterConfig, devices=None, failure_plan=None):
    """Run a trace through the multi-replica router on the fleet clock;
    returns (results, summary).  Request arrival times must be on the
    fleet clock (start at 0), not ``time.monotonic``."""
    router = Router(params, cfg, scfg, rcfg, devices=devices,
                    failure_plan=failure_plan)
    for req in requests:
        router.submit_request(req)
    results = router.run()
    summary = router.metrics().summary()
    summary["decode_traces"] = sum(
        r.sched.decode_traces for r in router.replicas if r.alive)
    return results, summary


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen1.5-0.5b")
    ap.add_argument("--preset", choices=list(PRESETS), default=None)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--batch", type=int, default=4,
                    help="decode slots (continuous-batching batch size)")
    ap.add_argument("--requests", type=int, default=16)
    ap.add_argument("--prompt-len", type=int, default=64,
                    help="max synthetic prompt length (lengths cycle "
                         "through ~{1/4, 1/2, 3/4, 1} of this)")
    ap.add_argument("--gen", type=int, default=32,
                    help="generation budget per request")
    ap.add_argument("--rate", type=float, default=0.0,
                    help="Poisson arrival rate in req/s (0: closed batch)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--pim-mode", choices=list(engine.MODES), default=None,
                    help="linear lowering; quant_tp shards per-rank int8 "
                         "Pallas tiles over the mesh 'model' axis "
                         "(set --model-parallel > 1)")
    ap.add_argument("--autotune", action="store_true",
                    help="plan crossbar GEMM configurations at warmup "
                         "(pim_sim: every linear shape at the decode batch "
                         "bucket; quant/quant_tp: race the two int8 "
                         "lowerings) and decode with the tuned picks")
    ap.add_argument("--autotune-table", default=None, metavar="PATH",
                    help="tuning-table JSON (format: benchmarks/check.py "
                         "header): loaded before warmup if it exists — "
                         "warmup then hits instead of re-searching — and "
                         "written back after the run")
    ap.add_argument("--autotune-trials", type=int, default=1,
                    help="timed trials per raced candidate during warmup")
    ap.add_argument("--model-parallel", type=int, default=1)
    ap.add_argument("--paged", action="store_true",
                    help="block-paged KV pool (admits reserve blocks from "
                         "a free list; long-tail prompts stop paying "
                         "worst-case reservation).  Sliding-window archs "
                         "page regardless of this flag")
    ap.add_argument("--block-size", type=int, default=16,
                    help="tokens per KV block (paged pool)")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="physical KV blocks (default: full parity with "
                         "the contiguous pool; smaller oversubscribes and "
                         "defers admissions under pressure)")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="trie prefix index over the paged pool with "
                         "refcounted copy-on-write block sharing; matched "
                         "prompt blocks skip prefill (implies --paged)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="split long-prompt prefill into chunks of this "
                         "many tokens interleaved with decode steps (a "
                         "--block-size multiple; implies --paged)")
    ap.add_argument("--step-token-budget", type=int, default=None,
                    help="max prefill tokens one scheduler step may "
                         "process (chunks + admissions); bounds decode "
                         "TPOT jitter under long-prompt bursts")
    ap.add_argument("--packed-prefill", action="store_true",
                    help="pack bursts of short queued prompts into one "
                         "segment-masked prefill call (implies --paged)")
    ap.add_argument("--speculative", action="store_true",
                    help="self-speculative decode: --draft-mode drafts "
                         "draft_k-1 tokens per round, the serving mode "
                         "verifies the run in one batched step; greedy "
                         "acceptance keeps outputs bit-identical")
    ap.add_argument("--draft-mode", choices=list(engine.MODES),
                    default="quant",
                    help="cheap lowering for the draft pass (share the "
                         "verify mode's per-row quantization — quant for "
                         "pim_sim/quant_tp — for ~100%% acceptance)")
    ap.add_argument("--draft-k", type=int, default=4,
                    help="verify width: tokens checked per verify step "
                         "(draft_k-1 drafted; 1 is plain decode)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="prepend one fixed N-token system prompt to every "
                         "synthetic request (the prefix-cache workload)")
    ap.add_argument("--sequential", action="store_true",
                    help="also run the trace one-request-at-a-time "
                         "(max_batch=1) for an A/B comparison")
    ap.add_argument("--replicas", type=int, default=1,
                    help="serving replicas behind the router; each gets "
                         "its own device slice, mesh, KV pool, and prefix "
                         "trie (1: single scheduler, no router)")
    ap.add_argument("--router-policy", choices=list(ROUTER_POLICIES),
                    default="least_loaded",
                    help="per-request dispatch policy (--replicas > 1)")
    ap.add_argument("--queue-policy", choices=["fifo", "sjf"],
                    default="fifo",
                    help="admission order, global queue and per-replica "
                         "backfill alike (sjf: shortest prompt first)")
    ap.add_argument("--kill-replica", default=None, metavar="R:S",
                    help="inject a failure: kill replica R after router "
                         "step S (drain-and-requeue, then respawn)")
    args = ap.parse_args()
    enable_compile_cache()

    fleet = args.replicas > 1
    mesh = None
    mesh_ctx = contextlib.nullcontext()
    if jax.device_count() > 1 and not fleet:
        mesh = ElasticMesh(model_parallel=args.model_parallel).make()
        print(f"[mesh] {dict(mesh.shape)} over {mesh.size} devices")
        mesh_ctx = dctx.use_mesh(mesh)

    cfg = build_cfg(args)
    if args.pim_mode:
        cfg = cfg.scaled(pim_mode=args.pim_mode)
    # reload persisted tuner picks before any scheduler warms up: warmup
    # then *hits* the table (counted in [autotune]) instead of re-searching
    if args.autotune_table and os.path.exists(args.autotune_table):
        n = autotune.load_table(args.autotune_table)
        print(f"[autotune] loaded {n} plan(s) from {args.autotune_table}")
    if args.autotune:
        autotune.enable(True)
    # right-size the cache pool: capacity = longest prompt + budget (decode
    # attention cost scales with pool capacity, not with tokens generated)
    cfg = cfg.scaled(max_seq_len=args.shared_prefix + args.prompt_len
                     + args.gen)
    params = M.init_params(cfg, jax.random.PRNGKey(args.seed))
    if mesh is not None:
        params = place_params(params, cfg, mesh)
    plens = sorted({max(1, args.prompt_len * f // 4) for f in (1, 2, 3, 4)})
    requests = synthetic_requests(
        args.requests, vocab_size=cfg.vocab_size, prompt_lens=plens,
        max_new_tokens=args.gen, rate=args.rate, seed=args.seed,
        # the router's FleetClock starts at 0; the plain scheduler runs
        # on time.monotonic
        start_time=0.0 if fleet else time.monotonic(),
        shared_prefix_len=args.shared_prefix)

    # recurrent blocks fold right-padding into their state: serve those
    # unbucketed (exact; one prefill compile per distinct prompt length)
    bucket = 1 if cfg.has_recurrent_blocks else max(8, args.prompt_len // 4)

    with mesh_ctx:
        if fleet:
            plan = None
            if args.kill_replica:
                r, s = args.kill_replica.split(":")
                plan = FailurePlan(kill_replica=int(r), at_step=int(s))
            scfg = ServingConfig(
                max_batch=args.batch, prompt_bucket=bucket,
                paged=args.paged, block_size=args.block_size,
                num_blocks=args.num_blocks, prefix_cache=args.prefix_cache,
                queue_policy=args.queue_policy, autotune=args.autotune,
                autotune_trials=args.autotune_trials,
                prefill_chunk=args.prefill_chunk,
                step_token_budget=args.step_token_budget,
                packed_prefill=args.packed_prefill,
                speculative=args.speculative, draft_mode=args.draft_mode,
                draft_k=args.draft_k)
            rcfg = RouterConfig(n_replicas=args.replicas,
                                policy=args.router_policy,
                                model_parallel=args.model_parallel)
            results, summary = serve_fleet(params, cfg, requests,
                                           scfg=scfg, rcfg=rcfg,
                                           failure_plan=plan)
        else:
            results, summary = serve_trace(
                params, cfg, requests, max_batch=args.batch,
                prompt_bucket=bucket, mesh=mesh, paged=args.paged,
                block_size=args.block_size, num_blocks=args.num_blocks,
                prefix_cache=args.prefix_cache,
                queue_policy=args.queue_policy, autotune=args.autotune,
                autotune_trials=args.autotune_trials,
                prefill_chunk=args.prefill_chunk,
                step_token_budget=args.step_token_budget,
                packed_prefill=args.packed_prefill,
                speculative=args.speculative, draft_mode=args.draft_mode,
                draft_k=args.draft_k)
        print(f"served {summary['n_finished']}/{summary['n_requests']} "
              f"requests, {summary['total_tokens']} tokens @ "
              f"{summary['tokens_per_s']:.0f} tok/s "
              f"(batch {args.batch}, {summary['decode_traces']} decode "
              f"compiles)")
        print(f"TTFT {summary['mean_ttft_s'] * 1e3:.0f}ms mean | "
              f"TPOT {summary['mean_tpot_s'] * 1e3:.1f}ms | "
              f"queue wait {summary['mean_queue_wait_s'] * 1e3:.0f}ms | "
              f"active slots {summary['mean_active_slots']:.1f}")
        if args.paged or args.prefix_cache or cfg.sliding_window:
            print(f"[pool] peak KV {summary['peak_kv_bytes'] / 1e6:.2f}MB "
                  f"(peak {summary['peak_pool_blocks']:.0f} blocks, "
                  f"occupancy {summary['mean_block_occupancy'] * 100:.0f}%, "
                  f"internal frag "
                  f"{summary['mean_internal_frag'] * 100:.0f}%, "
                  f"{summary['deferred_admits']} deferred admits)")
        if args.prefix_cache:
            print(f"[prefix] hit rate "
                  f"{summary['prefix_hit_rate'] * 100:.0f}% | "
                  f"{summary['prefix_tokens_reused']:.0f} prompt tokens "
                  f"served from the index | TTFT hit "
                  f"{summary['mean_ttft_hit_s'] * 1e3:.0f}ms vs miss "
                  f"{summary['mean_ttft_miss_s'] * 1e3:.0f}ms | "
                  f"{summary['peak_blocks_shared']:.0f} blocks shared, "
                  f"{summary['cow_copies']:.0f} COW copies")
        if (args.prefill_chunk or args.step_token_budget
                or args.packed_prefill):
            print(f"[chunked] p99 TPOT {summary['p99_tpot_s'] * 1e3:.1f}ms "
                  f"| {summary['prefill_chunks']} prefill chunks, "
                  f"{summary['packed_prefills']} packed prefills "
                  f"(chunk {args.prefill_chunk}, budget "
                  f"{args.step_token_budget})")
        if fleet:
            per = ", ".join(f"r{r}: {v:.0f}" for r, v in
                            sorted(summary["per_replica_tok_s"].items()))
            print(f"[router] {summary['router_policy']} over "
                  f"{args.replicas} replicas | per-replica tok/s {{{per}}} "
                  f"| {summary['rebalanced_requests']} rebalanced, "
                  f"{summary['replica_restarts']} restarts | "
                  f"queue {args.queue_policy}, p50 wait "
                  f"{summary['p50_queue_wait_s'] * 1e3:.0f}ms")
        if args.speculative and summary.get("spec_rounds", 0):
            print(f"[spec] draft {args.draft_mode} k={args.draft_k}: "
                  f"{summary['accepted_tokens']}/"
                  f"{summary['verified_tokens']} verified tokens accepted "
                  f"({summary['drafted_tokens']} drafted) | "
                  f"mean accept len {summary['mean_accept_len']:.2f} | "
                  f"{summary['accepted_per_step']:.2f} tok/verify step")
        if args.pim_mode == "pim_sim":
            info = engine.cache_info()
            print(f"[pim] crossbar uploads {info.exec_uploads}, "
                  f"weight-stationary session hits {info.exec_hits}")
        if args.autotune and args.pim_mode in ("quant", "quant_tp"):
            # the crossbar tuner has nothing to plan here; race the two
            # int8 linear lowerings instead (PR 5's bit-exact pair)
            autotune.autotune_linear(args.batch, cfg.d_model, cfg.d_model,
                                     trials=args.autotune_trials)
        if args.autotune or args.autotune_table:
            print(f"[autotune] {autotune.summary()}")
        if args.autotune_table:
            n = autotune.save_table(args.autotune_table)
            print(f"[autotune] saved {n} plan(s) to {args.autotune_table}")
        if args.pim_mode == "quant_tp" and mesh is not None:
            from repro.kernels.quant_matmul.tp import tile_summary

            r = mesh.shape.get("model", 1)
            if r > 1:
                for line in tile_summary(cfg, r):
                    print(f"[tp] {line} x{r} ranks")
            else:
                print("[tp] model axis is 1: quant_tp fell back to "
                      "single-rank quant (set --model-parallel > 1)")
        if args.sequential:
            # replay the same trace: keep relative arrival offsets so both
            # runs are gated by the identical Poisson process
            t0 = min(r.arrival_time for r in requests)
            base = time.monotonic()
            for req in requests:
                req.arrival_time = base + (req.arrival_time - t0)
            _, seq = serve_trace(params, cfg, requests, max_batch=1,
                                 prompt_bucket=bucket, mesh=mesh)
            speed = summary["tokens_per_s"] / max(seq["tokens_per_s"], 1e-9)
            print(f"sequential baseline: {seq['tokens_per_s']:.0f} tok/s "
                  f"-> continuous batching {speed:.2f}x")
        rid0 = min(results)
        print("sample generation:", results[rid0][:16].tolist())
    return results


if __name__ == "__main__":
    main()
