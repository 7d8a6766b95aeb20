"""One run of one cell: set up, warm up, measure, check, report.

``serve_bench.py`` is the command; this module does the work so tests can
drive a run at a small size on the CPU (``require_chip=False``).
"""
from __future__ import annotations

import argparse
import contextlib
import dataclasses
import gc
import json
import shutil
import sys
import tempfile
import time
from typing import Dict, List, Optional, Tuple

import numpy as np

from servebench import check, flops, spec, traffic, trace, weights
from servebench.window import RequestRecord, RunRecord, StepRecord

#: an open loop waits this long past the window's close for the first
#: token of every request that was due in it
GRACE_S = 60.0
COMPILE_EVENTS = ("/jax/core/compile/backend_compile_duration",
                  "/jax/compilation_cache/cache_hits")


@dataclasses.dataclass
class Context:
    """What a metric reader (``metrics/<name>.py``) gets."""

    cell: spec.Cell
    cfg: object                  # the program's ModelConfig
    scfg: object                 # the program's ServingConfig
    run: RunRecord
    setup_s: float
    admits: Dict[int, Tuple[float, Optional[float]]]   # rid -> (arrival,
    #                              admit) as the scheduler counted them
    trace: Optional[trace.TraceSummary]
    peaks: Optional[Dict[str, float]]


class CompileCounter:
    """Programs compiled or loaded from the persistent cache while armed."""

    def __init__(self):
        import jax

        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, duration, **kw):
        self._event(event)

    def _event(self, event, **kw):
        if self.armed and event in COMPILE_EVENTS:
            self.count += 1


def _mesh(mesh):
    """The program's mesh context, or none for a cell without a mesh."""
    if mesh is None:
        return contextlib.nullcontext()
    from repro.dist import context as dctx

    return dctx.use_mesh(mesh)


def _span(name: str, on: bool):
    if not on:
        return contextlib.nullcontext()
    import jax

    return jax.profiler.TraceAnnotation(name)


class Driver:
    """Feeds a traffic plan to ``Scheduler.step()`` and records every
    token on the benchmark's clock."""

    clock = staticmethod(time.monotonic)

    def __init__(self, sched, plan: traffic.Plan, window: Optional[int],
                 spans: bool):
        self.sched = sched
        self.plan = plan
        self.window = window
        self.spans = spans
        self.run = RunRecord(open=0.0, close=0.0)
        self.next = 0
        self.late: List[float] = []
        self.drain_steps = 0
        self.drain_left = 0

    def submit(self, i: int, due: float) -> None:
        with _span("bench.submit", self.spans):
            self.late.append(self.clock() - due)
            rid = self.sched.submit(self.plan.prompt(i),
                                    int(self.plan.output_lens[i]),
                                    arrival_time=due)
        self.run.requests[rid] = RequestRecord(
            index=i, due=due, prompt_len=int(self.plan.prompt_lens[i]),
            output_len=int(self.plan.output_lens[i]))

    def step(self) -> None:
        with _span("bench.step", self.spans):
            start = self.clock()
            out = self.sched.step()
            end = self.clock()
        with _span("bench.bookkeeping", self.spans):
            rec = StepRecord(start=start, end=end, prefill_lens=[],
                             decode_rows=0)
            done = []
            for rid, tok in out:
                r = self.run.requests[rid]
                if r.tokens:
                    rec.decode_rows += 1
                    ctx = r.prompt_len + len(r.tokens)
                    rec.decode_ctx += min(ctx, self.window) if self.window \
                        else ctx
                else:
                    rec.prefill_lens.append(r.prompt_len)
                r.tokens.append(int(tok))
                r.times.append(end)
                if len(r.tokens) == r.output_len:
                    done.append(rid)
            self.run.steps.append(rec)
        if self.plan.loop == "closed" and end < self.run.close:
            for _ in done:
                if self.next < len(self.plan):
                    self.submit(self.next, end)
                    self.next += 1

    def _idle(self) -> bool:
        return self.sched.n_active == 0 and len(self.sched.queue) == 0

    def window_loop(self, seconds: float) -> None:
        plan, run = self.plan, self.run
        run.open = self.clock()
        run.close = run.open + seconds
        if plan.loop == "closed":
            for _ in range(min(plan.concurrency, len(plan))):
                self.submit(self.next, run.open)
                self.next += 1
        while True:
            now = self.clock()
            if now >= run.close:
                break
            if plan.loop == "open":
                while (self.next < len(plan)
                       and run.open + plan.arrivals[self.next] <= now):
                    self.submit(self.next,
                                run.open + float(plan.arrivals[self.next]))
                    self.next += 1
            if self._idle():
                until = run.close
                if plan.loop == "open" and self.next < len(plan):
                    until = min(until,
                                run.open + float(plan.arrivals[self.next]))
                with _span("bench.wait", self.spans):
                    time.sleep(max(0.0, until - self.clock()))
                continue
            self.step()
        run.loop_end = self.clock()

    def drain(self) -> None:
        """Open loop: submit what fell due as the window closed, then step
        until every request due in the window has its first token, for up
        to ``GRACE_S`` of serving (a traced run writes its trace first)."""
        run = self.run
        if self.plan.loop != "open":
            return
        while self.next < len(self.plan):
            self.submit(self.next,
                        run.open + float(self.plan.arrivals[self.next]))
            self.next += 1
        deadline = max(run.close, self.clock()) + GRACE_S
        while (any(not r.times for r in run.due_in_window())
               and self.clock() < deadline and not self._idle()):
            self.step()
            self.drain_steps += 1
        run.drain_end = self.clock()
        self.drain_left = self.sched.n_active + len(self.sched.queue)


def warm_up(sched, mix: Dict, cfg, scfg) -> int:
    """Run one short request at every prefill length the mix can draw, so
    each program the window uses is compiled (or loaded) now."""
    rng = np.random.default_rng(0)
    buckets = traffic.prompt_buckets(mix, scfg.prompt_bucket,
                                     cfg.sliding_window or 0)
    for b in buckets:
        sched.submit(rng.integers(0, cfg.vocab_size, b, dtype=np.int32), 2)
    sched.run()
    return len(buckets)


def _parse(argv):
    ap = argparse.ArgumentParser(
        description="Serve one benchmark cell on the chip and print its "
                    "metrics as the last line of standard output.")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="put the 4-bit control in the program's place: its "
                         "gap is the number compared, so the run reads "
                         "correct false (calibration and tests only)")
    return ap.parse_args(argv)


def _admits(sched, rids) -> Dict[int, Tuple[float, Optional[float]]]:
    reqs = getattr(getattr(sched, "metrics", None), "requests", {})
    return {rid: (reqs[rid].arrival_time, reqs[rid].admit_time)
            for rid in rids if rid in reqs}


def _log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def main(argv=None, *, started: float, layout: Optional[spec.Layout] = None,
         require_chip: bool = True) -> int:
    args = _parse(argv)
    cell = spec.load_cell(args.workload, layout)
    layout = layout or spec.Layout()

    import jax

    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    devs = jax.devices()
    marks = [("jax and devices", time.monotonic())]
    kind = devs[0].device_kind
    if require_chip and (devs[0].platform != "tpu" or len(devs) < cell.chips):
        _log(f"serve_bench: {args.workload} needs {cell.chips} TPU chip(s); "
             f"JAX found {len(devs)} {devs[0].platform} device(s)")
        return 2
    pk = flops.peaks(kind) if require_chip else None

    from repro.serving import Scheduler

    cfg = spec.model_config(cell)
    scfg = spec.serving_config(cell)
    mesh = spec.make_mesh(cell, devs)
    params = weights.make_params(cfg, args.seed, mesh=mesh)
    jax.block_until_ready(params)
    marks.append(("weights", time.monotonic()))
    with _mesh(mesh):
        sched = Scheduler(params, cfg, scfg, mesh=mesh)
        plan = traffic.make_plan(cell.traffic, args.seed, cfg.vocab_size,
                                 args.seconds)
        marks.append(("scheduler and plan", time.monotonic()))
        n_warm = warm_up(sched, cell.traffic, cfg, scfg)
        marks.append(("warm-up", time.monotonic()))
        counter = CompileCounter()
        drv = Driver(sched, plan, cfg.sliding_window, spans=bool(args.trace))

        tdir = None
        if args.trace:
            tdir = tempfile.mkdtemp(prefix="serve_bench_trace_")
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(tdir, profiler_options=opts)
        counter.armed = True
        setup_s = time.monotonic() - started
        with _span("bench.window", bool(args.trace)):
            drv.window_loop(args.seconds)
        counter.armed = False
        if args.trace:      # the drain serves untraced
            t_stop = time.monotonic()
            jax.profiler.stop_trace()
            _log(f"trace written in {time.monotonic() - t_stop:.1f} s")
        drv.drain()
    run = drv.run
    weight_devices = {len(leaf.sharding.device_set)
                      for leaf in jax.tree_util.tree_leaves(params)}
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs[:cell.chips])
    admits = _admits(sched, list(run.requests))
    if plan.loop == "open":
        due = run.due_in_window()
        failed = sum(1 for r in due if not r.times)
    else:       # a closed loop's requests in flight at the close are not
        due = [r for r in run.requests.values() if r.due < run.close]
        failed = 0      # failures: each was sent when its client was free
    finished = [r for r in run.requests.values()
                if len(r.tokens) == r.output_len]
    del sched, params
    gc.collect()

    summary = None
    if tdir is not None:
        t_read = time.monotonic()
        summary = trace.load(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        _log(f"trace read in {time.monotonic() - t_read:.1f} s")
    if summary is not None and summary.cut is not None:
        _log(f"trace cut at {summary.window_s:.6f} s of the "
             f"{summary.cut.opened[1] - summary.cut.opened[0]:.6f}-s window, "
             f"where the device's record ends: {summary.cut.steps_kept} of "
             f"{summary.cut.steps} steps kept")

    chk = cell.cell["check"]
    picked = check.sample(finished, args.seed, int(chk["sample"]))
    t_ref = time.monotonic()
    seqs = [(plan.prompt(r.index), np.asarray(r.tokens)) for r in picked]
    nums = check.compare(cell.config, args.seed, seqs,
                         control=bool(args.control),
                         reference_dir=layout.reference_dir) if seqs else {}
    _log(f"reference over {len(picked)} requests, "
         f"{nums.get('served_tokens', 0)} served tokens, longest "
         f"{max((r.prompt_len + r.output_len for r in picked), default=0)}"
         f" positions: {time.monotonic() - t_ref:.1f} s")
    if args.control and nums:
        _log(f"program widest_gap {nums['widest_gap']!r}; the 4-bit "
             f"control's gap is compared in its place")
    checks = {
        "widest_gap": {"value": nums.get("control_gap" if args.control
                                         else "widest_gap"),
                       "limit": float(chk["widest_gap"])},
        "compiles_in_window": {"value": counter.count, "limit": 0},
        "requests_never_answered": {"value": failed, "limit": 0},
    }
    correct = all(c["value"] is not None and c["value"] <= c["limit"]
                  for c in checks.values())

    readable = summary
    if summary is not None and not summary.readable:
        _log(f"the cut keeps {100 * summary.kept_share:.1f}% of the window, "
             f"under {100 * trace.MIN_SHARE:.0f}%: no reader reads the trace")
        readable = None
    ctx = Context(cell=cell, cfg=cfg, scfg=scfg, run=run, setup_s=setup_s,
                  admits=admits, trace=readable, peaks=pk)
    metrics = {}
    for m in (cell.per_layer if args.trace else cell.end_to_end):
        value = layout.metric_reader(m["name"])(ctx, m["name"])
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": devs[0].platform, "kind": kind, "count": len(devs),
              "memory_peak_bytes": int(peak)}
    result = {"correct": correct, "attempted": len(due), "failed": failed,
              "metrics": metrics, "device": device}
    if summary is not None:
        device.update(busy_s=summary.busy_s, window_s=summary.window_s)
        result["breakdown"] = {"device_ops": summary.top_ops(),
                               "idle_gaps": summary.idle_gaps()}
        cut = summary.cut
        result["trace_cut"] = None if cut is None else {
            "kept_s": summary.window_s,
            "window_s": cut.opened[1] - cut.opened[0],
            "steps_kept": cut.steps_kept, "steps": cut.steps}
    result["checks"] = checks
    for rid, r in run.requests.items():
        if plan.loop == "open" and run.open <= r.due < run.close \
                and not r.times:
            _log(f"never answered: request {r.index} due at "
                 f"{r.due - run.open:.3f} s, prompt {r.prompt_len}, "
                 f"output {r.output_len}, admitted at "
                 f"{admits.get(rid, (None, None))[1]!r}")
    if plan.loop == "open":
        _log("ttft and queue wait by request (s): " + " ".join(
            f"{r.index}:{(r.times[0] - r.due) if r.times else -1:.3f}/"
            f"{(admits[rid][1] or 0) - admits[rid][0]:.3f}"
            for rid, r in run.requests.items() if rid in admits))
        _log(f"drain: {run.drain_end - run.close:.3f} s past the close, "
             f"{drv.drain_steps} steps, {drv.drain_left} left active or "
             f"queued")
    slow = sorted(run.loop_steps(), key=lambda s: s.start - s.end)[:5]
    _log("longest steps in window (s at s): " + " ".join(
        f"{s.end - s.start:.3f}@{s.start - run.open:.1f}" for s in slow))
    late = sorted(drv.late)
    t, phases = started, []
    for name, at in marks + [("to the window", started + setup_s)]:
        phases.append(f"{name} {at - t:.2f}")
        t = at
    _log("setup by phase (s): " + ", ".join(phases))
    if mesh is not None:
        _log(f"mesh {dict(mesh.shape)}; weight leaves span "
             f"{sorted(weight_devices)} devices")
    _log(f"{args.workload} seed {args.seed}: {len(run.steps)} steps, "
         f"{len(run.requests)} requests, warm-up buckets {n_warm}, setup "
         f"{setup_s:.2f} s, generator late p50 "
         f"{late[len(late) // 2] * 1e3 if late else 0:.2f} ms max "
         f"{late[-1] * 1e3 if late else 0:.2f} ms")
    for name, c in checks.items():
        _log(f"check {name} {c['value']!r} limit {c['limit']!r}")
    print(json.dumps(result))
    return 0
