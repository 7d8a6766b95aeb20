"""The serving scheduler's own profiler spans and program names, and the
benchmark readers that turn them into per-layer metrics.

A tiny ``Scheduler`` runs under ``jax.profiler`` on the CPU backend inside
a ``bench.window`` span, as the benchmark's traced run does; the trace is
reduced with the benchmark's own ``servebench.trace.reduce_profile``.
"""
import glob
import os
import sys
import types

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.models import model_lib as M
from repro.serving import Scheduler, ServingConfig, synthetic_requests

BENCH = os.path.join(os.path.dirname(os.path.abspath(__file__)), "..",
                     "benchmarks", "serve")
sys.path.insert(0, BENCH)

from servebench import spec, trace  # noqa: E402

#: every span ``repro.serving`` records (PERF.md, section 3)
SPANS = {
    "serve.step", "serve.prefill_chunks", "serve.admit", "serve.prefill",
    "serve.prefill.wait", "serve.pool.admit", "serve.decode",
    "serve.decode.inputs", "serve.decode.dispatch", "serve.decode.wait",
    "serve.decode.commit", "serve.pool.evict", "serve.sample",
}
CHUNK = 8
JIT = "PjitFunction("     # a jitted call's host event


@pytest.fixture(scope="module")
def params(small_model_config):
    return M.init_params(small_model_config, jax.random.PRNGKey(0))


def _serve(sched, reqs):
    """Step ``sched`` through ``reqs``; returns the decode tokens ``step()``
    returned (a request's first token comes from its prefill)."""
    for r in reqs:
        sched.submit_request(r)
    seen, decoded = set(), 0
    while len(sched.queue) or sched.n_active:
        for rid, _ in sched.step():
            decoded += rid in seen
            seen.add(rid)
    return decoded


@pytest.fixture(scope="module")
def traced(small_model_config, params, tmp_path_factory):
    """One trace of two schedulers: plain bucketed prefill, and chunked
    prefill (prompts longer than ``CHUNK`` resume chunk by chunk)."""
    cfg = small_model_config
    plain = Scheduler(params, cfg, ServingConfig(max_batch=2,
                                                 prompt_bucket=8, paged=True,
                                                 block_size=4))
    chunked = Scheduler(params, cfg, ServingConfig(
        max_batch=2, prompt_bucket=8, paged=True, block_size=4,
        prefill_chunk=CHUNK))
    reqs = {
        "plain": synthetic_requests(4, vocab_size=cfg.vocab_size,
                                    prompt_lens=[5, 11, 3], max_new_tokens=4,
                                    seed=1),
        "chunked": synthetic_requests(3, vocab_size=cfg.vocab_size,
                                      prompt_lens=[19, 6], max_new_tokens=3,
                                      seed=2),
    }
    # compile outside the trace, as the benchmark's warm-up does
    _serve(plain, synthetic_requests(3, vocab_size=cfg.vocab_size,
                                     prompt_lens=[5, 11, 3], max_new_tokens=2,
                                     seed=9))
    d = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(d)
    try:
        with jax.profiler.TraceAnnotation(trace.WINDOW):
            decoded = (_serve(plain, reqs["plain"])
                       + _serve(chunked, reqs["chunked"]))
    finally:
        jax.profiler.stop_trace()
    pd = jax.profiler.ProfileData.from_file(
        glob.glob(os.path.join(d, "**", "*.xplane.pb"), recursive=True)[0])
    events = []     # (name, start, end, args) of the window's thread
    for plane in pd.planes:
        for line in plane.lines:
            evs = list(line.events)
            if any(e.name == trace.WINDOW for e in evs):
                events = [(e.name, e.start_ns, e.start_ns + e.duration_ns,
                           dict(e.stats) if e.name.startswith("serve.")
                           else {}) for e in evs]
    spans = [ev for ev in events if ev[0].startswith("serve.")]
    return types.SimpleNamespace(
        summary=trace.reduce_profile(pd), spans=spans, events=events,
        decoded=decoded,
        plain=plain, prompts={r.rid: r.prompt.shape[0]
                              for rs in reqs.values() for r in rs},
        chunked_rids={r.rid for r in reqs["chunked"]})


def test_span_names_are_the_documented_set(traced):
    names = {n for n, _, _ in traced.summary.host if n.startswith("serve.")}
    assert names == SPANS
    assert {n for n, *_ in traced.spans} == SPANS


def test_every_decode_lies_inside_a_step(traced):
    steps = [(s, e) for n, s, e, _ in traced.spans if n == "serve.step"]
    decodes = [(s, e) for n, s, e, _ in traced.spans if n == "serve.decode"]
    assert decodes
    for s, e in decodes:
        assert any(a <= s and e <= b for a, b in steps)
    # each scheduler numbers its steps 1, 2, ... (the first one's first
    # steps ran before the trace)
    nums = [a["step_num"] for n, *_, a in traced.spans if n == "serve.step"]
    assert nums.count(1) == 1
    assert all(b in (a + 1, 1) for a, b in zip(nums, nums[1:]))


def test_decode_rows_sum_to_the_decoded_tokens(traced):
    rows = [a["rows"] for n, *_, a in traced.spans if n == "serve.decode"]
    assert sum(rows) == traced.decoded > 0


def test_prefill_spans_carry_tokens_and_bucket(traced):
    pre = [a for n, *_, a in traced.spans if n == "serve.prefill"]
    by_rid = {}
    for a in pre:
        by_rid.setdefault(a["rid"], []).append(a)
    assert set(by_rid) == set(traced.prompts)
    for rid, calls in by_rid.items():
        plen = traced.prompts[rid]
        if rid in traced.chunked_rids and plen > CHUNK:
            # one span per chunk, chunks cover the prompt
            assert len(calls) == -(-plen // CHUNK)
            assert sum(a["tokens"] for a in calls) == plen
        else:
            (a,) = calls
            assert a["tokens"] == plen
            assert a["bucket"] == traced.plain._bucket(plen)
    admits = {a["rid"]: a["blocks"] for n, *_, a in traced.spans
              if n == "serve.pool.admit"}
    evicts = {a["rid"]: a["blocks"] for n, *_, a in traced.spans
              if n == "serve.pool.evict"}
    assert set(evicts) == set(traced.prompts)
    for rid, blocks in evicts.items():
        assert blocks == admits[rid] > 0


def test_prefill_spans_hold_one_prefill_call_and_no_pool_work(traced):
    """A ``serve.prefill`` span holds its prefill program's dispatch (and
    the first token's read-back), never a pool program: the pool's reads,
    extends and installs around a chunk are outside it."""
    pre = [(s, e) for n, s, e, _ in traced.spans if n == "serve.prefill"]
    assert pre
    for a, b in pre:
        inside = {n for n, s, e, _ in traced.events
                  if a <= s and e <= b and n.startswith(JIT)}
        assert len({n for n in inside
                    if n.startswith(JIT + "serve_prefill")}) == 1, inside
        assert not {n for n in inside if n.startswith(JIT + "pool_")}
    # the chunked scheduler's pool reads and extends did run
    jits = {n for n, *_ in traced.events if n.startswith(JIT)}
    assert JIT + "pool_read_prefix)" in jits


def test_span_args_are_computed_only_under_a_profiler():
    from repro.serving.scheduler import _span

    def args():
        raise AssertionError("span arguments computed with no profiler")

    with _span("serve.test", args):
        pass


def test_programs_are_named(traced):
    s = traced.plain
    active = np.ones(s.scfg.max_batch, bool)
    text = s._decode.lower(s.params, jnp.asarray(s._tokens),
                           jnp.asarray(s._pos), jnp.asarray(active),
                           s.pool.caches, s.pool.block_tables).as_text()
    assert "jit_serve_decode" in text
    toks = jnp.zeros((1, 8), jnp.int32)
    last = jnp.asarray([7], jnp.int32)
    assert "jit_serve_prefill" in s._prefill.lower(s.params, toks,
                                                   last).as_text()
    evict = s.pool._evict.lower(s.pool.caches,
                                jnp.asarray(s.pool._table[0]), jnp.int32(0))
    assert "jit_pool_evict" in evict.as_text()


# ---------------------------------------------------------------------------
# the benchmark's readers, on a hand-built trace
# ---------------------------------------------------------------------------

def _ctx(host, busy=((),), ops=None, window=(0.0, 10.0)):
    return types.SimpleNamespace(trace=trace.TraceSummary(
        window=window, ops=ops or {}, host=list(host),
        busy=[list(b) for b in busy]))


def _read(metric, ctx):
    return spec.Layout().metric_reader(metric)(ctx, metric)


HOST = [
    ("bench.window", 0.0, 10.0),
    ("serve.step", 1.0, 3.0),
    ("serve.admit", 1.0, 1.5),
    ("serve.prefill", 1.1, 1.4),            # 300 ms
    ("serve.prefill.wait", 1.2, 1.4),
    ("serve.decode", 1.5, 2.9),
    ("serve.decode.wait", 2.0, 2.5),
    ("serve.step", 4.0, 5.0),
    ("serve.prefill", 4.1, 4.2),            # 100 ms
    ("serve.decode", 4.3, 4.9),
    ("bench.bookkeeping", 5.0, 6.0),
]
#: device busy 1.4-2.0, 2.5-2.9 and 4.3-4.9: inside the steps it is idle
#: 1.0-1.4 (0.1 admit, 0.1 prefill, 0.2 wait), 2.0-2.5 (decode.wait),
#: 2.9-3.0 (step), 4.0-4.3 (0.1 step, 0.1 prefill, 0.1 step), 4.9-5.0
BUSY = [[(1.4, 2.0), (2.5, 2.9), (4.3, 4.9)]]


def test_prefill_ms_reads_the_median_of_whole_spans():
    """``prefill_ms`` reads the prefill programs' device time per
    ``serve.prefill`` span (one a call); the pool's copies around the
    prefill, and the other programs, are left out."""
    ops = {("jit_serve_prefill", "fusion"): [0.03, 2],
           ("jit_serve_prefill_resume", "fusion"): [0.01, 1],
           ("jit_serve_prefill", "while"): [5.0, 1],       # a container
           ("jit_pool_assign", "copy"): [1.0, 1],
           ("jit_serve_decode", "fusion"): [0.5, 4]}
    assert _read("prefill_ms.chat",
                 _ctx(HOST, BUSY, ops)) == pytest.approx(20.0)
    # a span that starts after the window's close is not counted
    assert _read("prefill_ms.chat",
                 _ctx(HOST, BUSY, ops, window=(0.0, 4.0))) == \
        pytest.approx(40.0)
    parent = {("jit__lambda", "fusion"): [0.03, 2]}
    assert _read("prefill_ms.chat", _ctx(HOST, BUSY, parent)) is None
    host = [h for h in HOST if h[0] != "serve.prefill"]
    assert _read("prefill_ms.offline", _ctx(host, BUSY, ops)) is None
    assert _read("prefill_ms.chat", types.SimpleNamespace(trace=None)) is None


def test_decode_device_ms_is_program_time_per_decode_span():
    ops = {("jit_serve_decode", "fusion"): [0.5, 4],
           ("jit_serve_decode", "while"): [9.0, 1],      # a container
           ("jit_serve_prefill", "fusion"): [3.0, 1]}
    assert _read("decode_device_ms.chat",
                 _ctx(HOST, BUSY, ops)) == pytest.approx(250.0)
    # two devices: device seconds are summed over both
    assert _read("decode_device_ms.offline",
                 _ctx(HOST, BUSY * 2, ops)) == pytest.approx(125.0)
    parent = {("jit__step", "fusion"): [0.5, 4]}
    assert _read("decode_device_ms.chat", _ctx(HOST, BUSY, parent)) is None
    host = [h for h in HOST if h[0] != "serve.decode"]
    assert _read("decode_device_ms.chat", _ctx(host, BUSY, ops)) is None


def test_idle_in_step_counts_idle_time_inside_steps(capsys):
    got = _read("idle_in_step.chat", _ctx(HOST, BUSY))
    # idle in steps: 0.4 + 0.5 + 0.1 + 0.3 + 0.1 = 1.4 s of a 10-s window
    assert got == pytest.approx(14.0)
    err = capsys.readouterr().err
    assert "serve.decode.wait 0.500000" in err
    assert "serve.step 0.400000" in err
    assert "serve.prefill 0.200000" in err         # 0.1 + 0.1
    assert "serve.prefill.wait 0.200000" in err
    assert "serve.admit 0.100000" in err
    assert "bench.bookkeeping" not in err
    host = [h for h in HOST if not h[0].startswith("serve.")]
    assert _read("idle_in_step.offline", _ctx(host, BUSY)) is None
    assert _read("idle_in_step.chat", _ctx(HOST, busy=())) is None
