"""repro.serving: queue, cache pool, continuous-batching scheduler, metrics.

The load-bearing assertions: (1) steady-state decode under a churning
request mix triggers exactly one jit trace (the recompile counter), and
(2) the scheduler's generations are bit-identical to naive one-request-
at-a-time prefill+decode — continuous batching changes throughput, never
tokens.
"""
import math

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist import context as dctx
from repro.dist import partitioning as dpart
from repro.launch.mesh import make_host_mesh
from repro.models import model_lib as M
from repro.serving import (AdmissionQueue, CachePool, Scheduler,
                           ServingConfig, ServingMetrics, make_request,
                           synthetic_requests)

B_SLOTS = 3


class FakeClock:
    """Settable clock: metrics become exactly computable in tests."""

    def __init__(self, t: float = 0.0):
        self.t = t

    def __call__(self) -> float:
        return self.t


@pytest.fixture(scope="module")
def cfg(small_model_config):
    return small_model_config


@pytest.fixture(scope="module")
def params(cfg):
    return M.init_params(cfg, jax.random.PRNGKey(0))


def _naive_decode(params, cfg, req):
    """One-request-at-a-time reference: unpadded prefill + scalar decode."""
    batch = {"tokens": jnp.asarray(req.prompt[None, :], jnp.int32)}
    logits, caches = jax.jit(lambda p, b: M.prefill(p, b, cfg))(params, batch)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    out = [int(tok[0, 0])]
    step = jax.jit(lambda p, t, pos, c: M.decode_step(p, t, pos, c, cfg))
    for i in range(req.max_new_tokens - 1):
        tok, _, caches = step(params, tok,
                              jnp.int32(len(req.prompt) + i), caches)
        out.append(int(tok[0, 0]))
    return np.asarray(out, np.int32)


# --------------------------------------------------------------------------
# queue
# --------------------------------------------------------------------------

def test_queue_fifo_and_arrival_gating():
    q = AdmissionQueue()
    r1 = make_request([1, 2], 4, arrival_time=1.0)
    r2 = make_request([3], 4, arrival_time=5.0)
    q.submit(r1)
    q.submit(r2)
    assert len(q) == 2
    assert q.pop(now=0.5) is None          # head not arrived yet
    assert q.pop(now=1.0) is r1            # FIFO head
    assert q.pop(now=1.0) is None          # r2 still in the future
    assert q.pop() is r2                   # now=None ignores arrival times
    assert q.pop() is None


def test_request_validation():
    with pytest.raises(ValueError, match="empty prompt"):
        make_request([], 4)
    with pytest.raises(ValueError, match="max_new_tokens"):
        make_request([1], 0)


def test_synthetic_requests_deterministic():
    a = synthetic_requests(5, vocab_size=64, prompt_lens=[3, 7],
                           max_new_tokens=4, rate=10.0, seed=3)
    b = synthetic_requests(5, vocab_size=64, prompt_lens=[3, 7],
                           max_new_tokens=4, rate=10.0, seed=3)
    for ra, rb in zip(a, b):
        assert np.array_equal(ra.prompt, rb.prompt)
        assert ra.arrival_time == rb.arrival_time
    assert a[0].arrival_time <= a[-1].arrival_time


# --------------------------------------------------------------------------
# cache pool
# --------------------------------------------------------------------------

def test_cache_pool_assign_read_evict(cfg, params):
    pool = CachePool(cfg, max_batch=2, max_len=cfg.max_seq_len)
    toks = jnp.asarray(np.arange(8)[None, :], jnp.int32)
    _, cache = jax.jit(lambda p, b: M.prefill(p, b, cfg))(
        params, {"tokens": toks})
    pool.assign(1, cache)
    got = pool.read_slot(1)
    for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(cache)):
        np.testing.assert_array_equal(np.asarray(a),
                                      np.asarray(b).astype(a.dtype))
    # slot 0 untouched (still zeros)
    assert all(not np.asarray(l).any() for l in jax.tree.leaves(
        pool.read_slot(0)))
    pool.evict(1)
    assert all(not np.asarray(l).any() for l in jax.tree.leaves(
        pool.read_slot(1)))


def test_cache_pool_pspecs_keep_slot_dim_replicated(cfg):
    """Serving pool placement: slot (batch) dim replicated, heads on
    "model" — dist.cache_pspecs(batch_over_dp=False)."""
    mesh = make_host_mesh(model=2)
    specs = M.cache_specs(cfg, 4, 16)
    with dctx.use_mesh(mesh):
        pinned = dpart.cache_pspecs(specs, mesh, batch_over_dp=False)
        default = dpart.cache_pspecs(specs, mesh)
    for spec, leaf in zip(jax.tree.leaves(
            pinned, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)),
            jax.tree.leaves(specs,
                            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))):
        entries = tuple(spec)
        assert len(entries) < 2 or entries[1] is None
        if len(leaf.shape) >= 4:
            assert entries[-2] == "model"
    # and the default still shards the batch dim over DP somewhere
    assert any(tuple(s)[1:2] not in ((), (None,)) for s in jax.tree.leaves(
        default, is_leaf=lambda x: isinstance(x, jax.sharding.PartitionSpec)))


# --------------------------------------------------------------------------
# scheduler: slot mechanics
# --------------------------------------------------------------------------

def test_slot_backfill_and_eviction_order(cfg, params):
    """Admissions fill the lowest free slot; a finished slot is evicted and
    backfilled on the next step while other slots keep decoding."""
    clk = FakeClock()
    sched = Scheduler(params, cfg, ServingConfig(max_batch=2,
                                                 prompt_bucket=8),
                      clock=clk)
    r_short = sched.submit([1, 2, 3], 3)        # finishes first
    r_long = sched.submit([4, 5, 6, 7], 6)
    r_wait = sched.submit([8, 9], 5)            # queued until a slot frees

    sched.step()          # admit emits token 1, decode token 2 for both
    assert sched._slot_rid.tolist() == [r_short, r_long]
    assert len(sched.queue) == 1
    sched.step()                                 # r_short emits its 3rd token
    assert sched._slot_rid[0] == -1              # ... and is evicted
    assert sched._slot_rid[1] == r_long
    # evicted slot is zeroed (stale KV cannot leak into the next request)
    assert all(not np.asarray(l).any()
               for l in jax.tree.leaves(sched.pool.read_slot(0)))
    sched.step()                                 # backfill into slot 0
    assert sched._slot_rid.tolist() == [r_wait, r_long]
    assert len(sched.queue) == 0
    out = sched.run()
    assert {r_short: 3, r_long: 6, r_wait: 5} == {
        rid: len(toks) for rid, toks in out.items()}


def test_scheduler_rejects_oversized_request(cfg, params):
    sched = Scheduler(params, cfg, ServingConfig(max_batch=1))
    with pytest.raises(ValueError, match="exceeds cache capacity"):
        sched.submit(np.zeros(cfg.max_seq_len, np.int32), 1)


def test_scheduler_rejects_unservable_configs(cfg, params):
    """Explicit capability boundaries: multimodal prefill inputs are a
    ROADMAP follow-on, not silent garbage.  Sliding-window configs are
    servable since the paged pool landed — they page unconditionally (a
    windowed slot is a ring over its block list, which the contiguous
    pool cannot express)."""
    with pytest.raises(NotImplementedError, match="multimodal"):
        Scheduler(params, cfg.scaled(vision_dim=8, n_patches=4),
                  ServingConfig(max_batch=1))
    sched = Scheduler(params, cfg.scaled(sliding_window=16),
                      ServingConfig(max_batch=1))
    assert sched.pool.paged, "windowed configs must auto-page"


def test_run_raises_on_stalled_clock(cfg, params):
    """run() must not spin forever when an injected clock never reaches the
    head request's arrival time."""
    sched = Scheduler(params, cfg, ServingConfig(max_batch=1),
                      clock=FakeClock(0.0))
    sched.submit([1, 2], 2, arrival_time=100.0)
    with pytest.raises(RuntimeError, match="clock is not advancing"):
        sched.run()


def test_eos_stops_generation_early(cfg, params):
    probe = Scheduler(params, cfg, ServingConfig(max_batch=1))
    rid = probe.submit([5, 6, 7], 6)
    full = probe.run()[rid].tolist()
    # the first generated token past the first that is new to the run:
    # its first occurrence is where generation must stop, short of budget
    stop = next(i for i in range(1, len(full) - 1)
                if full[i] not in full[:i])
    sched = Scheduler(params, cfg, ServingConfig(max_batch=1,
                                                 eos_id=full[stop]))
    rid2 = sched.submit([5, 6, 7], 6)
    got = sched.run()[rid2]
    assert got.tolist() == full[:stop + 1]
    assert len(got) < len(full)
    assert sched.metrics.requests[rid2].finish_time is not None


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_quant_decode_row_does_not_depend_on_batch_size(cfg, dtype):
    """Under ``quant`` (per-row int8 linears) a request's decode logits
    are bit-identical whether it steps alone or beside other rows, so a
    max_batch=1 server emits the tokens a batched one does: the float
    products left (attention, unembed) run a lone row as two, since XLA
    lowers one-row products in another summation order."""
    c = cfg.scaled(dtype=dtype, pim_mode="quant")
    params = M.init_params(c, jax.random.PRNGKey(1))
    prompt = jnp.asarray([[3, 1, 4, 1, 5, 9, 2, 6]], jnp.int32)
    logits, caches = M.prefill(params, {"tokens": prompt}, c)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    step = M.serve_jit(lambda p, t, pos, cc: M.decode_step(p, t, pos, cc, c))
    rows = {}
    for b in (1, 4):
        cb = jax.tree.map(lambda a: jnp.concatenate([a] * b, axis=1), caches)
        _, lg, _ = step(params, jnp.concatenate([tok] * b), jnp.int32(8), cb)
        rows[b] = np.asarray(lg)
    np.testing.assert_array_equal(rows[1][0], rows[4][0])


def test_serve_jit_rounds_every_bf16_op():
    """``serve_jit`` compiles without excess precision: a bf16 result is
    rounded even where XLA fuses it into f32 work, as op-by-op execution
    rounds it — so fusion choices, which follow the batch's shape, cannot
    change a row's numbers.  A plain jit keeps the extra precision."""
    def f(v):
        return (v.astype(jnp.bfloat16) * 3).astype(jnp.float32) + 1

    x = jnp.full((4, 8), 1 / 3, jnp.float32)
    np.testing.assert_array_equal(np.asarray(M.serve_jit(f)(x)),
                                  np.asarray(f(x)))
    assert not np.array_equal(np.asarray(jax.jit(f)(x)), np.asarray(f(x)))


def test_one_row_unembed_matches_its_row_in_a_batch():
    from repro.models.layers import unembed

    rng = np.random.default_rng(0)
    x = jnp.asarray(rng.standard_normal((4, 64)), jnp.bfloat16)
    table = jnp.asarray(rng.standard_normal((1000, 64)), jnp.bfloat16)
    np.testing.assert_array_equal(np.asarray(unembed(x[:1], table))[0],
                                  np.asarray(unembed(x, table))[0])


def test_vocab_padding_rows_are_never_emitted(cfg):
    """The embedding table pads the vocabulary up to ``padded_vocab`` rows
    (qwen1.5-0.5b: 151936 -> 152064).  Serving logits cover the real
    vocabulary only, so no greedy pick can land on a padding row."""
    small = cfg.scaled(vocab_size=cfg.padded_vocab - 5,
                       pad_vocab_multiple=8)
    assert small.padded_vocab > small.vocab_size
    params = M.init_params(small, jax.random.PRNGKey(0))
    toks = jnp.asarray([[5, 6, 7]], jnp.int32)
    logits, caches = M.prefill(params, {"tokens": toks}, small)
    assert logits.shape[-1] == small.vocab_size
    _, logits, _ = M.decode_step(params, toks[:, -1:], jnp.int32(3), caches,
                                 small)
    assert logits.shape[-1] == small.vocab_size
    sched = Scheduler(params, small, ServingConfig(max_batch=2, paged=True,
                                                   block_size=4))
    rids = [sched.submit(list(range(1, 4 + i)), 6) for i in range(3)]
    out = sched.run()
    assert all(0 <= t < small.vocab_size for r in rids for t in out[r])


# --------------------------------------------------------------------------
# scheduler: metrics
# --------------------------------------------------------------------------

def test_ttft_tpot_accounting(cfg, params):
    clk = FakeClock()
    sched = Scheduler(params, cfg, ServingConfig(max_batch=1,
                                                 prompt_bucket=8),
                      clock=clk)
    rid = sched.submit([1, 2, 3, 4], 3, arrival_time=0.0)
    clk.t = 5.0
    sched.step()          # admit (token 1) + decode (token 2), both @ 5.0
    clk.t = 7.0
    sched.step()                                 # third token @ 7.0, finish
    m = sched.metrics.requests[rid]
    assert m.ttft == pytest.approx(5.0)
    assert m.queue_wait == pytest.approx(5.0)
    assert m.tpot == pytest.approx(1.0)          # (7 - 5) / 2
    assert m.n_tokens == 3 and m.finish_time == pytest.approx(7.0)
    s = sched.metrics.summary()
    assert s["n_finished"] == 1 and s["total_tokens"] == 3
    assert s["tokens_per_s"] == pytest.approx(3 / 2.0)  # busy window 5..7
    assert s["max_queue_depth"] == 0


def _sample_list_summary(queue, pool):
    """The per-step keys of ``summary()`` as computed from whole sample
    lists (``queue``: (depth, active) per step; ``pool``: stats dicts with
    ``tokens_live``) — the arithmetic the running aggregates replace."""
    mean = ServingMetrics._mean
    return {
        "max_queue_depth": max((d for d, _ in queue), default=0),
        "mean_active_slots": mean([float(a) for _, a in queue]),
        "peak_kv_bytes": max((p["kv_bytes_in_use"] for p in pool),
                             default=math.nan),
        "peak_pool_blocks": max((p["blocks_in_use"] for p in pool),
                                default=math.nan),
        "mean_block_occupancy": mean([p["blocks_in_use"] / p["blocks_total"]
                                      for p in pool if p["blocks_total"]]),
        "mean_internal_frag": mean(
            [1.0 - p["tokens_live"] / p["tokens_reserved"] for p in pool
             if p["tokens_reserved"] and not math.isnan(p["tokens_live"])]),
        "peak_blocks_shared": max((p.get("blocks_shared", 0.0)
                                   for p in pool), default=0.0),
        "cow_copies": max((p.get("cow_copies", 0.0) for p in pool),
                          default=0.0),
    }


def test_step_aggregates_match_sample_list_arithmetic(cfg, params):
    """The per-step samples are kept as running aggregates (constant
    memory); on a recorded sequence ``summary()`` and ``merged()`` give
    what the whole sample lists gave."""
    sched = Scheduler(params, cfg, ServingConfig(max_batch=2, prompt_bucket=8,
                                                 paged=True, block_size=4))
    queue, pool = [], []
    m = sched.metrics
    sample_queue, sample_pool = m.sample_queue, m.sample_pool

    def record_queue(depth, active):
        queue.append((depth, active))
        sample_queue(depth, active)

    def record_pool(stats, tokens_live=math.nan):
        pool.append(dict(stats, tokens_live=tokens_live))
        sample_pool(stats, tokens_live)

    m.sample_queue, m.sample_pool = record_queue, record_pool
    for r in synthetic_requests(5, vocab_size=cfg.vocab_size,
                                prompt_lens=[5, 9, 3], max_new_tokens=4,
                                seed=3):
        sched.submit_request(r)
    sched.run()
    # edge samples: an empty reservation, nothing live to measure, no
    # blocks at all, a contiguous pool's stats (no shared-block keys)
    record_pool(dict(pool[0], tokens_reserved=0.0), 3.0)
    record_pool(pool[1])
    record_pool(dict(pool[1], blocks_total=0.0), 1.0)
    record_pool({"kv_bytes_in_use": 1.0, "kv_bytes_reserved": 1.0,
                 "blocks_in_use": 2.0, "blocks_total": 2.0,
                 "tokens_reserved": 8.0, "tokens_in_use": 8.0}, 5.0)
    assert len(queue) > 3 and len(pool) > 5
    want = _sample_list_summary(queue, pool)
    got = m.summary()
    for k, v in want.items():
        assert got[k] == pytest.approx(v, nan_ok=True), k

    parts = [ServingMetrics(), ServingMetrics(), ServingMetrics()]
    for i, (d, a) in enumerate(queue):
        parts[i % 2].sample_queue(d, a)
    for i, p in enumerate(pool):
        parts[i % 2].sample_pool(p, p["tokens_live"])
    got = ServingMetrics.merged(parts).summary()   # parts[2] is empty
    for k, v in want.items():
        assert got[k] == pytest.approx(v, nan_ok=True), k
    empty = ServingMetrics().summary()
    for k, v in _sample_list_summary([], []).items():
        assert empty[k] == pytest.approx(v, nan_ok=True), k


# --------------------------------------------------------------------------
# scheduler: steady state + end-to-end equivalence
# --------------------------------------------------------------------------

def test_churning_stream_matches_naive_decode_and_never_recompiles(
        cfg, params):
    """Acceptance: a churning request stream produces tokens identical to
    one-at-a-time decode, with exactly one decode-step trace."""
    reqs = synthetic_requests(7, vocab_size=cfg.vocab_size,
                              prompt_lens=[5, 9, 13, 3], max_new_tokens=6,
                              seed=1)
    sched = Scheduler(params, cfg, ServingConfig(max_batch=B_SLOTS,
                                                 prompt_bucket=8))
    for r in reqs:
        sched.submit_request(r)
    out = sched.run()
    assert sched.decode_traces == 1, \
        "slot churn must not recompile the decode step"
    assert sched.n_active == 0 and len(sched.queue) == 0
    for r in reqs:
        want = _naive_decode(params, cfg, r)
        assert np.array_equal(out[r.rid], want), r.rid
    s = sched.metrics.summary()
    assert s["n_finished"] == len(reqs)
    assert s["total_tokens"] == sum(r.max_new_tokens for r in reqs)


def test_recurrent_arch_unbucketed_prefill_matches_naive():
    """SSM/xLSTM stacks serve exactly with prompt_bucket=1 (no padding to
    fold into the recurrent state); slot churn still never recompiles."""
    import repro.configs as configs

    rcfg = configs.get("xlstm-1.3b").smoke()
    rparams = M.init_params(rcfg, jax.random.PRNGKey(3))
    reqs = synthetic_requests(3, vocab_size=rcfg.vocab_size,
                              prompt_lens=[4, 7], max_new_tokens=4, seed=2)
    sched = Scheduler(rparams, rcfg, ServingConfig(max_batch=2,
                                                   prompt_bucket=1))
    for r in reqs:
        sched.submit_request(r)
    out = sched.run()
    assert sched.decode_traces == 1
    for r in reqs:
        want = _naive_decode(rparams, rcfg, r)
        assert np.array_equal(out[r.rid], want), r.rid


def test_single_token_requests_never_occupy_slots(cfg, params):
    """max_new_tokens=1 completes at admit (prefill emits the only token)."""
    sched = Scheduler(params, cfg, ServingConfig(max_batch=2))
    rids = [sched.submit([i + 1, i + 2], 1) for i in range(4)]
    out = sched.run()
    assert sched.decode_traces == 0              # decode never even traced
    for rid in rids:
        assert out[rid].shape == (1,)
        assert sched.metrics.requests[rid].finish_time is not None
    # admit-and-finish never touched the pool: free slots stay zeroed
    for slot in range(2):
        assert all(not np.asarray(l).any()
                   for l in jax.tree.leaves(sched.pool.read_slot(slot)))
