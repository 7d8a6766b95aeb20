"""Prefix caching with copy-on-write block sharing, plus the serving-path
bugfix sweep that rode along.

Tentpole coverage: trie-hit admits must be *bit-identical* to cold
prefill (through the paged pool with and without the index, against the
contiguous pool, under the 8-device mesh, and per PIM engine mode);
refcount invariants must hold under seeded Poisson churn (no block freed
while referenced, no leak at drain); COW must fire — and preserve other
referents' bits — on fork divergent tails and on windowed ring wraps.

Satellite regressions: a request finishing at admit must not consume its
free-slot iteration; a deferred rid must reset on successful admit (now
the ``_deferred_rids`` set — see test_serving_chunked for the SJF
head-churn case);
``stats()`` must report logical ``tokens_reserved`` and physical
``tokens_in_use`` separately, with aligned keys across both pools.
"""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as C
from repro.dist import context as dctx
from repro.launch.mesh import make_mesh
from repro.models import model_lib as M
from repro.serving import (PagedCachePool, Scheduler, ServingConfig,
                           make_request, synthetic_requests)


def _smoke():
    return C.get("qwen1.5-0.5b").smoke()


def _tiny(mode):
    return C.get("qwen1.5-0.5b").smoke().scaled(
        n_layers=1, pattern=("ad",), d_model=32, n_heads=2, n_kv_heads=2,
        head_dim=16, d_ff=64, vocab_size=64, pad_vocab_multiple=8,
        loss_chunk=8, max_seq_len=16, pim_mode=mode)


def _mesh_ctx(mode):
    if mode != "quant_tp":
        return contextlib.nullcontext()
    return dctx.use_mesh(make_mesh((8,), ("model",)))


def _shared_trace(cfg, *, shared_len, tails, budget, seed=0):
    """Requests sharing one system prompt, with divergent random tails."""
    rng = np.random.default_rng(seed)
    shared = rng.integers(1, cfg.vocab_size, shared_len)
    return [make_request(
        np.concatenate([shared, rng.integers(1, cfg.vocab_size, t)]), budget)
        for t in tails]


def _run(params, cfg, scfg, reqs, *, mesh=None):
    sched = Scheduler(params, cfg, scfg, mesh=mesh)
    rids = [sched.submit_request(make_request(r.prompt, r.max_new_tokens))
            for r in reqs]
    out = sched.run()
    return sched, [out[rid] for rid in rids]


def _check_refcounts(pool):
    """The allocator's ground-truth invariant: _ref equals the reference
    multiset (slot block lists + trie entries), the free list holds
    exactly the unreferenced non-sentinel blocks, once each."""
    refs = np.zeros(pool.num_blocks, np.int64)
    for bl in pool._slot_blocks:
        for b in bl:
            refs[b] += 1
    trie = pool.prefix.blocks() if pool.prefix is not None else []
    for b in trie:
        refs[b] += 1
    assert (refs == pool._ref).all(), (refs.tolist(), pool._ref.tolist())
    free = set(pool._free)
    assert len(free) == len(pool._free), "free list holds duplicates"
    assert 0 not in free, "sentinel block leaked into the free list"
    used = {b for bl in pool._slot_blocks for b in bl} | set(trie)
    assert not free & used, "block simultaneously free and referenced"
    assert len(free) + len(used) == pool.num_blocks - 1, "block leak"


# ---------------------------------------------------------------------------
# tentpole: bit-exactness of trie-hit admits
# ---------------------------------------------------------------------------

def test_prefix_admits_bit_exact_across_pools():
    """Warm (trie-hit) generations must match cold paged and contiguous
    pool generations token for token, under the 8-device mesh."""
    cfg = _smoke()
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    mesh = make_mesh((8,), ("model",))
    reqs = _shared_trace(cfg, shared_len=32, tails=(5, 7, 9, 6), budget=6)
    base = dict(max_batch=2, prompt_bucket=8)
    _, contiguous = _run(params, cfg, ServingConfig(**base), reqs, mesh=mesh)
    _, cold = _run(params, cfg, ServingConfig(paged=True, **base), reqs,
                   mesh=mesh)
    warm_sched, warm = _run(params, cfg,
                            ServingConfig(prefix_cache=True, **base), reqs,
                            mesh=mesh)
    for a, b, c in zip(contiguous, cold, warm):
        assert (a == b).all()
        assert (b == c).all()
    assert warm_sched.decode_traces == 1
    s = warm_sched.metrics.summary()
    assert s["prefix_hit_rate"] == pytest.approx(3 / 4)  # first admit is cold
    assert s["prefix_tokens_reused"] == 3 * 32
    _check_refcounts(warm_sched.pool)


def test_prefix_bit_exact_per_pim_mode(pim_test_mode):
    """The trie-hit path must stay bit-identical to cold prefill under
    every engine lowering (CI's PIM_TEST_MODE matrix owns this)."""
    cfg = _tiny(pim_test_mode)
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    reqs = _shared_trace(cfg, shared_len=8, tails=(3, 2, 4), budget=4,
                         seed=2)
    base = dict(max_batch=2, prompt_bucket=4, block_size=4)
    with _mesh_ctx(pim_test_mode):
        _, cold = _run(params, cfg, ServingConfig(paged=True, **base), reqs)
        sched, warm = _run(params, cfg,
                           ServingConfig(prefix_cache=True, **base), reqs)
    for a, b in zip(cold, warm):
        assert (a == b).all(), f"prefix-cache divergence under {pim_test_mode}"
    assert sched.decode_traces == 1
    assert sched.metrics.summary()["prefix_tokens_reused"] == 2 * 8


def test_windowed_ring_wrap_cow_bit_exact():
    """A windowed slot whose ring wraps onto mapped prefix blocks must COW
    them — generations stay identical to the no-prefix-cache run and the
    trie's copy of the prefix survives for later hits."""
    cfg = _smoke().scaled(sliding_window=8, max_seq_len=64)
    params = M.init_params(cfg, jax.random.PRNGKey(2))
    # plen 7 <= window 8 (so the prompt matches the trie), budget 12 wraps
    # the 8-token ring
    reqs = _shared_trace(cfg, shared_len=4, tails=(3, 3, 3), budget=12,
                         seed=3)
    base = dict(max_batch=2, prompt_bucket=4, block_size=4)
    _, cold = _run(params, cfg, ServingConfig(**base), reqs)
    sched, warm = _run(params, cfg, ServingConfig(prefix_cache=True, **base),
                       reqs)
    for a, b in zip(cold, warm):
        assert (a == b).all()
    assert sched.pool.cow_copies > 0, "ring wrap never triggered COW"
    assert sched.decode_traces == 1
    _check_refcounts(sched.pool)


# ---------------------------------------------------------------------------
# tentpole: fork (parallel sampling) + COW on the divergent tail
# ---------------------------------------------------------------------------

def test_fork_cow_divergent_tail():
    """fork() shares content blocks by reference; the boundary block COWs
    on the sibling's first divergent write, and the sibling's generation
    matches a fully private continuation bit for bit."""
    cfg = _smoke()
    params = M.init_params(cfg, jax.random.PRNGKey(3))
    rng = np.random.default_rng(4)
    prompt = rng.integers(1, cfg.vocab_size, 10)  # boundary mid-block (bs 4)
    prefill = jax.jit(lambda p, t, li: M.prefill(p, {"tokens": t}, cfg,
                                                 last_index=li))
    toks = np.zeros((1, 16), np.int32)
    toks[0, :10] = prompt
    logits, cache = prefill(params, jnp.asarray(toks),
                            jnp.asarray([9], np.int32))
    first = int(np.asarray(jnp.argmax(logits, -1))[0])
    div = (first + 1) % cfg.vocab_size  # forced divergent second branch

    # donated, as the scheduler's decode step: ``pool.caches`` is replaced
    # by the result before anything reads it again
    def step(p, t, pos, act, caches, bt):
        return M.decode_step_slots(p, t, pos, act, caches, cfg,
                                   block_tables=bt)

    dec = M.serve_jit(step, donate="caches")

    def decode(pool, n_slots, firsts, steps=6):
        tokens = np.zeros((n_slots, 1), np.int32)
        pos = np.zeros(n_slots, np.int32)
        act = np.zeros(n_slots, bool)
        outs = [[] for _ in range(len(firsts))]
        for s, f in enumerate(firsts):
            tokens[s, 0] = f
            pos[s] = 10
            act[s] = True
        for _ in range(steps):
            for s in range(len(firsts)):
                pool.ensure_writable(s, int(pos[s]))
            nt, _, nc = dec(params, jnp.asarray(tokens), jnp.asarray(pos),
                            jnp.asarray(act), pool.caches, pool.block_tables)
            pool.caches = nc
            t = np.asarray(nt)
            for s in range(len(firsts)):
                outs[s].append(int(t[s, 0]))
                tokens[s, 0] = t[s, 0]
            pos += act
        return outs

    pool = PagedCachePool(cfg, 2, cfg.max_seq_len, block_size=4,
                          prefix_cache=True)
    pool.admit(0, cache, 10, 16, prompt=prompt)
    pool.fork(0, 1, 10, 16)
    assert pool.has_shared
    a, b = decode(pool, 2, [first, div])
    assert a != b, "forced divergent branches converged"
    assert pool.cow_copies == 1, "boundary block must COW exactly once"

    # reference: the divergent branch on a private, freshly admitted slot
    ref_pool = PagedCachePool(cfg, 1, cfg.max_seq_len, block_size=4)
    ref_pool.admit(0, cache, 10, 16)
    (ref,) = decode(ref_pool, 1, [div])
    assert ref == b, "fork sibling diverged from private continuation"

    pool.evict(0)
    pool.evict(1)
    _check_refcounts(pool)
    # drained: only the trie holds blocks
    assert pool.blocks_in_use == pool.prefix.n_blocks
    pool.clear_prefix()
    assert pool.blocks_in_use == 0


# ---------------------------------------------------------------------------
# tentpole: refcount invariants under churn
# ---------------------------------------------------------------------------

def test_refcount_invariants_under_poisson_churn():
    """Seeded Poisson trace through a deliberately undersized pool:
    admissions defer, the trie reclaims under pressure, rings of varying
    budgets churn blocks — after every scheduler step the refcount
    ground truth must hold, and the drain must not leak a block."""
    cfg = _smoke()
    params = M.init_params(cfg, jax.random.PRNGKey(4))
    rng = np.random.default_rng(5)
    shared = rng.integers(1, cfg.vocab_size, 16)
    arrivals = np.cumsum(rng.exponential(0.5, size=14))
    reqs = [make_request(
        np.concatenate([shared, rng.integers(1, cfg.vocab_size,
                                             int(rng.integers(2, 9)))]),
        int(rng.integers(1, 7)), arrival_time=float(t)) for t in arrivals]

    now = [0.0]
    sched = Scheduler(params, cfg,
                      ServingConfig(max_batch=3, prompt_bucket=8,
                                    block_size=4, prefix_cache=True,
                                    num_blocks=24),
                      clock=lambda: now[0])
    for r in reqs:
        sched.submit_request(r)
    for _ in range(400):
        sched.step()
        _check_refcounts(sched.pool)
        now[0] += 0.5
        if not len(sched.queue) and not sched.active_slots.any():
            break
    assert not len(sched.queue) and not sched.active_slots.any(), \
        "trace failed to drain"
    # no leak at drain: everything still allocated is owned by the trie
    assert sched.pool.blocks_in_use == sched.pool.prefix.n_blocks
    sched.pool.clear_prefix()
    assert sched.pool.blocks_in_use == 0
    assert len(sched.pool._free) == sched.pool.num_blocks - 1


# ---------------------------------------------------------------------------
# satellites: admit-loop and deferral bookkeeping regressions
# ---------------------------------------------------------------------------

def test_finished_at_admit_retries_same_slot():
    """A burst of one-token requests must drain in a single scheduler
    step: each finishes at admit without occupying its slot, so the slot
    is retried with the next queued request."""
    cfg = _smoke()
    params = M.init_params(cfg, jax.random.PRNGKey(5))
    sched = Scheduler(params, cfg, ServingConfig(max_batch=2,
                                                 prompt_bucket=4))
    rng = np.random.default_rng(6)
    one_shot = [sched.submit(rng.integers(1, cfg.vocab_size, 3), 1)
                for _ in range(4)]
    long_rid = sched.submit(rng.integers(1, cfg.vocab_size, 3), 5)
    emitted = sched.step()
    # all four one-token requests AND the long request admitted in step 1
    assert len(sched.queue) == 0
    assert {rid for rid, _ in emitted} == set(one_shot) | {long_rid}
    assert sched.n_active == 1  # only the long request holds a slot
    out = sched.run()
    for rid in one_shot:
        assert out[rid].shape == (1,)
    assert out[long_rid].shape == (5,)


def test_deferred_rid_resets_after_admit():
    """deferred -> admitted -> deferred-again must count two deferral
    events, even when the later request reuses the earlier rid (the
    pre-fix dedupe never reset ``_deferred_rid`` after the head got in,
    silently swallowing the second event)."""
    cfg = _smoke()
    params = M.init_params(cfg, jax.random.PRNGKey(6))
    rng = np.random.default_rng(7)
    # each request: plen 4 + budget 8 = 12 tokens = 3 blocks of 4; pool
    # holds 3 usable blocks, so exactly one request fits at a time
    mk = lambda rid: make_request(rng.integers(1, cfg.vocab_size, 4), 8,
                                  rid=rid)
    sched = Scheduler(params, cfg,
                      ServingConfig(max_batch=2, prompt_bucket=4,
                                    paged=True, block_size=4, num_blocks=4),
                      clock=lambda: 0.0)
    sched.submit_request(mk(rid=9001))
    sched.step()
    assert sched.n_active == 1
    sched.submit_request(mk(rid=777))
    sched.step()
    assert sched.metrics.deferred_admits == 1     # 777 deferred behind 9001
    for _ in range(20):
        sched.step()
        if not len(sched.queue) and not sched.active_slots.any():
            break
    assert sched.metrics.deferred_admits == 1     # dedupe: one event per wait
    sched.submit_request(mk(rid=9002))
    sched.step()
    assert sched.n_active == 1                    # 9002 admitted
    sched.submit_request(mk(rid=777))             # rid reuse: worst case
    sched.step()
    assert sched.metrics.deferred_admits == 2, \
        "_deferred_rid not reset on successful admit"


# ---------------------------------------------------------------------------
# satellites: stats keys and validation gates
# ---------------------------------------------------------------------------

def test_stats_reserved_vs_in_use_and_key_alignment():
    """tokens_reserved (logical, per referencing slot) and tokens_in_use
    (physical, each block once) must diverge exactly by the shared
    blocks; both pools must emit the shared key set."""
    cfg = _smoke()
    params = M.init_params(cfg, jax.random.PRNGKey(7))
    prefill = jax.jit(lambda p, t, li: M.prefill(p, {"tokens": t}, cfg,
                                                 last_index=li))
    rng = np.random.default_rng(8)
    prompt = rng.integers(1, cfg.vocab_size, 8)
    toks = np.zeros((1, 8), np.int32)
    toks[0, :] = prompt
    _, cache = prefill(params, jnp.asarray(toks), jnp.asarray([7], np.int32))

    pool = PagedCachePool(cfg, 2, cfg.max_seq_len, block_size=4,
                          prefix_cache=True)
    pool.admit(0, cache, 8, 12, prompt=prompt)     # 3 blocks
    st = pool.stats()
    assert st["tokens_reserved"] == 3 * 4
    assert st["tokens_in_use"] == 3 * 4            # nothing shared yet

    pool.fork(0, 1, 8, 12)                         # shares 2 content blocks
    st = pool.stats()
    assert st["tokens_reserved"] == 6 * 4          # both slots' reservations
    assert st["tokens_in_use"] == 4 * 4            # 3 + 1 fresh, shared once
    assert st["blocks_shared"] == 2.0
    assert st["prefix_blocks"] == 2.0              # plen 8 registered fully

    from repro.serving import CachePool

    flat = CachePool(cfg, 2, cfg.max_seq_len)
    core = {"kv_bytes_in_use", "kv_bytes_reserved", "blocks_in_use",
            "blocks_total", "tokens_reserved", "tokens_in_use"}
    assert core <= set(flat.stats())
    assert core <= set(st)
    assert flat.stats()["tokens_in_use"] == 0.0
    flat.admit(0, cache, 8, 12)
    assert flat.stats()["tokens_in_use"] == float(cfg.max_seq_len)
    assert flat.stats()["tokens_reserved"] == float(2 * cfg.max_seq_len)


def test_prefix_cache_rejects_non_separable_stacks():
    """Recurrent state and MoE capacity dropping make KV depend on more
    than the prefix — the scheduler must refuse rather than silently
    serve wrong bits."""
    moe = _smoke().scaled(pattern=("ae",), n_layers=2, n_experts=4)
    with pytest.raises(ValueError, match="MoE"):
        Scheduler(None, moe, ServingConfig(prefix_cache=True))
    rec = _smoke().scaled(pattern=("md",), n_layers=2)
    with pytest.raises(ValueError, match="prefix_cache"):
        Scheduler(None, rec, ServingConfig(prefix_cache=True,
                                           prompt_bucket=1))


def test_shared_prefix_synthetic_trace():
    """synthetic_requests(shared_prefix_len=N) prepends one identical
    N-token run to every prompt, reproducibly across calls with the same
    seed (warm-up and measured benchmark traces must share it)."""
    a = synthetic_requests(4, vocab_size=97, prompt_lens=[3, 5],
                          shared_prefix_len=8, seed=11)
    b = synthetic_requests(2, vocab_size=97, prompt_lens=[4],
                          shared_prefix_len=8, seed=11)
    head = a[0].prompt[:8]
    for r in a + b:
        assert (r.prompt[:8] == head).all()
    assert a[0].prompt.shape == (11,)
    assert a[1].prompt.shape == (13,)
    plain = synthetic_requests(2, vocab_size=97, prompt_lens=[4], seed=11)
    assert plain[0].prompt.shape == (4,)
