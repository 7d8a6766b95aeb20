"""Block-paged KV-cache pool: allocator mechanics, scheduler churn
equivalence, and sliding-window serving.

Load-bearing assertions mirror test_serving.py's, extended to the paged
layout: (1) the pool layout is a memory optimization, never a semantic
one — a churning Poisson request mix yields tokens bit-identical to the
sequential (max_batch=1) oracle through *both* pools; (2) block churn
never recompiles the decode step (the block table's shape is fixed);
(3) free-list exhaustion defers admission instead of crashing, and evict
returns blocks; (4) sliding-window configs — which the contiguous pool
rejects by construction — serve end-to-end as rings over their block
lists, matching both the naive ring-decode oracle and a teacher-forced
full-prefill oracle with prompts on either side of the window.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.dist import context as dctx
from repro.launch.mesh import make_host_mesh
from repro.models import model_lib as M
from repro.serving import PagedCachePool, Scheduler, ServingConfig


@pytest.fixture(scope="module")
def cfg(small_model_config):
    return small_model_config


@pytest.fixture(scope="module")
def params(cfg):
    return M.init_params(cfg, jax.random.PRNGKey(0))


@pytest.fixture(scope="module")
def wcfg(cfg):
    return cfg.scaled(sliding_window=16)


@pytest.fixture(scope="module")
def wparams(wcfg):
    return M.init_params(wcfg, jax.random.PRNGKey(0))


def _naive_decode(params, cfg, prompt, n):
    """One-request-at-a-time reference: unpadded prefill + scalar decode."""
    batch = {"tokens": jnp.asarray(np.asarray(prompt)[None, :], jnp.int32)}
    logits, caches = jax.jit(lambda p, b: M.prefill(p, b, cfg))(params, batch)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    out = [int(tok[0, 0])]
    step = jax.jit(lambda p, t, pos, c: M.decode_step(p, t, pos, c, cfg))
    for i in range(n - 1):
        tok, _, caches = step(params, tok, jnp.int32(len(prompt) + i), caches)
        out.append(int(tok[0, 0]))
    return np.asarray(out, np.int32)


def _teacher_forced(params, cfg, prompt, n):
    """Cache-free oracle: re-prefill the whole sequence for every token.
    Exercises none of the ring/paging machinery, so it cross-checks it."""
    toks = list(map(int, prompt))
    out = []
    for _ in range(n):
        logits, _ = M.prefill(params, {"tokens": jnp.asarray([toks],
                                                            jnp.int32)}, cfg)
        t = int(np.asarray(jnp.argmax(logits, -1))[0])
        out.append(t)
        toks.append(t)
    return np.asarray(out, np.int32)


# --------------------------------------------------------------------------
# pool mechanics
# --------------------------------------------------------------------------

def test_paged_pool_admit_read_evict_roundtrip(cfg, params):
    """Admit converts the prefill cache into position-ordered blocks;
    read_slot gathers them back; evict zeroes and frees the blocks."""
    pool = PagedCachePool(cfg, max_batch=2, block_size=8)
    assert pool.blocks_per_slot == cfg.max_seq_len // 8
    plen = 8
    toks = jnp.asarray(np.arange(plen)[None, :], jnp.int32)
    _, cache = jax.jit(lambda p, b: M.prefill(p, b, cfg))(
        params, {"tokens": toks})
    pool.admit(1, cache, plen=plen, n_tokens=12)   # ceil(12/8) = 2 blocks
    assert pool.blocks_in_use == 2
    assert pool.peak_blocks_in_use == 2
    got = pool.read_slot(1)
    for li, c in got.items():
        for key in M.PAGED_KV_KEYS:
            if key not in c:
                continue
            g = np.asarray(c[key])          # (ns, 1, lcap, ...)
            want = np.asarray(cache[li][key]).astype(g.dtype)
            np.testing.assert_array_equal(g[:, :, :plen], want[:, :, :plen])
            # reserved-but-unwritten positions inside the slot's blocks
            # were zeroed at admit (prefill headroom never leaks through)
            assert not g[:, :, plen:16].any()
    # slot 0 untouched
    assert all(not np.asarray(l).any()
               for l in jax.tree.leaves(pool.read_slot(0)))
    pool.evict(1)
    assert pool.blocks_in_use == 0
    assert all(not np.asarray(l).any()
               for l in jax.tree.leaves(pool.read_slot(1)))


def test_paged_pool_free_list_accounting(cfg, params):
    """Blocks freed by evict are reusable; double-admit and free-list
    underflow are loud errors, not corruption."""
    pool = PagedCachePool(cfg, max_batch=2, block_size=16, num_blocks=3)
    toks = jnp.asarray(np.arange(4)[None, :], jnp.int32)
    _, cache = jax.jit(lambda p, b: M.prefill(p, b, cfg))(
        params, {"tokens": toks})
    assert pool.can_admit(20) and not pool.can_admit(40)  # 2 usable blocks
    pool.admit(0, cache, plen=4, n_tokens=20)
    assert not pool.can_admit(20)                # free list exhausted
    with pytest.raises(RuntimeError, match="free list underflow"):
        pool.admit(1, cache, plen=4, n_tokens=20)
    with pytest.raises(RuntimeError, match="already holds"):
        pool.admit(0, cache, plen=4, n_tokens=4)
    pool.evict(0)
    assert pool.can_admit(20)                    # blocks came back
    pool.admit(1, cache, plen=4, n_tokens=20)
    assert pool.blocks_in_use == 2


def test_unsatisfiable_request_rejected_at_submit(cfg, params):
    """A request that could never fit the whole pool is refused up front —
    deferring it would stall the queue forever."""
    sched = Scheduler(params, cfg,
                      ServingConfig(max_batch=1, paged=True, block_size=16,
                                    num_blocks=2))     # 1 usable block
    with pytest.raises(ValueError, match="KV blocks"):
        sched.submit(list(range(1, 9)), 16)            # needs 2 blocks
    sched.submit([1, 2], 8)                            # 1 block: fine


def test_exhaustion_defers_admission_until_blocks_free(cfg, params):
    """A request whose reservation exceeds the free list stays queued
    (FIFO back-pressure) and is served once an eviction frees blocks."""
    sched = Scheduler(params, cfg,
                      ServingConfig(max_batch=2, prompt_bucket=8, paged=True,
                                    block_size=16, num_blocks=3))
    r1 = sched.submit(list(range(1, 9)), 20)     # 2 blocks: whole free list
    r2 = sched.submit([5, 6, 7], 10)             # 1 block: must wait
    sched.step()
    assert sched._slot_rid.tolist() == [r1, -1]  # r2 deferred, slot left free
    assert len(sched.queue) == 1
    assert sched.metrics.deferred_admits == 1
    out = sched.run()
    assert len(out[r1]) == 20 and len(out[r2]) == 10
    assert sched.decode_traces == 1
    # counted once per deferred *request*, not per step spent waiting
    assert sched.metrics.deferred_admits == 1
    want = _naive_decode(params, cfg, [5, 6, 7], 10)
    np.testing.assert_array_equal(out[r2], want)


def test_paged_specs_and_block_math(cfg, wcfg):
    """paged_cache_specs re-lays only the attention-KV leaves; block-need
    arithmetic clamps windowed requests to the ring."""
    specs = M.paged_cache_specs(cfg, batch=4, seq_len=64, num_blocks=9,
                                block_size=8)
    for c in specs.values():
        for key, leaf in c.items():
            if key in M.PAGED_KV_KEYS:
                assert leaf.shape[1:3] == (9, 8)
            else:
                assert leaf.shape[1] == 4        # slot-indexed
    assert cfg.kv_blocks_for(1, 16) == 1
    assert cfg.kv_blocks_for(17, 16) == 2
    assert cfg.window_ring_blocks(16) is None
    assert wcfg.window_ring_blocks(8) == 2       # window 16 / block 8
    assert wcfg.kv_blocks_for(1000, 8) == 2      # ring-capped, not linear


# --------------------------------------------------------------------------
# mesh placement
# --------------------------------------------------------------------------

def test_paged_pool_under_mesh_matches_meshless(cfg, params):
    """Block-table round-trip under the 8-device mesh: paged leaves keep
    the block dim replicated with heads on "model" (cache_pspecs: the
    heads are the major part of each (hkv * hd) row), the table
    replicates, and generations match the meshless run."""
    mesh = make_host_mesh(model=2)
    with dctx.use_mesh(mesh):
        sched = Scheduler(params, cfg,
                          ServingConfig(max_batch=2, prompt_bucket=8,
                                        paged=True, block_size=8),
                          mesh=mesh)
        for c in sched.pool.caches.values():
            for key, leaf in c.items():
                entries = tuple(leaf.sharding.spec)
                if key in M.PAGED_KV_KEYS:
                    assert entries[1] is None    # block dim replicated
                    assert entries[-1] == "model"
        assert sched.pool.block_tables.sharding.is_fully_replicated
        rids = [sched.submit([1, 2, 3, 4, 5], 6), sched.submit([9, 8], 4)]
        out = sched.run()
        assert sched.decode_traces == 1
    plain = Scheduler(params, cfg, ServingConfig(max_batch=2,
                                                 prompt_bucket=8,
                                                 paged=True, block_size=8))
    rids2 = [plain.submit([1, 2, 3, 4, 5], 6), plain.submit([9, 8], 4)]
    out2 = plain.run()
    for ra, rb in zip(rids, rids2):
        np.testing.assert_array_equal(out[ra], out2[rb])


def test_paged_mqa_pool_under_mesh_stays_replicated(cfg):
    """One KV head under model=2: "model" would split each (hkv * hd) row
    inside the head, so the pool stays replicated (cache_pspecs), the
    decode attention runs unsplit on every rank, and generations match
    the meshless run."""
    mqa = cfg.scaled(n_kv_heads=1)
    params = M.init_params(mqa, jax.random.PRNGKey(0))
    scfg = ServingConfig(max_batch=2, prompt_bucket=8, paged=True,
                         block_size=8)
    prompts = ([1, 2, 3, 4, 5], 6), ([9, 8], 4)
    mesh = make_host_mesh(model=2)
    with dctx.use_mesh(mesh):
        sched = Scheduler(params, mqa, scfg, mesh=mesh)
        for c in sched.pool.caches.values():
            for key in M.PAGED_KV_KEYS:
                if key in c:
                    assert c[key].sharding.is_fully_replicated
        rids = [sched.submit(p, n) for p, n in prompts]
        out = sched.run()
    plain = Scheduler(params, mqa, scfg)
    rids2 = [plain.submit(p, n) for p, n in prompts]
    out2 = plain.run()
    for ra, rb in zip(rids, rids2):
        np.testing.assert_array_equal(out[ra], out2[rb])


# --------------------------------------------------------------------------
# scheduler churn: paged == contiguous == sequential oracle
# --------------------------------------------------------------------------

def test_random_churn_both_pools_match_sequential_oracle(cfg, params):
    """A seeded Poisson admit/finish trace with randomized prompt lengths
    *and* budgets runs through the contiguous pool, the paged pool, and a
    sequential (max_batch=1) scheduler: all three emit bit-identical
    tokens, and neither batched run ever recompiles its decode step."""
    rng = np.random.default_rng(11)
    n_req = 9
    t, reqs = 0.0, []
    for _ in range(n_req):
        t += float(rng.exponential(1.0 / 200.0))  # Poisson arrivals
        plen = int(rng.integers(1, 20))
        budget = int(rng.integers(1, 9))          # includes admit-finishers
        reqs.append((rng.integers(0, cfg.vocab_size, plen), budget, t))

    def run_pool(**kw):
        sched = Scheduler(params, cfg,
                          ServingConfig(prompt_bucket=8, **kw))
        base = sched.clock()
        rids = [sched.submit(p, b, arrival_time=base + at)
                for p, b, at in reqs]
        res = sched.run()
        return [res[r] for r in rids], sched

    oracle, _ = run_pool(max_batch=1)
    got_c, sc = run_pool(max_batch=3, paged=False)
    got_p, sp = run_pool(max_batch=3, paged=True, block_size=8)
    assert sc.decode_traces <= 1 and sp.decode_traces <= 1, \
        "slot/block churn must not recompile the decode step"
    for want, a, b in zip(oracle, got_c, got_p):
        np.testing.assert_array_equal(a, want)
        np.testing.assert_array_equal(b, want)
    # the paged run peaked strictly below the contiguous reservation
    assert (sp.metrics.summary()["peak_kv_bytes"]
            < sc.metrics.summary()["peak_kv_bytes"])


# --------------------------------------------------------------------------
# sliding-window serving
# --------------------------------------------------------------------------

def test_sliding_window_serves_end_to_end(wcfg, wparams):
    """Windowed configs serve through the (auto-enabled) paged pool with
    prompts on both sides of the window and decodes straddling it,
    matching the naive ring-decode oracle and the cache-free
    teacher-forced oracle."""
    rng = np.random.default_rng(3)
    short = rng.integers(0, wcfg.vocab_size, 5)   # < window; decode crosses
    long_ = rng.integers(0, wcfg.vocab_size, 29)  # > window at prefill
    sched = Scheduler(wparams, wcfg,
                      ServingConfig(max_batch=2, prompt_bucket=8,
                                    block_size=8))
    assert sched.pool.paged
    assert sched.pool.blocks_per_slot == 2        # the ring, not max_len/8
    rid_s = sched.submit(short, 20)
    rid_l = sched.submit(long_, 10)
    out = sched.run()
    assert sched.decode_traces == 1
    for rid, prompt, n in ((rid_s, short, 20), (rid_l, long_, 10)):
        np.testing.assert_array_equal(
            out[rid], _naive_decode(wparams, wcfg, prompt, n))
        np.testing.assert_array_equal(
            out[rid], _teacher_forced(wparams, wcfg, prompt, n))


def test_sliding_window_matches_unwindowed_when_window_never_binds(
        cfg, params, wcfg, wparams):
    """A request whose prompt+generation stays inside the window must
    decode as if unwindowed — the window mask never cuts a key.  (The
    last-token logits of windowed vs unwindowed prefill agree too.)"""
    prompt = [3, 1, 4, 1]
    n = 6                                         # 4 + 6 <= window 16
    sched = Scheduler(wparams, wcfg, ServingConfig(max_batch=1,
                                                   prompt_bucket=8,
                                                   block_size=8))
    rid = sched.submit(prompt, n)
    got = sched.run()[rid]
    want = _naive_decode(params, cfg, prompt, n)  # unwindowed, same params
    np.testing.assert_array_equal(got, want)


def test_windowed_bucket_rule(wcfg, wparams):
    """Prompts bucket while the padded length stays inside the window;
    past it they run unpadded (pad KV inside the ring would corrupt)."""
    sched = Scheduler(wparams, wcfg, ServingConfig(max_batch=1,
                                                   prompt_bucket=8))
    assert sched._bucket(3) == 8                  # 8 <= window 16
    assert sched._bucket(13) == 16                # 16 <= window 16
    assert sched._bucket(17) == 17                # 24 > window: unpadded


def test_int8_kv_pages_with_scales(cfg, params):
    """Quantized KV caches page too (values + per-position scales)."""
    qcfg = cfg.scaled(kv_cache_dtype="int8")
    qparams = params                              # same tree, new cache dtype
    outs = {}
    for paged in (False, True):
        sched = Scheduler(qparams, qcfg,
                          ServingConfig(max_batch=2, prompt_bucket=8,
                                        paged=paged, block_size=8))
        rid = sched.submit([1, 2, 3, 4, 5], 6)
        outs[paged] = sched.run()[rid]
    np.testing.assert_array_equal(outs[True], outs[False])


@pytest.mark.parametrize("case", ["bf16", "window", "int8", "verify",
                                  "trash"])
def test_paged_append_matches_put_then_gather(cfg, wcfg, case):
    """Each layer of the paged decode step writes its K/V rows into the
    whole pool at its layer index and reads its blocks back
    (``paged_append``, at the targets of ``paged_write_targets``).  Step
    after step that equals writing the rows row by row and gathering the
    slots' blocks, bit for bit: the attention's inputs at every row of a
    slot's blocks, and the pool.  The steps cross block boundaries, wrap
    the sliding-window ring, run speculative verify runs of 3 rows, and
    write past a slot's capacity (rows for the trash block 0, whose
    contents no query reads)."""
    ns, bs, b = 2, 4, 3
    c = wcfg if case == "window" else cfg
    bps = c.window_ring_blocks(bs) or 6
    lcap = bps * bs
    nb = 2 * bps + 1
    # slot 0 holds all its blocks, slot 1 the first four (sentinels after
    # them), slot 2 none (an empty slot: every write goes to the trash)
    table = np.zeros((b, bps), np.int32)
    table[0] = np.arange(1, bps + 1)
    table[1, :4] = np.arange(bps + 1, bps + 5)
    s = 3 if case == "verify" else 1
    pos = np.array({"window": [13, 2, 0], "trash": [lcap - 5, 9, 3]}
                   .get(case, [2, 5, 0]), np.int32)
    steps = 12 if case == "window" else 8
    rng = np.random.default_rng(0)
    if case == "int8":
        leaves = {"k": (np.int8, (8,)), "k_scale": (np.float32, (2,))}
    else:
        leaves = {"k": (jnp.bfloat16, (8,))}

    def draw(shape, dtype):
        if dtype == np.int8:
            return rng.integers(-127, 128, shape).astype(np.int8)
        return rng.standard_normal(shape).astype(dtype)

    pools = {k: draw((ns, nb, bs) + row, dt)
             for k, (dt, row) in leaves.items()}
    tables = jnp.asarray(table)
    live = np.repeat(table != 0, bs, axis=1)      # rows queries may read
    for _ in range(steps):
        widx = (pos % lcap if c.sliding_window else pos)[:, None] \
            + np.arange(s)
        blk = np.where(widx < lcap,
                       table[np.arange(b)[:, None],
                             np.minimum(widx, lcap - 1) // bs], 0)
        got = M.paged_write_targets(c, jnp.asarray(pos), s, tables, bs)
        for want, g in zip((widx, blk, widx % bs), got):
            np.testing.assert_array_equal(np.asarray(g), want)
        for key, (dt, row) in leaves.items():
            rows = draw((ns, b, s) + row, dt)
            ref = pools[key].copy()
            new = jnp.asarray(pools[key])
            for li in range(ns):
                for i in range(b):
                    for j in range(s):
                        ref[li, blk[i, j], widx[i, j] % bs] = rows[li, i, j]
                new, view = M.paged_append(new, li, jnp.asarray(rows[li]),
                                           got[1], got[2], tables)
                want = ref[li][table].reshape((b, lcap) + row)
                np.testing.assert_array_equal(np.asarray(view)[live],
                                              want[live])
            new = np.asarray(new)
            np.testing.assert_array_equal(new[:, 1:], ref[:, 1:])
            pools[key] = new
        pos = pos + s
