"""The paged decode step writes its new K/V rows in place.

A decode step appends one row per slot (a few per slot in a speculative
verify run) to a KV pool of gigabytes.  The compiled step must therefore
alias every paged pool leaf from its input to its output (the scheduler's
jit donates the pool) and hold no op the size of the pool, or of one
layer of it, that copies, slices or transposes it: only the in-place
scatter of the step's rows.  And because the step consumes the pool it
was given, nothing in the scheduler may read a pre-step pool again —
copy-on-write, the prefix index and the speculative round included.

The configs here compute in float32: in bfloat16 the CPU backend upcasts
around its scatters and gathers with converts the size of the pool, which
the TPU's compiler does not insert, so they would say nothing about the
program the chip runs.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

import repro.configs as configs
from repro.models import model_lib as M
from repro.serving import Scheduler, ServingConfig

from paged_hlo import pool_sized_moves, unaliased_pool_leaves


def _cfg(**kw):
    # three super-blocks, so one layer of the pool has a shape of its own
    return configs.get("qwen1.5-0.5b").smoke().scaled(n_layers=3, **kw)


@pytest.mark.parametrize("kw", [{}, {"sliding_window": 8},
                                {"kv_cache_dtype": "int8"}],
                         ids=["full", "window", "int8"])
def test_decode_program_writes_pool_in_place(kw):
    cfg = _cfg(**kw)
    params = M.init_params(cfg, jax.random.PRNGKey(0))
    s = Scheduler(params, cfg, ServingConfig(max_batch=4, prompt_bucket=8,
                                             paged=True, block_size=4))
    text = s._decode.lower(
        s.params, jnp.asarray(s._tokens), jnp.asarray(s._pos),
        jnp.ones(4, bool), s.pool.caches,
        s.pool.block_tables).compile().as_text()

    assert unaliased_pool_leaves(text, s.pool.caches) == 0
    bad = pool_sized_moves(text, s.pool.caches)
    assert not bad, "pool-sized ops in the decode step:\n" + "\n".join(bad)


def _spy(sched):
    """Record every pool handed to a decoding program of ``sched``."""
    seen = []

    def wrap(program):
        def call(p, tokens, pos, active, caches, tables):
            seen.append(caches)
            return program(p, tokens, pos, active, caches, tables)
        return call

    sched._decode = wrap(sched._decode)
    if sched._spec is not None:
        sched._spec._draft = wrap(sched._spec._draft)
        sched._spec._verify = wrap(sched._spec._verify)
    return seen


@pytest.mark.parametrize("case", ["prefix_cow", "speculative"])
def test_donated_pool_is_never_read_again(case):
    """Dozens of steps over shared prompts whose windowed rings wrap onto
    trie blocks (copy-on-write), or of speculative rounds: every decoding
    program consumes the pool it is given, no later read meets a deleted
    array, and the tokens equal those of a plain run."""
    if case == "prefix_cow":
        # prompts of 7 <= window 8 hit the trie; budgets of 12 wrap the
        # 8-token ring onto the mapped prefix blocks
        cfg = _cfg(sliding_window=8, max_seq_len=64)
        extra, budget = dict(prefix_cache=True), 12
    else:
        cfg = _cfg(pim_mode="xla")
        extra = dict(speculative=True, draft_mode="quant", draft_k=3)
        budget = 30
    params = M.init_params(cfg, jax.random.PRNGKey(1))
    rng = np.random.default_rng(2)
    system = rng.integers(1, cfg.vocab_size, 4)
    prompts = [np.concatenate([system, rng.integers(1, cfg.vocab_size, 3)])
               for _ in range(6)]
    base = dict(max_batch=2, prompt_bucket=4, paged=True, block_size=4)

    sched = Scheduler(params, cfg, ServingConfig(**base, **extra))
    seen = _spy(sched)
    rids = [sched.submit(p, budget) for p in prompts]
    steps = consumed = 0
    while sched.active_slots.any() or len(sched.queue):
        before = sched.pool.caches
        decoding = sched.decoding_slots.any()
        n_seen = len(seen)
        sched.step()
        steps += 1
        assert len(seen) > n_seen or not decoding
        assert all(leaf.is_deleted() for pool in seen[n_seen:]
                   for leaf in jax.tree.leaves(pool)), \
            "a decoding program left its input pool alive"
        if seen[n_seen:] and seen[n_seen] is before:
            # no admit, copy-on-write or reclaim replaced the pool before
            # the decode: the pre-step pool itself is consumed
            assert all(leaf.is_deleted() for leaf in jax.tree.leaves(before))
            consumed += 1
    assert steps >= 24 and consumed >= 12
    if case == "prefix_cow":
        assert sched.pool.cow_copies > 0, "ring wrap never triggered COW"

    ref = Scheduler(params, cfg, ServingConfig(**base))
    ref_rids = [ref.submit(p, budget) for p in prompts]
    ref_out = ref.run()
    for rid, ref_rid in zip(rids, ref_rids):
        np.testing.assert_array_equal(sched.output(rid), ref_out[ref_rid])


@pytest.mark.parametrize("shape", [(3, 1, 4, 2, 16, 24), (1, 1, 8, 2, 80, 40),
                                   (2, 3, 4, 4, 16, 24)],
                         ids=["gqa", "gqa_batch1", "verify_run"])
def test_row_attention_matches_per_head_attention(shape):
    """The paged step's attention over whole (hkv * hd) rows
    (``decode_attention_rows``) gives ``decode_attention``'s result over
    the same cache split into heads, to the rounding of the output dtype:
    a score's products may be added in another order, so a bfloat16
    output may differ by one unit in its last place."""
    from repro.models import attention as attn

    b, sq, h, hkv, d, n = shape
    rng = np.random.default_rng(3)
    valid = rng.random((b, sq, n) if sq > 1 else (b, n)) < 0.7
    valid[..., 0] = True
    for dt in (jnp.bfloat16, jnp.float32):
        q, k, v = (jnp.asarray(rng.standard_normal(sh), dt) for sh in
                   ((b, sq, h, d), (b, n, hkv, d), (b, n, hkv, d)))
        want = np.asarray(jax.jit(
            lambda q, k, v, m: attn.decode_attention(q, k, v, valid=m))(
            q, k, v, valid), np.float32)
        got = np.asarray(jax.jit(attn.decode_attention_rows)(
            q, k.reshape(b, n, hkv * d), v.reshape(b, n, hkv * d), valid),
            np.float32)
        if dt == jnp.bfloat16:
            np.testing.assert_allclose(got, want, rtol=2 ** -7,
                                       atol=2 ** -12)
        else:
            np.testing.assert_allclose(got, want, rtol=0, atol=1e-6)
