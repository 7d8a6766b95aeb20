"""Decode-cache pools for continuous batching: slot-contiguous and paged.

Two pool layouts share one scheduler-facing API (``can_admit`` /
``admit`` / ``evict`` / ``read_slot`` / ``stats``):

:class:`CachePool` is the naive layout — the full decode-cache tree of
``models.model.cache_specs`` at ``(max_batch, max_len)``, allocated once;
every slot reserves worst-case ``max_len`` KV whether its request needs 10
tokens or 10k.  Admits/evicts are single jitted ``dynamic_update_slice``
writes on the batch dim.

:class:`PagedCachePool` is the PartitionPIM move applied to HBM: just as
the paper divides one fixed crossbar into dynamic partitions so
independent work shares the substrate without worst-case reservation, the
paged pool divides each attention-KV leaf into a ``(num_blocks,
block_size, ...)`` physical store shared by all slots (``k``/``v`` hold
each token as one ``hkv * hd`` row: see ``models.model.fold_kv_rows``;
``read_slot`` and ``read_prefix`` give them back as ``(hkv, hd)``).  A
per-slot block table (``(max_batch, blocks_per_slot)`` int32, sentinel
``0`` pointing at a reserved trash block) maps logical token blocks to
physical ones; a host-side free-list allocator reserves exactly
``ceil((prompt + budget) / block_size)`` blocks per request at admit time
(admission defers when the free list is short — never a mid-decode OOM),
and evict returns the blocks.  The jitted decode step reads through a
gather on the block table, whose shape is fixed, so block churn never
recompiles anything, and writes its new rows into the pool in place.

Paging is also what unblocks **sliding-window serving**: a windowed slot
is a *ring* over its block list with capacity ``ceil(window / block) *
block`` — prefill installs the last ``min(prompt, window)`` positions,
decode wraps, and the reservation stops depending on prompt + generation
length entirely.  Recurrent state (ssm/conv, xLSTM c/n/m) and
cross-attention memory are fixed-size per slot and stay slot-indexed in
both pools (``models.model.PAGED_KV_KEYS`` names what pages).

**Prefix caching** (``prefix_cache=True``) lets *requests* share the
paged substrate the way the paper's partitions share the crossbar: a
:class:`PrefixIndex` — a trie over block-sized token runs — maps fully
matched prompt blocks of a new request straight into its block table
(refcount bump, no prefill, no copy), and only the divergent tail is
prefilled (``models.model.prefill(prefix=...)`` resumes at the
block-aligned offset).  Every physical block carries a refcount; a block
returns to the free list — and is zeroed — only when the last reference
drops.  A block's lifetime is therefore::

    free -> private (ref 1, one slot)
         -> shared  (ref > 1: other slots via trie hits/forks, or the
                     index itself, which holds one reference per entry)
         -> COW     (first write into a shared block copies it into a
                     fresh private block first; see ensure_writable)

The COW path fires on the two writes that can land in a shared block: a
fork's divergent continuation entering the partially filled boundary
block (``fork`` is the parallel-sampling n>1 primitive — siblings share
every content block), and a sliding-window ring wrapping onto mapped
prefix blocks.  Unwindowed trie hits never COW: the divergent tail always
starts on a fresh block (matches are block-aligned and capped at
``plen - 1``).  Admission budgets outstanding COW copies against the free
list (``_cow_debt``) so a copy never finds it empty; under pressure,
``can_admit`` reclaims LRU index-only blocks (ref held solely by the
trie) before deferring.

``stats()`` keys (consumed by ``ServingMetrics.sample_pool`` and gated
indirectly through ``benchmarks/check.py``): ``tokens_reserved`` is the
*logical* per-slot reservation (each slot's block-list length x
block_size — a shared block counts once per referencing slot, i.e. what
every request would have allocated privately), while ``tokens_in_use`` is
the *physical* occupancy (allocated blocks x block_size, each block
once).  ``mean_internal_frag`` divides live tokens by the logical
reservation; the physical/logical gap is exactly the prefix-sharing win,
reported via ``blocks_shared``.

Under a mesh both pools are placed by ``dist.cache_pspecs(...,
batch_over_dp=False)``: heads shard over "model" (a paged pool's rows
only where "model" divides the KV heads, else they replicate), but the
slot dim — and
for paged leaves the *block* dim in its place — stays replicated:
continuous batching touches arbitrary slots/blocks every step, and a
sharded dim 1 would make each admit a cross-device scatter.  Block tables
are tiny int32 and replicated.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.dist import partitioning as dpart
from repro.models import model_lib as M
from repro.models.config import ModelConfig

__all__ = ["CachePool", "PagedCachePool", "PrefixIndex"]


def _kv_leaf_bytes(tree) -> int:
    """Bytes of the attention-KV (pageable) leaves of a cache tree."""
    total = 0
    for c in tree.values():
        for key in M.PAGED_KV_KEYS:
            if key in c:
                leaf = c[key]
                total += int(np.prod(leaf.shape)) * jnp.dtype(leaf.dtype
                                                              ).itemsize
    return total


class PrefixIndex:
    """Trie over block-sized token runs -> physical KV blocks.

    Each node is one *full* block of ``block_size`` token ids (the edge
    key) holding the physical block where that run's KV lives; a path
    from the root spells a block-aligned prompt prefix.  The index holds
    its own reference on every registered block (the pool bumps the
    refcount on ``insert``'s adoptions), so shared prefixes survive the
    eviction of the slots that minted them.  ``match`` touches nodes for
    LRU; ``pop_lru_blocks`` releases least-recently-used leaves whose
    block the pool can actually free (index-only references) when the
    free list runs short.
    """

    def __init__(self, block_size: int):
        self.block_size = block_size
        # node: [block_id, children dict keyed by token tuple, lru stamp]
        self._root: Dict[tuple, list] = {}
        self._clock = 0
        self.n_blocks = 0

    def _tick(self) -> int:
        self._clock += 1
        return self._clock

    def match(self, tokens) -> List[int]:
        """Blocks covering the longest fully-block-aligned prefix of
        ``tokens`` present in the index (touches matched nodes)."""
        bs = self.block_size
        out: List[int] = []
        children = self._root
        for i in range(len(tokens) // bs):
            key = tuple(int(t) for t in tokens[i * bs:(i + 1) * bs])
            node = children.get(key)
            if node is None:
                break
            node[2] = self._tick()
            out.append(node[0])
            children = node[1]
        return out

    def insert(self, tokens, blocks: Sequence[int]) -> List[int]:
        """Register ``blocks[i]`` as holding ``tokens[i*bs:(i+1)*bs]``.

        Existing nodes keep their canonical block (the new request was
        mapped onto it anyway if it matched); returns the block ids newly
        adopted — the caller owns bumping their refcount.
        """
        bs = self.block_size
        new: List[int] = []
        children = self._root
        for i, b in enumerate(blocks):
            key = tuple(int(t) for t in tokens[i * bs:(i + 1) * bs])
            node = children.get(key)
            if node is None:
                node = [int(b), {}, 0]
                children[key] = node
                new.append(int(b))
                self.n_blocks += 1
            node[2] = self._tick()
            children = node[1]
        return new

    def blocks(self) -> List[int]:
        """Every registered block id (tests / invariant checks)."""
        out: List[int] = []
        stack = [self._root]
        while stack:
            for node in stack.pop().values():
                out.append(node[0])
                stack.append(node[1])
        return out

    def pop_lru_blocks(self, want: int, reclaimable) -> List[int]:
        """Drop least-recently-used *leaf* entries whose block satisfies
        ``reclaimable(block_id)`` until ``want`` blocks were released (or
        none remain); returns the released ids.  Dropping a leaf may
        expose its parent as the next candidate."""
        released: List[int] = []
        while len(released) < want:
            best = None
            stack = [self._root]
            while stack:
                children = stack.pop()
                for key, node in children.items():
                    if node[1]:
                        stack.append(node[1])
                    elif reclaimable(node[0]) and (best is None
                                                   or node[2] < best[0]):
                        best = (node[2], children, key, node)
            if best is None:
                break
            _, children, key, node = best
            del children[key]
            self.n_blocks -= 1
            released.append(node[0])
        return released

    def drop_all(self) -> List[int]:
        """Forget every entry; returns all previously held block ids."""
        out = self.blocks()
        self._root = {}
        self.n_blocks = 0
        return out


class CachePool:
    """Decode caches for ``max_batch`` slots of up to ``max_len`` tokens.

    ``caches`` is the live cache tree threaded through the jitted decode
    step; the scheduler re-binds it after every step.  ``assign`` expects a
    single-request prefill cache (batch dim 1) produced at the pool's
    ``max_len`` capacity (i.e. with ``cfg.max_seq_len == max_len``).
    """

    paged = False
    block_tables = None            # uniform scheduler interface

    def __init__(self, cfg: ModelConfig, max_batch: int,
                 max_len: Optional[int] = None, *, mesh=None):
        self.max_batch = max_batch
        self.max_len = max_len or cfg.max_seq_len
        self.max_tokens = self.max_len          # per-slot token capacity
        specs = M.cache_specs(cfg, max_batch, self.max_len)
        self.kv_reserved_bytes = _kv_leaf_bytes(specs)
        self.caches: Dict[str, Any] = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), specs,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        if mesh is not None:
            self.caches = jax.device_put(self.caches, dpart.tree_shardings(
                dpart.cache_pspecs(self.caches, mesh, batch_over_dp=False),
                mesh))
        self._assigned: set = set()   # occupied slots (stats only)

        def pool_assign(pool, request_cache, slot):
            return jax.tree.map(
                lambda c, r: jax.lax.dynamic_update_slice_in_dim(
                    c, r.astype(c.dtype), slot, axis=1),
                pool, request_cache)

        def pool_evict(pool, slot):
            return jax.tree.map(
                lambda c: jax.lax.dynamic_update_slice_in_dim(
                    c, jnp.zeros(c.shape[:1] + (1,) + c.shape[2:], c.dtype),
                    slot, axis=1),
                pool)

        def pool_read(pool, slot):
            return jax.tree.map(
                lambda c: jax.lax.dynamic_slice_in_dim(c, slot, 1, axis=1),
                pool)

        self._assign = jax.jit(pool_assign)
        self._evict = jax.jit(pool_evict)
        self._read = jax.jit(pool_read)

    def assign(self, slot: int, request_cache) -> None:
        """Install a (batch-1) prefill cache into ``slot``."""
        self.caches = self._assign(self.caches, request_cache,
                                   jnp.int32(slot))
        self._assigned.add(int(slot))

    def evict(self, slot: int) -> None:
        """Zero ``slot`` (logical free; keeps stale KV out of the pool)."""
        self.caches = self._evict(self.caches, jnp.int32(slot))
        self._assigned.discard(int(slot))

    def read_slot(self, slot: int):
        """The (batch-1) cache view of ``slot`` — tests/inspection."""
        return self._read(self.caches, jnp.int32(slot))

    # ---- uniform pool interface -------------------------------------

    def can_admit(self, n_tokens: int) -> bool:
        """Contiguous slots always fit (capacity was reserved up front)."""
        return True

    @property
    def free_blocks(self) -> int:
        """Free capacity in slot units (the router's least-loaded signal;
        the contiguous pool's allocation granularity is one slot)."""
        return self.max_batch - len(self._assigned)

    def admit(self, slot: int, request_cache, plen: int, n_tokens: int,
              *, prompt=None, prefix_blocks=None) -> None:
        self.assign(slot, request_cache)

    def stats(self) -> Dict[str, float]:
        """Occupancy snapshot.  The contiguous pool's KV bytes are its
        static worst-case reservation — that constant is exactly what the
        paged pool's ``bytes_in_use`` undercuts on long-tail traces.
        ``tokens_reserved`` is the static reservation; ``tokens_in_use``
        counts only occupied slots (each at full ``max_len`` capacity —
        slot-contiguous rows have no finer granularity)."""
        return {
            "kv_bytes_in_use": float(self.kv_reserved_bytes),
            "kv_bytes_reserved": float(self.kv_reserved_bytes),
            "blocks_in_use": float(self.max_batch),
            "blocks_total": float(self.max_batch),
            "tokens_reserved": float(self.max_batch * self.max_len),
            "tokens_in_use": float(len(self._assigned) * self.max_len),
        }


class PagedCachePool:
    """Block-paged decode caches: attention KV in shared physical blocks.

    ``block_size`` tokens per block; ``num_blocks`` physical blocks per KV
    leaf (default: full parity with the contiguous pool — every slot can
    hold ``blocks_per_slot`` blocks — plus the reserved trash block; pass
    something smaller to actually oversubscribe).  Block 0 is never
    allocated: it is the sentinel target of unassigned block-table entries,
    absorbing the garbage writes of inactive decode slots.

    ``admit`` expects a (batch-1) prefill cache and the request's true
    prompt length: the paged leaves are *converted* — gathered from the
    prefill layout (dense, or the windowed ring) into position-ordered
    logical blocks, invalid positions zeroed — and scattered to the slot's
    physical blocks in one jitted op per prefill bucket shape.  With
    ``prefix_blocks`` (a trie hit from :meth:`prefix_match`) the matched
    blocks are mapped by reference and only the tail cache — emitted by
    the resumed prefill, positions ``m..plen-1`` — is scattered, at block
    offset ``m``.

    ``prefix_cache=True`` attaches the :class:`PrefixIndex` and enables
    per-block refcounting/COW (see the module docstring for the block
    lifetime).  The caller is responsible for gating it to stacks whose
    KV is position-independent (no recurrent blocks, no MoE token
    dropping); windowed prompts participate only while ``plen <= window``
    — up to there the ring layout is the dense layout.
    """

    paged = True

    def __init__(self, cfg: ModelConfig, max_batch: int,
                 max_len: Optional[int] = None, *, block_size: int = 16,
                 num_blocks: Optional[int] = None, mesh=None,
                 prefix_cache: bool = False):
        if block_size < 1:
            raise ValueError("block_size must be >= 1")
        self.cfg = cfg
        self.max_batch = max_batch
        self.max_len = max_len or cfg.max_seq_len
        self.block_size = block_size
        ring = cfg.window_ring_blocks(block_size)
        self.blocks_per_slot = (ring if ring is not None
                                else -(-self.max_len // block_size))
        self.lcap = self.blocks_per_slot * block_size   # logical tokens/slot
        # windowed slots can generate forever (the ring wraps); unwindowed
        # ones are bounded by the configured horizon, NOT the block-rounded
        # lcap — the layout must never admit a request the contiguous pool
        # would reject (positions past max_len are outside the declared
        # context even when rounding leaves physical room)
        self.max_tokens = (None if cfg.sliding_window else self.max_len)
        self.num_blocks = (num_blocks if num_blocks is not None
                           else max_batch * self.blocks_per_slot + 1)
        if self.num_blocks < 2:
            raise ValueError("num_blocks must be >= 2 (block 0 is the "
                             "reserved sentinel/trash block)")
        self._mesh = mesh

        specs = M.paged_cache_specs(cfg, max_batch, self.max_len,
                                    self.num_blocks, block_size)
        per_pool = _kv_leaf_bytes(specs)
        self.block_bytes = per_pool // self.num_blocks  # all leaves/layers
        self._has_paged_leaves = per_pool > 0
        self.caches: Dict[str, Any] = jax.tree.map(
            lambda s: jnp.zeros(s.shape, s.dtype), specs,
            is_leaf=lambda x: isinstance(x, jax.ShapeDtypeStruct))
        if mesh is not None:
            rows = {key: cfg.n_kv_heads for key in M.FOLDED_KV_KEYS}
            self.caches = jax.device_put(self.caches, dpart.tree_shardings(
                dpart.cache_pspecs(self.caches, mesh, batch_over_dp=False,
                                   row_heads=rows), mesh))

        # host allocator state: free-list (LIFO keeps reuse warm), per-slot
        # block lists, per-block refcounts, and the sentinel-padded table
        # mirrored to device
        self._free: List[int] = list(range(self.num_blocks - 1, 0, -1))
        self._slot_blocks: List[List[int]] = [[] for _ in range(max_batch)]
        self._table = np.zeros((max_batch, self.blocks_per_slot), np.int32)
        self._table_dev = None
        self._ref = np.zeros(self.num_blocks, np.int64)
        # blocks each slot may yet overwrite while shared (boundary block
        # of a fork, mapped prefix under a wrapping ring): the free list
        # keeps this many blocks in reserve so COW never underflows
        self._cow_debt = np.zeros(max_batch, np.int64)
        self.cow_copies = 0
        self.peak_blocks_in_use = 0
        self.prefix = (PrefixIndex(block_size)
                       if prefix_cache and self._has_paged_leaves else None)

        window = cfg.sliding_window
        lcap, bs = self.lcap, block_size

        def pool_assign(pool, request_cache, table_row, slot, plen, start):
            def paged_leaf(c, rleaf):
                # rleaf (ns, 1, cap_p, ...): dense positions 0..cap_p-1, or
                # — windowed — position p at ring index p % cap_p.  ``start``
                # offsets the source read: a packed prefill emits several
                # segments in one (1, L) stream, and each segment's admit
                # reads its own span ``start..start+plen-1`` of it.
                cap_p = rleaf.shape[2]
                r = jnp.arange(lcap)
                if window:
                    # same congruence the paged decode read applies (the
                    # windowed pool's lcap IS the ring capacity)
                    p_r, valid = M.ring_slot_positions(plen - 1, r, lcap,
                                                       window)
                else:
                    p_r = r
                    valid = r < plen
                src = (start + p_r) % cap_p
                logical = jnp.take(rleaf[:, 0], src, axis=1)  # (ns, lcap,...)
                vshape = (1, lcap) + (1,) * (logical.ndim - 2)
                logical = jnp.where(valid.reshape(vshape), logical, 0)
                blocks = logical.reshape(
                    (logical.shape[0], self.blocks_per_slot, bs)
                    + c.shape[3:]).astype(c.dtype)
                # sentinel-padded rows scatter their tail into trash block 0
                return c.at[:, table_row].set(blocks)

            out = {}
            for li, c in pool.items():
                oc = {}
                for key, leaf in c.items():
                    if key in M.PAGED_KV_KEYS:
                        oc[key] = paged_leaf(leaf, request_cache[li][key])
                    else:
                        oc[key] = jax.lax.dynamic_update_slice_in_dim(
                            leaf, request_cache[li][key].astype(leaf.dtype),
                            slot, axis=1)
                out[li] = oc
            return out

        def pool_assign_tail(pool, request_cache, table_row, slot, plen, m):
            # tail-resume install: rleaf (ns, 1, cap_t, ...) holds dense
            # positions m..plen-1 at indices 0..plen-m-1 (the resumed
            # prefill emits the tail only, unpadded to capacity); the
            # mapped prefix entries of table_row are sentinel 0, routing
            # their (masked-to-zero) writes into the trash block while the
            # shared prefix blocks stay untouched.  Valid for windowed
            # slots too: prefix mapping requires plen <= window, where the
            # ring layout IS the dense layout.
            def tail_leaf(c, rleaf):
                cap_t = rleaf.shape[2]
                r = jnp.arange(lcap)
                src = jnp.clip(r - m, 0, cap_t - 1)
                valid = (r >= m) & (r < plen)
                logical = jnp.take(rleaf[:, 0], src, axis=1)
                vshape = (1, lcap) + (1,) * (logical.ndim - 2)
                logical = jnp.where(valid.reshape(vshape), logical, 0)
                blocks = logical.reshape(
                    (logical.shape[0], self.blocks_per_slot, bs)
                    + c.shape[3:]).astype(c.dtype)
                return c.at[:, table_row].set(blocks)

            out = {}
            for li, c in pool.items():
                oc = {}
                for key, leaf in c.items():
                    if key in M.PAGED_KV_KEYS:
                        oc[key] = tail_leaf(leaf, request_cache[li][key])
                    else:
                        oc[key] = jax.lax.dynamic_update_slice_in_dim(
                            leaf, request_cache[li][key].astype(leaf.dtype),
                            slot, axis=1)
                out[li] = oc
            return out

        def pool_evict(pool, table_row, slot):
            out = {}
            for li, c in pool.items():
                oc = {}
                for key, leaf in c.items():
                    if key in M.PAGED_KV_KEYS:
                        z = jnp.zeros((leaf.shape[0], self.blocks_per_slot,
                                       bs) + leaf.shape[3:], leaf.dtype)
                        oc[key] = leaf.at[:, table_row].set(z)
                    else:
                        oc[key] = jax.lax.dynamic_update_slice_in_dim(
                            leaf, jnp.zeros(
                                leaf.shape[:1] + (1,) + leaf.shape[2:],
                                leaf.dtype), slot, axis=1)
                out[li] = oc
            return out

        def pool_read(pool, table_row, slot):
            out = {}
            for li, c in pool.items():
                oc = {}
                for key, leaf in c.items():
                    if key in M.PAGED_KV_KEYS:
                        g = leaf[:, table_row]          # (ns, bps, bs, ...)
                        oc[key] = M.unfold_kv_rows(
                            key, g.reshape((g.shape[0], 1, lcap)
                                           + g.shape[3:]), cfg)
                    else:
                        oc[key] = jax.lax.dynamic_slice_in_dim(
                            leaf, slot, 1, axis=1)
                out[li] = oc
            return out

        def pool_read_prefix(pool, blocks):
            # dense (ns, 1, nb*bs, ...) gather of the mapped prefix — the
            # ``prefix`` operand of the tail-resume prefill (one trace per
            # distinct prefix block count)
            nb = blocks.shape[0]
            out = {}
            for li, c in pool.items():
                oc = {}
                for key, leaf in c.items():
                    if key in M.PAGED_KV_KEYS:
                        g = leaf[:, blocks]             # (ns, nb, bs, ...)
                        oc[key] = M.unfold_kv_rows(
                            key, g.reshape((g.shape[0], 1, nb * bs)
                                           + g.shape[3:]), cfg)
                if oc:
                    out[li] = oc
            return out

        def pool_cow(pool, src, dst):
            out = {}
            for li, c in pool.items():
                oc = {}
                for key, leaf in c.items():
                    if key in M.PAGED_KV_KEYS:
                        oc[key] = leaf.at[:, dst].set(
                            jax.lax.dynamic_index_in_dim(leaf, src, axis=1,
                                                         keepdims=False))
                    else:
                        oc[key] = leaf
                out[li] = oc
            return out

        def pool_zero_block(pool, b):
            out = {}
            for li, c in pool.items():
                oc = {}
                for key, leaf in c.items():
                    if key in M.PAGED_KV_KEYS:
                        oc[key] = leaf.at[:, b].set(
                            jnp.zeros((leaf.shape[0],) + leaf.shape[2:],
                                      leaf.dtype))
                    else:
                        oc[key] = leaf
                out[li] = oc
            return out

        def pool_copy_state(pool, src, dst):
            # fork: duplicate the slot-indexed (non-paged) leaves of
            # ``src`` into ``dst``; paged leaves are shared by reference
            out = {}
            for li, c in pool.items():
                oc = {}
                for key, leaf in c.items():
                    if key in M.PAGED_KV_KEYS:
                        oc[key] = leaf
                    else:
                        row = jax.lax.dynamic_slice_in_dim(leaf, src, 1,
                                                           axis=1)
                        oc[key] = jax.lax.dynamic_update_slice_in_dim(
                            leaf, row, dst, axis=1)
                out[li] = oc
            return out

        self._assign = jax.jit(pool_assign)
        self._assign_tail = jax.jit(pool_assign_tail)
        self._evict = jax.jit(pool_evict)
        self._read = jax.jit(pool_read)
        self._read_prefix = jax.jit(pool_read_prefix)
        self._cow = jax.jit(pool_cow)
        self._zero_block = jax.jit(pool_zero_block)
        self._copy_state = jax.jit(pool_copy_state)

    # ---- allocator ---------------------------------------------------

    @property
    def blocks_in_use(self) -> int:
        return self.num_blocks - 1 - len(self._free)

    @property
    def free_blocks(self) -> int:
        """Blocks on the free list (the router's least-loaded signal;
        trie-held ref==1 blocks are reclaimable but not counted — they
        are *cache*, and a router should prefer a replica with genuinely
        idle capacity over one that must evict its prefix index)."""
        return len(self._free)

    @property
    def has_shared(self) -> bool:
        """Any block referenced more than once (COW checks are needed)."""
        return bool((self._ref > 1).any())

    def blocks_needed(self, n_tokens: int) -> int:
        if not self._has_paged_leaves:   # pure-recurrent stack: nothing pages
            return 0
        return min(self.cfg.kv_blocks_for(n_tokens, self.block_size),
                   self.blocks_per_slot)

    def _will_wrap(self, n_tokens: int) -> bool:
        """Whether a windowed request writing ``n_tokens`` wraps its ring
        (and may therefore overwrite mapped/registered prefix blocks)."""
        return bool(self.cfg.sliding_window) and n_tokens > self.lcap

    def _reclaim(self, want: int) -> None:
        """Free up to ``want`` index-only blocks (held solely by the
        prefix trie, LRU first) back to the free list."""
        if self.prefix is None:
            return
        dropped = self.prefix.pop_lru_blocks(
            want, lambda b: self._ref[b] == 1)
        for b in dropped:
            self._ref[b] = 0
            self.caches = self._zero_block(self.caches, jnp.int32(b))
        self._free.extend(reversed(dropped))

    def can_admit(self, n_tokens: int, prefix_tokens: int = 0,
                  extra_reserved: int = 0) -> bool:
        """Whether the free list covers a request writing ``n_tokens``
        positions, of which the leading ``prefix_tokens`` arrive mapped
        from the prefix index (no allocation).  Budgets the request's own
        worst-case COW copies plus every outstanding debt, reclaiming
        LRU index-only blocks under pressure before giving up.

        ``extra_reserved``: blocks already spoken for by earlier members
        of the same batch (packed prefill collects several admits before
        allocating any — each check must budget its predecessors)."""
        need = self.blocks_needed(n_tokens)
        mapped = min(prefix_tokens // self.block_size, need)
        fresh = need - mapped
        debt = 0
        if self._will_wrap(n_tokens):
            # the wrapping ring may COW every mapped block and (with the
            # index attached) every own block it registers
            debt = mapped + (fresh if self.prefix is not None else 0)
        want = fresh + debt + int(self._cow_debt.sum()) + extra_reserved
        if want > len(self._free):
            self._reclaim(want - len(self._free))
        return want <= len(self._free)

    @property
    def block_tables(self) -> jnp.ndarray:
        """Device copy of the (max_batch, blocks_per_slot) table,
        replicated under the pool's mesh."""
        if self._table_dev is None:
            t = jnp.asarray(self._table)
            if self._mesh is not None:
                t = jax.device_put(t, jax.sharding.NamedSharding(
                    self._mesh, jax.sharding.PartitionSpec()))
            self._table_dev = t
        return self._table_dev

    # ---- prefix index ------------------------------------------------

    def prefix_match(self, prompt) -> Tuple[int, List[int]]:
        """``(m, blocks)``: the longest block-aligned trie prefix of
        ``prompt``, capped at ``plen - 1`` so the divergent tail always
        holds at least one token (the resumed prefill must produce the
        request's first-token logits).  Windowed prompts match only while
        ``plen <= window`` — past it the ring layout diverges from the
        dense one the index describes."""
        if self.prefix is None:
            return 0, []
        plen = len(prompt)
        w = self.cfg.sliding_window
        if w and plen > w:
            return 0, []
        blocks = self.prefix.match(prompt)
        while blocks and len(blocks) * self.block_size > plen - 1:
            blocks.pop()
        return len(blocks) * self.block_size, blocks

    def read_prefix(self, blocks: Sequence[int]):
        """Dense ``(ns, 1, m, ...)`` view of a mapped prefix's paged
        leaves — the ``prefix`` operand of ``models.model.prefill``."""
        return self._read_prefix(self.caches,
                                 jnp.asarray(list(blocks), jnp.int32))

    def _register(self, slot: int, prompt, plen: int, wrap: bool) -> None:
        """Adopt the slot's fully covered prompt blocks into the index
        (the index holds one reference per adopted block)."""
        w = self.cfg.sliding_window
        if w and plen > w:
            return          # ring layout != dense past the window
        nfull = min(plen // self.block_size, len(self._slot_blocks[slot]))
        if nfull <= 0:
            return
        new = self.prefix.insert(prompt, self._slot_blocks[slot][:nfull])
        for b in new:
            self._ref[b] += 1
        if wrap:
            # its own registered blocks are now shared with the index and
            # in the overwrite path of the wrapping ring
            self._cow_debt[slot] += len(new)

    def clear_prefix(self) -> int:
        """Drop every prefix-index reference (a block returns to the free
        list when that was its last one); returns blocks freed."""
        if self.prefix is None:
            return 0
        freed = 0
        for b in self.prefix.drop_all():
            self._ref[b] -= 1
            if self._ref[b] <= 0:
                self._ref[b] = 0
                self.caches = self._zero_block(self.caches, jnp.int32(b))
                self._free.append(int(b))
                freed += 1
        return freed

    # ---- pool ops ----------------------------------------------------

    def admit(self, slot: int, request_cache, plen: int, n_tokens: int,
              *, prompt=None, prefix_blocks=None, start: int = 0) -> None:
        """Reserve blocks for ``n_tokens`` total positions and install the
        (batch-1) prefill cache of a ``plen``-token prompt into ``slot``.

        ``prefix_blocks`` (from :meth:`prefix_match`) maps the matched
        blocks by reference — ``request_cache`` is then the *tail* cache
        of the resumed prefill (positions ``m..plen-1``), scattered at
        block offset ``m``.  ``prompt`` (when the prefix index is
        attached) registers the request's fully covered blocks for future
        hits.  ``start`` offsets the source read into ``request_cache``
        — a packed prefill emits several segments in one stream and each
        segment admits from its own span (incompatible with
        ``prefix_blocks``; packed segments are trie misses by
        construction).  Callers must check :meth:`can_admit` first; an
        insufficient free list here is a scheduler bug, not back-pressure.

        A *chunked* admit passes ``plen < plen_total`` with ``n_tokens``
        covering the whole request — the full reservation happens up
        front (mid-prefill block reservation), later chunks land via
        :meth:`extend`, and ``prompt=None`` defers trie registration to
        :meth:`register_prefix` at completion.
        """
        if self._slot_blocks[slot]:
            raise RuntimeError(f"slot {slot} already holds blocks")
        need = self.blocks_needed(n_tokens)
        mapped = [int(b) for b in (prefix_blocks or [])]
        if len(mapped) > need:
            raise RuntimeError(
                f"slot {slot}: prefix of {len(mapped)} blocks exceeds the "
                f"reservation of {need}")
        fresh_n = need - len(mapped)
        if fresh_n > len(self._free):
            raise RuntimeError(
                f"free list underflow: slot {slot} needs {fresh_n} blocks, "
                f"{len(self._free)} free — check can_admit() before admit")
        for b in mapped:
            self._ref[b] += 1
        fresh = [self._free.pop() for _ in range(fresh_n)]
        for b in fresh:
            self._ref[b] = 1
        blocks = mapped + fresh
        self._slot_blocks[slot] = blocks
        self._table[slot] = 0
        self._table[slot, :need] = blocks
        self._table_dev = None
        self.peak_blocks_in_use = max(self.peak_blocks_in_use,
                                      self.blocks_in_use)
        if mapped:
            m = len(mapped) * self.block_size
            tail_row = self._table[slot].copy()
            tail_row[:len(mapped)] = 0  # prefix blocks are never re-written
            self.caches = self._assign_tail(
                self.caches, request_cache, jnp.asarray(tail_row),
                jnp.int32(slot), jnp.int32(plen), jnp.int32(m))
        else:
            self.caches = self._assign(self.caches, request_cache,
                                       jnp.asarray(self._table[slot]),
                                       jnp.int32(slot), jnp.int32(plen),
                                       jnp.int32(start))
        wrap = self._will_wrap(n_tokens)
        if wrap:
            self._cow_debt[slot] += len(mapped)
        if self.prefix is not None and prompt is not None:
            self._register(slot, prompt, plen, wrap)

    def extend(self, slot: int, request_cache, m: int, new_len: int) -> None:
        """Install a prefill continuation chunk: ``request_cache`` holds
        positions ``m..new_len-1`` of ``slot``'s prompt (the tail cache a
        resumed prefill emits), scattered at block offset ``m`` into the
        slot's *already reserved* blocks (see the chunked note on
        :meth:`admit`).  ``m`` must be block-aligned; the first ``m //
        block_size`` table entries are sentinel'd so the chunk's writes
        never touch blocks earlier chunks filled."""
        if not self._slot_blocks[slot]:
            raise RuntimeError(f"slot {slot} holds no blocks to extend")
        if m % self.block_size:
            raise ValueError(f"extend offset {m} not block-aligned")
        row = self._table[slot].copy()
        row[:m // self.block_size] = 0
        self.caches = self._assign_tail(
            self.caches, request_cache, jnp.asarray(row),
            jnp.int32(slot), jnp.int32(new_len), jnp.int32(m))

    def slot_blocks(self, slot: int) -> List[int]:
        """The slot's physical block ids in logical order (a copy) — the
        chunked scheduler reads back the leading ``done // block_size``
        of these as the prefix operand of the next chunk's resume."""
        return list(self._slot_blocks[slot])

    def register_prefix(self, slot: int, prompt, plen: int,
                        n_tokens: int) -> None:
        """Adopt a completed chunked prefill's prompt blocks into the
        prefix index — the deferred ``prompt=`` leg of :meth:`admit`
        (chunked admits pass ``prompt=None``; registering a half-written
        prompt would serve bogus hits)."""
        if self.prefix is None or prompt is None:
            return
        self._register(slot, prompt, plen, self._will_wrap(n_tokens))

    def fork(self, src: int, dst: int, pos: int, n_tokens: int) -> None:
        """Map ``src``'s content blocks (positions ``< pos``) into ``dst``
        by reference — the parallel-sampling (n>1) primitive: no prefill,
        no copy.  Fresh private blocks cover the rest of ``dst``'s
        reservation; the slot-indexed (non-paged) leaves are copied.  The
        first divergent write into the shared boundary block — the
        partially filled one when ``pos % block_size != 0`` — copies on
        write (:meth:`ensure_writable`)."""
        if self._slot_blocks[dst]:
            raise RuntimeError(f"slot {dst} already holds blocks")
        need = self.blocks_needed(n_tokens)
        content = min(-(-int(pos) // self.block_size), need)
        shared = [int(b) for b in self._slot_blocks[src][:content]]
        fresh_n = need - len(shared)
        if fresh_n > len(self._free):
            raise RuntimeError(
                f"free list underflow: fork into slot {dst} needs "
                f"{fresh_n} blocks, {len(self._free)} free")
        for b in shared:
            self._ref[b] += 1
        fresh = [self._free.pop() for _ in range(fresh_n)]
        for b in fresh:
            self._ref[b] = 1
        blocks = shared + fresh
        self._slot_blocks[dst] = blocks
        self._table[dst] = 0
        self._table[dst, :need] = blocks
        self._table_dev = None
        self.peak_blocks_in_use = max(self.peak_blocks_in_use,
                                      self.blocks_in_use)
        if shared:
            if self._will_wrap(n_tokens):
                self._cow_debt[dst] += len(shared)
            elif int(pos) % self.block_size:
                self._cow_debt[dst] += 1   # the shared boundary block
        self.caches = self._copy_state(self.caches, jnp.int32(src),
                                       jnp.int32(dst))

    def ensure_writable(self, slot: int, pos: int) -> int:
        """Guarantee the block receiving ``slot``'s write at ``pos`` is
        private: a shared target is copied-on-write into a fresh block
        first (the other referents — sibling slots, the prefix index —
        keep the original bits).  Returns copies made (0 or 1).  The
        scheduler calls this for every active slot before each decode
        step; ``has_shared`` short-circuits the common all-private case.
        """
        blocks = self._slot_blocks[slot]
        if not blocks:
            return 0
        p = int(pos) % self.lcap if self.cfg.sliding_window else int(pos)
        bi = p // self.block_size
        if bi >= len(blocks):
            return 0
        b = blocks[bi]
        if self._ref[b] <= 1:
            return 0
        if not self._free:
            self._reclaim(1)
        if not self._free:
            raise RuntimeError(
                f"free list underflow on COW for slot {slot} (block {b}) "
                f"— admission under-budgeted its _cow_debt")
        new = self._free.pop()
        self._ref[new] = 1
        self._ref[b] -= 1
        blocks[bi] = new
        self._table[slot, bi] = new
        self._table_dev = None
        self.caches = self._cow(self.caches, jnp.int32(b), jnp.int32(new))
        self.cow_copies += 1
        if self._cow_debt[slot] > 0:
            self._cow_debt[slot] -= 1
        self.peak_blocks_in_use = max(self.peak_blocks_in_use,
                                      self.blocks_in_use)
        return 1

    def evict(self, slot: int) -> None:
        """Drop the slot's block references: a block is zeroed and
        returned to the free list only when its refcount hits zero —
        blocks still shared with other slots or held by the prefix index
        survive untouched (the zeroing scatter routes their table entries
        to the trash block)."""
        blocks = self._slot_blocks[slot]
        if blocks:
            row = self._table[slot].copy()
            freed = []
            for i, b in enumerate(blocks):
                self._ref[b] -= 1
                if self._ref[b] <= 0:
                    self._ref[b] = 0
                    freed.append(b)
                else:
                    row[i] = 0   # still referenced: zero the trash instead
            self.caches = self._evict(self.caches, jnp.asarray(row),
                                      jnp.int32(slot))
            self._free.extend(reversed(freed))
        self._slot_blocks[slot] = []
        self._table[slot] = 0
        self._cow_debt[slot] = 0
        self._table_dev = None

    def read_slot(self, slot: int):
        """The (batch-1) *logical* cache view of ``slot``: paged leaves are
        gathered back to position-ordered ``(ns, 1, lcap, ...)`` arrays
        (sentinel blocks read the trash block — callers mask by length),
        slot-state leaves sliced as-is.  Tests/inspection."""
        return self._read(self.caches, jnp.asarray(self._table[slot]),
                          jnp.int32(slot))

    def stats(self) -> Dict[str, float]:
        """Occupancy snapshot for ``ServingMetrics.sample_pool`` (see the
        module docstring for the tokens_reserved / tokens_in_use
        contract)."""
        used = self.blocks_in_use
        reserved = sum(len(b) for b in self._slot_blocks)
        return {
            "kv_bytes_in_use": float(used * self.block_bytes),
            "kv_bytes_reserved": float((self.num_blocks - 1)
                                       * self.block_bytes),
            "blocks_in_use": float(used),
            "blocks_total": float(self.num_blocks - 1),
            # logical per-slot reservation: a shared block counts once per
            # referencing slot (what each request would own privately)
            "tokens_reserved": float(reserved * self.block_size),
            # physical occupancy: every allocated block exactly once
            "tokens_in_use": float(used * self.block_size),
            "blocks_shared": float(int((self._ref > 1).sum())),
            "prefix_blocks": float(self.prefix.n_blocks
                                   if self.prefix is not None else 0),
            "cow_copies": float(self.cow_copies),
        }
