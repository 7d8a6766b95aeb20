"""Self-speculative decoding: cheap-mode draft, expensive-mode verify,
exact greedy acceptance.

The engine's bit-exactness invariant — "quant", "quant_tp", and
"pim_sim" all quantize activations *per row* and accumulate the same
integers, so they agree on every logit — is usually stated as a test
property.  This module turns it into throughput.  One round:

1. **Draft**: the cheap mode (``draft_mode``, e.g. ``"quant"``) runs
   ``k - 1`` ordinary single-token decode steps from the batch's current
   tokens, producing a candidate run per slot.  Drafting shares the KV
   pool: its writes land at the run's positions and are overwritten by
   the verify step below, and — for a slot within ``k - 2`` rows of
   capacity — writes past the last reserved row are discarded by the
   model's guarded per-slot write paths (trash-block routing / drop
   semantics, the same guard the verify run applies), never wrapped or
   clamped onto live rows.  Drafting also shares the compiled-artifact
   cache, but executes
   inside :func:`repro.pim.engine.draft_ctx`, whose ``"draft"`` session
   namespace keeps its crossbar-state uploads from LRU-evicting the
   verify path's resident :class:`~repro.pim.engine.ExecutionSession`
   state.
2. **Verify**: the expensive mode (the scheduler's ``cfg.pim_mode``)
   checks all ``k`` positions — current token plus ``k - 1`` drafts — in
   **one** batched :func:`repro.models.model_lib.decode_run_slots` call,
   re-writing every KV row it covers with verify-mode bits.
3. **Accept**: greedy decode makes acceptance a pure integer comparison
   (:func:`accept_length`): the longest prefix of drafts matching the
   verify continuations is committed, plus the verify continuation after
   it — at least one token per round, so even an all-rejected round makes
   forward progress.  Rejected rows hold garbage KV, but every decode
   mask in the stack is position-gated, so the next round's writes land
   on them before any query can see them — rollback is just "don't
   advance ``pos`` past the accepted rows".

Because the committed tokens are, by construction, exactly the greedy
chain the verify mode would have produced alone, speculative decode is
**bit-identical to non-speculative decode** in every mode and for every
draft quality — a bad draft (e.g. an ``"xla"`` float draft against an
integer verify mode) only lowers the acceptance length, never changes a
token.  The speedup comes from amortization: a ``pim_sim`` verify of
``k`` rows costs close to one single-row step (the simulator's per-gate
interpreter overhead dominates its vectorized row math, the same
latency-hiding batching PartitionPIM's partitions buy in hardware), so
``k`` tokens ride one expensive step plus ``k - 1`` cheap ones.

Shapes are pinned: the draft step is the plain ``(B, 1)`` decode jit and
the verify step a single ``(B, k)`` jit, so acceptance-length churn never
recompiles — ``draft_traces`` / ``verify_traces`` count retraces the way
the scheduler's ``decode_traces`` does, and tests pin both to 1.
"""
from __future__ import annotations

import jax.numpy as jnp
import numpy as np

from repro.models import model_lib as M
from repro.models.config import ModelConfig
from repro.pim import engine

__all__ = ["SpeculativeDecoder", "accept_length"]


def accept_length(fed: np.ndarray, verify: np.ndarray) -> int:
    """Tokens committed by one verify run, in ``1..S`` (host-side, exact).

    ``fed`` (S,) is the token run the verify step consumed — the current
    token followed by ``S - 1`` drafts; ``verify`` (S,) the greedy
    continuation it produced at each position.  ``verify[0]`` conditions
    only on already-committed tokens, so it is always accepted;
    ``verify[i]`` is accepted while every draft before it matched its
    verify continuation (``fed[j + 1] == verify[j]`` for ``j < i``) —
    the first mismatch invalidates every later position's prefix.
    """
    n = 1
    s = len(fed)
    while n < s and fed[n] == verify[n - 1]:
        n += 1
    return n


class SpeculativeDecoder:
    """Draft/verify round engine for one scheduler's slot batch.

    Owns the two jitted callables — the ``(B, 1)`` draft step traced
    under ``cfg.scaled(pim_mode=draft_mode)`` inside
    :func:`engine.draft_ctx`, and the ``(B, k)`` verify step traced under
    the scheduler's own ``cfg`` — plus their retrace counters.  Draft and
    verify *should* share the engine's per-row integer quantization
    ("quant"/"quant_tp" drafting for a "pim_sim" or "quant_tp" verify)
    so acceptance stays ~100%; any pairing is still exact, just slower.
    ``donate`` names the arguments both jits donate, as the scheduler's
    plain decode step does (``("caches",)`` for a paged pool): the
    ``caches`` passed to ``run_round`` are then consumed.
    """

    def __init__(self, cfg: ModelConfig, draft_mode: str, draft_k: int,
                 donate=()):
        if draft_k < 2:
            raise ValueError("SpeculativeDecoder needs draft_k >= 2 "
                             "(draft_k=1 is plain decode; the scheduler "
                             "short-circuits it)")
        self.cfg = cfg
        self.draft_mode = draft_mode
        self.k = draft_k
        self.dcfg = cfg.scaled(pim_mode=draft_mode)
        self.draft_traces = 0
        self.verify_traces = 0

        def serve_draft(p, tokens, pos, active, caches, tables):
            self.draft_traces += 1
            # draft_ctx: trace-time session namespace — the drafting
            # pass's pim_sim callbacks (if any) hit a "draft" session
            # pool and can never evict the verify path's resident state
            with engine.draft_ctx():
                return M.decode_step_slots(p, tokens, pos, active, caches,
                                           self.dcfg, block_tables=tables)

        def serve_verify(p, tokens, pos, active, caches, tables):
            self.verify_traces += 1
            return M.decode_run_slots(p, tokens, pos, active, caches,
                                      self.cfg, block_tables=tables)

        self._draft = M.serve_jit(serve_draft, donate=donate)
        self._verify = M.serve_jit(serve_verify, donate=donate)

    def run_round(self, params, tokens: np.ndarray, pos: np.ndarray,
                  active: np.ndarray, caches, tables):
        """One draft + verify round over the whole slot batch.

        ``tokens`` (B, 1) int32 current token per slot, ``pos`` (B,)
        int32 its absolute position, ``active`` (B,) bool the decoding
        mask.  Returns ``(toks_run, verify_tok, new_caches)``: the
        (B, k) run the verify step consumed, its (B, k) greedy
        continuations, and the cache tree with every covered row
        rewritten in verify-mode bits.  The caller commits
        ``verify_tok[slot, :accept_length(...)]`` per slot and advances
        ``pos`` by the (budget/EOS-clipped) emission count.
        """
        b = tokens.shape[0]
        toks_run = np.zeros((b, self.k), np.int32)
        toks_run[:, 0] = tokens[:, 0]
        pos_j = jnp.asarray(pos)
        act_j = jnp.asarray(active)
        for i in range(1, self.k):
            # feed each draft step its token from the host copy: the draft
            # step's own output can carry a mesh sharding (under quant_tp's
            # "model" axis) that would retrace the draft jit on round one
            cur, _, caches = self._draft(params, toks_run[:, i - 1:i],
                                         pos_j + (i - 1), act_j, caches,
                                         tables)
            toks_run[:, i] = np.asarray(cur)[:, 0]
        vt, _, caches = self._verify(params, jnp.asarray(toks_run), pos_j,
                                     act_j, caches, tables)
        return toks_run, np.asarray(vt), caches
