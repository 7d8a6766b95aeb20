"""Operations and bytes the served decoder needs, from its shapes.

Counted from the configuration's widths and from what each step did (the
prompts it admitted, the rows it decoded), not from the program's HLO, so
no change to the program can change the yardstick.

* ``kernel_calls``: the int8 matmuls of the ``quant`` lowering, one per
  linear per layer per call of a jitted step, with the rows that call
  passes (a decode step passes every slot; a prefill its padded prompt).
  Under ``quant_tp`` over ``r`` chips of the "model" axis, each linear is
  one call on every chip, of that chip's tile: ``(M, K/r, N)`` where the
  program splits the weight's rows, else ``(M, K, ceil(N/r))``.  Which
  one follows the program's ``dist.partitioning.tp_shard_dim`` rule, copied
  here (``tile``) and held to it by a test.  With ``r = 1`` that is the
  whole ``(M, K, N)``, once.
* ``model_ops``: the operations of the tokens really processed, split by
  the arithmetic they run in: int8 linears, and the rest (attention's two
  products over each token's real context, the output table over each
  emitted token's row) in bf16.
"""
from __future__ import annotations

import json
import os
from typing import Dict, Iterable, List, Tuple

from servebench.window import StepRecord

PEAKS = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def peaks(device_kind: str) -> Dict[str, float]:
    """The published peaks of ``device_kind``; an unknown device is an
    error, never a default."""
    with open(PEAKS) as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in "
                       f"{PEAKS}")
    return table[device_kind]


def linear_shapes(cfg) -> List[Tuple[int, int]]:
    """(K, N) of every quantized linear of one layer."""
    d, h, hk, hd, ff = cfg.d_model, cfg.n_heads, cfg.n_kv_heads, cfg.hd, \
        cfg.d_ff
    out = [(d, h * hd), (d, hk * hd), (d, hk * hd), (h * hd, d),
           (d, ff), (ff, d)]
    if cfg.gated_mlp:
        out.append((d, ff))
    return out


def bucket(plen: int, prompt_bucket: int, window) -> int:
    b = -(-plen // prompt_bucket) * prompt_bucket
    return plen if window and b > window else b


def tile(k: int, n: int, r: int) -> Tuple[int, int]:
    """One chip's ``(K, N)`` of a ``(K, N)`` weight split over ``r`` chips
    of the "model" axis.  The split dim is the larger of those that ``r``
    divides, ties to ``N``; the rows only where ``K`` is that dim, else
    the columns, padded up to a multiple of ``r``."""
    if r == 1:
        return k, n
    n_fits = n % r == 0 and n >= r
    if k % r == 0 and k >= r and (not n_fits or k > n):
        return k // r, n
    return k, -(-n // r)


def kernel_calls(steps: Iterable[StepRecord], cfg, max_batch: int,
                 prompt_bucket: int, r: int = 1
                 ) -> Dict[Tuple[int, int, int], int]:
    """(M, K, N) -> number of ``quant_matmul_int`` calls, over all ``r``
    chips that split each linear (one call per chip, of its tile)."""
    rows: Dict[int, int] = {}
    for s in steps:
        for plen in s.prefill_lens:
            m = bucket(plen, prompt_bucket, cfg.sliding_window)
            rows[m] = rows.get(m, 0) + 1
        if s.decode_rows:
            rows[max_batch] = rows.get(max_batch, 0) + 1
    out: Dict[Tuple[int, int, int], int] = {}
    for m, n_calls in rows.items():
        for k, n in linear_shapes(cfg):
            key = (m,) + tile(k, n, r)
            out[key] = out.get(key, 0) + n_calls * cfg.n_layers * r
    return out


def call_cost(m: int, k: int, n: int) -> Tuple[float, float]:
    """Operations and HBM bytes one (M, K) x (K, N) int8 matmul needs:
    both int8 operands read once, the int32 result written once."""
    return 2.0 * m * k * n, float(m * k + k * n + 4 * m * n)


def roofline_seconds(calls: Dict[Tuple[int, int, int], int],
                     pk: Dict[str, float]) -> Tuple[float, float, float]:
    """(least time, time bound by operations, time bound by bytes), each
    summed over the calls, at the int8 peak and the HBM bandwidth."""
    least = by_ops = by_bytes = 0.0
    for (m, k, n), count in calls.items():
        ops, nbytes = call_cost(m, k, n)
        t_ops = ops / pk["int8_ops_per_s"]
        t_bytes = nbytes / pk["hbm_bytes_per_s"]
        least += count * max(t_ops, t_bytes)
        by_ops += count * t_ops
        by_bytes += count * t_bytes
    return least, by_ops, by_bytes


def prompt_ctx(n: int, window) -> int:
    """Summed context of a prompt's ``n`` queries: the query at position
    ``p`` sees ``min(p + 1, window)`` keys."""
    w = min(n, window) if window else n
    return w * (w + 1) // 2 + (n - w) * w


def model_ops(steps: Iterable[StepRecord], cfg) -> Tuple[float, float]:
    """(int8 linear operations, other operations) of the tokens the steps
    processed: each admitted prompt's real tokens and one output row, and
    each decoded row."""
    lin = 2.0 * sum(k * n for k, n in linear_shapes(cfg)) * cfg.n_layers
    attn = 4.0 * cfg.n_heads * cfg.hd * cfg.n_layers    # per (query, key)
    head = 2.0 * cfg.d_model * cfg.vocab_size
    int8 = other = 0.0
    for s in steps:
        for plen in s.prefill_lens:
            int8 += lin * plen
            other += attn * prompt_ctx(plen, cfg.sliding_window) + head
        int8 += lin * s.decode_rows
        other += attn * s.decode_ctx + head * s.decode_rows
    return int8, other
