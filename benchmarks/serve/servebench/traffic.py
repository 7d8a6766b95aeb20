"""Seeded traffic from a mix's parameters.

A mix (``traffic/<name>.json``) gives the loop and two lognormal length
distributions; an open loop's arrivals are Poisson at ``rate_per_s``::

    {"loop": "open", "rate_per_s": 0.5,
     "prompt_len": {"dist": "lognormal", "median": 512, "sigma": 1.0,
                    "min": 64, "max": 2048},
     "output_len": {...}, "strata": 25}

    {"loop": "closed", "concurrency": 64, "prompt_len": {...}, ...}

Lengths and inter-arrival gaps come from a grid of ``strata`` quantiles of
their distribution, shuffled in consecutive blocks of ``strata`` requests
in one fixed order (``SCHEDULE_SEED``).  The run's seed draws only the
prompt token ids (uniform over the vocabulary, per request from ``(seed,
index)``) and the weights: every seed sends the same sizes at the same
times.  An open loop's schedule is therefore one sample path of its
Poisson process, not a fresh draw per run.  Near capacity the order of
arrivals decides the queueing tail (runs of the chat cell on seeds that
also shuffled the order read a TTFT p95 from 267 ms to 3.8 s, while two
runs of one seed mostly agreed), so the order is part of the work and is
held fixed.
"""
from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist
from typing import Dict, List

import numpy as np

_NORMAL = NormalDist()
#: the one order in which every run sends its mix's sizes and gaps
SCHEDULE_SEED = 0


def quantiles(spec: Dict, n: int) -> np.ndarray:
    """``n`` evenly spaced quantiles ``(i + 0.5) / n`` of a length
    distribution, clipped to ``[min, max]`` and rounded to whole tokens."""
    if spec["dist"] != "lognormal":
        raise ValueError(f"unknown length distribution {spec['dist']!r}")
    u = (np.arange(n) + 0.5) / n
    z = np.array([_NORMAL.inv_cdf(x) for x in u])
    v = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    lo, hi = spec.get("min", 1), spec.get("max", np.inf)
    return np.clip(np.rint(v), lo, hi).astype(np.int64)


def _stratified(rng: np.random.Generator, grid: np.ndarray,
                n: int) -> np.ndarray:
    """``n`` values: consecutive blocks, each a permutation of ``grid``."""
    blocks = -(-n // len(grid))
    return np.concatenate([rng.permutation(grid)
                           for _ in range(blocks)])[:n]


@dataclasses.dataclass
class Plan:
    """The requests of one run, in submission order."""

    loop: str                      # "open" | "closed"
    prompt_lens: np.ndarray        # (n,)
    output_lens: np.ndarray        # (n,)
    arrivals: np.ndarray           # (n,) seconds after the window opens
    #                                (open loop); zeros for a closed loop
    concurrency: int               # closed loop: outstanding requests
    seed: int
    vocab: int

    def __len__(self) -> int:
        return len(self.prompt_lens)

    def prompt(self, i: int) -> np.ndarray:
        rng = np.random.default_rng([self.seed, i])
        return rng.integers(0, self.vocab, int(self.prompt_lens[i]),
                            dtype=np.int32)


def make_plan(mix: Dict, seed: int, vocab: int, seconds: float,
              max_requests: int = 0) -> Plan:
    """The requests a run of ``seconds`` can draw.  An open loop gets every
    arrival due before the window closes; a closed loop gets
    ``max_requests`` (or a generous default) to consume in order."""
    strata = int(mix.get("strata", 64))
    rng = np.random.default_rng(SCHEDULE_SEED)
    p_grid = quantiles(mix["prompt_len"], strata)
    o_grid = quantiles(mix["output_len"], strata)
    if mix["loop"] == "open":
        rate = float(mix["rate_per_s"])
        u = (np.arange(strata) + 0.5) / strata
        g_grid = -np.log1p(-u) / rate          # exponential quantiles
        n = int(seconds * rate * 2) + strata
        gaps = _stratified(rng, g_grid, n)
        arrivals = np.cumsum(gaps) - gaps[0]   # first request at the open
        n = int(np.searchsorted(arrivals, seconds))
        arrivals = arrivals[:n]
        concurrency = 0
    elif mix["loop"] == "closed":
        concurrency = int(mix["concurrency"])
        n = max_requests or (concurrency * 64)
        arrivals = np.zeros(n)
    else:
        raise ValueError(f"unknown loop {mix['loop']!r}")
    return Plan(loop=mix["loop"],
                prompt_lens=_stratified(rng, p_grid, n),
                output_lens=_stratified(rng, o_grid, n),
                arrivals=arrivals, concurrency=concurrency, seed=seed,
                vocab=vocab)


def prompt_buckets(mix: Dict, prompt_bucket: int,
                   window: int = 0) -> List[int]:
    """Every padded prefill length the mix can draw, as the scheduler pads
    them: up to a ``prompt_bucket`` multiple (unpadded past a sliding
    window)."""
    out = set()
    for plen in quantiles(mix["prompt_len"], int(mix.get("strata", 64))):
        b = -(-int(plen) // prompt_bucket) * prompt_bucket
        out.add(int(plen) if window and b > window else b)
    return sorted(out)
