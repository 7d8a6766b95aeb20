"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the finished requests, drawn from the seed and always holding the longest,
is run through the float32 reference over each prompt followed by its
served tokens.  For every served token the
reference gives the gap by which that token's logit lies below its best
logit at that position (0 where the program chose the reference's best).
``widest_gap`` is the largest such gap over the sample; the cell's limit
on it is in ``cells/<workload>.json``.

The control (``control=True``; calibration and tests only, never a
benchmark run) is the same reference one precision step below the
configuration's int8 linears, at 4 bits: at each position of the same
prompts and tokens, the gap of the token that the 4-bit model puts first.
A run with ``--control 1`` compares ``control_gap`` in place of
``widest_gap``, so it reads ``correct`` false.

The reference is the module ``reference/<name>.py`` of the benchmark's
directory (``Layout.reference_dir``), where the configuration file names
it (``"reference": "<name>"``, default ``model``), loaded by its path.
A new architecture brings its own as a new file.  Such a module imports
nothing of the program, takes nothing the program made, and gives:

* ``Reference(config, seed, bits)``: the model of the configuration file's
  dict, its weights remade from ``seed`` (``servebench.weights.leaf_value``
  by leaf path, layer by layer), every quantized linear at ``bits`` bits
  (8 as served, 4 for the control);
* ``Reference.hidden(tokens)``: the final hidden states ``(N, T, d)`` of
  an ``(N, T)`` int32 token batch, each sequence from position 0, padding
  on the right;
* ``Reference.output_table()``: the ``(V, d)`` output table over the
  vocabulary's ``V`` rows;
* ``row_logits(rows, table)``: yields ``(start, logits)`` over blocks of
  the ``(R, d)`` rows, the float32 logits ``(n, V)`` of rows
  ``start .. start + n``.
"""
from __future__ import annotations

import os
from typing import Dict, List, Sequence, Tuple

import jax.numpy as jnp
import numpy as np

from servebench.spec import REFERENCE_DIR, load_module
from servebench.window import RequestRecord

PAD_TO = 512


def reference_module(config: Dict, reference_dir: str = REFERENCE_DIR):
    """The reference module the configuration names."""
    name = config.get("reference", "model")
    return load_module(os.path.join(reference_dir, f"{name}.py"),
                       f"servebench_reference_{name}")


def sample(finished: Sequence[RequestRecord], seed: int, k: int
           ) -> List[RequestRecord]:
    """``k`` finished requests drawn from the seed: the longest (prompt and
    output together) and ``k - 1`` others."""
    by_len = sorted(finished, key=lambda r: (r.prompt_len + r.output_len,
                                             r.index), reverse=True)
    if not by_len:
        return []
    rest = by_len[1:]
    rng = np.random.default_rng([seed, 0x5EED])
    pick = rng.choice(len(rest), size=min(k - 1, len(rest)), replace=False)
    return [by_len[0]] + [rest[i] for i in sorted(pick)]


def _batch(seqs: List[Tuple[np.ndarray, np.ndarray]]):
    """Padded (N, T) tokens ``prompt + served[:-1]`` and, per served token,
    its (sequence, position) and id."""
    t = max(len(p) + len(s) - 1 for p, s in seqs)
    t = -(-t // PAD_TO) * PAD_TO
    toks = np.zeros((len(seqs), t), np.int32)
    rows, ids = [], []
    for n, (p, s) in enumerate(seqs):
        full = np.concatenate([p, s[:-1]])
        toks[n, :len(full)] = full
        rows += [(n, len(p) - 1 + i) for i in range(len(s))]
        ids += list(s)
    return toks, np.array(rows), np.array(ids, np.int64)


def _rows(ref, toks, rows):
    h = ref.hidden(toks)
    return h[jnp.asarray(rows[:, 0]), jnp.asarray(rows[:, 1])]


def compare(config: Dict, seed: int,
            seqs: List[Tuple[np.ndarray, np.ndarray]],
            control: bool = False, reference_dir: str = REFERENCE_DIR
            ) -> Dict[str, float]:
    """``widest_gap`` of the served tokens (and ``control_gap``), by the
    reference the configuration names in ``reference_dir``."""
    mod = reference_module(config, reference_dir)
    toks, rows, ids = _batch(seqs)
    ctrl_ids = None
    if control:
        low = mod.Reference(config, seed, bits=4)
        table = low.output_table()
        h = _rows(low, toks, rows)
        ctrl_ids = np.concatenate([np.asarray(jnp.argmax(lg, -1))
                                   for _, lg in mod.row_logits(h, table)])
        del low, table, h
    ref = mod.Reference(config, seed, bits=8)
    table = ref.output_table()
    h = _rows(ref, toks, rows)
    gaps, cgaps = [], []
    for s, lg in mod.row_logits(h, table):
        best = jnp.max(lg, -1)
        n = lg.shape[0]
        got = jnp.take_along_axis(lg, jnp.asarray(ids[s:s + n])[:, None],
                                  -1)[:, 0]
        gaps.append(np.asarray(best - got))
        if ctrl_ids is not None:
            c = jnp.take_along_axis(
                lg, jnp.asarray(ctrl_ids[s:s + n])[:, None], -1)[:, 0]
            cgaps.append(np.asarray(best - c))
    out = {"widest_gap": float(np.concatenate(gaps).max()),
           "served_tokens": int(len(ids))}
    if control:
        out["control_gap"] = float(np.concatenate(cgaps).max())
    return out
