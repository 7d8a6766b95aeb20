"""A whole run with the timed path broken underneath reads ``correct``
false: a decode step that hands back the KV cache unchanged, and a served
token altered where the decode step produces it.  (Half a batch left out
and a missing exchange between chips are training and multi-chip faults;
a one-chip serving cell has neither.)"""
import json
import os
import sys
import time

import jax.numpy as jnp
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(os.path.dirname(BENCH)),
                                    "src")]

from servebench import run, testing  # noqa: E402

SEED = 2 ** 32 + 4242


def _state_unchanged(orig):
    def step(params, tokens, pos, active, caches, cfg, block_tables=None):
        tok, logits, _ = orig(params, tokens, pos, active, caches, cfg,
                              block_tables=block_tables)
        return tok, logits, caches
    return step


def _token_altered(orig):
    def step(params, tokens, pos, active, caches, cfg, block_tables=None):
        tok, logits, new = orig(params, tokens, pos, active, caches, cfg,
                                block_tables=block_tables)
        tok = jnp.where(active[:, None], (tok + 1) % cfg.vocab_size, 0)
        return tok, logits, new
    return step


@pytest.mark.parametrize("fault", [_state_unchanged, _token_altered])
def test_broken_decode_step_is_not_correct(tmp_path, capsys, monkeypatch,
                                           fault):
    from repro.models import model_lib as M

    monkeypatch.setattr(M, "decode_step_slots", fault(M.decode_step_slots))
    layout = testing.smoke_layout(str(tmp_path), testing.TINY_DANUBE,
                                  loop="closed")
    rc = run.main(["--workload", "tiny-closed", "--seed", str(SEED),
                   "--seconds", "1", "--trace", "0"],
                  started=time.monotonic(), layout=layout,
                  require_chip=False)
    assert rc == 0
    res = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    assert res["correct"] is False
    gap = res["checks"]["widest_gap"]
    assert gap["value"] > gap["limit"]
