"""The control: the reference one precision step below the configuration's
int8 linears (4 bits), read at the same prompts and served tokens, fails
the limit that the program's own served tokens pass."""
import json
import os
import sys
import time

import numpy as np
import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, os.path.join(os.path.dirname(os.path.dirname(BENCH)),
                                    "src")]

from servebench import check, run, spec, testing, weights  # noqa: E402

SEED = 2 ** 32 + 901


@pytest.mark.parametrize("config", [testing.TINY_QWEN,
                                    testing.TINY_DANUBE])
def test_control_fails_the_limit_the_program_passes(tmp_path, config):
    from repro.serving import Scheduler

    layout = testing.smoke_layout(str(tmp_path), config, loop="closed")
    cell = spec.load_cell("tiny-closed", layout)
    cfg = spec.model_config(cell)
    sched = Scheduler(weights.make_params(cfg, SEED), cfg,
                      spec.serving_config(cell))
    rng = np.random.default_rng(SEED)
    prompts = [rng.integers(0, cfg.vocab_size, n).astype(np.int32)
               for n in (5, 9, 13, 17, 21, 24)]
    rids = [sched.submit(p, 12) for p in prompts]
    out = sched.run()
    nums = check.compare(cell.config, SEED,
                         [(p, np.asarray(out[r])) for p, r in
                          zip(prompts, rids)], control=True)
    limit = cell.cell["check"]["widest_gap"]
    assert nums["widest_gap"] <= limit < nums["control_gap"]
    assert nums["control_gap"] > 3 * max(nums["widest_gap"], 0.05)


@pytest.mark.parametrize("config", [testing.TINY_QWEN,
                                    testing.TINY_DANUBE])
def test_a_control_run_reads_not_correct(tmp_path, capsys, config):
    """``--control 1`` puts the control in the program's place: the whole
    run's verdict, not only the number, comes out false."""
    layout = testing.smoke_layout(str(tmp_path), config, loop="closed")
    rc = run.main(["--workload", "tiny-closed", "--seed", str(SEED),
                   "--seconds", "1", "--trace", "0", "--control", "1"],
                  started=time.monotonic(), layout=layout,
                  require_chip=False)
    assert rc == 0
    out = capsys.readouterr()
    res = json.loads(out.out.strip().splitlines()[-1])
    assert res["correct"] is False
    gap = res["checks"]["widest_gap"]
    assert gap["value"] > gap["limit"]
    assert "program widest_gap" in out.err
