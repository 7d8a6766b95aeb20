"""The harness as a whole, on the CPU at a small size: it refuses a CPU
device, and a cell assembled from new files by name alone runs end to end
through the program's ``Scheduler`` and the reference check."""
import json
import os
import subprocess
import sys
import time

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(os.path.dirname(BENCH))
sys.path[:0] = [BENCH, os.path.join(ROOT, "src")]

from servebench import run, spec, testing  # noqa: E402

SEED = 2 ** 32 + 77          # past 32 bits, as the driver's seeds are


def _result(capsys):
    out = capsys.readouterr()
    return json.loads(out.out.strip().splitlines()[-1]), out.err


def test_refuses_a_cpu_device(capsys):
    rc = run.main(["--workload", "qwen05b-quant-chat", "--seed", "1",
                   "--seconds", "1", "--trace", "0"],
                  started=time.monotonic())
    assert rc != 0
    assert capsys.readouterr().out == ""


def test_command_without_a_chip_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, os.path.join(BENCH, "serve_bench.py"),
         "--workload", "danube18b-quant-short", "--seed", str(SEED),
         "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=120)
    assert p.returncode != 0
    assert "{" not in p.stdout
    assert "needs 1 TPU chip" in p.stderr


def test_every_cell_of_the_benchmark_resolves():
    bench = spec.Layout().benchmark()
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"])
        cfg = spec.model_config(cell)
        scfg = spec.serving_config(cell)
        assert cfg.pim_mode == "quant" and scfg.paged
        c = cell.config
        assert (cfg.d_model, cfg.n_layers, cfg.vocab_size) == (
            c["hidden_size"], c["num_hidden_layers"], c["vocab_size"])
        assert cfg.rope_theta == c["rope_theta"]
        assert cfg.norm_eps == c["rms_norm_eps"]
        assert {m["name"] for m in cell.end_to_end} >= {"setup_s"}
        assert cell.per_layer
        for m in cell.end_to_end + cell.per_layer:
            assert callable(spec.Layout().metric_reader(m["name"]))


def test_new_cell_from_files_alone(tmp_path, capsys):
    layout = testing.smoke_layout(str(tmp_path), testing.TINY_QWEN,
                                  loop="open")
    rc = run.main(["--workload", "tiny-open", "--seed", str(SEED),
                   "--seconds", "1", "--trace", "0"],
                  started=time.monotonic(), layout=layout,
                  require_chip=False)
    assert rc == 0
    res, _ = _result(capsys)
    assert res["correct"] is True
    assert res["attempted"] >= 5 and res["failed"] == 0
    assert set(res["metrics"]) == {"ttft_p50_ms", "itl_p95_ms", "setup_s"}
    assert all(m["value"] > 0 for m in res["metrics"].values())
    assert list(res)[-1] == "checks"
    assert res["checks"]["compiles_in_window"]["value"] == 0


def test_closed_loop_past_the_window_traced(tmp_path, capsys):
    layout = testing.smoke_layout(str(tmp_path), testing.TINY_DANUBE,
                                  loop="closed")
    rc = run.main(["--workload", "tiny-closed", "--seed", str(SEED),
                   "--seconds", "1", "--trace", "1"],
                  started=time.monotonic(), layout=layout,
                  require_chip=False)
    assert rc == 0
    res, err = _result(capsys)
    assert res["correct"] is True
    # --trace 1 reports the per-layer metrics, only those it can read
    assert set(res["metrics"]) == {"prefill_step_share"}
    assert 0 < res["metrics"]["prefill_step_share"]["value"] < 100
    assert res["device"]["window_s"] > 0
    assert err.strip().splitlines()[-3].startswith("check widest_gap")


@pytest.mark.parametrize("name", ["ttft_p50_ms", "mfu", "device_idle",
                                  "quant_matmul_roofline"])
def test_a_reader_with_nothing_to_read_returns_nothing(name):
    from servebench.window import RunRecord

    ctx = run.Context(cell=None, cfg=None, scfg=None,
                      run=RunRecord(open=0.0, close=1.0), setup_s=1.0,
                      admits={}, trace=None, peaks=None)
    assert spec.Layout().metric_reader(name)(ctx, name) is None
