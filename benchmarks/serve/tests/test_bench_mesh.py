"""A cell on four chips, from files alone: its cell file's ``mesh`` lays
the program over a model=4 mesh, the weights are made under the program's
shardings, and a whole run reads ``correct`` true; with the sum across
chips left out it reads false.  The ``quant_tp`` kernel's calls are counted
per chip, as the program's tiles make them.  Each check runs in a process
of its own with four host devices (``four_devices.py``)."""
import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path[:0] = [BENCH, HERE, os.path.join(
    os.path.dirname(os.path.dirname(BENCH)), "src")]

import four_devices  # noqa: E402
from servebench import spec, testing  # noqa: E402


def _run(tmp_path, *fault):
    p = four_devices.launch("run", str(tmp_path), *fault)
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1]), p.stderr


def test_four_chip_cell_runs_end_to_end(tmp_path):
    res, err = _run(tmp_path)
    assert res["correct"] is True
    assert res["attempted"] >= 5 and res["failed"] == 0
    assert res["checks"]["widest_gap"]["value"] <= 0.5
    assert "mesh {'pod': 1, 'data': 1, 'model': 4}; weight leaves span " \
           "[4] devices" in err


def test_missing_exchange_between_chips_is_not_correct(tmp_path):
    res, _ = _run(tmp_path, "no_exchange")
    assert res["correct"] is False
    gap = res["checks"]["widest_gap"]
    assert gap["value"] > gap["limit"]


@pytest.fixture(scope="module")
def calls():
    p = four_devices.launch("calls")
    assert p.returncode == 0, p.stderr[-4000:]
    return json.loads(p.stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("arch", ["qwen1.5-0.5b", "h2o-danube-1.8b",
                                  "granite-20b"])
@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_quant_tp_calls_are_counted_per_chip(calls, arch, program):
    got = calls[arch][program]
    # the jaxpr traces each shard_map tile once; it runs on all 4 chips
    assert got["counted"] == [[k, 4 * v] for k, v in got["traced"]]


@pytest.mark.parametrize("mesh", [{"model": 2}, {"data": 2, "model": 2},
                                  {"pod": 4}])
def test_a_mesh_must_size_the_cells_chips(tmp_path, mesh):
    layout = testing.smoke_layout(str(tmp_path), testing.TINY_QWEN,
                                  chips=4, pim_mode="quant_tp", mesh=mesh)
    with pytest.raises(ValueError, match="mesh"):
        spec.load_cell("tiny-open", layout)


def test_a_cell_without_a_mesh_builds_none(tmp_path):
    layout = testing.smoke_layout(str(tmp_path), testing.TINY_QWEN)
    cell = spec.load_cell("tiny-open", layout)
    assert cell.mesh is None and cell.model_ranks == 1
    assert spec.make_mesh(cell, []) is None
    with_mesh = testing.smoke_layout(str(tmp_path / "tp"), testing.TINY_QWEN,
                                     chips=4, pim_mode="quant_tp",
                                     mesh={"model": 4})
    assert spec.load_cell("tiny-open", with_mesh).model_ranks == 4
