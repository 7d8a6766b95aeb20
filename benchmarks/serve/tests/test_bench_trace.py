"""The trace reduction, on a recorded trace: three decode steps of a
one-layer ``quant`` model on one TPU v5e, each inside ``bench.step``, with
2 ms of ``bench.bookkeeping`` after it, all inside ``bench.window``."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from servebench import trace  # noqa: E402

TRACE = os.path.join(os.path.dirname(HERE), "testdata",
                     "smoke_decode.xplane.pb")


@pytest.fixture(scope="module")
def summary():
    import jax

    return trace.reduce_profile(jax.profiler.ProfileData.from_file(TRACE))


def test_window_is_the_bench_window_span(summary):
    lo, hi = summary.window
    assert hi > lo
    assert summary.window_s == pytest.approx(0.01641641, rel=1e-4)
    names = {n for n, _, _ in summary.host}
    assert {"bench.window", "bench.step", "bench.bookkeeping"} <= names


def test_busy_is_a_union_inside_the_window(summary):
    assert len(summary.busy) == 1                 # one device
    iv = summary.busy[0]
    assert all(a < b for a, b in iv)
    assert all(b1 < a2 for (_, b1), (a2, _) in zip(iv, iv[1:]))
    lo, hi = summary.window
    assert lo <= iv[0][0] and iv[-1][1] <= hi
    assert 0 < summary.busy_s < summary.window_s
    # the union never exceeds the summed op time
    total = sum(v[0] for v in summary.ops.values())
    assert summary.busy_s <= total + 1e-12


def test_kernel_events_are_told_apart(summary):
    seconds, count = summary.op_seconds("quant_matmul_int")
    assert count == 3 * 7          # 3 decode steps x 7 linears x 1 layer
    assert seconds > 0
    mods = {mod for (mod, name) in summary.ops if name == "quant_matmul_int"}
    assert mods == {"jit__step"}


def test_breakdown_lists(summary):
    top = summary.top_ops()
    assert 0 < len(top) <= 10
    assert all(isinstance(n, str) and s > 0 for n, s in top)
    assert [s for _, s in top] == sorted((s for _, s in top), reverse=True)
    gaps = summary.idle_gaps()
    assert 0 < len(gaps) <= 10
    assert gaps[0][0] == "bench.bookkeeping"     # the 2 ms sleeps
    assert all(g[1] <= summary.window_s for g in gaps)


def test_op_and_module_names():
    assert trace.op_name("%quant_matmul_int.14 = s32[128,128] custom-call("
                         "s8[128,512] %pad.39)") == "quant_matmul_int"
    assert trace.op_name("%fusion = bf16[4,64] fusion(...)") == "fusion"
    assert trace.module_name("jit__step(8158213297991489907)") == "jit__step"
    assert trace.merge([(3, 4), (0, 1), (0.5, 2), (4, 5)]) == [(0, 2), (3, 5)]
