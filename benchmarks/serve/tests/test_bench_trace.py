"""The trace reduction, on a recorded trace: three decode steps of a
one-layer ``quant`` model on one TPU v5e, each inside ``bench.step``, with
2 ms of ``bench.bookkeeping`` after it, all inside ``bench.window``."""
import os
import sys

from types import SimpleNamespace

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.dirname(HERE), os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(HERE))), "src")]

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


def _ctx(summary, steps, chips=1):
    import repro.configs as configs
    from repro.serving import ServingConfig
    from servebench import flops, run
    from servebench.window import RunRecord

    class Cell:
        pass

    cell = Cell()
    cell.chips = chips
    cell.model_ranks = chips
    rec = RunRecord(open=0.0, close=1.0)
    rec.steps = steps
    cfg = configs.get("qwen1.5-0.5b").smoke().scaled(
        pim_mode="quant", n_layers=1, max_seq_len=64)
    return run.Context(cell=cell, cfg=cfg,
                       scfg=ServingConfig(max_batch=4, prompt_bucket=16),
                       run=rec, setup_s=1.0, admits={}, trace=summary,
                       peaks=flops.peaks("TPU v5 lite"))


def _decode_steps(n):
    from servebench.window import StepRecord

    return [StepRecord(0.1 * i, 0.1 * i + 0.05, [], decode_rows=4,
                       decode_ctx=40) for i in range(n)]


READ_BEFORE = {"quant_matmul_roofline": 6.495643575430589,
               "mfu": 3.2113796515157383e-06,
               "device_idle": 99.61190053123677,
               "decode_device_ms": None, "prefill_ms": None,
               "idle_in_step": None}


@pytest.mark.parametrize("name", sorted(READ_BEFORE))
def test_readers_read_the_recorded_trace_as_before(summary, name):
    from servebench import spec

    ctx = _ctx(summary, _decode_steps(3))
    assert spec.Layout().metric_reader(name)(ctx, name) == READ_BEFORE[name]


def test_a_whole_record_is_not_cut(summary):
    assert summary.cut is None and summary.kept_share == 1.0
    steps = _decode_steps(3)
    assert summary.window_steps(steps) is steps


def _profile(kept, step_s=0.5):
    """A profile of ten steps in a 10-s window, step ``s`` on the host from
    ``s`` to ``s + step_s`` (with a ``serve.decode`` span), and 7 kernel
    events of 10 ms each on the device from ``s + 0.1`` to ``s + 0.41``;
    ``kept(s, i)`` says which events the profiler kept."""
    def ev(name, start, end):
        return SimpleNamespace(name=name, start_ns=round(start * 1e9),
                               duration_ns=round((end - start) * 1e9))

    host = [ev("bench.window", 0.0, 10.0)]
    for s in range(10):
        host += [ev("bench.step", s, s + step_s),
                 ev("serve.decode", s + 0.05, s + step_s - 0.01)]
    ops = [ev("%quant_matmul_int_kernel.3 = s32[4,64] custom-call()",
              s + 0.1 + 0.05 * i, s + 0.11 + 0.05 * i)
           for s in range(10) for i in range(7) if kept(s, i)]
    mods = [ev("jit_serve_decode(1)", s + 0.1, s + 0.42) for s in range(10)]
    line = lambda name, events: SimpleNamespace(name=name, events=events)
    return SimpleNamespace(planes=[
        SimpleNamespace(name="/host:CPU", lines=[line("main", host)]),
        SimpleNamespace(name="/device:TPU:0", lines=[
            line("XLA Ops", ops), line("XLA Modules", mods)])])


CUTS = {
    # every event kept: no cut
    "whole": (lambda s, i: True, 0.5, None, None),
    # the record ends after step 7's host span, its queued events after
    # it: cut where the record ends, 8 steps kept
    "tail": (lambda s, i: s < 8, 0.3, 7.41, 8),
    # the record ends inside step 7: cut at its start
    "inside_a_step": (lambda s, i: s < 7 or (s == 7 and i < 3), 0.5, 7.0, 7),
    # step 7's host span ends after its last event, so the record may not
    # hold it whole: cut at its start
    "after_a_step": (lambda s, i: s < 8, 0.5, 7.0, 7),
    # one event of each step lost, the record reaching the end: no cut
    "spread": (lambda s, i: i, 0.5, None, None),
}


@pytest.mark.parametrize("case", sorted(CUTS))
def test_window_is_cut_where_the_device_record_ends(case):
    kept, step_s, cut, steps_kept = CUTS[case]
    t = trace.reduce_profile(_profile(kept, step_s))
    if cut is None:
        assert t.cut is None and t.window == (0.0, 10.0)
        return
    assert t.window[1] == pytest.approx(cut, abs=1e-9)
    assert t.cut.opened == (0.0, 10.0)
    assert (t.cut.steps_kept, t.cut.steps) == (steps_kept, 10)
    assert t.kept_share == pytest.approx(cut / 10.0)
    assert all(s < t.window[1] for _, s, _ in t.host)
    assert all(b <= t.window[1] for a, b in t.busy[0])
    assert t.op_seconds("quant_matmul_int")[1] == 7 * steps_kept
    assert t.window_steps(list(range(10))) == list(range(steps_kept))
    assert t.window_steps(list(range(9))) is None


def test_a_cut_under_half_the_window_is_not_read():
    t = trace.reduce_profile(_profile(lambda s, i: s < 4, 0.3))
    assert t.kept_share == pytest.approx(0.341)
    assert not t.readable
    assert trace.reduce_profile(_profile(lambda s, i: s < 8)).readable


@pytest.mark.parametrize("case", ["whole", "tail", "inside_a_step", "spread"])
def test_readers_read_the_cut_window(case):
    from servebench import flops, spec

    kept, step_s, cut, steps_kept = CUTS[case]
    t = trace.reduce_profile(_profile(kept, step_s))
    steps = _decode_steps(10)
    ctx = _ctx(t, steps)
    layout = spec.Layout()
    read = lambda name: layout.metric_reader(name)(ctx, name)
    events = t.op_seconds("quant_matmul_int")[1]
    if case == "spread":        # 60 events for 70 calls: silent
        assert events == 60 and read("quant_matmul_roofline") is None
        return
    k = steps_kept or 10
    least, _, _ = flops.roofline_seconds(
        flops.kernel_calls(steps[:k], ctx.cfg, 4, 16), ctx.peaks)
    assert read("quant_matmul_roofline") == pytest.approx(
        100.0 * least / (7 * k * 0.01), rel=1e-6)
    assert read("device_idle") == pytest.approx(
        100.0 * (1 - 7 * k * 0.01 / (cut or 10.0)), rel=1e-6)
    assert read("decode_device_ms") == pytest.approx(70.0, rel=1e-6)
