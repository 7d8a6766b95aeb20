"""Window arithmetic: tails over every request, missing first tokens
counted, gaps clipped to the window."""
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))

from servebench.window import (RequestRecord, RunRecord, StepRecord,  # noqa
                               inter_token_gaps, percentile, tokens_in_window,
                               ttfts)


def req(i, due, times):
    return RequestRecord(index=i, due=due, prompt_len=8, output_len=4,
                         times=list(times), tokens=[0] * len(times))


def test_nearest_rank_percentile():
    v = list(range(1, 101))
    assert percentile(v, 95) == 95
    assert percentile(v, 50) == 50
    assert percentile([3.0], 95) == 3.0
    assert percentile([5, 1, 4, 2, 3], 80) == 4
    # 20 values: p95 is the 19th, so one value lies beyond it
    assert percentile(list(range(20)), 95) == 18


def test_ttft_counts_every_due_request_and_missing_ones():
    run = RunRecord(open=10.0, close=20.0, drain_end=25.0)
    run.requests = {
        1: req(0, 10.0, [10.5, 10.6]),
        2: req(1, 12.0, [13.0]),
        3: req(2, 19.0, []),            # never answered: waited to 25
        4: req(3, 21.0, [21.5]),        # due after the window: not counted
        5: req(4, 9.0, [9.5]),          # due before it: not counted
    }
    assert sorted(ttfts(run)) == pytest.approx([0.5, 1.0, 6.0])
    assert percentile(ttfts(run), 95) == pytest.approx(6.0)


def test_gaps_end_in_the_window_and_start_at_its_opening():
    run = RunRecord(open=10.0, close=20.0)
    run.requests = {
        1: req(0, 9.0, [9.0, 10.5, 11.0]),    # first gap clipped to 0.5
        2: req(1, 19.0, [19.5, 20.5]),        # ends after the close
    }
    assert sorted(inter_token_gaps(run)) == pytest.approx([0.5, 0.5])
    assert tokens_in_window(run) == 3


def test_loop_steps_are_the_steps_the_window_started():
    run = RunRecord(open=10.0, close=20.0)
    run.steps = [StepRecord(9.0, 10.5, [], 1), StepRecord(12.0, 13.0, [8], 2),
                 StepRecord(19.9, 20.4, [], 3), StepRecord(20.5, 21.0, [], 3)]
    assert [s.decode_rows for s in run.loop_steps()] == [2, 3]
    assert run.seconds == 10.0
