"""What one run recorded, and the arithmetic of its measured window.

All times are the benchmark's own host clock (``time.monotonic``) and are
stamped when ``Scheduler.step()`` returns the ``(rid, token)`` pairs: a
token counts from the moment the caller can see it.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, List


@dataclasses.dataclass
class RequestRecord:
    index: int                  # position in the traffic plan
    due: float                  # when the request was due to be sent
    prompt_len: int
    output_len: int
    times: List[float] = dataclasses.field(default_factory=list)
    tokens: List[int] = dataclasses.field(default_factory=list)


@dataclasses.dataclass
class StepRecord:
    start: float
    end: float
    prefill_lens: List[int]     # real prompt lengths admitted this step
    decode_rows: int            # slots that decoded a token this step
    decode_ctx: int = 0         # keys those rows attended to, summed


@dataclasses.dataclass
class RunRecord:
    open: float
    close: float
    loop_end: float = 0.0       # end of the last step the window started
    drain_end: float = 0.0      # end of the steps after the close
    requests: Dict[int, RequestRecord] = dataclasses.field(
        default_factory=dict)
    steps: List[StepRecord] = dataclasses.field(default_factory=list)

    @property
    def seconds(self) -> float:
        return self.close - self.open

    def due_in_window(self) -> List[RequestRecord]:
        return [r for r in self.requests.values()
                if self.open <= r.due < self.close]

    def loop_steps(self) -> List[StepRecord]:
        """The steps the window started (the last may end after it)."""
        return [s for s in self.steps if self.open <= s.start < self.close]


def percentile(values: List[float], q: float) -> float:
    """Nearest-rank percentile: the smallest value with at least ``q``% of
    the sample at or below it."""
    if not values:
        return math.nan
    v = sorted(values)
    k = max(1, math.ceil(q / 100.0 * len(v)))
    return v[k - 1]


def ttfts(run: RunRecord) -> List[float]:
    """Time to first token of every request due in the window, from its due
    time.  A request that never had one (it counts as failed) enters with
    the time it waited until the run stopped waiting for it."""
    end = max(run.drain_end, run.close)
    return [(r.times[0] if r.times else end) - r.due
            for r in run.due_in_window()]


def inter_token_gaps(run: RunRecord) -> List[float]:
    """Every gap between consecutive tokens of one request whose later token
    falls in the window, its start clipped to the window's opening."""
    out = []
    for r in run.requests.values():
        for a, b in zip(r.times, r.times[1:]):
            if run.open <= b <= run.close:
                out.append(b - max(a, run.open))
    return out


def tokens_in_window(run: RunRecord) -> int:
    return sum(1 for r in run.requests.values() for t in r.times
               if run.open <= t <= run.close)
