"""Reduce a JAX profiler trace to what the per-layer metrics read.

The traced run wraps its measured window in a ``bench.window`` host span
(``jax.profiler.TraceAnnotation``), each scheduler step in ``bench.step``,
request submission in ``bench.submit``, its own record keeping in
``bench.bookkeeping`` and sleeps for the next arrival in ``bench.wait``.
On a TPU the device plane (``/device:TPU:<n>``) has an ``XLA Ops`` line
whose event names are HLO instructions (``%quant_matmul_int.14 = ...``)
and an ``XLA Modules`` line naming the jitted program (``jit__step(...)``);
host and device events share one clock.

Busy time is the union of the ``XLA Ops`` intervals inside the window,
per device, averaged over the devices.

The profiler stops keeping events once it holds a fixed number of them, so
a long busy window can lose its tail: the device's record ends while
``bench.step`` spans run on.  The window is then cut once, here, where the
record ends or where the first step it does not wholly hold starts, if that
is earlier; every reader reads the cut window and the steps that start in
it.  ``TraceSummary.cut`` records the cut.
"""
from __future__ import annotations

import collections
import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Tuple

WINDOW = "bench.window"
STEP = "bench.step"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
#: ops whose interval spans the ops of their body
CONTAINERS = ("while", "conditional", "call")
#: the least share of the window a cut may keep for the readers to read it
MIN_SHARE = 0.5

Interval = Tuple[float, float]


@dataclasses.dataclass(frozen=True)
class Cut:
    """Where the window was cut, and the ``bench.step`` spans before it."""

    opened: Interval            # the whole ``bench.window`` span
    steps_kept: int             # steps that start before the cut
    steps: int                  # steps that start in the whole window


@dataclasses.dataclass
class TraceSummary:
    #: the ``bench.window`` span, seconds; its end is the cut, if any
    window: Interval
    #: (program, op name) -> [device seconds, count] inside the window,
    #: summed over the devices
    ops: Dict[Tuple[str, str], List[float]]
    host: List[Tuple[str, float, float]]   # host spans of the main thread
    busy: List[List[Interval]]  # per device, merged
    cut: Optional[Cut] = None

    @property
    def window_s(self) -> float:
        return self.window[1] - self.window[0]

    @property
    def kept_share(self) -> float:
        """The share of the ``bench.window`` span the window keeps."""
        if self.cut is None:
            return 1.0
        lo, hi = self.cut.opened
        return self.window_s / (hi - lo)

    @property
    def readable(self) -> bool:
        """The window keeps at least ``MIN_SHARE`` of the span, so the
        readers may read it."""
        return self.kept_share >= MIN_SHARE

    def window_steps(self, steps: list) -> Optional[list]:
        """The run's window steps (one a ``bench.step`` span, in order)
        that start in the window: all of them where it was not cut, else
        the first ``cut.steps_kept``; ``None`` where the run's steps and the
        trace's spans do not pair up."""
        if self.cut is None:
            return steps
        if len(steps) != self.cut.steps:
            return None
        return steps[:self.cut.steps_kept]

    @property
    def busy_s(self) -> float:
        per = [sum(b - a for a, b in iv) for iv in self.busy]
        return sum(per) / len(per) if per else 0.0

    def op_seconds(self, prefix: str) -> Tuple[float, int]:
        """Summed device time and count of ops whose name starts with
        ``prefix``, over all devices."""
        sel = [v for (_, name), v in self.ops.items()
               if name.startswith(prefix)]
        return sum(v[0] for v in sel), int(sum(v[1] for v in sel))

    def top_ops(self, n: int = 10) -> List[List]:
        """The ``n`` ops with the most device time, by program; a control
        op (``while``) is left out, as the ops of its body are listed."""
        tot = sorted(((k, v) for k, v in self.ops.items()
                      if k[1] not in CONTAINERS), key=lambda kv: -kv[1][0])
        return [[f"{mod}:{name}", v[0]] for (mod, name), v in tot[:n]]

    def idle_gaps(self, n: int = 10) -> List[List]:
        """The ``n`` longest gaps on the first device with no op running,
        each named by the innermost host span over its midpoint."""
        if not self.busy:
            return []
        edges = [self.window[0]]
        for a, b in self.busy[0]:
            edges += [a, b]
        edges.append(self.window[1])
        gaps = [(b - a, a, b) for a, b in zip(edges[::2], edges[1::2])
                if b > a]
        gaps.sort(reverse=True)
        out = []
        for length, a, b in gaps[:n]:
            out.append([self.host_label((a + b) / 2), length])
        return out

    def host_label(self, t: float) -> str:
        cover = [(s, e, name) for name, s, e in self.host if s <= t <= e]
        if not cover:
            return "no host span"
        cover.sort(key=lambda x: (x[1] - x[0]))
        bench = [c[2] for c in cover if c[2].startswith("bench.")
                 and c[2] != WINDOW]
        inner = cover[0][2]
        if bench and inner != bench[0]:
            return f"{bench[0]}/{inner}"
        return bench[0] if bench else inner


def merge(intervals: List[Interval]) -> List[Interval]:
    out: List[Interval] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1] = (out[-1][0], b)
        else:
            out.append((a, b))
    return out


def op_name(hlo: str) -> str:
    """``%quant_matmul_int.14 = s32[...] ...`` -> ``quant_matmul_int``."""
    head = hlo.split(" = ", 1)[0].lstrip("%")
    return re.sub(r"\.\d+$", "", head)


def module_name(event_name: str) -> str:
    """``jit__step(8158213297991489907)`` -> ``jit__step``."""
    return event_name.split("(", 1)[0]


def _events(line):
    for e in line.events:
        yield e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9


def record_cut(host, ends: List[float], window: Interval) -> Optional[float]:
    """Where to cut the window: the earliest of the devices' last op ends
    (``ends``), or the start of the first step that ends after it, if that
    is earlier; ``None`` where no ``bench.step`` span of the window starts
    after the device's record ends."""
    if not ends:
        return None
    end = min(ends)
    lo, hi = window
    steps = [(s, e) for n, s, e in host if n == STEP and lo <= s < hi]
    if not any(s >= end for s, _ in steps):
        return None
    return min([end] + [s for s, e in steps if e > end])


def reduce_profile(pd) -> Optional[TraceSummary]:
    """A :class:`TraceSummary` of a ``jax.profiler.ProfileData``; ``None``
    when the trace holds no ``bench.window`` span."""
    host: List[Tuple[str, float, float]] = []
    window = None
    for plane in pd.planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            evs = list(_events(line))
            if any(n == WINDOW for n, _, _ in evs):
                host = evs
                window = next((s, e) for n, s, e in evs if n == WINDOW)
    if window is None:
        return None
    devices = []
    for plane in pd.planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        lines = {line.name: line for line in plane.lines}
        if OPS_LINE not in lines:
            continue
        mods = sorted((s, e, module_name(n))
                      for n, s, e in _events(lines[MODULES_LINE])) \
            if MODULES_LINE in lines else []
        devices.append((mods, sorted(_events(lines[OPS_LINE]),
                                     key=lambda x: x[1])))
    lo, hi = window
    cut = record_cut(host, [max(e for _, _, e in evs)
                            for _, evs in devices if evs], window)
    cut_info = None
    if cut is not None:
        starts = [s for n, s, _ in host if n == STEP and lo <= s < hi]
        cut_info = Cut(opened=window, steps_kept=sum(s < cut for s in starts),
                       steps=len(starts))
        hi = cut
    ops: Dict[Tuple[str, str], List[float]] = collections.defaultdict(
        lambda: [0.0, 0])
    busy = []
    for mods, evs in devices:
        spans = []
        j = 0
        for n, s, e in evs:
            if e <= lo or s >= hi:
                continue
            s, e = max(s, lo), min(e, hi)
            while j < len(mods) and mods[j][1] < s:
                j += 1
            mod = mods[j][2] if j < len(mods) and mods[j][0] <= s else "?"
            acc = ops[(mod, op_name(n))]
            acc[0] += e - s
            acc[1] += 1
            spans.append((s, e))
        busy.append(merge(spans))
    host = [(n, s, e) for n, s, e in host if e > lo and s < hi]
    return TraceSummary(window=(lo, hi), ops=dict(ops), host=host,
                        busy=busy, cut=cut_info)


def load(log_dir: str) -> Optional[TraceSummary]:
    import jax

    files = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not files:
        return None
    return reduce_profile(jax.profiler.ProfileData.from_file(
        max(files, key=os.path.getmtime)))
