"""Share of the traced window in which the first device runs no op while
the program's main thread is inside ``serve.step``, in %: the idle time the
scheduler's own host code leaves, apart from the benchmark's record keeping
and arrival waits.  It also logs on stderr the idle seconds under each
innermost ``serve.*`` span, by overlap: every idle second of the window
that a span covers, where ``breakdown.idle_gaps`` names only the ten
longest gaps, each by the span over its midpoint.  Nothing is read where
the program has no such span."""
import collections
import sys

from servebench.trace import merge

STEP = "serve.step"
PREFIX = "serve."


def _innermost(spans):
    """Sorted, disjoint ``(start, end, name)`` pieces of the time the
    (nested) spans cover, each named by the innermost span over it."""
    out, stack, t = [], [], None
    for s, e, n in sorted(spans, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            end, top = stack.pop()
            if end > t:
                out.append((t, end, top))
                t = end
        if stack and s > t:
            out.append((t, s, stack[-1][1]))
        t = s if t is None else max(t, s)
        stack.append((e, n))
    while stack:
        end, top = stack.pop()
        if end > t:
            out.append((t, end, top))
            t = end
    return out


def _idle(pieces, busy):
    """Idle seconds under each piece's name: its length less the busy time
    inside it (pieces sorted and disjoint, ``busy`` merged)."""
    out = collections.Counter()
    i = 0
    for s, e, n in pieces:
        while i < len(busy) and busy[i][1] <= s:
            i += 1
        j, covered = i, 0.0
        while j < len(busy) and busy[j][0] < e:
            covered += min(e, busy[j][1]) - max(s, busy[j][0])
            j += 1
        out[n] += e - s - covered
    return out


def read(ctx, name):
    t = ctx.trace
    if t is None or not t.busy or not t.window_s:
        return None
    lo, hi = t.window
    spans = [(max(s, lo), min(e, hi), n) for n, s, e in t.host
             if n.startswith(PREFIX) and e > lo and s < hi]
    steps = merge([(s, e) for s, e, n in spans if n == STEP])
    if not steps:
        return None
    in_step = sum(_idle([(s, e, STEP) for s, e in steps], t.busy[0]).values())
    by_span = _idle(_innermost(spans), t.busy[0])
    print(f"{name}: idle s by innermost span: " + ", ".join(
        f"{n} {v:.6f}" for n, v in by_span.most_common()), file=sys.stderr)
    return 100.0 * in_step / t.window_s
