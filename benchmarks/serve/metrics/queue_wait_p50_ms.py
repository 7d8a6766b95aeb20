"""Median wait in the scheduler's admission queue, in ms: admit minus
arrival as the scheduler's own ``RequestMetrics`` recorded them, over the
requests due in the window (a request never admitted waits until the run
stopped waiting for it)."""
from servebench.window import percentile


def read(ctx, name):
    run = ctx.run
    end = max(run.drain_end, run.close)
    waits = []
    for rid, r in run.requests.items():
        if not run.open <= r.due < run.close or rid not in ctx.admits:
            continue
        arrival, admit = ctx.admits[rid]
        waits.append((admit if admit is not None else end) - arrival)
    return percentile(waits, 50) * 1e3 if waits else None
