"""Share of the window's step time spent in steps that admitted a prompt,
in % (the benchmark's host clock around each ``Scheduler.step()``)."""


def read(ctx, name):
    steps = ctx.run.loop_steps()
    total = sum(s.end - s.start for s in steps)
    if not total:
        return None
    pre = sum(s.end - s.start for s in steps if s.prefill_lens)
    return 100.0 * pre / total
