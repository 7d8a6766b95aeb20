"""Share of the traced window in which no operation ran on the device, in %
(profiler trace: 1 minus the union of the ``XLA Ops`` intervals; the window
is cut where the device's record ends, ``servebench.trace``)."""


def read(ctx, name):
    t = ctx.trace
    if t is None or not t.busy or not t.window_s:
        return None
    return 100.0 * (1.0 - t.busy_s / t.window_s)
