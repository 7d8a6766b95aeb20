"""Seconds from the start of the process to the window's opening: weights,
scheduler, warm-up, and any compile or compile-cache load (host clock)."""


def read(ctx, name):
    return ctx.setup_s
