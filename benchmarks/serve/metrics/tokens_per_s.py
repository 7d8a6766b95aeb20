"""Output tokens emitted in the window over the window's length (host
clock)."""
from servebench.window import tokens_in_window


def read(ctx, name):
    n = tokens_in_window(ctx.run)
    return n / ctx.run.seconds if n else None
