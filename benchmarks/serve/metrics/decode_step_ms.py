"""Median host time of the window's steps that only decoded (no prompt
admitted), in ms.  A step returns after the host has read its tokens back,
so each time covers the device's work."""
import statistics


def read(ctx, name):
    t = [s.end - s.start for s in ctx.run.loop_steps()
         if s.decode_rows and not s.prefill_lens]
    return statistics.median(t) * 1e3 if t else None
