"""The whole step's share of the chips' peak, in %: the operations of the
tokens the window's steps really processed, each class at the peak of the
arithmetic it runs in (int8 linears at the int8 peak, the rest at the bf16
peak) of all the cell's chips together, over the summed wall time of those
steps (host clock)."""
from servebench.flops import model_ops


def read(ctx, name):
    if ctx.peaks is None:
        return None
    steps = ctx.run.loop_steps()
    wall = sum(s.end - s.start for s in steps)
    if not wall:
        return None
    int8, other = model_ops(steps, ctx.cfg)
    at_peak = (int8 / ctx.peaks["int8_ops_per_s"]
               + other / ctx.peaks["bf16_flops_per_s"])
    return 100.0 * at_peak / (ctx.cell.chips * wall)
