"""``quant_matmul_int``'s share of its roofline, in %: the least time its
calls in the window could take on this chip (each call the larger of its
operations over the int8 peak and its bytes over the HBM bandwidth,
``servebench.flops``) over the kernel's device time in the trace.

Calls are counted from the steps that start in the traced window (cut where
the device's record ends, ``servebench.trace``) and the model's linear
shapes, per chip under ``quant_tp`` (each chip's tile, once on each chip),
and the least time is scaled by the kernel events the trace holds over
those calls.  Where the two counts differ by more than 1%, the path is not
the one these counts describe, and nothing is read."""
import sys

from servebench.flops import kernel_calls, roofline_seconds

KERNEL = "quant_matmul_int"


def read(ctx, name):
    t = ctx.trace
    if t is None or ctx.peaks is None:
        return None
    steps = t.window_steps(ctx.run.loop_steps())
    if steps is None:
        print(f"{name}: the run's steps and the trace's spans do not pair "
              f"up", file=sys.stderr)
        return None
    seconds, events = t.op_seconds(KERNEL)
    calls = kernel_calls(steps, ctx.cfg, ctx.scfg.max_batch,
                         ctx.scfg.prompt_bucket, ctx.cell.model_ranks)
    n = sum(calls.values())
    if events != n:
        print(f"{name}: {events} {KERNEL} events in the trace, {n} calls "
              f"from the steps", file=sys.stderr)
    if not events or abs(events - n) > n / 100:
        return None
    least, _, _ = roofline_seconds(calls, ctx.peaks)
    return 100.0 * least * (events / n) / seconds
