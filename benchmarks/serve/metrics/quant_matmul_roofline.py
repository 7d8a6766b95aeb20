"""``quant_matmul_int``'s share of its roofline, in %: the least time its
calls in the window could take on this chip (each call the larger of its
operations over the int8 peak and its bytes over the HBM bandwidth,
``servebench.flops``) over the kernel's device time in the trace.

Calls are counted from the window's steps and the model's linear shapes,
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
    seconds, events = t.op_seconds(KERNEL)
    calls = kernel_calls(ctx.run.loop_steps(), ctx.cfg, ctx.scfg.max_batch,
                         ctx.scfg.prompt_bucket)
    n = sum(calls.values())
    if events != n:
        print(f"{name}: {events} {KERNEL} events in the trace, {n} calls "
              f"from the steps", file=sys.stderr)
    if not events or abs(events - n) > n / 100:
        return None
    least, _, _ = roofline_seconds(calls, ctx.peaks)
    return 100.0 * least * (events / n) / seconds
