"""Device time of the decode program per decode step, in ms: the device
seconds of the ops of ``jit_serve_decode`` in the traced window, per device,
over the program's ``serve.decode`` spans in the window.  Silent where
either count is 0, as in a program that names neither."""
from servebench.programs import ms_per_span


def read(ctx, name):
    return ms_per_span(ctx.trace, "jit_serve_decode", "serve.decode")
