"""Device time of one prefill call, in ms: the device seconds of the ops of
the prefill programs (``jit_serve_prefill``, ``_resume``, ``_packed``) in
the traced window, per device, over the program's ``serve.prefill`` spans
in the window, one a call.  The pool's copies that the device runs before
or after a prefill are not counted.  Silent where either count is 0, as in
a program that names neither."""
from servebench.programs import ms_per_span


def read(ctx, name):
    return ms_per_span(ctx.trace, "jit_serve_prefill", "serve.prefill")
