"""Device time per call of a program that ``repro.serving`` names after
its function and wraps in a span of its own, read from a traced run."""
from servebench.trace import CONTAINERS


def ms_per_span(t, program, span):
    """Device ms of the ops of the programs whose name starts with
    ``program`` in the window, per device (control ops left out, as the ops
    of their bodies are counted), over the ``span`` spans that start in the
    window.  ``None`` where either count is 0, as in a program that names
    neither."""
    if t is None or not t.busy:
        return None
    seconds = sum(v[0] for (mod, op), v in t.ops.items()
                  if mod.startswith(program) and op not in CONTAINERS)
    lo, hi = t.window
    calls = sum(1 for n, s, _ in t.host if n == span and lo <= s < hi)
    if not seconds or not calls:
        return None
    return seconds / len(t.busy) / calls * 1e3
