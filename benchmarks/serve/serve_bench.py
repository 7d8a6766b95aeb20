#!/usr/bin/env python3
"""Serving benchmark: one cell of ``BENCHMARK.json`` on the chip.

    python3 benchmarks/serve/serve_bench.py --workload <name> --seed <n> \\
        --seconds <s> --trace <0|1>

Makes the weights on the cell's chips from the seed (split as the program
splits them where the cell file gives a ``mesh``), builds the program's
``repro.serving.Scheduler`` as the cell says, warms up every prefill length
the cell's traffic can draw, drives ``Scheduler.step()`` for ``--seconds``
under the cell's traffic, checks the served tokens against the float32
reference, and prints one JSON object as the last line of standard output
(the numbers compared, each beside its limit, are also the last lines of
standard error).  With ``--trace 1`` the window runs under the profiler and
the line carries the cell's per-layer metrics instead of its end-to-end
ones.  Without a TPU, or with fewer chips than the cell asks for, it exits
non-zero and prints no result.

JAX's persistent compilation cache is ``.jax_cache`` at the checkout's
root, so only the first run of a cell in a checkout compiles.
"""
import time

STARTED = time.monotonic()

import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(HERE))
sys.path[:0] = [HERE, os.path.join(ROOT, "src")]
os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")

from servebench.run import main  # noqa: E402

if __name__ == "__main__":
    sys.exit(main(sys.argv[1:], started=STARTED))
