"""Serving benchmark harness: cells, traffic, window arithmetic, trace
reduction and the float32 reference comparison.

Everything that belongs to one configuration, traffic mix, cell or
per-layer metric lives in a file of its own under ``benchmarks/serve``
and is found by the name ``BENCHMARK.json`` gives it (:mod:`.spec`).
"""
