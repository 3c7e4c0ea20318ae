"""Chip benchmark harness: drives ``ContinuousBatchingEngine.step()`` under a
traffic mix, times it by the host clock, reduces a profiler trace to
per-layer metrics, and checks the served tokens against a plain reference.

Everything that belongs to one configuration, traffic mix, metric, kernel
or device lives in a file of its own under ``benchmarks/chip/`` and is
found by the name ``BENCHMARK.json`` gives it (see ``spec.py``).
"""
