"""The chip benchmark's harness: cells, traffic, the system under test,
the correctness check and the reduction of profiler traces to metrics.

Everything that belongs to one configuration, traffic mix, per-layer
metric or kernel sits in a file of its own under ``benchmarks/chip`` and
is found by the name ``BENCHMARK.json`` gives it.
"""
