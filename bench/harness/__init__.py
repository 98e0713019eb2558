"""Benchmark harness: cell lookup, traffic, trace reduction, counts.

Everything that belongs to one configuration, traffic mix or per-layer
metric lives in a file of its own under ``bench/`` and is found by the
name ``BENCHMARK.json`` gives it; this package holds only the general
machinery that reads those files.
"""
