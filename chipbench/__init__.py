"""Chip benchmark: one cell (configuration x traffic mix) per run of
``python chipbench/run.py``; see ``BENCHMARK.json`` and ``PERF.md``."""
