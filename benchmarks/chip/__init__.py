"""Chip benchmark of the serving stack: one cell (a model configuration
under a traffic mix) per run, driven through the program's own engine.

``run.py`` is the entry point. Configurations, traffic mixes, per-cell
settings and per-layer metric readers are data and small files found by
the names in ``BENCHMARK.json``; the yardstick (traffic generation, the
plain reference, work counts, peaks and the trace reduction) lives here
and imports nothing of the program.
"""
