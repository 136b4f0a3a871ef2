"""Chip benchmark of the serving stack: one cell (a model configuration
under a traffic mix) per run, driven through the program's own engine.

``run.py`` is the entry point. Configurations, architectures, traffic
mixes, per-cell settings and per-layer metric readers are data and small
files found by the names in ``BENCHMARK.json`` and by a configuration's
``model_type`` (``spec.py``); the yardstick (traffic generation, the
plain references, work counts, peaks and the trace reduction) lives here
and imports nothing of the program.
"""
