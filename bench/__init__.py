"""The on-chip benchmark of the Recorder: one cell per run of ``run.py``.

Everything a cell needs is found by name: a configuration under
``configs/``, a traffic mix under ``mixes/``, and one reader per
per-layer metric under ``metrics/``.  ``BENCHMARK.json`` at the root of
the repository lists the cells and metrics.
"""
