"""The cell benchmark's harness (PERF.md says what it measures and why).

``benchmark/run.py`` is the one entry point. Everything that belongs to one
configuration, one traffic mix or one per-layer metric is a file of its own
under ``benchmark/`` that this package finds by the name ``BENCHMARK.json``
gives it, so a later PR adds files and entries and edits nothing here.
"""
