"""Chip benchmark of the selection service (see BENCHMARK.json, PERF.md).

``run.py`` is the one command.  Everything it needs for a cell is found by
name: ``configs/<config>.json`` (the deployment), ``traffic/<traffic>.json``
(the traffic mix, read by the generator of its ``kind``) and
``metrics/<metric>.py`` (one reader per per-layer metric).
"""
