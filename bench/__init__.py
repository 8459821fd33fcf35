"""Chip benchmark of the selection service (see BENCHMARK.json, PERF.md).

``run.py`` is the one command.  Everything it needs for a cell is found by
name, under the tree the cell was read from (``registry.Cell.root``):

* ``configs/<config>.json``: the deployment (``file`` in BENCHMARK.json);
* ``traffic/<traffic>.json``: the traffic mix;
* ``limits/<workload>.json``: the limit of each number compared;
* ``metrics/<metric>.py``: one ``read(ctx)`` per per-layer metric.

Code that a name stands for comes from a built-in table first, then from a
file (``registry.lookup``):

* the mix's ``kind``: ``drivers.KINDS``, else ``kinds/<kind>.py`` (its
  ``Driver``, a subclass of ``drivers.Driver``);
* the configuration's ``corpus.kind`` (``clusters`` where absent):
  ``drivers.CORPORA``, else ``corpora/<kind>.py`` (its ``draw(cfg, n,
  seed)``);
* the configuration's ``objective``, the epoch reference:
  ``drivers.EPOCH_REFERENCES``, else ``objectives/<objective>.py`` (its
  ``epoch_numbers(x, rng, m, cfg, sel_gids, sel_feats, value)``);
* a traced kernel's stem, its operations and bytes: ``kernel_work.WORK``,
  else ``work/<stem>.py`` (its ``work(operands, results)``; a kernel found
  in neither has no roofline).

The configuration's ``kernel_kwargs`` (a JSON object, such as ``{"h":
0.75}``) reach the service as a sorted tuple.  So a deployment is added as
new files and entries; no file here changes.
"""
