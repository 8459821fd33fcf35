"""Cells shrunk to CPU size, for the benchmark's own tests: the same
drivers, references and limits at a few thousand rows of width at most
32."""
from __future__ import annotations

from bench import registry


def cell(workload: str, **config) -> registry.Cell:
  """``workload`` from BENCHMARK.json with its sizes cut to CPU scale
  (``config`` overrides keys of the configuration after the cut)."""
  c = registry.Cell(registry.load_benchmark(), workload)
  cfg = c.config
  cfg.update(d=min(int(cfg["d"]), 32), append_block=256, kappa=8, k_final=8)
  if cfg["corpus"].get("kind", "clusters") == "clusters":
    cfg["corpus"] = dict(cfg["corpus"], clusters=32)
  if "rows_per_chip" in cfg:
    cfg["rows_per_chip"] = 1024
  if "capacity_per_chip" in cfg:
    cfg["capacity_per_chip"] = 8192
  cfg.update(config)
  t = c.traffic
  if t["kind"] == "bulk_append":
    t.update(call_rows=512, check_rows=64)
  if t["kind"] == "tenant_queries":
    t.update(setup_rows=2048, rate_per_s=100, check_requests=32, k_min=2,
             k_max=8)
  return c
