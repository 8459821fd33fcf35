"""Find the knee of a query cell: the highest offered rate it serves without
a growing queue.  One process, one set-up, one window per rate.

    python3 bench/knee.py --workload marco-query --seed 5 --seconds 8 \
        --rates 400 800 1200 1600 2000

Prints one JSON line per rate: latency percentiles, drain time, occupancy,
and ``growth`` (the median latency of the window's last fifth of requests
over that of its first fifth; a queue that grows reads well above 1).  The
cell's traffic file then fixes its rate as a number: a later benchmark PR
sweeps again, no run ever searches.
"""
from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

from run import NoChip, devices_for  # bench/run.py puts src/ on sys.path
from bench import drivers, registry


def sweep(cell: registry.Cell, seed: int, seconds: float, rates, devices):
  from repro.util import compile_cache
  import jax
  compile_cache()
  jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
  drv = drivers.driver_for(cell.traffic["kind"], cell.root)(
      cell.config, dict(cell.traffic), seed, cell.chips, devices,
      root=cell.root)
  drv.setup()
  out = []
  for rate in rates:
    drv.mix["rate_per_s"] = float(rate)
    t0 = time.perf_counter()
    e2e = drv.window(seconds)
    lat = drv.loop.latency_s()
    fifth = max(lat.shape[0] // 5, 1)
    row = {"rate_per_s": rate, "requests": int(lat.shape[0]),
           "p50_ms": 1e3 * float(np.nanpercentile(lat, 50)),
           "p95_ms": e2e["query_p95_ms"],
           "p99_ms": 1e3 * float(np.nanpercentile(lat, 99)),
           "served_per_s": e2e["query_per_s"],
           "growth": float(np.nanmedian(lat[-fifth:])
                           / np.nanmedian(lat[:fifth])),
           "wall_s": time.perf_counter() - t0}
    row.update({k: drv.counters[k] for k in
                ("drain_ms", "occupancy", "lateness_p99_ms")})
    print(json.dumps(row), flush=True)
    out.append(row)
  drv.free()
  return out


def main() -> int:
  ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  ap.add_argument("--workload", default="marco-query")
  ap.add_argument("--seed", type=int, default=1)
  ap.add_argument("--seconds", type=float, default=8.0)
  ap.add_argument("--rates", type=float, nargs="+", required=True)
  args = ap.parse_args()
  cell = registry.Cell(registry.load_benchmark(), args.workload)
  try:
    devices = devices_for(cell.chips)
  except NoChip as e:
    print(f"knee: {e}", file=sys.stderr)
    return 2
  sweep(cell, args.seed, args.seconds, args.rates, devices)
  return 0


if __name__ == "__main__":
  sys.exit(main())
