"""Where a traced window's time goes, by the program's own spans and scopes.

    python3 bench/scope_breakdown.py --workload marco-query --seed 5 \
        --seconds 10

One set-up and one window traced as ``bench/run.py --trace 1`` traces it,
then one JSON line: busy and idle time; device seconds per program scope
(per chip) and the share of the busy time the scopes cover; idle seconds by
the innermost open program or ``bench.*`` span; a query cell's mean drain
split into host and ``store.query_call`` time; the numbers of
``program_trace.layer_metrics``; and the window's end-to-end metrics with
the profiler on, to set beside an untraced run's.  Nothing is compared with
the reference.
"""
from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys

from run import TRACE_DIR, NoChip, devices_for  # puts src/ on sys.path
from bench import drivers, program_trace, registry, tracing


def breakdown(cell: registry.Cell, seed: int, seconds: float,
              devices) -> dict:
  import jax
  from repro import obs
  from repro.util import compile_cache
  compile_cache()
  jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
  drv = drivers.KINDS[cell.traffic["kind"]](
      cell.config, cell.traffic, seed, cell.chips, devices, seconds)
  drv.setup()
  gc.collect()
  gc.freeze()
  wait = obs.REGISTRY.histogram("repro_batcher_queue_wait_seconds")
  w0 = wait.get()
  shutil.rmtree(TRACE_DIR, ignore_errors=True)
  jax.profiler.start_trace(str(TRACE_DIR))
  try:
    with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
      e2e = drv.window(seconds, span=jax.profiler.TraceAnnotation)
  finally:
    jax.profiler.stop_trace()
  w1 = wait.get()
  if w1["count"] > w0["count"]:
    drv.counters["queue_wait_ms"] = (1e3 * (w1["sum"] - w0["sum"])
                                     / (w1["count"] - w0["count"]))
  path = tracing.find_xplane(str(TRACE_DIR))
  red = program_trace.reduce_program(tracing.load_xplane(path),
                                     program_trace.load_op_scopes(path))
  shutil.rmtree(TRACE_DIR, ignore_errors=True)
  drv.free()
  idle = red.window_s - red.busy_s
  return {
      "workload": cell.name, "seed": seed, "traced_e2e": e2e,
      "window_s": red.window_s, "busy_s": red.busy_s,
      "devices": red.devices,
      "scope_s": {sc: red.scope_s(sc) / red.devices for sc in red.scopes()},
      "scoped_pct": red.coverage_pct() if red.scopes() else 0.0,
      "idle_s": sorted(([k, v] for k, v in red.gaps.items()),
                       key=lambda kv: -kv[1]),
      "unannotated_pct_of_idle": (
          100.0 * red.gaps.get(program_trace.UNANNOTATED, 0.0) / idle
          if idle > 0 else 0.0),
      "drain_split_ms": red.drain_split_ms(),
      "layer_metrics": program_trace.layer_metrics(red, drv.counters),
      "counters": {k: v for k, v in drv.counters.items()
                   if isinstance(v, (int, float))}}


def main(argv=None) -> int:
  ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  ap.add_argument("--workload", required=True)
  ap.add_argument("--seed", type=int, required=True)
  ap.add_argument("--seconds", type=float, default=10.0)
  args = ap.parse_args(argv)
  cell = registry.Cell(registry.load_benchmark(), args.workload)
  try:
    devices = devices_for(cell.chips)
  except NoChip as e:
    print(f"scope_breakdown: {e}", file=sys.stderr)
    return 2
  print(json.dumps(breakdown(cell, args.seed, args.seconds, devices)),
        flush=True)
  return 0


if __name__ == "__main__":
  sys.exit(main())
