"""Run one benchmark cell on the chips of this machine.

    python3 bench/run.py --workload tiny-epoch --seed 7 --seconds 30 --trace 0

Set-up (corpus from the seed, the service, warm-up of the cell's own
shapes) counts as ``setup_s``; then the cell's traffic runs for
``--seconds``; then, with the program's state freed, what the window
produced is compared with the plain reference.  The last line of standard
output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics`` (the cell's end-to-end metrics, or with ``--trace 1`` its
per-layer metrics), ``device``, with ``--trace 1`` a ``breakdown``, and last
``checks``: each compared number beside its limit.  A run that finds no TPU,
or fewer chips than the cell asks for, prints no result and exits 2.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import math  # noqa: E402
import pathlib  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402

ROOT = pathlib.Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT / "src"), str(ROOT)]

from bench import drivers, kernel_work, registry, tracing  # noqa: E402

TRACE_DIR = ROOT / ".bench_trace"


class NoChip(RuntimeError):
  pass


class Context:
  """What a per-layer metric reader may read."""

  def __init__(self, red, peaks, counters, root=registry.ROOT):
    self.trace = red
    self.peaks = peaks
    self.counters = counters
    self.root = root

  def roofline(self, kernel: str):
    return tracing.roofline_pct(self.trace, kernel, self.peaks, self.root)


def devices_for(chips: int):
  """The machine's TPU devices; no TPU, or fewer chips than the cell asks
  for, is an error (no fallback to the CPU)."""
  import jax
  devs = jax.devices()
  if devs[0].platform != "tpu":
    raise NoChip(f"needs a TPU; JAX found {devs[0].platform}")
  if len(devs) < chips:
    raise NoChip(f"the cell needs {chips} chips; JAX found {len(devs)}")
  return devs


def load_limits(root: pathlib.Path, workload: str) -> dict:
  with open(root / "bench" / "limits" / f"{workload}.json") as f:
    return json.load(f)["limits"]


def run(cell: registry.Cell, seed: int, seconds: float, trace: bool,
        devices, t_start: float = T_START) -> dict:
  """One run of ``cell``; returns the result object."""
  import jax
  from repro.util import compile_cache
  compile_cache()
  jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
  drv = drivers.driver_for(cell.traffic["kind"], cell.root)(
      cell.config, cell.traffic, seed, cell.chips, devices, seconds,
      root=cell.root)
  drv.setup()
  # everything set-up made lives as long as the run: a full collection
  # inside the window scans only what the window allocates
  gc.collect()
  gc.freeze()
  setup_s = time.perf_counter() - t_start
  compiles = []

  def on_event(event, duration, **_):
    if event == "/jax/core/compile/backend_compile_duration":
      compiles.append(duration)

  jax.monitoring.register_event_duration_secs_listener(on_event)
  used = devices[:cell.chips]
  result: dict = {"correct": False, "attempted": 0, "failed": 0,
                  "metrics": {}}
  if trace:
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    jax.profiler.start_trace(str(TRACE_DIR))
    try:
      with jax.profiler.TraceAnnotation(tracing.WINDOW_SPAN):
        drv.window(seconds, span=jax.profiler.TraceAnnotation)
    finally:
      jax.profiler.stop_trace()
    red = tracing.reduce_trace(
        tracing.load_xplane(tracing.find_xplane(str(TRACE_DIR))))
    shutil.rmtree(TRACE_DIR, ignore_errors=True)
    kernels: dict = {}
    for name in red.calls:
      k = kernels.setdefault(tracing.stem(name), [0, 0.0])
      k[0] += red.op_count[name]
      k[1] += red.op_s[name]
    drv.counters["kernels"] = kernels
    # each traced Pallas call's shapes and the work counted from them
    drv.counters["kernel_calls"] = {
        name: {"operands": ops, "results": res,
               "work": kernel_work.work(tracing.stem(name), ops, res,
                                        cell.root)}
        for name, (ops, res) in red.calls.items()}
    ctx = Context(red, tracing.load_peaks(used[0].device_kind), drv.counters,
                  cell.root)
    for m in cell.per_layer:
      v = cell.reader(m["name"])(ctx)
      if v is not None:
        result["metrics"][m["name"]] = {"value": float(v), "unit": m["unit"]}
  else:
    e2e = drv.window(seconds)
    e2e["setup_s"] = setup_s
    for m in cell.end_to_end:
      result["metrics"][m["name"]] = {"value": float(e2e[m["name"]]),
                                      "unit": m["unit"]}
  jax.monitoring.unregister_event_duration_listener(on_event)
  # nothing may compile inside the window: every shape is warmed in set-up
  drv.counters["window_compiles"] = len(compiles)
  peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
             for d in used)
  result["device"] = {"platform": used[0].platform,
                      "kind": used[0].device_kind,
                      "count": len(devices), "memory_peak_bytes": int(peak)}
  if trace:
    result["device"].update(busy_s=red.busy_s, window_s=red.window_s)
    result["breakdown"] = {
        "device_ops": [[n, s / red.devices] for n, s in red.top_ops()],
        "idle_gaps": red.top_gaps()}
  drv.free()
  t_check = time.perf_counter()
  numbers = drv.check()
  drv.counters["check_s"] = time.perf_counter() - t_check
  limits = load_limits(cell.root, cell.name)
  missing = sorted(set(numbers) - set(limits))
  if missing:
    raise KeyError(f"no limit for {missing} in bench/limits/{cell.name}.json")
  checks = {k: {"value": float(v), "limit": float(limits[k])}
            for k, v in sorted(numbers.items())}
  ok = all(math.isfinite(c["value"]) and c["value"] <= c["limit"]
           for c in checks.values())
  result.update(correct=bool(ok and drv.failed == 0),
                attempted=int(drv.attempted), failed=int(drv.failed))
  result["counters"] = drv.counters
  result["checks"] = checks
  return result


def main(argv=None) -> int:
  ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  ap.add_argument("--workload", required=True)
  ap.add_argument("--seed", type=int, required=True)
  ap.add_argument("--seconds", type=float, required=True)
  ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
  args = ap.parse_args(argv)
  cell = registry.Cell(registry.load_benchmark(), args.workload)
  try:
    devices = devices_for(cell.chips)
  except NoChip as e:
    print(f"bench: {e}", file=sys.stderr)
    return 2
  result = run(cell, args.seed, args.seconds, bool(args.trace), devices)
  for k, v in result["counters"].items():
    print(f"counter {k} = {v!r}", file=sys.stderr)
  for k, c in result["checks"].items():
    print(f"check {k} = {c['value']!r} limit {c['limit']!r}",
          file=sys.stderr)
  print(json.dumps(result), flush=True)
  return 0


if __name__ == "__main__":
  sys.exit(main())
