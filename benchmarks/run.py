"""Benchmark suite entry point: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV lines (plus human-readable detail).
``--quick`` shrinks sweeps; ``--only <name>`` runs a single benchmark;
``--json PATH`` additionally writes machine-readable results (name,
us_per_call, derived, shapes, backend) -- the format the committed
``BENCH_*.json`` baselines and benchmarks/check_regression.py consume.
"""
from __future__ import annotations

import argparse
import time


def main() -> None:
  ap = argparse.ArgumentParser()
  ap.add_argument("--quick", action="store_true")
  ap.add_argument("--only", default=None)
  ap.add_argument("--json", default=None, metavar="PATH",
                  help="write machine-readable results to PATH")
  args = ap.parse_args()

  from repro.util import compile_cache

  compile_cache()
  from benchmarks import (common, fig4_exemplar, fig6_active_set,
                          fig8_speedup, fig9_maxcut, fig10_coverage,
                          kernels_bench, query_serving, roofline,
                          select_step, service_epochs, sieve_query,
                          store_transfer, tree_merge)

  if args.json:
    common.start_collection()

  suites = {
      "fig4_exemplar": lambda: fig4_exemplar.run(quick=args.quick),
      "fig6_active_set": lambda: fig6_active_set.run(quick=args.quick),
      "fig9_maxcut": lambda: fig9_maxcut.run(quick=args.quick),
      "fig10_coverage": lambda: fig10_coverage.run(quick=args.quick),
      "fig8_speedup": lambda: fig8_speedup.run(quick=args.quick),
      "kernels": lambda: kernels_bench.run(quick=args.quick),
      "roofline": lambda: roofline.run(quick=args.quick),
      "select_step": lambda: select_step.run(quick=args.quick),
      "service_epochs": lambda: service_epochs.run(quick=args.quick),
      "query_serving": lambda: query_serving.run(quick=args.quick),
      "sieve_query": lambda: sieve_query.run(quick=args.quick),
      "store_transfer": lambda: store_transfer.run(quick=args.quick),
      "tree_merge": lambda: tree_merge.run(quick=args.quick),
  }
  names = [args.only] if args.only else list(suites)
  failures = []
  for name in names:
    print(f"\n### {name} " + "#" * (60 - len(name)), flush=True)
    t0 = time.time()
    try:
      suites[name]()
    except Exception as e:  # keep the suite going; failures print clearly
      failures.append(name)
      print(f"{name},FAILED,{e!r}", flush=True)
    print(f"# {name} done in {time.time()-t0:.1f}s", flush=True)
  if args.json:
    common.write_json(args.json, quick=args.quick, failures=failures)
  if failures:
    raise SystemExit(f"benchmark failures: {failures}")


if __name__ == "__main__":
  main()
