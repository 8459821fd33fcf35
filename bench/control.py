"""The lower-precision control of a cell: the same run with the feature rows
stored in bfloat16 (the program's own lower-precision path; the
configurations state float32).  Its numbers set the upper reading of each
limit in bench/limits/ and it must come out not correct.  The benchmark's
own runs never run it.

    python3 bench/control.py --workload tiny-epoch --seed 5 --seconds 10
"""
from __future__ import annotations

import argparse
import json
import sys

from run import NoChip, devices_for, run
from bench import registry


def main() -> int:
  ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  ap.add_argument("--workload", required=True)
  ap.add_argument("--seed", type=int, required=True)
  ap.add_argument("--seconds", type=float, required=True)
  args = ap.parse_args()
  cell = registry.Cell(registry.load_benchmark(), args.workload)
  cell.config["feat_dtype"] = "bfloat16"
  try:
    devices = devices_for(cell.chips)
  except NoChip as e:
    print(f"control: {e}", file=sys.stderr)
    return 2
  result = run(cell, args.seed, args.seconds, False, devices)
  print(json.dumps({"control": "bfloat16 feature storage",
                    "correct": result["correct"],
                    "checks": result["checks"]}), flush=True)
  return 0


if __name__ == "__main__":
  sys.exit(main())
