"""Each cell's comparison at CPU size: the program as configured comes out
correct, and the lower-precision control (bfloat16 feature storage, the
program's own lower-precision path) does not."""
import time

import jax
import pytest

from bench import registry, tiny
from bench import run as R

BENCH = registry.load_benchmark()
ONE_CHIP = [w["name"] for w in BENCH["workloads"] if w["chips"] == 1]
SEED = 2 ** 31 + 12345


def _run(cell, seed=SEED, seconds=1.0):
  return R.run(cell, seed, seconds, False, jax.devices(),
               t_start=time.perf_counter())


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_cell_is_correct_as_configured(workload):
  res = _run(tiny.cell(workload))
  assert res["correct"], res["checks"]
  assert res["attempted"] > 0 and res["failed"] == 0
  assert list(res)[-1] == "checks"
  names = {m["name"] for m in tiny.cell(workload).end_to_end}
  assert set(res["metrics"]) == names
  assert all(v["value"] > 0 for v in res["metrics"].values())


@pytest.mark.parametrize("workload", ONE_CHIP)
def test_lower_precision_control_is_not_correct(workload):
  cell = tiny.cell(workload, feat_dtype="bfloat16")
  res = _run(cell)
  assert not res["correct"]
  gap = "feat_gap" if cell.traffic["kind"] == "epochs" else "sieve_feat_gap"
  assert res["checks"][gap]["value"] > 1e-4, res["checks"]
