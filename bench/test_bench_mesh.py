"""The four-chip epoch cell on four CPU devices (a subprocess, since the
device count is fixed when JAX starts): correct as configured, not correct
under the lower-precision control or with the merge's exchange between
chips left out."""
import json
import os
import pathlib
import subprocess
import sys

ROOT = pathlib.Path(__file__).resolve().parents[1]

SCRIPT = r"""
import json, sys, time
import jax, jax.numpy as jnp
from bench import run as R, tiny

def go(cell):
  r = R.run(cell, 2 ** 32 + 9, 1.0, False, jax.devices(),
            t_start=time.perf_counter())
  return {"correct": r["correct"], "checks": r["checks"]}

out = {"sound": go(tiny.cell("tiny-epoch-m4")),
       "control": go(tiny.cell("tiny-epoch-m4", feat_dtype="bfloat16"))}
m = 4
real = jax.lax.all_gather
def local_only(x, axis_name, **kw):
  # every chip merges only its own round-1 block
  return jnp.broadcast_to(x[None], (m,) + x.shape)
jax.lax.all_gather = local_only
out["no_exchange"] = go(tiny.cell("tiny-epoch-m4"))
jax.lax.all_gather = real
print(json.dumps(out))
"""


def test_four_chip_epoch_cell():
  env = dict(os.environ, JAX_PLATFORMS="cpu",
             XLA_FLAGS="--xla_force_host_platform_device_count=4")
  out = subprocess.run([sys.executable, "-c", SCRIPT], cwd=ROOT, env=env,
                       capture_output=True, text=True, timeout=600)
  assert out.returncode == 0, out.stderr[-4000:]
  res = json.loads(out.stdout.strip().splitlines()[-1])
  assert res["sound"]["correct"], res["sound"]
  assert not res["control"]["correct"], res["control"]
  assert not res["no_exchange"]["correct"], res["no_exchange"]
  gap = res["no_exchange"]["checks"]["greedy_gap"]
  assert gap["value"] > gap["limit"]
