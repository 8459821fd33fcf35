"""Accumulation-tree merge benchmark: flat vs tree epochs on a wide mesh
(the BENCH_8.json trajectory of ISSUE 10).

The flat GreeDi merge all_gathers every shard's kappa candidates onto every
shard and runs one (m*kappa)-candidate greedy; at m=64 that is a 2048-row
replicated merge whose cost grows linearly in m.  The accumulation tree
(core/greedi.py, ``merge="tree"``) re-views the mesh as log_b m nested axes
and merges b-child groups per level, so no shard ever materialises more than
``max_factor(m, b) * kappa`` candidate rows.  Two operating points, each in
its own forced-host-device subprocess (the in-process run.py driver keeps
its single device):

  * **tree vs flat** -- ``greedi_sharded_fast`` epochs on an m=64 mesh
    (quick: m=16), flat vs ``merge="tree", tree_branch=8`` (quick: 4).
    The b=m reduction contract is asserted bit-exact before timing.  The
    gated ``speedup_tree_vs_flat`` entry is wall-clock flat/tree; the
    deterministic ``speedup_merge_bytes_flat_over_tree`` entry is the peak
    merge-row ratio from ``merge_peak_rows`` (m*kappa vs max_factor*kappa
    rows -- exact, zero variance, machine-independent).
  * **lazy vs standard round 1** -- ``greedi_sharded_fast`` with
    ``mode="lazy"`` vs ``mode="standard"`` on a 4-shard mesh with big
    shards (n_local=4096), where the cached-column lazy rescan beats the
    full per-step column sweep.  Selections are asserted identical first
    (the lazy contract is bit-parity, not approximation).

Speedup entries are dimensionless ratios -- what
benchmarks/check_regression.py gates against BENCH_8.json.  Raw epoch
timings ride along as informational (ungated) entries.
"""
from __future__ import annotations

import json
import os
import subprocess
import sys

D = 32
EPOCH_REPS = 3


def _emit_child(name: str, us: float, derived: str, shapes: dict) -> None:
  import jax
  shapes = dict(shapes, platform=jax.default_backend())
  print("BENCH " + json.dumps({"name": name, "us": us, "derived": derived,
                               "shapes": shapes}), flush=True)


def _time(fn, reps: int) -> float:
  import time

  import jax
  jax.block_until_ready(fn())            # compile + settle
  ts = []
  for _ in range(reps):
    t0 = time.perf_counter()
    jax.block_until_ready(fn())
    ts.append(time.perf_counter() - t0)
  return min(ts)


def _child_tree(m: int, b: int, n: int, kappa: int, kf: int) -> None:
  import jax
  import jax.numpy as jnp
  import numpy as np

  from repro.core import greedi as GD
  from repro.util import make_mesh

  mesh = make_mesh((m,), ("data",))
  shapes = {"n": n, "d": D, "kappa": kappa, "k_final": kf, "mesh": m,
            "branch": b}
  feats = jnp.asarray(np.random.default_rng(0).normal(size=(n, D)),
                      jnp.float32)

  def jit_epoch(**kw):
    return jax.jit(lambda f: GD.greedi_sharded_fast(
        f, mesh=mesh, kappa=kappa, k_final=kf, **kw))

  flat = jit_epoch()
  tree = jit_epoch(merge="tree", tree_branch=b)

  # b=m reduction contract: the degenerate tree IS the flat merge, bit for
  # bit -- assert before trusting either timing
  r_flat = flat(feats)
  r_degen = jax.jit(lambda f: GD.greedi_sharded_fast(
      f, mesh=mesh, kappa=kappa, k_final=kf, merge="tree",
      tree_branch=m))(feats)
  np.testing.assert_array_equal(np.asarray(r_flat.sel_gids),
                                np.asarray(r_degen.sel_gids))
  np.testing.assert_array_equal(np.asarray(r_flat.stage1_values),
                                np.asarray(r_degen.stage1_values))

  r_tree = tree(feats)
  assert (np.asarray(r_tree.sel_gids)[np.asarray(r_tree.sel_valid)] >= 0).all()

  t_flat = _time(lambda: flat(feats), EPOCH_REPS)
  t_tree = _time(lambda: tree(feats), EPOCH_REPS)
  _emit_child(f"tree_merge/flat_epoch_m{m}", t_flat * 1e6, "us_per_epoch",
              shapes)
  _emit_child(f"tree_merge/tree_epoch_m{m}", t_tree * 1e6, "us_per_epoch",
              shapes)
  _emit_child(f"tree_merge/speedup_tree_vs_flat_m{m}", t_flat / t_tree,
              "x_flat_over_tree", shapes)

  # peak merge footprint: exact row counts from the same helper the service
  # exports as a gauge -- deterministic, so the gate is noise-free
  rows_flat = GD.merge_peak_rows(m, kappa)
  rows_tree = GD.merge_peak_rows(m, kappa, merge="tree", tree_branch=b)
  bshapes = dict(shapes, rows_flat=rows_flat, rows_tree=rows_tree)
  _emit_child(f"tree_merge/flat_merge_bytes_m{m}", rows_flat * D * 4,
              "peak_merge_bytes", bshapes)
  _emit_child(f"tree_merge/tree_merge_bytes_m{m}", rows_tree * D * 4,
              "peak_merge_bytes", bshapes)
  _emit_child(f"tree_merge/speedup_merge_bytes_flat_over_tree_m{m}",
              rows_flat / rows_tree, "x_flat_over_tree_rows", bshapes)


def _child_lazy(m: int, n: int, kappa: int, kf: int) -> None:
  import jax
  import jax.numpy as jnp
  import numpy as np

  from repro.core import greedi as GD
  from repro.util import make_mesh

  mesh = make_mesh((m,), ("data",))
  shapes = {"n": n, "d": D, "kappa": kappa, "k_final": kf, "mesh": m}
  feats = jnp.asarray(np.random.default_rng(1).normal(size=(n, D)),
                      jnp.float32)

  def jit_epoch(mode):
    return jax.jit(lambda f: GD.greedi_sharded_fast(
        f, mesh=mesh, kappa=kappa, k_final=kf, mode=mode))

  std, lazy = jit_epoch("standard"), jit_epoch("lazy")
  r_std, r_lazy = std(feats), lazy(feats)
  # lazy is an exact reformulation of round 1, not an approximation
  np.testing.assert_array_equal(np.asarray(r_std.sel_gids),
                                np.asarray(r_lazy.sel_gids))
  assert int(np.asarray(r_lazy.r1_rescans).sum()) > 0

  t_std = _time(lambda: std(feats), EPOCH_REPS)
  t_lazy = _time(lambda: lazy(feats), EPOCH_REPS)
  _emit_child(f"tree_merge/fast_standard_epoch_n{n}", t_std * 1e6,
              "us_per_epoch", shapes)
  _emit_child(f"tree_merge/fast_lazy_epoch_n{n}", t_lazy * 1e6,
              "us_per_epoch", shapes)
  _emit_child(f"tree_merge/speedup_fast_lazy_vs_standard_n{n}",
              t_std / t_lazy, "x_standard_over_lazy", shapes)


def _run_child(ndev: int, args: list[str], timeout: int = 3600) -> list[str]:
  # a forced-host-device smoke suite by design: the child never contends
  # for an accelerator the parent process may hold
  env = dict(os.environ, JAX_PLATFORMS="cpu")
  env["XLA_FLAGS"] = (env.get("XLA_FLAGS", "") +
                      f" --xla_force_host_platform_device_count={ndev}"
                      ).strip()
  out = subprocess.run(
      [sys.executable, os.path.abspath(__file__), "--child"] + args,
      env=env, capture_output=True, text=True, timeout=timeout)
  if out.returncode != 0:
    raise RuntimeError(f"tree_merge child {args} failed:\n{out.stdout}\n"
                       f"{out.stderr}")
  return out.stdout.splitlines()


def run(quick: bool = False) -> None:
  from benchmarks.common import emit

  if quick:
    tree_args = ["tree", "16", "4", "8192", "16", "16"]
    lazy_args = ["lazy", "4", "8192", "16", "16"]
    ndev_tree = 16
  else:
    tree_args = ["tree", "64", "8", "32768", "32", "32"]
    lazy_args = ["lazy", "4", "16384", "16", "16"]
    ndev_tree = 64

  lines = _run_child(ndev_tree, tree_args)
  lines += _run_child(int(lazy_args[1]), lazy_args)
  for line in lines:
    if line.startswith("BENCH "):
      r = json.loads(line[len("BENCH "):])
      emit(r["name"], r["us"], derived=r["derived"], shapes=r["shapes"])


if __name__ == "__main__":
  if sys.argv[1:2] == ["--child"]:
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, os.path.join(root, "src"))
    sys.path.insert(0, root)
    if sys.argv[2] == "tree":
      _child_tree(*(int(x) for x in sys.argv[3:8]))
    else:
      _child_lazy(*(int(x) for x in sys.argv[3:7]))
  else:
    run(quick="--quick" in sys.argv)
