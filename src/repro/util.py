"""Small framework utilities.

``scan``/``fori`` wrap jax.lax control flow with a global "unroll" switch:
XLA's cost_analysis counts a while-loop body ONCE regardless of trip count,
so the dry-run's cost pass re-lowers the model with every scan fully unrolled
(``unroll_scans()``) and reads exact HLO FLOPs from the *lowered* (pre-XLA)
module.  The compiled artifact used for memory/collective analysis keeps the
rolled loops.
"""
from __future__ import annotations

import contextlib
import os
import pathlib
import threading
from typing import Any, Callable

import jax
import jax.numpy as jnp

_STATE = threading.local()


def _unrolling() -> bool:
  return getattr(_STATE, "unroll", False)


@contextlib.contextmanager
def unroll_scans():
  prev = getattr(_STATE, "unroll", False)
  _STATE.unroll = True
  try:
    yield
  finally:
    _STATE.unroll = prev


def scan(body: Callable, init, xs, length: int | None = None, *,
         unroll: int | bool | None = None):
  if length is None:
    length = jax.tree.leaves(xs)[0].shape[0]
  if unroll is None:
    unroll = length if _unrolling() else 1
  return jax.lax.scan(body, init, xs, length=length, unroll=unroll)


def fori(lo: int, hi: int, body: Callable, init):
  """fori_loop that fully unrolls under ``unroll_scans()`` (static bounds)."""
  if _unrolling():
    c = init
    for t in range(lo, hi):
      c = body(t, c)
    return c
  return jax.lax.fori_loop(lo, hi, body, init)


# ---------------------------------------------------------------------------
# meshes, shard_map and the compilation cache
# ---------------------------------------------------------------------------

REPO_ROOT = pathlib.Path(__file__).resolve().parents[2]


def make_mesh(axis_shapes, axis_names, devices=None):
  """jax.make_mesh over the first prod(axis_shapes) of ``devices`` (default:
  all devices of the process) with Auto axis types: the sharded paths here
  place data through explicit shard_map specs, not sharding-in-types."""
  return jax.make_mesh(axis_shapes, axis_names,
                       axis_types=(jax.sharding.AxisType.Auto,)
                       * len(axis_names), devices=devices)


def shard_map(f, *, mesh, in_specs, out_specs):
  """jax.shard_map with varying-manual-axes checking off."""
  return jax.shard_map(f, mesh=mesh, in_specs=in_specs, out_specs=out_specs,
                       check_vma=False)


def compile_cache() -> str:
  """Turn on JAX's persistent compilation cache; return its directory.

  ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting: it is left
  to JAX and no other directory is set.  Otherwise the cache lives at the
  fixed path ``<repo>/.jax_cache``: the directory is part of what a later
  process must find again, so it never carries a temporary name, a pid or a
  time.
  """
  env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
  if env:
    return env
  path = str(REPO_ROOT / ".jax_cache")
  jax.config.update("jax_compilation_cache_dir", path)
  return path
