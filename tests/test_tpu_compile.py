"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Interpret mode (every other kernel test) cannot see what the chip's
compiler refuses: scalar stores to VMEM, blocks narrower than 128 lanes, or
tiles that overflow VMEM.  These tests lower each kernel with Mosaic for a
``v5e:2x2`` topology that is described, not attached, and check that the
result holds a ``tpu_custom_call``.  Nothing runs, so they say nothing about
results or speed.

The topology is described inside a module fixture (never at import time):
only one process at a time may load the TPU library, and every pytest
worker imports this file.  The persistent compilation cache is off around
these compiles, because an entry written for a described chip cannot be read
back without one.
"""
from __future__ import annotations

import functools
import os

import jax
import jax.numpy as jnp
import pytest

from repro.kernels import autotune
from repro.kernels.facility_gain import facility_gain_pallas
from repro.kernels.pairwise import pairwise_pallas
from repro.kernels.select_top1 import (coverage_select_pallas,
                                       facility_select_pallas,
                                       graph_cut_select_pallas,
                                       info_select_pallas)

N = 1 << 20      # evaluation rows of the one-chip smoke corpus
D = 64           # its feature width
AB = 1024        # append chunk of the smoke's bound pass
B = 128          # query tile of the batched exact tier on TPU


@pytest.fixture(scope="module")
def one_chip():
  from jax.experimental import topologies
  from jax.experimental.compilation_cache import compilation_cache as cc
  from jax.sharding import SingleDeviceSharding

  prev_log = os.environ.get("TPU_LOG_DIR")
  os.environ["TPU_LOG_DIR"] = "disabled"
  prev_cache = jax.config.jax_enable_compilation_cache
  jax.config.update("jax_enable_compilation_cache", False)
  cc.reset_cache()
  try:
    try:
      topo = topologies.get_topology_desc(platform="tpu",
                                          topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler installed here
      pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    yield SingleDeviceSharding(topo.devices[0])
  finally:
    jax.config.update("jax_enable_compilation_cache", prev_cache)
    cc.reset_cache()
    if prev_log is None:
      os.environ.pop("TPU_LOG_DIR", None)
    else:
      os.environ["TPU_LOG_DIR"] = prev_log


def _blk(n: int, d: int = D, itemsize: int = 4) -> int:
  """The block the TPU autotable gives an n-row axis (what ops.py uses)."""
  return autotune.pick_block(n, d, backend="tpu", itemsize=itemsize)


def _facility_gain(d: int, dtype, n: int):
  bm = _blk(n, d, jnp.dtype(dtype).itemsize)
  fn = functools.partial(facility_gain_pallas, block_m=bm, block_n=bm)
  return fn, [((n, d), dtype), ((n, d), dtype), ((n,), jnp.float32),
              ((n,), jnp.float32)]


def _facility_select():
  fn = functools.partial(facility_select_pallas, block_m=_blk(N),
                         block_n=_blk(N))
  return fn, [((N, D), jnp.float32), ((N, D), jnp.float32),
              ((N,), jnp.float32), ((N,), jnp.float32), ((N,), jnp.float32)]


def _facility_select_batched():
  one = functools.partial(facility_select_pallas, block_m=_blk(N),
                          block_n=_blk(N))
  fn = jax.vmap(one, in_axes=(None, None, 0, 0, 0))
  return fn, [((N, D), jnp.float32), ((N, D), jnp.float32),
              ((B, N), jnp.float32), ((B, N), jnp.float32),
              ((B, N), jnp.float32)]


def _facility_select_round2():
  """Round 2's merged block: kappa = 64 candidates, padded to one lane-wide
  block by ops.py (a 64-row block is refused by Mosaic)."""
  nc = _blk(64)
  fn = functools.partial(facility_select_pallas, block_m=_blk(N),
                         block_n=nc)
  return fn, [((N, D), jnp.float32), ((nc, D), jnp.float32),
              ((N,), jnp.float32), ((N,), jnp.float32), ((nc,), jnp.float32)]


def _coverage_select():
  n = 1 << 16
  fn = functools.partial(coverage_select_pallas, block_m=_blk(n),
                         block_n=_blk(n))
  return fn, [((n, D), jnp.float32), ((n, D), jnp.float32)] + [
      ((n,), jnp.float32)] * 4


def _info_select():
  n, k = 1 << 16, 64
  fn = functools.partial(info_select_pallas, block_n=_blk(n))
  return fn, [((k, D), jnp.float32), ((k, k), jnp.float32),
              ((n, D), jnp.float32), ((n,), jnp.float32)]


def _info_select_d6():
  """The GP active-set deployment's round-1 pass: one chip's share of the
  click log, 11,468,800 rows of 6-d user features, in one call."""
  n, k, d = 11468800, 64, 6
  fn = functools.partial(info_select_pallas, block_n=_blk(n, d))
  return fn, [((k, d), jnp.float32), ((k, k), jnp.float32),
              ((n, d), jnp.float32), ((n,), jnp.float32)]


def _graph_cut_select():
  n = 4096
  fn = functools.partial(graph_cut_select_pallas, block_m=_blk(n, n),
                         block_n=_blk(n, n))
  return fn, [((n, n), jnp.float32), ((n,), jnp.float32),
              ((n,), jnp.float32)]


def _pairwise():
  fn = functools.partial(pairwise_pallas, kernel="linear",
                         block_x=_blk(AB), block_y=_blk(N))
  return fn, [((AB, D), jnp.float32), ((N, D), jnp.float32)]


CASES = {
    "facility_gain_d64": lambda: _facility_gain(D, jnp.float32, N),
    "facility_gain_d64_bf16": lambda: _facility_gain(D, jnp.bfloat16, N),
    "facility_gain_d3072": lambda: _facility_gain(3072, jnp.float32, 1 << 16),
    "facility_select": _facility_select,
    "facility_select_batched": _facility_select_batched,
    "facility_select_round2": _facility_select_round2,
    "coverage_select": _coverage_select,
    "info_select": _info_select,
    "info_select_d6": _info_select_d6,
    "graph_cut_select": _graph_cut_select,
    "pairwise": _pairwise,
}


@pytest.mark.parametrize("name", sorted(CASES))
def test_kernel_compiles_for_v5e(one_chip, name):
  fn, shapes = CASES[name]()
  args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip) for s, dt in shapes]
  compiled = jax.jit(fn).lower(*args).compile()
  assert "tpu_custom_call" in compiled.as_text()


def test_info_gain_epoch_calls_info_select_for_v5e(one_chip, monkeypatch):
  """The standard-mode information-gain epoch of the GP active-set
  deployment (one chip's 11,468,800 6-d rows, kappa = k_final = 64),
  compiled for the chip, calls the fused select kernel under the instruction
  name ``info_select`` for round 1 and the gain kernel ``info_gain_cond``
  for round 2: the names the device trace gives those kernels and the
  benchmark's kernel work reads.  Its resident block and temporaries fit
  the chip's 16 GB."""
  import re

  import numpy as np
  from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

  from repro.kernels import ops
  from repro.service import SelectionService
  from repro.util import make_mesh

  monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
  monkeypatch.setattr(autotune, "default_backend", lambda: "tpu")
  # the process may have cached interpret mode from an earlier CPU test
  monkeypatch.setattr(ops, "_interpret", lambda: False)
  cap, d = 11_468_800, 6
  svc = SelectionService(make_mesh((1,), ("data",)), d=d, kappa=64,
                         k_final=64, kernel="rbf",
                         kernel_kwargs=(("h", 0.75),), backend="pallas",
                         mode="standard", warm_start=False,
                         objective="info_gain", sieve=False)
  svc.mesh = Mesh(np.array(list(one_chip.device_set)), ("data",))
  rows = NamedSharding(svc.mesh, P("data"))
  rep = NamedSharding(svc.mesh, P())
  args = [jax.ShapeDtypeStruct(s, dt, sharding=sh) for s, dt, sh in (
      ((cap, d), jnp.float32, rows), ((cap,), jnp.int32, rows),
      ((cap,), jnp.float32, rows), ((1,), jnp.float32, rep),
      ((), jnp.float32, rep), ((2,), jnp.uint32, rep))]
  compiled = jax.jit(svc._epoch_raw).lower(*args).compile()
  mem = compiled.memory_analysis()
  assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9, mem
  text = compiled.as_text()
  calls = re.findall(r"%([A-Za-z_]+)\.\d+ = .*custom_call_target="
                     r'"tpu_custom_call"', text)
  assert sorted(calls) == ["info_gain_cond", "info_select"], calls


def test_vmem_clamp_shrinks_wide_tiles():
  """The TPU autotable's blocks shrink with the row width until the
  kernel's double-buffered tiles fit the VMEM budget, and never drop below
  the 128-lane floor."""
  assert _blk(N, 64) == 512
  assert _blk(N, 3072) < _blk(N, 64)
  assert _blk(N, 3072, itemsize=2) >= _blk(N, 3072, itemsize=4)
  assert _blk(N, 1 << 16) == autotune.LANES
  assert _blk(8, 64) == autotune.LANES
