"""Pallas TPU kernel: blocked pairwise similarity matrix (RBF / linear).

Used when a benchmark legitimately needs the materialized kernel matrix
(e.g. the GP active-set information-gain cross terms, Sec. 3.4.1).  Tiles the
(nx, ny) output; the feature contraction runs on the MXU; the RBF transform
is fused so only the finished tile is written to HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import DOT_PRECISION

DEFAULT_B = 256


def _kernel(x_ref, y_ref, out_ref, *, kernel: str, h: float):
  x = x_ref[...].astype(jnp.float32)
  y = y_ref[...].astype(jnp.float32)
  dot = jax.lax.dot_general(x, y, (((1,), (1,)), ((), ())),
                            precision=DOT_PRECISION,
                            preferred_element_type=jnp.float32)
  if kernel == "rbf":
    x2 = jnp.sum(x * x, axis=1, keepdims=True)
    y2 = jnp.sum(y * y, axis=1, keepdims=True)
    d2 = jnp.maximum(x2 - 2.0 * dot + y2.T, 0.0)
    out_ref[...] = jnp.exp(-d2 / (h * h))
  else:
    out_ref[...] = dot


def pairwise_pallas(x, y, *, kernel: str = "rbf", h: float = 0.75,
                    block_x: int = DEFAULT_B, block_y: int = DEFAULT_B,
                    interpret: bool = False):
  nx, d = x.shape
  ny = y.shape[0]
  assert nx % block_x == 0 and ny % block_y == 0, (nx, ny, block_x, block_y)
  grid = (nx // block_x, ny // block_y)
  return pl.pallas_call(
      functools.partial(_kernel, kernel=kernel, h=h),
      grid=grid,
      in_specs=[
          pl.BlockSpec((block_x, d), lambda i, j: (i, 0)),
          pl.BlockSpec((block_y, d), lambda i, j: (j, 0)),
      ],
      out_specs=pl.BlockSpec((block_x, block_y), lambda i, j: (i, j)),
      out_shape=jax.ShapeDtypeStruct((nx, ny), jnp.float32),
      interpret=interpret,
  )(x, y)
