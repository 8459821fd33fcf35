"""Pallas TPU kernel: fused graph-cut per-node gain sweep.

For the cut objective f(S) = sum_{i in S, j not in S} w_ij the marginal gain
of node v is deg_v - 2 (W x)_v = (W (1 - 2x))_v where x is the indicator of S.
The naive path reads W twice (degree reduce + matvec); this kernel streams
(BM, BN) weight tiles through VMEM once, forms 1 - 2x per column tile, and
accumulates the row-tile partial matvec on the MXU.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import DOT_PRECISION

DEFAULT_BM = 256   # row-tile size
DEFAULT_BN = 256   # column-tile size


def _kernel(w_ref, x_ref, out_ref):
  j = pl.program_id(1)  # column-tile index (innermost -> accumulation dim)

  w = w_ref[...].astype(jnp.float32)            # (BM, BN)
  x = x_ref[...].astype(jnp.float32)            # (1, BN)
  v = 1.0 - 2.0 * x                             # (1, BN)

  part = jax.lax.dot_general(w, v, (((1,), (1,)), ((), ())),
                             precision=DOT_PRECISION,
                             preferred_element_type=jnp.float32)  # (BM, 1)

  @pl.when(j == 0)
  def _init():
    out_ref[...] = jnp.zeros_like(out_ref)

  out_ref[...] += part.T


def graph_cut_gain_pallas(w, in_s, *, block_m: int = DEFAULT_BM,
                          block_n: int = DEFAULT_BN,
                          interpret: bool = False):
  """Fused node gains; (n, n), (n,) -> (n,) float32.

  n % block_m == 0 and n % block_n == 0 are required (ops.py pads).
  """
  n = w.shape[0]
  assert w.shape == (n, n), w.shape
  assert n % block_m == 0 and n % block_n == 0, (n, block_m, block_n)
  x = in_s.astype(jnp.float32)[None, :]         # (1, n)

  grid = (n // block_m, n // block_n)
  out = pl.pallas_call(
      _kernel,
      grid=grid,
      in_specs=[
          pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
          pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
      ],
      out_specs=pl.BlockSpec((1, block_m), lambda i, j: (0, i)),
      out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
      interpret=interpret,
  )(w, x)
  return out[0]
