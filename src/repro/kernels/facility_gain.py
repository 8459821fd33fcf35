"""Pallas TPU kernel: fused facility-location marginal-gain evaluation.

This is the greedy hot loop (Eq. 2 of the paper applied to the exemplar
objective of Sec. 3.4.2): for every candidate j,

    gain[j] = sum_i mask_i * max( sim(e_i, c_j) - cov_i, 0 )

The naive path materializes the (ne, nc) similarity matrix in HBM each greedy
step.  This kernel streams (BM, d) eval tiles and (BN, d) candidate tiles
through VMEM, does the similarity matmul on the MXU, and reduces the
relu-thresholded increments in-register -- sim never touches HBM.  Arithmetic
intensity goes from O(1) (read sim, subtract, reduce) to O(d) per output.

Tiles are 128-aligned for the MXU; the eval-axis is the innermost grid dim so
the output block is revisited and accumulated across eval tiles.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import DOT_PRECISION

DEFAULT_BM = 256   # eval-tile rows
DEFAULT_BN = 256   # candidate-tile rows


def _kernel(ev_ref, cd_ref, covm_ref, out_ref, *, kernel: str, h: float):
  i = pl.program_id(1)  # eval-tile index (innermost -> accumulation dim)

  ev = ev_ref[...].astype(jnp.float32)        # (BM, d)
  cd = cd_ref[...].astype(jnp.float32)        # (BN, d)
  cov = covm_ref[0, :].astype(jnp.float32)    # (BM,)
  msk = covm_ref[1, :].astype(jnp.float32)    # (BM,)

  sim = jax.lax.dot_general(ev, cd, (((1,), (1,)), ((), ())),
                            precision=DOT_PRECISION,
                            preferred_element_type=jnp.float32)  # (BM, BN)
  if kernel == "rbf":
    e2 = jnp.sum(ev * ev, axis=1, keepdims=True)
    c2 = jnp.sum(cd * cd, axis=1, keepdims=True)
    d2 = jnp.maximum(e2 - 2.0 * sim + c2.T, 0.0)
    sim = jnp.exp(-d2 / (h * h))

  inc = jnp.maximum(sim - cov[:, None], 0.0) * msk[:, None]
  part = jnp.sum(inc, axis=0, keepdims=True)  # (1, BN)

  @pl.when(i == 0)
  def _init():
    out_ref[...] = jnp.zeros_like(out_ref)

  out_ref[...] += part


def facility_gain_pallas(eval_feats, cand_feats, cov, eval_mask, *,
                         kernel: str = "linear", h: float = 0.75,
                         block_m: int = DEFAULT_BM, block_n: int = DEFAULT_BN,
                         interpret: bool = False):
  """Fused gains; shapes (ne, d), (nc, d), (ne,), (ne,) -> (nc,) float32.

  ne % block_m == 0 and nc % block_n == 0 are required (ops.py pads).
  """
  ne, d = eval_feats.shape
  nc = cand_feats.shape[0]
  assert ne % block_m == 0 and nc % block_n == 0, (ne, nc, block_m, block_n)
  covm = jnp.stack([cov.astype(jnp.float32),
                    eval_mask.astype(jnp.float32)])  # (2, ne)

  grid = (nc // block_n, ne // block_m)
  out = pl.pallas_call(
      functools.partial(_kernel, kernel=kernel, h=h),
      grid=grid,
      in_specs=[
          pl.BlockSpec((block_m, d), lambda j, i: (i, 0)),
          pl.BlockSpec((block_n, d), lambda j, i: (j, 0)),
          pl.BlockSpec((2, block_m), lambda j, i: (0, i)),
      ],
      out_specs=pl.BlockSpec((1, block_n), lambda j, i: (0, j)),
      out_shape=jax.ShapeDtypeStruct((1, nc), jnp.float32),
      interpret=interpret,
  )(eval_feats, cand_feats, covm)
  return out[0]
