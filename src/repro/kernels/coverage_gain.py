"""Pallas TPU kernel: fused saturated-coverage marginal-gain evaluation.

Lin & Bilmes (2011) coverage objective: for every candidate j,

    gain[j] = sum_i mask_i * [ min(cover_i + s_ij, cap_i) - min(cover_i, cap_i) ]

with s_ij = max(sim(e_i, c_j), 0).  Same streaming structure as
facility_gain.py: (BM, d) eval tiles x (BN, d) candidate tiles, similarity on
the MXU, the saturation clamp and masked reduce in-register; the (ne, nc)
similarity matrix never touches HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import DOT_PRECISION

DEFAULT_BM = 256   # eval-tile rows
DEFAULT_BN = 256   # candidate-tile rows


def _kernel(ev_ref, cd_ref, aux_ref, out_ref, *, kernel: str, h: float):
  i = pl.program_id(1)  # eval-tile index (innermost -> accumulation dim)

  ev = ev_ref[...].astype(jnp.float32)          # (BM, d)
  cd = cd_ref[...].astype(jnp.float32)          # (BN, d)
  cover = aux_ref[0, :].astype(jnp.float32)     # (BM,)
  cap = aux_ref[1, :].astype(jnp.float32)       # (BM,)
  msk = aux_ref[2, :].astype(jnp.float32)       # (BM,)

  sim = jax.lax.dot_general(ev, cd, (((1,), (1,)), ((), ())),
                            precision=DOT_PRECISION,
                            preferred_element_type=jnp.float32)  # (BM, BN)
  if kernel == "rbf":
    e2 = jnp.sum(ev * ev, axis=1, keepdims=True)
    c2 = jnp.sum(cd * cd, axis=1, keepdims=True)
    d2 = jnp.maximum(e2 - 2.0 * sim + c2.T, 0.0)
    sim = jnp.exp(-d2 / (h * h))
  sim = jnp.maximum(sim, 0.0)

  new = jnp.minimum(cover[:, None] + sim, cap[:, None])
  inc = (new - jnp.minimum(cover, cap)[:, None]) * msk[:, None]
  part = jnp.sum(inc, axis=0, keepdims=True)    # (1, BN)

  @pl.when(i == 0)
  def _init():
    out_ref[...] = jnp.zeros_like(out_ref)

  out_ref[...] += part


def coverage_gain_pallas(eval_feats, cand_feats, cover, cap, eval_mask, *,
                         kernel: str = "linear", h: float = 0.75,
                         block_m: int = DEFAULT_BM, block_n: int = DEFAULT_BN,
                         interpret: bool = False):
  """Fused gains; (ne, d), (nc, d), (ne,), (ne,), (ne,) -> (nc,) float32.

  ne % block_m == 0 and nc % block_n == 0 are required (ops.py pads).
  """
  ne, d = eval_feats.shape
  nc = cand_feats.shape[0]
  assert ne % block_m == 0 and nc % block_n == 0, (ne, nc, block_m, block_n)
  aux = jnp.stack([cover.astype(jnp.float32), cap.astype(jnp.float32),
                   eval_mask.astype(jnp.float32)])  # (3, ne)

  grid = (nc // block_n, ne // block_m)
  out = pl.pallas_call(
      functools.partial(_kernel, kernel=kernel, h=h),
      grid=grid,
      in_specs=[
          pl.BlockSpec((block_m, d), lambda j, i: (i, 0)),
          pl.BlockSpec((block_n, d), lambda j, i: (j, 0)),
          pl.BlockSpec((3, block_m), lambda j, i: (0, i)),
      ],
      out_specs=pl.BlockSpec((1, block_n), lambda j, i: (0, j)),
      out_shape=jax.ShapeDtypeStruct((1, nc), jnp.float32),
      interpret=interpret,
  )(eval_feats, cand_feats, aux)
  return out[0]
