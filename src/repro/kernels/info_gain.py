"""Pallas TPU kernel: fused information-gain cross-term for GP active sets.

The IVM / information-gain oracle (Sec. 3.4.1) needs, for every candidate v,

    cond[v] = k(v, v) + ridge - || L^{-1} k(S, v) ||^2

where L = chol(K_SS + ridge I).  The naive path materializes the (k_max, nc)
cross-kernel matrix in HBM, solves against it, and reduces.  This kernel
streams (BN, d) candidate tiles through VMEM: the cross-kernel tile and the
back-substitution (as a matmul with the precomputed inverse ``linv``) both run
on the MXU, and the diagonal variance reduce happens in-register -- the
(k_max, nc) intermediate never touches HBM.

``linv`` has the columns for not-yet-selected (padded) rows zeroed by the
caller, which is equivalent to masking the dead rows of k(S, cand).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import DOT_PRECISION

DEFAULT_BN = 256   # candidate-tile rows


def _kernel(sel_ref, linv_ref, cd_ref, out_ref, *, kernel: str, h: float,
            ridge: float):
  sel = sel_ref[...].astype(jnp.float32)        # (k, d)
  linv = linv_ref[...].astype(jnp.float32)      # (k, k)
  cd = cd_ref[...].astype(jnp.float32)          # (BN, d)

  k_sc = jax.lax.dot_general(sel, cd, (((1,), (1,)), ((), ())),
                             precision=DOT_PRECISION,
                             preferred_element_type=jnp.float32)  # (k, BN)
  c2 = jnp.sum(cd * cd, axis=1)                 # (BN,)
  if kernel == "rbf":
    s2 = jnp.sum(sel * sel, axis=1, keepdims=True)
    d2 = jnp.maximum(s2 - 2.0 * k_sc + c2[None, :], 0.0)
    k_sc = jnp.exp(-d2 / (h * h))
    k_vv = jnp.ones_like(c2)
  else:
    k_vv = c2

  c = jax.lax.dot_general(linv, k_sc, (((1,), (0,)), ((), ())),
                          precision=DOT_PRECISION,
                          preferred_element_type=jnp.float32)     # (k, BN)
  cond = k_vv + ridge - jnp.sum(c * c, axis=0)
  out_ref[...] = jnp.maximum(cond, 1e-12)[None, :]


def info_gain_cond_pallas(sel_feats, linv, cand_feats, *,
                          kernel: str = "rbf", h: float = 0.75,
                          ridge: float = 1.0, block_n: int = DEFAULT_BN,
                          interpret: bool = False):
  """Fused conditional variances; (k, d), (k, k), (nc, d) -> (nc,) float32.

  nc % block_n == 0 is required (ops.py pads).  The selected block (k, d) and
  linv (k, k) are small (k <= k_max) and stay resident across the grid.
  """
  k, d = sel_feats.shape
  nc = cand_feats.shape[0]
  assert nc % block_n == 0, (nc, block_n)
  assert linv.shape == (k, k), (linv.shape, k)

  grid = (nc // block_n,)
  out = pl.pallas_call(
      functools.partial(_kernel, kernel=kernel, h=h, ridge=ridge),
      grid=grid,
      in_specs=[
          pl.BlockSpec((k, d), lambda j: (0, 0)),
          pl.BlockSpec((k, k), lambda j: (0, 0)),
          pl.BlockSpec((block_n, d), lambda j: (j, 0)),
      ],
      out_specs=pl.BlockSpec((1, block_n), lambda j: (0, j)),
      out_shape=jax.ShapeDtypeStruct((1, nc), jnp.float32),
      interpret=interpret,
  )(sel_feats, linv, cand_feats)
  return out[0]
