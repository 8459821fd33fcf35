"""Pallas TPU kernels: fused select-step oracles (in-kernel top-1 reduction).

The greedy hot loop (Eq. 2) only ever consumes the *argmax* of the marginal
gains, yet the gain kernels in facility_gain.py / coverage_gain.py /
info_gain.py / graph_cut_gain.py write the full (n,) gains vector to HBM,
which a second XLA pass argmaxes and a third re-touches for the update.  The
"select" family here fuses the reduction into the gain kernel itself: each
candidate tile's gains live only in a VMEM scratch accumulator, a per-tile
top-1 (max + lowest-index-of-max) runs in-register once the tile is fully
accumulated, and a running global (best_gain, best_idx) pair -- the only
thing that ever leaves the kernel -- is folded across the candidate grid.
The (n,) gains vector never touches HBM and argmax disappears as a pass.
The pair is held in lane-wide (1, 128) output blocks (Mosaic refuses scalar
stores to VMEM); lane 0 is read back.

Semantics shared by every kernel (and their ref.py ground truths):

  * ``ok`` masks selectable candidates; masked-out entries score ``NEG``
    (cond kernels: 0.0, their natural floor) so any feasible entry wins.
  * ties break to the LOWEST candidate index: tiles are visited in index
    order, in-tile ties take the smallest offset, and the running best is
    only replaced on a strictly greater score.
  * with no feasible candidate the result is (floor, 0), matching
    ``jnp.argmax`` over an all-floor vector.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.autotune import LANES
from repro.kernels.ref import DOT_PRECISION, NEG


def _top1_fold(scores, base, best_ref, idx_ref):
  """Fold a (1, B) masked score tile into the running (best, idx) pair.

  The pair lives in lane-wide (1, LANES) VMEM blocks, every lane holding
  the same value: Mosaic cannot store a scalar to VMEM, so the fold stays
  in vector ops end to end (keepdims reductions, lane broadcasts)."""
  b = scores.shape[1]
  m = jnp.max(scores, axis=1, keepdims=True)                 # (1, 1)
  iota = jax.lax.broadcasted_iota(jnp.int32, (1, b), 1)
  ti = jnp.min(jnp.where(scores == m, iota, b), axis=1, keepdims=True)
  best = best_ref[...]
  upd = m > best                                              # (1, LANES)
  idx_ref[...] = jnp.where(upd, base + ti, idx_ref[...])
  best_ref[...] = jnp.where(upd, m, best)


def _init_best(best_ref, idx_ref):
  best_ref[...] = jnp.full(best_ref.shape, -jnp.inf, jnp.float32)
  idx_ref[...] = jnp.zeros(idx_ref.shape, jnp.int32)


def _scalar_outs():
  return (
      (jax.ShapeDtypeStruct((1, LANES), jnp.float32),
       jax.ShapeDtypeStruct((1, LANES), jnp.int32)),
      (pl.BlockSpec((1, LANES), lambda *_: (0, 0)),
       pl.BlockSpec((1, LANES), lambda *_: (0, 0))),
  )


# ---------------------------------------------------------------------------
# facility location
# ---------------------------------------------------------------------------


def _facility_kernel(ev_ref, cd_ref, covm_ref, ok_ref, best_ref, idx_ref,
                     acc_ref, *, kernel: str, h: float):
  j = pl.program_id(0)  # candidate-tile index (outer)
  i = pl.program_id(1)  # eval-tile index (inner -> accumulation dim)
  ne_b = pl.num_programs(1)

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

  @pl.when((j == 0) & (i == 0))
  def _init():
    _init_best(best_ref, idx_ref)

  @pl.when(i == 0)
  def _reset():
    acc_ref[...] = jnp.zeros_like(acc_ref)

  acc_ref[...] += part

  @pl.when(i == ne_b - 1)
  def _finalize():
    ok = ok_ref[...].astype(jnp.float32)      # (1, BN)
    masked = jnp.where(ok > 0, acc_ref[...], NEG)
    _top1_fold(masked, j * acc_ref.shape[1], best_ref, idx_ref)


def facility_select_pallas(eval_feats, cand_feats, cov, eval_mask, cand_ok, *,
                           kernel: str = "linear", h: float = 0.75,
                           block_m: int = 256, block_n: int = 256,
                           interpret: bool = False):
  """Fused top-1 facility gain; -> ((), f32 best, (), int32 idx).

  Shapes (ne, d), (nc, d), (ne,), (ne,), (nc,); ne % block_m == 0 and
  nc % block_n == 0 are required (ops.py pads, with ok=0 on padded rows).
  """
  ne, d = eval_feats.shape
  nc = cand_feats.shape[0]
  assert ne % block_m == 0 and nc % block_n == 0, (ne, nc, block_m, block_n)
  covm = jnp.stack([cov.astype(jnp.float32),
                    eval_mask.astype(jnp.float32)])      # (2, ne)
  okm = cand_ok.astype(jnp.float32)[None, :]             # (1, nc)

  out_shape, out_specs = _scalar_outs()
  best, idx = pl.pallas_call(
      functools.partial(_facility_kernel, kernel=kernel, h=h),
      grid=(nc // block_n, ne // block_m),
      in_specs=[
          pl.BlockSpec((block_m, d), lambda j, i: (i, 0)),
          pl.BlockSpec((block_n, d), lambda j, i: (j, 0)),
          pl.BlockSpec((2, block_m), lambda j, i: (0, i)),
          pl.BlockSpec((1, block_n), lambda j, i: (0, j)),
      ],
      out_specs=out_specs,
      out_shape=out_shape,
      scratch_shapes=[pltpu.VMEM((1, block_n), jnp.float32)],
      interpret=interpret,
  )(eval_feats, cand_feats, covm, okm)
  return best[0, 0], idx[0, 0]


# ---------------------------------------------------------------------------
# saturated coverage
# ---------------------------------------------------------------------------


def _coverage_kernel(ev_ref, cd_ref, aux_ref, ok_ref, best_ref, idx_ref,
                     acc_ref, *, kernel: str, h: float):
  j = pl.program_id(0)
  i = pl.program_id(1)
  ne_b = pl.num_programs(1)

  ev = ev_ref[...].astype(jnp.float32)          # (BM, d)
  cd = cd_ref[...].astype(jnp.float32)          # (BN, d)
  cover = aux_ref[0, :].astype(jnp.float32)     # (BM,)
  cap = aux_ref[1, :].astype(jnp.float32)       # (BM,)
  msk = aux_ref[2, :].astype(jnp.float32)       # (BM,)

  sim = jax.lax.dot_general(ev, cd, (((1,), (1,)), ((), ())),
                            precision=DOT_PRECISION,
                            preferred_element_type=jnp.float32)
  if kernel == "rbf":
    e2 = jnp.sum(ev * ev, axis=1, keepdims=True)
    c2 = jnp.sum(cd * cd, axis=1, keepdims=True)
    d2 = jnp.maximum(e2 - 2.0 * sim + c2.T, 0.0)
    sim = jnp.exp(-d2 / (h * h))
  sim = jnp.maximum(sim, 0.0)

  new = jnp.minimum(cover[:, None] + sim, cap[:, None])
  inc = (new - jnp.minimum(cover, cap)[:, None]) * msk[:, None]
  part = jnp.sum(inc, axis=0, keepdims=True)

  @pl.when((j == 0) & (i == 0))
  def _init():
    _init_best(best_ref, idx_ref)

  @pl.when(i == 0)
  def _reset():
    acc_ref[...] = jnp.zeros_like(acc_ref)

  acc_ref[...] += part

  @pl.when(i == ne_b - 1)
  def _finalize():
    ok = ok_ref[...].astype(jnp.float32)
    masked = jnp.where(ok > 0, acc_ref[...], NEG)
    _top1_fold(masked, j * acc_ref.shape[1], best_ref, idx_ref)


def coverage_select_pallas(eval_feats, cand_feats, cover, cap, eval_mask,
                           cand_ok, *, kernel: str = "linear", h: float = 0.75,
                           block_m: int = 256, block_n: int = 256,
                           interpret: bool = False):
  """Fused top-1 saturated-coverage gain; same contract as facility select."""
  ne, d = eval_feats.shape
  nc = cand_feats.shape[0]
  assert ne % block_m == 0 and nc % block_n == 0, (ne, nc, block_m, block_n)
  aux = jnp.stack([cover.astype(jnp.float32), cap.astype(jnp.float32),
                   eval_mask.astype(jnp.float32)])       # (3, ne)
  okm = cand_ok.astype(jnp.float32)[None, :]

  out_shape, out_specs = _scalar_outs()
  best, idx = pl.pallas_call(
      functools.partial(_coverage_kernel, kernel=kernel, h=h),
      grid=(nc // block_n, ne // block_m),
      in_specs=[
          pl.BlockSpec((block_m, d), lambda j, i: (i, 0)),
          pl.BlockSpec((block_n, d), lambda j, i: (j, 0)),
          pl.BlockSpec((3, block_m), lambda j, i: (0, i)),
          pl.BlockSpec((1, block_n), lambda j, i: (0, j)),
      ],
      out_specs=out_specs,
      out_shape=out_shape,
      scratch_shapes=[pltpu.VMEM((1, block_n), jnp.float32)],
      interpret=interpret,
  )(eval_feats, cand_feats, aux, okm)
  return best[0, 0], idx[0, 0]


# ---------------------------------------------------------------------------
# information-gain conditional variance (top-1 over cond; log is monotone)
# ---------------------------------------------------------------------------


def _info_kernel(sel_ref, linv_ref, cd_ref, ok_ref, best_ref, idx_ref, *,
                 kernel: str, h: float, ridge: float):
  j = pl.program_id(0)

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
  cond = jnp.maximum(k_vv + ridge - jnp.sum(c * c, axis=0), 1e-12)

  @pl.when(j == 0)
  def _init():
    _init_best(best_ref, idx_ref)

  bn = cd.shape[0]
  ok = ok_ref[...].astype(jnp.float32)          # (1, BN)
  # cond >= 1e-12 > 0, so the 0.0 floor keeps any feasible candidate ahead
  masked = jnp.where(ok > 0, cond[None, :], 0.0)
  _top1_fold(masked, j * bn, best_ref, idx_ref)


def info_select_pallas(sel_feats, linv, cand_feats, cand_ok, *,
                       kernel: str = "rbf", h: float = 0.75,
                       ridge: float = 1.0, block_n: int = 256,
                       interpret: bool = False):
  """Fused top-1 conditional variance; -> ((), f32 best cond, (), int32 idx).

  The information-gain 0.5 log(cond / sigma^2) and the DPP log(cond) are
  strictly increasing in cond, so the cond-space argmax IS the gain argmax;
  the caller maps the returned scalar through its log.  Infeasible
  candidates floor at 0.0 (cond is clamped >= 1e-12, so feasible wins).
  """
  k, d = sel_feats.shape
  nc = cand_feats.shape[0]
  assert nc % block_n == 0, (nc, block_n)
  assert linv.shape == (k, k), (linv.shape, k)
  okm = cand_ok.astype(jnp.float32)[None, :]

  out_shape, out_specs = _scalar_outs()
  best, idx = pl.pallas_call(
      functools.partial(_info_kernel, kernel=kernel, h=h, ridge=ridge),
      grid=(nc // block_n,),
      in_specs=[
          pl.BlockSpec((k, d), lambda j: (0, 0)),
          pl.BlockSpec((k, k), lambda j: (0, 0)),
          pl.BlockSpec((block_n, d), lambda j: (j, 0)),
          pl.BlockSpec((1, block_n), lambda j: (0, j)),
      ],
      out_specs=out_specs,
      out_shape=out_shape,
      interpret=interpret,
  )(sel_feats, linv, cand_feats, okm)
  return best[0, 0], idx[0, 0]


# ---------------------------------------------------------------------------
# graph cut (top-1 over per-node gains)
# ---------------------------------------------------------------------------


def _graph_cut_kernel(w_ref, x_ref, ok_ref, best_ref, idx_ref, acc_ref):
  i = pl.program_id(0)  # row-tile index (outer)
  j = pl.program_id(1)  # column-tile index (inner -> accumulation dim)
  nc_b = pl.num_programs(1)

  w = w_ref[...].astype(jnp.float32)            # (BM, BN)
  x = x_ref[...].astype(jnp.float32)            # (1, BN)
  v = 1.0 - 2.0 * x

  part = jax.lax.dot_general(w, v, (((1,), (1,)), ((), ())),
                             precision=DOT_PRECISION,
                             preferred_element_type=jnp.float32)  # (BM, 1)

  @pl.when((i == 0) & (j == 0))
  def _init():
    _init_best(best_ref, idx_ref)

  @pl.when(j == 0)
  def _reset():
    acc_ref[...] = jnp.zeros_like(acc_ref)

  acc_ref[...] += part.T

  @pl.when(j == nc_b - 1)
  def _finalize():
    ok = ok_ref[...].astype(jnp.float32)        # (1, BM)
    masked = jnp.where(ok > 0, acc_ref[...], NEG)
    _top1_fold(masked, i * acc_ref.shape[1], best_ref, idx_ref)


def graph_cut_select_pallas(w, in_s, node_ok, *, block_m: int = 256,
                            block_n: int = 256, interpret: bool = False):
  """Fused top-1 node cut gain; (n, n), (n,), (n,) -> ((,) f32, (,) int32)."""
  n = w.shape[0]
  assert w.shape == (n, n), w.shape
  assert n % block_m == 0 and n % block_n == 0, (n, block_m, block_n)
  x = in_s.astype(jnp.float32)[None, :]
  okm = node_ok.astype(jnp.float32)[None, :]

  out_shape, out_specs = _scalar_outs()
  best, idx = pl.pallas_call(
      _graph_cut_kernel,
      grid=(n // block_m, n // block_n),
      in_specs=[
          pl.BlockSpec((block_m, block_n), lambda i, j: (i, j)),
          pl.BlockSpec((1, block_n), lambda i, j: (0, j)),
          pl.BlockSpec((1, block_m), lambda i, j: (0, i)),
      ],
      out_specs=out_specs,
      out_shape=out_shape,
      scratch_shapes=[pltpu.VMEM((1, block_m), jnp.float32)],
      interpret=interpret,
  )(w, x, okm)
  return best[0, 0], idx[0, 0]
