"""Plain reference of a GreeDi epoch under information gain,
f(S) = 1/2 logdet(I + sigma^-2 K_SS) with the RBF kernel
k(a, b) = exp(-||a - b||^2 / h^2), for the comparison that decides
``correct``.

It imports nothing of the program.  Round 1 is the textbook greedy in
pivoted-Cholesky form: each row keeps its conditional variance
cond_i = k(x_i, x_i) + sigma^2 - sum_t v_t,i^2, and each pick s adds the
column v = (k(x, x_s) - V^T V[:, s]) / sqrt(cond_s), so the gain of row i
is 1/2 log(cond_i / sigma^2).  Rows are held feature-major, (d, n), and V
as (k, n), so that it fits beside nothing at a chip's share.  Device work
is float32 at ``Precision.HIGHEST``; the walk along the program's picks and
the value are float64 on the host.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import reference

HIGHEST = jax.lax.Precision.HIGHEST


def _params(cfg: dict) -> tuple[float, float]:
  if cfg["kernel"] != "rbf":
    raise ValueError(f"the info-gain reference has the rbf kernel only, "
                     f"not {cfg['kernel']!r}")
  return float(cfg["kernel_kwargs"]["h"]), float(cfg["sigma"])


@functools.partial(jax.jit, static_argnums=(1,))
def _greedy(xt, k: int, h, sigma):
  """k greedy picks of information gain over the columns of ``xt`` (d, n);
  ties go to the lowest index.  Returns local indices."""
  n = xt.shape[1]
  s2 = sigma * sigma

  def step(t, c):
    v, cond, picked, out = c
    j = jnp.argmax(jnp.where(picked, -jnp.inf, cond))
    diff = xt - xt[:, j][:, None]
    kj = jnp.exp(-jnp.sum(diff * diff, axis=0) / (h * h))      # (n,)
    vj = jnp.matmul(v[:, j], v, precision=HIGHEST)             # (n,)
    col = (kj - vj) / jnp.sqrt(cond[j])
    return (v.at[t].set(col), cond - col * col, picked.at[j].set(True),
            out.at[t].set(j))

  init = (jnp.zeros((k, n), jnp.float32),
          jnp.full((n,), 1.0 + s2, jnp.float32), jnp.zeros((n,), bool),
          jnp.zeros((k,), jnp.int32))
  return jax.lax.fori_loop(0, k, step, init)[3]


def round1(x: np.ndarray, rng, m: int, kappa: int, h: float,
           sigma: float) -> np.ndarray:
  """GreeDi round 1: each machine's kappa greedy picks, as row indices of
  ``x``, over the partition ``reference.partition`` defines."""
  out = []
  for rows in reference.partition(rng, x.shape[0], m):
    xt = jnp.asarray(np.ascontiguousarray(x[rows].T))
    loc = np.asarray(_greedy(xt, kappa, jnp.float32(h), jnp.float32(sigma)))
    del xt
    out.append(rows[loc])
  return np.concatenate(out)


def _gram(a: np.ndarray, b: np.ndarray, h: float) -> np.ndarray:
  a = a.astype(np.float64)
  b = b.astype(np.float64)
  d2 = ((a[:, None, :] - b[None, :, :]) ** 2).sum(-1)
  return np.exp(-d2 / (h * h))


def walk(x: np.ndarray, cands: np.ndarray, picks: np.ndarray, h: float,
         sigma: float) -> tuple[np.ndarray, np.ndarray]:
  """Along ``picks`` (positions into ``cands``), each step's best gain over
  the unpicked candidates and the pick's own gain, in float64."""
  kc = _gram(x[cands], x[cands], h)
  s2 = sigma * sigma
  cond = np.diag(kc) + s2
  v = np.zeros((len(picks), len(cands)))
  picked = np.zeros(len(cands), bool)
  best, got = [], []
  for t, j in enumerate(picks):
    g = 0.5 * np.log(np.maximum(cond, 1e-300) / s2)
    best.append(np.max(np.where(picked, -np.inf, g)))
    got.append(g[j])
    col = (kc[:, j] - v[:t].T @ v[:t, j]) / np.sqrt(cond[j])
    v[t] = col
    cond = cond - col * col
    picked[j] = True
  return np.asarray(best), np.asarray(got)


def value(x: np.ndarray, sel: np.ndarray, h: float, sigma: float) -> float:
  """float64 1/2 logdet(I + sigma^-2 K_SS)."""
  k = _gram(x[sel], x[sel], h)
  sign, logdet = np.linalg.slogdet(np.eye(len(sel)) + k / (sigma * sigma))
  return 0.5 * logdet if sign > 0 else float("nan")


def epoch_numbers(x: np.ndarray, rng, m: int, cfg: dict, sel_gids,
                  sel_feats, value_reported: float) -> dict:
  """The numbers of one checked epoch, as ``reference.epoch_numbers``
  gives them for facility location:

  * ``feat_gap``: largest |returned feature - stored row| over the coreset;
  * ``greedy_gap``: largest shortfall, over the returned picks in order, of
    a pick's gain below the best gain of any unpicked merge candidate
    (round 1 as this reference runs it, plus the program's own picks),
    relative to that best;
  * ``value_rel``: |reported value - float64 f(coreset)| / f(coreset).
  """
  h, sigma = _params(cfg)
  sel = np.asarray(sel_gids, np.int64)
  feats = np.asarray(sel_feats, np.float32)
  if sel.size == 0 or (sel < 0).any() or len(set(sel.tolist())) != sel.size:
    return {"feat_gap": float("inf"), "greedy_gap": float("inf"),
            "value_rel": float("inf")}
  feat_gap = float(np.max(np.abs(feats - x[sel])))
  r1 = round1(x, rng, m, int(cfg["kappa"]), h, sigma)
  cands = np.unique(np.concatenate([r1, sel]))
  best, got = walk(x, cands, np.searchsorted(cands, sel), h, sigma)
  greedy_gap = float(np.max((best - got) / np.maximum(best, 1e-30)))
  ref = value(x, sel, h, sigma)
  return {"feat_gap": feat_gap, "greedy_gap": max(greedy_gap, 0.0),
          "value_rel": abs(float(value_reported) - ref) / max(ref, 1e-30)}
