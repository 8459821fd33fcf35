"""Submodular objectives as fixed-shape, jit/scan-friendly state machines.

Every objective exposes the same functional interface so the greedy loops in
``core/greedy.py`` and the distributed protocol in ``core/greedi.py`` can be
written once:

    state = obj.init(eval_feats)                    # summary of f restricted to
                                                    # the *evaluation* set
    gains = obj.gains(state, cand_feats)            # marginal gains f(S+v)-f(S)
                                                    # for every candidate, (nc,)
    state = obj.update(state, chosen_feat)          # S <- S + {v*}
    value = obj.value(state)                        # f(S) w.r.t. the eval set

The *evaluation set* is the data over which f is defined.  In GreeDi's global
mode it is (a shard of) the full ground set; in the decomposable/local mode of
Sec. 4.5 (Thm 10) it is the machine-local partition or the random subset U.
Candidates are represented purely by feature vectors, so the only data that
ever crosses machines is ``(kappa, d)`` blocks -- the paper's communication
model (poly(m, k), independent of n).

All state is padded to static shapes (``k_max``) so that the greedy loop is a
single ``lax.fori_loop`` and the whole selection jits/lowers cleanly under
``shard_map`` on a production mesh.

Gain-oracle backends: every objective carries a ``backend`` field
("pallas" | "ref" | "auto") resolved through kernels/dispatch.py, so the hot
marginal-gain loop routes to a fused Pallas kernel on TPU (or its pure-jnp
oracle elsewhere) without per-objective flags.  Similarity kernels outside
``dispatch.FUSED_SIMS`` fall back to the generic jnp path below.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp

from repro.kernels import dispatch

Array = jax.Array

# The masked-gain floor and the lowest-index masked argmax are defined ONCE,
# in kernels/ref.py (they are the ground-truth semantics every fused select
# kernel must replicate); re-exported here as the core layer's select path.
from repro.kernels.ref import DOT_PRECISION, NEG, masked_top1  # noqa: E402,F401


def _kernel_h(kernel_kwargs: tuple) -> float:
  """Bandwidth for the fused oracles (ignored by the linear kernel)."""
  return float(dict(kernel_kwargs).get("h", 0.75))

# ---------------------------------------------------------------------------
# Similarity kernels
# ---------------------------------------------------------------------------


def linear_kernel(x: Array, y: Array) -> Array:
  """Dot-product similarity. x: (n, d), y: (m, d) -> (n, m)."""
  return jnp.matmul(x, y.T, precision=DOT_PRECISION)


def rbf_kernel(x: Array, y: Array, h: float = 0.75) -> Array:
  """Squared-exponential kernel exp(-||x-y||^2 / h^2) (paper Sec. 3.4.1)."""
  x2 = jnp.sum(x * x, axis=-1, keepdims=True)
  y2 = jnp.sum(y * y, axis=-1, keepdims=True)
  d2 = jnp.maximum(x2 - 2.0 * jnp.matmul(x, y.T, precision=DOT_PRECISION)
                   + y2.T, 0.0)
  return jnp.exp(-d2 / (h * h))


def neg_sq_dist(x: Array, y: Array) -> Array:
  """-||x-y||^2: the (negated) k-means dissimilarity l = d^2 of Sec. 6.1."""
  x2 = jnp.sum(x * x, axis=-1, keepdims=True)
  y2 = jnp.sum(y * y, axis=-1, keepdims=True)
  return -(x2 - 2.0 * jnp.matmul(x, y.T, precision=DOT_PRECISION) + y2.T)


KERNELS: dict[str, Callable[..., Array]] = {
    "linear": linear_kernel,
    "rbf": rbf_kernel,
    "neg_sq_dist": neg_sq_dist,
}


# ---------------------------------------------------------------------------
# Facility location (exemplar-based clustering, Sec. 3.4.2) and max-coverage
# ---------------------------------------------------------------------------


class FLState(NamedTuple):
  """cov[i] = max_{s in S} sim(i, s), clipped below at the phantom baseline."""
  cov: Array          # (n_eval,) current best similarity per eval point
  eval_feats: Array   # (n_eval, d) -- carried so gains() needs no closure
  eval_mask: Array    # (n_eval,) 1.0 for live eval rows (padding support)
  value: Array        # scalar f(S)


@dataclasses.dataclass(frozen=True)
class FacilityLocation:
  """f(S) = mean_i [ max_{s in S} sim(e_i, s) - baseline ]_+ .

  With ``sim = -l`` (negated dissimilarity) and ``baseline = -l(e_i, e_0)``
  this is exactly the phantom-exemplar k-medoid surrogate of Eq. (6):
  f(S) = L({e0}) - L(S + {e0}).  With a 0/1 incidence "similarity" it is
  weighted max-coverage.  Monotone, nonnegative, decomposable (Sec 4.5).

  ``backend`` selects the gain oracle through kernels/dispatch.py: the fused
  Pallas kernel (kernels/facility_gain.py) streams eval/candidate tiles
  through VMEM instead of materializing sim(eval, cand) in HBM.  ``select``
  routes the whole greedy select step through the fused top-1 oracle
  (kernels/select_top1.py): the gains vector never leaves the kernel.
  """
  monotone = True  # marginal gains are >= 0 and diminishing (lazy-exact)

  kernel: str = "linear"
  kernel_kwargs: tuple = ()
  baseline: float = 0.0
  backend: str = "auto"

  def _sim(self, x: Array, y: Array) -> Array:
    return KERNELS[self.kernel](x, y, **dict(self.kernel_kwargs))

  def init(self, eval_feats: Array, eval_mask: Array | None = None) -> FLState:
    n = eval_feats.shape[0]
    if eval_mask is None:
      eval_mask = jnp.ones((n,), eval_feats.dtype)
    cov = jnp.full((n,), self.baseline, eval_feats.dtype)
    return FLState(cov, eval_feats, eval_mask, jnp.zeros((), eval_feats.dtype))

  def gains(self, state: FLState, cand_feats: Array) -> Array:
    denom = jnp.maximum(jnp.sum(state.eval_mask), 1.0)
    if self.kernel in dispatch.FUSED_SIMS:
      fn = dispatch.resolve("facility_gain", self.backend)
      return fn(state.eval_feats, cand_feats, state.cov, state.eval_mask,
                kernel=self.kernel, h=_kernel_h(self.kernel_kwargs)) / denom
    sim = self._sim(state.eval_feats, cand_feats)          # (ne, nc)
    inc = jnp.maximum(sim - state.cov[:, None], 0.0)
    return (state.eval_mask @ inc) / denom

  def select(self, state: FLState, cand_feats: Array,
             feasible: Array) -> tuple[Array, Array]:
    """Fused select step: (best normalized gain, int32 candidate index)."""
    if self.kernel in dispatch.FUSED_SIMS:
      denom = jnp.maximum(jnp.sum(state.eval_mask), 1.0)
      fn = dispatch.resolve_select("facility_gain", self.backend)
      best, idx = fn(state.eval_feats, cand_feats, state.cov, state.eval_mask,
                     feasible, kernel=self.kernel,
                     h=_kernel_h(self.kernel_kwargs))
      return best / denom, idx
    return masked_top1(self.gains(state, cand_feats), feasible)

  def update(self, state: FLState, feat: Array) -> FLState:
    sim = self._sim(state.eval_feats, feat[None, :])[:, 0]
    new_cov = jnp.maximum(state.cov, sim)
    denom = jnp.maximum(jnp.sum(state.eval_mask), 1.0)
    gain = jnp.sum((new_cov - state.cov) * state.eval_mask) / denom
    return FLState(new_cov, state.eval_feats, state.eval_mask,
                   state.value + gain)

  def value(self, state: FLState) -> Array:
    return state.value

  # Distributed evaluation helper: partial (unnormalized) statistics so that
  # a psum over shards reproduces the global objective exactly.
  def partial_stats(self, state: FLState, cand_feats: Array) -> tuple[Array, Array]:
    """Returns (sum-of-gains (nc,), live-count ()) -- psum-able."""
    if self.kernel in dispatch.FUSED_SIMS:
      fn = dispatch.resolve("facility_gain", self.backend)
      part = fn(state.eval_feats, cand_feats, state.cov, state.eval_mask,
                kernel=self.kernel, h=_kernel_h(self.kernel_kwargs))
      return part, jnp.sum(state.eval_mask)
    sim = self._sim(state.eval_feats, cand_feats)
    inc = jnp.maximum(sim - state.cov[:, None], 0.0)
    return state.eval_mask @ inc, jnp.sum(state.eval_mask)


class FLPreState(NamedTuple):
  cov: Array
  sim: Array          # (n_eval, n_cand) precomputed similarities
  eval_feats: Array
  eval_mask: Array
  value: Array


@dataclasses.dataclass(frozen=True)
class FacilityLocationPre:
  """Facility location with the (eval x cand) similarity matrix precomputed
  once per greedy run instead of once per *step*.

  Greedy recomputes every candidate's marginal gain each step; with the
  matrix cached, a step is one masked relu-reduce over S instead of a fresh
  (n_e x n_c x d) contraction -- a k-fold FLOP reduction for the whole run.
  Memory trade: O(n_e * n_c) resident, so this is the small-n benchmark path
  (and the TPU path keeps the streaming Pallas kernel instead).

  ``supports_lazy = False``: gains() answers for the *cached* candidate set
  regardless of the slice it is handed, so the tile-sliced rescoring of
  ``greedy(mode="lazy")`` cannot apply; greedy falls back to standard.
  """
  monotone = True
  supports_lazy = False

  kernel: str = "linear"
  kernel_kwargs: tuple = ()
  baseline: float = 0.0

  def _sim(self, x, y):
    return KERNELS[self.kernel](x, y, **dict(self.kernel_kwargs))

  def init(self, eval_feats: Array, eval_mask: Array | None = None,
           cand_feats: Array | None = None) -> FLPreState:
    n = eval_feats.shape[0]
    if eval_mask is None:
      eval_mask = jnp.ones((n,), eval_feats.dtype)
    if cand_feats is None:
      cand_feats = eval_feats
    sim = self._sim(eval_feats, cand_feats)
    cov = jnp.full((n,), self.baseline, eval_feats.dtype)
    return FLPreState(cov, sim, eval_feats, eval_mask,
                      jnp.zeros((), eval_feats.dtype))

  def gains(self, state: FLPreState, cand_feats: Array) -> Array:
    del cand_feats  # static candidate set: use the cached matrix
    denom = jnp.maximum(jnp.sum(state.eval_mask), 1.0)
    inc = jnp.maximum(state.sim - state.cov[:, None], 0.0)
    return (state.eval_mask @ inc) / denom

  def select(self, state: FLPreState, cand_feats: Array,
             feasible: Array) -> tuple[Array, Array]:
    return masked_top1(self.gains(state, cand_feats), feasible)

  def update(self, state: FLPreState, feat: Array) -> FLPreState:
    sim = self._sim(state.eval_feats, feat[None, :])[:, 0]
    new_cov = jnp.maximum(state.cov, sim)
    denom = jnp.maximum(jnp.sum(state.eval_mask), 1.0)
    gain = jnp.sum((new_cov - state.cov) * state.eval_mask) / denom
    return FLPreState(new_cov, state.sim, state.eval_feats, state.eval_mask,
                      state.value + gain)

  def value(self, state: FLPreState) -> Array:
    return state.value


# ---------------------------------------------------------------------------
# Information gain for GP active-set selection / IVM (Sec. 3.4.1)
# ---------------------------------------------------------------------------


class IGState(NamedTuple):
  sel_feats: Array   # (k_max, d) selected features, zero-padded
  count: Array       # () int32 number selected
  chol: Array        # (k_max, k_max) Cholesky of (K_SS + sigma^2 I), identity-padded
  value: Array       # scalar f(S) = 0.5 logdet(I + sigma^-2 K_SS)


class IGShardState(NamedTuple):
  """``IGState`` plus the shard's live evaluation-row count.

  Information gain is evaluation-set independent, so the sharded protocol's
  state needs nothing from the local partition except its live mass: the
  count makes ``partial_stats`` weight the (identical-on-every-shard) gains
  so the engine's psum-weighted mean reproduces them exactly (core/greedi.py
  ``_objective_engine``)."""
  inner: IGState
  n_live: Array      # () float32 live eval rows on this shard


def _masked_linv(chol: Array, count: Array) -> Array:
  """inv(L) with the columns of not-yet-selected rows zeroed.

  linv @ k(S, cand) then equals L^-1 applied to the live-row-masked cross
  kernel, which is what the fused info-gain oracle consumes (the identity
  padding of ``chol`` keeps the inverse well defined for any count).
  """
  k_max = chol.shape[0]
  linv = jax.scipy.linalg.solve_triangular(
      chol, jnp.eye(k_max, dtype=chol.dtype), lower=True)
  live = (jnp.arange(k_max) < count)[None, :]
  return jnp.where(live, linv, 0.0)


@dataclasses.dataclass(frozen=True)
class InformationGain:
  """f(S) = 0.5 logdet(I + sigma^-2 K_SS); monotone submodular (Krause+Guestrin).

  Incremental Cholesky of M = K_SS + sigma^2 I in a fixed (k_max, k_max)
  buffer.  Marginal gain of v:  0.5 log( (k_vv + s2 - ||L^-1 k_Sv||^2) / s2 ).

  ``backend`` routes the candidate sweep through the fused info-gain
  cross-term kernel (kernels/info_gain.py): the (k_max, nc) cross-kernel
  matrix and its back-substitution stay in VMEM; only (nc,) conditional
  variances are written out -- and through the fused select oracle, only the
  winning (cond, index) pair is (the log being strictly increasing, the
  cond-space argmax IS the gain argmax).
  """
  monotone = True  # 0.5 log(cond/s2) >= 0 for s2-noised GPs, diminishing

  k_max: int
  kernel: str = "rbf"
  kernel_kwargs: tuple = (("h", 0.75),)
  sigma: float = 1.0
  backend: str = "auto"

  def _k(self, x: Array, y: Array) -> Array:
    return KERNELS[self.kernel](x, y, **dict(self.kernel_kwargs))

  # f does not depend on an eval set, only on the selected set; buffers are
  # sized by the feature dim, so init takes ``d`` instead of eval features.
  def init_d(self, d: int, dtype=jnp.float32) -> IGState:
    return IGState(
        sel_feats=jnp.zeros((self.k_max, d), dtype),
        count=jnp.zeros((), jnp.int32),
        chol=jnp.eye(self.k_max, dtype=dtype),
        value=jnp.zeros((), dtype),
    )

  @staticmethod
  def _state(state) -> IGState:
    return state.inner if isinstance(state, IGShardState) else state

  def init(self, eval_feats: Array, eval_mask: Array | None = None
           ) -> IGShardState:
    """Sharded-protocol surface (core/greedi.py): f ignores the evaluation
    set, so only its live mass is recorded (see ``IGShardState``)."""
    ne, d = eval_feats.shape
    if eval_mask is None:
      n_live = jnp.asarray(float(ne), jnp.float32)
    else:
      n_live = jnp.sum(eval_mask.astype(jnp.float32))
    return IGShardState(self.init_d(d), n_live)

  def partial_stats(self, state, cand_feats: Array) -> tuple[Array, Array]:
    """(live-count-weighted gains, live count) for the psum-reduced merge.

    Every shard computes the SAME gains from the replicated candidate block
    (f is eval-set independent), so weighting by the shard's live count
    makes ``psum(part * w) / psum(n_live * w)`` reproduce them exactly for
    any liveness weighting ``w``."""
    n_live = (state.n_live if isinstance(state, IGShardState)
              else jnp.asarray(1.0, jnp.float32))
    return self.gains(state, cand_feats) * n_live, n_live

  def _cross(self, state: IGState, cand_feats: Array) -> Array:
    """L^-1 K_{S,cand} with rows past ``count`` zeroed: (k_max, nc)."""
    k_sc = self._k(state.sel_feats, cand_feats)            # (k_max, nc)
    row_live = (jnp.arange(self.k_max) < state.count)[:, None]
    k_sc = jnp.where(row_live, k_sc, 0.0)
    return jax.scipy.linalg.solve_triangular(state.chol, k_sc, lower=True)

  @staticmethod
  def _linv(state: IGState) -> Array:
    """The masked inverse factor the fused oracles consume: Cholesky
    state upkeep, so it is scoped with ``update``."""
    with jax.named_scope("info_gain.update"):
      return _masked_linv(state.chol, state.count)

  def gains(self, state, cand_feats: Array) -> Array:
    state = self._state(state)
    s2 = self.sigma ** 2
    if self.kernel in dispatch.FUSED_SIMS:
      fn = dispatch.resolve("info_gain_cond", self.backend)
      linv = self._linv(state)
      with jax.named_scope("info_gain.oracle"):
        cond = fn(state.sel_feats, linv, cand_feats, kernel=self.kernel,
                  h=_kernel_h(self.kernel_kwargs), ridge=s2)
        return 0.5 * jnp.log(cond / s2)
    with jax.named_scope("info_gain.oracle"):
      c = self._cross(state, cand_feats)                   # (k_max, nc)
      k_vv = jax.vmap(lambda x: self._k(x[None], x[None])[0, 0])(cand_feats)
      cond = jnp.maximum(k_vv + s2 - jnp.sum(c * c, axis=0), 1e-12)
      return 0.5 * jnp.log(cond / s2)

  def select(self, state, cand_feats: Array,
             feasible: Array) -> tuple[Array, Array]:
    state = self._state(state)
    s2 = self.sigma ** 2
    if self.kernel in dispatch.FUSED_SIMS:
      fn = dispatch.resolve_select("info_gain_cond", self.backend)
      linv = self._linv(state)
      with jax.named_scope("info_gain.oracle"):
        cond, idx = fn(state.sel_feats, linv, cand_feats, feasible,
                       kernel=self.kernel, h=_kernel_h(self.kernel_kwargs),
                       ridge=s2)
        return 0.5 * jnp.log(jnp.maximum(cond, 1e-12) / s2), idx
    return masked_top1(self.gains(state, cand_feats), feasible)

  def update(self, state, feat: Array):
    if isinstance(state, IGShardState):
      return IGShardState(self.update(state.inner, feat), state.n_live)
    with jax.named_scope("info_gain.update"):
      # a row stored in bfloat16 enters the float32 state exactly
      feat = feat.astype(state.sel_feats.dtype)
      s2 = self.sigma ** 2
      c = self._cross(state, feat[None, :])[:, 0]          # (k_max,)
      k_vv = self._k(feat[None], feat[None])[0, 0]
      diag = jnp.sqrt(jnp.maximum(k_vv + s2 - jnp.sum(c * c), 1e-12))
      i = state.count
      # Write row i of the Cholesky: [c_0..c_{i-1}, diag, 0...]; keep the
      # identity padding on the diagonal for rows > i.
      row = jnp.where(jnp.arange(self.k_max) < i, c, 0.0)
      row = row.at[i].set(diag)
      chol = jax.lax.dynamic_update_slice(state.chol, row[None, :], (i, 0))
      sel = jax.lax.dynamic_update_slice(state.sel_feats, feat[None, :],
                                         (i, 0))
      gain = 0.5 * jnp.log(jnp.maximum(diag * diag, 1e-12) / s2)
      return IGState(sel, i + 1, chol, state.value + gain)

  def value(self, state) -> Array:
    return self._state(state).value


# ---------------------------------------------------------------------------
# Log-det of a DPP kernel (Sec. 3.4.1; non-monotone in general)
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class LogDetDPP:
  """f(S) = logdet(K_S) via the same incremental Cholesky, no noise floor.

  Non-monotone once marginal conditional variances drop below 1.  Shares the
  fused info-gain cross-term oracle with InformationGain (ridge = jitter).
  """
  monotone = False  # gains go negative: greedy(mode="lazy") falls back

  k_max: int
  kernel: str = "rbf"
  kernel_kwargs: tuple = (("h", 0.75),)
  jitter: float = 1e-6
  backend: str = "auto"

  def _k(self, x, y):
    k = KERNELS[self.kernel](x, y, **dict(self.kernel_kwargs))
    return k

  def init_d(self, d: int, dtype=jnp.float32) -> IGState:
    return IGState(
        sel_feats=jnp.zeros((self.k_max, d), dtype),
        count=jnp.zeros((), jnp.int32),
        chol=jnp.eye(self.k_max, dtype=dtype),
        value=jnp.zeros((), dtype),
    )

  def _cross(self, state, cand_feats):
    k_sc = self._k(state.sel_feats, cand_feats)
    row_live = (jnp.arange(self.k_max) < state.count)[:, None]
    k_sc = jnp.where(row_live, k_sc, 0.0)
    return jax.scipy.linalg.solve_triangular(state.chol, k_sc, lower=True)

  def gains(self, state, cand_feats):
    if self.kernel in dispatch.FUSED_SIMS:
      fn = dispatch.resolve("info_gain_cond", self.backend)
      cond = fn(state.sel_feats, _masked_linv(state.chol, state.count),
                cand_feats, kernel=self.kernel,
                h=_kernel_h(self.kernel_kwargs), ridge=self.jitter)
    else:
      c = self._cross(state, cand_feats)
      k_vv = jax.vmap(lambda x: self._k(x[None], x[None])[0, 0])(cand_feats)
      cond = jnp.maximum(k_vv + self.jitter - jnp.sum(c * c, axis=0), 1e-12)
    return jnp.log(cond)

  def select(self, state, cand_feats, feasible):
    if self.kernel in dispatch.FUSED_SIMS:
      fn = dispatch.resolve_select("info_gain_cond", self.backend)
      cond, idx = fn(state.sel_feats, _masked_linv(state.chol, state.count),
                     cand_feats, feasible, kernel=self.kernel,
                     h=_kernel_h(self.kernel_kwargs), ridge=self.jitter)
      return jnp.log(jnp.maximum(cond, 1e-12)), idx
    return masked_top1(self.gains(state, cand_feats), feasible)

  def update(self, state, feat):
    c = self._cross(state, feat[None, :])[:, 0]
    k_vv = self._k(feat[None], feat[None])[0, 0]
    diag = jnp.sqrt(jnp.maximum(k_vv + self.jitter - jnp.sum(c * c), 1e-12))
    i = state.count
    row = jnp.where(jnp.arange(self.k_max) < i, c, 0.0)
    row = row.at[i].set(diag)
    chol = jax.lax.dynamic_update_slice(state.chol, row[None, :], (i, 0))
    sel = jax.lax.dynamic_update_slice(state.sel_feats, feat[None, :], (i, 0))
    gain = jnp.log(jnp.maximum(diag * diag, 1e-12))
    return IGState(sel, i + 1, chol, state.value + gain)

  def value(self, state):
    return state.value


class SatCovState(NamedTuple):
  cover: Array        # (n_eval,) accumulated similarity mass per eval point
  cap: Array          # (n_eval,) saturation level alpha * C_i(V), fixed at init
  eval_feats: Array
  eval_mask: Array
  value: Array


@dataclasses.dataclass(frozen=True)
class SaturatedCoverage:
  """Lin & Bilmes (2011) document-summarization objective:

      f(S) = sum_i min( C_i(S), alpha * C_i(V) ),   C_i(S) = sum_{j in S} s_ij

  Monotone submodular; the saturation alpha*C_i(V) rewards covering every
  document a little instead of a few documents a lot.  ``total`` (C_i(V))
  may be supplied at init so the objective stays decomposable/local
  (Sec. 4.5): each machine can use the saturation levels of its own
  partition; otherwise it is computed once from the eval set and carried in
  the state (it only depends on V, not on S).

  ``backend`` routes the gain sweep through the fused saturated-coverage
  kernel (kernels/coverage_gain.py) and the select step through its fused
  top-1 variant (kernels/select_top1.py).
  """
  monotone = True

  kernel: str = "linear"
  kernel_kwargs: tuple = ()
  alpha: float = 0.25
  backend: str = "auto"

  def _sim(self, x, y):
    return jnp.maximum(KERNELS[self.kernel](x, y, **dict(self.kernel_kwargs)),
                       0.0)

  def init(self, eval_feats: Array, eval_mask: Array | None = None,
           total: Array | None = None) -> SatCovState:
    n = eval_feats.shape[0]
    if eval_mask is None:
      eval_mask = jnp.ones((n,), eval_feats.dtype)
    if total is None:
      total = jnp.sum(self._sim(eval_feats, eval_feats)
                      * eval_mask[None, :].astype(jnp.float32), axis=1)
    cover = jnp.zeros((n,), jnp.float32)
    return SatCovState(cover, self.alpha * total.astype(jnp.float32),
                       eval_feats, eval_mask, jnp.zeros(()))

  def gains(self, state: SatCovState, cand_feats: Array) -> Array:
    denom = jnp.maximum(jnp.sum(state.eval_mask), 1.0)
    if self.kernel in dispatch.FUSED_SIMS:
      fn = dispatch.resolve("coverage_gain", self.backend)
      return fn(state.eval_feats, cand_feats, state.cover, state.cap,
                state.eval_mask, kernel=self.kernel,
                h=_kernel_h(self.kernel_kwargs)) / denom
    sim = self._sim(state.eval_feats, cand_feats)          # (ne, nc)
    new = jnp.minimum(state.cover[:, None] + sim, state.cap[:, None])
    inc = new - jnp.minimum(state.cover, state.cap)[:, None]
    return (state.eval_mask @ inc) / denom

  def select(self, state: SatCovState, cand_feats: Array,
             feasible: Array) -> tuple[Array, Array]:
    if self.kernel in dispatch.FUSED_SIMS:
      denom = jnp.maximum(jnp.sum(state.eval_mask), 1.0)
      fn = dispatch.resolve_select("coverage_gain", self.backend)
      best, idx = fn(state.eval_feats, cand_feats, state.cover, state.cap,
                     state.eval_mask, feasible, kernel=self.kernel,
                     h=_kernel_h(self.kernel_kwargs))
      return best / denom, idx
    return masked_top1(self.gains(state, cand_feats), feasible)

  def update(self, state: SatCovState, feat: Array) -> SatCovState:
    sim = self._sim(state.eval_feats, feat[None, :])[:, 0]
    cap = state.cap
    new_cover = state.cover + sim
    denom = jnp.maximum(jnp.sum(state.eval_mask), 1.0)
    gain = jnp.sum((jnp.minimum(new_cover, cap) -
                    jnp.minimum(state.cover, cap)) * state.eval_mask) / denom
    return SatCovState(new_cover, cap, state.eval_feats, state.eval_mask,
                       state.value + gain)

  def value(self, state: SatCovState) -> Array:
    return state.value

  # Distributed evaluation helper (same contract as FacilityLocation's): a
  # psum of the unnormalized partial gains over shards, weighted by live
  # counts, reproduces the global objective -- what the round-2 engine of
  # core/greedi.py consumes, making saturated coverage a first-class
  # protocol objective (and a service objective, see service/store.py).
  def partial_stats(self, state: SatCovState,
                    cand_feats: Array) -> tuple[Array, Array]:
    """Returns (sum-of-gains (nc,), live-count ()) -- psum-able."""
    if self.kernel in dispatch.FUSED_SIMS:
      fn = dispatch.resolve("coverage_gain", self.backend)
      part = fn(state.eval_feats, cand_feats, state.cover, state.cap,
                state.eval_mask, kernel=self.kernel,
                h=_kernel_h(self.kernel_kwargs))
      return part, jnp.sum(state.eval_mask)
    sim = self._sim(state.eval_feats, cand_feats)
    new = jnp.minimum(state.cover[:, None] + sim, state.cap[:, None])
    inc = new - jnp.minimum(state.cover, state.cap)[:, None]
    return state.eval_mask @ inc, jnp.sum(state.eval_mask)


# ---------------------------------------------------------------------------
# Graph cut (Sec. 6.3; non-monotone) -- index-based, explicit weight matrix
# ---------------------------------------------------------------------------


class CutState(NamedTuple):
  w: Array        # (n, n) symmetric weights over the universe
  in_s: Array     # (n,) {0,1} indicator of S restricted to the universe
  value: Array


@dataclasses.dataclass(frozen=True)
class GraphCut:
  """f(S) = sum_{i in S, j not in S} w_ij on an explicit (small) graph.

  Candidates are *universe indices* encoded as one-hot rows so the generic
  greedy loop (which traffics in "feature" rows) applies unchanged: the
  "feature" of node v is e_v, and gains/update recover the index by argmax.
  The paper evaluates this on a 1,899-node social graph, so a dense,
  replicated W is the intended regime.

  ``backend`` routes the per-node gain sweep deg - 2 Wx == W (1 - 2x) through
  the fused single-pass kernel (kernels/graph_cut_gain.py).

  ``assume_node_order=True`` additionally routes the select step through the
  fused node-space top-1 kernel (kernels/select_top1.py), mapping the winning
  node back to its (lowest) feasible candidate row.  It is opt-in because
  node-space tie-breaking only matches the candidate-space argmax when
  candidates are laid out in node order (the ``jnp.eye(n)`` convention): for
  permuted one-hot layouts and exactly-tied cut gains (realistic with
  integer/binary weights) the two orders pick different rows.  The default
  select path reduces in candidate space and is exact for any layout.
  """
  monotone = False  # cut gains go negative: greedy(mode="lazy") falls back

  backend: str = "auto"
  assume_node_order: bool = False

  def init_w(self, w: Array) -> CutState:
    n = w.shape[0]
    w = 0.5 * (w + w.T)
    w = w * (1.0 - jnp.eye(n, dtype=w.dtype))  # zero diagonal
    return CutState(w, jnp.zeros((n,), w.dtype), jnp.zeros((), w.dtype))

  def gains(self, state: CutState, cand_feats: Array) -> Array:
    # cand_feats: (nc, n) one-hot. gain(v) = deg_v - 2 * (W x)_v  for v not in S
    fn = dispatch.resolve("graph_cut_gain", self.backend)
    node_gain = fn(state.w, state.in_s)
    return cand_feats @ node_gain

  def select(self, state: CutState, cand_feats: Array,
             feasible: Array) -> tuple[Array, Array]:
    if self.assume_node_order:
      fn = dispatch.resolve_select("graph_cut_gain", self.backend)
      # project candidate feasibility onto the universe (one-hot rows)
      node_ok = (feasible.astype(jnp.float32) @ cand_feats) > 0
      best, node = fn(state.w, state.in_s, node_ok)
      # winning node -> its first feasible candidate row
      hit = feasible & (cand_feats[:, node] > 0)
      return best, jnp.argmax(hit).astype(jnp.int32)
    return masked_top1(self.gains(state, cand_feats), feasible)

  def update(self, state: CutState, feat: Array) -> CutState:
    gain = self.gains(state, feat[None, :])[0]
    in_s = jnp.maximum(state.in_s, feat)
    return CutState(state.w, in_s, state.value + gain)

  def value(self, state: CutState) -> Array:
    return state.value


# ---------------------------------------------------------------------------
# Modular (additive) objective -- sanity baseline: GreeDi is exactly optimal
# ---------------------------------------------------------------------------


class ModState(NamedTuple):
  weights: Array   # (d,) fixed linear weights
  value: Array


@dataclasses.dataclass(frozen=True)
class Modular:
  """f(S) = sum_{v in S} relu(w . x_v): modular => distributed == centralized."""
  monotone = True

  def init_w(self, weights: Array) -> ModState:
    return ModState(weights, jnp.zeros((), weights.dtype))

  def gains(self, state: ModState, cand_feats: Array) -> Array:
    return jnp.maximum(cand_feats @ state.weights, 0.0)

  def select(self, state: ModState, cand_feats: Array,
             feasible: Array) -> tuple[Array, Array]:
    return masked_top1(self.gains(state, cand_feats), feasible)

  def update(self, state: ModState, feat: Array) -> ModState:
    return ModState(state.weights,
                    state.value + jnp.maximum(feat @ state.weights, 0.0))

  def value(self, state: ModState) -> Array:
    return state.value


# ---------------------------------------------------------------------------
# Warm-start bound maintainers (the selection service's cross-epoch tables)
# ---------------------------------------------------------------------------
#
# The streaming selection service (src/repro/service/) carries, per document,
# an upper bound on its *empty-set* marginal gain across epochs, so round 1's
# lazy greedy can skip its step-0 full pass (``greedy(warm_bounds=...)``,
# docs/service.md).  What makes such a bound maintainable under appends and
# valid under ANY re-randomized partition is objective-specific; a
# ``BoundMaintainer`` packages exactly that math:
#
#   * ``append_update``  -- one fused (new_rows x block) pass producing (a)
#     the mass the new documents add to every older document's bound and (b)
#     the new documents' own bounds.  Pure local math: the *placement* (which
#     block columns live on which shard, the psum of the new documents' row
#     sums) belongs to the caller (service/store.CorpusStore runs this
#     sharded over the mesh via the ``bound_update`` dispatch oracle).
#   * ``epoch_bounds``   -- turn carried sum-form table entries into per-item
#     empty-set gain bounds under a shard evaluating ``n_live`` live rows.
#
# Maintainers are registered per objective *type*; each maintainer's own
# ``supports(objective)`` additionally gates on the instance configuration
# (e.g. similarity kernel, baseline sign for the sum-form maintainer) so an
# objective whose parameters break that maintainer's validity argument simply
# gets none -- and the service falls back to cold lazy selection, which is
# always exact.  The gates live WITH the maintainer, not in the registry:
# a future maintainer with different validity conditions brings its own.
#
# Adding a maintainer for a new objective (ROADMAP: info-gain / graph-cut):
# state the validity argument (every evaluation point must contribute
# non-negatively to the singleton gain, and the per-pair contribution must be
# partition-independent so the whole-corpus sum dominates any partition's),
# implement ``supports``/``append_update``/``epoch_bounds``, and register it
# here.  The service/store layers are objective-agnostic and pick it up
# untouched.


@dataclasses.dataclass(frozen=True)
class SumFormBoundMaintainer:
  """Sum-form singleton-gain bounds: ``table[i] = sum_e relu(sim(e, i))``.

  Validity (docs/service.md): for facility location with a non-negative
  baseline, doc i's empty-set gain under an evaluation set P is
  ``(1/|P|) sum_{e in P} relu(sim(e,i) - baseline) <= table[i] / |P|``
  because every evaluation point contributes non-negatively and the sum over
  any partition is a subset of the sum over the corpus.  Saturated coverage
  admits the same argument: its per-point contribution
  ``min(relu(sim), cap_e)`` is capped *below* relu(sim) regardless of the
  partition-dependent saturation level, so the identical relu-sum table is a
  valid bound there too -- one maintainer, two objectives.

  ``supports_sieve``: the same sum-form machinery powers the store's
  standing threshold sieves (select-on-append): the psum-reduced ``sums``
  of ``append_update`` ARE each new document's standing singleton gain, so
  sieve admission rides the bound pass at zero extra collectives.  A
  maintainer without sum-form singleton gains leaves the service epoch-only
  (``query`` falls back to the last epoch's selection).
  """
  oracle: str = "bound_update"
  supports_sieve: bool = True

  def supports(self, objective: Any) -> bool:
    """Whether this maintainer's validity argument holds for ``objective``:

      * the similarity kernel must be one the fused ``bound_update`` oracle
        implements (``dispatch.FUSED_SIMS``) -- e.g. ``neg_sq_dist``
        facility location runs cold;
      * a facility-location ``baseline < 0`` would make the true empty-set
        gain ``relu(sim - baseline)`` exceed ``relu(sim)``, breaking the
        sum-form bound -- run cold rather than select wrongly.
    """
    if getattr(objective, "kernel", None) not in dispatch.FUSED_SIMS:
      return False
    if float(getattr(objective, "baseline", 0.0)) < 0.0:
      return False
    return True

  def append_update(self, new_rows: Array, block_feats: Array,
                    new_valid: Array, block_valid: Array, *, kernel: str,
                    h: float, backend: str | None = None):
    """One fused (nb_new x nb_block) pass -> (add (nb_block,), sums (nb_new,)).

    ``add[j]`` is the evaluation mass the new documents contribute to block
    document j's bound; ``sums[i]`` is new document i's own bound restricted
    to this block's columns (the caller psums partial ``sums`` over shards).
    """
    fn = dispatch.resolve(self.oracle, backend or "auto")
    return fn(new_rows, block_feats, new_valid, block_valid, kernel=kernel,
              h=h)

  def epoch_bounds(self, table: Array, n_live: Array) -> Array:
    """Sum-form table entries -> mean-form empty-set bounds for a shard
    whose evaluation set has ``n_live`` live rows (broadcastable)."""
    return table / jnp.maximum(n_live, 1.0)


@dataclasses.dataclass(frozen=True)
class InfoGainPriorBoundMaintainer:
  """Data-independent prior bound for information gain (ROADMAP item).

  A document v's empty-set gain is EXACTLY its prior entropy reduction
  ``0.5 * log(1 + k(v,v) / sigma^2)`` -- independent of the evaluation set,
  the partition, and every other document.  So the "table" is trivial to
  maintain: appends set the new rows' own bounds and move nobody else's
  (``add == 0``), and ``epoch_bounds`` is the identity (the bound is
  per-item, not sum-form, so no live-count normalization applies).  Being
  the exact empty-set gain, the bound is tight: warm lazy epochs select
  bit-identically to cold ones (tested at the service level).

  ``sums_global``: unlike the sum-form maintainer, every shard computes each
  new row's COMPLETE bound from the replicated chunk rows -- the store must
  NOT psum the returned sums (service/store.py gates on this flag).

  ``supports_sieve`` is False: sieve admission scores need sum-form
  redundancy-discounted singleton gains, which this prior is not; the
  service stays epoch-only for queries.
  """
  sigma: float = 1.0
  supports_sieve: bool = False
  sums_global: bool = True

  def supports(self, objective: Any) -> bool:
    # k(v,v) must be computable from the row alone: 1 for rbf, ||v||^2 for
    # linear.  Other kernels run cold.
    return getattr(objective, "kernel", None) in ("rbf", "linear")

  def for_objective(self, objective: Any) -> "InfoGainPriorBoundMaintainer":
    """Bind the objective instance's noise level (``bound_maintainer_for``
    hook): the bound depends on sigma, which lives on the objective."""
    return dataclasses.replace(self, sigma=float(objective.sigma))

  def append_update(self, new_rows: Array, block_feats: Array,
                    new_valid: Array, block_valid: Array, *, kernel: str,
                    h: float, backend: str | None = None):
    del block_valid, h, backend  # prior bound: no cross terms, no oracle
    s2 = self.sigma ** 2
    if kernel == "rbf":
      k_vv = jnp.ones((new_rows.shape[0],), jnp.float32)
    else:  # linear
      k_vv = jnp.sum(new_rows.astype(jnp.float32) ** 2, axis=-1)
    sums = 0.5 * jnp.log1p(k_vv / s2) * new_valid.astype(jnp.float32)
    add = jnp.zeros((block_feats.shape[0],), jnp.float32)
    return add, sums

  def epoch_bounds(self, table: Array, n_live: Array) -> Array:
    del n_live  # per-item prior, partition-independent: already mean-form
    return table


_BOUND_MAINTAINERS: dict[type, Any] = {}


def register_bound_maintainer(obj_type: type, maintainer: Any) -> None:
  """Register (or replace) the warm-start bound maintainer for an objective
  type (see the section comment above for the contract)."""
  _BOUND_MAINTAINERS[obj_type] = maintainer


def bound_maintainer_for(objective: Any) -> Any | None:
  """The registered maintainer for ``objective``, or None when the objective
  (type, or configuration per the maintainer's own ``supports``) admits no
  maintained warm start.

  None means "run cold": the service still selects exactly, it just pays
  the lazy step-0 full pass each epoch.
  """
  maintainer = _BOUND_MAINTAINERS.get(type(objective))
  if maintainer is None:
    return None
  supports = getattr(maintainer, "supports", None)
  if supports is not None and not supports(objective):
    return None
  # maintainers whose math depends on instance parameters (e.g. the
  # info-gain prior needs sigma) bind them here
  bind = getattr(maintainer, "for_objective", None)
  if bind is not None:
    maintainer = bind(objective)
  return maintainer


register_bound_maintainer(FacilityLocation, SumFormBoundMaintainer())
register_bound_maintainer(SaturatedCoverage, SumFormBoundMaintainer())
register_bound_maintainer(InformationGain, InfoGainPriorBoundMaintainer())


# ---------------------------------------------------------------------------
# Brute force / exact evaluation helpers (tests & tiny benchmarks)
# ---------------------------------------------------------------------------


def set_value(objective: Any, state0: Any, feats: Array, idx: Array,
              mask: Array | None = None) -> Array:
  """f({feats[i] for i in idx}) by replaying updates; mask skips entries."""
  k = idx.shape[0]
  if mask is None:
    mask = jnp.ones((k,), bool)

  def body(state, im):
    i, live = im
    new = objective.update(state, feats[i])
    state = jax.tree.map(lambda a, b: jnp.where(live, a, b), new, state)
    return state, ()

  state, _ = jax.lax.scan(body, state0, (idx, mask))
  return objective.value(state)
