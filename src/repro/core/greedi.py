"""GreeDi: the paper's two-round distributed protocol (Alg. 2 / Alg. 3).

Three implementations share the greedy machinery from core/greedy.py and ONE
distributed-greedy core (``_dist_greedy_core``) for every merge round:

  * ``greedi_reference``   -- single-process, vmap-over-partitions. Used by the
    paper-figure benchmarks (Figs. 4, 6, 9, 10) and the theory tests; supports
    global and local (decomposable, Sec. 4.5) objective evaluation and all
    four naive baselines of Sec. 6.
  * ``greedi_sharded``     -- production path: shard_map over a mesh data axis.
    Round 1 is embarrassingly parallel per shard; the merge is one all_gather
    of (kappa, d) candidate blocks (bytes independent of n, the paper's
    communication model); round 2 is a *distributed* greedy whose per-step
    marginal gains are psum-reduced partial sums, so the full ground set is
    used for evaluation without ever moving it.
  * ``greedi_sharded_fast``-- same protocol specialized to facility location
    over any fused similarity kernel (dispatch.FUSED_SIMS): similarities are
    precomputed once per round through the ``pairwise`` oracle, so each greedy
    step is a masked relu-reduce instead of a fresh MXU contraction.
  * ``greedi_hierarchical``-- multi-pod: device -> pod (ICI all_gather) ->
    global (DCI all_gather) three-level merge, generalizing the paper's
    "multiple rounds" remark. Bounds compose (core/bounds.py).

Index tracking: every path threads *global ground-set indices* alongside
feature rows through round 1, the all_gather merge, and round 2, and returns
them as ``GreediResult.sel_gids`` -- the coreset as positions into the ground
set, which is what downstream consumers (data/selection.py, the training
loop) actually need.  The sharded paths accept an optional ``gids`` array so
a caller that pre-permuted the ground set (random partitioning) can map the
selection back to original document ids.

Select-step routing: round 1 of every path is the ``greedy`` loop and so
inherits the fused select oracles (one kernel pass per step, no (n,) gains
round-trip; ``mode="lazy"`` adds tile-bound lazy rescanning -- see
core/greedy.py and docs/perf.md).  The merge rounds run through
``_dist_greedy_core``, where the per-step argmax is the same ``masked_top1``
fold applied after the psum of partial gains.

Fault tolerance: ``straggler_keep`` masks partitions out of the merge AND out
of the evaluation weight: a dead machine contributes neither candidates nor
psum mass to round-2 gains, ``value_merged``, or ``stage1_values``, so the
protocol and Thm 4's proof degrade gracefully to the surviving machines (the
merged B simply misses some A_i, and f is evaluated over the alive data).
Straggler *detection* is a protocol output: pass per-machine heartbeat ages
(``liveness_age``/``liveness_deadline``) and the sharded paths derive the
mask themselves through a deadline-based liveness collective, returning it
as ``GreediResult.alive``; the Thm-10 U-subset holder is re-elected among
the alive shards instead of being pinned to machine 0.
Elasticity: the number of logical partitions is decoupled from physical
shards via core/partition.py.  Growing ground sets ride in pad-and-mask
blocks: rows with ``gids = -1`` are holes -- never candidates, never
evaluation mass -- so any n (including non-divisible) shards cleanly, and
a long-lived selection service (src/repro/service/) can append documents
between epochs without re-tracing.
"""
from __future__ import annotations

import inspect
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.greedy import (GreedyResult, _argsort_desc, _pad_to, greedy,
                               with_backend)
from repro.core.objectives import NEG, _kernel_h, masked_top1
from repro.core.partition import random_partition
from repro.kernels import autotune, dispatch
from repro.util import fori as _ufori
from repro.util import shard_map as _shard_map

Array = jax.Array


# ---------------------------------------------------------------------------
# shared helpers
# ---------------------------------------------------------------------------


def set_value_feats(objective, state0, sel_feats: Array, valid: Array):
  """Replay updates for an explicit selected-feature block -> final state."""

  def body(state, fv):
    f, v = fv
    new = objective.update(state, f)
    state = jax.tree.map(lambda a, b: jnp.where(v, a, b), new, state)
    return state, ()

  state, _ = jax.lax.scan(body, state0, (sel_feats, valid))
  return state


def _init_arity(init_for) -> int:
  """Positional arity of a user ``init_for`` (3 when it takes the candidate
  block for a precompute path, else 2).

  Signature inspection instead of try/except TypeError: the latter silently
  swallowed TypeErrors raised *inside* the user function and re-ran it with
  fewer arguments.  A ``*args`` callable is taken at its word and receives
  the candidate block (wrap a 2-arg init in an explicit 2-arg signature if
  that is not wanted) -- the old probe-and-retry could only tell the two
  apart by swallowing exceptions.
  """
  try:
    sig = inspect.signature(init_for)
  except (TypeError, ValueError):  # builtins without inspectable signatures
    return 2
  n = 0
  for p in sig.parameters.values():
    if p.kind in (p.POSITIONAL_ONLY, p.POSITIONAL_OR_KEYWORD):
      n += 1
    elif p.kind is p.VAR_POSITIONAL:
      return 3
  return n


def _call_init(init_for, eval_feats: Array, eval_mask: Array,
               cand_feats: Array):
  if _init_arity(init_for) >= 3:
    return init_for(eval_feats, eval_mask, cand_feats)
  return init_for(eval_feats, eval_mask)


def _take_k(x: Array, k: int, fill) -> Array:
  """First k rows of a machine's kappa-row block, padded when kappa < k.

  The A_max alt arm must match round 2's (k_final, ...) shapes: for
  kappa > k_final the greedy prefix IS A_max^gc[k_final]; for kappa < k_final
  the machine simply proposed fewer items, so the tail is explicit padding
  (0 rows / False / -1 ids) rather than an opaque broadcast error.
  """
  if x.shape[0] >= k:
    return x[:k]
  pad = k - x.shape[0]
  return jnp.concatenate(
      [x, jnp.full((pad, *x.shape[1:]), fill, x.dtype)], axis=0)


def greedi_keys(rng: Array) -> tuple[Array, Array, Array, Array]:
  """The protocol's independent keys: (partition, round-1, round-2, U-subset).

  Exposed so callers that run partitioning *outside* the protocol (the
  sharded index-selection path in data/selection.py) derive the exact same
  partition as ``greedi_reference`` under the same seed.
  """
  keys = jax.random.split(rng, 4)
  return keys[0], keys[1], keys[2], keys[3]


class GreediResult(NamedTuple):
  sel_feats: Array      # (k_final, d) the returned solution A_gd
  sel_valid: Array      # (k_final,) bool
  value: Array          # f(A_gd) under the final evaluation objective
  value_merged: Array   # f(A_B^gc)   (round-2 solution)
  value_best_single: Array  # f(A_max^gc) (best single-machine solution)
  stage1_values: Array  # (m,) f(A_i) under final evaluation
  sel_gids: Array       # (k_final,) int32 global ground-set ids, -1 = no-op
  alive: Array          # (m,) bool: machines the protocol actually used
                        # (straggler_keep AND the liveness collective) --
                        # a protocol *output*, see docs/service.md
  r1_rescans: Array     # (m,) int32 device-fed diagnostic: tiles rescanned
                        # by each machine's round-1 lazy greedy (0 unless
                        # mode="lazy"); see GreedyResult.rescans / repro.obs


def _replicated_result_specs():
  return jax.tree.map(
      lambda _: P(), GreediResult(*([0] * len(GreediResult._fields))))


# ---------------------------------------------------------------------------
# THE distributed-greedy core (round 2 / merge levels of every sharded path)
# ---------------------------------------------------------------------------


class _Engine(NamedTuple):
  """What a sharded variant plugs into the shared distributed-greedy loop.

  The candidate block rides inside the engine (``cands``/``cmask``/``cgids``)
  so the gains basis and the returned features/gids cannot desynchronize.
  Gain/value quantities are *local, unnormalized* contributions; the core
  psum-reduces them over the given mesh axes, weighted by the shard's
  evaluation weight.
  """
  state0: Any
  # state -> (nc,) local partial marginal gains for every candidate
  partial_gains: Callable[[Any], Array]
  # (state, chosen column j, chosen feature row, take?) -> new state
  apply_update: Callable[[Any, Array, Array, Array], Any]
  # state -> () local partial objective value
  partial_value: Callable[[Any], Array]
  cands: Array   # (nc, d) replicated candidate block
  cmask: Array   # (nc,) bool selectable
  cgids: Array   # (nc,) int32 global ids of the candidates


def _objective_engine(objective, local_feats: Array, cands: Array,
                      cmask: Array, cgids: Array,
                      eval_mask: Array | None = None) -> _Engine:
  """Engine over a generic objective exposing partial_stats/update/value.

  ``eval_mask`` marks the shard's *live* evaluation rows (pad-and-mask holes
  carry 0): the state binds the masked eval set and the psum-able partial
  value is weighted by the live count, so hole rows move nothing.
  """
  if eval_mask is None:
    eval_mask = jnp.ones((local_feats.shape[0],), local_feats.dtype)
  # count in f32: a low-precision feature dtype (bf16 masks) would round
  # live counts past 256 and skew the psum weights against the f32 denoms
  n_live = jnp.sum(eval_mask.astype(jnp.float32))

  def partial_gains(state):
    return objective.partial_stats(state, cands)[0]

  def apply_update(state, j, feat, take):
    del j
    new = objective.update(state, feat)
    return jax.tree.map(lambda a, b: jnp.where(take, a, b), new, state)

  def partial_value(state):
    return objective.value(state) * n_live

  return _Engine(objective.init(local_feats, eval_mask), partial_gains,
                 apply_update, partial_value, cands, cmask, cgids)


def _dist_greedy_core(engine: _Engine, steps: int, axes, weight: Array,
                      denom: Array, feat_dtype):
  """Distributed greedy over the engine's replicated candidate block.

  Per step: psum the weighted local partial gains over ``axes``, then fold
  gains, feasibility mask, and argmax into ONE top-1 reduction
  (``masked_top1`` -- same tie-breaking as the fused select oracles of the
  local rounds; the psum itself is irreducible, since every shard holds only
  a *partial* sum, so the merged (nc,) vector -- nc = m*kappa, tiny by the
  paper's communication model -- is materialized once and reduced once).
  ``weight`` is the shard's evaluation weight (0 for dead/straggling machines
  and for shards outside the Thm-10 U-subset); ``denom`` the psum of weighted
  eval counts.  Returns (sel_feats (steps, d), sel_valid (steps,),
  sel_gids (steps,) int32, value ()) -- all replicated.
  """
  cands, cmask, cgids = engine.cands, engine.cmask, engine.cgids
  nc, d = cands.shape

  def body(t, c):
    state, selmask, outf, outv, outg = c
    gains = jax.lax.psum(engine.partial_gains(state) * weight, axes) / denom
    feasible = cmask & (~selmask)
    _, chosen = masked_top1(gains, feasible)
    take = jnp.any(feasible)
    feat = cands[chosen]
    state = engine.apply_update(state, chosen, feat, take)
    selmask = selmask.at[chosen].set(jnp.where(take, True, selmask[chosen]))
    outf = outf.at[t].set(jnp.where(take, feat, 0.0))
    outv = outv.at[t].set(take)
    outg = outg.at[t].set(jnp.where(take, cgids[chosen], -1))
    return (state, selmask, outf, outv, outg)

  c0 = (engine.state0, jnp.zeros((nc,), bool),
        jnp.zeros((steps, d), feat_dtype), jnp.zeros((steps,), bool),
        jnp.full((steps,), -1, jnp.int32))
  state, _, f, v, g = _ufori(0, steps, body, c0)
  val = jax.lax.psum(engine.partial_value(state) * weight, axes) / denom
  return f, v, g, val


# ---------------------------------------------------------------------------
# reference implementation (single process, vmap over partitions)
# ---------------------------------------------------------------------------


def greedi_reference(rng: Array, feats: Array, *, m: int, kappa: int,
                     k_final: int, objective, init_for,
                     local_eval: bool = False,
                     final_subset: int | None = None,
                     mode: str = "standard", sample_frac: float | None = None,
                     stop_nonpositive: bool = False,
                     backend: str | None = None) -> GreediResult:
  """Algorithm 2 (GreeDi) on one host.

  Args:
    init_for: callable (eval_feats, eval_mask) -> objective state. For
      set-only objectives (information gain, DPP) it may ignore its inputs.
      A 3-argument callable additionally receives the candidate block (the
      precompute path of e.g. FacilityLocationPre).
    local_eval: round-1 machines evaluate f on their local partition only
      (the decomposable mode of Sec. 4.5 / Fig. 4b).
    final_subset: if given, round 2 and the final comparison evaluate f on a
      random subset U of this size (Thm 10); else on the full ground set.
    backend: optional gain-oracle backend override for both rounds
      ("pallas" | "ref" | "auto", see kernels/dispatch.py).
  """
  objective = with_backend(objective, backend)
  n, d = feats.shape
  # round 2 gets its own key: r_sel is consumed by the round-1 split, and
  # reusing it would correlate stochastic/random-mode sampling across rounds
  r_part, r_sel, r_r2, r_u = greedi_keys(rng)
  parts, pmask, perm = random_partition(r_part, feats, m)

  # ---- round 1: independent greedy per machine --------------------------
  def run_one(part, mask_row, key):
    if local_eval:
      st0 = _call_init(init_for, part, mask_row.astype(part.dtype), part)
    else:
      st0 = _call_init(init_for, feats, jnp.ones((n,), part.dtype), part)
    return greedy(objective, st0, part, kappa, cand_mask=mask_row,
                  rng=key, mode=mode, sample_frac=sample_frac,
                  stop_nonpositive=stop_nonpositive)

  keys = jax.random.split(r_sel, m)
  r1 = jax.vmap(run_one)(parts, pmask, keys)      # feats: (m, kappa, d)
  valid1 = r1.idx >= 0

  # global doc ids of every round-1 candidate: perm[machine, local_idx]
  gid1 = jnp.take_along_axis(perm, jnp.maximum(r1.idx, 0), axis=1)
  gid1 = jnp.where(valid1, gid1, -1).astype(jnp.int32)      # (m, kappa)

  # ---- final evaluation objective ---------------------------------------
  if final_subset is not None:
    u_idx = jax.random.choice(r_u, n, (final_subset,), replace=False)
    eval_feats = feats[u_idx]
    eval_mask = jnp.ones((final_subset,), feats.dtype)
  else:
    eval_feats = feats
    eval_mask = jnp.ones((n,), feats.dtype)
  st_final0 = _call_init(init_for, eval_feats, eval_mask,
                         r1.feats.reshape(m * kappa, d))

  # ---- A_max: best single-machine solution under final evaluation -------
  stage1_vals = jax.vmap(
      lambda sf, v: objective.value(set_value_feats(objective, st_final0, sf, v))
  )(r1.feats, valid1)
  best_i = jnp.argmax(stage1_vals)

  # ---- round 2: greedy over the merged candidates ------------------------
  B = r1.feats.reshape(m * kappa, d)
  bmask = valid1.reshape(m * kappa)
  bgids = gid1.reshape(m * kappa)
  r2 = greedy(objective, st_final0, B, k_final, cand_mask=bmask,
              rng=r_r2, mode=mode, sample_frac=sample_frac,
              stop_nonpositive=stop_nonpositive)
  r2_gids = jnp.where(r2.idx >= 0, bgids[jnp.maximum(r2.idx, 0)], -1)
  v_merged = objective.value(r2.state)
  v_best_single = stage1_vals[best_i]

  use_merged = v_merged >= v_best_single
  # A_max may have kappa > k_final items; truncate to the first k_final (they
  # are the greedy prefix, which is exactly A_max^gc[k_final]).
  alt_feats = _take_k(r1.feats[best_i], k_final, 0.0)
  alt_valid = _take_k(valid1[best_i], k_final, False)
  alt_gids = _take_k(gid1[best_i], k_final, -1)
  sel_feats = jnp.where(use_merged, r2.feats, alt_feats)
  sel_valid = jnp.where(use_merged, r2.idx >= 0, alt_valid)
  sel_gids = jnp.where(use_merged, r2_gids, alt_gids)
  value = jnp.maximum(v_merged, v_best_single)
  return GreediResult(sel_feats, sel_valid, value, v_merged, v_best_single,
                      stage1_vals, sel_gids, jnp.ones((m,), bool),
                      r1.rescans.astype(jnp.int32))


def centralized_greedy(feats: Array, k: int, *, objective, init_for,
                       rng: Array | None = None, mode: str = "standard",
                       sample_frac: float | None = None,
                       stop_nonpositive: bool = False,
                       backend: str | None = None) -> tuple[GreedyResult, Array]:
  objective = with_backend(objective, backend)
  n = feats.shape[0]
  st0 = _call_init(init_for, feats, jnp.ones((n,), feats.dtype), feats)
  r = greedy(objective, st0, feats, k, rng=rng, mode=mode,
             sample_frac=sample_frac, stop_nonpositive=stop_nonpositive)
  return r, objective.value(r.state)


# ---------------------------------------------------------------------------
# naive baselines of Sec. 6
# ---------------------------------------------------------------------------


def baselines(rng: Array, feats: Array, *, m: int, k: int, objective,
              init_for, stop_nonpositive: bool = False,
              backend: str | None = None) -> dict[str, Array]:
  """random/random, random/greedy, greedy/merge, greedy/max (paper Sec. 6)."""
  objective = with_backend(objective, backend)
  n, d = feats.shape
  r_part, r_a, r_b = jax.random.split(rng, 3)
  parts, pmask, _ = random_partition(r_part, feats, m)
  npp = parts.shape[1]
  st_full0 = init_for(feats, jnp.ones((n,), feats.dtype))
  out: dict[str, Array] = {}

  # -- random/random: k random out of (m x k random) == k random overall
  idx = jax.random.choice(r_a, n, (k,), replace=False)
  st = set_value_feats(objective, st_full0, feats[idx], jnp.ones((k,), bool))
  out["random/random"] = objective.value(st)

  # -- random/greedy: k random per machine, then greedy over the mk pool
  def pick_rand(key, mask_row):
    pr = jax.random.uniform(key, (npp,)) - jnp.where(mask_row, 0.0, 1e9)
    return jax.lax.top_k(pr, min(k, npp))[1]
  keys = jax.random.split(r_b, m)
  rand_idx = jax.vmap(pick_rand)(keys, pmask)               # (m, k)
  pool = jnp.take_along_axis(parts, rand_idx[..., None], axis=1)
  pool_mask = jnp.take_along_axis(pmask, rand_idx, axis=1)
  r = greedy(objective, st_full0, pool.reshape(-1, d), k,
             cand_mask=pool_mask.reshape(-1),
             stop_nonpositive=stop_nonpositive)
  out["random/greedy"] = objective.value(r.state)

  # -- greedy/merge: ceil(k/m) greedy per machine, merged as-is
  kpm = -(-k // m)
  def run_small(part, mask_row):
    st0 = init_for(feats, jnp.ones((n,), feats.dtype))
    return greedy(objective, st0, part, kpm, cand_mask=mask_row,
                  stop_nonpositive=stop_nonpositive)
  r1 = jax.vmap(run_small)(parts, pmask)
  merged = r1.feats.reshape(m * kpm, d)[:k]
  mvalid = (r1.idx >= 0).reshape(m * kpm)[:k]
  st = set_value_feats(objective, st_full0, merged, mvalid)
  out["greedy/merge"] = objective.value(st)

  # -- greedy/max: greedy k per machine, report the best single solution
  def run_k(part, mask_row):
    st0 = init_for(feats, jnp.ones((n,), feats.dtype))
    return greedy(objective, st0, part, k, cand_mask=mask_row,
                  stop_nonpositive=stop_nonpositive)
  rk = jax.vmap(run_k)(parts, pmask)
  vals = jax.vmap(
      lambda sf, v: objective.value(set_value_feats(objective, st_full0, sf, v))
  )(rk.feats, rk.idx >= 0)
  out["greedy/max"] = jnp.max(vals)
  return out


# ---------------------------------------------------------------------------
# production path: shard_map over the mesh
# ---------------------------------------------------------------------------


def _combined_index(axis_names: tuple[str, ...], mesh) -> Array:
  """Row-major shard index over ``axis_names`` (static sizes from the mesh,
  so the index folds to constants per shard)."""
  idx = jax.lax.axis_index(axis_names[0])
  for a in axis_names[1:]:
    idx = idx * mesh.shape[a] + jax.lax.axis_index(a)
  return idx


def _psum(x, axis_names):
  return jax.lax.psum(x, axis_names)


def _mesh_size(mesh, axis_names) -> int:
  m = 1
  for a in axis_names:
    m *= mesh.shape[a]
  return m


def _prep_gids(gids: Array | None, n: int) -> Array:
  if gids is None:
    return jnp.arange(n, dtype=jnp.int32)
  assert gids.shape == (n,), (gids.shape, n)
  return gids.astype(jnp.int32)


def _prep_liveness(liveness_age, liveness_deadline, m: int):
  """Normalize the liveness inputs to ((m,) f32 ages, () f32 deadline).

  ``liveness_age=None`` means "no detection": ages 0 against an infinite
  deadline, so every machine passes the collective and ``straggler_keep``
  alone decides (the pre-detection behavior, bit-for-bit).
  """
  if liveness_age is None:
    age = jnp.zeros((m,), jnp.float32)
    deadline = jnp.asarray(jnp.inf, jnp.float32)
  else:
    age = jnp.asarray(liveness_age, jnp.float32)
    assert age.shape == (m,), (age.shape, m)
    deadline = jnp.asarray(
        jnp.inf if liveness_deadline is None else liveness_deadline,
        jnp.float32)
  return age, deadline


def _liveness_collective(my_bit: Array, me: Array, m: int, axis_names):
  """The deadline-based liveness collective: every shard contributes one
  heartbeat bit (did my last heartbeat land within the deadline?) and the
  gathered (m,) vector IS the straggler mask -- a protocol output, not an
  operator-supplied input.  Implemented as a psum of one-hot rows so the
  result is indexed by the row-major combined shard index regardless of how
  many mesh axes the protocol spans (an all_gather with explicit placement).
  """
  row = jnp.zeros((m,), jnp.float32).at[me].set(my_bit.astype(jnp.float32))
  return jax.lax.psum(row, axis_names) > 0.0


# ---------------------------------------------------------------------------
# accumulation-tree merge (merge="tree"): level structure helpers
# ---------------------------------------------------------------------------


def _norm_branch(m: int, tree_branch: int | None) -> int:
  """Normalize the tree branching factor: default 8 (a comfortable gathered
  block), clamped to the mesh size (b >= m is the flat-equivalent one-level
  tree, the degenerate case the bit-exactness contract is stated over)."""
  b = 8 if tree_branch is None else int(tree_branch)
  if b < 2 and m > 1:
    raise ValueError(f"tree_branch must be >= 2, got {b}")
  return max(min(b, m), 1)


def _tree_factors(m: int, b: int) -> tuple[int, ...]:
  """Inner-to-outer child counts of the accumulation tree over ``m`` shards:
  ``b`` children at every level with one final (possibly smaller) outer
  factor, so the product is exactly m and the depth is ceil(log_b m)."""
  factors = []
  rem = m
  while rem > b:
    if rem % b:
      raise ValueError(
          f"mesh size {m} does not factor into tree_branch={b} levels "
          f"(need m = b^t * c with c <= b); pick a branch factor whose "
          "powers divide the mesh, or use merge='flat'")
    factors.append(b)
    rem //= b
  factors.append(rem)
  return tuple(factors)


def _tree_mesh(mesh, factors: tuple[int, ...]):
  """Re-view the caller's devices as one mesh axis per tree level
  (outer -> inner, row-major): the flat combined shard index -- and with it
  the row layout, liveness indexing, and gid threading -- is unchanged, and
  each merge level becomes an all_gather over ONE named axis with psums over
  the axis suffix (its subtree), i.e. ``greedi_hierarchical``'s pod step
  run once per level.  Returns (mesh, axis_names)."""
  shape = tuple(reversed(factors))
  names = tuple(f"tree{i}" for i in range(len(shape)))
  devs = mesh.devices.reshape(shape)
  return jax.sharding.Mesh(
      devs, names,
      axis_types=(jax.sharding.AxisType.Auto,) * len(names)), names


def _resolve_merge_mesh(mesh, axis_names, m: int, merge: str,
                        tree_branch: int | None):
  """Validate the merge knob and, for merge="tree", swap the caller's mesh
  for its accumulation-tree re-view (same devices, same order)."""
  if merge == "flat":
    return mesh, axis_names
  if merge != "tree":
    raise ValueError(f"merge must be 'flat' or 'tree', got {merge!r}")
  if mesh.devices.size != m:
    raise ValueError(
        "merge='tree' re-views the mesh devices as tree levels and needs "
        f"the merge axes {axis_names} to cover the whole mesh "
        f"(axes span {m} of {mesh.devices.size} devices)")
  return _tree_mesh(mesh, _tree_factors(m, _norm_branch(m, tree_branch)))


def merge_peak_rows(m: int, kappa: int, *, merge: str = "flat",
                    tree_branch: int | None = None) -> int:
  """Peak per-shard merged-candidate rows under the chosen merge strategy:
  the largest gathered block any single merge level materializes.  Flat
  gathers all m kappa-blocks at once (m * kappa rows); the tree gathers at
  most the widest level's child count (<= tree_branch) worth of blocks.
  This is the static counterpart of the ``repro_merge_peak_*`` live metrics
  the service feeds from its epoch outputs (docs/service.md)."""
  if merge == "flat":
    return m * kappa
  if merge != "tree":
    raise ValueError(f"merge must be 'flat' or 'tree', got {merge!r}")
  return max(_tree_factors(m, _norm_branch(m, tree_branch))) * kappa


def _fast_r1_lazy(s11: Array, local_valid: Array, kappa: int, d: int):
  """Round 1 of ``greedi_sharded_fast`` with tile-bound lazy pruning over
  the CACHED similarity matrix (``mode="lazy"``).

  Mirrors core/greedy._greedy_lazy on bound-sorted masked *columns* of
  ``s11`` instead of feature rows: ``stale[j]`` holds column j's last
  computed coverage gain sum_i relu(s11[i, j] - cov[i]) -- a valid upper
  bound by submodularity -- and each step rescans bound-sorted column tiles
  (one (nl, tile) gather + relu-reduce each) until the next head bound
  cannot beat the running best.  Rescanning while ``head >= best`` plus the
  lowest-column-index tie preference reproduces the standard full-column
  scan's ``masked_top1`` selection bit-for-bit, so the kappa-fold FLOP cut
  of the cached similarities composes with lazy pruning.  Returns
  (sel_idx (kappa,) int32, took (kappa,) bool, rescans () int32).
  """
  n_local = s11.shape[0]
  if kappa == 0:
    return (jnp.zeros((0,), jnp.int32), jnp.zeros((0,), bool), jnp.int32(0))
  tile = autotune.lazy_tile(n_local, d)
  tile = max(min(tile, autotune.floor_pow2(n_local, cap=tile)), 1)
  npad = -(-n_local // tile) * tile
  nt = npad // tile
  int_max = jnp.int32(jnp.iinfo(jnp.int32).max)
  valid_pad = _pad_to(local_valid, npad, False)

  # step 0: one full column pass both selects and seeds the bounds, at the
  # exact expression the standard path evaluates (bit-parity of the sums)
  cov0 = jnp.zeros((n_local,), jnp.float32)
  g0 = jnp.sum(jnp.maximum(s11 - cov0[:, None], 0.0), axis=0)
  _, j0 = masked_top1(g0, local_valid)
  take0 = jnp.any(local_valid)
  cov = jnp.where(take0, jnp.maximum(cov0, s11[:, j0]), cov0)
  selmask = jnp.zeros((npad,), bool).at[j0].set(take0)
  carry0 = (cov, selmask, _pad_to(g0, npad, NEG),
            jnp.zeros((kappa,), jnp.int32).at[0].set(j0),
            jnp.zeros((kappa,), bool).at[0].set(take0), jnp.int32(0))

  def body(t, c):
    cov, selmask, stale, sel_idx, took, resc = c
    feasible = (~selmask) & valid_pad
    pri = jnp.where(feasible, stale, NEG)
    # bound ties keep column order; NOT jnp.argsort -- see _argsort_desc for
    # the multi-device CPU sort hazard this sidesteps
    sorted_pri, order = _argsort_desc(pri)

    def cond(s):
      p, best, _, _ = s
      head = sorted_pri[jnp.minimum(p * tile, npad - 1)]
      return (p < nt) & (head >= best)

    def rescan_tile(s):
      p, best, bidx, st = s
      ids = jax.lax.dynamic_slice(order, (p * tile,), (tile,))
      idc = jnp.minimum(ids, n_local - 1)   # pad slots: clipped, infeasible
      g = jnp.sum(jnp.maximum(s11[:, idc] - cov[:, None], 0.0), axis=0)
      st = st.at[ids].set(g)
      gm = jnp.where(feasible[ids], g, NEG)
      tb = jnp.max(gm)
      gi = jnp.min(jnp.where(gm == tb, ids, int_max))  # lowest column index
      better = (tb > best) | ((tb == best) & (gi < bidx))
      return (p + 1, jnp.where(better, tb, best),
              jnp.where(better, gi, bidx), st)

    init = (jnp.int32(0), jnp.float32(-jnp.inf), int_max, stale)
    p_fin, _, bidx, stale = jax.lax.while_loop(cond, rescan_tile, init)
    take = jnp.any(feasible)
    j = jnp.where(take, jnp.clip(bidx, 0, n_local - 1), 0)
    cov = jnp.where(take, jnp.maximum(cov, s11[:, j]), cov)
    selmask = selmask.at[j].set(jnp.where(take, True, selmask[j]))
    return (cov, selmask, stale, sel_idx.at[t].set(j),
            took.at[t].set(take), resc + p_fin)

  _, _, _, sel_idx, took, rescans = _ufori(1, kappa, body, carry0)
  return sel_idx, took, rescans


def greedi_sharded(feats: Array, *, mesh, kappa: int, k_final: int,
                   objective, axis_names: tuple[str, ...] = ("data",),
                   straggler_keep: Array | None = None,
                   u_subset_eval: bool = False,
                   rng: Array | None = None,
                   backend: str | None = None,
                   gids: Array | None = None,
                   mode: str = "standard",
                   warm_bounds: Array | None = None,
                   liveness_age: Array | None = None,
                   liveness_deadline: float | None = None,
                   merge: str = "flat",
                   tree_branch: int | None = None):
  """GreeDi over a device mesh; round-2 gains are psum-reduced partial sums.

  Args:
    feats: (n, d) ground set, n divisible by the product of axis sizes (any
      original size can be padded up with hole rows carrying ``gids = -1``,
      which are masked out of candidates AND evaluation everywhere).
    objective: must expose init/gains/update/value and partial_stats (the
      facility-location family -- the paper's decomposable flagship).
    mode: greedy mode for the *round-1* shard-local selection ("standard"
      routes through the fused select oracles; "lazy" adds tile-bound lazy
      rescanning -- both bit-identical selections, see core/greedy.py).
      Round 2 always runs the distributed psum core, whose per-step argmax
      is the same fused top-1 reduction over the merged candidate block.
    straggler_keep: optional (m,) bool; False partitions are dropped at the
      merge (failed/straggling machines) AND excluded from the evaluation
      weight, so dead machines' data moves neither round-2 gains nor the
      reported values.  The Thm 4 bound then holds with
      m_alive = sum(straggler_keep) over the alive ground set.
    u_subset_eval: Thm 10 mode -- evaluate round 2 on ONE machine's
      partition (a uniformly random ~n/m subset) instead of psum over the
      full set.  The U-holder is the first *alive* shard (re-elected via
      the liveness/straggler mask), so a dead machine 0 no longer collapses
      the evaluation weight to zero.
    backend: optional gain-oracle backend override (kernels/dispatch.py);
      applies to round-1 gains and the psum-reduced round-2 partial stats.
    gids: optional (n,) global ids of the rows of ``feats`` (defaults to
      arange); the selection is reported as ``sel_gids`` through these.
      Negative ids mark *holes* (pad-and-mask rows of a growing ground set,
      see docs/service.md): never candidates, never evaluation mass.
    warm_bounds: optional (n,) upper bounds on each row's empty-set gain
      under its shard's local evaluation, threaded to the round-1 lazy
      greedy (mode="lazy" only) so step 0 skips its full pass -- the
      epoch warm start of the selection service, whose per-objective
      validity lives in the ``BoundMaintainer`` registry of
      core/objectives.py (docs/service.md).
    liveness_age: optional (m,) seconds since each machine's last
      heartbeat.  When given, the protocol itself derives the straggler
      mask: each shard contributes the bit ``age <= liveness_deadline`` to
      a liveness collective and the gathered mask (ANDed with any explicit
      ``straggler_keep``) is used everywhere and returned as
      ``GreediResult.alive``.
    liveness_deadline: deadline in the same units as ``liveness_age``.
    merge: "flat" (one all_gather of all m kappa-blocks, merged once) or
      "tree" (accumulation tree: r = ceil(log_b m) levels of b-child
      sub-mesh merges, peak per-shard gathered block (b*kappa, d) instead
      of (m*kappa, d) -- see docs/greedi.md).  ``tree_branch = m`` (or any
      b >= m) is a one-level tree and reduces to the flat merge
      bit-exactly; ``stage1_values`` is then per-machine as usual, else
      per *root child* (one entry per top-level subtree).
    tree_branch: children per tree node (merge="tree" only; default 8).
      ``m`` must factor as b^t * c with c <= b.

  Returns a GreediResult (replicated on every shard).
  """
  objective = with_backend(objective, backend)
  m = _mesh_size(mesh, axis_names)
  mesh, axis_names = _resolve_merge_mesh(mesh, axis_names, m, merge,
                                         tree_branch)
  n, d = feats.shape
  assert n % m == 0, (n, m)
  if straggler_keep is None:
    straggler_keep = jnp.ones((m,), bool)
  if rng is None:
    rng = jax.random.PRNGKey(0)
  gids = _prep_gids(gids, n)
  age, deadline = _prep_liveness(liveness_age, liveness_deadline, m)
  use_warm = warm_bounds is not None
  wb = (jnp.zeros((n,), jnp.float32) if warm_bounds is None
        else jnp.asarray(warm_bounds, jnp.float32))
  assert wb.shape == (n,), (wb.shape, n)

  in_specs = (P(axis_names), P(axis_names), P(axis_names), P(), P(), P(), P())
  out_specs = _replicated_result_specs()

  def fn(local_feats, local_gids, local_wb, keep, key, age, deadline):
    me = _combined_index(axis_names, mesh)
    # ---- liveness: the straggler mask is a protocol output ---------------
    my_bit = age[me] <= deadline
    keep = keep & _liveness_collective(my_bit, me, m, axis_names)
    my_keep = keep[me]
    local_valid = local_gids >= 0                   # pad-and-mask holes
    evalw = local_valid.astype(local_feats.dtype)
    n_live = jnp.sum(evalw.astype(jnp.float32))

    # ---- round 1: local greedy on the shard's live partition rows --------
    st0 = objective.init(local_feats, evalw)
    r1 = greedy(objective, st0, local_feats, kappa, cand_mask=local_valid,
                rng=key, mode=mode,
                warm_bounds=local_wb if use_warm else None)
    sel = r1.feats                                   # (kappa, d)
    valid = (r1.idx >= 0) & my_keep
    gsel = jnp.where(r1.idx >= 0, local_gids[jnp.maximum(r1.idx, 0)], -1)

    if merge == "flat":
      # ---- merge: one all_gather of the candidate blocks -----------------
      B = jax.lax.all_gather(sel, axis_names)          # (m, kappa, d)
      Bvalid = jax.lax.all_gather(valid, axis_names)   # (m, kappa)
      Bgids = jax.lax.all_gather(gsel, axis_names)     # (m, kappa)
      Bflat = B.reshape(m * kappa, d)
      Bmask = Bvalid.reshape(m * kappa)
      Bgflat = Bgids.reshape(m * kappa)

      # evaluation weight of this shard: full-set eval or the Thm-10 U
      # subset held by the first ALIVE shard, and zero for dead machines --
      # their data carries no evaluation mass
      u_holder = jnp.argmax(keep)                      # first alive shard
      w = jnp.where(u_subset_eval, (me == u_holder).astype(jnp.float32), 1.0)
      w = w * my_keep.astype(jnp.float32)
      denom = _psum(n_live * w, axis_names)
      denom = jnp.maximum(denom, 1.0)

      # ---- A_max: value of each machine's solution under final eval ------
      def value_of(sel_i, valid_i):
        st = set_value_feats(objective, objective.init(local_feats, evalw),
                             sel_i, valid_i)
        # local mean * local live count -> psum-able sum
        return objective.value(st) * n_live * w
      part_vals = jax.vmap(value_of)(B, Bvalid)        # (m,)
      stage1_vals = _psum(part_vals, axis_names) / denom
      stage1_vals = jnp.where(keep, stage1_vals, -jnp.inf)
      best_i = jnp.argmax(stage1_vals)

      # ---- round 2: distributed greedy over B ----------------------------
      engine = _objective_engine(objective, local_feats, Bflat, Bmask,
                                 Bgflat, eval_mask=evalw)
      merged_feats, merged_valid, merged_gids, v_merged = _dist_greedy_core(
          engine, k_final, axis_names, w, denom, feats.dtype)
    else:
      # ---- merge: accumulation tree, innermost axis up -------------------
      # Level l all_gathers the subtree representatives' blocks over ONE
      # mesh axis (c_l children) and reruns the same distributed greedy
      # with psums over the axis SUFFIX -- exactly this subtree's shards.
      # psum/all_gather return identical bits on every participant, so the
      # whole subtree carries identical representatives upward without a
      # re-broadcast; with b = m the loop is a single level over the full
      # mesh -- the flat merge's own op sequence, hence bit-identical.
      Q, Qv, Qg = sel, valid, gsel
      r_lv = len(axis_names)
      for li in range(r_lv):
        root = li == r_lv - 1
        ax = axis_names[r_lv - 1 - li]
        sub_axes = axis_names[r_lv - 1 - li:]
        c_l = mesh.shape[ax]
        s_l = _mesh_size(mesh, sub_axes)
        kprev = Q.shape[0]
        B = jax.lax.all_gather(Q, ax)                  # (c_l, kprev, d)
        Bvalid = jax.lax.all_gather(Qv, ax)
        Bgids = jax.lax.all_gather(Qg, ax)
        Bflat = B.reshape(c_l * kprev, d)
        Bmask = Bvalid.reshape(c_l * kprev)
        Bgflat = Bgids.reshape(c_l * kprev)
        # Thm-10 holder *per subtree*: the first alive shard among the s_l
        # consecutive combined indices this level's psums span, re-elected
        # from the liveness mask at every level -- a dead interior node's
        # subtree keeps merging under its next alive member's U subset
        base = (me // s_l) * s_l
        sub_keep = jax.lax.dynamic_slice(keep, (base,), (s_l,))
        u_holder = base + jnp.argmax(sub_keep)
        w = jnp.where(u_subset_eval, (me == u_holder).astype(jnp.float32),
                      1.0)
        w = w * my_keep.astype(jnp.float32)
        denom = jnp.maximum(_psum(n_live * w, sub_axes), 1.0)
        if root:
          # A_max over the root's children (== per-machine when b = m);
          # a child is alive iff ANY shard of its subtree is
          def value_of(sel_i, valid_i):
            st = set_value_feats(objective,
                                 objective.init(local_feats, evalw),
                                 sel_i, valid_i)
            return objective.value(st) * n_live * w
          part_vals = jax.vmap(value_of)(B, Bvalid)    # (c_l,)
          stage1_vals = _psum(part_vals, sub_axes) / denom
          child_keep = jnp.any(keep.reshape(c_l, s_l // c_l), axis=1)
          stage1_vals = jnp.where(child_keep, stage1_vals, -jnp.inf)
          best_i = jnp.argmax(stage1_vals)
        engine = _objective_engine(objective, local_feats, Bflat, Bmask,
                                   Bgflat, eval_mask=evalw)
        Q, Qv, Qg, v_merged = _dist_greedy_core(
            engine, k_final if root else kappa, sub_axes, w, denom,
            feats.dtype)
      merged_feats, merged_valid, merged_gids = Q, Qv, Qg

    # ---- pick the better of A_B and A_max --------------------------------
    v_best_single = stage1_vals[best_i]
    use_merged = v_merged >= v_best_single
    sel_feats = jnp.where(use_merged, merged_feats,
                          _take_k(B[best_i], k_final, 0.0))
    sel_valid = jnp.where(use_merged, merged_valid,
                          _take_k(Bvalid[best_i], k_final, False))
    sel_gids = jnp.where(use_merged, merged_gids,
                         _take_k(Bgids[best_i], k_final, -1))
    value = jnp.maximum(v_merged, v_best_single)
    # per-machine lazy rescan counts: scalar -> (m,) replicated, ordered by
    # the same combined shard index as every other per-machine output
    rescans = jax.lax.all_gather(r1.rescans.astype(jnp.int32), axis_names)
    return GreediResult(sel_feats, sel_valid, value, v_merged, v_best_single,
                        stage1_vals, sel_gids, keep, rescans.reshape(m))

  shmapped = _shard_map(fn, mesh=mesh, in_specs=in_specs,
                        out_specs=out_specs)
  return shmapped(feats, gids, wb, straggler_keep, rng, age, deadline)


def _check_cache_fits(n_local: int, mesh) -> None:
  """Refuse a cached-similarity epoch whose (n/m, n/m) f32 block cannot fit
  the device, instead of running it out of memory.  Backends that report no
  memory limit (the CPU) are not checked."""
  stats = mesh.devices.flat[0].memory_stats() or {}
  limit = stats.get("bytes_limit")
  need = n_local * n_local * 4
  if limit and need > limit:
    raise ValueError(
        f"greedi_sharded_fast caches a ({n_local}, {n_local}) f32 similarity "
        f"block per shard ({need / 2**30:.1f} GiB), more than the device's "
        f"{limit / 2**30:.1f} GiB; use more shards or greedi_sharded, which "
        "streams the similarities")


def greedi_sharded_fast(feats: Array, *, mesh, kappa: int, k_final: int,
                        axis_names: tuple[str, ...] = ("data",),
                        kernel: str = "linear",
                        kernel_kwargs: tuple = (),
                        straggler_keep: Array | None = None,
                        rng: Array | None = None,
                        backend: str | None = None,
                        gids: Array | None = None,
                        liveness_age: Array | None = None,
                        liveness_deadline: float | None = None,
                        mode: str = "standard",
                        merge: str = "flat",
                        tree_branch: int | None = None):
  """Perf-optimized sharded GreeDi for the facility-location objective over
  any fused similarity kernel (the production data-selection path).

  vs ``greedi_sharded`` (perf hillclimb #3, see EXPERIMENTS.md Sec Perf):
    * round 1 precomputes the local (n/m x n/m) similarity matrix ONCE; each
      greedy step is then a masked relu-reduce instead of a fresh
      (n/m x n/m x d) contraction  -> kappa-fold FLOP cut;
    * round 2 precomputes S2 = sim(local eval, merged B) once and feeds the
      cached columns to the shared distributed-greedy core;
    * A_max needs NO replay: f(A_i) = mean_e max over machine i's columns
      of S2 (a reshape + max + psum).

  Similarities route through the ``pairwise`` oracle in kernels/dispatch.py,
  so ``kernel`` may be any of ``dispatch.FUSED_SIMS`` (linear / rbf with
  bandwidth ``kernel_kwargs=(("h", ...),)``) and ``backend`` picks the fused
  Pallas kernel vs the XLA reference, exactly like the generic objectives.
  Equivalent to ``greedi_sharded`` with
  ``FacilityLocation(kernel=kernel, kernel_kwargs=kernel_kwargs)`` (baseline
  0): the marginal-gain math is identical, so the returned solution matches
  exactly (tests assert this), including under ``straggler_keep``, hole rows
  (``gids = -1``: excluded from candidates, evaluation mass, and A_max), and
  the liveness collective (``liveness_age``/``liveness_deadline``, same
  contract as ``greedi_sharded``).

  ``mode="lazy"`` routes round 1 through ``_fast_r1_lazy``: tile-bound lazy
  pruning over the cached similarity columns, bit-identical selections to
  ``mode="standard"`` (the kappa-fold FLOP cut composes with lazy pruning).
  ``merge``/``tree_branch`` select the flat vs accumulation-tree merge with
  the same contract as ``greedi_sharded`` (b = m reduces to flat
  bit-exactly).
  """
  if mode not in ("standard", "lazy"):
    raise ValueError(f"mode must be 'standard' or 'lazy', got {mode!r}")
  if kernel not in dispatch.FUSED_SIMS:
    raise ValueError(
        f"greedi_sharded_fast caches similarities through the 'pairwise' "
        f"oracle and supports kernels {dispatch.FUSED_SIMS}, got {kernel!r}; "
        "use greedi_sharded with a generic objective instead")
  sim = dispatch.resolve("pairwise", backend or "auto")
  h = _kernel_h(kernel_kwargs)  # same default resolution as the objectives
  m = _mesh_size(mesh, axis_names)
  mesh, axis_names = _resolve_merge_mesh(mesh, axis_names, m, merge,
                                         tree_branch)
  n, d = feats.shape
  assert n % m == 0, (n, m)
  _check_cache_fits(n // m, mesh)
  if straggler_keep is None:
    straggler_keep = jnp.ones((m,), bool)
  if rng is None:
    rng = jax.random.PRNGKey(0)
  gids = _prep_gids(gids, n)
  age, deadline = _prep_liveness(liveness_age, liveness_deadline, m)

  out_specs = _replicated_result_specs()

  def fn(local_feats, local_gids, keep, key, age, deadline):
    del key  # round 1 is deterministic standard greedy
    me = _combined_index(axis_names, mesh)
    n_local = local_feats.shape[0]
    my_bit = age[me] <= deadline
    keep = keep & _liveness_collective(my_bit, me, m, axis_names)
    my_keep = keep[me]
    local_valid = local_gids >= 0                   # pad-and-mask holes
    vrow = local_valid.astype(jnp.float32)
    n_live = jnp.sum(vrow)
    w = my_keep.astype(jnp.float32)

    # ---- round 1: local greedy over the precomputed local sim matrix ----
    # hole EVAL rows are zeroed out of the similarity block so they carry no
    # coverage mass (an rbf kernel gives a zero feature row sim > 0)
    s11 = sim(local_feats, local_feats, kernel=kernel, h=h)  # (nl, nl) f32
    s11 = s11 * vrow[:, None]

    if mode == "lazy":
      sel_idx, took, r1_resc = _fast_r1_lazy(s11, local_valid, kappa, d)
    else:
      def r1_body(t, c):
        cov, selmask, sel_idx, took = c
        gains = jnp.sum(jnp.maximum(s11 - cov[:, None], 0.0), axis=0)
        feasible = (~selmask) & local_valid
        _, j = masked_top1(gains, feasible)
        take = jnp.any(feasible)
        cov = jnp.where(take, jnp.maximum(cov, s11[:, j]), cov)
        selmask = selmask.at[j].set(jnp.where(take, True, selmask[j]))
        return (cov, selmask, sel_idx.at[t].set(j), took.at[t].set(take))

      cov0 = jnp.zeros((n_local,), jnp.float32)
      _, _, sel_idx, took = _ufori(
          0, kappa, r1_body,
          (cov0, jnp.zeros((n_local,), bool),
           jnp.zeros((kappa,), jnp.int32), jnp.zeros((kappa,), bool)))
      r1_resc = jnp.int32(0)
    sel = local_feats[sel_idx]                                # (kappa, d)
    # steps past the live local rows find nothing feasible; invalidate them
    # exactly like the generic path's greedy (idx = -1 once nothing is
    # feasible), so kappa > live rows cannot leak duplicate candidates/gids
    # (or hole rows) into the merge
    gsel = jnp.where(took, local_gids[sel_idx], -1)
    valid = my_keep & took

    if merge == "flat":
      # ---- merge + ONE cross-similarity matmul ----------------------------
      denom = _psum(n_live * w, axis_names)
      denom = jnp.maximum(denom, 1.0)
      B = jax.lax.all_gather(sel, axis_names)                 # (m, kappa, d)
      Bvalid = jax.lax.all_gather(valid, axis_names)          # (m, kappa)
      Bgids = jax.lax.all_gather(gsel, axis_names)            # (m, kappa)
      Bflat = B.reshape(m * kappa, d)
      Bmask = Bvalid.reshape(m * kappa)
      Bgflat = Bgids.reshape(m * kappa)
      s2 = sim(local_feats, Bflat, kernel=kernel, h=h)        # (nl, m*kappa)
      s2 = s2 * vrow[:, None]

      # ---- A_max: no replay needed ----------------------------------------
      # invalid candidate columns (padding past a machine's live rows, or
      # rows of a dead machine) carry no coverage in f(A_i)
      s2_pos = jnp.maximum(s2, 0.0) * Bmask.astype(jnp.float32)[None, :]
      per_machine = jnp.max(s2_pos.reshape(n_local, m, kappa), axis=2)
      stage1_vals = _psum(jnp.sum(per_machine, axis=0) * w,
                          axis_names) / denom
      stage1_vals = jnp.where(keep, stage1_vals, -jnp.inf)
      best_i = jnp.argmax(stage1_vals)

      # ---- round 2: the shared core over cached similarity columns --------
      # s2's columns are Bflat's rows by construction, so the cached-gain
      # closures and the candidate block stay in lockstep inside the engine
      engine = _Engine(
          state0=jnp.zeros((n_local,), jnp.float32),
          partial_gains=lambda cov: jnp.sum(
              jnp.maximum(s2 - cov[:, None], 0.0), axis=0),
          apply_update=lambda cov, j, feat, take: jnp.where(
              take, jnp.maximum(cov, s2[:, j]), cov),
          partial_value=jnp.sum,
          cands=Bflat, cmask=Bmask, cgids=Bgflat,
      )
      merged_feats, merged_valid, merged_gids, v_merged = _dist_greedy_core(
          engine, k_final, axis_names, w, denom, feats.dtype)
    else:
      # ---- merge: accumulation tree over cached similarities --------------
      # same level structure as greedi_sharded's tree branch; each level
      # caches ONE (nl, c_l*kprev) cross-similarity block -- the per-level
      # peak replaces the flat (nl, m*kappa) block
      Q, Qv, Qg = sel, valid, gsel
      r_lv = len(axis_names)
      for li in range(r_lv):
        root = li == r_lv - 1
        ax = axis_names[r_lv - 1 - li]
        sub_axes = axis_names[r_lv - 1 - li:]
        c_l = mesh.shape[ax]
        kprev = Q.shape[0]
        B = jax.lax.all_gather(Q, ax)                  # (c_l, kprev, d)
        Bvalid = jax.lax.all_gather(Qv, ax)
        Bgids = jax.lax.all_gather(Qg, ax)
        Bflat = B.reshape(c_l * kprev, d)
        Bmask = Bvalid.reshape(c_l * kprev)
        Bgflat = Bgids.reshape(c_l * kprev)
        denom = jnp.maximum(_psum(n_live * w, sub_axes), 1.0)
        s2 = sim(local_feats, Bflat, kernel=kernel, h=h)
        s2 = s2 * vrow[:, None]
        if root:
          s2_pos = jnp.maximum(s2, 0.0) * Bmask.astype(jnp.float32)[None, :]
          per_child = jnp.max(s2_pos.reshape(n_local, c_l, kprev), axis=2)
          stage1_vals = _psum(jnp.sum(per_child, axis=0) * w,
                              sub_axes) / denom
          s_l = _mesh_size(mesh, sub_axes)
          child_keep = jnp.any(keep.reshape(c_l, s_l // c_l), axis=1)
          stage1_vals = jnp.where(child_keep, stage1_vals, -jnp.inf)
          best_i = jnp.argmax(stage1_vals)
        engine = _Engine(
            state0=jnp.zeros((n_local,), jnp.float32),
            partial_gains=lambda cov, s2=s2: jnp.sum(
                jnp.maximum(s2 - cov[:, None], 0.0), axis=0),
            apply_update=lambda cov, j, feat, take, s2=s2: jnp.where(
                take, jnp.maximum(cov, s2[:, j]), cov),
            partial_value=jnp.sum,
            cands=Bflat, cmask=Bmask, cgids=Bgflat,
        )
        Q, Qv, Qg, v_merged = _dist_greedy_core(
            engine, k_final if root else kappa, sub_axes, w, denom,
            feats.dtype)
      merged_feats, merged_valid, merged_gids = Q, Qv, Qg

    v_best_single = stage1_vals[best_i]
    use_merged = v_merged >= v_best_single
    sel_feats = jnp.where(use_merged, merged_feats,
                          _take_k(B[best_i], k_final, 0.0))
    sel_valid = jnp.where(use_merged, merged_valid,
                          _take_k(Bvalid[best_i], k_final, False))
    sel_gids = jnp.where(use_merged, merged_gids,
                         _take_k(Bgids[best_i], k_final, -1))
    value = jnp.maximum(v_merged, v_best_single)
    if mode == "lazy":
      rescans = jax.lax.all_gather(r1_resc, axis_names).reshape(m)
    else:
      # standard round 1 scans every column every step -- no lazy rescans
      rescans = jnp.zeros((m,), jnp.int32)
    return GreediResult(sel_feats, sel_valid, value, v_merged, v_best_single,
                        stage1_vals, sel_gids, keep, rescans)

  shmapped = _shard_map(
      fn, mesh=mesh,
      in_specs=(P(axis_names), P(axis_names), P(), P(), P(), P()),
      out_specs=out_specs)
  return shmapped(feats, gids, straggler_keep, rng, age, deadline)


def greedi_hierarchical(feats: Array, *, mesh, kappa: int, k_final: int,
                        objective,
                        pod_axis: str = "pod", data_axis: str = "data",
                        straggler_keep: Array | None = None,
                        rng: Array | None = None,
                        backend: str | None = None,
                        gids: Array | None = None,
                        mode: str = "standard"):
  """Three-level GreeDi for multi-pod meshes: device -> pod -> global.

  Level 1: each device greedily selects kappa from its local partition.
  Level 2: all_gather over the *intra-pod* data axis (ICI); a distributed
           greedy (gains psum-reduced over the pod) picks kappa per pod.
  Level 3: all_gather the per-pod solutions over the pod axis (DCI, i.e. the
           expensive inter-pod links carry only (pods * kappa * d) bytes);
           a distributed greedy over the full mesh picks k_final.

  Both merge levels run through the same ``_dist_greedy_core`` as the flat
  sharded path, with per-level psum axes and denominators.  Global indices
  thread through every level, and ``straggler_keep`` ((mp*md,) bool, indexed
  pod-major like the shard layout) masks dead devices out of the candidates
  AND the evaluation weight at every level, so a dead device's data never
  moves gains or values.

  The returned value also tracks the best pod-level solution so the final
  answer is max over levels, mirroring Alg. 2's max(A_max, A_B).
  """
  objective = with_backend(objective, backend)
  mp, md = mesh.shape[pod_axis], mesh.shape[data_axis]
  m = mp * md
  n, d = feats.shape
  assert n % m == 0, (n, m)
  if straggler_keep is None:
    straggler_keep = jnp.ones((m,), bool)
  if rng is None:
    rng = jax.random.PRNGKey(0)
  gids = _prep_gids(gids, n)
  both = (pod_axis, data_axis)

  def fn(local_feats, local_gids, keep, key):
    me = _combined_index(both, mesh)
    my_keep = keep[me]
    local_valid = local_gids >= 0                   # pad-and-mask holes
    evalw = local_valid.astype(local_feats.dtype)
    n_live = jnp.sum(evalw.astype(jnp.float32))
    w = my_keep.astype(jnp.float32)
    nl_w = n_live * w
    denom_pod = jnp.maximum(_psum(nl_w, (data_axis,)), 1.0)
    denom_all = jnp.maximum(_psum(nl_w, both), 1.0)

    # ---- level 1: device-local greedy ------------------------------------
    st0 = objective.init(local_feats, evalw)
    r1 = greedy(objective, st0, local_feats, kappa, cand_mask=local_valid,
                rng=key, mode=mode)
    valid1 = (r1.idx >= 0) & my_keep
    g1 = jnp.where(r1.idx >= 0, local_gids[jnp.maximum(r1.idx, 0)], -1)

    # ---- level 2: intra-pod merge + distributed greedy (ICI) --------------
    Bp = jax.lax.all_gather(r1.feats, data_axis).reshape(md * kappa, d)
    Bp_mask = jax.lax.all_gather(valid1, data_axis).reshape(md * kappa)
    Bp_gids = jax.lax.all_gather(g1, data_axis).reshape(md * kappa)
    pod_f, pod_v, pod_g, _ = _dist_greedy_core(
        _objective_engine(objective, local_feats, Bp, Bp_mask, Bp_gids,
                          eval_mask=evalw),
        kappa, (data_axis,), w, denom_pod, feats.dtype)

    # ---- level 3: inter-pod merge + distributed greedy (DCI) --------------
    Bg = jax.lax.all_gather(pod_f, pod_axis).reshape(mp * kappa, d)
    Bg_mask = jax.lax.all_gather(pod_v, pod_axis).reshape(mp * kappa)
    Bg_gids = jax.lax.all_gather(pod_g, pod_axis).reshape(mp * kappa)
    glob_f, glob_v, glob_g, glob_val = _dist_greedy_core(
        _objective_engine(objective, local_feats, Bg, Bg_mask, Bg_gids,
                          eval_mask=evalw),
        k_final, both, w, denom_all, feats.dtype)

    # best pod-level solution, evaluated globally over the alive data
    def pod_value(sel_i, valid_i):
      st = set_value_feats(objective, objective.init(local_feats, evalw),
                           sel_i, valid_i)
      return objective.value(st) * n_live * w
    pods_f = jax.lax.all_gather(pod_f, pod_axis)        # (mp, kappa, d)
    pods_v = jax.lax.all_gather(pod_v, pod_axis)
    pods_g = jax.lax.all_gather(pod_g, pod_axis)
    pod_vals = _psum(jax.vmap(pod_value)(pods_f, pods_v), both) / denom_all
    pod_vals = jnp.where(jnp.any(pods_v, axis=1), pod_vals, -jnp.inf)
    best_p = jnp.argmax(pod_vals)
    v_best_pod = pod_vals[best_p]

    use_glob = glob_val >= v_best_pod
    sel_feats = jnp.where(use_glob, glob_f,
                          _take_k(pods_f[best_p], k_final, 0.0))
    sel_valid = jnp.where(use_glob, glob_v,
                          _take_k(pods_v[best_p], k_final, False))
    sel_gids = jnp.where(use_glob, glob_g,
                         _take_k(pods_g[best_p], k_final, -1))
    value = jnp.maximum(glob_val, v_best_pod)
    rescans = jax.lax.all_gather(r1.rescans.astype(jnp.int32), both)
    return GreediResult(sel_feats, sel_valid, value, glob_val, v_best_pod,
                        pod_vals, sel_gids, keep, rescans.reshape(m))

  out_specs = _replicated_result_specs()
  shmapped = _shard_map(
      fn, mesh=mesh, in_specs=(P(both), P(both), P(), P()),
      out_specs=out_specs)
  return shmapped(feats, gids, straggler_keep, rng)
