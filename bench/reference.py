"""Plain references for the comparisons that decide ``correct``.

They import nothing of the program and take nothing it made: they read the
rows the benchmark generated and the semantics the service documents
(GreeDi's two rounds of greedy facility location; the sum-form bound table
``table[i] = sum_j relu(x_i . x_j)``; the threshold sieves' admission score
``gain * relu(1 - redundancy)``; the seeded merge's tie-break jitter).  What
the program produced is only ever the thing being checked.

Device work is float32 at ``Precision.HIGHEST`` (a TPU multiplies float32 in
bfloat16 passes otherwise); sums that decide a number are taken in float64
on the host.  Large products run in row blocks so that they fit beside
nothing else: the program's state is freed before any of this runs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

HIGHEST = jax.lax.Precision.HIGHEST
# the seeded sieve merge multiplies each pooled score by 1 + JITTER * u,
# u ~ uniform(PRNGKey(seed), pool size) (docs/service.md "Multi-tenant
# serving"); seed 0 multiplies by exactly 1
QUERY_JITTER = 1e-4
BLOCK_ROWS = 1 << 16


def _mm(a, b):
  return jnp.matmul(a, b.T, precision=HIGHEST)


# ---- GreeDi epoch ----------------------------------------------------------

@functools.partial(jax.jit, static_argnums=(1,))
def _greedy(x, k: int):
  """k greedy picks of facility location over the rows of ``x`` (all rows
  are both evaluation points and candidates); returns local indices."""
  s = _mm(x, x)

  def step(t, c):
    cov, picked, out = c
    g = jnp.sum(jnp.maximum(s - cov[:, None], 0.0), axis=0)
    g = jnp.where(picked, -jnp.inf, g)
    j = jnp.argmax(g)
    return (jnp.maximum(cov, s[:, j]), picked.at[j].set(True),
            out.at[t].set(j))

  n = x.shape[0]
  init = (jnp.zeros((n,), jnp.float32), jnp.zeros((n,), bool),
          jnp.zeros((k,), jnp.int32))
  return jax.lax.fori_loop(0, k, step, init)[2]


def partition(rng, n: int, m: int) -> np.ndarray:
  """(m, n // m) row indices of each machine under the epoch key ``rng``:
  the service splits it into (partition, run) keys and lays row
  ``perm[p]`` at position p, machine i owning a contiguous n / m block."""
  r_part = jax.random.split(rng)[0]
  return np.asarray(jax.random.permutation(r_part, n)).reshape(m, n // m)


def round1(x: np.ndarray, rng, m: int, kappa: int) -> np.ndarray:
  """GreeDi round 1: each machine's kappa greedy picks, as row indices of
  ``x`` in machine order (the merge's candidate block)."""
  parts = partition(rng, x.shape[0], m)
  out = []
  for rows in parts:
    loc = np.asarray(_greedy(jnp.asarray(x[rows]), kappa))
    out.append(rows[loc])
  return np.concatenate(out)


@jax.jit
def _walk(x, cands, picks_pos, picks_ok):
  """Along the program's picks (positions into ``cands``), each step's best
  gain over the unpicked candidates and the picked one's gain (sums over
  all rows)."""
  s = _mm(x, x[cands])                                   # (n, c)

  def step(c, inp):
    cov, picked = c
    j, ok = inp
    g = jnp.sum(jnp.maximum(s - cov[:, None], 0.0), axis=0)
    best = jnp.max(jnp.where(picked, -jnp.inf, g))
    cov = jnp.where(ok, jnp.maximum(cov, s[:, j]), cov)
    return (cov, picked.at[j].set(picked[j] | ok)), (best, g[j])

  n, c = x.shape[0], cands.shape[0]
  init = (jnp.zeros((n,), jnp.float32), jnp.zeros((c,), bool))
  _, (best, got) = jax.lax.scan(step, init, (picks_pos, picks_ok))
  return best, got


def facility_value(x: np.ndarray, sel: np.ndarray) -> float:
  """float64 f(S) = mean_i relu(max_{s in S} x_i . x_s) over all rows."""
  total = 0.0
  xs = jnp.asarray(x[np.asarray(sel)])
  for off in range(0, x.shape[0], BLOCK_ROWS):
    c = np.asarray(_mm(jnp.asarray(x[off:off + BLOCK_ROWS]), xs),
                   np.float64)
    total += float(np.maximum(c.max(axis=1), 0.0).sum())
  return total / x.shape[0]


def epoch_numbers(x: np.ndarray, rng, m: int, kappa: int, sel_gids,
                  sel_feats, value: float) -> dict:
  """The numbers of one checked epoch.

  * ``feat_gap``: largest |returned feature - stored row| over the coreset
    (the rows are handed over as given: an exact copy reads 0);
  * ``greedy_gap``: largest shortfall, over the returned picks in order, of
    a pick's gain below the best gain of any unpicked merge candidate
    (round 1 as the reference runs it, plus the program's own picks),
    relative to that best;
  * ``value_rel``: |reported value - float64 f(coreset)| / f(coreset).
  """
  sel = np.asarray(sel_gids, np.int64)
  feats = np.asarray(sel_feats, np.float32)
  if sel.size == 0 or (sel < 0).any() or len(set(sel.tolist())) != sel.size:
    return {"feat_gap": float("inf"), "greedy_gap": float("inf"),
            "value_rel": float("inf")}
  feat_gap = float(np.max(np.abs(feats - x[sel])))
  cands = np.unique(np.concatenate([round1(x, rng, m, kappa), sel]))
  pos = np.searchsorted(cands, sel).astype(np.int32)
  best, got = _walk(jnp.asarray(x), jnp.asarray(cands.astype(np.int32)),
                    jnp.asarray(pos), jnp.ones(pos.shape, bool))
  best, got = np.asarray(best, np.float64), np.asarray(got, np.float64)
  greedy_gap = float(np.max((best - got) / np.maximum(best, 1e-30)))
  ref = facility_value(x, sel)
  return {"feat_gap": feat_gap, "greedy_gap": max(greedy_gap, 0.0),
          "value_rel": abs(float(value) - ref) / max(ref, 1e-30)}


# ---- bound table and sieves ------------------------------------------------

@functools.partial(jax.jit, static_argnums=(2,))
def _chunk_sums(q, xb, chunk: int):
  s = jnp.maximum(_mm(q, xb), 0.0)                      # (r, rows)
  return jnp.sum(s.reshape(q.shape[0], -1, chunk), axis=2)


def chunk_relu_sums(q: np.ndarray, x: np.ndarray, chunk: int) -> np.ndarray:
  """(r, n / chunk) float64: for each query row, sum_j relu(q . x_j) over
  each append chunk of ``x`` (rows in append order)."""
  assert x.shape[0] % chunk == 0, (x.shape, chunk)
  qd = jnp.asarray(q, jnp.float32)
  step = max(BLOCK_ROWS // chunk, 1) * chunk
  out = [np.asarray(_chunk_sums(qd, jnp.asarray(x[off:off + step]), chunk),
                    np.float64)
         for off in range(0, x.shape[0], step)]
  return np.concatenate(out, axis=1)


def bound_numbers(x: np.ndarray, rows: np.ndarray, table: np.ndarray,
                  chunk: int) -> dict:
  """``bound_rel``: largest relative gap between the program's bound table
  and sum_j relu(x_i . x_j) over every stored row, at the sampled rows."""
  ref = chunk_relu_sums(x[rows], x, chunk).sum(axis=1)
  got = np.asarray(table, np.float64)[rows]
  return {"bound_rel": float(np.max(np.abs(got - ref)
                                    / np.maximum(ref, 1e-30)))}


def _cos(a: np.ndarray, b: np.ndarray) -> np.ndarray:
  a = a.astype(np.float64)
  b = b.astype(np.float64)
  na = np.maximum((a * a).sum(-1), 1e-12) ** 0.5
  nb = np.maximum((b * b).sum(-1), 1e-12) ** 0.5
  return np.maximum(a @ b.T, 0.0) / (na[:, None] * nb[None, :])


@functools.partial(jax.jit, static_argnums=(2,))
def _standing_block(q, xb, chunk: int, q0, c0):
  """(r, cols / chunk) sums of relu(q_i . x_j) over each append chunk of
  the column block, counting only rows j stored by the end of row i's own
  chunk (``q0``, ``c0``: the blocks' first row indices)."""
  s = jnp.maximum(_mm(q, xb), 0.0)
  i = q0 + jnp.arange(q.shape[0])
  j = c0 + jnp.arange(xb.shape[0])
  s = jnp.where(j[None, :] < ((i // chunk + 1) * chunk)[:, None], s, 0.0)
  return jnp.sum(s.reshape(q.shape[0], -1, chunk), axis=2)


def standing_sums(x: np.ndarray, chunk: int) -> np.ndarray:
  """(n,) float64: each row's standing singleton sum at admission,
  sum_j relu(x_i . x_j) over every row stored up to the end of row i's
  append chunk (rows in append order)."""
  n = x.shape[0]
  assert n % chunk == 0, (n, chunk)
  blk = int(np.gcd(n, max(BLOCK_ROWS // 8, chunk)))
  xd = jnp.asarray(x, jnp.float32)
  out = np.zeros((n,), np.float64)
  for a in range(0, n, blk):
    q = xd[a:a + blk]
    for c in range(0, a + blk, blk):       # later rows are not yet stored
      part = _standing_block(q, xd[c:c + blk], chunk, a, c)
      out[a:a + blk] += np.asarray(part, np.float64).sum(axis=1)
  return out


@jax.jit
def _redundancy(xb, q0, members, pos):
  """(r, P): each row's redundancy against each bucket, the largest
  relu(cos) to a member admitted before it (``members`` (P, k, d),
  ``pos`` (P, k) their append positions, past the end for empty slots)."""
  p, k, d = members.shape
  flat = members.reshape(p * k, d)
  c = jnp.maximum(_mm(xb, flat), 0.0)
  nq = jnp.maximum(jnp.sum(xb * xb, axis=1), 1e-12)
  nm = jnp.maximum(jnp.sum(flat * flat, axis=1), 1e-12)
  cos = c / jnp.sqrt(nq[:, None] * nm[None, :])
  i = q0 + jnp.arange(xb.shape[0])
  earlier = pos.reshape(-1)[None, :] < i[:, None]
  cos = jnp.where(earlier, cos, 0.0).reshape(xb.shape[0], p, k)
  return jnp.max(cos, axis=2)


class Sieves:
  """The program's standing sieve state, checked against the rows and
  re-weighted by the reference.

  ``state`` is what the store reads back: (gid, gain, feat, cnt, delta,
  jtop), (P, k), (P, k), (P, k, d), (P,), (m,), (m,) over P = m shards x T
  threshold buckets.  Row i is offered, in append order, to every bucket
  of the shard that stores it (``rows_per_shard`` rows each) from the
  chunk on which the bucket opened; its admission score is its standing
  singleton sum at admission (``standing_sums``) times ``relu(1 - r)``,
  r its largest cosine to a member admitted before it; the bucket admits
  it iff that score reaches the bucket's threshold and a slot is free.
  Slot p of a shard holds threshold (1 + eps)^(jtop - (T - 1) + p), jtop
  the grid top ceil(log Delta / log(1 + eps)) of the running largest
  singleton sum Delta, and opens on the first chunk whose grid top
  reaches it.  Checked:

  * each member's recorded gain is its admission score;
  * Delta is the largest standing sum of any row, and the grid top
    follows from it;
  * the members of each bucket are rows offered to it, in append order;
    each admitted row's score is at or above the bucket's threshold and
    each row rejected while the bucket had room is below it.
  """

  def __init__(self, x: np.ndarray, n_stored: int, chunk: int, state,
               eps: float, rows_per_shard: int):
    gid, gain, feat, cnt, delta, jtop = state
    self.gid = np.asarray(gid)
    self.feat = np.asarray(feat, np.float32)
    self.prog_gain = np.asarray(gain, np.float64)
    self.cnt = np.asarray(cnt)
    p, k = self.gid.shape
    live = np.arange(k)[None, :] < self.cnt[:, None]
    self.live = live & (self.gid >= 0)
    self.bad_members = bool((self.gid[live] < 0).any()
                            or (self.gid[live] >= n_stored).any())
    self.ref_gain = np.zeros((p, k), np.float64)
    self.feat_gap = 0.0
    self.gain_rel = 0.0
    self.admit_gap = 0.0
    if self.bad_members:
      return
    x = x[:n_stored]
    sums = standing_sums(x, chunk)
    for b in range(p):
      slots = np.nonzero(self.live[b])[0]
      if not slots.size:
        continue
      g = self.gid[b, slots]
      red = _cos(x[g], x[g])
      for c, slot in enumerate(slots):
        r = float(np.max(red[c, :c])) if c else 0.0
        self.ref_gain[b, slot] = sums[g[c]] * max(1.0 - r, 0.0)
    if self.live.any():
      self.feat_gap = float(np.max(np.abs(self.feat[self.live]
                                          - x[self.gid[self.live]])))
      ref = self.ref_gain[self.live]
      self.gain_rel = float(np.max(np.abs(self.prog_gain[self.live] - ref)
                                   / np.maximum(ref, 1e-30)))
    self.admit_gap = self._admission(x, sums, chunk, np.asarray(delta),
                                     np.asarray(jtop), eps, rows_per_shard)

  def _admission(self, x, sums, chunk, delta, jtop, eps, rows_per_shard):
    """The largest relative error of the grid and of the admissions: the
    program's Delta against the reference's, a member's score below its
    threshold, or a rejected row's above it; inf where the members cannot
    be the admissions of an append-order stream."""
    n = x.shape[0]
    p, k = self.gid.shape
    m = delta.shape[0]
    t = p // m
    lg = float(np.log1p(eps))
    run_max = np.maximum.accumulate(sums.reshape(-1, chunk).max(axis=1))
    tops = np.ceil(np.log(run_max) / lg).astype(np.int64)
    gap = float(np.max(np.abs(delta.astype(np.float64) - run_max[-1])
                       / run_max[-1]))
    if (jtop != tops[-1]).any():
      return float("inf")
    pos = np.where(self.live, self.gid, n).astype(np.int32)
    members = jnp.asarray(x[np.maximum(self.gid, 0)], jnp.float32)
    blk = int(np.gcd(n, BLOCK_ROWS // 8))
    red = np.concatenate([
        np.asarray(_redundancy(jnp.asarray(x[a:a + blk]), a, members,
                               jnp.asarray(pos)), np.float64)
        for a in range(0, n, blk)])
    shard_of = np.arange(n) // rows_per_shard
    for b in range(p):
      s, slot = divmod(b, t)
      expo = int(jtop[s]) - (t - 1) + slot
      tau = float(np.exp(expo * lg))
      start = int(np.argmax(tops >= expo)) * chunk
      g = self.gid[b, :self.cnt[b]].astype(np.int64)
      if ((g < start).any() or (np.diff(g) <= 0).any()
          or (shard_of[g] != s).any()):
        return float("inf")
      stop = int(g[-1]) + 1 if self.cnt[b] == k else n
      rows = np.arange(start, stop)
      rows = rows[shard_of[rows] == s]
      score = sums[rows] * np.maximum(1.0 - red[rows, b], 0.0)
      took = np.isin(rows, g)
      score[took] = self.ref_gain[b, :self.cnt[b]]
      gap = max(gap, float(np.max((tau - score[took]) / tau, initial=0.0)),
                float(np.max((score[~took] - tau) / tau, initial=0.0)))
    return gap

  def numbers(self) -> dict:
    if self.bad_members:
      return {"sieve_feat_gap": float("inf"), "sieve_gain_rel": float("inf"),
              "sieve_admit_gap": float("inf")}
    return {"sieve_feat_gap": self.feat_gap, "sieve_gain_rel": self.gain_rel,
            "sieve_admit_gap": self.admit_gap}


def jitter(seed: int, n: int) -> np.ndarray:
  """The seeded merge's score multipliers over the n pooled slots."""
  if seed == 0:
    return np.ones((n,), np.float64)
  u = np.asarray(jax.random.uniform(jax.random.PRNGKey(np.int32(seed)),
                                    (n,), jnp.float32))
  return 1.0 + QUERY_JITTER * u.astype(np.float64)


class Merge:
  """Checks sieve-merge answers: each pick must be the best remaining
  pooled candidate, by the reference's weights, up to rounding."""

  def __init__(self, sieves: Sieves, x: np.ndarray):
    self.g = sieves.gid.reshape(-1)
    self.w = np.where(sieves.live, sieves.ref_gain, 0.0).reshape(-1)
    self.ok = sieves.live.reshape(-1)
    safe = np.maximum(self.g, 0)
    self.x = x
    self.cos_pool = _cos(x[safe], x[safe])

  def numbers(self, k: int, exclude, seed: int, out_gids, estimate: float,
              n_docs: int):
    """(merge_gap, merge_value_rel, violations) of one answer: the largest
    shortfall of a pick below the best remaining candidate (relative), the
    gap of the answer's value estimate (sum of its pick scores over the
    stored rows) from the reference's, and the count of picks that are not
    pooled, excluded, repeated, past k, or missing while a candidate with
    a positive score is left."""
    n = self.g.shape[0]
    mult = jitter(seed, n)
    ok = self.ok & ~np.isin(self.g, np.asarray(exclude, np.int64))
    redmax = np.zeros((n,), np.float64)
    taken: list[int] = []
    gap, total, bad = 0.0, 0.0, 0
    out_gids = [int(v) for v in out_gids]
    for i in range(k):
      avail = ok & ~np.isin(self.g, taken)
      score = np.where(avail, self.w * np.maximum(1.0 - redmax, 0.0) * mult,
                       -np.inf)
      best = float(np.max(score)) if avail.any() else 0.0
      if i >= len(out_gids):
        bad += best > 0.0
        break
      g = out_gids[i]
      at = np.nonzero(avail & (self.g == g))[0]
      if not at.size:                 # not pooled, excluded or repeated
        bad += 1
        break
      j = at[np.argmax(score[at])]
      got = float(score[j])
      gap = max(gap, (best - got) / max(best, 1e-30))
      total += got
      taken.append(g)
      redmax = np.maximum(redmax, self.cos_pool[:, j])
    if len(out_gids) > k:
      bad += 1
    ref = total / max(n_docs, 1)
    value_rel = abs(float(estimate) - ref) / max(ref, 1e-30)
    return gap, value_rel, bad
