"""Pure-jnp oracles for every Pallas kernel in this package.

Each Pallas kernel must match its oracle to numerical tolerance across the
shape/dtype sweeps in tests/test_kernels.py (interpret=True on CPU).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

Array = jax.Array

NEG = -1e30  # masked-gain floor shared with the select kernels / greedy loops

# Every f32 contraction of the oracles and kernels runs at full f32
# precision.  XLA's TPU default rounds f32 matmul operands to bf16, which
# would put the XLA oracles, the Mosaic kernels and a host reference ~1e-3
# apart -- past the warm-bound slack and the pallas/ref parity the selection
# contracts are stated in.  On CPU this is what XLA does anyway.
DOT_PRECISION = jax.lax.Precision.HIGHEST


def _mm(a: Array, b: Array) -> Array:
  return jnp.matmul(a, b, precision=DOT_PRECISION)


def masked_top1(scores: Array, ok: Array, floor: float = NEG):
  """Ground truth for every select oracle: lowest-index argmax of the masked
  scores.  Returns ((), f32 best-masked-score, (), int32 index); with no
  feasible entry the result is (floor, 0), matching ``jnp.argmax`` on an
  all-floor vector."""
  masked = jnp.where(ok, scores.astype(jnp.float32), floor)
  i = jnp.argmax(masked).astype(jnp.int32)
  return masked[i], i


def _sim(ev: Array, cd: Array, kernel: str, h: float) -> Array:
  if kernel == "linear":
    return _mm(ev, cd.T)
  if kernel == "rbf":
    e2 = jnp.sum(ev * ev, axis=-1, keepdims=True)
    c2 = jnp.sum(cd * cd, axis=-1, keepdims=True)
    d2 = jnp.maximum(e2 - 2.0 * _mm(ev, cd.T) + c2.T, 0.0)
    return jnp.exp(-d2 / (h * h))
  raise ValueError(kernel)


def facility_gain_ref(eval_feats: Array, cand_feats: Array, cov: Array,
                      eval_mask: Array, *, kernel: str = "linear",
                      h: float = 0.75) -> Array:
  """Unnormalized marginal coverage gains: (nc,) float32.

  gain[j] = sum_i mask_i * max(sim(e_i, c_j) - cov_i, 0)
  """
  sim = _sim(eval_feats.astype(jnp.float32), cand_feats.astype(jnp.float32),
             kernel, h)
  inc = jnp.maximum(sim - cov.astype(jnp.float32)[:, None], 0.0)
  return _mm(eval_mask.astype(jnp.float32), inc)


def pairwise_ref(x: Array, y: Array, *, kernel: str = "rbf",
                 h: float = 0.75) -> Array:
  """Full similarity matrix (nx, ny) float32."""
  return _sim(x.astype(jnp.float32), y.astype(jnp.float32), kernel, h)


def info_gain_cond_ref(sel_feats: Array, linv: Array, cand_feats: Array, *,
                       kernel: str = "rbf", h: float = 0.75,
                       ridge: float = 1.0) -> Array:
  """Posterior conditional variance of each candidate given the selected set.

  cond[j] = k(v_j, v_j) + ridge - || linv @ k(S, v_j) ||^2, clamped at 1e-12.

  ``linv`` is inv(L) for L = chol(K_SS + ridge I) with columns past the live
  selection count zeroed, so padded selection rows contribute nothing.  The
  information-gain objective maps this to 0.5 log(cond / sigma^2); the DPP
  log-det maps it to log(cond).
  """
  sel = sel_feats.astype(jnp.float32)
  cd = cand_feats.astype(jnp.float32)
  k_sc = _sim(sel, cd, kernel, h)                       # (k, nc)
  c = _mm(linv.astype(jnp.float32), k_sc)              # (k, nc)
  if kernel == "rbf":
    k_vv = jnp.ones((cd.shape[0],), jnp.float32)
  else:
    k_vv = jnp.sum(cd * cd, axis=-1)
  cond = k_vv + ridge - jnp.sum(c * c, axis=0)
  return jnp.maximum(cond, 1e-12)


def coverage_gain_ref(eval_feats: Array, cand_feats: Array, cover: Array,
                      cap: Array, eval_mask: Array, *, kernel: str = "linear",
                      h: float = 0.75) -> Array:
  """Unnormalized saturated-coverage gains (Lin & Bilmes): (nc,) float32.

  gain[j] = sum_i mask_i * [ min(cover_i + s_ij, cap_i) - min(cover_i, cap_i) ]
  with s_ij = max(sim(e_i, c_j), 0).
  """
  sim = jnp.maximum(
      _sim(eval_feats.astype(jnp.float32), cand_feats.astype(jnp.float32),
           kernel, h), 0.0)
  cover = cover.astype(jnp.float32)
  cap = cap.astype(jnp.float32)
  new = jnp.minimum(cover[:, None] + sim, cap[:, None])
  inc = new - jnp.minimum(cover, cap)[:, None]
  return _mm(eval_mask.astype(jnp.float32), inc)


def graph_cut_gain_ref(w: Array, in_s: Array) -> Array:
  """Per-node cut gains deg_v - 2 (W x)_v == W @ (1 - 2x): (n,) float32."""
  wf = w.astype(jnp.float32)
  return _mm(wf, 1.0 - 2.0 * in_s.astype(jnp.float32))


# ---------------------------------------------------------------------------
# select oracles: gains + lowest-index argmax in one call (ground truth for
# the fused in-kernel top-1 reductions in select_top1.py)
# ---------------------------------------------------------------------------


def facility_select_ref(eval_feats: Array, cand_feats: Array, cov: Array,
                        eval_mask: Array, cand_ok: Array, *,
                        kernel: str = "linear", h: float = 0.75):
  gains = facility_gain_ref(eval_feats, cand_feats, cov, eval_mask,
                            kernel=kernel, h=h)
  return masked_top1(gains, cand_ok)


def coverage_select_ref(eval_feats: Array, cand_feats: Array, cover: Array,
                        cap: Array, eval_mask: Array, cand_ok: Array, *,
                        kernel: str = "linear", h: float = 0.75):
  gains = coverage_gain_ref(eval_feats, cand_feats, cover, cap, eval_mask,
                            kernel=kernel, h=h)
  return masked_top1(gains, cand_ok)


def info_select_ref(sel_feats: Array, linv: Array, cand_feats: Array,
                    cand_ok: Array, *, kernel: str = "rbf", h: float = 0.75,
                    ridge: float = 1.0):
  """Top-1 over conditional variances (cond >= 1e-12, so the 0.0 floor keeps
  any feasible candidate ahead of masked ones); the caller maps the winning
  cond through its log, which is strictly increasing and so order-preserving."""
  cond = info_gain_cond_ref(sel_feats, linv, cand_feats, kernel=kernel, h=h,
                            ridge=ridge)
  return masked_top1(cond, cand_ok, floor=0.0)


def graph_cut_select_ref(w: Array, in_s: Array, node_ok: Array):
  return masked_top1(graph_cut_gain_ref(w, in_s), node_ok)


# ---------------------------------------------------------------------------
# threshold-sieve admission: the streaming select-on-append oracle
# (ground truth for the chunk-vectorized ``sieve_update`` in ops.py)
# ---------------------------------------------------------------------------


def sieve_redundancy_ref(v: Array, members: Array, live: Array, *,
                         kernel: str = "linear", h: float = 0.75) -> Array:
  """Normalized redundancy of item ``v`` (d,) against each sieve bucket.

  ``members`` is the (T, k, d) per-bucket member block, ``live`` its (T, k)
  bool occupancy.  Returns (T,) in [0, 1]: the max over live members of
  ``relu(sim(v, s)) / sqrt(sim(v, v) * sim(s, s))`` -- Cauchy-Schwarz for
  PSD similarity kernels caps the ratio at 1 (an exact duplicate scores 1,
  an orthogonal item 0).  Empty buckets score 0.
  """
  t, k, d = members.shape
  sim = _sim(v[None].astype(jnp.float32),
             members.reshape(t * k, d).astype(jnp.float32),
             kernel, h)[0].reshape(t, k)
  if kernel == "linear":
    vsq = jnp.maximum(jnp.sum(v.astype(jnp.float32) ** 2), 1e-12)
    msq = jnp.maximum(jnp.sum(members.astype(jnp.float32) ** 2, axis=-1),
                      1e-12)
    red = jnp.maximum(sim, 0.0) / jnp.sqrt(vsq * msq)
  else:  # rbf: sim(v, v) == 1, sim already in [0, 1]
    red = sim
  return jnp.max(jnp.where(live, red, 0.0), axis=1)


def sieve_admit_ref(v: Array, gain: Array, gid: Array, active: Array,
                    tau: Array, sieve_gid: Array, sieve_gain: Array,
                    sieve_feat: Array, sieve_count: Array, *,
                    kernel: str = "linear", h: float = 0.75):
  """ONE streaming admission step -- the per-item ground truth semantics the
  chunk-vectorized ``ops.sieve_update`` must replay row by row.

  Item ``v`` (d,) with standing singleton gain ``gain`` () and id ``gid``
  () is offered to every threshold bucket: its admission score is the
  redundancy-discounted singleton gain

      score_t = gain * relu(1 - redundancy(v, bucket_t))

  and bucket t admits iff ``active`` (the item lands on this shard),
  ``score_t >= tau[t]``, the bucket has a free slot, and ``gid >= 0``.
  Admitted items land in slot ``count_t`` with their score as the recorded
  gain.  Returns the updated (sieve_gid, sieve_gain, sieve_feat,
  sieve_count).
  """
  t, k = sieve_gid.shape
  live = jnp.arange(k)[None, :] < sieve_count[:, None]
  red = sieve_redundancy_ref(v, sieve_feat, live, kernel=kernel, h=h)
  score = gain * jnp.maximum(1.0 - red, 0.0)
  admit = (active & (score >= tau) & (sieve_count < k) & (gid >= 0))
  slot = jnp.where(admit, sieve_count, k)          # k = dropped
  rows = jnp.arange(t)
  sieve_gid = sieve_gid.at[rows, slot].set(gid, mode="drop")
  sieve_gain = sieve_gain.at[rows, slot].set(score, mode="drop")
  sieve_feat = sieve_feat.at[rows, slot].set(v[None, :], mode="drop")
  return (sieve_gid, sieve_gain, sieve_feat,
          sieve_count + admit.astype(sieve_count.dtype))


def mha_ref(q: Array, k: Array, v: Array, *, causal: bool = True,
            scale: float | None = None) -> Array:
  """Reference GQA attention. q: (B, H, Lq, dh); k, v: (B, Hkv, Lk, dh)."""
  b, hq, lq, dh = q.shape
  hkv = k.shape[1]
  group = hq // hkv
  if scale is None:
    scale = dh ** -0.5
  kr = jnp.repeat(k, group, axis=1)
  vr = jnp.repeat(v, group, axis=1)
  logits = jnp.einsum("bhqd,bhkd->bhqk", q.astype(jnp.float32),
                      kr.astype(jnp.float32)) * scale
  if causal:
    lk = k.shape[2]
    mask = jnp.arange(lq)[:, None] + (lk - lq) >= jnp.arange(lk)[None, :]
    logits = jnp.where(mask, logits, -1e30)
  p = jax.nn.softmax(logits, axis=-1)
  out = jnp.einsum("bhqk,bhkd->bhqd", p, vr.astype(jnp.float32))
  return out.astype(q.dtype)
