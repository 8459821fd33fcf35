"""jit'd public wrappers around the Pallas kernels.

Handles padding to block multiples, dtype promotion, and backend dispatch:
on the CPU backend the kernels execute in interpret mode (the kernel body
runs as traced jnp ops -- bit-accurate vs the TPU lowering semantics), on TPU
they compile to Mosaic, and any other backend is refused.
``force_xla=True`` routes to the pure-jnp reference (used to A/B the kernels
and by tiny shapes where tiling is overhead).

Block sizes default to ``None`` = "consult the autotable" (kernels/autotune.py,
keyed on (n, d, backend)); an explicit block argument still wins, clamped to
a power of two that fits the operand.  Both are static, trace-time choices.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels import autotune, dispatch, ref
from repro.kernels.coverage_gain import coverage_gain_pallas
from repro.kernels.facility_gain import facility_gain_pallas
from repro.kernels.flash_attention import flash_attention_pallas
from repro.kernels.graph_cut_gain import graph_cut_gain_pallas
from repro.kernels.info_gain import info_gain_cond_pallas
from repro.kernels.pairwise import pairwise_pallas
from repro.kernels.select_top1 import (coverage_select_pallas,
                                       facility_select_pallas,
                                       graph_cut_select_pallas,
                                       info_select_pallas)

Array = jax.Array


@functools.lru_cache(maxsize=None)
def _interpret() -> bool:
  """Whether the Pallas kernels run in interpret mode: on the CPU backend
  only.  Any other backend that is not a TPU (a GPU, or a TPU that failed to
  initialize and fell back elsewhere) is refused instead of silently
  interpreted at a fraction of the speed.  Cached: the process backend is
  read once, at trace time (dispatch.py doc)."""
  backend = jax.default_backend()
  if backend not in ("tpu", "cpu"):
    raise RuntimeError(
        f"the Pallas kernels compile for TPU and interpret on CPU; backend "
        f"{backend!r} is neither (pass backend='ref' for the XLA oracles)")
  return backend == "cpu"


def _pad_rows(x: Array, mult: int, value=0.0) -> Array:
  n = x.shape[0]
  pad = (-n) % mult
  if pad == 0:
    return x
  return jnp.pad(x, [(0, pad)] + [(0, 0)] * (x.ndim - 1),
                 constant_values=value)


def _block(n: int, d: int, explicit: int | None, itemsize: int = 4) -> int:
  """Resolve a tile size: explicit override (rounded down to a power of two,
  then clamped to fit n) or the autotable.  The clamp caps at the override
  itself, so any explicit power-of-two block (512, 1024, ...) is honored
  whenever the operand is big enough.  Either way the block is at least
  ``autotune.LANES``: a short axis is padded up to one lane-wide block."""
  if explicit is not None:
    cap = 1 << max(int(explicit).bit_length() - 1, 3)  # pow2 <= explicit
    return max(autotune.floor_pow2(n, cap=cap), autotune.LANES)
  return autotune.pick_block(n, d, itemsize=itemsize)


@functools.partial(jax.jit, static_argnames=("kernel", "h", "block_m",
                                             "block_n", "force_xla"))
def facility_gain(eval_feats: Array, cand_feats: Array, cov: Array,
                  eval_mask: Array, *, kernel: str = "linear", h: float = 0.75,
                  block_m: int | None = None, block_n: int | None = None,
                  force_xla: bool = False) -> Array:
  """Unnormalized facility-location gains (nc,) -- see facility_gain.py."""
  if force_xla:
    return ref.facility_gain_ref(eval_feats, cand_feats, cov, eval_mask,
                                 kernel=kernel, h=h)
  ne, d = eval_feats.shape
  nc = cand_feats.shape[0]
  sz = eval_feats.dtype.itemsize
  bm, bn = _block(ne, d, block_m, sz), _block(nc, d, block_n, sz)
  ev = _pad_rows(eval_feats, bm)
  cd = _pad_rows(cand_feats, bn)
  cv = _pad_rows(cov, bm, value=jnp.inf)   # inf cover => padded rows gain 0
  mk = _pad_rows(eval_mask, bm, value=0.0)
  out = facility_gain_pallas(ev, cd, cv, mk, kernel=kernel, h=h, block_m=bm,
                             block_n=bn, interpret=_interpret())
  return out[:nc]


@functools.partial(jax.jit, static_argnames=("kernel", "h", "block_m",
                                             "block_n", "force_xla"))
def facility_select(eval_feats: Array, cand_feats: Array, cov: Array,
                    eval_mask: Array, cand_ok: Array, *,
                    kernel: str = "linear", h: float = 0.75,
                    block_m: int | None = None, block_n: int | None = None,
                    force_xla: bool = False):
  """Fused top-1 facility gain -> ((), f32 best, (), int32 idx)."""
  if force_xla:
    return ref.facility_select_ref(eval_feats, cand_feats, cov, eval_mask,
                                   cand_ok, kernel=kernel, h=h)
  ne, d = eval_feats.shape
  nc = cand_feats.shape[0]
  sz = eval_feats.dtype.itemsize
  bm, bn = _block(ne, d, block_m, sz), _block(nc, d, block_n, sz)
  ev = _pad_rows(eval_feats, bm)
  cd = _pad_rows(cand_feats, bn)
  cv = _pad_rows(cov, bm, value=jnp.inf)
  mk = _pad_rows(eval_mask, bm, value=0.0)
  ok = _pad_rows(cand_ok.astype(jnp.float32), bn, value=0.0)
  return facility_select_pallas(ev, cd, cv, mk, ok, kernel=kernel, h=h,
                                block_m=bm, block_n=bn,
                                interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("kernel", "h", "ridge",
                                             "block_n", "force_xla"))
def info_gain_cond(sel_feats: Array, linv: Array, cand_feats: Array, *,
                   kernel: str = "rbf", h: float = 0.75, ridge: float = 1.0,
                   block_n: int | None = None, force_xla: bool = False) -> Array:
  """Posterior conditional variances (nc,) -- see info_gain.py."""
  if force_xla:
    return ref.info_gain_cond_ref(sel_feats, linv, cand_feats, kernel=kernel,
                                  h=h, ridge=ridge)
  k, d = sel_feats.shape
  nc = cand_feats.shape[0]
  bn = _block(nc, d, block_n, cand_feats.dtype.itemsize)
  kpad = (-k) % 8  # sublane-align the resident selection block
  sl = _pad_rows(sel_feats, 8)
  lv = jnp.pad(linv, ((0, kpad), (0, kpad))) if kpad else linv
  cd = _pad_rows(cand_feats, bn)
  out = info_gain_cond_pallas(sl, lv, cd, kernel=kernel, h=h, ridge=ridge,
                              block_n=bn, interpret=_interpret())
  return out[:nc]


@functools.partial(jax.jit, static_argnames=("kernel", "h", "ridge",
                                             "block_n", "force_xla"))
def info_select(sel_feats: Array, linv: Array, cand_feats: Array,
                cand_ok: Array, *, kernel: str = "rbf", h: float = 0.75,
                ridge: float = 1.0, block_n: int | None = None,
                force_xla: bool = False):
  """Fused top-1 conditional variance -> ((), f32 best cond, (), int32 idx)."""
  if force_xla:
    return ref.info_select_ref(sel_feats, linv, cand_feats, cand_ok,
                               kernel=kernel, h=h, ridge=ridge)
  k, d = sel_feats.shape
  nc = cand_feats.shape[0]
  bn = _block(nc, d, block_n, cand_feats.dtype.itemsize)
  kpad = (-k) % 8
  sl = _pad_rows(sel_feats, 8)
  lv = jnp.pad(linv, ((0, kpad), (0, kpad))) if kpad else linv
  cd = _pad_rows(cand_feats, bn)
  ok = _pad_rows(cand_ok.astype(jnp.float32), bn, value=0.0)
  return info_select_pallas(sl, lv, cd, ok, kernel=kernel, h=h, ridge=ridge,
                            block_n=bn, interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("kernel", "h", "block_m",
                                             "block_n", "force_xla"))
def coverage_gain(eval_feats: Array, cand_feats: Array, cover: Array,
                  cap: Array, eval_mask: Array, *, kernel: str = "linear",
                  h: float = 0.75, block_m: int | None = None,
                  block_n: int | None = None,
                  force_xla: bool = False) -> Array:
  """Unnormalized saturated-coverage gains (nc,) -- see coverage_gain.py."""
  if force_xla:
    return ref.coverage_gain_ref(eval_feats, cand_feats, cover, cap,
                                 eval_mask, kernel=kernel, h=h)
  ne, d = eval_feats.shape
  nc = cand_feats.shape[0]
  sz = eval_feats.dtype.itemsize
  bm, bn = _block(ne, d, block_m, sz), _block(nc, d, block_n, sz)
  ev = _pad_rows(eval_feats, bm)
  cd = _pad_rows(cand_feats, bn)
  cv = _pad_rows(cover, bm)
  cp = _pad_rows(cap, bm)      # cap 0 + mask 0 => padded rows gain 0
  mk = _pad_rows(eval_mask, bm, value=0.0)
  out = coverage_gain_pallas(ev, cd, cv, cp, mk, kernel=kernel, h=h,
                             block_m=bm, block_n=bn, interpret=_interpret())
  return out[:nc]


@functools.partial(jax.jit, static_argnames=("kernel", "h", "block_m",
                                             "block_n", "force_xla"))
def coverage_select(eval_feats: Array, cand_feats: Array, cover: Array,
                    cap: Array, eval_mask: Array, cand_ok: Array, *,
                    kernel: str = "linear", h: float = 0.75,
                    block_m: int | None = None, block_n: int | None = None,
                    force_xla: bool = False):
  """Fused top-1 saturated-coverage gain -> ((), f32 best, (), int32 idx)."""
  if force_xla:
    return ref.coverage_select_ref(eval_feats, cand_feats, cover, cap,
                                   eval_mask, cand_ok, kernel=kernel, h=h)
  ne, d = eval_feats.shape
  nc = cand_feats.shape[0]
  sz = eval_feats.dtype.itemsize
  bm, bn = _block(ne, d, block_m, sz), _block(nc, d, block_n, sz)
  ev = _pad_rows(eval_feats, bm)
  cd = _pad_rows(cand_feats, bn)
  cv = _pad_rows(cover, bm)
  cp = _pad_rows(cap, bm)
  mk = _pad_rows(eval_mask, bm, value=0.0)
  ok = _pad_rows(cand_ok.astype(jnp.float32), bn, value=0.0)
  return coverage_select_pallas(ev, cd, cv, cp, mk, ok, kernel=kernel, h=h,
                                block_m=bm, block_n=bn,
                                interpret=_interpret())


@functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                             "force_xla"))
def graph_cut_gain(w: Array, in_s: Array, *, block_m: int | None = None,
                   block_n: int | None = None,
                   force_xla: bool = False) -> Array:
  """Per-node cut gains (n,) -- see graph_cut_gain.py."""
  if force_xla:
    return ref.graph_cut_gain_ref(w, in_s)
  n = w.shape[0]
  sz = w.dtype.itemsize
  bm, bn = _block(n, n, block_m, sz), _block(n, n, block_n, sz)
  b = max(bm, bn)
  pad = (-n) % b
  wp = jnp.pad(w, ((0, pad), (0, pad))) if pad else w
  xp = _pad_rows(in_s, b)
  out = graph_cut_gain_pallas(wp, xp, block_m=bm, block_n=bn,
                              interpret=_interpret())
  return out[:n]


@functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                             "force_xla"))
def graph_cut_select(w: Array, in_s: Array, node_ok: Array, *,
                     block_m: int | None = None, block_n: int | None = None,
                     force_xla: bool = False):
  """Fused top-1 node cut gain -> ((), f32 best, (), int32 node idx)."""
  if force_xla:
    return ref.graph_cut_select_ref(w, in_s, node_ok)
  n = w.shape[0]
  sz = w.dtype.itemsize
  bm, bn = _block(n, n, block_m, sz), _block(n, n, block_n, sz)
  b = max(bm, bn)
  pad = (-n) % b
  wp = jnp.pad(w, ((0, pad), (0, pad))) if pad else w
  xp = _pad_rows(in_s, b)
  ok = _pad_rows(node_ok.astype(jnp.float32), b, value=0.0)
  return graph_cut_select_pallas(wp, xp, ok, block_m=bm, block_n=bn,
                                 interpret=_interpret())


# ---------------------------------------------------------------------------
# query-batched select oracles: the fused top-1 reductions vmapped over a
# leading query axis.  The corpus-side operands (feature blocks, adjacency)
# are SHARED across the batch -- vmap in_axes=None -- so one scan of the
# candidate block serves B concurrent selection requests; only the per-query
# selection state (coverage, masks, Cholesky factors) carries the (B, ...)
# batch dimension.  Batch width comes from kernels/autotune.query_tile via
# the callers (service/store.py pads ragged batches up to it).
# ---------------------------------------------------------------------------


@functools.partial(jax.jit, static_argnames=("kernel", "h", "block_m",
                                             "block_n", "force_xla"))
def facility_select_batched(eval_feats: Array, cand_feats: Array, cov: Array,
                            eval_mask: Array, cand_ok: Array, *,
                            kernel: str = "linear", h: float = 0.75,
                            block_m: int | None = None,
                            block_n: int | None = None,
                            force_xla: bool = False):
  """Query-batched fused top-1 facility gain -> ((B,) best, (B,) idx).

  ``cov``/``eval_mask``/``cand_ok`` are (B, ne)/(B, ne)/(B, nc) per-query
  state; ``eval_feats``/``cand_feats`` are shared across the batch.
  """
  fn = functools.partial(facility_select, kernel=kernel, h=h,
                         block_m=block_m, block_n=block_n,
                         force_xla=force_xla)
  return jax.vmap(fn, in_axes=(None, None, 0, 0, 0))(
      eval_feats, cand_feats, cov, eval_mask, cand_ok)


@functools.partial(jax.jit, static_argnames=("kernel", "h", "block_m",
                                             "block_n", "force_xla"))
def coverage_select_batched(eval_feats: Array, cand_feats: Array,
                            cover: Array, cap: Array, eval_mask: Array,
                            cand_ok: Array, *, kernel: str = "linear",
                            h: float = 0.75, block_m: int | None = None,
                            block_n: int | None = None,
                            force_xla: bool = False):
  """Query-batched fused top-1 saturated-coverage gain -> ((B,), (B,)).

  Per-query state: ``cover`` (B, ne), ``eval_mask`` (B, ne), ``cand_ok``
  (B, nc); the saturation caps and feature blocks are shared.
  """
  fn = functools.partial(coverage_select, kernel=kernel, h=h,
                         block_m=block_m, block_n=block_n,
                         force_xla=force_xla)
  return jax.vmap(fn, in_axes=(None, None, 0, None, 0, 0))(
      eval_feats, cand_feats, cover, cap, eval_mask, cand_ok)


@functools.partial(jax.jit, static_argnames=("kernel", "h", "ridge",
                                             "block_n", "force_xla"))
def info_select_batched(sel_feats: Array, linv: Array, cand_feats: Array,
                        cand_ok: Array, *, kernel: str = "rbf",
                        h: float = 0.75, ridge: float = 1.0,
                        block_n: int | None = None, force_xla: bool = False):
  """Query-batched fused top-1 conditional variance -> ((B,), (B,)).

  Per-query state: the selection block ``sel_feats`` (B, k, d), its inverse
  Cholesky factor ``linv`` (B, k, k), and ``cand_ok`` (B, nc); the candidate
  block is shared across the batch.
  """
  fn = functools.partial(info_select, kernel=kernel, h=h, ridge=ridge,
                         block_n=block_n, force_xla=force_xla)
  return jax.vmap(fn, in_axes=(0, 0, None, 0))(
      sel_feats, linv, cand_feats, cand_ok)


@functools.partial(jax.jit, static_argnames=("block_m", "block_n",
                                             "force_xla"))
def graph_cut_select_batched(w: Array, in_s: Array, node_ok: Array, *,
                             block_m: int | None = None,
                             block_n: int | None = None,
                             force_xla: bool = False):
  """Query-batched fused top-1 node cut gain -> ((B,), (B,)).

  Per-query state: the selection indicator ``in_s`` (B, n) and ``node_ok``
  (B, n); the adjacency is shared across the batch.
  """
  fn = functools.partial(graph_cut_select, block_m=block_m, block_n=block_n,
                         force_xla=force_xla)
  return jax.vmap(fn, in_axes=(None, 0, 0))(w, in_s, node_ok)


@functools.partial(jax.jit, static_argnames=("kernel", "h", "block_x",
                                             "block_y", "force_xla"))
def pairwise(x: Array, y: Array, *, kernel: str = "rbf", h: float = 0.75,
             block_x: int | None = None, block_y: int | None = None,
             force_xla: bool = False) -> Array:
  """Similarity matrix (nx, ny) float32 -- see pairwise.py."""
  if force_xla:
    return ref.pairwise_ref(x, y, kernel=kernel, h=h)
  nx, ny = x.shape[0], y.shape[0]
  d = x.shape[1]
  sz = x.dtype.itemsize
  bx, by = _block(nx, d, block_x, sz), _block(ny, d, block_y, sz)
  xp = _pad_rows(x, bx)
  yp = _pad_rows(y, by)
  out = pairwise_pallas(xp, yp, kernel=kernel, h=h, block_x=bx, block_y=by,
                        interpret=_interpret())
  return out[:nx, :ny]


@functools.partial(jax.jit, static_argnames=("kernel", "h", "force_xla"))
def bound_update(new_rows: Array, block_feats: Array, new_valid: Array,
                 block_valid: Array, *, kernel: str = "linear",
                 h: float = 0.75, force_xla: bool = False):
  """Fused append-time warm-bound pass: one (nb_new x nb_block) similarity
  sweep serving both sides of a corpus append (see service/store.py):

      add[j]  = sum_i relu(sim(new_i, block_j))   -- new evaluation mass
                                                     credited to document j
      sums[i] = sum_j relu(sim(new_i, block_j))   -- new document i's own
                                                     sum-form bound (partial:
                                                     this block's columns)

  Rows/columns with ``new_valid``/``block_valid`` 0 (chunk padding, holes)
  contribute nothing.  Routes the similarity block through the same fused
  ``pairwise`` implementations as the GreeDi fast engine, so it shards over
  a mesh by simply handing each shard its local block columns.
  """
  s = pairwise(new_rows, block_feats, kernel=kernel, h=h, force_xla=force_xla)
  s = jnp.maximum(s, 0.0)
  s = s * new_valid[:, None] * block_valid[None, :]
  return jnp.sum(s, axis=0), jnp.sum(s, axis=1)


@functools.partial(jax.jit, static_argnames=("kernel", "h", "force_xla"))
def sieve_update(rows: Array, gains: Array, rgids: Array, active: Array,
                 tau: Array, sieve_gid: Array, sieve_gain: Array,
                 sieve_feat: Array, sieve_count: Array, *,
                 kernel: str = "linear", h: float = 0.75,
                 force_xla: bool = False):
  """Streaming threshold-sieve admission over one append chunk.

  Replays ``ref.sieve_admit_ref`` for every chunk row IN ORDER (the stream
  semantics of sieve-streaming: item i's redundancy is measured against the
  buckets as updated by items 0..i-1, including intra-chunk admissions) --
  but all similarity work is hoisted OUT of the sequential part: one fused
  ``pairwise`` sweep of the chunk against the standing members (ab, T*k) and
  one of the chunk against itself (ab, ab), so the scan body is pure
  gather/mask/scatter bookkeeping.  Cost per chunk is O(ab * (T*k + ab) * d)
  similarity flops -- the same order as the ``bound_update`` pass this rides
  along with -- regardless of how many admissions happen.

  Args:
    rows: (ab, d) chunk feature rows.
    gains: (ab,) standing sum-form singleton gains of the chunk rows (the
      ``sums`` output of the ``bound_update`` pass, already psum-reduced).
    rgids: (ab,) int32 chunk gids (-1 = chunk padding).
    active: (ab,) bool -- rows this shard's sieve should consider (valid AND
      landing in this shard's slice AND a usable threshold grid exists).
    tau: (T,) per-bucket admission thresholds.
    sieve_gid / sieve_gain / sieve_feat / sieve_count: this shard's standing
      sieve state -- (T, k) int32 / (T, k) f32 / (T, k, d) f32 / (T,) int32.

  Returns the four updated sieve arrays.
  """
  ab, d = rows.shape
  t, k = sieve_gid.shape
  s_pre = pairwise(rows, sieve_feat.reshape(t * k, d), kernel=kernel, h=h,
                   force_xla=force_xla)                       # (ab, t*k)
  s_intra = pairwise(rows, rows, kernel=kernel, h=h,
                     force_xla=force_xla)                     # (ab, ab)
  if kernel == "linear":
    rsq = jnp.maximum(jnp.sum(rows.astype(jnp.float32) ** 2, axis=-1), 1e-12)
    msq_pre = jnp.maximum(
        jnp.sum(sieve_feat.astype(jnp.float32) ** 2, axis=-1), 1e-12)

  def step(carry, i):
    gid_b, gain_b, src, cnt = carry
    live = jnp.arange(k)[None, :] < cnt[:, None]
    # slot similarity: intra-chunk members (src >= 0) read the chunk-self
    # sweep, standing members the pre-chunk sweep
    safe = jnp.maximum(src, 0)
    sim = jnp.where(src >= 0, s_intra[i, safe],
                    s_pre[i].reshape(t, k))
    if kernel == "linear":
      msq = jnp.where(src >= 0, rsq[safe], msq_pre)
      red = jnp.maximum(sim, 0.0) / jnp.sqrt(rsq[i] * msq)
    else:  # rbf: sim(v, v) == 1 and sim already lands in [0, 1]
      red = sim
    red = jnp.max(jnp.where(live, red, 0.0), axis=1)          # (t,)
    score = gains[i] * jnp.maximum(1.0 - red, 0.0)
    admit = active[i] & (score >= tau) & (cnt < k) & (rgids[i] >= 0)
    slot = jnp.where(admit, cnt, k)                           # k = dropped
    rws = jnp.arange(t)
    gid_b = gid_b.at[rws, slot].set(rgids[i], mode="drop")
    gain_b = gain_b.at[rws, slot].set(score, mode="drop")
    src = src.at[rws, slot].set(i, mode="drop")
    return (gid_b, gain_b, src, cnt + admit.astype(cnt.dtype)), ()

  src0 = jnp.full((t, k), -1, jnp.int32)
  (sieve_gid, sieve_gain, src, sieve_count), _ = jax.lax.scan(
      step, (sieve_gid, sieve_gain, src0, sieve_count), jnp.arange(ab))
  sieve_feat = jnp.where((src >= 0)[..., None],
                         rows[jnp.maximum(src, 0)], sieve_feat)
  return sieve_gid, sieve_gain, sieve_feat, sieve_count


@functools.partial(jax.jit, static_argnames=("causal", "scale", "block_q",
                                             "block_k", "force_xla"))
def flash_attention(q: Array, k: Array, v: Array, *, causal: bool = True,
                    scale: float | None = None, block_q: int = 128,
                    block_k: int = 128, force_xla: bool = False) -> Array:
  """Causal GQA attention (B, H, L, dh) -- see flash_attention.py."""
  if force_xla:
    return ref.mha_ref(q, k, v, causal=causal, scale=scale)
  lq = q.shape[2]
  bq = min(block_q, autotune.floor_pow2(lq))
  bk = min(block_k, autotune.floor_pow2(lq))
  pad = (-lq) % max(bq, bk)
  if pad:
    qp = jnp.pad(q, ((0, 0), (0, 0), (0, pad), (0, 0)))
    kp = jnp.pad(k, ((0, 0), (0, 0), (0, pad), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (0, 0), (0, pad), (0, 0)))
  else:
    qp, kp, vp = q, k, v
  out = flash_attention_pallas(qp, kp, vp, causal=causal, scale=scale,
                               block_q=bq, block_k=bk, lk_valid=lq,
                               interpret=_interpret())
  return out[:, :, :lq]


# ---------------------------------------------------------------------------
# registry: one gain + one select oracle per objective, fused + reference
# ---------------------------------------------------------------------------

dispatch.register("facility_gain", pallas=facility_gain,
                  ref=functools.partial(facility_gain, force_xla=True))
dispatch.register("info_gain_cond", pallas=info_gain_cond,
                  ref=functools.partial(info_gain_cond, force_xla=True))
dispatch.register("coverage_gain", pallas=coverage_gain,
                  ref=functools.partial(coverage_gain, force_xla=True))
dispatch.register("graph_cut_gain", pallas=graph_cut_gain,
                  ref=functools.partial(graph_cut_gain, force_xla=True))
# materialized similarity blocks: the cached-similarity GreeDi fast path
# (core/greedi.py greedi_sharded_fast) and the GP cross-term benchmarks
dispatch.register("pairwise", pallas=pairwise,
                  ref=functools.partial(pairwise, force_xla=True))
# append-time warm-bound maintenance (sum-form relu tables): the sharded
# bound-update entry point of the selection service's CorpusStore
dispatch.register("bound_update", pallas=bound_update,
                  ref=functools.partial(bound_update, force_xla=True))
# streaming threshold-sieve admission over an append chunk: the standing
# select-on-append state behind SelectionService.query (service/store.py);
# per-item ground truth in ref.sieve_admit_ref
dispatch.register("sieve_update", pallas=sieve_update,
                  ref=functools.partial(sieve_update, force_xla=True))

# fused select-step oracles (in-kernel top-1; see select_top1.py)
dispatch.register_select("facility_gain", pallas=facility_select,
                         ref=functools.partial(facility_select,
                                               force_xla=True))
dispatch.register_select("info_gain_cond", pallas=info_select,
                         ref=functools.partial(info_select, force_xla=True))
dispatch.register_select("coverage_gain", pallas=coverage_select,
                         ref=functools.partial(coverage_select,
                                               force_xla=True))
dispatch.register_select("graph_cut_gain", pallas=graph_cut_select,
                         ref=functools.partial(graph_cut_select,
                                               force_xla=True))

# query-batched select oracles (the multi-tenant serving path): one corpus
# scan answers a whole query batch -- same stable names, vmapped semantics
dispatch.register_select_batched(
    "facility_gain", pallas=facility_select_batched,
    ref=functools.partial(facility_select_batched, force_xla=True))
dispatch.register_select_batched(
    "info_gain_cond", pallas=info_select_batched,
    ref=functools.partial(info_select_batched, force_xla=True))
dispatch.register_select_batched(
    "coverage_gain", pallas=coverage_select_batched,
    ref=functools.partial(coverage_select_batched, force_xla=True))
dispatch.register_select_batched(
    "graph_cut_gain", pallas=graph_cut_select_batched,
    ref=functools.partial(graph_cut_select_batched, force_xla=True))


# ---------------------------------------------------------------------------
# traceable entry points (repro.analysis): every oracle family above at
# representative shapes, with R3 mask annotations.  Row sizes are distinct
# from d and from each other so a reduced-axis size match really means "a
# pad-and-mask row axis".  Builders resolve "auto" so the analyzer traces
# the implementation production uses on this host's backend.
# ---------------------------------------------------------------------------

_NE, _NC, _AB, _D = 384, 96, 48, 16  # eval rows, candidates, append chunk, d


def _f32(*shape):
  return jax.ShapeDtypeStruct(shape, jnp.float32)


def _i32(*shape):
  return jax.ShapeDtypeStruct(shape, jnp.int32)


def _ep(name, builder, needs_devices=1):
  dispatch.register_entry_point(name, builder, needs_devices=needs_devices)


_ep("oracle:facility_gain", lambda: dispatch.TraceSpec(
    fn=dispatch.resolve("facility_gain", "auto"),
    args=(_f32(_NE, _D), _f32(_NC, _D), _f32(_NE), _f32(_NE)),
    mask_args=(3,), row_sizes=(_NE,)))

_ep("select:facility_gain", lambda: dispatch.TraceSpec(
    fn=dispatch.resolve_select("facility_gain", "auto"),
    args=(_f32(_NE, _D), _f32(_NC, _D), _f32(_NE), _f32(_NE), _f32(_NC)),
    mask_args=(3, 4), row_sizes=(_NE, _NC)))

_ep("oracle:coverage_gain", lambda: dispatch.TraceSpec(
    fn=dispatch.resolve("coverage_gain", "auto"),
    args=(_f32(_NE, _D), _f32(_NC, _D), _f32(_NE), _f32(_NE), _f32(_NE)),
    mask_args=(4,), row_sizes=(_NE,)))

_ep("select:coverage_gain", lambda: dispatch.TraceSpec(
    fn=dispatch.resolve_select("coverage_gain", "auto"),
    args=(_f32(_NE, _D), _f32(_NC, _D), _f32(_NE), _f32(_NE), _f32(_NE),
          _f32(_NC)),
    mask_args=(4, 5), row_sizes=(_NE, _NC)))

# info-gain's eval-set independence means no row mask on the gain side; the
# select side masks the candidate axis through cand_ok
_ep("oracle:info_gain_cond", lambda: dispatch.TraceSpec(
    fn=dispatch.resolve("info_gain_cond", "auto"),
    args=(_f32(8, _D), _f32(8, 8), _f32(_NC, _D))))

_ep("select:info_gain_cond", lambda: dispatch.TraceSpec(
    fn=dispatch.resolve_select("info_gain_cond", "auto"),
    args=(_f32(8, _D), _f32(8, 8), _f32(_NC, _D), _f32(_NC)),
    mask_args=(3,), row_sizes=(_NC,)))

# graph-cut contracts the full adjacency (no pad-and-mask rows at this
# surface; node_ok only gates the top-1), so R3 has nothing to audit here
_ep("oracle:graph_cut_gain", lambda: dispatch.TraceSpec(
    fn=dispatch.resolve("graph_cut_gain", "auto"),
    args=(_f32(_NC, _NC), _f32(_NC))))

_ep("select:graph_cut_gain", lambda: dispatch.TraceSpec(
    fn=dispatch.resolve_select("graph_cut_gain", "auto"),
    args=(_f32(_NC, _NC), _f32(_NC), _f32(_NC))))

# the query-batched select family: per-query state carries a leading batch
# axis (_B distinct from every row size so a match means "the query axis");
# the row-axis reductions and mask roots are the unbatched oracles', vmapped
_B = 3

_ep("select_batched:facility_gain", lambda: dispatch.TraceSpec(
    fn=dispatch.resolve_select_batched("facility_gain", "auto"),
    args=(_f32(_NE, _D), _f32(_NC, _D), _f32(_B, _NE), _f32(_B, _NE),
          _f32(_B, _NC)),
    mask_args=(3, 4), row_sizes=(_NE, _NC)))

_ep("select_batched:coverage_gain", lambda: dispatch.TraceSpec(
    fn=dispatch.resolve_select_batched("coverage_gain", "auto"),
    args=(_f32(_NE, _D), _f32(_NC, _D), _f32(_B, _NE), _f32(_NE),
          _f32(_B, _NE), _f32(_B, _NC)),
    mask_args=(4, 5), row_sizes=(_NE, _NC)))

_ep("select_batched:info_gain_cond", lambda: dispatch.TraceSpec(
    fn=dispatch.resolve_select_batched("info_gain_cond", "auto"),
    args=(_f32(_B, 8, _D), _f32(_B, 8, 8), _f32(_NC, _D), _f32(_B, _NC)),
    mask_args=(3,), row_sizes=(_NC,)))

_ep("select_batched:graph_cut_gain", lambda: dispatch.TraceSpec(
    fn=dispatch.resolve_select_batched("graph_cut_gain", "auto"),
    args=(_f32(_NC, _NC), _f32(_B, _NC), _f32(_B, _NC))))

_ep("oracle:pairwise", lambda: dispatch.TraceSpec(
    fn=dispatch.resolve("pairwise", "auto"),
    args=(_f32(_AB, _D), _f32(_NC, _D))))

_ep("oracle:bound_update", lambda: dispatch.TraceSpec(
    fn=dispatch.resolve("bound_update", "auto"),
    args=(_f32(_AB, _D), _f32(_NE, _D), _f32(_AB), _f32(_NE)),
    mask_args=(2, 3), row_sizes=(_AB, _NE)))

# sieve admission is per-item (a scan over the chunk); its row-axis work is
# gather/scatter bookkeeping, not reductions, so only the taint roots matter
_ep("oracle:sieve_update", lambda: dispatch.TraceSpec(
    fn=dispatch.resolve("sieve_update", "auto"),
    args=(_f32(_AB, _D), _f32(_AB), _i32(_AB),
          jax.ShapeDtypeStruct((_AB,), jnp.bool_), _f32(4),
          _i32(4, 8), _f32(4, 8), _f32(4, 8, _D), _i32(4)),
    mask_args=(2, 3)))

_ep("oracle:flash_attention", lambda: dispatch.TraceSpec(
    fn=flash_attention, args=(_f32(1, 2, 64, _D), _f32(1, 2, 64, _D),
                              _f32(1, 2, 64, _D))))
