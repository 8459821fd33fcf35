"""Device-resident, mesh-sharded corpus block for the selection service.

The paper's GreeDi protocol assumes the data already lives on the machines;
PR 4's service instead kept the pad-and-mask block in host NumPy and re-fed
the full ``(capacity, d)`` block over H2D every epoch.  ``CorpusStore`` makes
data placement a first-class abstraction (the same move that lets
horizontally-scalable submodular maximization scale past one machine's
memory): the block's three arrays -- ``feats (capacity, d)``,
``gids (capacity,)``, and the warm-bound table -- are jax Arrays laid out
row-sharded over the service mesh (``NamedSharding(mesh, P(axis_names))``)
and never leave the devices.

Transfer accounting (what actually crosses H2D; docs/service.md):

  * ``append``  -- ONE fixed-shape chunk per ``append_block`` rows: the new
    feature rows, their gids, a validity mask, and the write offset.  A
    jitted row writer scatters them into the resident block (out-of-range /
    padding rows are dropped), so appends move O(append_block * d) bytes
    regardless of capacity and never re-trace at fixed capacity.
  * ``epoch``   -- nothing from here.  The service's compiled epoch function
    takes the resident arrays by reference; an idle epoch transfers only
    scalars (rng key, heartbeat ages, deadline).
  * growth      -- capacity doubles in place on device (pad + reshard), the
    O(log n) re-compile of the growth contract.  No host round-trip, and
    the bound table is preserved bit-exactly (tested).  Sieve state has a
    capacity-independent shape and migrates bit-exactly for free (tested).
  * ``query``   -- nothing from the corpus block: the standing sieve state
    merges on device and only the (k,) winners + scores cross D2H.
  * ``query_batch`` -- one batched merge call per query tile: the per-query
    (k, exclusion list, seed) triples cross H2D (O(B * query_mask_cap)
    ints) and the (B, k) winners + scores cross D2H; the sieve state is
    shared across all lanes of the batched merge.  The exact tier
    additionally reads the resident block (still zero H2D for it).

Select-on-append (the sieve): when the maintainer supports it (sum-form
relu tables, ``supports_sieve``), each shard additionally keeps
``n_thresholds = O(log Delta / eps)`` threshold buckets of up to
``sieve_k`` members -- fixed-shape device state row-sharded like the bound
table -- admitting new rows *inside the same fused append pass* via the
``sieve_update`` oracle.  The admission score is the redundancy-discounted
standing singleton gain (see ``kernels/ref.sieve_admit_ref``); the
geometric threshold grid tracks the running max singleton gain Delta and
re-grids by rolling buckets down when Delta grows.  ``query_sieves`` merges
the standing buckets on device (one jit, capacity-independent shapes) so a
fresh coreset is O(k) host work after any append, with no epoch run.

Warm-bound maintenance is objective-generic: the store holds a *sum-form*
bound table maintained by the objective's registered ``BoundMaintainer``
(core/objectives.py).  The ``(append_block x capacity)`` append-time pass
runs SHARDED over the mesh through the ``bound_update`` dispatch oracle --
each shard sweeps the new rows against its local block columns (the
per-column credit stays sharded; the new rows' own sums are psum-reduced) --
instead of on one device, closing the ROADMAP "distributed append" item.
Objectives without a maintainer get a store with ``maintainer=None``: the
table stays zero and the service selects cold (always exact).

Float64 without x64: the host store accumulated its table in NumPy float64
to keep f32 summation drift below the epoch slack.  jax arrays in this
process are f32 (x64 disabled), so the resident table is a **double-float
pair** ``(hi, lo)`` -- 2Sum-compensated f32 accumulation carrying ~48
mantissa bits, numerically the same guarantee, migrated exactly on growth.
Epochs consume ``hi`` (the f32 rounding is covered by the service's bound
slack, exactly as the host store's f64 -> f32 cast was).
"""
from __future__ import annotations

import numpy as np

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro import obs
from repro.core.greedi import _combined_index, _mesh_size
from repro.core.objectives import _kernel_h
from repro.kernels import autotune, dispatch
from repro.util import shard_map as _shard_map

Array = jax.Array

_NEG = -1e30   # masked-score floor of the query merge (kernels/ref.NEG)
_JTOP_COLD = -(1 << 30)  # sieve grid sentinel: no positive gain seen yet
# relative tie-break jitter of seeded queries: big enough to decorrelate
# near-equal candidates across tenants, small enough to never reorder
# admission scores with a real gap
_QUERY_JITTER = 1e-4


def _sieve_n_thresholds(sieve_k: int, eps: float) -> int:
  """Bucket count covering the SieveStreaming grid [Delta/(2k), Delta]."""
  return int(np.ceil(np.log(2 * sieve_k) / np.log1p(eps))) + 1


def _np_sim(a: np.ndarray, b: np.ndarray, kernel: str, h: float) -> np.ndarray:
  """Host-side mirror of kernels/ref._sim for the epoch-reset sieve replay."""
  a = a.astype(np.float32)
  b = b.astype(np.float32)
  if kernel == "linear":
    return a @ b.T
  d2 = np.maximum((a * a).sum(-1)[:, None] - 2.0 * (a @ b.T)
                  + (b * b).sum(-1)[None, :], 0.0)
  return np.exp(-d2 / (h * h))


def _df_add(hi: Array, lo: Array, x: Array):
  """Add f32 ``x`` into the double-float pair ``(hi, lo)``.

  2Sum (Knuth) computes the exact f32 rounding error of ``hi + x``; the
  error accumulates in ``lo`` and a Fast2Sum renormalization keeps
  ``|lo| <= ulp(hi)/2``.  The pair tracks the true sum to ~2^-48 relative
  over any realistic append history -- the device-resident stand-in for the
  host store's float64 table.
  """
  s = hi + x
  b = s - hi
  err = (hi - (s - b)) + (x - b)
  lo = lo + err
  hi2 = s + lo
  lo2 = lo - (hi2 - s)
  return hi2, lo2


class CorpusStore:
  """Device-resident pad-and-mask corpus block with maintained warm bounds.

  Args:
    mesh / axis_names: the service mesh; rows shard over the named axes.
    d: feature dimension.
    capacity: initial block capacity, rounded up to a mesh multiple;
      doubles on overflow (``append`` grows automatically, ``reserve``
      pre-grows).
    append_block: fixed chunk shape of the jitted row writer; bigger
      appends are chunked, so appends never re-trace at fixed capacity.
    kernel / kernel_kwargs / backend: similarity kernel + oracle backend
      for the maintainer's bound pass (unused when ``maintainer`` is None).
    maintainer: the objective's ``BoundMaintainer``
      (``core.objectives.bound_maintainer_for``) or None to keep no table.
    sieve_k: standing-sieve depth (bucket size / max query coreset size);
      0 disables the sieve.  Requires a maintainer with ``supports_sieve``
      (the sum-form machinery supplies the admission gains).
    sieve_eps: geometric grid ratio of the threshold sieve (1 + eps).
    query_mask_cap: fixed per-query exclusion-list capacity of the batched
      query path (tenant visibility filters pad up to it with -1, so masked
      queries never retrace).
    query_batch_tile: compiled batch width of the batched query merge;
      None consults ``kernels/autotune.query_tile``.  Ragged batches pad up
      to it and bigger batches chunk through it, so the batched merge
      compiles exactly once for the store lifetime.
    feat_dtype: storage dtype of the feature rows.
  """

  def __init__(self, mesh, *, d: int, capacity: int = 4096,
               append_block: int = 1024,
               axis_names: tuple[str, ...] = ("data",),
               kernel: str = "linear", kernel_kwargs: tuple = (),
               backend: str | None = None, maintainer=None,
               sieve_k: int = 0, sieve_eps: float = 0.5,
               query_mask_cap: int = 16,
               query_batch_tile: int | None = None,
               feat_dtype=np.float32):
    self._mesh = mesh
    self._axis_names = axis_names
    self._m = _mesh_size(mesh, axis_names)
    self._d = d
    self._append_block = append_block
    self._kernel = kernel
    self._kernel_kwargs = kernel_kwargs
    self._backend = backend
    self._maintainer = maintainer
    self._feat_dtype = feat_dtype
    self._sharding = NamedSharding(mesh, P(axis_names))

    self._cap = self._round_capacity(max(capacity, append_block))
    self._n = 0
    self._next_gid = 0
    # duplicate-id bookkeeping, host-side and O(ids the caller chose):
    # auto-allocated ids are contiguous watermark ranges (merged, so the
    # list stays tiny), explicit ids go in a set -- the default auto path
    # stores no per-id state and the check never touches the device
    self._auto_ranges: list[tuple[int, int]] = []
    self._explicit_gids: set[int] = set()
    self._growths = 0
    self._write_trace_count = 0
    self._bounds_seen = False

    self._sieve_k = 0
    self._sieve_eps = float(sieve_eps)
    if sieve_k and maintainer is not None and getattr(
        maintainer, "supports_sieve", False):
      self._sieve_k = int(sieve_k)
    self._sieve_T = (_sieve_n_thresholds(self._sieve_k, self._sieve_eps)
                     if self._sieve_k else 0)
    self._query_fn = None
    self._query_trace_count = 0
    self._query_count = 0
    self._mask_cap = int(query_mask_cap)
    self._qb_tile = (int(query_batch_tile) if query_batch_tile
                     else autotune.query_tile())
    self._query_batch_fn = None
    self._query_batch_trace_count = 0
    self._query_batch_calls = 0
    self._query_batch_queries = 0
    self._query_exact_fn = None
    self._query_exact_key = None
    self._query_exact_trace_count = 0

    self._alloc(self._cap)
    self._alloc_sieve()
    self._compile()

  # ---- placement -----------------------------------------------------------

  def _round_capacity(self, cap: int) -> int:
    """Smallest mesh multiple >= cap (the block must tile the data axes)."""
    return -(-cap // self._m) * self._m

  def _dev(self, x: np.ndarray) -> Array:
    return jax.device_put(x, self._sharding)

  def _alloc(self, cap: int) -> None:
    self._feats = self._dev(np.zeros((cap, self._d), self._feat_dtype))
    self._gids = self._dev(np.full((cap,), -1, np.int32))
    self._ub_hi = self._dev(np.zeros((cap,), np.float32))
    self._ub_lo = self._dev(np.zeros((cap,), np.float32))

  def _alloc_sieve(self) -> None:
    """Fixed-shape standing-sieve state, row-sharded like the bound table:
    (m * T, k) gid/gain blocks, (m * T, k, d) member features, per-bucket
    counts, and the per-shard running Delta / grid-top exponent.  Shapes are
    capacity-independent, so growth migrates the sieve bit-exactly by simply
    not touching it."""
    if not self._sieve_k:
      return
    m, t, k = self._m, self._sieve_T, self._sieve_k
    self._sieve_gid = self._dev(np.full((m * t, k), -1, np.int32))
    self._sieve_gain = self._dev(np.zeros((m * t, k), np.float32))
    self._sieve_feat = self._dev(np.zeros((m * t, k, self._d), np.float32))
    self._sieve_cnt = self._dev(np.zeros((m * t,), np.int32))
    self._sieve_delta = self._dev(np.zeros((m,), np.float32))
    self._sieve_jtop = self._dev(np.full((m,), _JTOP_COLD, np.int32))

  def _grow(self) -> None:
    """Double the capacity in place on device: pad each resident array and
    re-balance it over the mesh (values -- including the bound pair -- are
    copied exactly).  One of the O(log n) growth re-compiles.  Sieve state
    has capacity-independent shapes and is deliberately left untouched."""
    new_cap = self._round_capacity(self._cap * 2)
    pad = new_cap - self._cap

    def _pad(x, fill):
      widths = [(0, pad)] + [(0, 0)] * (x.ndim - 1)
      return jnp.pad(x, widths, constant_values=fill)

    # repro: allow(R4): growth migration is a sanctioned O(log n) recompile -- a fresh jit per capacity doubling, never per append
    mig = jax.jit(_pad, static_argnums=(1,), out_shardings=self._sharding)
    self._feats = mig(self._feats, 0)
    self._gids = mig(self._gids, -1)
    self._ub_hi = mig(self._ub_hi, 0)
    self._ub_lo = mig(self._ub_lo, 0)
    self._cap = new_cap
    self._growths += 1
    self._compile()

  # ---- the compiled row writer / bound pass --------------------------------

  def _compile(self) -> None:
    cap, ab = self._cap, self._append_block
    ax = self._axis_names
    mesh = self._mesh
    npp = cap // self._m
    maintainer = self._maintainer
    kernel = self._kernel
    h = _kernel_h(self._kernel_kwargs)
    backend = self._backend
    sieve_t = self._sieve_T
    log1pe = float(np.log1p(self._sieve_eps))
    sieve_op = (dispatch.resolve("sieve_update", backend or "auto")
                if self._sieve_k else None)

    def sieve_body(state, rows, rgids, mine, sums):
      """Standing-sieve update for one chunk, on this shard's local state:
      fold the chunk's (already psum-reduced) singleton gains into the
      running Delta, re-grid by rolling buckets down if the grid top moved,
      then stream the shard's own rows through ``sieve_update``.  All
      O(append_block) work; the one extra collective is the psum the bound
      pass already pays."""
      lsgid, lsgain, lsfeat, lscnt, ldelta, ljtop = state
      # Delta folds in EVERY valid chunk row (padding rows carry gid -1),
      # not just this shard's -- sums is already psum-reduced, so every
      # shard derives the same grid and the sieves stay mergeable.
      valid = rgids >= 0
      delta_new = jnp.maximum(ldelta[0],
                              jnp.max(jnp.where(valid, sums, 0.0)))
      has = delta_new > 0.0
      jtop_new = jnp.where(
          has,
          jnp.ceil(jnp.log(jnp.maximum(delta_new, 1e-30))
                   / log1pe).astype(jnp.int32),
          _JTOP_COLD)
      # Delta grew past the grid top: drop the `shift` lowest thresholds
      # (their buckets roll out) and open fresh top buckets.  Slot p holds
      # threshold (1+eps)^(jtop - (T-1) + p), so a roll by -shift keeps
      # every surviving bucket's contents exactly.
      shift = jnp.clip(jtop_new - ljtop[0], 0, sieve_t)
      cleared = jnp.arange(sieve_t) >= (sieve_t - shift)

      def _roll(x, fill):
        mask = cleared.reshape((sieve_t,) + (1,) * (x.ndim - 1))
        return jnp.where(mask, fill, jnp.roll(x, -shift, axis=0))

      lsgid = _roll(lsgid, -1)
      lsgain = _roll(lsgain, 0.0)
      lsfeat = _roll(lsfeat, 0.0)
      lscnt = _roll(lscnt, 0)
      expo = (jtop_new - (sieve_t - 1)
              + jnp.arange(sieve_t)).astype(jnp.float32)
      tau = jnp.exp(expo * log1pe)
      cnt_before = jnp.sum(lscnt)
      lsgid, lsgain, lsfeat, lscnt = sieve_op(
          rows, sums, rgids, mine & has, tau, lsgid, lsgain, lsfeat, lscnt,
          kernel=kernel, h=h)
      ldelta = jnp.full_like(ldelta, delta_new)
      ljtop = jnp.full_like(ljtop, jtop_new)
      # device-fed diagnostics (repro.obs): rows this shard offered to its
      # sieves and net bucket-count growth (admissions) this chunk
      considered = jnp.sum(mine & has).astype(jnp.int32)
      admitted = (jnp.sum(lscnt) - cnt_before).astype(jnp.int32)
      return (lsgid, lsgain, lsfeat, lscnt, ldelta, ljtop), admitted, \
          considered

    def body(lfeats, lgids, lhi, llo, *rest):
      sieve_state, (rows, rgids, rvalid, off) = rest[:-4], rest[-4:]
      # ---- shard-local row write: each shard scatters only the chunk rows
      # that land in its own slice (O(append_block) work per shard, no
      # collectives) -- the write pattern a global scatter on the sharded
      # block would otherwise turn into an O(capacity) GSPMD gather/scatter
      with jax.named_scope("store.write"):
        me = _combined_index(ax, mesh)
        pos = off + jnp.arange(ab, dtype=jnp.int32) - me * npp
        mine = (rvalid > 0) & (pos >= 0) & (pos < npp)
        widx = jnp.where(mine, pos, npp)   # out of local range -> dropped
        lfeats = lfeats.at[widx].set(rows, mode="drop")
        lgids = lgids.at[widx].set(rgids, mode="drop")
      if maintainer is not None:
        # ---- sharded (append_block x capacity) bound pass: each shard
        # sweeps the new rows against its own (already updated) block
        # columns, so the new rows' mutual/self terms are included exactly
        # once.  The per-column credit stays sharded; only the new rows'
        # own sums cross shards (one (append_block,) psum).
        with jax.named_scope("store.bound_pass"):
          lvalid = (lgids >= 0).astype(jnp.float32)
          add, sums_part = maintainer.append_update(
              rows, lfeats, rvalid, lvalid, kernel=kernel, h=h,
              backend=backend)
          if getattr(maintainer, "sums_global", False):
            # data-independent maintainers (e.g. the info-gain prior
            # bound) compute each new row's COMPLETE bound identically on
            # every shard -- a psum here would multiply it by the mesh size
            sums = sums_part
          else:
            sums = jax.lax.psum(sums_part, ax)
          lhi, llo = _df_add(lhi, llo, add)
          lhi = lhi.at[widx].set(sums, mode="drop")
          llo = llo.at[widx].set(jnp.zeros((ab,), jnp.float32), mode="drop")
      # device-fed diagnostics, UNCONDITIONAL extra (1,)-per-shard outputs
      # (the no-retrace contract of repro.obs); host reads them only when
      # obs is enabled
      admitted = jnp.zeros((1,), jnp.int32)
      considered = jnp.zeros((1,), jnp.int32)
      if maintainer is not None and sieve_state:
        # ---- standing-sieve admission rides the same pass: the psum'd
        # sums ARE the admission gains, so the sieve adds no collectives
        with jax.named_scope("store.sieve_scan"):
          sieve_state, adm, cons = sieve_body(sieve_state, rows, rgids,
                                              mine, sums)
        admitted = adm.reshape(1)
        considered = cons.reshape(1)
      return (lfeats, lgids, lhi, llo) + tuple(sieve_state) + (admitted,
                                                               considered)

    n_state = 4 + (6 if self._sieve_k else 0)
    self._n_state = n_state

    def write(*arrays_and_chunk):
      self._write_trace_count += 1  # python side effect: counts (re-)traces
      return _shard_map(
          body, mesh=mesh,
          in_specs=(P(ax),) * n_state + (P(), P(), P(), P()),
          out_specs=(P(ax),) * (n_state + 2))(*arrays_and_chunk)

    # outputs pinned to the store's row sharding: the resident block must
    # stay mesh-sharded across appends no matter what GSPMD would infer.
    # The raw body is kept for the analyzer (repro.analysis.entries).
    self._append_raw = write
    self._append_fn = jax.jit(
        write, donate_argnums=tuple(range(n_state)),
        out_shardings=(self._sharding,) * (n_state + 2))

    def gather(gids_blk, hi, q):
      eq = gids_blk[None, :] == q[:, None]          # (kq, capacity)
      hit = jnp.any(eq, axis=1)
      return jnp.where(hit, hi[jnp.argmax(eq, axis=1)], 0.0)

    # table lookup by gid for the epoch-reset sieve seeding: one jit object
    # per capacity, O(k) D2H per call
    self._gather_fn = jax.jit(gather)

  # ---- public surface ------------------------------------------------------

  @property
  def n_docs(self) -> int:
    return self._n

  @property
  def capacity(self) -> int:
    return self._cap

  @property
  def growths(self) -> int:
    return self._growths

  @property
  def write_trace_count(self) -> int:
    """Row-writer traces so far (1 per capacity: appends never re-trace)."""
    return self._write_trace_count

  @property
  def feats(self) -> Array:
    """(capacity, d) resident feature block, row-sharded over the mesh."""
    return self._feats

  @property
  def gids(self) -> Array:
    """(capacity,) resident gids; -1 rows are holes."""
    return self._gids

  @property
  def ubound_device(self) -> Array:
    """(capacity,) f32 resident bound table (the pair's ``hi`` word) -- what
    the compiled epoch function consumes (service slack covers the f32
    rounding, exactly as it covered the host store's f64 -> f32 cast)."""
    return self._ub_hi

  @property
  def ubound(self) -> np.ndarray:
    """(capacity,) float64 view of the bound table (hi + lo, exact).

    Pulls the pair to host -- diagnostics/tests only; the hot path reads
    ``ubound_device``.
    """
    return (np.asarray(self._ub_hi).astype(np.float64)
            + np.asarray(self._ub_lo).astype(np.float64))

  @property
  def bounds_populated(self) -> bool:
    """True iff the warm-bound table carries any actual signal -- i.e. a
    maintainer exists and at least one table entry is nonzero.  A cold store
    (no appends, or an all-zero corpus) reports False, so operators don't
    misread cold epochs as warm.  The one-bit device read is cached once it
    turns True (the table only ever accumulates rows)."""
    if self._maintainer is None or self._n == 0:
      return False
    if not self._bounds_seen:
      self._bounds_seen = bool(jax.device_get(jnp.any(self._ub_hi != 0.0)))
    return self._bounds_seen

  # ---- standing-sieve surface ----------------------------------------------

  @property
  def sieve_enabled(self) -> bool:
    return self._sieve_k > 0

  @property
  def sieve_k(self) -> int:
    return self._sieve_k

  @property
  def sieve_thresholds(self) -> int:
    """Bucket count T = O(log Delta / eps) (0 when the sieve is disabled)."""
    return self._sieve_T

  @property
  def sieve_state_bytes(self) -> int:
    """Device bytes held by the standing sieve across all shards."""
    if not self._sieve_k:
      return 0
    m, t, k = self._m, self._sieve_T, self._sieve_k
    return m * t * (k * 4 + k * 4 + k * self._d * 4) + m * (4 + 4 + 4)

  @property
  def query_trace_count(self) -> int:
    """Query-merge traces so far (1 total: shapes are capacity-independent,
    so growth never re-traces the query path)."""
    return self._query_trace_count

  @property
  def query_count(self) -> int:
    return self._query_count

  @property
  def query_batch_trace_count(self) -> int:
    """Batched-merge traces so far (1 total: the compiled batch shape is the
    fixed query tile and capacity-independent, so neither ragged batches nor
    growth ever re-trace the batched query path)."""
    return self._query_batch_trace_count

  @property
  def query_batch_calls(self) -> int:
    """Batched-merge device calls so far (1 per drained query tile)."""
    return self._query_batch_calls

  @property
  def query_batch_queries(self) -> int:
    """Requests answered through the batched sieve merge so far."""
    return self._query_batch_queries

  @property
  def query_exact_trace_count(self) -> int:
    """Exact-tier traces so far (1 per (capacity, k_cap): this tier scans
    the resident block, so growth legitimately retraces it)."""
    return self._query_exact_trace_count

  @property
  def query_mask_cap(self) -> int:
    """Fixed per-query exclusion-list capacity of the masked query paths."""
    return self._mask_cap

  @property
  def query_batch_tile(self) -> int:
    """Compiled batch width of the batched query paths (autotuned)."""
    return self._qb_tile

  def sieve_state_host(self):
    """Host pull of (gid, gain, feat, count, delta, jtop) -- tests only."""
    assert self._sieve_k, "sieve disabled"
    return tuple(np.asarray(x) for x in
                 (self._sieve_gid, self._sieve_gain, self._sieve_feat,
                  self._sieve_cnt, self._sieve_delta, self._sieve_jtop))

  def _compile_query(self) -> None:
    """One jit for the device-side sieve merge.  Input shapes depend only on
    (mesh, T, k, d, query_mask_cap) -- never on capacity -- so this compiles
    exactly once per store.  Every bucket of every shard pools into one
    candidate set (N = m * T * k) and a k-step greedy MMR pass re-applies
    the admission score (redundancy-discounted standing gain) over the pool
    -- at least as good as the best single threshold bucket, which carries
    the sieve guarantee.  Redundancy updates one pooled column per pick, so
    no (N, N) matrix is ever materialized.  A gid admitted into several
    buckets dedupes itself twice over: the second copy is fully redundant
    with the first (red == 1 -> score == 0) AND explicitly masked by gid
    once the first is picked -- the explicit mask is what makes dedup
    rounding-independent (see the step body).  Greedy picks are nested, so
    a caller wanting k' < k representatives takes the first k' outputs.
    Only the (k,) winners + scores leave the device.

    The body (``merge_tile``) is written over an explicit lane axis of B
    queries sharing the pool: each greedy step gathers the B lanes' picks
    as a (B, d) block and makes ONE (N x d) . (d x B) similarity call for
    all of them, so a step costs one pass over the pool whatever B is, and
    the step state is O(N * B).  The single-query merge is the same body
    at B = 1; the batched merge (``_compile_query_batch``) runs it at the
    query tile.

    Per-query parameters (all runtime arguments, so they never retrace):

      * ``kq``   -- requested coreset size; picks past it are masked to -1,
        which equals host-side slicing because greedy prefixes are nested.
      * ``excl`` -- (query_mask_cap,) int32 gid exclusion list, -1-padded
        (the tenant visibility filter; -1 pad slots only ever match hole
        candidates, which the validity mask already drops).
      * ``seed`` -- tie-break decorrelation: seed != 0 multiplies scores by
        (1 + ~1e-4 * uniform), reordering only near-equal candidates.
        seed == 0 multiplies by exactly 1.0, so default queries stay
        bitwise identical to the unseeded merge.
    """
    t, k, m = self._sieve_T, self._sieve_k, self._m
    kernel = self._kernel
    h = _kernel_h(self._kernel_kwargs)
    pairwise = dispatch.resolve("pairwise", self._backend or "auto")
    n = m * t * k

    @jax.named_scope("store.query_merge")
    def merge_tile(sgid, sgain, sfeat, kq, excl, seed):
      """(B,) kq, (B, query_mask_cap) excl, (B,) seed -> (B, k) gids and
      scores; the pool (sgid, sgain, sfeat) is shared by every lane."""
      b = kq.shape[0]
      gt = sgid.reshape(n)
      wt = sgain.reshape(n)
      ft = sfeat.reshape(n, self._d).astype(jnp.float32)
      if kernel == "linear":
        nsq = jnp.maximum(jnp.sum(ft * ft, -1), 1e-12)
      ok = (gt >= 0) & ~jnp.any(gt[None, :, None] == excl[:, None, :], axis=2)
      u = jax.vmap(lambda s: jax.random.uniform(
          jax.random.PRNGKey(s), (n,), jnp.float32))(seed)
      mult = jnp.where(seed[:, None] != 0, 1.0 + _QUERY_JITTER * u, 1.0)

      def step(i, c):
        picked, redmax, out_g, out_s = c
        score = wt * jnp.maximum(1.0 - redmax, 0.0) * mult
        # ``picked`` masks every pool slot whose gid a lane has already
        # taken: a doc admitted into several buckets must not be returned
        # twice.  The redundancy discount alone is not enough -- red == 1
        # can round to 1 +/- ulp, and under seed jitter a leftover ~ulp
        # score re-picks the copy (and does so differently in the single
        # vs batched executable).
        score = jnp.where(ok & ~picked, score, _NEG)
        j = jnp.argmax(score, axis=1).astype(jnp.int32)
        s = jnp.max(score, axis=1)
        take = (s > 0.0) & (i < kq)
        gj = gt[j]
        out_g = out_g.at[:, i].set(jnp.where(take, gj, -1))
        out_s = out_s.at[:, i].set(jnp.where(take, s, 0.0))
        picked = picked | (take[:, None] & (gt[None, :] == gj[:, None]))
        # one similarity call for the whole tile: pool x the B picked rows
        simj = pairwise(ft, ft[j], kernel=kernel, h=h).T
        if kernel == "linear":
          redj = jnp.maximum(simj, 0.0) / jnp.sqrt(nsq * nsq[j][:, None])
        else:
          redj = simj
        redmax = jnp.where(take[:, None], jnp.maximum(redmax, redj), redmax)
        return picked, redmax, out_g, out_s

      init = (jnp.zeros((b, n), bool), jnp.zeros((b, n), jnp.float32),
              jnp.full((b, k), -1, jnp.int32), jnp.zeros((b, k), jnp.float32))
      _, _, out_g, out_s = jax.lax.fori_loop(0, k, step, init)
      return out_g, out_s

    def merge_one(sgid, sgain, sfeat, kq, excl, seed):
      g, s = merge_tile(sgid, sgain, sfeat, kq[None], excl[None], seed[None])
      return g[0], s[0]

    def merge(sgid, sgain, sfeat, kq, excl, seed):
      self._query_trace_count += 1  # python side effect: counts traces
      return self._replicated(merge_one)(sgid, sgain, sfeat, kq, excl, seed)

    # raw bodies kept for the analyzer (repro.analysis.entries) and for the
    # batched compile (the same body at the query tile's lane count)
    self._merge_tile = merge_tile
    self._query_raw = merge
    self._query_fn = jax.jit(merge)

  def _compile_query_batch(self) -> None:
    """One jit for the BATCHED sieve merge: ``merge_tile`` over the
    per-query (kq, excl, seed) triples of a whole query tile, the sieve
    state shared across lanes.  Each greedy step is one (N x d) . (d x B)
    similarity call for all B lanes (O(N * B) memory), so one pass over the
    standing summaries per step answers the whole batch.  The compiled
    batch width is the fixed ``query_batch_tile`` (ragged batches pad,
    bigger batches chunk), and shapes stay capacity-independent -- the
    batched merge traces exactly once for the store lifetime
    (``query_batch_trace_count``)."""
    if self._query_fn is None:
      self._compile_query()
    merge_tile = self._merge_tile

    def merge_batch(sgid, sgain, sfeat, kq, excl, seeds):
      self._query_batch_trace_count += 1  # python side effect: trace count
      return self._replicated(merge_tile)(sgid, sgain, sfeat, kq, excl, seeds)

    # raw body kept for the analyzer (repro.analysis.entries)
    self._query_batch_raw = merge_batch
    self._query_batch_fn = jax.jit(merge_batch)

  def _replicated(self, fn):
    """Run a sieve merge whole on every device of the mesh: the standing
    state (m * T * k rows) is gathered to each one.  Pallas kernels cannot
    be partitioned by GSPMD, so on a mesh of several chips the merge's
    ``pairwise`` calls must sit inside a shard_map."""
    return _shard_map(fn, mesh=self._mesh, in_specs=(P(),) * 6,
                      out_specs=(P(), P()))

  def _full_excl(self, b: int | None = None) -> np.ndarray:
    """All -1 exclusion list(s): the 'no tenant filter' argument."""
    shape = (self._mask_cap,) if b is None else (b, self._mask_cap)
    return np.full(shape, -1, np.int32)

  def query_sieves(self, k: int | None = None, exclude_gids=None,
                   seed: int = 0):
    """Merge the standing sieves into a (sieve_k,) coreset: (gids, scores)
    as host arrays, gid -1 past the end.  O(k) D2H and no corpus-block
    access -- the merge reads ONLY the fixed-shape sieve state (tested by
    poisoning the feature block).

    ``k`` masks picks past the requested size (equal to slicing, prefixes
    are nested); ``exclude_gids`` is a pre-normalized (query_mask_cap,)
    int32 -1-padded exclusion list (tenant visibility filter); ``seed``
    applies tie-break jitter when nonzero.  All three are runtime
    arguments of the one compiled merge -- heterogeneous queries never
    retrace."""
    assert self._sieve_k, "sieve disabled on this store"
    if self._query_fn is None:
      self._compile_query()
    kq = self._sieve_k if k is None else int(k)
    excl = (self._full_excl() if exclude_gids is None
            else np.asarray(exclude_gids, np.int32))
    assert excl.shape == (self._mask_cap,), excl.shape
    gids, scores = self._query_fn(self._sieve_gid, self._sieve_gain,
                                  self._sieve_feat, jnp.int32(kq),
                                  jnp.asarray(excl), jnp.int32(seed))
    self._query_count += 1
    gids, scores = np.asarray(gids), np.asarray(scores)
    self._feed_transfer(h2d=excl.nbytes + 8, d2h=gids.nbytes + scores.nbytes)
    return gids, scores

  def query_sieves_batch(self, ks, exclude, seeds):
    """Batched sieve merge: one device call per query tile answers a whole
    heterogeneous request batch.

    Args:
      ks: (B,) int32 per-query coreset sizes.
      exclude: (B, query_mask_cap) int32 -1-padded per-query exclusion
        lists (tenant visibility filters).
      seeds: (B,) int32 per-query tie-break seeds (0 = deterministic).

    Ragged batches pad up to the compiled ``query_batch_tile`` with inert
    k=0 lanes; larger batches chunk through it.  Either way the compiled
    batch shape is fixed and capacity-independent, so the batched merge
    traces exactly once for the store lifetime.  Returns host
    (B, sieve_k) gids / scores; each lane selects exactly what the
    single-query merge selects at the same (k, excl, seed) -- scores agree
    to ~ulp only, because the batched and single merges are different XLA
    executables and may round the d-dim reductions differently (selection
    parity survives that because near-equal candidates are either the same
    gid, deduped exactly, or decorrelated by the seed jitter).
    """
    assert self._sieve_k, "sieve disabled on this store"
    if self._query_batch_fn is None:
      self._compile_query_batch()
    ks = np.asarray(ks, np.int32)
    exclude = np.asarray(exclude, np.int32)
    seeds = np.asarray(seeds, np.int32)
    b = ks.shape[0]
    assert exclude.shape == (b, self._mask_cap), exclude.shape
    assert seeds.shape == (b,), seeds.shape
    bq = self._qb_tile
    out_g, out_s = [], []
    for off in range(0, b, bq):
      with obs.span("store.query_pack"):
        kc = ks[off:off + bq]
        nb = kc.shape[0]
        pad = bq - nb
        if pad:
          kc = np.pad(kc, (0, pad))  # k = 0: padding lanes pick nothing
          ec = np.pad(exclude[off:off + bq], ((0, pad), (0, 0)),
                      constant_values=-1)
          sc = np.pad(seeds[off:off + bq], (0, pad))
        else:
          ec = exclude[off:off + bq]
          sc = seeds[off:off + bq]
        args = jnp.asarray(kc), jnp.asarray(ec), jnp.asarray(sc)
      with obs.span("store.query_call"):
        g, s = self._query_batch_fn(self._sieve_gid, self._sieve_gain,
                                    self._sieve_feat, *args)
        g, s = np.asarray(g), np.asarray(s)
      self._feed_transfer(h2d=kc.nbytes + ec.nbytes + sc.nbytes,
                          d2h=g.nbytes + s.nbytes)
      out_g.append(g[:nb])
      out_s.append(s[:nb])
      self._query_batch_calls += 1
    self._query_batch_queries += b
    return np.concatenate(out_g), np.concatenate(out_s)

  def _compile_query_exact(self, k_cap: int) -> None:
    """Exact-tier batched query: a batched greedy facility-location pass
    over the RESIDENT corpus block.  Each greedy step is ONE scan of the
    block through the ``select_batched`` facility oracle -- per-query
    coverage/visibility ride the batch axis, the feature block is shared --
    so B tenants pay one corpus scan per pick instead of B.  Shapes depend
    on (capacity, k_cap), so growth retraces this tier (its own counter;
    the sieve tier is the capacity-independent one)."""
    kernel = self._kernel
    h = _kernel_h(self._kernel_kwargs)
    backend = self._backend or "auto"
    sel_b = dispatch.resolve_select_batched("facility_gain", backend)
    pair = dispatch.resolve("pairwise", backend)

    def exact(feats, gids, kq, excl):
      self._query_exact_trace_count += 1  # python side effect: trace count
      cap = feats.shape[0]
      b = kq.shape[0]
      f32 = feats.astype(jnp.float32)
      valid = gids >= 0
      hidden = jnp.any(gids[None, :, None] == excl[:, None, :], axis=-1)
      vis = (valid[None, :] & ~hidden).astype(jnp.float32)   # (b, cap)
      nvis = jnp.sum(vis, axis=1)

      def step(i, c):
        cov, okf, out_g, out_s = c
        best, idx = sel_b(f32, f32, cov, vis, okf, kernel=kernel, h=h)
        take = (best > 0.0) & (i < kq)
        sim = pair(f32[idx], f32, kernel=kernel, h=h)        # (b, cap)
        cov = jnp.where(take[:, None], jnp.maximum(cov, sim), cov)
        picked = jnp.arange(cap)[None, :] == idx[:, None]
        okf = jnp.where(take[:, None] & picked, 0.0, okf)
        out_g = out_g.at[:, i].set(jnp.where(take, gids[idx], -1))
        out_s = out_s.at[:, i].set(jnp.where(take, best, 0.0))
        return cov, okf, out_g, out_s

      init = (jnp.zeros((b, cap), jnp.float32), vis,
              jnp.full((b, k_cap), -1, jnp.int32),
              jnp.zeros((b, k_cap), jnp.float32))
      _, _, out_g, out_s = jax.lax.fori_loop(0, k_cap, step, init)
      return out_g, out_s, nvis

    # raw body kept for the analyzer (repro.analysis.entries)
    self._query_exact_raw = exact
    self._query_exact_fn = jax.jit(exact)
    self._query_exact_key = (int(k_cap), self._cap)

  def query_exact_batch(self, ks, exclude, k_cap: int):
    """Exact-tier batched query over the resident block (facility location).

    Same request surface as ``query_sieves_batch`` minus seeds (the exact
    greedy is deterministic); returns host (B, k_cap) gids / scores plus
    the (B,) per-query visible-row counts (the value normalizer).  The
    cumulative scores are the exact greedy facility gains over each
    tenant's visible rows."""
    key = (int(k_cap), self._cap)
    if self._query_exact_fn is None or self._query_exact_key != key:
      self._compile_query_exact(int(k_cap))
    ks = np.asarray(ks, np.int32)
    exclude = np.asarray(exclude, np.int32)
    b = ks.shape[0]
    assert exclude.shape == (b, self._mask_cap), exclude.shape
    bq = self._qb_tile
    out_g, out_s, out_n = [], [], []
    for off in range(0, b, bq):
      kc = ks[off:off + bq]
      nb = kc.shape[0]
      pad = bq - nb
      if pad:
        kc = np.pad(kc, (0, pad))
        ec = np.pad(exclude[off:off + bq], ((0, pad), (0, 0)),
                    constant_values=-1)
      else:
        ec = exclude[off:off + bq]
      g, s, nv = self._query_exact_fn(self._feats, self._gids,
                                      jnp.asarray(kc), jnp.asarray(ec))
      g, s, nv = np.asarray(g), np.asarray(s), np.asarray(nv)
      self._feed_transfer(h2d=kc.nbytes + ec.nbytes,
                          d2h=g.nbytes + s.nbytes + nv.nbytes)
      out_g.append(g[:nb])
      out_s.append(s[:nb])
      out_n.append(nv[:nb])
    return (np.concatenate(out_g), np.concatenate(out_s),
            np.concatenate(out_n))

  def reset_sieves(self, sel_feats=None, sel_gids=None) -> None:
    """Epoch hand-off: clear the sieves and re-grid from the current table.

    The new Delta is the table's max standing singleton gain (one scalar
    D2H), so the grid reflects the WHOLE corpus rather than only rows seen
    since the last reset.  The epoch's selection (``sel_feats``/
    ``sel_gids``, padding filtered by the caller) seeds the fresh buckets
    through the same admission rule, replayed host-side on shard 0's slice
    with the selected rows' table entries as gains -- so a query right
    after an epoch answers with (at least) the epoch's own picks.
    """
    if not self._sieve_k:
      return
    with obs.span("store.reset_sieves"):
      self._reset_sieves(sel_feats, sel_gids)

  def _reset_sieves(self, sel_feats, sel_gids) -> None:
    m, t, k, d = self._m, self._sieve_T, self._sieve_k, self._d
    eps = self._sieve_eps
    delta = float(jax.device_get(jnp.max(self._ub_hi)))
    sgid = np.full((m * t, k), -1, np.int32)
    sgain = np.zeros((m * t, k), np.float32)
    sfeat = np.zeros((m * t, k, d), np.float32)
    scnt = np.zeros((m * t,), np.int32)
    if delta > 0.0:
      jtop = int(np.ceil(np.log(delta) / np.log1p(eps)))
      tau = np.exp((jtop - (t - 1) + np.arange(t)) * np.log1p(eps))
      if sel_feats is not None and len(sel_feats):
        sel_feats = np.asarray(sel_feats, np.float32)
        gains = self._gather_bounds(np.asarray(sel_gids, np.int32))
        kern, h = self._kernel, _kernel_h(self._kernel_kwargs)
        for v, g, gid in zip(sel_feats, gains, np.asarray(sel_gids)):
          # mirror of ref.sieve_admit_ref on shard 0's buckets
          red = np.zeros((t,), np.float32)
          for p in range(t):
            c = int(scnt[p])
            if c:
              sim = _np_sim(v[None], sfeat[p, :c], kern, h)[0]
              if kern == "linear":
                vsq = max((v.astype(np.float32) ** 2).sum(), 1e-12)
                msq = np.maximum(
                    (sfeat[p, :c].astype(np.float32) ** 2).sum(-1), 1e-12)
                sim = np.maximum(sim, 0.0) / np.sqrt(vsq * msq)
              red[p] = max(float(np.max(sim)), 0.0)
          score = float(g) * np.maximum(1.0 - red, 0.0)
          admit = (score >= tau) & (scnt[:t] < k) & (gid >= 0)
          for p in np.nonzero(admit)[0]:
            sgid[p, scnt[p]] = gid
            sgain[p, scnt[p]] = score[p]
            sfeat[p, scnt[p]] = v
            scnt[p] += 1
    else:
      jtop = _JTOP_COLD
    self._sieve_gid = self._dev(sgid)
    self._sieve_gain = self._dev(sgain)
    self._sieve_feat = self._dev(sfeat)
    self._sieve_cnt = self._dev(scnt)
    self._sieve_delta = self._dev(np.full((m,), max(delta, 0.0), np.float32))
    self._sieve_jtop = self._dev(np.full((m,), jtop, np.int32))

  def _gather_bounds(self, gids_q: np.ndarray) -> np.ndarray:
    """Table entries of the given gids (0.0 for unknown ids): O(k) D2H."""
    return np.asarray(self._gather_fn(self._gids, self._ub_hi,
                                      jnp.asarray(gids_q)))

  def reserve(self, n_total: int) -> None:
    """Pre-grow so ``n_total`` documents fit without mid-append growth."""
    while n_total > self._cap:
      self._grow()

  def _feed_transfer(self, *, h2d: int = 0, d2h: int = 0) -> None:
    """Count query-path host<->device bytes (always on; host ints only).
    One counter family spans every transfer path -- append writes, epoch
    arguments/results, and the query tiers -- so the docs/service.md
    transfer table has a live row per label."""
    xfer = obs.REGISTRY.counter("repro_transfer_bytes_total",
                                "host<->device bytes moved, by path")
    if h2d:
      xfer.inc(h2d, path="query_h2d")
    if d2h:
      xfer.inc(d2h, path="query_d2h")

  def _feed_append_metrics(self, rows_written: int,
                           h2d_bytes: int = 0) -> None:
    """Feed the always-on registry counters after one append chunk
    (host ints only; docs/observability.md)."""
    reg = obs.REGISTRY
    reg.counter("repro_append_chunks_total",
                "fixed-shape append chunks written").inc()
    reg.counter("repro_append_rows_total",
                "document rows appended").inc(rows_written)
    reg.counter("repro_transfer_bytes_total",
                "host<->device bytes moved, by path").inc(
                    h2d_bytes, path="append_h2d")
    reg.gauge("repro_store_growths", "capacity doublings so far").set(
        self._growths)

  def _feed_append_diagnostics(self, diags: list) -> None:
    """Feed the device-fed sieve counters once per ``append`` call, when
    obs is enabled.  ``diags`` holds each chunk's (admitted, considered)
    per-shard (m,) counts, left on the device while the chunks ran; they
    cross D2H here in one read, with the grid level, so enabling obs never
    stalls the chunk pipeline."""
    if not obs.enabled() or not diags:
      return
    jtop = self._sieve_jtop if self._sieve_k else None
    host, jtop = jax.device_get((diags, jtop))
    reg = obs.REGISTRY
    admissions = reg.counter("repro_sieve_admissions_total",
                             "sieve bucket admissions (device-fed)")
    rejections = reg.counter(
        "repro_sieve_rejections_total",
        "sieve rows considered but not admitted (device-fed)")
    for adm, cons in host:
      admitted = int(adm.sum())
      admissions.inc(max(admitted, 0))
      rejections.inc(max(int(cons.sum()) - admitted, 0))
    if jtop is not None and int(jtop[0]) != _JTOP_COLD:
      reg.gauge("repro_sieve_grid_level",
                "sieve threshold-grid top exponent jtop (device-fed)").set(
                    int(jtop[0]))

  def append(self, feats, gids=None) -> None:
    """Write documents into the resident block (chunked, fixed shapes).

    ``gids`` default to consecutive ids.  Explicit gids must be unique --
    within the batch and against every id already in the block: a duplicate
    would alias two documents under one id downstream (selection sets,
    trainer batch lookups) and is rejected with ``ValueError`` before any
    row is written.  The check is pure host bookkeeping (watermark ranges
    for auto ids, a set for explicit ones): no device round-trip, and no
    per-id state on the default auto path.  The bookkeeping is committed
    only after every chunk has landed, so a failed ``reserve`` (growth OOM)
    leaves the id space clean for a retry.  A device failure *mid-write*
    is not recoverable in place -- the writer donates the resident buffers
    -- and calls for the restart-and-replay path (docs/service.md).
    """
    feats = np.asarray(feats, self._feat_dtype)
    assert feats.ndim == 2 and feats.shape[1] == self._d, feats.shape
    with obs.span("store.append", rows=feats.shape[0]):
      self._append(feats, gids)

  def _append(self, feats: np.ndarray, gids) -> None:
    b = feats.shape[0]
    auto = gids is None
    if auto:
      # auto ids are allocated above the watermark: collision-free by
      # construction (explicit appends push the watermark past their max)
      start = self._next_gid
      gids = np.arange(start, start + b, dtype=np.int32)
    else:
      gids = np.asarray(gids, np.int32)
      assert gids.shape == (b,) and (gids >= 0).all(), "gids must be >= 0"
      uniq, counts = np.unique(gids, return_counts=True)
      if uniq.size != b:
        raise ValueError(
            f"duplicate gids within append: {uniq[counts > 1].tolist()}")
      # vectorized clash check, O(b log ranges + b) host work: the auto
      # ranges are disjoint and start-sorted by construction (the watermark
      # only moves up and adjacent ranges merge), so one searchsorted finds
      # each id's candidate range; explicit ids are one set intersection
      clash = set(map(int, uniq.tolist())) & self._explicit_gids
      if self._auto_ranges:
        starts = np.fromiter((s for s, _ in self._auto_ranges), np.int64,
                             len(self._auto_ranges))
        ends = np.fromiter((e for _, e in self._auto_ranges), np.int64,
                           len(self._auto_ranges))
        idx = np.searchsorted(starts, uniq, side="right") - 1
        in_auto = (idx >= 0) & (uniq < ends[np.maximum(idx, 0)])
        clash |= set(map(int, uniq[in_auto].tolist()))
      if clash:
        raise ValueError(f"gids already in the corpus: {sorted(clash)}")
    self.reserve(self._n + b)

    ab = self._append_block
    diags = []
    for off in range(0, b, ab):
      with obs.span("store.append_chunk"):
        chunk = feats[off:off + ab]
        cb = chunk.shape[0]
        pad = ab - cb
        rows = chunk if not pad else np.concatenate(
            [chunk, np.zeros((pad, self._d), self._feat_dtype)])
        rgids = gids[off:off + ab] if not pad else np.concatenate(
            [gids[off:off + ab], np.full((pad,), -1, np.int32)])
        rvalid = np.concatenate([np.ones((cb,), np.float32),
                                 np.zeros((pad,), np.float32)])
        state = [self._feats, self._gids, self._ub_hi, self._ub_lo]
        if self._sieve_k:
          state += [self._sieve_gid, self._sieve_gain, self._sieve_feat,
                    self._sieve_cnt, self._sieve_delta, self._sieve_jtop]
        out = self._append_fn(*state, rows, rgids, rvalid,
                              jnp.int32(self._n))
        self._feats, self._gids, self._ub_hi, self._ub_lo = out[:4]
        if self._sieve_k:
          (self._sieve_gid, self._sieve_gain, self._sieve_feat,
           self._sieve_cnt, self._sieve_delta,
           self._sieve_jtop) = out[4:self._n_state]
        self._n += cb
        diags.append(out[self._n_state:])
        # the writer's H2D traffic: only the fixed-shape chunk crosses (the
        # resident block is donated in place), plus the n scalar
        self._feed_append_metrics(
            cb, h2d_bytes=rows.nbytes + rgids.nbytes + rvalid.nbytes + 4)
    self._feed_append_diagnostics(diags)

    # every chunk landed: commit the id bookkeeping
    if auto:
      self._next_gid = start + b
      if b:
        if self._auto_ranges and self._auto_ranges[-1][1] == start:
          self._auto_ranges[-1] = (self._auto_ranges[-1][0], start + b)
        else:
          self._auto_ranges.append((start, start + b))
    else:
      self._explicit_gids.update(int(g) for g in gids.tolist())
      self._next_gid = max(self._next_gid, int(gids.max()) + 1 if b else 0)
