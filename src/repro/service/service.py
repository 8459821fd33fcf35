"""Long-lived streaming selection service: multi-epoch GreeDi over a
growing corpus (see docs/service.md).

The paper states GreeDi as a one-shot MapReduce job, but its target
workload -- exemplar selection feeding a trainer -- is repeated: every
epoch re-selects from a corpus that is still being embedded.  The service
layer splits that into two pieces:

  * **`CorpusStore`** (service/store.py) owns the *data plane*: the
    pad-and-mask ``(capacity, d)`` block lives device-resident and
    mesh-sharded, appends move only the new rows through a jitted
    fixed-chunk row writer, growth migrates buffers on device, and the
    objective's ``BoundMaintainer`` (core/objectives.py) keeps the
    warm-start bound table current with a mesh-sharded
    ``(append_block x capacity)`` pass per append chunk.
  * **`SelectionService`** (this file) is the *lifecycle orchestrator*: it
    owns the mesh, the heartbeat board, the epoch schedule, and ONE
    compiled epoch function (re-partition + the index-tracked sharded
    engine).  Every input that changes between epochs -- the resident store
    arrays, heartbeat ages, deadline, rng -- is a runtime argument, so
    epochs and appends never re-trace; an idle epoch transfers only
    scalars (the store arrays are already on the devices).  Capacity
    doubling changes the argument shapes and re-compiles at most O(log n)
    times.

Per epoch the service draws a fresh uniform partition
(``core/partition.partition_perm`` -- Barbosa-style re-randomization, which
preserves the distributed approximation guarantee across repeated runs) and
runs ``greedi_sharded(mode="lazy")``.  With a maintained bound table, round
1 is WARM-STARTED: the sum-form table divided by each shard's live count
upper-bounds every document's empty-set gain under *any* partition
(``BoundMaintainer.epoch_bounds``; validity argument in docs/service.md), so
lazy step 0 skips its full pass while the selection stays bit-identical to a
cold run -- for every objective with a registered maintainer (facility
location and saturated coverage today); objectives without one fall back to
cold lazy, which is always exact.

Straggler detection is a protocol OUTPUT: a ``HeartbeatBoard`` records
per-shard liveness, the epoch feeds heartbeat *ages* plus a deadline into
the protocol's liveness collective, and the derived mask comes back as
``GreediResult.alive`` (the Thm-10 U-holder is re-elected among alive
shards).

Determinism contract: epoch t's partition key is ``fold_in(seed, t)``, the
bound table is a pure function of the append history (deterministic device
reductions at fixed mesh), and the compiled protocol holds no cross-epoch
state -- so a restarted service that replays the same appends reproduces
the same selections bit-for-bit (tested).

Floating point: the carried bounds are only *mathematically* upper bounds;
f32 summation order differs between the incremental table and the fresh
per-epoch gain pass, so an un-inflated bound can undershoot the true gain
by an ulp-scale epsilon and stop the lazy rescan one tile early.  The
store therefore accumulates the table in a compensated double-float pair
(~f64 precision; service/store.py) and every epoch's bounds are inflated
by a small relative slack (``_BOUND_SLACK_*``) before use -- slack costs a
little pruning, never correctness, because the lazy loop verifies every
candidate it returns by rescanning its tile.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Iterator, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs
from repro.core import greedi as GD
from repro.core import objectives as O
from repro.core.objectives import NEG
from repro.core.partition import (partition_perm, permute_rows,
                                  shard_live_counts)
from repro.service.heartbeat import HeartbeatBoard
from repro.service.store import CorpusStore

Array = jax.Array

# relative / absolute inflation applied to the carried bounds each epoch,
# covering f32 summation-order noise between the incremental table and the
# fresh gain pass (measured ~1e-6 at n = 8k; slack is >> that, and gain
# GAPS in the near-duplicate selection regime are larger still)
_BOUND_SLACK_REL = 1e-3
_BOUND_SLACK_ABS = 1e-6

# named service objectives; any instance exposing the protocol surface of
# core/greedi.py (init/gains/update/value/partial_stats) works too.
# "info_gain" is constructed specially (its state carries a fixed-size
# Cholesky factor, so it needs the service's step budget as k_max).
_OBJECTIVES = {
    "facility": O.FacilityLocation,
    "saturated_coverage": O.SaturatedCoverage,
}


@dataclasses.dataclass(frozen=True)
class EpochStats:
  """Per-epoch operational stats streamed to the trainer alongside ids."""
  epoch: int            # epoch index (monotone over the service lifetime)
  n_live: int           # live documents at selection time
  capacity: int         # current pad-and-mask block capacity
  value: float          # f(selection) over the alive data
  alive: np.ndarray     # (m,) protocol-derived liveness mask
  warm: bool            # whether warm-started bounds were in effect
  wall_s: float         # wall-clock of the epoch (device-synced)
  retraces: int         # cumulative epoch-fn traces: 1 per capacity
                        # actually selected at (<= 1 + growths)


class EpochResult(NamedTuple):
  sel_gids: np.ndarray  # selected document ids, filtered (no -1 no-ops)
  stats: EpochStats
  raw: Any              # the full replicated GreediResult


class QueryResult(NamedTuple):
  """Answer of ``SelectionService.query`` -- fresh after every append.

  ``value_estimate`` is the sieve's own surrogate (sum of admission-time
  redundancy-discounted singleton gains, mean-normalized); it lower-bounds
  the selection's marginal structure but is NOT f(selection) -- compare
  selections through the objective when exactness matters (docs/service.md).
  """
  sel_gids: np.ndarray  # selected ids, filtered (no -1 padding)
  value_estimate: float  # sieve surrogate value (see above); exact f for
                         # ``source == "epoch"`` / ``"exact"`` answers
  source: str            # "sieve" (standing buckets) | "epoch" (last epoch)
                         # | "exact" (batched greedy over the corpus block)
  appends_since_epoch: int  # appends since the last epoch refinement: a
                         # "sieve" answer folds them in at sieve fidelity,
                         # an "epoch" answer does not reflect them at all
  wall_s: float          # host wall-clock of the query (for ``query_batch``
                         # answers: of the whole drained batch -- that IS
                         # each request's latency)


@dataclasses.dataclass(frozen=True)
class QueryRequest:
  """One tenant's request for ``SelectionService.query_batch``.

  ``k`` is the coreset size (None -> the service ``k_final``); ``seed``
  decorrelates tie-breaks between tenants (0 keeps the deterministic
  merge -- a default request is bitwise identical to ``query()``);
  ``exclude_gids`` is the tenant's visibility filter: document ids this
  query must never return (up to ``store.query_mask_cap`` of them).
  """
  k: int | None = None
  seed: int = 0
  exclude_gids: tuple = ()


class SelectionService:
  """Multi-epoch sharded GreeDi over a device-resident growing ground set.

  Args:
    mesh: device mesh to run the sharded protocol over.
    d: feature dimension of the corpus embeddings.
    kappa: per-machine round-1 proposals (the propose side of the
      propose/select training regime).
    k_final: coreset size per epoch.
    capacity: initial block capacity (rounded up to a mesh multiple);
      doubles on overflow, re-compiling the epoch function.
    kernel / kernel_kwargs / backend: similarity kernel and gain-oracle
      backend, as in data/selection.py.
    objective: "facility" (default), "saturated_coverage", or an objective
      instance exposing the sharded-protocol surface (init/partial_stats/
      update/value).  Warm starts engage whenever the objective has a
      registered ``BoundMaintainer`` (core/objectives.py); otherwise the
      service runs cold lazy -- selections are exact either way.
    mode: round-1 greedy mode; "lazy" (default) enables the cross-epoch
      warm start, "standard" is the fused-select path.
    warm_start: maintain the append-time bound table and thread it into
      round 1 (lazy mode + maintained objective only; selections are
      identical either way).
    deadline: liveness deadline in seconds; None disables detection (all
      heartbeats pass).
    seed: base key for the per-epoch partition/selection rng schedule.
    append_block: append chunk size; the store's row writer and bound pass
      are compiled for this fixed shape so appends never re-trace (bigger
      appends are chunked).
    query_mask_cap / query_batch_tile: multi-tenant query knobs, forwarded
      to the store -- the fixed per-query exclusion-list capacity and the
      compiled batch width of ``query_batch`` (None = autotuned).
    merge / tree_branch: epoch merge strategy (core/greedi.py): "flat"
      all_gathers all m round-1 blocks at once; "tree" runs the
      accumulation-tree merge with ``tree_branch`` children per node, so
      the peak per-shard gathered block is (b*kappa, d) per level instead
      of (m*kappa, d).  ``tree_branch = m`` reduces to flat bit-exactly.
  """

  def __init__(self, mesh, *, d: int, kappa: int, k_final: int,
               capacity: int = 4096, kernel: str = "linear",
               kernel_kwargs: tuple = (), backend: str | None = None,
               axis_names: tuple[str, ...] = ("data",), mode: str = "lazy",
               warm_start: bool = True, deadline: float | None = None,
               seed: int = 0, append_block: int = 1024,
               feat_dtype=np.float32, objective: str | Any = "facility",
               sieve: bool = True, query_mask_cap: int = 16,
               query_batch_tile: int | None = None,
               merge: str = "flat", tree_branch: int | None = None):
    self.mesh = mesh
    self._axis_names = axis_names
    self._m = GD._mesh_size(mesh, axis_names)
    self._d = d
    self._kappa = kappa
    self._k_final = k_final
    self._mode = mode
    self._deadline = deadline
    self._merge = merge
    self._tree_branch = tree_branch
    # validates merge/tree_branch eagerly (mesh must factor) and fixes the
    # peak per-shard merged-candidate block the epoch jit will gather
    self._merge_peak_rows = GD.merge_peak_rows(
        self._m, kappa, merge=merge, tree_branch=tree_branch)
    if isinstance(objective, str):
      if objective == "info_gain":
        # one state instance serves round 1 (kappa steps) and round 2 /
        # the A_max replay (k_final and kappa steps respectively)
        objective = O.InformationGain(k_max=max(kappa, k_final),
                                      kernel=kernel,
                                      kernel_kwargs=kernel_kwargs)
      elif objective in _OBJECTIVES:
        objective = _OBJECTIVES[objective](kernel=kernel,
                                           kernel_kwargs=kernel_kwargs)
      else:
        raise ValueError(f"objective {objective!r} not in "
                         f"{sorted(_OBJECTIVES) + ['info_gain']} "
                         "(or pass an instance)")
    self._objective = objective
    # the store's bound pass and the epoch protocol must match the
    # objective's configuration: similarity kernel AND oracle backend.  A
    # passed instance's ``backend`` wins whenever the service-level arg is
    # left at None (previously it was silently dropped, so the bound pass
    # could run a different oracle backend than the objective's gain loop).
    kernel = getattr(objective, "kernel", kernel)
    kernel_kwargs = getattr(objective, "kernel_kwargs", kernel_kwargs)
    if backend is None:
      backend = getattr(objective, "backend", None)
    self._backend = backend
    self._maintainer = (O.bound_maintainer_for(objective)
                        if warm_start and mode == "lazy" else None)
    self._warm = self._maintainer is not None
    self._key = jax.random.PRNGKey(seed)
    self._epoch_idx = 0
    self._trace_count = 0
    self._appends_since_epoch = 0
    self._last_epoch: EpochResult | None = None
    self.store = CorpusStore(
        mesh, d=d, capacity=capacity, append_block=append_block,
        axis_names=axis_names, kernel=kernel, kernel_kwargs=kernel_kwargs,
        backend=backend, maintainer=self._maintainer,
        sieve_k=k_final if sieve else 0, feat_dtype=feat_dtype,
        query_mask_cap=query_mask_cap, query_batch_tile=query_batch_tile)
    self.board = HeartbeatBoard(self._m)
    self._compile()

  # ---- the compiled epoch --------------------------------------------------

  def _compile(self) -> None:
    """Build the ONE epoch function.  Shapes (capacity) are read off the
    runtime arguments, so capacity growth re-traces this same jit object --
    that is the O(log n) recompile budget, counted by ``retrace_count``."""
    m = self._m
    obj = self._objective
    axis_names = self._axis_names
    warm, maintainer = self._warm, self._maintainer

    def _epoch(feats, gids, ubound, ages, deadline, rng):
      self._trace_count += 1  # python side effect: counts (re-)traces
      cap = feats.shape[0]
      npp = cap // m
      r_part, r_run = jax.random.split(rng)
      # fresh uniform partition every epoch (Barbosa-style re-randomization);
      # cap is a mesh multiple, so the perm has no padding of its own and
      # the only holes are the block's gid = -1 rows (zero features, which
      # is what the move fills them with).  Live rows move shard to shard
      # (all_to_all), never through a full copy on every device.
      perm = partition_perm(r_part, cap)
      feats_sh, gids_sh, ub_sh = permute_rows(
          (feats, gids, ubound), (0, -1, 0), perm, gids >= 0, mesh=self.mesh,
          axis_names=axis_names)
      wb = None
      if warm:
        valid_sh = gids_sh >= 0
        # sum-form corpus table -> per-shard mean-form empty-set bounds
        # (holes sort to NEG); the divide-by-live-count transform is the
        # maintainer's epoch_bounds
        nv = shard_live_counts(valid_sh, m)
        wb = jnp.where(valid_sh, ub_sh, NEG)
        wb = maintainer.epoch_bounds(wb, jnp.repeat(nv, npp))
        # slack keeps the bounds valid under f32 summation-order noise
        wb = wb * (1.0 + _BOUND_SLACK_REL) + _BOUND_SLACK_ABS
      result = GD.greedi_sharded(
          feats_sh, mesh=self.mesh, kappa=self._kappa,
          k_final=self._k_final, objective=obj, axis_names=axis_names,
          rng=r_run, backend=self._backend, gids=gids_sh, mode=self._mode,
          warm_bounds=wb, liveness_age=ages, liveness_deadline=deadline,
          merge=self._merge, tree_branch=self._tree_branch)
      # device-fed diagnostics, UNCONDITIONAL extra outputs (the no-retrace
      # contract of repro.obs): per-shard live evaluation mass under this
      # epoch's partition, and the per-shard peak merged-candidate rows the
      # merge gathered (O(b*kappa) under merge="tree" vs O(m*kappa) flat --
      # the live counterpart of the docs/service.md transfer table).  The
      # host only device_gets them when obs is enabled.
      eval_mass = jnp.sum((gids_sh >= 0).reshape(m, npp).astype(jnp.int32),
                          axis=1)
      merge_rows = jnp.full((m,), self._merge_peak_rows, jnp.int32)
      return result, eval_mass, merge_rows

    # the raw (unjitted) epoch body is the analyzer's traceable entry point
    # (repro.analysis.entries traces it with jax.make_jaxpr at store shapes)
    self._epoch_raw = _epoch
    self._epoch_fn = jax.jit(_epoch)

  # ---- public surface ------------------------------------------------------

  @property
  def n_docs(self) -> int:
    return self.store.n_docs

  @property
  def capacity(self) -> int:
    return self.store.capacity

  @property
  def warm(self) -> bool:
    """Whether warm-started bounds are active (lazy mode + a registered
    ``BoundMaintainer`` for the objective)."""
    return self._warm

  @property
  def sieve_enabled(self) -> bool:
    """Whether the store keeps standing threshold sieves (select-on-append),
    i.e. ``query`` answers fresh after every append."""
    return self.store.sieve_enabled

  @property
  def appends_since_epoch(self) -> int:
    return self._appends_since_epoch

  @property
  def objective(self):
    return self._objective

  @property
  def retrace_count(self) -> int:
    """Epoch-function traces so far (1 after the first epoch at a given
    capacity; growth adds at most O(log n) more over the lifetime)."""
    return self._trace_count

  @property
  def growths(self) -> int:
    return self.store.growths

  def append(self, feats, gids=None) -> None:
    """Grow the ground set: delegate to the device-resident store.

    Only the new rows cross H2D; when warm starts are on the store's
    maintainer runs one mesh-sharded (append_block x capacity) pass per
    chunk that (a) sets the new documents' bounds exactly and (b) credits
    their evaluation mass to every older document's bound -- the update
    that keeps the carried bounds valid (docs/service.md).  Duplicate
    explicit gids raise ``ValueError`` before anything is written.
    """
    n_before = self.store.n_docs
    self.store.append(feats, gids)
    if self.store.n_docs > n_before:
      self._appends_since_epoch += 1

  def _norm_k(self, k: int | None) -> int:
    k = self._k_final if k is None else int(k)
    if not 0 < k <= self._k_final:
      raise ValueError(f"k must be in (0, {self._k_final}], got {k}")
    return k

  def _norm_excl(self, exclude_gids) -> np.ndarray | None:
    """Tenant exclusion list -> fixed (query_mask_cap,) -1-padded int32
    array (None when the filter is empty).  The fixed pad shape is what
    keeps heterogeneously-masked queries on the one compiled merge."""
    if exclude_gids is None:
      return None
    a = np.asarray(exclude_gids, np.int32).ravel()
    if a.size == 0:
      return None
    if (a < 0).any():
      raise ValueError("exclude_gids must be >= 0")
    mc = self.store.query_mask_cap
    if a.size > mc:
      raise ValueError(
          f"at most {mc} excluded gids per query (store query_mask_cap; "
          f"got {a.size})")
    out = np.full((mc,), -1, np.int32)
    out[:a.size] = a
    return out

  def query(self, k: int | None = None, *, seed: int = 0,
            exclude_gids=None) -> QueryResult:
    """Answer "give me k representatives NOW" without running the protocol.

    Freshness contract (docs/service.md): with the standing sieve enabled
    (sum-form maintainer objectives), the answer reflects EVERY append so
    far -- the store merges its threshold buckets on device and only the
    (k,) winners cross D2H, so host work is O(k) and the corpus block is
    never touched.  When nothing was appended since the last epoch, the
    epoch's (exact-protocol) selection is returned directly.  Without a
    sieve the last epoch's selection is the best available answer (stale by
    ``appends_since_epoch`` appends).  Greedy prefixes are nested, so any
    ``k <= k_final`` reuses the same compiled merge.

    Multi-tenant parameters (docs/service.md "Multi-tenant serving"):
    ``exclude_gids`` hides up to ``store.query_mask_cap`` document ids from
    this query (per-tenant visibility filter); ``seed != 0`` decorrelates
    tie-breaks between tenants with a ~1e-4 relative score jitter.  Either
    one forces the sieve path (the cached epoch answer can't apply a
    filter), and both are runtime arguments of the one compiled merge --
    ``store.query_trace_count`` stays 1 no matter how heterogeneous the
    query stream is.
    """
    k = self._norm_k(k)
    with obs.span("service.query", k=k) as sp:
      excl = self._norm_excl(exclude_gids)
      stale = self._appends_since_epoch
      if excl is None and seed == 0 and self._last_epoch is not None and (
          stale == 0 or not self.store.sieve_enabled):
        le = self._last_epoch
        src, sel, val = "epoch", le.sel_gids[:k], float(le.stats.value)
      else:
        if not self.store.sieve_enabled:
          raise RuntimeError(
              "query() needs a standing sieve (an objective with a sum-form "
              "BoundMaintainer) or at least one completed epoch (and masked "
              "/ seeded queries always need the sieve)")
        gids, scores = self.store.query_sieves(k=k, exclude_gids=excl,
                                               seed=seed)
        slots = gids[:k]
        sel = slots[slots >= 0]
        # only live winner slots count: a slot with gid -1 is empty, and its
        # score must not pollute the estimate (k can exceed the live winners)
        val = float(scores[:k][slots >= 0].sum()) / max(self.store.n_docs, 1)
        src = "sieve"
      sp.add(tier=src, stale=stale)
    self._feed_query_metrics(src, 1, stale, sp.wall_s, path="single")
    return QueryResult(sel, val, src, stale, sp.wall_s)

  def query_batch(self, requests, tier: str = "sieve") -> list[QueryResult]:
    """Answer a whole batch of tenant requests: one device call per query
    tile instead of one per request.

    ``requests`` is a sequence of ``QueryRequest`` (plain ints are accepted
    as a k-only shorthand; None means "all defaults").  Per-request routing
    mirrors ``query()`` exactly -- default requests short-circuit to the
    cached epoch answer when nothing is stale, everything else drains
    through the batched sieve merge -- so batched answers select exactly
    what the same requests issued one-by-one select (tested; value
    estimates agree to ~ulp, the batched merge being a separate XLA
    executable of the same body).  Each result's
    ``wall_s`` is the whole drained batch's wall clock: that IS the latency
    every request in the batch observed.

    ``tier="exact"`` routes every request through the exact tier instead: a
    batched greedy facility-location pass over the resident corpus block
    (one corpus scan per pick serves all B tenants), exact per-tenant
    values over each tenant's visible rows.  Facility-location objectives
    with a fused kernel only; capacity growth retraces this tier.
    """
    if tier not in ("sieve", "exact"):
      raise ValueError(f"tier must be 'sieve' or 'exact', got {tier!r}")
    reqs = [r if isinstance(r, QueryRequest)
            else QueryRequest() if r is None else QueryRequest(k=int(r))
            for r in requests]
    with obs.span("service.query_batch", tier=tier, batch=len(reqs)) as sp:
      stale = self._appends_since_epoch
      sp.add(stale=stale)
      norm = [(self._norm_k(r.k), self._norm_excl(r.exclude_gids or None),
               int(r.seed)) for r in reqs]
      mc = self.store.query_mask_cap

      def _pack_excl(sub):
        return np.stack([e if e is not None else np.full((mc,), -1, np.int32)
                         for e in sub]) if sub else np.zeros((0, mc), np.int32)

      if tier == "exact":
        if not isinstance(self._objective, O.FacilityLocation):
          raise ValueError(
              "tier='exact' currently supports the facility-location "
              f"objective only (got {type(self._objective).__name__})")
        from repro.kernels.dispatch import FUSED_SIMS
        if getattr(self._objective, "kernel", None) not in FUSED_SIMS:
          raise ValueError("tier='exact' needs a fused similarity kernel "
                           f"({FUSED_SIMS})")
        ks = np.array([k for k, _, _ in norm], np.int32)
        ex = _pack_excl([e for _, e, _ in norm])
        g, s, nvis = self.store.query_exact_batch(ks, ex, k_cap=self._k_final)
        answers = []
        for i, (k, _, _) in enumerate(norm):
          slots = g[i, :k]
          val = float(s[i, :k][slots >= 0].sum()) / max(float(nvis[i]), 1.0)
          answers.append(("exact", slots[slots >= 0], val))
      else:
        answers = [None] * len(reqs)
        batch_idx = []
        for i, (k, excl, seed) in enumerate(norm):
          if excl is None and seed == 0 and self._last_epoch is not None and (
              stale == 0 or not self.store.sieve_enabled):
            le = self._last_epoch
            answers[i] = ("epoch", le.sel_gids[:k], float(le.stats.value))
          elif not self.store.sieve_enabled:
            raise RuntimeError(
                "query_batch() needs a standing sieve (an objective with a "
                "sum-form BoundMaintainer) or at least one completed epoch "
                "(and masked / seeded requests always need the sieve)")
          else:
            batch_idx.append(i)
        if batch_idx:
          ks = np.array([norm[i][0] for i in batch_idx], np.int32)
          ex = _pack_excl([norm[i][1] for i in batch_idx])
          sd = np.array([norm[i][2] for i in batch_idx], np.int32)
          g, s = self.store.query_sieves_batch(ks, ex, sd)
          nd = max(self.store.n_docs, 1)
          for j, i in enumerate(batch_idx):
            k = norm[i][0]
            slots = g[j, :k]
            val = float(s[j, :k][slots >= 0].sum()) / nd
            answers[i] = ("sieve", slots[slots >= 0], val)
    for src in set(a[0] for a in answers):
      self._feed_query_metrics(src, sum(1 for a in answers if a[0] == src),
                               stale, sp.wall_s, path="batch")
    return [QueryResult(sel, val, src, stale, sp.wall_s)
            for src, sel, val in answers]

  def epoch(self, rng: Array | None = None) -> EpochResult:
    """Run one selection epoch: re-partition, select, stream ids + stats.

    ``rng`` defaults to ``fold_in(seed, epoch_index)`` so a restarted
    service that replays the same appends reproduces the same schedule.
    Idle epochs transfer only the arguments built here -- heartbeat ages,
    the deadline, and the rng key; the corpus block stays device-resident.
    """
    if rng is None:
      rng = jax.random.fold_in(self._key, self._epoch_idx)
    ages = jnp.asarray(self.board.ages(), jnp.float32)
    deadline = jnp.asarray(
        np.inf if self._deadline is None else self._deadline, jnp.float32)
    # "warm" must mean warm bounds were actually THREADED with signal: a
    # configured-warm service whose table is still all zeros (cold start,
    # zero corpus) ran this epoch effectively cold -- report that, so
    # dashboards don't misread cold epochs as warm
    warm_eff = self._warm and self.store.bounds_populated
    # host->device bytes this epoch: the corpus block is device-resident,
    # so only the arguments built here cross (ages + deadline + rng key)
    h2d = int(ages.nbytes) + 4 + 8
    with obs.span("service.epoch", epoch=self._epoch_idx,
                  warm=warm_eff) as sp:
      r, eval_mass, merge_rows = self._epoch_fn(
          self.store.feats, self.store.gids, self.store.ubound_device, ages,
          deadline, rng)
      jax.block_until_ready((r, eval_mass, merge_rows))
    wall = sp.wall_s
    sv = np.asarray(r.sel_valid)
    sel_all = np.asarray(r.sel_gids)
    feats_all = np.asarray(r.sel_feats)
    d2h = sv.nbytes + sel_all.nbytes + feats_all.nbytes
    sel = sel_all[sv]
    sel_feats = feats_all[sv]
    keep = sel >= 0
    sel, sel_feats = sel[keep], sel_feats[keep]
    stats = EpochStats(epoch=self._epoch_idx, n_live=self.store.n_docs,
                       capacity=self.store.capacity, value=float(r.value),
                       alive=np.asarray(r.alive), warm=warm_eff,
                       wall_s=wall, retraces=self._trace_count)
    self._feed_epoch_metrics(stats, r, eval_mass, merge_rows,
                             h2d_bytes=h2d, d2h_bytes=d2h)
    self._epoch_idx += 1
    result = EpochResult(sel, stats, r)
    # epoch output seeds the fresh sieve grid: queries between epochs start
    # from (at least) the refined selection, and the threshold grid is
    # re-derived from the whole corpus' standing gains
    self.store.reset_sieves(sel_feats, sel)
    self._appends_since_epoch = 0
    self._last_epoch = result
    return result

  def _feed_query_metrics(self, tier: str, n: int, stale: int, wall_s: float,
                          path: str) -> None:
    reg = obs.REGISTRY
    reg.counter("repro_queries_total",
                "queries answered, by serving tier").inc(n, tier=tier)
    reg.gauge("repro_query_staleness_appends",
              "appends since the last epoch at answer time").set(stale)
    reg.histogram("repro_query_wall_seconds",
                  "query wall clock (batch: whole drained batch)").observe(
                      wall_s, path=path)

  def _feed_epoch_metrics(self, stats: EpochStats, r, eval_mass, merge_rows,
                          *, h2d_bytes: int, d2h_bytes: int) -> None:
    """Feed the metrics registry after an epoch (docs/observability.md).

    Registry updates are always on (cheap host math over already-fetched
    stats); the device-fed diagnostics -- per-shard eval mass, lazy tile
    rescans, and per-shard peak merge rows -- cross D2H only when obs is
    enabled, so the disabled service pays no extra transfers.
    """
    reg = obs.REGISTRY
    reg.counter("repro_epochs_total", "selection epochs run").inc()
    xfer = reg.counter("repro_transfer_bytes_total",
                       "host<->device bytes moved, by path")
    xfer.inc(h2d_bytes, path="epoch_h2d")
    xfer.inc(d2h_bytes, path="epoch_d2h")
    reg.histogram("repro_epoch_wall_seconds",
                  "device-synced epoch wall clock").observe(stats.wall_s)
    reg.gauge("repro_epoch_value", "f(selection) of the last epoch").set(
        stats.value)
    reg.gauge("repro_alive_shards",
              "shards the liveness collective kept last epoch").set(
                  int(stats.alive.sum()))
    reg.gauge("repro_epoch_retraces",
              "cumulative epoch-fn traces (1 per capacity)").set(
                  stats.retraces)
    reg.gauge("repro_corpus_live_docs", "live documents").set(stats.n_live)
    reg.gauge("repro_corpus_capacity", "pad-and-mask capacity").set(
        stats.capacity)
    reg.gauge("repro_epoch_warm", "1 when warm bounds carried signal").set(
        int(stats.warm))
    if not obs.enabled():
      return
    em = np.asarray(eval_mass)
    rescans = np.asarray(r.r1_rescans)
    rows = np.asarray(merge_rows)
    row_bytes = self._d * np.dtype(self.store.feats.dtype).itemsize
    for i in range(em.shape[0]):
      reg.gauge("repro_epoch_eval_mass",
                "per-shard live evaluation rows (device-fed)").set(
                    int(em[i]), shard=i)
      reg.gauge("repro_merge_peak_rows",
                "per-shard peak merged-candidate rows gathered by the "
                "epoch merge (device-fed; b*kappa tree vs m*kappa flat)"
                ).set(int(rows[i]), shard=i)
      reg.gauge("repro_merge_peak_bytes",
                "per-shard peak merged-candidate bytes (rows * d * "
                "itemsize)").set(int(rows[i]) * row_bytes, shard=i)
    reg.counter("repro_lazy_tile_rescans_total",
                "round-1 lazy tiles rescanned (device-fed)").inc(
                    int(rescans.sum()))

  def selections(self, n_epochs: int) -> Iterator[np.ndarray]:
    """Yield ``sel_gids`` for ``n_epochs`` epochs -- the iterator shape
    ``data/pipeline.batches_from_epochs`` consumes on the trainer side."""
    for _ in range(n_epochs):
      yield self.epoch().sel_gids
