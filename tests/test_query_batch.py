"""Multi-tenant batched query serving (ISSUE 8).

Parity is the whole contract: a batch of heterogeneous requests (varying
k, tie-break seed, per-tenant exclusion masks) drained through ONE
``query_batch`` call must select exactly what the same requests select
issued one-by-one through ``query()`` -- in every service state (sieve
fresh, epoch cached, post-append stale), on one device and on a 4-shard
mesh -- while the compiled-once transfer contract holds
(``query_trace_count == 1`` and ``query_batch_trace_count == 1`` for the
service lifetime).  Value estimates agree to ~ulp only: the batched merge
is a separate XLA executable of the same body, and executables may round
the d-dim reductions differently.
"""
from __future__ import annotations

import numpy as np
import pytest

from repro.service import QueryBatcher, QueryRequest, SelectionService
from repro.util import make_mesh

D, KAPPA, K = 16, 8, 8


def _service(n_docs: int = 256, seed: int = 0, **kw) -> SelectionService:
  mesh = make_mesh((1,), ("data",))
  svc = SelectionService(mesh, d=D, kappa=KAPPA, k_final=K, capacity=512,
                         seed=0, **kw)
  rng = np.random.default_rng(seed)
  feats = rng.standard_normal((n_docs, D)).astype(np.float32)
  svc.append(feats / np.linalg.norm(feats, axis=1, keepdims=True))
  return svc


def _heterogeneous(svc, b: int) -> list[QueryRequest]:
  base = svc.query()
  return [QueryRequest(k=1 + (i % K), seed=i % 3,
                       exclude_gids=tuple(int(g)
                                          for g in base.sel_gids[:i % 4]))
          for i in range(b)]


def _assert_parity(svc, reqs):
  batched = svc.query_batch(reqs)
  seq = [svc.query(r.k, seed=r.seed, exclude_gids=r.exclude_gids or None)
         for r in reqs]
  for i, (rb, rs) in enumerate(zip(batched, seq)):
    assert rb.source == rs.source, (i, rb.source, rs.source)
    np.testing.assert_array_equal(rb.sel_gids, rs.sel_gids, err_msg=str(i))
    assert np.isclose(rb.value_estimate, rs.value_estimate,
                      rtol=1e-5, atol=1e-7), (i, rb, rs)
  return batched


# ---------------------------------------------------------------------------
# batched == sequential parity, across service states
# ---------------------------------------------------------------------------


def test_batch_matches_sequential_pre_epoch():
  svc = _service()
  _assert_parity(svc, _heterogeneous(svc, 13))
  assert svc.store.query_trace_count == 1
  assert svc.store.query_batch_trace_count == 1


def test_batch_matches_sequential_across_epoch_and_append():
  """The per-request routing (epoch short-circuit vs sieve merge) must
  mirror query() exactly in every staleness state."""
  svc = _service()
  svc.epoch()
  # stale == 0: default requests ride the cached epoch answer, the rest
  # go through the sieves -- sources must still agree request-for-request
  reqs = [QueryRequest(), QueryRequest(k=3), QueryRequest(seed=5),
          QueryRequest(k=2, exclude_gids=(0, 1))]
  res = _assert_parity(svc, reqs)
  assert res[0].source == "epoch" and res[2].source == "sieve"
  rng = np.random.default_rng(7)
  svc.append(rng.standard_normal((64, D)).astype(np.float32))
  res = _assert_parity(svc, _heterogeneous(svc, 9))  # stale: all sieve
  assert all(r.source == "sieve" for r in res)
  # the whole heterogeneous run above compiled each merge exactly once
  assert svc.store.query_trace_count == 1
  assert svc.store.query_batch_trace_count == 1


def test_batch_chunks_beyond_tile():
  """Batches larger than the compiled tile chunk through it -- same
  answers, still one trace."""
  svc = _service(query_batch_tile=4)
  assert svc.store.query_batch_tile == 4
  _assert_parity(svc, _heterogeneous(svc, 11))   # 3 chunks, one ragged
  assert svc.store.query_batch_trace_count == 1
  assert svc.store.query_batch_calls == 3        # ceil(11 / 4) device calls
  assert svc.store.query_batch_queries == 11


def test_int_and_none_request_shorthand():
  svc = _service()
  res = svc.query_batch([None, 3])
  assert len(res[0].sel_gids) <= K and len(res[1].sel_gids) <= 3
  np.testing.assert_array_equal(res[1].sel_gids, res[0].sel_gids[:3])


def test_seeded_batch_never_repeats_a_gid():
  """Tie-break jitter must not re-pick a doc admitted into two buckets
  (gid-level dedup in the merge, not just the redundancy discount)."""
  svc = _service()
  rng = np.random.default_rng(3)
  dup = rng.standard_normal((4, D)).astype(np.float32)
  svc.append(np.repeat(dup, 8, axis=0))          # heavy duplication
  for seed in range(6):
    q = svc.query(seed=seed)
    assert len(set(q.sel_gids.tolist())) == len(q.sel_gids), (seed, q)


def test_request_validation():
  svc = _service()
  with pytest.raises(ValueError):
    svc.query_batch([QueryRequest(k=K + 1)])
  with pytest.raises(ValueError):
    svc.query_batch([QueryRequest(exclude_gids=(-3,))])
  with pytest.raises(ValueError):
    svc.query_batch([QueryRequest(exclude_gids=tuple(
        range(svc.store.query_mask_cap + 1)))])
  with pytest.raises(ValueError):
    svc.query_batch([QueryRequest()], tier="fast")


def test_exclusions_actually_hide_gids():
  svc = _service()
  base = svc.query()
  hide = tuple(int(g) for g in base.sel_gids[:3])
  for r in svc.query_batch([QueryRequest(exclude_gids=hide),
                            QueryRequest(seed=2, exclude_gids=hide)]):
    assert not set(hide) & set(r.sel_gids.tolist()), (hide, r.sel_gids)


# ---------------------------------------------------------------------------
# the merge over the whole tile: structure and a host reference
# ---------------------------------------------------------------------------


def _loop_similarity_calls(jaxpr):
  """(primitive, eqn) of every similarity call inside a loop body of
  ``jaxpr``: pallas_calls, and dot_generals outside any pallas kernel."""
  found = []

  def walk(j, in_loop):
    for e in j.eqns:
      name = e.primitive.name
      if in_loop and name in ("pallas_call", "dot_general"):
        found.append((name, e))
      if name == "pallas_call":
        continue                     # the kernel's own body is not a call
      for p in e.params.values():
        for sub in (p if isinstance(p, (list, tuple)) else [p]):
          inner = getattr(sub, "jaxpr", sub)
          if hasattr(inner, "eqns"):
            walk(inner, in_loop or name in ("while", "scan"))

  walk(jaxpr, False)
  return found


@pytest.mark.parametrize("backend", ["ref", "pallas"])
@pytest.mark.parametrize("kernel", ["linear", "rbf"])
def test_batched_merge_makes_one_similarity_call_per_step(kernel, backend):
  """Each greedy step of the batched merge is ONE similarity call covering
  every lane of the tile: the pool against the (B, d) picked rows -> (n, B),
  never a per-lane call (no lane axis in a pallas grid, no lane-batched
  dot_general)."""
  import jax
  import jax.numpy as jnp
  svc = _service(kernel=kernel, backend=backend, query_batch_tile=4)
  st = svc.store
  st._compile_query_batch()
  t, k, m = st.sieve_thresholds, st.sieve_k, st._m
  n, b, mc = m * t * k, st.query_batch_tile, st.query_mask_cap
  sds = jax.ShapeDtypeStruct
  jaxpr = jax.make_jaxpr(st._query_batch_raw)(
      sds((m * t, k), jnp.int32), sds((m * t, k), jnp.float32),
      sds((m * t, k, D), jnp.float32), sds((b,), jnp.int32),
      sds((b, mc), jnp.int32), sds((b,), jnp.int32))
  calls = _loop_similarity_calls(jaxpr.jaxpr)
  assert len(calls) == 1, [(nm, e.outvars[0].aval.shape) for nm, e in calls]
  name, eqn = calls[0]
  assert name == ("pallas_call" if backend == "pallas" else "dot_general")
  if name == "pallas_call":
    assert len(eqn.params["grid_mapping"].grid) == 2
    pool, picks = (v.aval.shape for v in eqn.invars)
    (out,) = (v.aval.shape for v in eqn.outvars)
    assert pool[0] >= n and picks[0] >= b and out == (pool[0], picks[0])
  else:
    assert eqn.outvars[0].aval.shape == (n, b)


def _np_merge(gid, gain, feat, kq, excl, seed, kernel, h=0.75):
  """Host greedy MMR over the pooled sieve members, float64: the merge's
  semantics written out plainly (tie-break jitter from the same seeded
  uniform draw)."""
  import jax
  n, k = gid.size, gid.shape[-1]
  g = gid.reshape(n)
  w = gain.reshape(n).astype(np.float64)
  f = feat.reshape(n, -1).astype(np.float64)
  if kernel == "linear":
    nsq = np.maximum((f * f).sum(-1), 1e-12)
    red = np.maximum(f @ f.T, 0.0) / np.sqrt(nsq[:, None] * nsq[None, :])
  else:
    sq = (f * f).sum(-1)
    d2 = np.maximum(sq[:, None] - 2.0 * f @ f.T + sq[None, :], 0.0)
    red = np.exp(-d2 / (h * h))
  mult = np.ones(n)
  if seed:
    u = np.asarray(jax.random.uniform(jax.random.PRNGKey(seed), (n,)))
    mult = 1.0 + 1e-4 * u.astype(np.float64)
  ok = (g >= 0) & ~np.isin(g, excl[excl >= 0])
  redmax = np.zeros(n)
  out_g, out_s = np.full(k, -1), np.zeros(k)
  for i in range(min(kq, k)):
    score = np.where(ok, w * np.maximum(1.0 - redmax, 0.0) * mult, -np.inf)
    j = int(np.argmax(score))
    if score[j] <= 0.0:
      break
    out_g[i], out_s[i] = g[j], score[j]
    ok &= g != g[j]
    redmax = np.maximum(redmax, red[j])
  return out_g, out_s


@pytest.mark.parametrize("kernel", ["linear", "rbf"])
def test_batched_merge_matches_host_greedy_mmr(kernel):
  """Every lane of a ragged tile -- mixed k (inert k = 0 lanes included),
  exclusions, zero and nonzero seeds -- selects what a plain host greedy
  MMR over the pooled sieve members selects, and so do the single-query
  merge and a batch of one."""
  svc = _service(n_docs=384, kernel=kernel, query_batch_tile=8)
  st = svc.store
  gid, gain, feat = st.sieve_state_host()[:3]
  base = svc.query()
  mc = st.query_mask_cap
  excl = np.full((5, mc), -1, np.int32)
  excl[1, :3] = base.sel_gids[:3]
  excl[3, :2] = base.sel_gids[2:4]
  excl[4, :1] = base.sel_gids[:1]
  ks = np.array([K, 3, 0, K, 5], np.int32)
  seeds = np.array([0, 7, 11, 2**31 - 1, 0], np.int32)
  got_g, got_s = st.query_sieves_batch(ks, excl, seeds)   # 5 of 8 lanes
  assert got_g.shape == (5, K)
  for b in range(5):
    want_g, want_s = _np_merge(gid, gain, feat, ks[b], excl[b], seeds[b],
                               kernel)
    np.testing.assert_array_equal(got_g[b], want_g, err_msg=str(b))
    np.testing.assert_allclose(got_s[b], want_s, rtol=1e-5, atol=1e-7)
  assert (got_g[2] == -1).all() and (got_s[2] == 0).all()  # k = 0: inert
  assert (got_g[0] >= 0).sum() > 3                          # a real answer
  # B = 1: the single-query merge and a one-lane batch
  want_g, want_s = _np_merge(gid, gain, feat, 6, excl[1], 5, kernel)
  one_g, one_s = st.query_sieves(6, excl[1], seed=5)
  bat_g, bat_s = st.query_sieves_batch([6], excl[1:2], [5])
  for g, s in ((one_g, one_s), (bat_g[0], bat_s[0])):
    np.testing.assert_array_equal(g, want_g)
    np.testing.assert_allclose(s, want_s, rtol=1e-5, atol=1e-7)


# ---------------------------------------------------------------------------
# satellite 1: empty sieve slots must not pollute value_estimate
# ---------------------------------------------------------------------------


def test_value_estimate_masks_empty_slots(monkeypatch):
  """query() sums scores[:k] -- slots whose gid is -1 (k exceeds the live
  winner count) must be masked out, even if a score leaks there."""
  svc = _service(n_docs=3)                        # 3 live docs, k_final=8
  orig = svc.store.query_sieves

  def poisoned(k=None, exclude_gids=None, seed=0):
    g, s = orig(k=k, exclude_gids=exclude_gids, seed=seed)
    return g, np.where(g < 0, 1e6, s)             # poison every empty slot

  monkeypatch.setattr(svc.store, "query_sieves", poisoned)
  q = svc.query()
  assert len(q.sel_gids) <= 3
  assert q.value_estimate < 1e3, q.value_estimate  # poison must not leak


# ---------------------------------------------------------------------------
# exact tier: batched greedy facility location over the resident block
# ---------------------------------------------------------------------------


def _ref_exact(feats, k, excl):
  """Host float32 greedy facility location over visible rows, mirroring
  the device step order (linear kernel, gains clamped at 0)."""
  n = len(feats)
  vis = np.array([i not in excl for i in range(n)])
  cov = np.zeros(n, np.float32)
  ok = vis.copy()
  sel = []
  for _ in range(k):
    sims = np.maximum(feats @ feats.T, 0.0).astype(np.float32)
    gains = (np.maximum(sims, cov[None, :]) - cov[None, :]) * vis[None, :]
    tot = gains.sum(axis=1) * ok
    j = int(np.argmax(tot))
    if tot[j] <= 0.0:
      break
    sel.append(j)
    ok[j] = False
    cov = np.maximum(cov, sims[j])
  return sel


def test_exact_tier_matches_reference_greedy():
  svc = _service(n_docs=48)
  reqs = [QueryRequest(k=4), QueryRequest(k=6, exclude_gids=(0, 5, 7))]
  res = svc.query_batch(reqs, tier="exact")
  feats = np.asarray(svc.store._feats, np.float32).reshape(-1, D)
  gids = np.asarray(svc.store._gids).reshape(-1)
  order = np.argsort(gids[gids >= 0])
  live = feats[gids >= 0][order]                  # rows in gid order
  for r, req in zip(res, reqs):
    assert r.source == "exact"
    want = _ref_exact(live, req.k, set(req.exclude_gids))
    np.testing.assert_array_equal(r.sel_gids, want)
  assert svc.store.query_exact_trace_count == 1


def test_exact_tier_rejects_non_facility():
  svc = _service(objective="info_gain")
  with pytest.raises(ValueError):
    svc.query_batch([QueryRequest()], tier="exact")


# ---------------------------------------------------------------------------
# micro-batcher serving loop
# ---------------------------------------------------------------------------


def test_batcher_drains_and_matches_sequential():
  svc = _service()
  reqs = _heterogeneous(svc, 10)
  seq = [svc.query(r.k, seed=r.seed, exclude_gids=r.exclude_gids or None)
         for r in reqs]
  with QueryBatcher(svc, max_batch=4, max_delay_s=0.05) as qb:
    futs = [qb.submit(r) for r in reqs]
    got = [f.result(timeout=30) for f in futs]
  for rs, rb in zip(seq, got):
    np.testing.assert_array_equal(rs.sel_gids, rb.sel_gids)
  assert qb.stats.submitted == qb.stats.served == 10
  assert qb.stats.batches >= 3                    # max_batch=4 over 10
  assert 0 < qb.stats.max_occupancy <= 4
  with pytest.raises(RuntimeError):
    qb.submit()                                   # closed


def test_batcher_propagates_request_errors():
  svc = _service()
  with QueryBatcher(svc, max_batch=2, max_delay_s=0.01) as qb:
    bad = qb.submit(QueryRequest(k=K + 5))
    with pytest.raises(ValueError):
      bad.result(timeout=30)


# ---------------------------------------------------------------------------
# 4-shard parity (subprocess: forced multi-device platform)
# ---------------------------------------------------------------------------


def test_batch_parity_four_shards(subrun):
  out = subrun("""
import numpy as np
from repro.service import QueryRequest, SelectionService
from repro.util import make_mesh

D, K = 16, 8
mesh = make_mesh((4,), ("data",))
svc = SelectionService(mesh, d=D, kappa=8, k_final=K, capacity=1024, seed=0)
rng = np.random.default_rng(0)
svc.append(rng.standard_normal((512, D)).astype(np.float32))
svc.epoch()
svc.append(rng.standard_normal((256, D)).astype(np.float32))
base = svc.query()
reqs = [QueryRequest(k=1 + (i % K), seed=i % 3,
                     exclude_gids=tuple(int(g) for g in base.sel_gids[:i % 4]))
        for i in range(11)]
batched = svc.query_batch(reqs)
seq = [svc.query(r.k, seed=r.seed, exclude_gids=r.exclude_gids or None)
       for r in reqs]
for i, (rb, rs) in enumerate(zip(batched, seq)):
    assert np.array_equal(rb.sel_gids, rs.sel_gids), (i, rb, rs)
    assert np.isclose(rb.value_estimate, rs.value_estimate,
                      rtol=1e-5, atol=1e-7), (i, rb, rs)
assert svc.store.query_trace_count == 1
assert svc.store.query_batch_trace_count == 1
print("SHARD_PARITY_OK")
""", n_devices=4)
  assert "SHARD_PARITY_OK" in out
