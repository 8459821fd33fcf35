"""One driver per traffic kind: set-up, the measured window, and the
comparison with the plain reference once the window has closed.

A driver is built from a cell's configuration and traffic mix (plain dicts)
and never from its name, so a new cell of an existing kind is data only.
The traffic kind, the corpus kind and the objective are found by
``registry.lookup``: the tables here first, then a file of the cell's tree
(``kinds/``, ``corpora/``, ``objectives/``; see ``bench/__init__.py``).
"""
from __future__ import annotations

import contextlib
import gc
import math
import time

import numpy as np

from bench import corpus, reference, registry, seeds, traffic

# how long the window's answers may take to come in after it closes
LATE_S = 60.0


def _facility_epoch(x, rng, m: int, cfg: dict, sel_gids, sel_feats,
                    value: float) -> dict:
  return reference.epoch_numbers(x, rng, m, int(cfg["kappa"]), sel_gids,
                                 sel_feats, value)


# objective -> the numbers of one checked epoch against its plain reference
EPOCH_REFERENCES = {"facility": _facility_epoch}
# corpus kind -> draw(cfg, n, seed): (n, d) rows on the default device and
# (n,) labels, label 0 the most common
CORPORA = {"clusters": corpus.draw}


def epoch_reference(cfg: dict, root=registry.ROOT):
  return registry.lookup(EPOCH_REFERENCES, root, "objectives",
                         cfg["objective"], "epoch_numbers")


def corpus_draw(cfg: dict, root=registry.ROOT):
  return registry.lookup(CORPORA, root, "corpora",
                         cfg["corpus"].get("kind", "clusters"), "draw")


def driver_for(kind: str, root=registry.ROOT):
  return registry.lookup(KINDS, root, "kinds", kind, "Driver")


def _service(cfg: dict, mesh, capacity: int, **kw):
  import jax.numpy as jnp
  from repro.service import SelectionService
  return SelectionService(
      mesh, d=int(cfg["d"]), kappa=int(cfg["kappa"]),
      k_final=int(cfg["k_final"]), capacity=capacity,
      kernel=cfg["kernel"],
      kernel_kwargs=tuple(sorted(cfg.get("kernel_kwargs", {}).items())),
      mode=cfg["mode"],
      warm_start=bool(cfg["warm_start"]), seed=0,
      append_block=int(cfg["append_block"]),
      feat_dtype=jnp.dtype(cfg["feat_dtype"]),
      objective=cfg["objective"], sieve=bool(cfg["sieve"]),
      query_mask_cap=int(cfg.get("query_mask_cap", 16)),
      merge=cfg.get("merge", "flat"), **kw)


def _mesh(devices, chips: int):
  from repro.util import make_mesh
  return make_mesh((chips,), ("data",), devices=devices[:chips])


def _maybe(span, name):
  """``span(name)`` (a host span of a traced run), or nothing."""
  return contextlib.nullcontext() if span is None else span(name)


def _sieves(cfg: dict, x, stored: int, state, rows_per_shard: int):
  return reference.Sieves(x, stored, int(cfg["append_block"]), state,
                          float(cfg["sieve_eps"]), rows_per_shard)


def _facility_only(cfg: dict, kind: str) -> None:
  """The bound, sieve and merge references are facility location's."""
  if cfg["objective"] != "facility":
    raise ValueError(
        f"{kind} traffic is checked against facility-location references "
        f"(bound table, sieves, merge) only; objective "
        f"{cfg['objective']!r} has none")


class Driver:
  """Shared shape of a driver; subclasses fill in the kind.  ``root`` is
  the tree the cell was read from: its files are found there."""

  def __init__(self, cfg: dict, mix: dict, seed: int, chips: int, devices,
               seconds: float | None = None, root=registry.ROOT):
    self.cfg, self.mix, self.seed, self.chips = cfg, mix, seed, chips
    self.devices = devices
    self.seconds = seconds
    self.root = root
    self.attempted = 0
    self.failed = 0
    self.counters: dict = {}


class Epochs(Driver):
  """Back-to-back selection epochs on a resident corpus, each with its own
  partition key."""

  def setup(self) -> None:
    import jax
    cfg = self.cfg
    self.epoch_numbers = epoch_reference(cfg, self.root)
    self.n = int(cfg["rows_per_chip"]) * self.chips
    xd, _ = corpus_draw(cfg, self.root)(cfg, self.n, self.seed)
    self.x = np.asarray(xd)
    del xd
    self.svc = _service(cfg, _mesh(self.devices, self.chips), self.n)
    self.svc.append(self.x)
    jax.block_until_ready(self.svc.store.ubound_device)
    self.keys = seeds.key(self.seed, seeds.EPOCH_KEYS)
    # warm-up epoch: compiles (or loads) the one epoch program
    self.svc.epoch(rng=jax.random.fold_in(self.keys, 1 << 30))
    self.results = []

  def window(self, seconds: float, span=None) -> dict:
    import jax
    t0 = time.perf_counter()
    e = 0
    rescans = 0
    walls = []
    while True:
      rng = jax.random.fold_in(self.keys, e)
      t1 = time.perf_counter()
      with _maybe(span, "bench.epoch"):
        r = self.svc.epoch(rng=rng)
      walls.append(time.perf_counter() - t1)
      raw = r.raw
      sv = np.asarray(raw.sel_valid)
      g = np.asarray(raw.sel_gids)[sv]
      f = np.asarray(raw.sel_feats, np.float32)[sv]
      keep = g >= 0
      self.results.append((rng, g[keep], f[keep], float(r.stats.value)))
      rescans += int(np.asarray(raw.r1_rescans).sum())
      e += 1
      if time.perf_counter() - t0 >= seconds:
        break
    elapsed = time.perf_counter() - t0
    self.attempted = e
    self.counters.update(epochs=e, r1_rescans_per_epoch=rescans / e,
                         epoch_wall_min_s=min(walls),
                         epoch_wall_max_s=max(walls))
    return {"epoch_s": elapsed / e}


  def free(self) -> None:
    del self.svc
    gc.collect()

  def check(self) -> dict:
    """Every number over the first epoch of the window and one drawn from
    the seed."""
    pick = {0}
    if len(self.results) > 1:
      r = seeds.rng(self.seed, seeds.CHECK_SAMPLE)
      pick.add(int(r.integers(1, len(self.results))))
    out: dict = {}
    for i in sorted(pick):
      rng, g, f, v = self.results[i]
      nums = self.epoch_numbers(self.x, rng, self.chips, self.cfg, g, f, v)
      for k, val in nums.items():
        out[k] = max(out.get(k, 0.0), val)
    self.counters["checked_epochs"] = len(pick)
    return out


class BulkAppend(Driver):
  """Append calls of ``call_rows`` into a store of fixed capacity that
  starts empty; every row is new.  Set-up draws the rows a window of
  ``--seconds`` can take at ``draw_rows_per_s`` (a few times the measured
  rate), and no more than the store holds."""

  def setup(self) -> None:
    import jax
    cfg, mix = self.cfg, self.mix
    _facility_only(cfg, "bulk_append")
    self.cap = int(cfg["capacity_per_chip"]) * self.chips
    self.call = int(mix["call_rows"])
    n = self.cap
    if self.seconds is not None and "draw_rows_per_s" in mix:
      calls = 1 + math.ceil(float(mix["draw_rows_per_s"]) * self.seconds
                            / self.call)
      n = min(n, calls * self.call)
    xd, _ = corpus_draw(cfg, self.root)(cfg, n, self.seed)
    self.x = np.asarray(xd)
    del xd
    self.svc = _service(cfg, _mesh(self.devices, self.chips), self.cap)
    self.svc.append(self.x[:self.call])
    jax.block_until_ready(self.svc.store.ubound_device)
    self.stored = self.call

  def window(self, seconds: float, span=None) -> dict:
    import jax
    t0 = time.perf_counter()
    rows = 0
    while self.stored + self.call <= self.x.shape[0]:
      with _maybe(span, "bench.append"):
        self.svc.append(self.x[self.stored:self.stored + self.call])
        jax.block_until_ready(self.svc.store.ubound_device)
      self.stored += self.call
      rows += self.call
      if time.perf_counter() - t0 >= seconds:
        break
    elapsed = time.perf_counter() - t0
    chunks = rows // int(self.cfg["append_block"])
    self.attempted = chunks
    self.counters.update(chunks=chunks, rows=rows, stored=self.stored,
                         window_s=elapsed, rows_drawn=self.x.shape[0],
                         out_of_rows=self.stored + self.call
                         > self.x.shape[0])
    return {"append_rows_per_s": rows / elapsed}


  def free(self) -> None:
    st = self.svc.store
    self.table = st.ubound
    self.sieve = st.sieve_state_host() if st.sieve_enabled else None
    del self.svc, st
    gc.collect()

  def check(self) -> dict:
    ab = int(self.cfg["append_block"])
    r = seeds.rng(self.seed, seeds.CHECK_SAMPLE)
    rows = r.choice(self.stored, size=min(int(self.mix["check_rows"]),
                                          self.stored), replace=False)
    x = self.x[:self.stored]
    out = reference.bound_numbers(x, rows, self.table, ab)
    if self.sieve is not None:
      out.update(_sieves(self.cfg, x, self.stored, self.sieve,
                         self.cap // self.chips).numbers())
    return out


class TenantQueries(Driver):
  """Multi-tenant sieve queries offered open loop through the service's
  micro-batcher, on a store filled in set-up."""

  def setup(self) -> None:
    import jax
    from repro.service import QueryRequest
    from repro.service.batching import QueryBatcher
    cfg, mix = self.cfg, self.mix
    _facility_only(cfg, "tenant_queries")
    self.cap = int(cfg["capacity_per_chip"]) * self.chips
    self.stored = int(mix["setup_rows"])
    xd, assign = corpus_draw(cfg, self.root)(cfg, self.stored, self.seed)
    self.x = np.asarray(xd)
    assign = np.asarray(assign)
    del xd
    self.svc = _service(cfg, _mesh(self.devices, self.chips), self.cap)
    self.svc.append(self.x)
    jax.block_until_ready(self.svc.store.ubound_device)
    popular = np.nonzero(assign < int(mix["excl_clusters"]))[0]
    self.tenants = traffic.tenants(mix, popular)
    self.batcher = QueryBatcher(self.svc,
                                max_delay_s=float(mix["max_delay_s"]))
    self.QueryRequest = QueryRequest
    # warm-up: one full tile and one ragged drain through the batcher
    k0, k1 = int(mix["k_min"]), int(mix["k_max"])
    warm = [self._request(i % len(self.tenants), k0 + i % (k1 - k0 + 1))
            for i in range(self.svc.store.query_batch_tile + 3)]
    for f in [self.batcher.submit(q) for q in warm]:
      f.result()
    # and one second of the window's own traffic through the open loop, so
    # no first-time cost of that path lands in the window
    burst = traffic.schedule(mix, 1.0, self.seed)
    loop = traffic.OpenLoop(burst.due_s, tick_s=float(mix["tick_s"]))
    loop.run(self.batcher.submit,
             lambda i: self._request(burst.tenant[i], burst.k[i]))
    loop.wait(LATE_S)

  def _request(self, tenant: int, k: int):
    t = self.tenants[tenant]
    return self.QueryRequest(k=int(k), seed=t.seed, exclude_gids=t.exclude)

  def window(self, seconds: float, span=None) -> dict:
    sched = traffic.schedule(self.mix, seconds, self.seed)
    self.sched = sched
    st0 = (self.batcher.stats.served, self.batcher.stats.batches)
    from repro import obs
    hist = obs.REGISTRY.histogram("repro_batcher_drain_wall_seconds")
    h0 = hist.get()
    loop = traffic.OpenLoop(sched.due_s, tick_s=float(self.mix["tick_s"]),
                            span=span)
    self.reqs = [self._request(sched.tenant[i], sched.k[i])
                 for i in range(sched.due_s.shape[0])]
    loop.run(self.batcher.submit, lambda i: self.reqs[i])
    missing = loop.wait(seconds + LATE_S)
    lat = loop.latency_s()
    h1 = hist.get()
    served = self.batcher.stats.served - st0[0]
    drains = self.batcher.stats.batches - st0[1]
    n = lat.shape[0]
    self.loop = loop
    self.attempted = n
    self.failed = missing + loop.errors
    late = loop.lateness_s
    self.counters.update(
        requests=n, drains=drains,
        occupancy=served / max(drains, 1),
        drain_ms=1e3 * (h1["sum"] - h0["sum"]) / max(h1["count"] - h0["count"],
                                                      1),
        lateness_p50_ms=1e3 * float(np.nanpercentile(late, 50)),
        lateness_p99_ms=1e3 * float(np.nanpercentile(late, 99)),
        lateness_max_ms=1e3 * float(np.nanmax(late)))
    done = lat[np.isfinite(lat)]
    # a request that never resolved misses every limit: it counts at the
    # end of the tail
    lat_all = np.where(np.isfinite(lat), lat, np.inf)
    out = {"query_p95_ms": 1e3 * float(np.percentile(lat_all, 95)),
           "query_per_s": done.shape[0] / max(float(np.nanmax(loop.done_at)),
                                              seconds)}
    return out


  def free(self) -> None:
    self.batcher.close()
    st = self.svc.store
    self.sieve = st.sieve_state_host()
    self.n_docs = st.n_docs
    del self.svc, st, self.batcher
    gc.collect()

  def check(self) -> dict:
    sv = _sieves(self.cfg, self.x, self.stored, self.sieve,
                 self.cap // self.chips)
    out = sv.numbers()
    merge = reference.Merge(sv, self.x)
    done = [i for i, f in enumerate(self.loop.futures)
            if f.done() and f.exception() is None]
    r = seeds.rng(self.seed, seeds.CHECK_SAMPLE)
    take = r.choice(len(done), size=min(int(self.mix["check_requests"]),
                                        len(done)), replace=False)
    gap, vrel, bad = 0.0, 0.0, 0
    for j in take:
      i = done[j]
      q, res = self.reqs[i], self.loop.futures[i].result()
      a, b, c = merge.numbers(q.k, q.exclude_gids, q.seed, res.sel_gids,
                              res.value_estimate, self.n_docs)
      gap, vrel, bad = max(gap, a), max(vrel, b), bad + c
    self.counters["checked_requests"] = len(take)
    out.update(merge_gap=gap, merge_value_rel=vrel,
               answer_violations=float(bad))
    return out


KINDS = {"epochs": Epochs, "bulk_append": BulkAppend,
         "tenant_queries": TenantQueries}
