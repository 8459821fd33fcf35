"""Unified observability layer (ISSUE 9): registry, spans, sidecar, and the
no-retrace contract.

The load-bearing assertions are the contract ones: with observability fully
ENABLED (trace emission + device-fed metric reads), the service's compiled
surfaces must trace exactly as often as with it disabled --
``retrace_count == 1`` per capacity and
``query_trace_count == query_batch_trace_count == 1`` for the lifetime.
The device diagnostics are unconditional extra outputs of the already-jitted
functions, so the traced program is identical either way; these tests pin
that structure.
"""
from __future__ import annotations

import json
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from repro import obs
from repro.obs.metrics import Registry
from repro.service import QueryBatcher, QueryRequest, SelectionService
from repro.service.heartbeat import HeartbeatBoard
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


def _http(url: str, data: bytes | None = None):
  with urllib.request.urlopen(url, data=data, timeout=10) as r:
    return r.status, r.read().decode()


# ---------------------------------------------------------------- registry


def test_counter_labels_and_monotonicity():
  reg = Registry()
  c = reg.counter("requests_total", "help text")
  c.inc(tier="sieve")
  c.inc(2, tier="sieve")
  c.inc(tier="exact")
  assert c.get(tier="sieve") == 3.0
  assert c.get(tier="exact") == 1.0
  assert c.get(tier="missing") == 0.0
  with pytest.raises(ValueError):
    c.inc(-1)


def test_gauge_and_histogram_semantics():
  reg = Registry()
  g = reg.gauge("alive")
  g.set(3)
  g.set(2)
  assert g.get() == 2.0
  h = reg.histogram("wall", buckets=(0.1, 1.0, 10.0))
  for v in (0.05, 0.5, 5.0, 50.0):
    h.observe(v)
  got = h.get()
  assert got["count"] == 4 and got["sum"] == pytest.approx(55.55)
  # cumulative prometheus buckets: le=0.1 -> 1, le=1 -> 2, le=10 -> 3
  assert got["buckets"] == {0.1: 1, 1.0: 2, 10.0: 3}


def test_registry_get_or_create_and_kind_mismatch():
  reg = Registry()
  assert reg.counter("x") is reg.counter("x")
  with pytest.raises(TypeError):
    reg.gauge("x")
  snap = reg.snapshot()
  assert snap["x"]["type"] == "counter"
  reg.reset()
  assert reg.snapshot() == {}


def test_prometheus_text_exposition():
  reg = Registry()
  reg.counter("hits_total", "hits").inc(5, path="/metrics")
  reg.gauge("temp").set(1.5)
  reg.histogram("lat", buckets=(0.1, 1.0)).observe(0.05)
  text = obs.prometheus_text(reg)
  assert "# TYPE hits_total counter" in text
  assert 'hits_total{path="/metrics"} 5' in text
  assert "temp 1.5" in text
  assert 'lat_bucket{le="0.1"} 1' in text
  assert 'lat_bucket{le="+Inf"} 1' in text
  assert "lat_sum 0.05" in text and "lat_count 1" in text


def test_stats_line_format():
  line = obs.stats_line("epoch", epoch=3, wall_s=0.12345, warm=True,
                        mode="service")
  assert line.startswith("epoch ")
  assert "epoch=3" in line and "warm=true" in line and "mode=service" in line
  assert "wall_s=0.1234" in line or "wall_s=0.1235" in line


def test_write_stats_json_embeds_registry(tmp_path):
  p = tmp_path / "stats.json"
  obs.write_stats_json(str(p), [{"event": "done"}], tool="test")
  payload = json.loads(p.read_text())
  assert payload["tool"] == "test"
  assert payload["stats"] == [{"event": "done"}]
  assert isinstance(payload["metrics"], dict)


# ------------------------------------------------------------------- spans


def test_span_measures_wall_even_when_disabled():
  assert not obs.enabled()
  with obs.span("unit.sleep", n=1) as sp:
    time.sleep(0.01)
  assert sp.wall_s >= 0.01


def test_span_emits_jsonl_only_when_enabled(tmp_path):
  trace = tmp_path / "trace.jsonl"
  with obs.span("unit.before"):
    pass
  obs.enable(trace_out=str(trace))
  try:
    with obs.span("unit.during", k=2) as sp:
      sp.add(extra="yes")
  finally:
    obs.disable()
  with obs.span("unit.after"):
    pass
  recs = [json.loads(l) for l in trace.read_text().splitlines()]
  assert [r["name"] for r in recs] == ["unit.during"]
  (r,) = recs
  assert set(r) == {"name", "ts", "dur_s", "pid", "tid", "parent", "attrs"}
  assert r["attrs"] == {"k": 2, "extra": "yes"}
  assert r["dur_s"] >= 0 and r["parent"] is None


def _profile(path) -> "ProfileData":
  from jax.profiler import ProfileData
  (pb,) = path.rglob("*.xplane.pb")
  return ProfileData.from_file(str(pb))


def _host_events(pd, name: str) -> list:
  return [e for p in pd.planes if p.name.startswith("/host:")
          for ln in p.lines for e in ln.events if e.name == name]


def test_span_lands_in_profiler_trace_with_obs_off(tmp_path):
  import jax
  assert not obs.enabled()
  jax.profiler.start_trace(str(tmp_path))
  try:
    with obs.span("unit.profiled"):
      time.sleep(0.002)
  finally:
    jax.profiler.stop_trace()
  (ev,) = _host_events(_profile(tmp_path), "unit.profiled")
  assert ev.duration_ns >= 2e6


def test_span_jsonl_ts_on_profiler_clock_and_parent(tmp_path):
  """A record's ``ts`` is its annotation's start in the host plane (the
  profiler's clock plus ``profile_start_time``), and ``parent`` names the
  enclosing span of the same thread."""
  import jax
  trace = tmp_path / "trace.jsonl"
  obs.enable(trace_out=str(trace))
  jax.profiler.start_trace(str(tmp_path / "prof"))
  try:
    with obs.span("unit.outer"):
      time.sleep(0.002)
      with obs.span("unit.inner"):
        time.sleep(0.002)
  finally:
    jax.profiler.stop_trace()
    obs.disable()
  recs = {r["name"]: r for r in map(json.loads,
                                    trace.read_text().splitlines())}
  assert recs["unit.outer"]["parent"] is None
  assert recs["unit.inner"]["parent"] == "unit.outer"
  pd = _profile(tmp_path / "prof")
  (start,) = [int(v) for p in pd.planes for k, v in p.stats
              if k == "profile_start_time"]
  for name in ("unit.outer", "unit.inner"):
    (ev,) = _host_events(pd, name)
    assert abs((start + ev.start_ns) * 1e-9 - recs[name]["ts"]) < 1e-3


# ----------------------------------------------------------------- sidecar


def test_sidecar_metrics_and_health_endpoints():
  t = [100.0]
  board = HeartbeatBoard(4, clock=lambda: t[0])
  reg = Registry()
  reg.counter("demo_total").inc(7)
  with obs.Sidecar(board=board, registry=reg) as sc:
    status, text = _http(sc.url + "/metrics")
    assert status == 200 and "demo_total 7" in text
    # the sidecar's own request counter shows up on the next scrape
    status, text = _http(sc.url + "/metrics")
    assert 'repro_sidecar_requests_total{method="GET",path="/metrics"}' in text
    t[0] += 2.0
    status, body = _http(sc.url + "/healthz")
    health = json.loads(body)
    assert health["status"] == "ok"
    assert health["shards"]["m"] == 4
    assert health["shards"]["ages_s"] == [2.0] * 4
    with pytest.raises(urllib.error.HTTPError):
      _http(sc.url + "/nope")


def test_sidecar_post_beat_feeds_the_same_board():
  """The out-of-band path: POST /healthz revives a shard whose pipeline
  stalled, exactly like a trainer fetch ack would."""
  t = [0.0]
  board = HeartbeatBoard(4, clock=lambda: t[0])
  with obs.Sidecar(board=board) as sc:
    board.fail(2)
    assert board.ages()[2] == np.inf
    status, body = _http(sc.url + "/healthz?shard=2", data=b"")
    assert status == 200 and json.loads(body) == {"ok": True, "shard": 2}
    assert board.ages()[2] == 0.0
    t[0] += 5.0
    # JSON-body form, shard omitted -> beats every shard
    status, _ = _http(sc.url + "/healthz", data=json.dumps({}).encode())
    assert status == 200
    assert board.ages().tolist() == [0.0] * 4
    with pytest.raises(urllib.error.HTTPError) as ei:
      _http(sc.url + "/healthz?shard=bogus", data=b"")
    assert ei.value.code == 400


def test_sidecar_without_board_rejects_beats():
  with obs.Sidecar(board=None) as sc:
    with pytest.raises(urllib.error.HTTPError) as ei:
      _http(sc.url + "/healthz", data=b"")
    assert ei.value.code == 503
    status, body = _http(sc.url + "/healthz")
    assert status == 200 and "shards" not in json.loads(body)


# ---------------------------------------------- the no-retrace contract


def test_obs_enabled_preserves_trace_counts(subrun, tmp_path):
  """THE acceptance criterion: observability fully on (span JSONL + device
  metric reads) must not change what gets traced -- the diagnostics are
  unconditional extra outputs of the same compiled programs."""
  trace = tmp_path / "svc_trace.jsonl"
  out = subrun("""
import numpy as np
from repro import obs
from repro.service import QueryRequest, SelectionService
from repro.util import make_mesh

obs.enable(trace_out={trace!r})
mesh = make_mesh((4,), ("data",))
svc = SelectionService(mesh, d=16, kappa=8, k_final=8, capacity=1024,
                       append_block=128, seed=0)
rng = np.random.default_rng(0)

def _block(n):
  f = rng.standard_normal((n, 16)).astype(np.float32)
  return f / np.linalg.norm(f, axis=1, keepdims=True)

svc.append(_block(256))
# pre-epoch: the sieve tier answers, through the batched executable
b0 = svc.query_batch([QueryRequest(k=2 + j) for j in range(5)])
assert all(r.source == "sieve" for r in b0)
for i in range(3):
  r = svc.epoch()
  q = svc.query(k=4, seed=i)
  b = svc.query_batch([QueryRequest(k=2 + j) for j in range(5)])
  svc.append(_block(64))

# the contract: one epoch trace per capacity, one query trace, one
# query_batch trace -- with obs FULLY enabled
assert svc.retrace_count == 1, svc.retrace_count
assert svc.store.query_trace_count == 1, svc.store.query_trace_count
assert svc.store.query_batch_trace_count == 1
assert svc.store.growths == 0

snap = obs.REGISTRY.snapshot()
# device-fed series made it host-side
mass = snap["repro_epoch_eval_mass"]["series"]
assert len(mass) == 4, mass                       # one gauge per shard
assert sum(s["value"] for s in mass) > 0
assert "repro_lazy_tile_rescans_total" in snap
adm = snap["repro_sieve_admissions_total"]["series"]
assert adm and adm[0]["value"] > 0, adm
assert snap["repro_epochs_total"]["series"][0]["value"] == 3
print("CONTRACT_OK")
""".format(trace=str(trace)), n_devices=4)
  assert "CONTRACT_OK" in out
  recs = [json.loads(l) for l in trace.read_text().splitlines()]
  names = [r["name"] for r in recs]
  assert names.count("service.epoch") == 3
  assert names.count("service.query") == 3
  assert names.count("service.query_batch") == 4
  for r in recs:
    assert set(r) == {"name", "ts", "dur_s", "pid", "tid", "parent",
                      "attrs"}
  epochs = [r for r in recs if r["name"] == "service.epoch"]
  assert [e["attrs"]["epoch"] for e in epochs] == [0, 1, 2]


def test_sidecar_beats_keep_stalled_shard_alive(subrun):
  """A shard whose pipeline consumer stalls stays alive as long as
  something beats its /healthz -- the sidecar feeds the SAME board as the
  fetch acks, so the liveness collective can't tell them apart."""
  out = subrun("""
import json, urllib.request
import numpy as np
from repro import obs
from repro.data.pipeline import EmbeddedCorpus, batches_from_epochs
from repro.service import SelectionService
from repro.service.heartbeat import HeartbeatBoard
from repro.util import make_mesh

t = [0.0]
mesh = make_mesh((4,), ("data",))
svc = SelectionService(mesh, d=8, kappa=4, k_final=8, capacity=256,
                       append_block=64, deadline=5.0, seed=0)
svc.board = HeartbeatBoard(4, clock=lambda: t[0])
corpus = EmbeddedCorpus(n_docs=64, feat_dim=8, vocab=64, seq_len=4)
svc.append(np.asarray(corpus.features()))

sel = np.arange(16)
streams = [batches_from_epochs(corpus, [sel] * 8, 2, 8,
                               board=svc.board, shard=i) for i in range(4)]
with obs.Sidecar(board=svc.board) as sc:
  for s in streams:
    next(s)
  # shard 3's consumer stalls; an external prober beats its /healthz
  for _ in range(3):
    t[0] += 3.0
    for s in streams[:3]:
      next(s)
    urllib.request.urlopen(sc.url + "/healthz?shard=3", data=b"",
                           timeout=10).read()
  r = svc.epoch()
  assert r.stats.alive.tolist() == [True] * 4, r.stats.alive
  # the prober stops too: now the shard really dies
  for _ in range(3):
    t[0] += 3.0
    for s in streams[:3]:
      next(s)
  r = svc.epoch()
  assert r.stats.alive.tolist() == [True, True, True, False], r.stats.alive
print("SIDECAR_LIVENESS_OK")
""", n_devices=4)
  assert "SIDECAR_LIVENESS_OK" in out


# -------------------------------------------------- batcher latency SLO


SLOW = 0.05


@pytest.fixture
def slow_worker():
  """A warmed service whose batched query first sleeps ``SLOW`` (the slow
  device worker); returns it, the wall of one real batched call, and the
  start time of each slow call."""
  svc = _service()
  svc.query()                              # warm the single-query path
  t0 = time.perf_counter()
  real = svc.query_batch
  real([QueryRequest()])                   # warm the batch path
  t_batch = time.perf_counter() - t0
  started = []

  def slow_query_batch(reqs, tier="sieve"):
    started.append(time.perf_counter())
    time.sleep(SLOW)                       # the slow worker
    return real(reqs, tier=tier)
  svc.query_batch = slow_query_batch
  return svc, t_batch, started


def test_batcher_latency_slo_under_slow_worker(slow_worker):
  """Submit-to-result latency stays bounded by max_delay plus one batch
  service time even when the device worker is slow -- the deadline drain
  fires on the clock, never waits for a full tile."""
  svc, t_batch, _ = slow_worker

  MAX_DELAY = 0.02
  reg = obs.REGISTRY
  req0 = reg.counter("repro_batcher_requests_total").get()
  bat0 = reg.counter("repro_batcher_batches_total").get()
  lats = []
  with QueryBatcher(svc, max_batch=4, max_delay_s=MAX_DELAY) as qb:
    for _ in range(12):
      t0 = time.perf_counter()
      qb.submit().result(timeout=30)
      lats.append(time.perf_counter() - t0)
  lats.sort()
  p95 = lats[int(0.95 * (len(lats) - 1))]
  # bound: the SLO deadline + one batch service time (+ scheduler slack);
  # a batcher that waited for a full tile would block until close() here
  assert p95 <= MAX_DELAY + SLOW + 3 * t_batch + 0.2, (p95, lats)

  # occupancy counters reconcile with the request count, and the registry
  # mirrors the per-instance stats
  st = qb.stats
  assert st.submitted == st.served == 12
  assert st.mean_occupancy * st.batches == pytest.approx(st.served)
  assert 1 <= st.max_occupancy <= 4
  assert reg.counter("repro_batcher_requests_total").get() - req0 == 12
  assert reg.counter("repro_batcher_batches_total").get() - bat0 == st.batches


def test_batcher_stats_reconcile_under_concurrency():
  svc = _service()
  svc.query_batch([QueryRequest()])        # warm
  with QueryBatcher(svc, max_batch=4, max_delay_s=0.02) as qb:
    futs = [qb.submit(QueryRequest(k=1 + i % K)) for i in range(10)]
    for f in futs:
      f.result(timeout=30)
  st = qb.stats
  assert st.submitted == st.served == 10
  assert st.batches >= 3                   # 10 requests, tile of 4
  assert st.mean_occupancy * st.batches == pytest.approx(st.served)
  assert st.max_occupancy <= 4


def test_batcher_queue_wait_counts_the_drain_in_flight(slow_worker):
  """A request submitted while a slow drain runs waits at least the rest of
  that drain, and ``repro_batcher_queue_wait_seconds`` records it."""
  import threading
  svc, _, started = slow_worker
  hist = obs.REGISTRY.histogram("repro_batcher_queue_wait_seconds")
  slow = svc.query_batch
  first = {}
  in_drain = threading.Event()

  def watched(reqs, tier="sieve"):
    if not in_drain.is_set():
      first["hist"] = hist.get()           # the first request's wait is in
      in_drain.set()
    return slow(reqs, tier=tier)
  svc.query_batch = watched
  with QueryBatcher(svc, max_batch=4, max_delay_s=0.02) as qb:
    fa = qb.submit()
    assert in_drain.wait(30)
    fb = qb.submit()
    t_b = time.perf_counter()              # after b's enqueue stamp
    fa.result(timeout=30)
    fb.result(timeout=30)
  remaining = started[0] + SLOW - t_b
  assert remaining > SLOW / 2, remaining   # b arrived mid-drain
  got = hist.get()
  assert got["count"] - first["hist"]["count"] == 1
  assert got["sum"] - first["hist"]["sum"] >= remaining


# ------------------------------------------ named scopes, append diagnostics


def _compiled_entries(svc):
  """Compiled HLO text of the epoch, append and batched-merge programs."""
  import jax
  import jax.numpy as jnp
  st = svc.store
  svc.query_batch([QueryRequest(k=3, seed=1)])
  ages = jnp.asarray(svc.board.ages(), jnp.float32)
  epoch = svc._epoch_fn.lower(st.feats, st.gids, st.ubound_device, ages,
                              jnp.float32(np.inf), jax.random.PRNGKey(0))
  ab = 128
  state = [st._feats, st._gids, st._ub_hi, st._ub_lo, st._sieve_gid,
           st._sieve_gain, st._sieve_feat, st._sieve_cnt, st._sieve_delta,
           st._sieve_jtop]
  append = st._append_fn.lower(*state, np.zeros((ab, D), np.float32),
                               np.zeros((ab,), np.int32),
                               np.zeros((ab,), np.float32), jnp.int32(0))
  bq, mc = st.query_batch_tile, st.query_mask_cap
  merge = st._query_batch_fn.lower(
      st._sieve_gid, st._sieve_gain, st._sieve_feat,
      jnp.zeros((bq,), jnp.int32), jnp.zeros((bq, mc), jnp.int32),
      jnp.zeros((bq,), jnp.int32))
  return {k: v.compile().as_text()
          for k, v in (("epoch", epoch), ("append", append),
                       ("merge", merge))}


def _n_instructions(hlo: str) -> int:
  import re
  return sum(1 for line in hlo.splitlines()
             if re.match(r"\s*(ROOT\s+)?%[\w.\-]+ = ", line))


def test_named_scopes_reach_the_hlo_and_change_no_instruction(monkeypatch):
  """The GreeDi rounds and the store passes carry their scope in the
  compiled HLO's op_name metadata, and the same programs compiled with
  every scope taken out have exactly as many instructions."""
  import contextlib
  import re
  import jax
  scoped = _compiled_entries(_service(append_block=128))
  for name, scope in (("epoch", "greedi.partition"),
                      ("epoch", "greedi.round1"), ("epoch", "greedi.merge"),
                      ("epoch", "greedi.round2"),
                      ("append", "store.bound_pass"),
                      ("append", "store.sieve_scan"),
                      ("append", "store.write"),
                      ("merge", "store.query_merge")):
    assert re.search(rf"[/(]{scope}[/)]", scoped[name]), (name, scope)
  monkeypatch.setattr(jax, "named_scope",
                      contextlib.contextmanager(lambda name: (yield)))
  bare = _compiled_entries(_service(append_block=128))
  for name in scoped:
    assert "greedi." not in bare[name].replace("greedi.py", "")
    assert _n_instructions(scoped[name]) == _n_instructions(bare[name])


def _epoch_hlo(svc) -> str:
  import jax
  import jax.numpy as jnp
  st = svc.store
  ages = jnp.asarray(svc.board.ages(), jnp.float32)
  return svc._epoch_fn.lower(st.feats, st.gids, st.ubound_device, ages,
                             jnp.float32(np.inf), jax.random.PRNGKey(0)
                             ).compile().as_text()


@pytest.mark.parametrize("mode", ["standard", "lazy"])
def test_info_gain_scopes_reach_the_epoch_hlo(monkeypatch, mode):
  """An info-gain epoch carries the oracle sweep and the Cholesky upkeep
  in their own scopes, nested in the GreeDi rounds, and compiles to as
  many instructions without them."""
  import contextlib
  import re
  import jax
  kw = dict(objective="info_gain", kernel="rbf", mode=mode, sieve=False)
  scoped = _epoch_hlo(_service(**kw))
  for outer in ("greedi.round1", "greedi.round2"):
    for scope in ("info_gain.oracle", "info_gain.update"):
      assert re.search(rf"{outer}/[^\"]*\b{scope}[/)]", scoped), (
          outer, scope)
  monkeypatch.setattr(jax, "named_scope",
                      contextlib.contextmanager(lambda name: (yield)))
  bare = _epoch_hlo(_service(**kw))
  assert "info_gain." not in bare.replace("info_gain.py", "")
  assert _n_instructions(scoped) == _n_instructions(bare)


def test_append_reads_sieve_diagnostics_once_per_call(monkeypatch):
  """With obs on, one append of several chunks crosses D2H once for its
  device-fed diagnostics, and its counters end equal to the sums of the
  same chunks appended one call each."""
  import jax
  rng = np.random.default_rng(3)
  feats = rng.standard_normal((512, D)).astype(np.float32)
  feats /= np.linalg.norm(feats, axis=1, keepdims=True)
  reg = obs.REGISTRY
  names = ("repro_sieve_admissions_total", "repro_sieve_rejections_total")

  def run(calls):
    svc = SelectionService(make_mesh((1,), ("data",)), d=D, kappa=KAPPA,
                           k_final=K, capacity=1024, append_block=128,
                           seed=0)
    svc.append(feats[:128])                # compile outside the count
    before = [reg.counter(n).get() for n in names]
    reads = []
    real = jax.device_get
    monkeypatch.setattr(jax, "device_get",
                        lambda x: reads.append(1) or real(x))
    for rows in np.split(feats[128:], calls):
      svc.append(rows)
    monkeypatch.setattr(jax, "device_get", real)
    return ([reg.counter(n).get() - b for n, b in zip(names, before)],
            reg.gauge("repro_sieve_grid_level").get(), len(reads))

  obs.enable()
  try:
    once, level_once, reads_once = run(1)    # 3 chunks in one call
    each, level_each, reads_each = run(3)    # one chunk per call
  finally:
    obs.disable()
  assert reads_once == 1 and reads_each == 3
  assert once == each and once[0] > 0
  assert level_once == level_each
