"""The streaming selection service (ISSUE 4): warm-started epoch bounds,
pad-and-mask growth, protocol-side straggler detection, and the U-holder
re-election fix.

Layers:

  * greedy-level: warm_bounds makes mode="lazy" skip the step-0 full pass
    but stays bit-identical to the cold run, for every monotone objective
    (and for deliberately loose / +inf bounds -- looser bounds cost
    rescans, never correctness);
  * protocol-level (subprocess meshes): the liveness collective derives the
    straggler mask (== an explicit straggler_keep run), the Thm-10 U-holder
    is re-elected among alive shards, holes (gids = -1) are immaterial;
  * service-level: restart determinism (same seed + same appends ==> same
    selections), warm == cold, and the 4-shard acceptance run (>= 3 epochs,
    append between, killed shard in the last, no re-trace, warm >= 1.3x
    cold on the near-dup corpus).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import objectives as O
from repro.core.greedy import greedy
from repro.service.heartbeat import HeartbeatBoard

jax.config.update("jax_platform_name", "cpu")


def _feats(seed, n, d):
  f = jax.random.normal(jax.random.PRNGKey(seed), (n, d))
  return f / jnp.linalg.norm(f, axis=1, keepdims=True)


# ---------------------------------------------------------------------------
# heartbeat board
# ---------------------------------------------------------------------------


def test_heartbeat_board_ages_and_fail():
  t = [100.0]
  board = HeartbeatBoard(4, clock=lambda: t[0])
  t[0] = 107.0
  np.testing.assert_allclose(board.ages(), [7.0] * 4)
  board.beat(2)
  t[0] = 110.0
  np.testing.assert_allclose(board.ages(), [10.0, 10.0, 3.0, 10.0])
  board.fail(1)
  ages = board.ages()
  assert np.isinf(ages[1]) and ages[1] > 0
  board.beat()  # global beat revives everyone
  np.testing.assert_allclose(board.ages(), [0.0] * 4)


# ---------------------------------------------------------------------------
# heartbeats from a real transport: the data pipeline's fetch cadence
# ---------------------------------------------------------------------------


def test_pipeline_fetch_beats_heartbeat():
  """Every batch a consumer fetches acks its shard's liveness on the board
  (ISSUE-5 satellite: HeartbeatBoard wired to a real signal)."""
  from repro.data.pipeline import EmbeddedCorpus, batches_from_epochs
  corpus = EmbeddedCorpus(n_docs=32, feat_dim=8, vocab=64, seq_len=4)
  t = [100.0]
  board = HeartbeatBoard(2, clock=lambda: t[0])
  sel = np.arange(16)
  g = batches_from_epochs(corpus, [sel, sel], 2, 3, board=board, shard=1)
  t[0] = 150.0
  next(g)
  ages = board.ages()
  assert ages[1] == 0.0 and ages[0] == 50.0   # only the consuming shard acks
  t[0] = 170.0
  next(g)
  np.testing.assert_allclose(board.ages(), [70.0, 0.0])
  # a consumer for the whole stream (shard=None) acks every shard
  g_all = batches_from_epochs(corpus, [sel], 2, 1, board=board)
  next(g_all)
  np.testing.assert_allclose(board.ages(), [0.0, 0.0])


def test_stalled_consumer_trips_liveness_collective(subrun):
  """A trainer shard that stops pulling batches stops beating; its age
  crosses the deadline and the next epoch's liveness collective masks it
  out (EpochStats.alive) -- no operator-supplied straggler mask anywhere."""
  out = subrun("""
import numpy as np, jax, jax.numpy as jnp
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
for s in streams:            # every shard's consumer fetches: all beat
  next(s)
t[0] += 1.0
r = svc.epoch()
assert r.stats.alive.tolist() == [True] * 4, r.stats.alive
# shard 3's consumer stalls; the rest keep fetching while time passes
for _ in range(3):
  t[0] += 3.0
  for s in streams[:3]:
    next(s)
r = svc.epoch()
assert r.stats.alive.tolist() == [True, True, True, False], r.stats.alive
assert len(r.sel_gids) == 8
# the stalled consumer resumes fetching: its next ack revives it
next(streams[3])
r = svc.epoch()
assert r.stats.alive.tolist() == [True] * 4, r.stats.alive
print("STALL_OK")
""", n_devices=4)
  assert "STALL_OK" in out


# ---------------------------------------------------------------------------
# warm-started lazy bounds: bit-identical on every monotone objective
# ---------------------------------------------------------------------------


def _monotone_cases():
  f = _feats(5, 220, 12)
  fa = jnp.abs(f)
  fl = O.FacilityLocation(kernel="linear")
  flr = O.FacilityLocation(kernel="rbf", kernel_kwargs=(("h", 1.0),))
  ig = O.InformationGain(k_max=6, kernel="rbf", kernel_kwargs=(("h", 0.75),),
                         sigma=0.7)
  cov = O.SaturatedCoverage(kernel="linear", alpha=0.25)
  mod = O.Modular()
  w = jnp.abs(jax.random.normal(jax.random.PRNGKey(9), (12,)))
  return {
      "facility_linear": (fl, fl.init(f), f, 8),
      "facility_rbf": (flr, flr.init(f), f, 8),
      "information_gain": (ig, ig.init_d(12), f, 6),
      "coverage": (cov, cov.init(fa), fa, 8),
      "modular": (mod, mod.init_w(w), f, 8),
  }


_MONOTONE = ["facility_linear", "facility_rbf", "information_gain",
             "coverage", "modular"]


@pytest.mark.parametrize("name", _MONOTONE)
def test_warm_lazy_bit_identical_to_cold(name):
  """Epoch warm start at the greedy level: seeding mode="lazy" with the
  previous epoch's (= exact empty-set) gains, with LOOSE over-estimates,
  and with +inf (unseen items) all reproduce the cold selection exactly."""
  obj, st0, feats, k = _monotone_cases()[name]
  cold = greedy(obj, st0, feats, k, mode="lazy")
  exact0 = obj.gains(st0, feats).astype(jnp.float32)
  bounds = {
      "carried": exact0,                        # epoch t's step-0 gains
      "loose": exact0 + 0.37,                   # stale-but-valid over-estimate
      "fresh_items": jnp.full_like(exact0, jnp.inf),   # appended docs
      "mixed": jnp.where(jnp.arange(220) % 3 == 0, jnp.inf, exact0 + 0.1),
  }
  for label, wb in bounds.items():
    warm = greedy(obj, st0, feats, k, mode="lazy", warm_bounds=wb)
    assert np.asarray(warm.idx).tolist() == np.asarray(cold.idx).tolist(), \
        (name, label)
    np.testing.assert_allclose(np.asarray(warm.gains),
                               np.asarray(cold.gains), rtol=1e-5, atol=1e-6,
                               err_msg=f"{name}/{label}")
    np.testing.assert_allclose(np.asarray(warm.values),
                               np.asarray(cold.values), rtol=1e-5, atol=1e-6)


def test_warm_lazy_nonmonotone_falls_back():
  """Non-monotone objectives silently fall back to standard; warm bounds
  are ignored there and the result still matches."""
  w = jnp.abs(jax.random.normal(jax.random.PRNGKey(6), (64, 64)))
  cut = O.GraphCut()
  st0 = cut.init_w(w)
  onehot = jnp.eye(64)
  a = greedy(cut, st0, onehot, 10, mode="standard", stop_nonpositive=True)
  b = greedy(cut, st0, onehot, 10, mode="lazy", stop_nonpositive=True,
             warm_bounds=jnp.full((64,), jnp.inf))
  assert np.asarray(a.idx).tolist() == np.asarray(b.idx).tolist()


# ---------------------------------------------------------------------------
# protocol level: liveness collective + U-holder re-election (subprocess)
# ---------------------------------------------------------------------------


def test_liveness_collective_equals_explicit_mask(subrun):
  """The protocol-derived straggler mask (heartbeat ages vs deadline) must
  reproduce an explicit straggler_keep run exactly, on both engines, and
  report the mask as GreediResult.alive."""
  out = subrun("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import objectives as O
from repro.core.greedi import greedi_sharded, greedi_sharded_fast
from repro.util import make_mesh
f = jax.random.normal(jax.random.PRNGKey(0), (256, 12))
f = f / jnp.linalg.norm(f, axis=1, keepdims=True)
obj = O.FacilityLocation(kernel="linear")
mesh = make_mesh((4,), ("data",))
keep = jnp.array([True, False, True, True])
ages = jnp.array([0.2, 1e9, 3.0, 0.0])   # shard 1 missed its deadline
for gen in (True, False):
  if gen:
    a = greedi_sharded(f, mesh=mesh, kappa=8, k_final=8, objective=obj,
                       straggler_keep=keep)
    b = greedi_sharded(f, mesh=mesh, kappa=8, k_final=8, objective=obj,
                       liveness_age=ages, liveness_deadline=5.0)
  else:
    a = greedi_sharded_fast(f, mesh=mesh, kappa=8, k_final=8,
                            straggler_keep=keep)
    b = greedi_sharded_fast(f, mesh=mesh, kappa=8, k_final=8,
                            liveness_age=ages, liveness_deadline=5.0)
  np.testing.assert_array_equal(np.asarray(a.sel_gids), np.asarray(b.sel_gids))
  np.testing.assert_allclose(float(a.value), float(b.value), rtol=1e-6)
  np.testing.assert_array_equal(np.asarray(a.alive), np.asarray(keep))
  np.testing.assert_array_equal(np.asarray(b.alive), np.asarray(keep))
# liveness composes with an explicit keep (AND)
c = greedi_sharded(f, mesh=mesh, kappa=8, k_final=8, objective=obj,
                   straggler_keep=jnp.array([True, True, True, False]),
                   liveness_age=ages, liveness_deadline=5.0)
np.testing.assert_array_equal(np.asarray(c.alive),
                              np.array([True, False, True, False]))
print("LIVENESS_OK")
""", n_devices=4)
  assert "LIVENESS_OK" in out


def test_u_holder_reelected_among_alive(subrun):
  """Thm-10 U-subset eval with machine 0 dead: the U-holder moves to the
  first alive shard instead of collapsing the evaluation weight to zero
  (the value equals f(sel) evaluated on that shard's partition)."""
  out = subrun("""
import jax, jax.numpy as jnp, numpy as np
from repro.core import objectives as O
from repro.core.greedi import greedi_sharded, set_value_feats
from repro.util import make_mesh
f = jax.random.normal(jax.random.PRNGKey(0), (256, 12))
f = f / jnp.linalg.norm(f, axis=1, keepdims=True)
obj = O.FacilityLocation(kernel="linear")
mesh = make_mesh((4,), ("data",))
keep = jnp.array([False, True, True, True])
r = greedi_sharded(f, mesh=mesh, kappa=8, k_final=8, objective=obj,
                   u_subset_eval=True, straggler_keep=keep)
assert float(r.value) > 0.1, "U-subset value degenerated with machine 0 dead"
# the elected U-holder is shard 1: its partition is rows [64, 128)
u = f[64:128]
st0 = obj.init(u, jnp.ones((64,), f.dtype))
want = obj.value(set_value_feats(obj, st0, r.sel_feats, r.sel_valid))
np.testing.assert_allclose(float(r.value), float(want), rtol=1e-5)
# all alive keeps the historical holder (machine 0)
r0 = greedi_sharded(f, mesh=mesh, kappa=8, k_final=8, objective=obj,
                    u_subset_eval=True)
u0 = f[:64]
st00 = obj.init(u0, jnp.ones((64,), f.dtype))
want0 = obj.value(set_value_feats(obj, st00, r0.sel_feats, r0.sel_valid))
np.testing.assert_allclose(float(r0.value), float(want0), rtol=1e-5)
print("UHOLDER_OK")
""", n_devices=4)
  assert "UHOLDER_OK" in out


# ---------------------------------------------------------------------------
# service level (single-device mesh runs in-process)
# ---------------------------------------------------------------------------


def _mesh1():
  from repro.util import make_mesh
  return make_mesh((1,), ("data",))


def _service(**kw):
  from repro.service import SelectionService
  base = dict(d=16, kappa=8, k_final=8, capacity=256, append_block=128)
  base.update(kw)
  return SelectionService(_mesh1(), **base)


def test_service_restart_determinism():
  """Same seed + same append history ==> identical selections across a
  service restart (compiled-state independence)."""
  f = np.asarray(_feats(0, 500, 16))
  runs = []
  for _ in range(2):  # second construction = the "restarted" service
    svc = _service(seed=3)
    svc.append(f[:300])
    sels = [svc.epoch().sel_gids.tolist()]
    svc.append(f[300:])            # grows 300 -> 500 (capacity doubles)
    sels += [svc.epoch().sel_gids.tolist() for _ in range(2)]
    runs.append(sels)
  assert runs[0] == runs[1]
  assert len(runs[0][2]) == 8


def test_service_warm_equals_cold_every_epoch():
  f = np.asarray(_feats(1, 500, 16))
  sels = {}
  for warm in (True, False):
    svc = _service(seed=7, warm_start=warm)
    svc.append(f[:256])
    out = [svc.epoch().sel_gids.tolist()]
    svc.append(f[256:])
    out += [svc.epoch().sel_gids.tolist() for _ in range(2)]
    sels[warm] = out
  assert sels[True] == sels[False]


def test_service_epoch_schedule_reranomizes():
  """Distinct epochs draw distinct partitions; explicit rng overrides the
  schedule and reproduces."""
  f = np.asarray(_feats(2, 400, 16))
  svc = _service(seed=0)
  svc.append(f)
  a = svc.epoch(jax.random.PRNGKey(5)).sel_gids.tolist()
  b = svc.epoch(jax.random.PRNGKey(5)).sel_gids.tolist()
  assert a == b  # same explicit key, same selection
  stats = [svc.epoch().stats for _ in range(2)]
  assert stats[0].epoch != stats[1].epoch
  assert all(s.retraces == 1 for s in stats)


def test_service_append_gid_contract():
  svc = _service()
  f = np.asarray(_feats(3, 100, 16))
  svc.append(f[:60])
  svc.append(f[60:], gids=np.arange(1000, 1040))
  r = svc.epoch()
  assert svc.n_docs == 100
  assert all((0 <= g < 60) or (1000 <= g < 1040) for g in r.sel_gids.tolist())
  with pytest.raises(AssertionError):
    svc.append(f[:4], gids=np.array([-1, 2, 3, 4]))


# ---------------------------------------------------------------------------
# THE acceptance run: 4 shards, >= 3 epochs, append, kill, warm >= 1.3x
# ---------------------------------------------------------------------------


def test_service_four_shard_acceptance(subrun):
  """ISSUE-4 acceptance: a 4-shard service runs 3+ epochs with an append
  between epochs and a killed shard in the last one, asserting (a) no
  re-trace after warm-up, (b) sel_gids set-equality with a cold one-shot
  run at the same partition seed, (c) warm-start epochs >= 1.3x faster
  than cold on the near-duplicate corpus (the BENCH_4.json regime)."""
  out = subrun("""
import time
import jax, jax.numpy as jnp, numpy as np
from benchmarks.common import near_dup_corpus
from repro.service import SelectionService
from repro.util import make_mesh

N, D, K = 16384, 32, 8
feats = np.asarray(near_dup_corpus(N, D, seed=0))
n0 = 12288
mesh = make_mesh((4,), ("data",))

def build(warm):
  svc = SelectionService(mesh, d=D, kappa=K, k_final=K, capacity=N,
                         seed=11, warm_start=warm, deadline=60.0)
  svc.append(feats[:n0])
  return svc

warm, cold = build(True), build(False)

# epoch 0 compiles; epoch 1 after an append; epoch 2 with a killed shard
sels = {s: [] for s in ("warm", "cold")}
for name, svc in (("warm", warm), ("cold", cold)):
  sels[name].append(svc.epoch())
  svc.append(feats[n0:])
  sels[name].append(svc.epoch())
  svc.board.fail(3)
  sels[name].append(svc.epoch())

for e, (a, b) in enumerate(zip(sels["warm"], sels["cold"])):
  # (b) the warm multi-epoch service selects the same coreset as a cold
  # one-shot run of the protocol at the same partition seed
  assert set(a.sel_gids.tolist()) == set(b.sel_gids.tolist()), e
  assert len(a.sel_gids) == K, (e, a.sel_gids)
last = sels["warm"][-1].stats
assert last.alive.tolist() == [True, True, True, False], last.alive

# (a) no re-trace after warm-up: one trace total at fixed capacity,
# across appends AND the straggler epoch
assert warm.retrace_count == 1, warm.retrace_count
assert cold.retrace_count == 1, cold.retrace_count
print("EPOCHS_OK")

# (c) warm >= 1.3x cold per epoch (both already compiled + bounds settled;
# revive shard 3 so the timed epochs do full work)
for svc in (warm, cold):
  svc.board.beat()
def best_epoch_s(svc, reps=3):
  return min(svc.epoch().stats.wall_s for _ in range(reps))
t_warm = best_epoch_s(warm)
t_cold = best_epoch_s(cold)
ratio = t_cold / t_warm
print(f"warm {t_warm*1e3:.0f}ms cold {t_cold*1e3:.0f}ms ratio {ratio:.2f}x")
assert ratio >= 1.3, f"warm-start speedup {ratio:.2f}x < 1.3x"
print("ACCEPTANCE_OK")
""", n_devices=4, timeout=900)
  assert "EPOCHS_OK" in out
  assert "ACCEPTANCE_OK" in out


# ---------------------------------------------------------------------------
# ISSUE-6 satellites: objective backend honored, warm stats honest on cold
# starts, heartbeat fail -> beat revival across consecutive epochs
# ---------------------------------------------------------------------------


def test_service_honors_objective_backend():
  """Regression (ISSUE 6 satellite): a passed objective instance's
  ``backend`` must flow to the store's bound pass and the epoch protocol
  when the service-level ``backend`` is None (it was silently dropped)."""
  from repro.core import objectives as O
  obj = O.FacilityLocation(kernel="linear", backend="ref")
  svc = _service(objective=obj)
  assert svc._backend == "ref"
  assert svc.store._backend == "ref"
  # an explicit service-level backend still wins over the objective's
  svc2 = _service(objective=obj, backend="auto")
  assert svc2._backend == "auto" and svc2.store._backend == "auto"
  # and the fixed service selects exactly like one configured directly
  f = np.asarray(_feats(9, 120, 16))
  svc.append(f)
  svc3 = _service(backend="ref")
  svc3.append(f)
  assert set(svc.epoch().sel_gids.tolist()) == \
      set(svc3.epoch().sel_gids.tolist())


def test_service_warm_stat_honest_on_cold_start():
  """Regression (ISSUE 6 satellite): ``EpochStats.warm`` must report
  whether warm bounds actually carried signal, not the configuration flag.
  An all-zero corpus keeps the table at zero: epoch 0 ran effectively
  cold and must say so; once real mass lands, warm turns True."""
  svc = _service()
  assert svc.warm                      # configured warm...
  svc.append(np.zeros((40, 16), np.float32))
  r0 = svc.epoch()
  assert r0.stats.warm is False        # ...but nothing was threaded
  svc.append(np.abs(np.asarray(_feats(2, 40, 16))))
  r1 = svc.epoch()
  assert r1.stats.warm is True
  # warm_start=False stays False regardless of table state
  svc2 = _service(warm_start=False)
  svc2.append(np.abs(np.asarray(_feats(2, 40, 16))))
  assert svc2.epoch().stats.warm is False


def test_heartbeat_fail_beat_revival_across_epochs(subrun):
  """ISSUE-6 satellite: a ``fail``-ed shard is masked out of THAT epoch's
  alive mask and a bare ``beat`` revives it in the NEXT epoch's -- the
  revival must be observable across two consecutive epochs, not just in
  board state."""
  out = subrun("""
import numpy as np
from repro.service import SelectionService
from repro.service.heartbeat import HeartbeatBoard
from repro.util import make_mesh

t = [0.0]
mesh = make_mesh((4,), ("data",))
svc = SelectionService(mesh, d=8, kappa=4, k_final=8, capacity=256,
                       append_block=64, deadline=5.0, seed=0)
svc.board = HeartbeatBoard(4, clock=lambda: t[0])
svc.append(np.abs(np.random.default_rng(0).normal(size=(64, 8))
                  .astype(np.float32)))
svc.board.beat()
r0 = svc.epoch()
assert r0.stats.alive.tolist() == [True] * 4, r0.stats.alive
svc.board.fail(2)
r1 = svc.epoch()
assert r1.stats.alive.tolist() == [True, True, False, True], r1.stats.alive
assert len(r1.sel_gids) > 0
svc.board.beat(2)                    # the shard reports healthy again
r2 = svc.epoch()
assert r2.stats.alive.tolist() == [True] * 4, r2.stats.alive
print("REVIVAL_OK")
""", n_devices=4)
  assert "REVIVAL_OK" in out


# ---------------------------------------------------------------------------
# ISSUE-7 satellite: info-gain objective warm-starts via the prior bound
# ---------------------------------------------------------------------------


def test_service_info_gain_warm_equals_cold_every_epoch():
  """The prior bound 0.5*log1p(k_vv/sigma^2) is the EXACT empty-set gain, so
  warm lazy epochs must select bit-identically to cold ones."""
  f = np.asarray(_feats(4, 500, 16))
  sels, stats = {}, {}
  for warm in (True, False):
    svc = _service(seed=7, warm_start=warm, objective="info_gain")
    svc.append(f[:256])
    out = [svc.epoch().sel_gids.tolist()]
    svc.append(f[256:])
    r = [svc.epoch() for _ in range(2)]
    out += [x.sel_gids.tolist() for x in r]
    sels[warm], stats[warm] = out, r[-1].stats
  assert sels[True] == sels[False]
  # parity must not be trivially cold==cold: the warm service really ran warm
  assert stats[True].warm and not stats[False].warm


def test_service_info_gain_warm_parity_sharded(subrun):
  """Same parity on a real 4-shard mesh: the maintainer's complete
  (non-psummed) sums must survive the sharded append path (sums_global)."""
  subrun("""
      import numpy as np
      from repro.service import SelectionService
      from repro.util import make_mesh

      f = np.random.default_rng(0).normal(size=(500, 16)).astype(np.float32)
      sels = {}
      for warm in (True, False):
        svc = SelectionService(make_mesh((4,), ("data",)), d=16, kappa=8,
                               k_final=8, capacity=256, append_block=128,
                               objective="info_gain", seed=7,
                               warm_start=warm)
        svc.append(f[:256])
        out = [svc.epoch().sel_gids.tolist()]
        svc.append(f[256:])
        rs = [svc.epoch() for _ in range(2)]
        out += [r.sel_gids.tolist() for r in rs]
        sels[warm] = out
        if warm:
          assert rs[-1].stats.warm
      assert sels[True] == sels[False], sels
      print("PARITY_OK")
      """, 4)


@pytest.mark.parametrize("sigma", [0.5, 2.0])
def test_service_sigma_reaches_the_info_gain_objective(sigma):
  """A service given an ``InformationGain(sigma=...)`` instance selects and
  values as a direct greedy of that instance over the epoch's partition
  order (one shard, kappa = k_final: round 2 re-picks round 1's coreset),
  differs in value from the sigma = 1 default, and takes its prior warm
  bound at that sigma."""
  from repro.core.partition import partition_perm
  n, k = 256, 8
  f = np.asarray(_feats(5, n, 6))
  key = jax.random.PRNGKey(11)

  def info_gain(s):
    return O.InformationGain(k_max=k, kernel="rbf",
                             kernel_kwargs=(("h", 0.75),), sigma=s)

  obj = info_gain(sigma)
  perm = np.asarray(partition_perm(jax.random.split(key)[0], n))
  xp = jnp.asarray(f[perm])
  want = greedy(obj, obj.init(xp), xp, k)
  got = {}
  for s in (sigma, 1.0):
    svc = _service(d=6, kappa=k, k_final=k, capacity=n, append_block=128,
                   objective=info_gain(s), mode="standard", warm_start=False)
    svc.append(f)
    got[s] = svc.epoch(rng=key)
  assert sorted(got[sigma].sel_gids.tolist()) == sorted(
      perm[np.asarray(want.idx)].tolist())
  np.testing.assert_allclose(got[sigma].stats.value,
                             float(want.values[-1]), rtol=1e-5)
  assert (got[1.0].stats.value > got[sigma].stats.value) == (sigma > 1.0)
  warm = _service(d=6, objective=info_gain(sigma))
  assert warm.warm and warm.store._maintainer.sigma == sigma
