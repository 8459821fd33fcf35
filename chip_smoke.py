"""Smoke run of the selection service's main path on TPU chips.

    python chip_smoke.py              # one chip: the service main path
    python chip_smoke.py --chips 4    # four chips: the sharded epoch only

One chip: a ``SelectionService`` over 2^20 documents x d = 64 (f32, seeded
near-duplicate corpus in the shape of ``benchmarks.common.near_dup_corpus``)
with facility location, the linear kernel, lazy warm-started epochs, standing
sieves and ``backend="auto"``: append 75%, epoch 0, append 25%, epochs 1 and
2, ``query()``, and a 128-request ``query_batch`` on the sieve tier.  A
second, 2^14-row service runs the same sequence against a ``backend="ref"``
copy on the CPU device of this process, and serves a 128-request batch on
the exact tier (that tier costs O(B k n^2 d) per batch, hours at 2^20).

Four chips: 4 x 2^20 rows sharded over a 4-chip mesh (each chip holds what
the one-chip run holds), epochs with the flat merge, the tree merge at b = 4
(bit-identical to flat by contract) and at b = 2 (two levels over ICI); a
warm service over the first 2^20 rows (2^18 per chip) with its epoch and a
128-request ``query_batch``; and a 2^14-row sharded epoch against
``greedi_reference`` on the host.

Every result is checked against a plain host reference; a failed check
raises, so the run exits non-zero and never prints the ok line.  The last
line of a passing run is the JSON object
``{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}``.
``main`` refuses any backend but TPU; ``run_one_chip`` / ``run_four_chip``
take sizes and run on whatever backend the process has (the CPU test of the
phases calls them at a tiny size).
"""
from __future__ import annotations

import argparse
import contextlib
import json
import os
import sys
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
sys.path[:0] = [os.path.join(ROOT, "src"), ROOT]

D = 64
KAPPA = K_FINAL = 64
APPEND_BLOCK = 1024
QUERY_BATCH = 128
N_ONE_CHIP = 1 << 20
N_PARITY = 1 << 14
# four chips: the warm path (sharded bound pass, sieves, query_batch) runs at
# 2^18 rows per chip; its append costs ~40 ms of chip time per 1024-row chunk
# on every chip, ~3 min at 4 x 2^20
N_WARM_4 = 1 << 20
# |reported f32 value - host float64 f(S)| <= VALUE_RTOL * f(S).  The value
# is a sum of k f32 gains, each a masked mean over up to 2^22 rows; blocked
# f32 sums of that length stay within ~1e-6 relative, and bf16-rounded
# similarities would be ~1e-3 off.
VALUE_RTOL = 1e-4
# batched vs sequential sieve merges: separate executables, ~ulp apart
QUERY_RTOL, QUERY_ATOL = 1e-5, 1e-7


class CheckFailed(AssertionError):
  pass


def check(log, name: str, ok: bool, detail: str = "") -> None:
  log(f"check {name}: {'ok' if ok else 'FAILED'} {detail}".rstrip())
  if not ok:
    raise CheckFailed(f"{name}: {detail}")


class Clock:
  """Wall time and XLA compile seconds of each named phase."""

  def __init__(self, log):
    self.log = log
    self.compile_s = 0.0
    self.phases: dict[str, float] = {}

  def __enter__(self):
    import jax
    jax.monitoring.register_event_duration_secs_listener(self._on_event)
    return self

  def __exit__(self, *exc):
    import jax
    jax.monitoring.unregister_event_duration_listener(self._on_event)
    self.log(f"compile_s total={self.compile_s!r}")

  def _on_event(self, event, duration, **_):
    if event == "/jax/core/compile/backend_compile_duration":
      self.compile_s += duration

  @contextlib.contextmanager
  def phase(self, name: str):
    c0, t0 = self.compile_s, time.perf_counter()
    yield
    wall = time.perf_counter() - t0
    self.phases[name] = wall
    self.log(f"phase {name}: wall_s={wall!r} "
             f"compile_s={self.compile_s - c0!r}")


def corpus(n: int, seed: int) -> np.ndarray:
  from benchmarks.common import near_dup_corpus
  return np.asarray(near_dup_corpus(n, d=D, seed=seed), np.float32)


def host_value(x: np.ndarray, sel: np.ndarray, rows=None) -> float:
  """Float64 facility value mean_i max(0, max_{s in sel} x_i . x_s) over the
  rows of ``x`` (or the boolean subset ``rows``)."""
  s = x[np.asarray(sel)].astype(np.float64)
  if rows is not None:
    x = x[rows]
  total = 0.0
  for off in range(0, x.shape[0], 1 << 16):
    c = x[off:off + (1 << 16)].astype(np.float64) @ s.T
    total += float(np.maximum(c.max(axis=1), 0.0).sum())
  return total / max(x.shape[0], 1)


def rel_err(a: float, b: float) -> float:
  return float(abs(a - b) / max(abs(b), 1e-30))


def check_selection(log, name, sel, n_live, k) -> None:
  sel = np.asarray(sel)
  ok = (len(sel) == k and len(np.unique(sel)) == len(sel)
        and bool(np.all((sel >= 0) & (sel < n_live))))
  check(log, f"{name} gids unique/in range/live", ok,
        f"n={len(sel)} min={sel.min() if len(sel) else None} "
        f"max={sel.max() if len(sel) else None} live={n_live}")


def check_value(log, name, reported: float, x, sel) -> None:
  ref = host_value(x, sel)
  err = rel_err(reported, ref)
  check(log, f"{name} value vs host f64", err <= VALUE_RTOL,
        f"reported={reported!r} host={ref!r} rel_err={err!r} "
        f"tol={VALUE_RTOL!r}")


def requests(svc, b: int, k: int):
  """``b`` heterogeneous tenant requests: varying k, tie-break seed and
  exclusion lists drawn from the service's current answer."""
  from repro.service import QueryRequest
  base = svc.query(seed=1).sel_gids
  mc = svc.store.query_mask_cap
  return [QueryRequest(k=1 + (i % k), seed=i % 4,
                       exclude_gids=tuple(int(g) for g in base[:min(i % 5,
                                                                    mc)]))
          for i in range(b)]


def check_query_batch(log, clock, svc, name: str) -> None:
  """A sieve-tier batch must answer what the same requests answer one by one
  through ``query()``; every answer is live and respects its exclusions."""
  reqs = requests(svc, QUERY_BATCH, K_FINAL)
  with clock.phase(f"{name} query_batch sieve"):
    batched = svc.query_batch(reqs)
  with clock.phase(f"{name} sequential queries"):
    seq = [svc.query(r.k, seed=r.seed, exclude_gids=r.exclude_gids or None)
           for r in reqs]
  bad = [i for i, (rb, rs) in enumerate(zip(batched, seq))
         if rb.source != rs.source
         or not np.array_equal(rb.sel_gids, rs.sel_gids)
         or not np.isclose(rb.value_estimate, rs.value_estimate,
                           rtol=QUERY_RTOL, atol=QUERY_ATOL)]
  srcs = sorted({r.source for r in batched})
  check(log, f"{name} query_batch == sequential query()", not bad,
        f"requests={len(reqs)} sources={srcs} mismatched={bad[:8]}")
  ok = all(len(np.unique(r.sel_gids)) == len(r.sel_gids)
           and len(r.sel_gids) <= q.k
           and np.all((r.sel_gids >= 0) & (r.sel_gids < svc.n_docs))
           and not set(r.sel_gids.tolist()) & set(q.exclude_gids)
           for r, q in zip(batched, reqs))
  check(log, f"{name} query_batch answers valid", ok)


def _service(mesh, **kw):
  from repro.service import SelectionService
  return SelectionService(mesh, d=D, kappa=KAPPA, k_final=K_FINAL,
                          append_block=APPEND_BLOCK, **kw)


def _epoch(log, clock, svc, name: str):
  with clock.phase(name):
    r = svc.epoch()
  s = r.stats
  log(f"{name}: live={s.n_live} cap={s.capacity} value={s.value!r} "
      f"warm={s.warm} device_wall_s={s.wall_s!r} traces={s.retraces} "
      f"rescans={np.asarray(r.raw.r1_rescans).tolist()}")
  return r


def _peak(log, devices) -> None:
  for dv in devices:
    st = dv.memory_stats() or {}
    log(f"memory {dv}: peak_bytes_in_use={st.get('peak_bytes_in_use')} "
        f"bytes_limit={st.get('bytes_limit')}")


def _oracle(log) -> None:
  import jax
  from repro.kernels import dispatch, ops
  want = "pallas" if jax.default_backend() == "tpu" else "ref"
  log(f"oracle: backend=auto -> {dispatch.auto_backend()} "
      f"(pallas interpret={ops._interpret()})")
  check(log, "auto resolves to the platform's oracle",
        dispatch.auto_backend() == want)


def _mosaic_kernels(log, svc) -> None:
  """Count the Mosaic kernels in the compiled epoch (0 off TPU, where the
  oracles are XLA).  Lowering reuses the cached trace: no retrace."""
  import jax
  st = svc.store
  ages = np.zeros((len(svc.board.ages()),), np.float32)
  txt = svc._epoch_fn.lower(st.feats, st.gids, st.ubound_device, ages,
                            np.float32(np.inf),
                            jax.random.PRNGKey(0)).as_text()
  n = txt.count("tpu_custom_call")
  log(f"epoch program: {n} tpu_custom_call ops")
  if jax.default_backend() == "tpu":
    check(log, "epoch runs Pallas kernels through Mosaic", n > 0)


def run_one_chip(n: int = N_ONE_CHIP, n_parity: int = N_PARITY, *,
                 seed: int = 0, log=print) -> dict:
  """The one-chip main path at ``n`` rows, then the ``n_parity``-row parity
  and exact-tier phase; returns the wall seconds of each phase.  Raises
  ``CheckFailed`` on any failed check."""
  with Clock(log) as clock:
    _one_chip(clock, n, n_parity, seed)
  return clock.phases


def _one_chip(clock, n: int, n_parity: int, seed: int) -> None:
  import jax
  from repro.util import make_mesh

  log = clock.log
  dev = jax.devices()[0]
  mesh = make_mesh((1,), ("data",))
  n0 = n - n // 4
  log(f"sizes: n={n} d={D} f32 kappa={KAPPA} k_final={K_FINAL} "
      f"append_block={APPEND_BLOCK} capacity={n} first_append={n0} "
      f"feature_bytes={n * D * 4} query_batch={QUERY_BATCH} "
      f"parity_n={n_parity} device={dev.platform}:{dev.device_kind}")
  _oracle(log)

  with clock.phase("corpus"):
    x = corpus(n, seed)

  svc = _service(mesh, capacity=n, seed=seed)
  with clock.phase("append 75%"):
    svc.append(x[:n0])
    jax.block_until_ready(svc.store.ubound_device)
  warm = [_epoch(log, clock, svc, "epoch 0")]
  with clock.phase("append 25%"):
    svc.append(x[n0:])
    jax.block_until_ready(svc.store.ubound_device)
  warm += [_epoch(log, clock, svc, f"epoch {e}") for e in (1, 2)]
  _mosaic_kernels(log, svc)
  check(log, "epoch trace count stays 1", svc.retrace_count == 1,
        f"traces={svc.retrace_count} growths={svc.growths}")
  check(log, "warm bounds in effect", all(r.stats.warm for r in warm))
  for e, r in enumerate(warm):
    live = r.stats.n_live
    check_selection(log, f"epoch {e}", r.sel_gids, live, K_FINAL)
    check_value(log, f"epoch {e}", r.stats.value, x[:live], r.sel_gids)

  with clock.phase("query"):
    q = svc.query(seed=1)
  log(f"query: source={q.source} ids={len(q.sel_gids)} "
      f"estimate={q.value_estimate!r} wall_s={q.wall_s!r}")
  check(log, "query answers from the sieve", q.source == "sieve")
  check_selection(log, "query", q.sel_gids, n, K_FINAL)
  check_query_batch(log, clock, svc, "main")
  _peak(log, [dev])

  cold = _service(mesh, capacity=n, seed=seed, warm_start=False)
  with clock.phase("cold service"):
    cold.append(x[:n0])
    cold_r = [_epoch(log, clock, cold, "cold epoch 0")]
    cold.append(x[n0:])
    cold_r += [_epoch(log, clock, cold, f"cold epoch {e}") for e in (1, 2)]
  for e, (w, c) in enumerate(zip(warm, cold_r)):
    check(log, f"epoch {e} warm selection == cold selection",
          np.array_equal(w.sel_gids, c.sel_gids))
  del svc, cold

  parity_phase(log, clock, n_parity, seed + 1)
  _peak(log, [dev])


def parity_phase(log, clock, n: int, seed: int) -> None:
  """The same epochs on this backend (``auto``) and with ``backend="ref"``
  on the CPU device; then the exact tier, checked against host values."""
  import jax
  from repro.util import make_mesh

  x = corpus(n, seed)
  n0 = n - n // 4
  cpu = jax.devices("cpu")[:1]
  runs = {}
  for name, mesh, backend in (
      ("device", make_mesh((1,), ("data",)), None),
      ("cpu-ref", make_mesh((1,), ("data",), devices=cpu), "ref")):
    svc = _service(mesh, capacity=n, seed=seed, backend=backend)
    svc.append(x[:n0])
    rs = [_epoch(log, clock, svc, f"parity {name} epoch 0")]
    svc.append(x[n0:])
    rs.append(_epoch(log, clock, svc, f"parity {name} epoch 1"))
    runs[name] = (svc, rs)
  for e, (a, b) in enumerate(zip(runs["device"][1], runs["cpu-ref"][1])):
    live = a.stats.n_live
    check_selection(log, f"parity epoch {e}", a.sel_gids, live, K_FINAL)
    if np.array_equal(a.sel_gids, b.sel_gids):
      held = "identical selections"
      ok = True
    else:
      va = host_value(x[:live], a.sel_gids)
      vb = host_value(x[:live], b.sel_gids)
      held = (f"selections differ in {len(set(a.sel_gids) ^ set(b.sel_gids))}"
              f" gids; host values {va!r} vs {vb!r}")
      ok = rel_err(va, vb) <= VALUE_RTOL
    check(log, f"parity epoch {e} device vs cpu-ref", ok,
          f"{held} (value tol {VALUE_RTOL!r})")

  svc = runs["device"][0]
  reqs = requests(svc, QUERY_BATCH, K_FINAL)
  with clock.phase("query_batch exact"):
    ans = svc.query_batch(reqs, tier="exact")
  bad, errs = [], []
  for i, (r, q) in enumerate(zip(ans, reqs)):
    vis = ~np.isin(np.arange(n), q.exclude_gids)
    if not (r.source == "exact" and len(r.sel_gids) == q.k
            and len(np.unique(r.sel_gids)) == q.k
            and vis[r.sel_gids].all()):
      bad.append(i)
    errs.append(rel_err(r.value_estimate, host_value(x, r.sel_gids, vis)))
  check(log, "exact tier answers valid", not bad,
        f"requests={len(reqs)} invalid={bad[:8]}")
  check(log, "exact tier values vs host f64", max(errs) <= VALUE_RTOL,
        f"requests={len(reqs)} max_rel_err={max(errs)!r} "
        f"tol={VALUE_RTOL!r}")


def run_four_chip(n_per_chip: int = N_ONE_CHIP, n_warm: int = N_WARM_4,
                  n_ref: int = N_PARITY, *, seed: int = 0,
                  log=print) -> dict:
  """The sharded epoch on a 4-device mesh and what it is compared with;
  returns the wall seconds of each phase.  Raises ``CheckFailed``."""
  with Clock(log) as clock:
    _four_chip(clock, n_per_chip, n_warm, n_ref, seed)
  return clock.phases


def _four_chip(clock, n_per_chip: int, n_warm: int, n_ref: int,
               seed: int) -> None:
  import jax
  from repro.data.selection import (greedi_select_indices,
                                    greedi_select_indices_sharded)
  from repro.util import make_mesh

  log = clock.log
  devs = jax.devices()[:4]
  mesh = make_mesh((4,), ("data",))
  n = 4 * n_per_chip
  log(f"sizes: n={n} ({n_per_chip} per chip) d={D} f32 kappa={KAPPA} "
      f"k_final={K_FINAL} append_block={APPEND_BLOCK} warm_n={n_warm} "
      f"ref_n={n_ref} devices={[f'{d.platform}:{d.id}' for d in devs]}")
  _oracle(log)
  with clock.phase("corpus"):
    x = corpus(n, seed)

  res = {}
  for name, kw in (("flat", {}), ("tree b=4", dict(merge="tree",
                                                   tree_branch=4)),
                   ("tree b=2", dict(merge="tree", tree_branch=2))):
    svc = _service(mesh, capacity=n, seed=seed, warm_start=False, **kw)
    with clock.phase(f"append ({name})"):
      svc.append(x)
    res[name] = _epoch(log, clock, svc, f"{name} epoch")
    if name == "flat":
      _mosaic_kernels(log, svc)
      _peak(log, devs)
    del svc
  f, t4 = res["flat"], res["tree b=4"]
  check(log, "flat == tree b=4 (bit-identical)",
        np.array_equal(f.sel_gids, t4.sel_gids)
        and f.stats.value == t4.stats.value
        and np.array_equal(np.asarray(f.raw.sel_feats),
                           np.asarray(t4.raw.sel_feats)),
        f"values {f.stats.value!r} vs {t4.stats.value!r}")
  for name, r in res.items():
    check_selection(log, name, r.sel_gids, n, K_FINAL)
    check_value(log, name, r.stats.value, x, r.sel_gids)

  # the warm path: sharded bound pass, per-shard sieves, batched queries
  svc = _service(mesh, capacity=n_warm, seed=seed)
  with clock.phase("warm append"):
    svc.append(x[:n_warm])
    jax.block_until_ready(svc.store.ubound_device)
  warm = _epoch(log, clock, svc, "warm flat epoch")
  check(log, "warm bounds in effect", warm.stats.warm)
  check_selection(log, "warm flat", warm.sel_gids, n_warm, K_FINAL)
  check_value(log, "warm flat", warm.stats.value, x[:n_warm], warm.sel_gids)
  check_query_batch(log, clock, svc, "4-chip")
  del svc

  xr = corpus(n_ref, seed + 1)
  rng = jax.random.PRNGKey(seed)
  with clock.phase("sharded reference-size epoch"):
    s_dev = greedi_select_indices_sharded(
        rng, jax.numpy.asarray(xr), mesh=mesh, kappa=KAPPA, k_final=K_FINAL,
        fast=False, mode="lazy")
  cpu = jax.devices("cpu")[0]
  with clock.phase("host greedi_reference"), jax.default_device(cpu):
    s_ref = greedi_select_indices(
        jax.random.PRNGKey(seed), jax.device_put(xr, cpu), m=4, kappa=KAPPA,
        k_final=K_FINAL, mode="lazy", backend="ref")
  check_selection(log, "sharded reference-size epoch", s_dev, n_ref, K_FINAL)
  if set(s_dev.tolist()) == set(s_ref.tolist()):
    held, ok = "identical selection sets", True
  else:
    va, vb = host_value(xr, s_dev), host_value(xr, s_ref)
    held = (f"sets differ in {len(set(s_dev) ^ set(s_ref))} gids; host "
            f"values {va!r} vs {vb!r}")
    ok = rel_err(va, vb) <= VALUE_RTOL
  check(log, "sharded epoch vs greedi_reference (m=4)", ok,
        f"{held} (value tol {VALUE_RTOL!r})")
  _peak(log, devs)


def main() -> int:
  ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
  ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                  help="1: the service main path; 4: the sharded epoch "
                  "on a 4-chip mesh and what it is compared with")
  args = ap.parse_args()

  import jax
  devices = jax.devices()
  dev = devices[0]
  if dev.platform != "tpu":
    print(f"chip_smoke: needs a TPU; JAX found {dev.platform}",
          file=sys.stderr)
    return 2
  if len(devices) < args.chips:
    print(f"chip_smoke: --chips {args.chips} needs {args.chips} devices; "
          f"JAX found {len(devices)}", file=sys.stderr)
    return 2

  def log(msg: str) -> None:
    print(msg, flush=True)

  from repro.util import compile_cache
  log(f"compile cache: {compile_cache()}")
  t0 = time.perf_counter()
  if args.chips == 1:
    run_one_chip(log=log)
  else:
    run_four_chip(log=log)
  log(f"total wall_s={time.perf_counter() - t0!r}")
  print(json.dumps({"ok": True, "device": {
      "platform": dev.platform, "kind": dev.device_kind,
      "count": len(devices)}}))
  return 0


if __name__ == "__main__":
  sys.exit(main())
