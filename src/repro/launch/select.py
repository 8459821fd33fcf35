"""Selection driver: run (sharded) GreeDi coreset selection from the CLI.

    PYTHONPATH=src python -m repro.launch.select --n 100000 --k 128 --mesh 8

With --mesh N the ground set is sharded over the first N devices of the
process (on the CPU backend the flag below forces N host devices; on an
accelerator the mesh needs N real chips) and the production shard_map path
runs (greedi_sharded_fast, or the generic
greedi_sharded with --no-fast); without it the reference implementation is
used.  Any --n works on a mesh: non-divisible ground sets are padded with
masked hole rows.  Both paths return *global document indices*, honor
--out (npy), and report coverage vs the centralized greedy when n is small
enough for the O(k n^2) baseline to be cheap (force with --coverage, skip
with --no-coverage).

With --epochs E (mesh mode) the long-lived SelectionService runs instead:
the corpus streams in (--append-frac held back and appended after the
first epoch), each epoch re-randomizes the partition and re-selects with
warm-started lazy bounds (--cold disables), and per-epoch stats print as
they stream.  --query-batch B additionally drives the multi-tenant path
(append -> query_batch -> epoch -> query_batch) with a batched-vs-
sequential parity assertion, so the CI smoke job only needs the exit
code.  --out then holds the LAST epoch's selection:

    PYTHONPATH=src python -m repro.launch.select \\
        --n 4096 --k 16 --mesh 4 --epochs 3 --append-frac 0.25
"""
from __future__ import annotations

import argparse
import os
import time


def _force_host_devices(n: int) -> None:
  """Append the forced-device-count flag to XLA_FLAGS (setdefault would
  silently drop it when XLA_FLAGS is already set for other reasons).  Only
  the CPU backend reads it; accelerator meshes use real devices."""
  flag = f"--xla_force_host_platform_device_count={n}"
  existing = os.environ.get("XLA_FLAGS", "")
  if "--xla_force_host_platform_device_count" not in existing:
    os.environ["XLA_FLAGS"] = f"{existing} {flag}".strip()


def _query_batch_cycle(svc, b: int, k: int, stage: str, emit) -> None:
  """Answer ``b`` heterogeneous tenant requests through one
  ``query_batch`` call, then replay them sequentially through ``query()``
  and fail loudly unless the selections are bit-identical -- the CI smoke
  job relies on the exit code alone."""
  import time

  import numpy as np

  from repro.service import QueryRequest

  mc = svc.store.query_mask_cap
  base = svc.query()  # known-live gids for the exclusion lists
  reqs = []
  for i in range(b):
    excl = tuple(int(g) for g in base.sel_gids[:min(i % 3, mc)] if g >= 0)
    reqs.append(QueryRequest(k=1 + (i % k), seed=i % 4, exclude_gids=excl))
  t0 = time.time()
  batched = svc.query_batch(reqs)
  t_batch = time.time() - t0
  t0 = time.time()
  seq = [svc.query(r.k, seed=r.seed, exclude_gids=r.exclude_gids)
         for r in reqs]
  t_seq = time.time() - t0
  for i, (rb, rs) in enumerate(zip(batched, seq)):
    # selections must match exactly; value estimates only to ~ulp (the
    # batched and single merges are different XLA executables, which may
    # round their d-dim reductions differently)
    if (not np.array_equal(rb.sel_gids, rs.sel_gids) or not np.isclose(
        rb.value_estimate, rs.value_estimate, rtol=1e-5, atol=1e-7)):
      raise SystemExit(f"[select] query_batch parity FAILED ({stage}, "
                       f"request {i}): batched={rb.sel_gids} "
                       f"(v={rb.value_estimate!r}) sequential="
                       f"{rs.sel_gids} (v={rs.value_estimate!r})")
  emit("query_batch", stage=stage, requests=b, batch_ms=t_batch * 1e3,
       qps=b / max(t_batch, 1e-9), seq_ms=t_seq * 1e3,
       speedup=t_seq / t_batch if t_batch > 0 else float("inf"),
       parity="ok", query_traces=svc.store.query_trace_count,
       batch_traces=svc.store.query_batch_trace_count)


def main() -> None:
  ap = argparse.ArgumentParser()
  ap.add_argument("--n", type=int, default=65536)
  ap.add_argument("--d", type=int, default=64)
  ap.add_argument("--k", type=int, default=64)
  ap.add_argument("--kappa", type=int, default=None)
  ap.add_argument("--m", type=int, default=8, help="logical partitions "
                  "(reference path)")
  ap.add_argument("--mesh", type=int, default=0, help="devices of the "
                  "sharded path (forced host devices on CPU)")
  ap.add_argument("--kernel", default="linear", choices=["linear", "rbf"])
  ap.add_argument("--backend", default=None,
                  choices=["pallas", "ref", "auto"],
                  help="gain-oracle backend override (kernels/dispatch.py)")
  ap.add_argument("--no-fast", action="store_true",
                  help="sharded path: use the generic objective engine "
                  "instead of the cached-similarity fast engine")
  ap.add_argument("--merge-tree", type=int, default=0, metavar="B",
                  help="merge round-1 blocks through an accumulation tree "
                  "with B children per node instead of one flat all_gather "
                  "(sharded and service modes; 0 = flat; B = mesh size is "
                  "bit-identical to flat -- see docs/greedi.md)")
  ap.add_argument("--epochs", type=int, default=0,
                  help="run the multi-epoch SelectionService for this many "
                  "epochs (mesh mode only)")
  ap.add_argument("--objective", default="facility",
                  choices=["facility", "saturated_coverage", "info_gain"],
                  help="service mode: selection objective; warm starts "
                  "engage for any objective with a registered "
                  "BoundMaintainer (core/objectives.py)")
  ap.add_argument("--append-frac", type=float, default=0.0,
                  help="service mode: fraction of the corpus appended only "
                  "after the first epoch (streaming ingest)")
  ap.add_argument("--query-every", type=int, default=0,
                  help="service mode: stream the held-back --append-frac "
                  "rows in blocks of this size and run service.query() "
                  "after each block (the standing-sieve select-on-append "
                  "path), printing per-query latency and value")
  ap.add_argument("--query-batch", type=int, default=0,
                  help="service mode: after the first append (pre-epoch) and "
                  "again after the last epoch, answer this many "
                  "heterogeneous tenant requests (varying k / seed / "
                  "exclusions) through one query_batch call, assert "
                  "bit-identical to sequential query() calls, and print "
                  "throughput (exit 1 on parity failure)")
  ap.add_argument("--cold", action="store_true",
                  help="service mode: disable warm-started lazy bounds")
  ap.add_argument("--deadline", type=float, default=None,
                  help="service mode: straggler liveness deadline (seconds)")
  ap.add_argument("--coverage", action="store_true",
                  help="force the centralized-greedy coverage baseline")
  ap.add_argument("--no-coverage", action="store_true",
                  help="skip the centralized-greedy coverage baseline")
  ap.add_argument("--out", default=None, help="write selected indices (npy)")
  ap.add_argument("--metrics-port", type=int, default=None,
                  help="serve the obs sidecar (/metrics Prometheus text, "
                  "/healthz liveness) on this port (0 = pick a free one); "
                  "service mode wires POST /healthz beats into the "
                  "heartbeat board")
  ap.add_argument("--trace-out", default=None,
                  help="write obs trace spans as JSONL to this path")
  ap.add_argument("--stats-json", default=None,
                  help="write every stats line plus a metrics-registry "
                  "snapshot to this path as JSON (all modes)")
  ap.add_argument("--linger", type=float, default=0.0,
                  help="keep the sidecar serving this many seconds after "
                  "the run (scrape window for smoke jobs)")
  args = ap.parse_args()

  if args.mesh:
    _force_host_devices(args.mesh)

  import jax
  import numpy as np

  from repro import obs
  from repro.util import compile_cache

  compile_cache()
  if args.mesh and len(jax.devices()) < args.mesh:
    raise SystemExit(
        f"[select] --mesh {args.mesh} needs {args.mesh} devices; the "
        f"{jax.default_backend()} backend has {len(jax.devices())}")
  from repro.data.pipeline import EmbeddedCorpus
  from repro.data.selection import (coverage_ratio, greedi_select_indices,
                                    greedi_select_indices_sharded)

  if (args.trace_out or args.stats_json or args.metrics_port is not None):
    obs.enable(trace_out=args.trace_out)

  records: list = []

  def emit(event, **fields):
    """The ONE stats format of every mode: an obs stats line to stdout plus
    a record for --stats-json."""
    print("[select] " + obs.stats_line(event, **fields))
    records.append(dict(event=event, **fields))

  sidecar = None
  kappa = args.kappa or args.k
  corpus = EmbeddedCorpus(n_docs=args.n, feat_dim=args.d, vocab=1024,
                          seq_len=8)
  feats = corpus.features()
  t0 = time.time()
  if args.mesh and args.epochs:
    from repro.service import SelectionService
    from repro.util import make_mesh
    mesh = make_mesh((args.mesh,), ("data",))
    svc = SelectionService(mesh, d=args.d, kappa=kappa, k_final=args.k,
                           capacity=args.n, kernel=args.kernel,
                           backend=args.backend, warm_start=not args.cold,
                           deadline=args.deadline, objective=args.objective,
                           merge="tree" if args.merge_tree else "flat",
                           tree_branch=args.merge_tree or None)
    if args.metrics_port is not None:
      # board wired in: POST /healthz beats feed the same HeartbeatBoard
      # as in-process beats (the out-of-band liveness path)
      sidecar = obs.Sidecar(board=svc.board, port=args.metrics_port)
      emit("sidecar", url=sidecar.url)
    n0 = args.n - int(args.n * args.append_frac)
    feats_np = np.asarray(feats)
    if args.objective == "saturated_coverage":
      feats_np = np.abs(feats_np)  # nonneg coverage mass (Lin & Bilmes)
    svc.append(feats_np[:n0])
    if args.query_batch:
      _query_batch_cycle(svc, args.query_batch, args.k, "pre-epoch", emit)
    res = None
    for e in range(args.epochs):
      svc.board.beat()   # all in-process shards are alive by construction
      res = svc.epoch()
      s = res.stats
      emit("epoch", epoch=s.epoch, docs=len(res.sel_gids), live=s.n_live,
           cap=s.capacity, f=s.value, alive=int(s.alive.sum()),
           shards=len(s.alive), warm=s.warm, wall_s=s.wall_s,
           traces=s.retraces)
      if e == 0 and n0 < args.n:
        if args.query_every:
          # stream the held-back rows in blocks, answering "give me k NOW"
          # after each append from the standing sieves -- no protocol run
          for boff in range(n0, args.n, args.query_every):
            svc.append(feats_np[boff:boff + args.query_every])
            q = svc.query()
            emit("query", docs=svc.n_docs, ids=len(q.sel_gids),
                 source=q.source, est=q.value_estimate,
                 stale_appends=q.appends_since_epoch,
                 wall_ms=q.wall_s * 1e3)
        else:
          svc.append(feats_np[n0:])
        emit("append", docs=args.n - n0)
    if args.query_batch:
      _query_batch_cycle(svc, args.query_batch, args.k, "post-epoch", emit)
    sel = res.sel_gids
    # the coverage baseline below must score the features selection ran on
    # (saturated coverage selects over the abs-mapped corpus)
    feats = jax.numpy.asarray(feats_np)
    mode_fields = dict(mode="service", m=args.mesh, epochs=args.epochs,
                       objective=args.objective,
                       merge=f"tree{args.merge_tree}" if args.merge_tree
                       else "flat")
  elif args.mesh:
    from repro.util import make_mesh  # jax imported post-env-setup
    mesh = make_mesh((args.mesh,), ("data",))
    if args.metrics_port is not None:
      sidecar = obs.Sidecar(port=args.metrics_port)
      emit("sidecar", url=sidecar.url)
    sel = greedi_select_indices_sharded(
        jax.random.PRNGKey(0), feats, mesh=mesh, kappa=kappa,
        k_final=args.k, kernel=args.kernel, fast=not args.no_fast,
        backend=args.backend,
        merge="tree" if args.merge_tree else "flat",
        tree_branch=args.merge_tree or None)
    mode_fields = dict(mode="sharded", m=args.mesh,
                       engine="generic" if args.no_fast else "fast",
                       merge=f"tree{args.merge_tree}" if args.merge_tree
                       else "flat")
  else:
    if args.metrics_port is not None:
      sidecar = obs.Sidecar(port=args.metrics_port)
      emit("sidecar", url=sidecar.url)
    sel = greedi_select_indices(jax.random.PRNGKey(0), feats, m=args.m,
                                kappa=kappa, k_final=args.k,
                                kernel=args.kernel, backend=args.backend)
    mode_fields = dict(mode="reference", m=args.m)
  t_sel = time.time() - t0

  # persist the coreset BEFORE the (expensive) coverage baseline so a
  # baseline OOM/timeout can't discard an already-computed selection
  if args.out:
    np.save(args.out, sel)
    emit("wrote", path=args.out)
  done = dict(mode_fields, docs=len(sel), wall_s=t_sel)
  # the baseline is O(k * n^2) on the full ground set -- default it on only
  # at sizes where that is cheap, and let --coverage / --no-coverage override
  want_cov = args.coverage or (not args.no_coverage and args.n <= 16384)
  if want_cov:
    done["coverage"] = float(coverage_ratio(feats, sel, args.k,
                                            kernel=args.kernel))
  elif not args.no_coverage:
    done["coverage"] = "skipped"
  emit("done", **done)

  if args.stats_json:
    obs.write_stats_json(args.stats_json, records,
                         tool="repro.launch.select", n=args.n, d=args.d,
                         k=args.k, mesh=args.mesh, epochs=args.epochs)
    print(f"[select] wrote {args.stats_json}")
  if sidecar is not None:
    if args.linger > 0:
      time.sleep(args.linger)
    sidecar.close()


if __name__ == "__main__":
  main()
