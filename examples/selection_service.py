"""Streaming selection service -> trainer loop (the serving-shaped regime).

``train_with_selection.py`` closes the paper's loop once: select a coreset,
train on it.  This example runs the loop the way a production trainer
consumes it (docs/service.md): a long-lived ``SelectionService`` owns the
mesh and the compiled GreeDi protocol, the corpus STREAMS in while training
is already underway, and every epoch re-randomizes the partition and
re-selects with warm-started lazy bounds -- the propose/select regime of
``launch/train.py`` (kappa proposals per machine, k_final selected), at
example scale:

  1. create the service; append the first half of the corpus;
  2. per epoch: ``service.epoch`` streams ``sel_gids`` + stats, the trainer
     consumes ``steps_per_epoch`` batches over that coreset
     (``data/pipeline.batches_from_epochs``);
  3. after the first epoch the remaining documents arrive (``append``);
     epoch 2 selects over the grown ground set without re-tracing;
  4. a shard "dies" before the last epoch (its heartbeat stops); the
     protocol detects it, masks it out, and selection continues.

    PYTHONPATH=src python examples/selection_service.py [--epochs 3]

Run with --mesh 4 to shard selection over forced host devices.
"""
import argparse
import os
import time


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument("--epochs", type=int, default=3)
  ap.add_argument("--steps-per-epoch", type=int, default=30)
  ap.add_argument("--batch", type=int, default=8)
  ap.add_argument("--coreset", type=int, default=128)
  ap.add_argument("--mesh", type=int, default=0,
                  help="devices of the sharded service (forced host devices "
                  "on CPU)")
  args = ap.parse_args()

  if args.mesh:
    flag = f"--xla_force_host_platform_device_count={args.mesh}"
    os.environ["XLA_FLAGS"] = \
        f"{os.environ.get('XLA_FLAGS', '')} {flag}".strip()

  import jax
  import numpy as np

  from repro.configs import get_config, reduced
  from repro.data.pipeline import EmbeddedCorpus, batches_from_epochs
  from repro.models import Parallelism, build_model
  from repro.service import SelectionService
  from repro.train.optimizer import OptConfig, init_opt_state
  from repro.train.train_step import make_train_step
  from repro.util import make_mesh

  cfg = reduced(get_config("qwen3-4b"))
  seq_len = 64
  corpus = EmbeddedCorpus(n_docs=2048, feat_dim=64, vocab=cfg.vocab,
                          seq_len=seq_len, n_clusters=48)
  feats = np.asarray(corpus.features())
  n_half = corpus.n_docs // 2

  if len(jax.devices()) < max(args.mesh, 1):
    raise SystemExit(f"--mesh {args.mesh} needs {args.mesh} devices; the "
                     f"{jax.default_backend()} backend has "
                     f"{len(jax.devices())}")
  mesh = make_mesh((max(args.mesh, 1),), ("data",))
  # the propose/select regime of launch/train.py, at example scale: each
  # machine proposes kappa, the merge selects k_final
  svc = SelectionService(mesh, d=64, kappa=args.coreset // 2,
                         k_final=args.coreset, capacity=corpus.n_docs,
                         deadline=30.0)
  svc.append(feats[:n_half])
  print(f"[service] ingested {n_half}/{corpus.n_docs} docs; "
        f"training starts while the rest embeds")

  model = build_model(cfg, remat=None)
  par = Parallelism(dp_axes=(), dp_size=0)
  params = model.init(jax.random.PRNGKey(42))
  opt = init_opt_state(params)
  total = args.epochs * args.steps_per_epoch
  step_fn = jax.jit(make_train_step(
      model, OptConfig(lr=1e-3, warmup_steps=max(total // 10, 5),
                       total_steps=total), par))

  def selections():
    for e in range(args.epochs):
      if e == 1:
        svc.append(feats[n_half:])   # the rest of the corpus arrived
        print(f"[service] appended {corpus.n_docs - n_half} docs")
      if e == args.epochs - 1 and svc.board.m > 1:
        svc.board.fail(svc.board.m - 1)   # a shard dies mid-run
        print("[service] shard "
              f"{svc.board.m - 1} stopped heartbeating")
      res = svc.epoch()
      s = res.stats
      print(f"[service] epoch {s.epoch}: {len(res.sel_gids)} docs from "
            f"{s.n_live} live, f={s.value:.4f}, "
            f"alive={int(s.alive.sum())}/{len(s.alive)}, "
            f"{s.wall_s:.2f}s, traces={s.retraces}")
      yield res.sel_gids

  t0 = time.time()
  # the trainer's data-fetch cadence IS the liveness signal: every batch
  # fetched below beats the board (board=..., docs/service.md), so healthy
  # consumption keeps every shard alive and the staged board.fail above is
  # the only way a shard goes dark.  One registration beat before the first
  # epoch covers the model-build gap since service construction.
  svc.board.beat()
  for step, batch in enumerate(batches_from_epochs(
      corpus, selections(), args.batch, args.steps_per_epoch,
      board=svc.board)):
    params, opt, metrics = step_fn(params, opt, batch)
    if step % 10 == 0 or step == total - 1:
      print(f"[train] step {step:4d} loss {float(metrics['loss']):.4f} "
            f"({time.time()-t0:.0f}s)", flush=True)
  # one trace per capacity actually selected at (multiple doublings between
  # epochs compile fewer times than 1 + growths)
  assert svc.retrace_count <= 1 + svc.growths, \
      "epochs re-traced the protocol"
  print(f"[done] {args.epochs} epochs, {total} steps, "
        f"{svc.retrace_count} protocol trace(s)")


if __name__ == "__main__":
  main()
