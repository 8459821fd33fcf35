"""The comparison catches a broken timed path: each fault is planted in the
program underneath a CPU-size run, and ``correct`` must come out false."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import run as R
from bench import tiny
from repro.core import greedi as GD
from repro.service import store as S
from repro.service.service import SelectionService

SEED = 2 ** 31 + 777


def _run(cell, seconds=1.0):
  return R.run(cell, SEED, seconds, False, jax.devices(),
               t_start=time.perf_counter())


def _fails(cell, seconds=1.0):
  res = _run(cell, seconds)
  assert not res["correct"], res["checks"]
  return res["checks"]


# ---- epochs ----------------------------------------------------------------

def test_epoch_with_half_the_rows_left_out(monkeypatch):
  orig = GD.greedi_sharded

  def half(feats, *, gids=None, **kw):
    keep = jnp.arange(gids.shape[0]) % 2 == 0
    return orig(feats, gids=jnp.where(keep, gids, -1), **kw)

  monkeypatch.setattr(GD, "greedi_sharded", half)
  checks = _fails(tiny.cell("tiny-epoch"))
  assert checks["greedy_gap"]["value"] > checks["greedy_gap"]["limit"]


def test_epoch_answer_altered_where_produced(monkeypatch):
  orig = GD.greedi_sharded

  def altered(feats, *, gids=None, **kw):
    r = orig(feats, gids=gids, **kw)
    g = r.sel_gids
    return r._replace(sel_gids=g.at[0].set((g[0] + 1) % feats.shape[0]))

  monkeypatch.setattr(GD, "greedi_sharded", altered)
  _fails(tiny.cell("tiny-epoch"))


# ---- ingest ----------------------------------------------------------------

def _wrap_append(monkeypatch, wrap):
  orig = S.CorpusStore._compile

  def compile_(self):
    orig(self)
    self._append_fn = wrap(self, self._append_fn)

  monkeypatch.setattr(S.CorpusStore, "_compile", compile_)


def test_append_that_returns_its_state_unchanged(monkeypatch):
  def stale(store, fn):
    m = store._m

    def call(*args):
      zero = jnp.zeros((m,), jnp.int32)
      return tuple(args[:store._n_state]) + (zero, zero)
    return call

  _wrap_append(monkeypatch, stale)
  checks = _fails(tiny.cell("marco-ingest"))
  assert checks["bound_rel"]["value"] > checks["bound_rel"]["limit"]


def test_append_with_half_the_chunk_left_out(monkeypatch):
  def half(store, fn):
    def call(*args):
      args = list(args)
      args[-2] = args[-2] * (np.arange(args[-2].shape[0]) % 2 == 0)
      return fn(*args)
    return call

  _wrap_append(monkeypatch, half)
  _fails(tiny.cell("marco-ingest"))


def test_bound_table_altered_where_produced(monkeypatch):
  orig = S._df_add
  monkeypatch.setattr(S, "_df_add", lambda hi, lo, x: orig(hi, lo, x * 1.001))
  checks = _fails(tiny.cell("marco-ingest"))
  assert checks["bound_rel"]["value"] > checks["bound_rel"]["limit"]


def _wrap_sieve(monkeypatch, wrap):
  """Plant ``wrap`` around the store's sieve admission op, and nowhere
  else: rows, bound table and recorded gains stay as the program makes
  them."""
  orig = S.dispatch.resolve

  def resolve(name, backend="auto"):
    fn = orig(name, backend)
    return wrap(fn) if name == "sieve_update" else fn

  monkeypatch.setattr(S.dispatch, "resolve", resolve)


def _half_of_each_chunk(fn):
  def call(rows, gains, rgids, active, *rest, **kw):
    keep = jnp.arange(active.shape[0]) % 2 == 0
    return fn(rows, gains, rgids, active & keep, *rest, **kw)
  return call


def _thresholds_ignored(fn):
  def call(rows, gains, rgids, active, tau, *rest, **kw):
    return fn(rows, gains, rgids, active, jnp.zeros_like(tau), *rest, **kw)
  return call


@pytest.mark.parametrize("workload", ["marco-ingest", "marco-query"])
@pytest.mark.parametrize("fault", [_half_of_each_chunk, _thresholds_ignored])
def test_sieve_admission_fault(monkeypatch, workload, fault):
  _wrap_sieve(monkeypatch, fault)
  checks = _fails(tiny.cell(workload))
  gap = checks["sieve_admit_gap"]
  assert gap["value"] > gap["limit"], checks
  # only the admission replay sees it: members, gains and bounds hold
  for name in ("sieve_feat_gap", "sieve_gain_rel", "bound_rel"):
    if name in checks:
      assert checks[name]["value"] <= checks[name]["limit"], checks


# ---- queries ---------------------------------------------------------------

def test_query_answers_altered_where_produced(monkeypatch):
  orig = SelectionService.query_batch

  def reversed_(self, requests, tier="sieve"):
    return [r._replace(sel_gids=r.sel_gids[::-1])
            for r in orig(self, requests, tier)]

  monkeypatch.setattr(SelectionService, "query_batch", reversed_)
  _fails(tiny.cell("marco-query"))


def test_query_batch_with_half_the_requests_left_out(monkeypatch):
  orig = S.CorpusStore.query_sieves_batch

  def half(self, ks, exclude, seeds):
    b = len(ks)
    h = (b + 1) // 2
    g, s = orig(self, ks[:h], exclude[:h], seeds[:h])
    idx = np.arange(b) % h
    return g[idx], s[idx]

  monkeypatch.setattr(S.CorpusStore, "query_sieves_batch", half)
  cell = tiny.cell("marco-query")
  cell.traffic["rate_per_s"] = 3000
  _fails(cell)
