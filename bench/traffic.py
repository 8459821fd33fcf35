"""The general traffic generator: a mix file of parameters in, a fixed
request schedule out, and the open loop that offers it.

Every seed gets the same set of inter-arrival gaps and requests (drawn from
the mix's own ``traffic_seed``); the run's seed only changes their order, so
runs of different seeds do the same work.
"""
from __future__ import annotations

import contextlib
import dataclasses
import threading
import time

import numpy as np

from bench import seeds


@dataclasses.dataclass(frozen=True)
class Tenant:
  seed: int                 # tie-break seed (nonzero)
  exclude: tuple            # gids this tenant may never see


@dataclasses.dataclass
class Schedule:
  due_s: np.ndarray         # (n,) seconds after the window opens, sorted
  tenant: np.ndarray        # (n,) tenant index
  k: np.ndarray             # (n,) requested coreset size


def tenants(mix: dict, popular_gids: np.ndarray) -> list:
  """The mix's tenant population: each with a nonzero seed and a fixed
  exclusion list of 0..``excl_max`` gids drawn from ``popular_gids`` (the
  rows most answers come from, so the filters bite)."""
  r = np.random.default_rng(int(mix["traffic_seed"]))
  out = []
  for _ in range(int(mix["tenants"])):
    e = int(r.integers(0, int(mix["excl_max"]) + 1))
    excl = r.choice(popular_gids, size=min(e, popular_gids.size),
                    replace=False)
    out.append(Tenant(int(r.integers(1, 1 << 30)),
                      tuple(sorted(int(g) for g in excl))))
  return out


def schedule(mix: dict, seconds: float, seed: int) -> Schedule:
  """``rate_per_s * seconds`` Poisson arrivals, their gaps scaled so the
  last is due as the window closes; tenants drawn Zipf(``tenant_zipf``)
  over the population; k uniform in [``k_min``, ``k_max``].  The gaps and
  requests are the mix's own; the seed shuffles them."""
  rate = float(mix["rate_per_s"])
  n = max(int(round(rate * seconds)), 1)
  fixed = np.random.default_rng([int(mix["traffic_seed"]), 1])
  gaps = fixed.exponential(1.0 / rate, n)
  gaps *= seconds / gaps.sum()
  nt = int(mix["tenants"])
  p = np.arange(1, nt + 1, dtype=np.float64) ** -float(mix["tenant_zipf"])
  who = fixed.choice(nt, size=n, p=p / p.sum())
  ks = fixed.integers(int(mix["k_min"]), int(mix["k_max"]) + 1, n)
  order = seeds.rng(seed, seeds.TRAFFIC_ORDER).permutation(n)
  due = np.cumsum(gaps[order]) - gaps[order][0]
  return Schedule(due, who[order], ks[order])


class OpenLoop:
  """Offers a schedule on time, whatever the server does.

  Requests due by the current tick are submitted together (at most
  ``group`` per pass, so the loop never holds the interpreter for long);
  then the loop sleeps until the next is due, at most ``tick_s``.  A
  request's latency runs from when it was DUE to when its future resolved,
  so a stall of the server or of this loop counts against every request it
  delays.  ``lateness_s`` is how late each submission left.
  """

  def __init__(self, due_s: np.ndarray, tick_s: float = 0.001,
               group: int = 32, span=None):
    self.due_s = np.asarray(due_s, np.float64)
    self.tick_s = tick_s
    self.group = group
    self.span = span
    n = self.due_s.shape[0]
    self.lateness_s = np.full((n,), np.nan)
    self.done_at = np.full((n,), np.nan)
    self.futures: list = [None] * n
    self.errors = 0
    self._lock = threading.Lock()
    self.t0 = 0.0

  def _resolved(self, i: int, fut) -> None:
    t = time.perf_counter()
    with self._lock:
      self.done_at[i] = t - self.t0
      if fut.exception() is not None:
        self.errors += 1

  def run(self, submit, make_request) -> None:
    """Submit request i (``make_request(i)``) through ``submit`` at its due
    time; returns when the last one has been submitted."""
    n = self.due_s.shape[0]
    self.t0 = time.perf_counter()
    i = 0
    while i < n:
      now = time.perf_counter() - self.t0
      if self.due_s[i] > now:
        time.sleep(min(self.due_s[i] - now, self.tick_s))
        continue
      j = i
      while j < n and j - i < self.group and self.due_s[j] <= now:
        j += 1
      with (self.span("bench.submit") if self.span
            else contextlib.nullcontext()):
        for r in range(i, j):
          self.lateness_s[r] = time.perf_counter() - self.t0 - self.due_s[r]
          fut = submit(make_request(r))
          self.futures[r] = fut
          fut.add_done_callback(lambda f, r=r: self._resolved(r, f))
      i = j

  def wait(self, timeout_s: float) -> int:
    """Wait up to ``timeout_s`` for every future; returns how many never
    resolved."""
    end = time.perf_counter() + timeout_s
    missing = 0
    for f in self.futures:
      left = end - time.perf_counter()
      try:
        f.result(timeout=max(left, 0.0))
      except Exception:  # noqa: BLE001 -- counted below, by resolution
        pass
      if not f.done():
        missing += 1
    return missing

  def latency_s(self) -> np.ndarray:
    """Per-request latency from due time to resolution (nan: never)."""
    with self._lock:
      return self.done_at - self.due_s
