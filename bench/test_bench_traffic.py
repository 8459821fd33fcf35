"""The open-loop load generator: fixed schedules, latency from the due
time, and lateness."""
import threading
import time
from concurrent.futures import Future

import numpy as np

from bench import traffic

MIX = {"kind": "tenant_queries", "rate_per_s": 400, "tenants": 50,
       "tenant_zipf": 1.1, "k_min": 8, "k_max": 64, "excl_max": 16,
       "traffic_seed": 11}
POPULAR = np.arange(100, 300)


def test_same_seed_same_schedule_and_requests():
  a = traffic.schedule(MIX, 5.0, 2 ** 31 + 17)
  b = traffic.schedule(MIX, 5.0, 2 ** 31 + 17)
  for f in ("due_s", "tenant", "k"):
    np.testing.assert_array_equal(getattr(a, f), getattr(b, f))
  ta, tb = traffic.tenants(MIX, POPULAR), traffic.tenants(MIX, POPULAR)
  assert ta == tb and len(ta) == MIX["tenants"]
  assert all(t.seed != 0 and len(t.exclude) <= 16 for t in ta)
  assert all(set(t.exclude) <= set(POPULAR.tolist()) for t in ta)
  assert np.all(np.diff(a.due_s) > 0) and a.due_s[0] == 0.0
  assert a.due_s[-1] < 5.0 and a.due_s.shape[0] == 2000


def test_another_seed_reorders_the_same_work():
  a = traffic.schedule(MIX, 30.0, 5)
  b = traffic.schedule(MIX, 30.0, 6)
  assert not np.array_equal(a.k, b.k)
  np.testing.assert_array_equal(np.sort(a.k), np.sort(b.k))
  np.testing.assert_array_equal(np.sort(a.tenant), np.sort(b.tenant))
  assert a.due_s.shape == b.due_s.shape
  np.testing.assert_allclose(np.sort(np.diff(a.due_s)),
                             np.sort(np.diff(b.due_s)), atol=1e-3)


class SerialServer:
  """Answers requests one at a time in a worker thread, 1 ms each; the
  request numbered ``stall_at`` holds the worker for ``stall_s``."""

  def __init__(self, stall_at: int, stall_s: float):
    self.q: list = []
    self.cv = threading.Condition()
    self.stall_at, self.stall_s = stall_at, stall_s
    self.closed = False
    self.t = threading.Thread(target=self._loop, daemon=True)
    self.t.start()

  def submit(self, i):
    f = Future()
    with self.cv:
      self.q.append((i, f))
      self.cv.notify()
    return f

  def _loop(self):
    while True:
      with self.cv:
        while not self.q and not self.closed:
          self.cv.wait()
        if not self.q:
          return
        i, f = self.q.pop(0)
      time.sleep(self.stall_s if i == self.stall_at else 0.001)
      f.set_result(i)

  def close(self):
    with self.cv:
      self.closed = True
      self.cv.notify()
    self.t.join(timeout=10)
    assert not self.t.is_alive()


def test_latency_counts_from_the_due_time_so_a_stall_delays_later_requests():
  due = np.arange(40) * 0.005
  server = SerialServer(stall_at=10, stall_s=0.25)
  loop = traffic.OpenLoop(due, tick_s=0.001)
  loop.run(server.submit, lambda i: i)
  assert loop.wait(10.0) == 0
  server.close()
  lat = loop.latency_s()
  assert np.all(np.isfinite(lat))
  assert np.all(lat[:10] < 0.1)
  # request 11 is due 5 ms after the stall began and waits out the rest
  assert lat[11] > 0.2
  assert np.all(lat[11:20] > 0.15)
  assert loop.errors == 0


def test_lateness_of_a_starved_generator_is_reported():
  due = np.arange(30) * 0.002
  calls = []

  def slow_submit(i):
    calls.append(i)
    if i == 5:
      time.sleep(0.1)            # the generator itself is held up
    f = Future()
    f.set_result(i)
    return f

  loop = traffic.OpenLoop(due, tick_s=0.001, group=1)
  loop.run(slow_submit, lambda i: i)
  assert calls == list(range(30))
  late = loop.lateness_s
  assert np.all(late >= 0)
  assert late[6] > 0.05 and late[:5].max() < 0.05
  # and the requests it delayed carry that wait in their latency
  assert loop.latency_s()[6] >= late[6]
