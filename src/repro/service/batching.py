"""Micro-batching request loop for the multi-tenant query path.

``SelectionService.query_batch`` amortizes one device scan across a whole
request batch -- but tenants don't arrive in batches, they arrive one at a
time.  ``QueryBatcher`` is the serving loop that turns the former into the
latter (the same accumulate/drain shape as ``serve/serve_step.generate``'s
token loop, applied to selection requests): ``submit()`` enqueues a
``QueryRequest`` and returns a future; a background worker drains the queue
through ONE ``query_batch`` call whenever

  * ``max_batch`` requests have accumulated (the store's compiled query
    tile by default -- a full tile is the highest-throughput drain), or
  * ``max_delay_s`` has passed since the worker, free again, found a
    request pending (the latency SLO knob).  The deadline starts when the
    worker wakes, not when the oldest request was submitted, so a request
    that arrives during a drain waits out the rest of that drain and then
    the whole deadline; ``repro_batcher_queue_wait_seconds`` records what
    each request waited before its drain started.

Every request in a drained batch observes the same wall clock (that is what
``QueryResult.wall_s`` reports), so the p50/p95 latency surface of the
service is the drain wall distribution -- benchmarked against the
sequential loop in benchmarks/query_serving.py (BENCH_7.json) and
contracted in docs/service.md "Multi-tenant serving".
"""
from __future__ import annotations

import dataclasses
import threading
import time
from concurrent.futures import Future

from repro import obs
from repro.service.service import QueryRequest


@dataclasses.dataclass
class BatcherStats:
  """Operational counters of one ``QueryBatcher`` lifetime."""
  submitted: int = 0    # requests accepted by submit()
  served: int = 0       # requests resolved (results or errors)
  batches: int = 0      # query_batch drains
  max_occupancy: int = 0  # largest drained batch (<= max_batch)

  @property
  def mean_occupancy(self) -> float:
    return self.served / self.batches if self.batches else 0.0


class QueryBatcher:
  """Accumulate-until-B-or-deadline micro-batcher over ``query_batch``.

  Thread-based (the drain is one blocking device call; jax releases the
  GIL, so submitters keep enqueueing while a batch is in flight).  Use as a
  context manager or call ``close()`` -- pending requests are drained, not
  dropped, on close.

  Args:
    service: the ``SelectionService`` to drain through.
    max_batch: drain threshold; None = the store's compiled query tile
      (bigger values still work -- the store chunks by tile).
    max_delay_s: the latency SLO knob -- how long the free worker waits
      for a full tile, once a request is pending, before a (possibly
      partial) drain.
    tier: forwarded to ``query_batch`` ("sieve" | "exact").
  """

  def __init__(self, service, *, max_batch: int | None = None,
               max_delay_s: float = 0.002, tier: str = "sieve"):
    self._svc = service
    self._max_batch = int(max_batch or service.store.query_batch_tile)
    if self._max_batch <= 0:
      raise ValueError(f"max_batch must be positive, got {self._max_batch}")
    self._max_delay = float(max_delay_s)
    self._tier = tier
    self._cv = threading.Condition()
    # (request, future, submit time on the perf_counter clock)
    self._pending: list[tuple[QueryRequest, Future, float]] = []
    self._closed = False
    self.stats = BatcherStats()
    self._thread = threading.Thread(target=self._loop, daemon=True,
                                    name="repro-query-batcher")
    self._thread.start()

  def submit(self, request: QueryRequest | None = None) -> Future:
    """Enqueue one request; the returned future resolves to its
    ``QueryResult`` after the batch it rides in drains."""
    req = request if request is not None else QueryRequest()
    fut: Future = Future()
    with self._cv:
      if self._closed:
        raise RuntimeError("QueryBatcher is closed")
      self._pending.append((req, fut, time.perf_counter()))
      self.stats.submitted += 1
      self._cv.notify()
    return fut

  def _loop(self) -> None:
    reg = obs.REGISTRY
    while True:
      with self._cv:
        while not self._pending and not self._closed:
          self._cv.wait()
        if not self._pending and self._closed:
          return
        # the deadline runs from now, when the worker found a request
        # pending: wait for a full tile, but never past the SLO
        with obs.span("batcher.wait"):
          deadline = time.perf_counter() + self._max_delay
          while len(self._pending) < self._max_batch and not self._closed:
            left = deadline - time.perf_counter()
            if left <= 0:
              break
            self._cv.wait(timeout=left)
        batch = self._pending[:self._max_batch]
        del self._pending[:self._max_batch]
      with obs.span("batcher.drain", tier=self._tier,
                    occupancy=len(batch)) as sp:
        t0 = time.perf_counter()
        reg.histogram("repro_batcher_queue_wait_seconds",
                      "per request: submit to the start of the drain it "
                      "rides in").observe_many([t0 - t for _, _, t in batch])
        try:
          results = self._svc.query_batch([r for r, _, _ in batch],
                                          tier=self._tier)
          error = None
        except Exception as e:  # a bad request poisons only its own batch
          error = e
        with obs.span("batcher.resolve"):
          for i, (_, fut, _) in enumerate(batch):
            if error is None:
              fut.set_result(results[i])
            else:
              fut.set_exception(error)
          self.stats.batches += 1
          self.stats.served += len(batch)
          self.stats.max_occupancy = max(self.stats.max_occupancy,
                                         len(batch))
          reg.counter("repro_batcher_requests_total",
                      "requests drained by the micro-batcher").inc(len(batch))
          reg.counter("repro_batcher_batches_total",
                      "micro-batch drains").inc()
      reg.histogram("repro_batcher_drain_wall_seconds",
                    "wall clock of one drain (the request latency "
                    "surface)").observe(sp.wall_s)

  def close(self) -> None:
    """Stop accepting requests, drain what's pending, join the worker."""
    with self._cv:
      self._closed = True
      self._cv.notify_all()
    self._thread.join()

  def __enter__(self) -> "QueryBatcher":
    return self

  def __exit__(self, *exc) -> None:
    self.close()
