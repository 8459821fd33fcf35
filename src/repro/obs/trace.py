"""Structured trace spans: the single timing source of the service layer.

``span("service.epoch", epoch=3)`` is a context manager that ALWAYS

  * measures wall clock (``Span.wall_s`` after exit -- the service's stats
    dataclasses consume it, so spans replace every ad-hoc
    ``time.perf_counter()`` pair in service.py / batching.py), and
  * opens a ``jax.profiler.TraceAnnotation`` of the span's name, so the span
    lands in a profiler trace next to the device ops it encloses.  With no
    profiler session running the annotation is inactive and costs well
    under a microsecond (docs/observability.md).

Only when observability is *enabled* does a span additionally append one
JSONL record to the configured trace sink::

    {"name", "ts", "dur_s", "pid", "tid", "parent", "attrs"}

``ts`` is the span's start on the clock the profiler stamps host events
with (the realtime clock, in seconds since the epoch), so a record lines up
with its annotation in the ``.xplane.pb`` host plane; ``parent`` is the
name of the enclosing span on the same thread (None at the top).

Enable/disable is process-global::

    obs.enable(trace_out="/tmp/trace.jsonl")   # or enable() for metrics-only
    with obs.span("service.epoch", epoch=i) as sp:
        ...
    stats.wall_s = sp.wall_s

A span never touches the device: callers that need device-synced timing
keep their own ``jax.block_until_ready`` inside the span, exactly as the
service epoch does.

``add(**attrs)`` attaches attributes discovered mid-span (e.g. the batch
occupancy a drain only knows after collecting); they merge into the JSONL
record at exit.
"""
from __future__ import annotations

import json
import os
import threading
import time
from typing import IO

import jax.profiler

_STATE_LOCK = threading.Lock()
_ENABLED = False
_TRACE_PATH: str | None = None
_TRACE_FILE: IO | None = None
_TRACE_WRITE_LOCK = threading.Lock()
_LOCAL = threading.local()   # .stack: names of this thread's open spans


def enable(trace_out: str | None = None) -> None:
  """Turn observability on (idempotent).  ``trace_out`` adds a JSONL span
  sink (opened lazily, appended, one record per line)."""
  global _ENABLED, _TRACE_PATH, _TRACE_FILE
  with _STATE_LOCK:
    _ENABLED = True
    if trace_out is not None and trace_out != _TRACE_PATH:
      if _TRACE_FILE is not None:
        _TRACE_FILE.close()
      _TRACE_PATH = trace_out
      _TRACE_FILE = None


def disable() -> None:
  """Turn observability off and close any open trace sink."""
  global _ENABLED, _TRACE_PATH, _TRACE_FILE
  with _STATE_LOCK:
    _ENABLED = False
    if _TRACE_FILE is not None:
      _TRACE_FILE.close()
    _TRACE_FILE = None
    _TRACE_PATH = None


def enabled() -> bool:
  return _ENABLED


def trace_out_path() -> str | None:
  return _TRACE_PATH


def _emit(record: dict) -> None:
  global _TRACE_FILE
  with _TRACE_WRITE_LOCK:
    if _TRACE_PATH is None:
      return
    if _TRACE_FILE is None:
      _TRACE_FILE = open(_TRACE_PATH, "a", buffering=1)
    _TRACE_FILE.write(json.dumps(record, sort_keys=True) + "\n")


def _stack() -> list:
  stack = getattr(_LOCAL, "stack", None)
  if stack is None:
    stack = _LOCAL.stack = []
  return stack


class Span:
  """One timed region; see module docstring.  Not reentrant."""

  __slots__ = ("name", "attrs", "wall_s", "_t0", "_ts", "_ann", "_emitting",
               "_parent")

  def __init__(self, name: str, attrs: dict):
    self.name = name
    self.attrs = attrs
    self.wall_s = 0.0
    self._t0 = 0.0
    self._ts = 0.0
    self._ann = None
    self._emitting = False
    self._parent = None

  def add(self, **attrs) -> None:
    """Attach attributes discovered mid-span (merged into the record)."""
    self.attrs.update(attrs)

  def __enter__(self) -> "Span":
    self._emitting = _ENABLED  # latch: enablement mid-span doesn't half-emit
    stack = _stack()
    self._parent = stack[-1] if stack else None
    stack.append(self.name)
    if self._emitting:
      self._ts = time.time_ns() * 1e-9
    self._ann = jax.profiler.TraceAnnotation(self.name)
    self._ann.__enter__()
    self._t0 = time.perf_counter()
    return self

  def __exit__(self, *exc) -> None:
    self.wall_s = time.perf_counter() - self._t0
    self._ann.__exit__(*exc)
    self._ann = None
    _stack().pop()
    if self._emitting:
      _emit({"name": self.name, "ts": self._ts, "dur_s": self.wall_s,
             "pid": os.getpid(), "tid": threading.get_ident(),
             "parent": self._parent, "attrs": self.attrs})


def span(name: str, **attrs) -> Span:
  """Open a timed span: ``with obs.span("service.query", tier="sieve"):``."""
  return Span(name, attrs)
