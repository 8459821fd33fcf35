"""Read the program's own spans and scopes out of a profiler trace.

``bench/tracing.py`` reduces a traced window to device busy time, op time
and idle gaps by ``bench.*`` span.  This module adds what the program
itself puts in the trace (docs/observability.md):

* host spans: every ``repro.obs`` span is a profiler annotation named
  ``batcher.*``, ``service.*`` or ``store.*`` on the host plane, on the line
  of the thread that opened it;
* device scopes: ``jax.named_scope`` names (``greedi.*``, ``store.*``) in
  each op's HLO ``op_name`` metadata.  On the TPU the op event does not
  carry it: its event metadata in the ``.xplane.pb`` does, as the
  ``tf_op`` stat (``jit(_epoch)/.../greedi.round1/.../pallas_call:``), which
  ``jax.profiler.ProfileData`` does not expose; ``load_op_scopes`` reads it
  from the file's protobuf wire format.  ``while`` ops carry no ``tf_op``:
  their time is covered by the scoped ops they contain.

It works on the plain trace form of ``bench/tracing.py`` plus a mapping
from op event name to its ``op_name``, so it is tested on the CPU against
a hand-made trace (``test_bench_program_trace.py``).
"""
from __future__ import annotations

import bisect
import dataclasses
import re

from bench import tracing

SPAN_PREFIXES = ("bench.", "batcher.", "service.", "store.")
SCOPE = re.compile(r"\b(?:greedi|store)\.\w+")
UNANNOTATED = "host:unannotated"


# ---- the .xplane.pb wire format, as far as op metadata needs it ------------

def _varint(buf: bytes, i: int) -> tuple[int, int]:
  out, shift = 0, 0
  while True:
    b = buf[i]
    i += 1
    out |= (b & 0x7F) << shift
    if b < 0x80:
      return out, i
    shift += 7


def _fields(buf: bytes, lo: int = 0, hi: int | None = None):
  """(field number, value) of one message: ints for varints, (start, end)
  offsets for length-delimited fields; fixed-width fields are skipped."""
  i, hi = lo, len(buf) if hi is None else hi
  while i < hi:
    tag, i = _varint(buf, i)
    field, wire = tag >> 3, tag & 7
    if wire == 0:
      v, i = _varint(buf, i)
      yield field, v
    elif wire == 2:
      n, i = _varint(buf, i)
      yield field, (i, i + n)
      i += n
    elif wire == 1:
      i += 8
    elif wire == 5:
      i += 4
    else:
      raise ValueError(f"unsupported protobuf wire type {wire}")


def _str(buf: bytes, span: tuple[int, int]) -> str:
  return buf[span[0]:span[1]].decode("utf-8", "replace")


def op_scopes_from_bytes(buf: bytes) -> dict:
  """``{op event name: op_name}`` of the device planes of a serialized
  XSpace (XSpace.planes = 1; XPlane.name = 2, event_metadata = 4,
  stat_metadata = 5; XEventMetadata.name = 2, stats = 5; XStat.metadata_id
  = 1, str_value = 5, ref_value = 7; XStatMetadata.name = 2)."""
  out: dict = {}
  for f, plane in _fields(buf):
    if f != 1:
      continue
    name, events, stat_names = "", [], {}
    for pf, v in _fields(buf, *plane):
      if pf == 2:
        name = _str(buf, v)
      elif pf in (4, 5):
        entry = dict(_fields(buf, *v))
        if 2 not in entry:
          continue
        if pf == 4:
          events.append(entry[2])
        else:
          meta = dict(_fields(buf, *entry[2]))
          if 2 in meta:
            stat_names[entry.get(1, 0)] = _str(buf, meta[2])
    if not re.fullmatch(r"/device:TPU:\d+", name):
      continue
    tf_op = [k for k, n in stat_names.items() if n == "tf_op"]
    if not tf_op:
      continue
    for ev in events:
      meta = dict(_fields(buf, *ev))
      ev_name = _str(buf, meta[2]) if 2 in meta else ""
      for sf, stat in _fields(buf, *ev):
        if sf != 5:
          continue
        st = dict(_fields(buf, *stat))
        if st.get(1) != tf_op[0]:
          continue
        if 5 in st:
          out.setdefault(ev_name, _str(buf, st[5]))
        elif 7 in st:
          out.setdefault(ev_name, stat_names.get(st[7], ""))
  return out


def load_op_scopes(path: str) -> dict:
  """``op_scopes_from_bytes`` of a profiler ``.xplane.pb`` file."""
  with open(path, "rb") as f:
    return op_scopes_from_bytes(f.read())


def scope_of(op_name: str | None) -> str | None:
  """Outermost program scope of an ``op_name`` path, or None.  A scope
  may sit inside a transform's name: ``vmap(store.query_merge)``."""
  m = SCOPE.search(op_name or "")
  return m.group(0) if m else None


# ---- the reduction ----------------------------------------------------------

def host_spans(planes: list[dict]) -> list[tuple[str, float, float, str]]:
  """``(name, start_ns, end_ns, thread)`` of every program and ``bench.*``
  span on the host planes; ``thread`` names the line it ran on."""
  out = []
  for p in planes:
    if p["name"].startswith("/host:"):
      for ln in p["lines"]:
        thread = f"{p['name']}/{ln['name']}"
        out += [(n, s, s + d, thread) for n, s, d in ln["events"]
                if n.startswith(SPAN_PREFIXES)]
  return out


@dataclasses.dataclass
class ProgramReduction:
  """Device time by program scope and idle time by program span, inside
  ``bench.window`` (seconds; device numbers as in ``tracing.Reduction``)."""
  window_s: float
  busy_s: float         # union of all ops, per device (as tracing.py)
  devices: int
  scope_iv: dict        # scope -> per device, its ops' merged intervals (ns)
  gaps: dict            # innermost open span -> idle seconds per device
  spans: list           # host spans wholly inside the window

  def scope_s(self, *scopes: str) -> float:
    """Seconds, summed over devices, under any of ``scopes``: the union of
    their ops' intervals on each device, so a ``while`` and the kernels it
    contains count once."""
    total = 0.0
    for dev in range(self.devices):
      iv = [x for sc in scopes if sc in self.scope_iv
            for x in self.scope_iv[sc][dev]]
      total += sum(e - s for s, e in tracing.union(iv)) * 1e-9
    return total

  def scopes(self) -> list[str]:
    return sorted(self.scope_iv)

  def coverage_pct(self, *scopes: str) -> float:
    """Share (%) of the device busy time under ``scopes`` (all by default)."""
    busy = self.busy_s * self.devices
    return 100.0 * self.scope_s(*(scopes or self.scopes())) / busy

  def scope_ms(self, scopes, per) -> float | None:
    """Device ms per chip and per unit of work under ``scopes``; None when
    no op carried them (a program without the scopes) or no work ran."""
    if not per or not any(sc in self.scope_iv for sc in scopes):
      return None
    return 1e3 * self.scope_s(*scopes) / self.devices / per

  def drain_split_ms(self) -> tuple[float, float, int] | None:
    """Mean per ``batcher.drain`` of (host ms: the drain minus its
    ``store.query_call`` spans on the same thread; call ms) and the drains
    counted; None without drains."""
    drains = [x for x in self.spans if x[0] == "batcher.drain"]
    if not drains:
      return None
    calls = [x for x in self.spans if x[0] == "store.query_call"]
    host = call = 0.0
    for _, s, e, th in drains:
      c = sum(ce - cs for _, cs, ce, cth in calls
              if cth == th and cs >= s and ce <= e)
      host += (e - s) - c
      call += c
    n = len(drains)
    return 1e-6 * host / n, 1e-6 * call / n, n


def _idle_by_span(gs: float, ge: float, spans: list, gaps: dict) -> None:
  """Split the idle gap [gs, ge) at the bounds of the spans open in it;
  each piece goes to the innermost span open over all of it (of those
  open, the one opened last), a piece under none to ``UNANNOTATED``."""
  over = [x for x in spans if x[1] < ge and x[2] > gs]
  cuts = sorted({gs, ge} | {t for x in over for t in x[1:3] if gs < t < ge})
  for a, b in zip(cuts, cuts[1:]):
    open_ = [x for x in over if x[1] <= a and x[2] >= b]
    label = max(open_, key=lambda x: x[1])[0] if open_ else UNANNOTATED
    gaps[label] = gaps.get(label, 0.0) + (b - a) * 1e-9


def reduce_program(planes: list[dict], op_scopes: dict) -> ProgramReduction:
  """Scope time and idle time by innermost span in the window of
  ``planes``; ``op_scopes`` maps op event names to their ``op_name``."""
  spans = host_spans(planes)
  windows = [(s, e) for n, s, e, _ in spans if n == tracing.WINDOW_SPAN]
  if not windows:
    raise ValueError(f"trace has no {tracing.WINDOW_SPAN!r} host span")
  lo, hi = windows[0]
  inner = sorted((x for x in spans
                  if x[0] != tracing.WINDOW_SPAN and x[2] > lo and x[1] < hi),
                 key=lambda x: x[1])
  starts = [x[1] for x in inner]
  longest = max((x[2] - x[1] for x in inner), default=0.0)
  per_dev: list[dict] = []
  gaps: dict = {}
  busy_total = 0.0
  for plane in tracing._device_planes(planes):
    ops = [(n, s, e) for n, s, e in tracing._ops(plane) if e > lo and s < hi]
    if not ops:
      continue
    by_scope: dict = {}
    for text, s, e in ops:
      sc = scope_of(op_scopes.get(text))
      if sc is not None:
        by_scope.setdefault(sc, []).append((max(s, lo), min(e, hi)))
    per_dev.append({sc: tracing.union(iv) for sc, iv in by_scope.items()})
    busy = tracing._clip(tracing.union([(s, e) for _, s, e in ops]), lo, hi)
    busy_total += sum(e - s for s, e in busy) * 1e-9
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    for gs, ge in zip(edges[::2], edges[1::2]):
      if ge > gs:
        # only spans that start after gs - longest can reach the gap
        near = inner[bisect.bisect_left(starts, gs - longest):
                     bisect.bisect_left(starts, ge)]
        _idle_by_span(gs, ge, near, gaps)
  if not per_dev:
    raise ValueError("no device operation ran inside the window")
  n_dev = len(per_dev)
  names = sorted({sc for d in per_dev for sc in d})
  scope_iv = {sc: [d.get(sc, []) for d in per_dev] for sc in names}
  return ProgramReduction(
      window_s=(hi - lo) * 1e-9, busy_s=busy_total / n_dev, devices=n_dev,
      scope_iv=scope_iv, gaps={k: v / n_dev for k, v in gaps.items()},
      spans=[x for x in inner if x[1] >= lo and x[2] <= hi])


def layer_metrics(red: ProgramReduction, counters: dict) -> dict:
  """The per-layer metrics the program's spans, scopes and counters give,
  by name, for what this run holds: ``counters`` are the driver's
  (``epochs``, ``chunks``), plus ``queue_wait_ms``, the window's mean of
  ``repro_batcher_queue_wait_seconds`` where a driver takes it."""
  out = {}
  epochs, chunks = counters.get("epochs"), counters.get("chunks")
  for name, scopes, per in (
      ("round1_ms.epoch", ("greedi.round1",), epochs),
      ("round2_ms.epoch", ("greedi.merge", "greedi.round2"), epochs),
      ("sieve_scan_ms.ingest", ("store.sieve_scan",), chunks)):
    v = red.scope_ms(scopes, per)
    if v is not None:
      out[name] = v
  split = red.drain_split_ms()
  if split is not None:
    out["drain_host_ms.query"] = split[0]
  if counters.get("queue_wait_ms") is not None:
    out["queue_wait_ms.query"] = counters["queue_wait_ms"]
  return out
