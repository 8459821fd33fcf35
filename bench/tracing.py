"""Reduce a profiler trace and the cell's compiled HLO to per-layer numbers.

Everything here works on plain data, so it is tested on the CPU against a
hand-made trace (``test_bench_tracing.py``):

* a trace is a list of planes ``{"name", "lines": [{"name", "events":
  [(name, start_ns, dur_ns), ...]}]}`` (``load_xplane`` turns the
  profiler's ``.xplane.pb`` into that form);
* device time is read from the ``XLA Ops`` line of each ``/device:TPU:<i>``
  plane, host spans from the host plane's ``bench.*`` annotations;
* on the TPU an op's event carries its compiled HLO instruction
  (``%pairwise.3 = f32[...] custom-call(...), ...``): the op is named by the
  instruction's name, and a kernel's work (operations and bytes) comes from
  the operand and result shapes of that custom call, never from the cell's
  configuration, so a later change of tile size cannot make it stale.
"""
from __future__ import annotations

import dataclasses
import json
import pathlib
import re

from bench import kernel_work, registry

OPS_LINE = "XLA Ops"
WINDOW_SPAN = "bench.window"
COLLECTIVE_KINDS = ("all-to-all", "all-gather", "all-reduce",
                    "reduce-scatter", "collective-permute")
_SUFFIX = re.compile(r"\.\d+$")
_SHAPE = re.compile(r"\b(" + "|".join(kernel_work.DTYPE_BYTES)
                    + r")\[([0-9,]*)\]")


def op_name(event: str) -> str:
  """Instruction name of an op event: ``%pairwise.3 = f32[..] ...`` ->
  ``pairwise.3`` (an event that is a bare name is returned as it is)."""
  m = re.match(r"\s*(?:ROOT\s+)?%([\w.\-]+)\s*=", event)
  return m.group(1) if m else event


def stem(name: str) -> str:
  """Op name without its numeric suffix: ``pairwise.8`` -> ``pairwise``."""
  return _SUFFIX.sub("", name)


def load_xplane(path: str) -> list[dict]:
  """The planes of a profiler ``.xplane.pb`` in the plain form above."""
  from jax.profiler import ProfileData
  pd = ProfileData.from_file(path)
  planes = []
  for p in pd.planes:
    lines = []
    for ln in p.lines:
      lines.append({"name": ln.name,
                    "events": [(e.name, float(e.start_ns),
                                float(e.duration_ns)) for e in ln.events]})
    planes.append({"name": p.name, "lines": lines})
  return planes


def find_xplane(trace_dir: str) -> str:
  found = sorted(pathlib.Path(trace_dir).rglob("*.xplane.pb"))
  if not found:
    raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
  return str(found[-1])


def _device_planes(planes: list[dict]) -> list[dict]:
  return [p for p in planes if re.fullmatch(r"/device:TPU:\d+", p["name"])]


def _ops(plane: dict) -> list[tuple[str, float, float]]:
  for ln in plane["lines"]:
    if ln["name"] == OPS_LINE:
      return [(n, s, s + d) for n, s, d in ln["events"]]
  return []


def _host_spans(planes: list[dict]) -> list[tuple[str, float, float]]:
  spans = []
  for p in planes:
    if p["name"].startswith("/host:"):
      for ln in p["lines"]:
        spans += [(n, s, s + d) for n, s, d in ln["events"]
                  if n.startswith("bench.")]
  return spans


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
  """Merged, sorted, disjoint intervals covering the given ones."""
  out: list[list[float]] = []
  for s, e in sorted(intervals):
    if out and s <= out[-1][1]:
      out[-1][1] = max(out[-1][1], e)
    else:
      out.append([s, e])
  return [(s, e) for s, e in out]


def _clip(iv, lo, hi):
  return [(max(s, lo), min(e, hi)) for s, e in iv if e > lo and s < hi]


@dataclasses.dataclass
class Reduction:
  """What one traced window says, in seconds (device numbers averaged over
  the devices that ran anything)."""
  window_s: float
  busy_s: float
  devices: int
  op_s: dict            # op name -> seconds, summed over devices
  op_count: dict        # op name -> events, summed over devices
  calls: dict           # op name -> (operand shapes, result shapes) of the
                        # Pallas calls among the ops
  collective_s: dict    # collective kind -> seconds, summed over devices
  gaps: dict            # host span name -> idle device seconds (per device)

  @property
  def idle_pct(self) -> float:
    return 100.0 * (1.0 - self.busy_s / self.window_s)

  def device_total_s(self) -> float:
    return self.busy_s * self.devices

  def top_ops(self, n: int = 10) -> list:
    return sorted(([k, v] for k, v in self.op_s.items()),
                  key=lambda kv: -kv[1])[:n]

  def top_gaps(self, n: int = 10) -> list:
    return sorted(([k, v] for k, v in self.gaps.items()),
                  key=lambda kv: -kv[1])[:n]


def reduce_trace(planes: list[dict]) -> Reduction:
  """Busy time, op and collective time, and idle gaps by host span, inside
  the host span ``bench.window``."""
  spans = _host_spans(planes)
  windows = [(s, e) for n, s, e in spans if n == WINDOW_SPAN]
  if not windows:
    raise ValueError(f"trace has no {WINDOW_SPAN!r} host span")
  lo, hi = windows[0]
  inner = [(n, s, e) for n, s, e in spans if n != WINDOW_SPAN]
  op_s: dict = {}
  op_count: dict = {}
  coll: dict = {}
  gaps: dict = {}
  calls: dict = {}
  busy_total, n_dev = 0.0, 0
  for plane in _device_planes(planes):
    ops = [(n, s, e) for n, s, e in _ops(plane) if e > lo and s < hi]
    if not ops:
      continue
    n_dev += 1
    for text, s, e in ops:
      n = op_name(text)
      if n not in calls:
        calls.update(custom_calls(text))
      s, e = max(s, lo), min(e, hi)
      op_s[n] = op_s.get(n, 0.0) + (e - s) * 1e-9
      op_count[n] = op_count.get(n, 0) + 1
      for kind in COLLECTIVE_KINDS:
        if n.startswith(kind):
          coll[kind] = coll.get(kind, 0.0) + (e - s) * 1e-9
    busy = union([(s, e) for _, s, e in ops])
    busy = _clip(busy, lo, hi)
    busy_total += sum(e - s for s, e in busy) * 1e-9
    edges = [lo] + [t for iv in busy for t in iv] + [hi]
    for gs, ge in zip(edges[::2], edges[1::2]):
      if ge <= gs:
        continue
      # a gap goes to the host span that covers most of it, if one covers
      # at least half of it
      best, label = 0.5 * (ge - gs), "host:unannotated"
      for n, s, e in inner:
        ov = min(e, ge) - max(s, gs)
        if ov >= best:
          best, label = ov, n
      gaps[label] = gaps.get(label, 0.0) + (ge - gs) * 1e-9
  if not n_dev:
    raise ValueError("no device operation ran inside the window")
  gaps = {k: v / n_dev for k, v in gaps.items()}
  return Reduction(window_s=(hi - lo) * 1e-9, busy_s=busy_total / n_dev,
                   devices=n_dev, op_s=op_s, op_count=op_count,
                   calls=calls, collective_s=coll, gaps=gaps)


# ---- compiled HLO -> kernel work -------------------------------------------

def _shapes(text: str) -> list[tuple[str, tuple[int, ...]]]:
  return [(dt, tuple(int(x) for x in dims.split(",") if x))
          for dt, dims in _SHAPE.findall(text)]


def custom_calls(hlo_text: str) -> dict:
  """``{instruction name: (operand shapes, result shapes)}`` of every Pallas
  (``tpu_custom_call``) instruction in a compiled HLO module."""
  out = {}
  for line in hlo_text.splitlines():
    if 'custom_call_target="tpu_custom_call"' not in line:
      continue
    m = re.match(r"\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(.*?)\s*custom-call\(",
                 line)
    ops = re.search(
        r"operand_layout_constraints=\{(.*?)\}\s*,\s*[a-z_]+=", line)
    if not m or not ops:
      continue
    out[m.group(1)] = (_shapes(ops.group(1)), _shapes(m.group(2)))
  return out


def load_peaks(device_kind: str) -> dict:
  """Published peaks of ``device_kind``; a kind not in the table is an
  error, never a default."""
  with open(pathlib.Path(__file__).with_name("peaks.json")) as f:
    table = json.load(f)["devices"]
  if device_kind not in table:
    raise KeyError(f"device kind {device_kind!r} is not in bench/peaks.json")
  return table[device_kind]


def roofline_pct(red: Reduction, kernel: str, peaks: dict,
                 root=registry.ROOT) -> float | None:
  """Share (%) of the least time the chip could take for every traced call
  of ``kernel`` (by stem) in the time those calls took.  None when the
  kernel did not run or its work is unknown (``kernel_work.work`` under
  ``root``)."""
  least, took = 0.0, 0.0
  for name, (operands, results) in red.calls.items():
    if stem(name) != kernel or name not in red.op_s:
      continue
    work = kernel_work.work(kernel, operands, results, root)
    if work is None:
      return None
    flops, moved = work
    t = max(flops / peaks["bf16_flops_per_s"],
            moved / peaks["hbm_bytes_per_s"])
    least += t * red.op_count[name]
    took += red.op_s[name]
  if took <= 0.0:
    return None
  return 100.0 * least / took
