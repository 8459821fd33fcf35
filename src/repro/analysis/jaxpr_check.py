"""Jaxpr-level hazard analysis (rules R1-R3, R7) over traced entry points.

The analyzer traces a registered entry point (``kernels/dispatch.py``
entry-point registry) with ``jax.make_jaxpr`` at representative shapes and
walks the closed jaxpr recursively, tracking three pieces of context:

* whether the current equation sits inside a ``while``/``scan`` body,
* the axis names and device count of every enclosing ``shard_map`` mesh,
* a ``(mask_taint, shard_varying)`` pair per variable.  The taint bit is
  seeded from the entry's declared mask inputs (gid-validity vectors of
  pad-and-mask blocks); the varying bit says "this value can differ across
  the shards of the enclosing shard_map" and is seeded from the
  shard_map's ``in_names`` (a sharded input varies, a replicated one does
  not), set by ``axis_index``, cleared by replicating collectives
  (``psum``/``pmax``/``pmin``/``all_gather``), and otherwise propagated
  forward through every equation with a fixpoint over loop carries.

R1  ``sort`` primitive inside a loop body under a multi-device shard_map on
    a non-TPU backend.  This is the PR 4 bug verbatim: XLA CPU's sort inside
    loop bodies under multi-device shard_map returned another shard's
    output.  ``core/greedy._argsort_desc`` branches at trace time -- on the
    hazardous configuration it emits a bitonic network (no sort primitive),
    so a clean trace proves the safe path was taken.  The CLI forces a
    multi-device host platform *before importing jax* so this rule traces
    the configuration production runs with.

R2  collective consistency: ``psum``/``all_gather``/... axis names must be
    bound by an enclosing shard_map mesh, and the two branches of a ``cond``
    must issue the same multiset of collectives (a collective under one
    branch only deadlocks the mesh when shards disagree on the predicate).

R3  mask discipline: a reduction over an axis whose size matches a declared
    pad-and-mask row count must consume (transitively) one of the declared
    validity masks.  Padded rows are zeroed *by* the mask; a reduction that
    never saw the mask is reading garbage rows.

R7  psum double counting: ``psum`` of a shard-INVARIANT (replicated)
    operand inside a multi-device shard_map.  Every shard contributes the
    same value, so the sum is the true value scaled by the mesh size --
    the classic "psum the replicated bias" bug.  An operand is replicated
    when it derives only from replicated shard_map inputs (empty
    ``in_names`` entry), literals/consts, or the outputs of replicating
    collectives, and never mixes in a sharded input or ``axis_index``.
"""
from __future__ import annotations

import dataclasses
import math
from pathlib import Path
from typing import Any, Callable, Iterator

import jax
from jax._src import source_info_util as _siu

from .findings import Finding

__all__ = ["check_entry", "check_closed_jaxpr"]

_REDUCE_PRIMS = {
    "reduce_sum", "reduce_max", "reduce_min", "reduce_prod",
    "reduce_or", "reduce_and", "argmax", "argmin",
}
# psum2 is what shard_map's replication-checking rewrite made of psum (jax 0.4)
_AXES_COLLECTIVES = {"psum", "psum2", "pmax", "pmin"}
_NAME_COLLECTIVES = {
    "all_gather", "all_to_all", "ppermute", "pbroadcast", "axis_index",
    "reduce_scatter", "psum_scatter",
}
_PSUMS = {"psum", "psum2"}
# collectives whose output is identical on every shard of the reduced axis
# (their result clears the shard-varying bit; everything else keeps it).
# pbroadcast is NOT here nor varying: it is a replication-type cast that
# leaves per-shard values untouched, so it passes the bit through.
_REPLICATING_COLLECTIVES = {"psum", "psum2", "pmax", "pmin", "all_gather"}

# (mask_taint, shard_varying) abstract value; see module docstring
_NOVAL = (False, False)


def _join(a: tuple, b: tuple) -> tuple:
  return (a[0] or b[0], a[1] or b[1])


def _any_val(vals: list) -> tuple:
  return (any(t for t, _ in vals), any(v for _, v in vals))


@dataclasses.dataclass(frozen=True)
class _Ctx:
  in_loop: bool = False
  mesh_axes: frozenset = frozenset()
  mesh_devices: int = 1


def _unwrap(j):
  return j.jaxpr if hasattr(j, "jaxpr") and hasattr(j, "consts") else j


def _iter_jaxprs(value: Any) -> Iterator[Any]:
  """Yield every (Closed)Jaxpr reachable inside an eqn param value."""
  if hasattr(value, "eqns"):
    yield value
  elif hasattr(value, "jaxpr") and hasattr(value, "consts"):
    yield value
  elif isinstance(value, (tuple, list)):
    for v in value:
      yield from _iter_jaxprs(v)


def _mesh_info(mesh) -> tuple[frozenset, int]:
  try:
    axes = frozenset(str(a) for a in mesh.axis_names)
  except Exception:
    axes = frozenset()
  size = getattr(mesh, "size", None)
  if size is None:
    try:
      size = math.prod(dict(mesh.shape).values())
    except Exception:
      size = 1
  return axes, int(size)


def _axis_names(params: dict, prim: str) -> set[str]:
  if prim in _AXES_COLLECTIVES:
    axes = params.get("axes", ())
  else:
    axes = params.get("axis_name", ())
  if not isinstance(axes, (tuple, list)):
    axes = (axes,)
  return {a for a in axes if isinstance(a, str)}


def _collectives_signature(jaxpr) -> tuple:
  """Sorted multiset of (prim, axes) collectives reachable in a jaxpr."""
  jaxpr = _unwrap(jaxpr)
  sig = []
  for eqn in jaxpr.eqns:
    name = eqn.primitive.name
    if name in _AXES_COLLECTIVES or name in _NAME_COLLECTIVES:
      sig.append((name, tuple(sorted(_axis_names(eqn.params, name)))))
    for v in eqn.params.values():
      for sub in _iter_jaxprs(v):
        sig.extend(_collectives_signature(sub))
  return tuple(sorted(sig))


class _Walker:
  """Forward taint + context walk producing Findings (deduplicated)."""

  def __init__(self, entry: str, row_sizes: frozenset, repo_root: Path,
               backend: str):
    self.entry = entry
    self.row_sizes = row_sizes
    self.repo_root = repo_root
    self.backend = backend
    self.findings: list[Finding] = []
    self._seen: set = set()

  # -- source locations ------------------------------------------------
  def _loc(self, eqn) -> tuple[str, int]:
    try:
      fr = _siu.user_frame(eqn.source_info)
    except Exception:
      fr = None
    if fr is None:
      return (f"<entry:{self.entry}>", 0)
    file = fr.file_name
    try:
      file = str(Path(file).resolve().relative_to(self.repo_root))
    except ValueError:
      pass
    return (file, int(getattr(fr, "start_line", 0) or 0))

  def _add(self, eqn, rule: str, msg: str, hint: str):
    file, line = self._loc(eqn)
    key = (rule, file, line, msg)
    if key in self._seen:
      return
    self._seen.add(key)
    self.findings.append(Finding(rule=rule, file=file, line=line, msg=msg,
                                 hint=hint, entry=self.entry))

  # -- the walk --------------------------------------------------------
  def walk(self, jaxpr, in_vals: list[tuple], ctx: _Ctx) -> list[tuple]:
    """Abstract-interpret one jaxpr; values are (taint, varying) pairs."""
    jaxpr = _unwrap(jaxpr)
    env: dict = {}

    def read(atom) -> tuple:
      return env.get(atom, _NOVAL) if hasattr(atom, "aval") and not hasattr(
          atom, "val") else _NOVAL

    if len(in_vals) != len(jaxpr.invars):
      # arity mismatch from an unmodeled higher-order primitive: be
      # conservative (over-taint) rather than raise false R3 positives
      in_vals = [_any_val(in_vals)] * len(jaxpr.invars)
    for v, val in zip(jaxpr.invars, in_vals):
      env[v] = val
    for v in jaxpr.constvars:
      env[v] = _NOVAL

    for eqn in jaxpr.eqns:
      vin = [read(x) for x in eqn.invars]
      vouts = self._eqn(eqn, vin, ctx)
      if len(vouts) != len(eqn.outvars):
        vouts = [_any_val(vin)] * len(eqn.outvars)
      for v, val in zip(eqn.outvars, vouts):
        env[v] = val
    return [read(v) for v in jaxpr.outvars]

  def _eqn(self, eqn, vin: list[tuple], ctx: _Ctx) -> list[tuple]:
    name = eqn.primitive.name
    p = eqn.params
    tin = [t for t, _ in vin]

    if name == "pjit":
      return self.walk(p["jaxpr"], vin, ctx)

    if name == "while":
      cn, bn = p["cond_nconsts"], p["body_nconsts"]
      cond_consts, body_consts = vin[:cn], vin[cn:cn + bn]
      carry = list(vin[cn + bn:])
      loop_ctx = dataclasses.replace(ctx, in_loop=True)
      for _ in range(2 * len(carry) + 1):
        outs = self.walk(p["body_jaxpr"], body_consts + carry, loop_ctx)
        new = [_join(a, b) for a, b in zip(carry, outs)]
        if new == carry:
          break
        carry = new
      self.walk(p["cond_jaxpr"], cond_consts + carry, loop_ctx)
      return carry

    if name == "scan":
      nc, ncar = p["num_consts"], p["num_carry"]
      consts, carry, xs = vin[:nc], list(vin[nc:nc + ncar]), vin[nc + ncar:]
      loop_ctx = dataclasses.replace(ctx, in_loop=True)
      ys: list[tuple] = []
      for _ in range(2 * len(carry) + 1):
        outs = self.walk(p["jaxpr"], consts + carry + xs, loop_ctx)
        new = [_join(a, b) for a, b in zip(carry, outs[:ncar])]
        ys = outs[ncar:]
        if new == carry:
          break
        carry = new
      return carry + ys

    if name == "cond":
      branches = p["branches"]
      ops = vin[1:]
      sigs = {_collectives_signature(b) for b in branches}
      if len(sigs) > 1:
        self._add(
            eqn, "R2",
            "cond branches issue different collectives (deadlocks the mesh "
            "when shards disagree on the predicate)",
            "hoist the collective out of the cond, or issue it in both "
            "branches")
      outs = None
      for b in branches:
        bouts = self.walk(b, list(ops), ctx)
        outs = bouts if outs is None else [_join(a, b_) for a, b_ in
                                           zip(outs, bouts)]
      return outs or []

    if name == "shard_map":
      axes, size = _mesh_info(p.get("mesh"))
      inner_ctx = dataclasses.replace(
          ctx, mesh_axes=ctx.mesh_axes | axes,
          mesh_devices=max(ctx.mesh_devices, size))
      # seed the varying bit from in_names: an input split over a mesh axis
      # (non-empty names dict) differs per shard; a replicated one does not
      in_names = p.get("in_names")
      if isinstance(in_names, (tuple, list)) and len(in_names) == len(vin):
        seeded = [(t, bool(names)) for (t, _), names in zip(vin, in_names)]
      else:
        seeded = [(t, True) for t, _ in vin]  # unknown layout: assume varying
      return self.walk(p["jaxpr"], seeded, inner_ctx)

    if name in ("custom_jvp_call", "custom_vjp_call", "remat", "checkpoint",
                "closed_call", "core_call", "custom_vjp_call_jaxpr"):
      inner = p.get("call_jaxpr", p.get("jaxpr"))
      if inner is not None:
        return self.walk(inner, vin, ctx)
      return [_any_val(vin)] * len(eqn.outvars)

    if name == "sort":
      if ctx.in_loop and ctx.mesh_devices > 1 and self.backend != "tpu":
        self._add(
            eqn, "R1",
            f"sort primitive inside a loop body under a {ctx.mesh_devices}-"
            f"device shard_map on backend '{self.backend}' (XLA CPU sort "
            "here can return another shard's output)",
            "route the sort through core/greedy._argsort_desc (bitonic "
            "network on multi-device non-TPU)")
      return [_any_val(vin)] * len(eqn.outvars)

    if name in _AXES_COLLECTIVES or name in _NAME_COLLECTIVES:
      unbound = _axis_names(p, name) - ctx.mesh_axes
      if unbound:
        self._add(
            eqn, "R2",
            f"{name} over axis {sorted(unbound)} not bound by any enclosing "
            "shard_map mesh",
            "match the collective's axis name to the mesh axis the "
            "shard_map maps over")
      if name in _PSUMS and ctx.mesh_devices > 1:
        # R7: every shard feeds the same value into the sum, so the result
        # is the true value multiplied by the mesh size.  Only psum is
        # flagged -- pmax/pmin of a replicated value are idempotent.
        for _, varying in vin:
          if not varying:
            self._add(
                eqn, "R7",
                f"psum of a shard-invariant (replicated) operand under a "
                f"{ctx.mesh_devices}-device shard_map scales it by the mesh "
                "size (double counting)",
                "psum only shard-varying partial values; for a replicated "
                "operand drop the collective or divide by "
                "jax.lax.psum(1, axis)")
            break
      if name == "pbroadcast":
        # replication-type cast, not a data movement: per-shard values are
        # unchanged, so the varying bit passes straight through
        return [(t, v) for t, v in vin]
      # axis_index IS the per-shard coordinate; replicating collectives
      # produce the same output on every shard; the rest (ppermute,
      # all_to_all, *_scatter) stay shard-varying
      varying_out = (name == "axis_index"
                     or name not in _REPLICATING_COLLECTIVES)
      return [(any(tin), varying_out)] * len(eqn.outvars)

    if name in _REDUCE_PRIMS:
      axes = p.get("axes", ())
      shape = eqn.invars[0].aval.shape
      reduced = {shape[a] for a in axes if a < len(shape)}
      if reduced & self.row_sizes and not tin[0]:
        self._add(
            eqn, "R3",
            f"{name} over pad-and-mask row axis (size {sorted(reduced & self.row_sizes)}) "
            "without consuming a validity mask",
            "mask the operand with the gid-validity vector (gids >= 0) "
            "before reducing")
      return [(tin[0], vin[0][1])] * len(eqn.outvars)

    if name == "dot_general":
      (lc, rc), _ = p["dimension_numbers"]
      lshape = eqn.invars[0].aval.shape
      contracted = {lshape[i] for i in lc if i < len(lshape)}
      if contracted & self.row_sizes and not (tin[0] or tin[1]):
        self._add(
            eqn, "R3",
            f"dot_general contracting over pad-and-mask row axis (size "
            f"{sorted(contracted & self.row_sizes)}) without a validity mask",
            "mask either operand with the gid-validity vector before the "
            "contraction")
      return [_join(vin[0], vin[1])]

    # default: sub-jaxprs of unmodeled primitives still get context checks
    for v in p.values():
      for sub in _iter_jaxprs(v):
        sub_j = _unwrap(sub)
        self.walk(sub_j, [_any_val(vin)] * len(sub_j.invars), ctx)
    return [_any_val(vin)] * len(eqn.outvars)


def check_closed_jaxpr(
    closed, *, entry: str, mask_positions: tuple[int, ...] = (),
    row_sizes: tuple[int, ...] = (), repo_root: Path | None = None,
    backend: str | None = None) -> list[Finding]:
  """Walk an already-traced ClosedJaxpr; see module docstring for rules."""
  repo_root = (repo_root or Path.cwd()).resolve()
  backend = backend or jax.default_backend()
  jaxpr = closed.jaxpr
  # top-level inputs: taint from the declared mask positions; the varying
  # bit is re-seeded at each shard_map boundary from its in_names
  vals = [(i in set(mask_positions), False)
          for i in range(len(jaxpr.invars))]
  w = _Walker(entry, frozenset(row_sizes), repo_root, backend)
  w.walk(jaxpr, vals, _Ctx())
  return w.findings


def check_entry(fn: Callable, args: tuple, *, entry: str,
                mask_positions: tuple[int, ...] = (),
                row_sizes: tuple[int, ...] = (),
                repo_root: Path | None = None) -> list[Finding]:
  """Trace ``fn(*args)`` (args may be ShapeDtypeStructs) and analyze it."""
  closed = jax.make_jaxpr(fn)(*args)
  return check_closed_jaxpr(
      closed, entry=entry, mask_positions=mask_positions,
      row_sizes=row_sizes, repo_root=repo_root)
