"""Operations and bytes of one call of each Pallas kernel, from the shapes
of its custom call in the compiled HLO.

Bytes are what the call must move at the least: every operand read once and
every result written once.  Operations are the multiply-adds of the
similarity contraction (2 per term); the elementwise epilogues are left
out, so a share computed from them is a lower bound.  Operations count
once against the bfloat16 peak, whatever the precision the kernel asks for,
and bytes once per operand: a kernel whose grid reads an operand more than
once, or whose float32 contraction takes several passes of the MXU, stays
below 100% by that much (PERF.md gives each kernel's ceiling).

A kernel outside ``WORK`` brings its arithmetic as a file
``bench/work/<stem>.py`` that defines ``work(operands, results) ->
(operations, bytes)``.
"""
from __future__ import annotations

from bench import registry

DTYPE_BYTES = {"f32": 4, "s32": 4, "u32": 4, "bf16": 2, "f16": 2, "s8": 1,
               "u8": 1, "pred": 1, "f64": 8, "s64": 8, "u64": 8, "s16": 2,
               "u16": 2}


def nbytes(shape: tuple[str, tuple[int, ...]]) -> int:
  """Bytes of one ``(dtype, dims)`` HLO shape."""
  dt, dims = shape
  n = DTYPE_BYTES[dt]
  for x in dims:
    n *= x
  return n


def _moved(operands, results) -> int:
  return sum(nbytes(s) for s in operands + results)


def _rows(dims) -> int:
  n = 1
  for x in dims[:-1]:
    n *= x
  return n


def _contract(operands, results):
  """Kernels whose first two operands are blocks of rows of width d
  ((..., a, d) and (..., b, d), leading dimensions batching the rows) and
  that contract every row of one with every row of the other."""
  (_, da), (_, db) = operands[0], operands[1]
  if da[-1] != db[-1]:
    raise ValueError(f"operand widths differ: {operands[:2]}")
  return 2.0 * _rows(da) * _rows(db) * da[-1], _moved(operands, results)


# kernel stem (the custom call's instruction name without its suffix) ->
# work of one call
WORK = {
    "pairwise": _contract,         # (a, d) x (..., b, d) similarity tile
    "facility_gain": _contract,    # (ne, d) eval rows x (nc, d) candidates
}


def work(kernel: str, operands, results, root=registry.ROOT):
  """(operations, bytes) of one call of ``kernel``, from ``WORK`` or from
  ``bench/work/<kernel>.py`` under ``root``; None if neither knows it."""
  try:
    fn = registry.lookup(WORK, root, "work", kernel, "work")
  except KeyError:
    return None
  return fn(operands, results)
