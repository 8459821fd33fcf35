"""Operations and bytes of one call of the fused information-gain select
kernel (``kernels/select_top1.py`` ``info_select_pallas``), from the shapes
of its custom call: operands (k, d) selected rows, (k, k) masked inverse
Cholesky factor, (nc, d) candidate block and (1, nc) feasibility mask.

Operations are the multiply-adds of its two contractions, 2 k nc d for the
cross-kernel tile k(S, cand) and 2 k^2 nc for its back-substitution by the
inverse factor; the RBF exponentials and the variance reduce are left out.
Bytes are every operand read once and every result written once, as
``kernel_work`` counts them.
"""
from __future__ import annotations

from bench import kernel_work


def work(operands, results):
  (_, (k, d)), (_, lk), (_, (nc, dc)) = operands[:3]
  if dc != d or tuple(lk) != (k, k):
    raise ValueError(f"unexpected info_select operands: {operands}")
  flops = 2.0 * k * nc * d + 2.0 * k * k * nc
  return flops, sum(kernel_work.nbytes(s) for s in operands + results)
