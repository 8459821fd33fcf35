"""Block-size autotable for the fused kernels, keyed on (n, d, backend).

Not a runtime autotuner: entries are a small, deterministic lookup table
(measured offline, see docs/perf.md "Tuning knobs") that replaces the
hardcoded 256x256 tiles the wrappers in ops.py used to bake in.  The table is
consulted at *trace time* -- all inputs are static shapes plus the cached
process backend -- so block choices never cause retraces and never read
``jax.default_backend()`` from inside jitted code (see kernels/dispatch.py
for the same contract on backend resolution).

Three knobs live here:

  * ``pick_block(n, d)``   -- tile size along an n-length kernel axis.  On
    TPU larger candidate tiles amortize grid overhead; on CPU the kernels
    only run in interpret mode (parity, not speed), so the table keeps the
    256 tiles the parity suite has always exercised.  Two limits of the
    chip's compiler hold on every backend, so interpret mode runs the blocks
    Mosaic would: a block is never narrower than ``LANES`` (row-vector
    blocks such as the ``(2, block)`` coverage/mask rows must span whole
    128-lane vregs -- short axes are padded up, not tiled finer), and wide
    rows shrink the block until the double-buffered tiles fit
    ``VMEM_BUDGET`` (d = 3072 f32 rows get 128-row tiles).
  * ``lazy_tile(n, d)``    -- rescoring granularity of the tile-bound lazy
    greedy in core/greedy.py.  Bigger tiles mean fewer bound entries and
    better matmul shapes but coarser pruning; the XLA path prefers bigger
    tiles than the TPU path (whose tiles must double-buffer through VMEM).
  * ``floor_pow2(n, cap)`` -- largest power-of-two <= cap that still
    divides into n without absurd padding (the lazy tiles and ops.py's
    explicit-override clamping, which then applies the lane floor).
"""
from __future__ import annotations

import functools

import jax


@functools.lru_cache(maxsize=None)
def default_backend() -> str:
  """Process-wide backend, read once (trace-time contract; see module doc)."""
  return jax.default_backend()


# TPU vreg lane count: the narrowest legal last dim of a kernel block
LANES = 128

# VMEM a kernel's tiles may hold.  v5e scopes 16 MiB per kernel by default;
# the rest is headroom for Mosaic's own temporaries.
VMEM_BUDGET = 12 << 20


def floor_pow2(n: int, cap: int = 256, floor: int = 8) -> int:
  """Largest power-of-two block <= cap that keeps padding overhead sane."""
  b = cap
  while b > floor and n < b:
    b //= 2
  return b


def _bucket_n(n: int) -> str:
  return "small" if n < 2048 else ("mid" if n < 32768 else "large")


def _bucket_d(d: int) -> str:
  return "narrow" if d <= 64 else "wide"


def _tile_bytes(b: int, d: int, itemsize: int) -> int:
  """VMEM of one grid step of the (b, d) x (b, d) similarity kernels: two
  double-buffered feature tiles, their f32 working copies, and the (b, b)
  f32 similarity tile with two elementwise temporaries."""
  return 4 * b * d * itemsize + 2 * b * d * 4 + 3 * b * b * 4


def fit_block(b: int, d: int, itemsize: int = 4) -> int:
  """Halve ``b`` until its tiles fit ``VMEM_BUDGET``; never below LANES."""
  while b > LANES and _tile_bytes(b, d, itemsize) > VMEM_BUDGET:
    b //= 2
  return max(b, LANES)


# (backend, n-bucket, d-bucket) -> kernel block size along the n axis,
# before the VMEM fit of ``fit_block``.
_BLOCK_TABLE: dict[tuple[str, str, str], int] = {
    ("tpu", "small", "narrow"): 256,
    ("tpu", "small", "wide"): 256,
    ("tpu", "mid", "narrow"): 512,
    ("tpu", "mid", "wide"): 256,
    ("tpu", "large", "narrow"): 512,
    ("tpu", "large", "wide"): 512,
    # cpu/gpu: interpret-mode parity only -- keep the historical 256 tiles
}
_DEFAULT_BLOCK = 256


def pick_block(n: int, d: int, backend: str | None = None,
               itemsize: int = 4) -> int:
  """Tile size along an n-length axis for (n, d) operands on ``backend``
  (``itemsize`` bytes per element); axes under 256 rows get one LANES
  block."""
  if n < 256:
    return LANES
  backend = backend or default_backend()
  b = _BLOCK_TABLE.get((backend, _bucket_n(n), _bucket_d(d)), _DEFAULT_BLOCK)
  return fit_block(b, d, itemsize)


# (backend, d-bucket) -> lazy-greedy rescore tile (core/greedy.py mode="lazy").
# The tile is the batch of bound-sorted candidates refreshed per rescan:
# bigger tiles amortize the gather + oracle launch, smaller tiles waste less
# rescoring past the stopping bound.
_LAZY_TILE: dict[tuple[str, str], int] = {
    ("tpu", "narrow"): 512,
    ("tpu", "wide"): 256,
    ("cpu", "narrow"): 512,
    ("cpu", "wide"): 256,
}


def lazy_tile(n: int, d: int, backend: str | None = None) -> int:
  """Rescore-tile size for the tile-bound lazy greedy over n candidates."""
  backend = backend or default_backend()
  key = (backend if backend == "tpu" else "cpu", _bucket_d(d))
  return floor_pow2(n, cap=_LAZY_TILE.get(key, 512))


# backend -> query-batch tile of the multi-tenant batched query path
# (service/store.py).  The tile is the compiled batch width B of the sieve
# merge / batched select oracles: ragged request batches pad up to it
# (so they never retrace) and bigger batches chunk through it.  TPU lanes
# want a wider tile to fill the VPU; on CPU the merge's batched matmul
# win saturates around 64 concurrent queries.
_QUERY_TILE: dict[str, int] = {
    "tpu": 128,
    "cpu": 64,
}
_DEFAULT_QUERY_TILE = 64


def query_tile(backend: str | None = None) -> int:
  """Compiled batch width of the batched query path on ``backend``."""
  backend = backend or default_backend()
  key = backend if backend == "tpu" else "cpu"
  return _QUERY_TILE.get(key, _DEFAULT_QUERY_TILE)
