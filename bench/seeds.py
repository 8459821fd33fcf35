"""Keys and generators drawn from a run's ``--seed`` (any int up to a little
over 2**31, so wider than a signed 32-bit word)."""
from __future__ import annotations

import numpy as np

# streams of one seed; each consumer folds in its own tag
CORPUS_ORDER, EPOCH_KEYS, TRAFFIC_ORDER, CHECK_SAMPLE = 1, 2, 3, 4


def words(seed: int) -> tuple[int, int]:
  """Two uint32 words of a non-negative seed of up to 64 bits."""
  seed = int(seed)
  if seed < 0 or seed >= 1 << 64:
    raise ValueError(f"seed must be in [0, 2**64), got {seed}")
  return seed & 0xFFFFFFFF, seed >> 32


def key(seed: int, stream: int):
  """jax PRNG key of ``stream`` under ``seed``."""
  import jax
  lo, hi = words(seed)
  k = jax.random.PRNGKey(np.uint32(lo))
  return jax.random.fold_in(jax.random.fold_in(k, np.uint32(hi)), stream)


def rng(seed: int, stream: int) -> np.random.Generator:
  """numpy generator of ``stream`` under ``seed``."""
  lo, hi = words(seed)
  return np.random.default_rng([lo, hi, stream])
