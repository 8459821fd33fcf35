"""Seeded corpus of unit-norm embeddings with near-duplicate clusters.

A copy of ``benchmarks.common.near_dup_corpus`` made for the chip: the whole
corpus is drawn on the device in one jitted call.  Cluster sizes follow a
Zipf(``alpha``) law and each member sits ``noise_norm`` (norm of its noise
vector, split evenly over the ``d`` dimensions) from its unit center, so
near-duplicates are as close at d = 3072 as at d = 64.

The rows are a fixed set (``corpus_seed`` of the configuration); a run's
seed only permutes their order, so every seed gives the same work.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from bench import seeds


@functools.partial(jax.jit, static_argnums=(1, 2, 3))
def _draw(k, n: int, d: int, clusters: int, noise: float, alpha: float,
          order_key):
  kc, ka, kn = jax.random.split(k, 3)
  centers = jax.random.normal(kc, (clusters, d), jnp.float32)
  centers = centers / jnp.linalg.norm(centers, axis=1, keepdims=True)
  logits = -alpha * jnp.log(jnp.arange(1, clusters + 1, dtype=jnp.float32))
  assign = jax.random.categorical(ka, logits, shape=(n,))
  f = centers[assign] + noise * jax.random.normal(kn, (n, d), jnp.float32)
  f = f / jnp.linalg.norm(f, axis=1, keepdims=True)
  perm = jax.random.permutation(order_key, n)
  return f[perm], assign[perm]


def draw(cfg: dict, n: int, seed: int):
  """(n, d) f32 rows on the default device and their (n,) cluster ids
  (cluster 0 is the most popular).  The row set depends only on ``cfg``;
  ``seed`` sets their order."""
  c = cfg["corpus"]
  d = int(cfg["d"])
  k = jax.random.PRNGKey(int(c["corpus_seed"]))
  noise = float(c["noise_norm"]) / np.sqrt(d)
  return _draw(k, n, d, int(c["clusters"]), noise, float(c["alpha"]),
               seeds.key(seed, seeds.CORPUS_ORDER))
