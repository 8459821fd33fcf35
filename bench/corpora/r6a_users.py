"""Seeded user visits in the shape of the Yahoo! R6A front-page log.

Each row is a user's 6-d feature as Li et al. (WWW 2010) build it: five
soft cluster memberships that sum to 1, plus a constant 1.  The memberships
are drawn from Dirichlet(``alpha``) (``alpha`` below 1 puts most of a row's
mass on one cluster, as soft k-means memberships do), on the device in one
jitted call.  The label is the largest membership.

The rows are a fixed set (``corpus_seed`` of the configuration); a run's
seed only permutes their order, so every seed gives the same work.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from bench import seeds

CLUSTERS = 5


@functools.partial(jax.jit, static_argnums=(1,))
def _draw(k, n: int, alpha, order_key):
  z = jax.random.dirichlet(k, jnp.full((CLUSTERS,), alpha, jnp.float32),
                           shape=(n,))
  rows = jnp.concatenate([z, jnp.ones((n, 1), jnp.float32)], axis=1)
  perm = jax.random.permutation(order_key, n)
  return rows[perm], jnp.argmax(z, axis=1)[perm]


def draw(cfg: dict, n: int, seed: int):
  """(n, 6) f32 rows on the default device and their (n,) labels.  The row
  set depends only on ``cfg``; ``seed`` sets their order."""
  c = cfg["corpus"]
  assert int(cfg["d"]) == CLUSTERS + 1, cfg["d"]
  return _draw(jax.random.PRNGKey(int(c["corpus_seed"])), n,
               jnp.float32(c["alpha"]), seeds.key(seed, seeds.CORPUS_ORDER))
