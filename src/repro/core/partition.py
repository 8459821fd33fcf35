"""Random partitioning of the ground set (GreeDi step 1) and the row moves
that lay a partition out over a device mesh."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.util import shard_map

Array = jax.Array


def partition_perm(rng: Array, n: int) -> Array:
  """The uniform permutation of n rows behind every random partition: row
  ``perm[p]`` lands at position p, and machine i owns positions
  [i * npp, (i + 1) * npp)."""
  return jax.random.permutation(rng, n)


def random_partition(rng: Array, feats: Array, m: int):
  """Uniformly-at-random partition into m equal parts (pad if needed).

  Returns (parts (m, npp, d), mask (m, npp) bool, perm (m*npp,) int32 with -1
  padding).  Uniform random assignment is what Theorems 8-11 assume.
  """
  n, d = feats.shape
  npp = -(-n // m)  # ceil
  perm = partition_perm(rng, n)
  pad = m * npp - n
  perm_p = jnp.concatenate([perm, jnp.full((pad,), -1, perm.dtype)])
  mask = perm_p >= 0
  safe = jnp.maximum(perm_p, 0)
  parts = feats[safe].reshape(m, npp, d)
  parts = jnp.where(mask.reshape(m, npp)[..., None], parts, 0.0)
  return parts, mask.reshape(m, npp), perm_p.reshape(m, npp)


def partition_gids(perm: Array, gids: Array | None = None) -> Array:
  """Global ids of the shard-contiguous layout a partition perm induces.

  ``perm`` is the (m, npp) int32 permutation from ``random_partition``
  (-1 = padding past a non-divisible n).  ``gids`` optionally maps the
  permuted row positions to original document ids, itself allowing -1 for
  the holes of a pad-and-mask block (a growing ground set, docs/service.md).
  Returns the flat (m*npp,) int32 gids side input for the sharded GreeDi
  paths, with holes from BOTH sources composed to -1.
  """
  p = perm.reshape(-1).astype(jnp.int32)
  if gids is None:
    return p
  safe = jnp.maximum(p, 0)
  return jnp.where(p >= 0, gids.astype(jnp.int32)[safe], -1)


def shard_live_counts(valid: Array, m: int) -> Array:
  """(m,) float32 live-row counts per shard of a shard-contiguous layout.

  ``valid`` is the flat (m*npp,) liveness mask a partition induces (gids >= 0
  after ``partition_gids`` -- holes of a pad-and-mask block compose to
  False).  The counts are the per-shard evaluation denominators the service
  uses to turn sum-form warm-bound tables into mean-form empty-set bounds
  (``BoundMaintainer.epoch_bounds``, core/objectives.py)."""
  return jnp.sum(valid.reshape(m, -1), axis=1).astype(jnp.float32)


def permute_rows(arrays, fills, perm: Array, valid: Array, *, mesh,
                 axis_names) -> tuple:
  """``x[perm]`` for each row-sharded array of ``arrays``, with the rows
  whose ``valid`` is False (pad-and-mask holes) left at ``fills``, without
  gathering any array onto every device.

  A global gather of a row-sharded block compiles to an all_gather of the
  whole block on each device (m times the block's memory and m - 1 blocks of
  traffic per device).  Here each device routes its own live rows instead:
  it inverts the (replicated) permutation, ranks its rows within their
  destination shard, and ships them in all_to_all rounds of C rows per
  (source, destination) pair, each received row landing in its slot.  C sits
  a few standard deviations above the mean pair count n / m^2, so one round
  almost always suffices; a loop over ``pmax`` of the pair counts keeps the
  result exact whatever the draw.  Peak memory per device is about three
  local blocks, independent of m.
  """
  m = 1
  for a in axis_names:
    m *= mesh.shape[a]
  if m == 1:
    keep = valid[perm]
    return tuple(
        jnp.where(keep.reshape((-1,) + (1,) * (x.ndim - 1)), x[perm], f)
        for x, f in zip(arrays, fills))
  n = perm.shape[0]
  npp = n // m
  mean = npp // m
  cap = min(npp, mean + 4 * int(mean ** 0.5) + 1)

  def body(perm, lvalid, *local):
    me = jax.lax.axis_index(axis_names)
    inv = jnp.zeros((n,), jnp.int32).at[perm].set(
        jnp.arange(n, dtype=jnp.int32))
    q = jax.lax.dynamic_slice(inv, (me * npp,), (npp,))
    dest, slot = q // npp, q % npp
    onehot = ((dest[:, None] == jnp.arange(m)[None, :])
              & lvalid[:, None]).astype(jnp.int32)
    rank = jnp.take_along_axis(jnp.cumsum(onehot, axis=0), dest[:, None],
                               axis=1)[:, 0] - 1
    rank = jnp.where(lvalid, rank, -1)        # holes are never sent
    most = jax.lax.pmax(jnp.max(rank) + 1, axis_names)  # largest pair count

    def round_(t, outs):
      pos = rank - t * cap
      pos = jnp.where((pos >= 0) & (pos < cap), pos, cap)  # cap = not now
      sent = jnp.full((m, cap), npp, jnp.int32).at[dest, pos].set(
          slot, mode="drop")
      got = jax.lax.all_to_all(sent, axis_names, 0, 0).reshape(m * cap)
      new = []
      for x, o in zip(local, outs):
        buf = jnp.zeros((m, cap) + x.shape[1:], x.dtype).at[dest, pos].set(
            x, mode="drop")
        rows = jax.lax.all_to_all(buf, axis_names, 0, 0)
        new.append(o.at[got].set(rows.reshape((m * cap,) + x.shape[1:]),
                                 mode="drop"))
      return tuple(new)

    outs = tuple(jnp.full_like(x, f) for x, f in zip(local, fills))
    return jax.lax.fori_loop(0, -(-most // cap), round_, outs)

  spec = P(axis_names)
  return shard_map(body, mesh=mesh,
                   in_specs=(P(), spec) + (spec,) * len(arrays),
                   out_specs=(spec,) * len(arrays))(perm, valid, *arrays)


def shard_for_mesh(feats: Array, mesh, axis_names) -> Array:
  """Lay the (already padded) ground set out across mesh data axes."""
  from jax.sharding import NamedSharding
  spec = P(axis_names)
  return jax.device_put(feats, NamedSharding(mesh, spec))
