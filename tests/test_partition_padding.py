"""Regression: ``random_partition`` zero-fills padded rows, and that padding
must never contribute to gains -- for every objective and both gain-oracle
backends, not just facility location.

Padding enters in two places: as *eval* rows (masked by eval_mask) and as
*candidate* rows (masked by cand_mask in the greedy loop).  A zero feature
row is NOT harmless by itself -- e.g. rbf similarity of a zero row against a
real point is exp(-||x||^2) > 0 -- so the masks are load-bearing.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import objectives as O
from repro.core.greedy import greedy
from repro.core.partition import random_partition

jax.config.update("jax_platform_name", "cpu")

N, M, D = 50, 4, 6   # npp = ceil(50/4) = 13 -> 2 padded rows


def _padded_partition(seed=0):
  feats = jax.random.normal(jax.random.PRNGKey(seed), (N, D))
  feats = feats / jnp.linalg.norm(feats, axis=1, keepdims=True)
  parts, mask, perm = random_partition(jax.random.PRNGKey(seed + 1), feats, M)
  # the last partition carries the padding
  i = int(np.argmin(np.asarray(mask).sum(axis=1)))
  assert not bool(mask[i].all()), "expected a partition with padded rows"
  return parts[i], mask[i]


def test_random_partition_zero_fills_padding():
  part, mask = _padded_partition()
  pad_rows = np.asarray(part)[~np.asarray(mask)]
  assert pad_rows.shape[0] > 0
  np.testing.assert_array_equal(pad_rows, 0.0)


@pytest.mark.parametrize("backend", ["ref", "pallas"])
@pytest.mark.parametrize("kernel,kwargs", [("linear", ()),
                                           ("rbf", (("h", 1.0),))])
def test_facility_location_padding_no_gain(backend, kernel, kwargs):
  part, mask = _padded_partition()
  live = np.asarray(mask)
  obj = O.FacilityLocation(kernel=kernel, kernel_kwargs=kwargs,
                           backend=backend)
  st_pad = obj.init(part, mask.astype(part.dtype))
  st_live = obj.init(part[jnp.asarray(live)])
  g_pad = obj.gains(st_pad, part)
  g_live = obj.gains(st_live, part)
  np.testing.assert_allclose(np.asarray(g_pad), np.asarray(g_live),
                             rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", ["ref", "pallas"])
@pytest.mark.parametrize("kernel,kwargs", [("linear", ()),
                                           ("rbf", (("h", 1.0),))])
def test_saturated_coverage_padding_no_gain(backend, kernel, kwargs):
  part, mask = _padded_partition(seed=3)
  part = jnp.abs(part)
  live = np.asarray(mask)
  obj = O.SaturatedCoverage(kernel=kernel, kernel_kwargs=kwargs, alpha=0.3,
                            backend=backend)
  st_pad = obj.init(part, mask.astype(part.dtype))
  st_live = obj.init(part[jnp.asarray(live)])
  g_pad = obj.gains(st_pad, part)
  g_live = obj.gains(st_live, part)
  np.testing.assert_allclose(np.asarray(g_pad), np.asarray(g_live),
                             rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_information_gain_padded_candidates_never_selected(backend):
  """Candidate-side padding: greedy with cand_mask must never pick a padded
  row, even though a zero row has positive IG gain under rbf."""
  part, mask = _padded_partition(seed=5)
  obj = O.InformationGain(k_max=8, kernel="rbf", kernel_kwargs=(("h", 0.75),),
                          sigma=0.5, backend=backend)
  # sanity: the padded (zero) candidate row really does have positive gain
  g = obj.gains(obj.init_d(D), part)
  assert float(g[int(np.argmin(np.asarray(mask)))]) > 0.0
  r = greedy(obj, obj.init_d(D), part, 8, cand_mask=mask)
  sel = np.asarray(r.idx)
  sel = sel[sel >= 0]
  assert np.asarray(mask)[sel].all(), "greedy selected a padded row"


@pytest.mark.parametrize("backend", ["ref", "pallas"])
def test_facility_location_padded_candidates_never_selected(backend):
  part, mask = _padded_partition(seed=6)
  obj = O.FacilityLocation(kernel="rbf", kernel_kwargs=(("h", 1.0),),
                           backend=backend)
  st0 = obj.init(part, mask.astype(part.dtype))
  r = greedy(obj, st0, part, 6, cand_mask=mask)
  sel = np.asarray(r.idx)
  sel = sel[sel >= 0]
  assert np.asarray(mask)[sel].all(), "greedy selected a padded row"


def test_graph_cut_padded_universe_rows_no_gain():
  """Zero-weight (padded) universe rows have exactly zero cut gain, so the
  cut objective is padding-safe by construction; verify through both
  backends."""
  n, n_pad = 20, 6
  w = jnp.abs(jax.random.normal(jax.random.PRNGKey(0), (n, n)))
  wp = jnp.zeros((n + n_pad, n + n_pad)).at[:n, :n].set(w)
  for backend in ("ref", "pallas"):
    obj = O.GraphCut(backend=backend)
    st = obj.init_w(wp)
    st = obj.update(st, jnp.eye(n + n_pad)[2])
    g = obj.gains(st, jnp.eye(n + n_pad))
    np.testing.assert_allclose(np.asarray(g[n:]), 0.0, atol=1e-6)


def test_permute_rows_matches_gather_without_moving_holes(subrun):
  """The epoch's shard-to-shard row move equals a plain gather by the
  partition permutation, bit for bit, with hole rows left at their fill --
  both in one all_to_all round and when the small shards need several."""
  out = subrun("""
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.core.partition import partition_perm, permute_rows
from repro.util import make_mesh
mesh = make_mesh((4,), ("data",))
sh = NamedSharding(mesh, P("data"))
for n in (32, 16384):
  x = jax.device_put(jnp.arange(n * 3, dtype=jnp.float32).reshape(n, 3), sh)
  g = jax.device_put(jnp.arange(n, dtype=jnp.int32) - 5, sh)
  v = jax.device_put((jnp.arange(n) % 3) != 0, sh)
  perm = partition_perm(jax.random.PRNGKey(n), n)
  a, b = jax.jit(lambda x, g, p, v: permute_rows(
      (x, g), (0, -1), p, v, mesh=mesh, axis_names=("data",)))(x, g, perm, v)
  pn = np.asarray(perm)
  keep = np.asarray(v)[pn]
  np.testing.assert_array_equal(
      np.asarray(a), np.where(keep[:, None], np.asarray(x)[pn], 0))
  np.testing.assert_array_equal(
      np.asarray(b), np.where(keep, np.asarray(g)[pn], -1))
  assert a.sharding.spec == P("data")
print("PERMUTE_OK")
""", n_devices=4)
  assert "PERMUTE_OK" in out
