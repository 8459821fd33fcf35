"""The information-gain deployment's plain reference
(``objectives/info_gain.py``): it agrees with a brute-force float64 greedy,
the service's standard-mode epoch agrees with it within the cell's limits,
and planted faults fail those limits."""
import json

import jax
import numpy as np
import pytest

from bench import drivers, kernel_work, registry, seeds, tiny

REF = registry.load(registry.ROOT, "objectives", "info_gain")
LIMITS = json.loads((registry.BENCH / "limits" / "yahoo-gp-epoch.json")
                    .read_text())["limits"]


def _cfg(**kw) -> dict:
  return tiny.cell("yahoo-gp-epoch", **kw).config


def _rows(cfg: dict, n: int, seed: int) -> np.ndarray:
  x, _ = drivers.corpus_draw(cfg)(cfg, n, seed)
  return np.asarray(x)


def _brute_force(x: np.ndarray, k: int, h: float, sigma: float):
  """Greedy picks and gains of f(S) = 1/2 logdet(I + sigma^-2 K_SS), one
  float64 slogdet per candidate and step; ties to the lowest index."""
  def f(sel):
    if not sel:
      return 0.0
    a = x[sel].astype(np.float64)
    kss = np.exp(-((a[:, None] - a[None]) ** 2).sum(-1) / (h * h))
    return 0.5 * np.linalg.slogdet(np.eye(len(sel)) + kss / sigma ** 2)[1]

  sel, gains = [], []
  for _ in range(k):
    base = f(sel)
    g = np.array([-np.inf if i in sel else f(sel + [i]) - base
                  for i in range(x.shape[0])])
    sel.append(int(np.argmax(g)))
    gains.append(float(g[sel[-1]]))
  return np.asarray(sel), np.asarray(gains), f(sel)


@pytest.mark.parametrize("sigma", [1.0, 0.5])
def test_reference_matches_brute_force_float64(sigma):
  cfg = _cfg(sigma=sigma)
  h = float(cfg["kernel_kwargs"]["h"])
  x = _rows(cfg, 64, 2 ** 31 + 99)
  sel, gains, val = _brute_force(x, 8, h, sigma)
  picks = np.asarray(REF._greedy(jax.numpy.asarray(x.T), 8, h, sigma))
  np.testing.assert_array_equal(picks, sel)
  best, got = REF.walk(x, np.arange(64), sel, h, sigma)
  np.testing.assert_allclose(got, gains, rtol=1e-9, atol=1e-12)
  np.testing.assert_allclose(best, gains, rtol=1e-9, atol=1e-12)
  assert REF.value(x, sel, h, sigma) == pytest.approx(val, rel=1e-12)


def _epoch(seed: int):
  """One standard-mode service epoch over the cell's rows at CPU size:
  (rows, epoch key, cfg, picks, their features, reported value)."""
  from repro.util import make_mesh
  cfg = _cfg()
  n = int(cfg["rows_per_chip"])
  x = _rows(cfg, n, seed)
  svc = drivers._service(cfg, make_mesh((1,), ("data",)), n)
  svc.append(x)
  rng = jax.random.fold_in(seeds.key(seed, seeds.EPOCH_KEYS), 0)
  r = svc.epoch(rng=rng)
  return x, rng, cfg, r.sel_gids, x[r.sel_gids], r.stats.value


def _within(numbers: dict) -> bool:
  return all(v <= LIMITS[k] for k, v in numbers.items())


@pytest.mark.parametrize("seed", [2 ** 31 + 5, 31337, 4_000_000_007])
def test_service_epoch_agrees_with_the_reference(seed):
  x, rng, cfg, sel, feats, value = _epoch(seed)
  assert cfg["mode"] == "standard" and x.shape == (1024, 6)
  assert len(sel) == cfg["k_final"] == 8
  nums = REF.epoch_numbers(x, rng, 1, cfg, sel, feats, value)
  assert set(nums) == set(LIMITS) and _within(nums), nums


def _cond(x: np.ndarray, sel: list, h: float, sigma: float) -> np.ndarray:
  """float64 conditional variance of every row given ``sel``."""
  x = x.astype(np.float64)
  k = lambda a, b: np.exp(-((a[:, None] - b[None]) ** 2).sum(-1) / (h * h))
  m = k(x[sel], x[sel]) + sigma ** 2 * np.eye(len(sel))
  ks = k(x, x[sel])
  return 1.0 + sigma ** 2 - np.einsum("ij,ij->i", ks @ np.linalg.inv(m), ks)


def test_planted_faults_fail_the_limits():
  """A pick swapped for the best row outside the coreset, and a value off
  by 1e-3, each fail their limit; the unplanted epoch passes."""
  x, rng, cfg, sel, feats, value = _epoch(2 ** 31 + 5)
  h, sigma = float(cfg["kernel_kwargs"]["h"]), float(cfg["sigma"])
  assert _within(REF.epoch_numbers(x, rng, 1, cfg, sel, feats, value))
  # the step whose pick leads the best row outside the coreset the most
  gaps = []
  for t in range(1, len(sel)):
    g = 0.5 * np.log(_cond(x, list(sel[:t]), h, sigma) / sigma ** 2)
    out = np.where(np.isin(np.arange(len(x)), sel), -np.inf, g)
    gaps.append(((g[sel[t]] - out.max()) / g[sel[t]], t, int(out.argmax())))
  gap, t, alt = max(gaps)
  assert gap > 10 * LIMITS["greedy_gap"]
  swapped = np.array(sel)
  swapped[t] = alt
  nums = REF.epoch_numbers(x, rng, 1, cfg, swapped, x[swapped], value)
  assert nums["greedy_gap"] > LIMITS["greedy_gap"], nums
  nums = REF.epoch_numbers(x, rng, 1, cfg, sel, feats, value * (1 + 1e-3))
  assert nums["value_rel"] > LIMITS["value_rel"], nums


def test_info_select_work_counts_both_contractions():
  nc, k, d = 11468800, 64, 6
  ops = [("f32", (k, d)), ("f32", (k, k)), ("f32", (nc, d)), ("f32", (1, nc))]
  res = [("f32", (1, 128)), ("s32", (1, 128))]
  flops, moved = kernel_work.work("info_select", ops, res)
  assert flops == 2 * k * nc * d + 2 * k * k * nc
  assert moved == 4 * (k * d + k * k + nc * d + nc + 2 * 128)
  with pytest.raises(ValueError):
    kernel_work.work("info_select", [ops[0], ops[1], ("f32", (nc, 8))], res)
