"""Every cell resolves by name to its files, and a cell or a deployment
added as files alone is picked up."""
import filecmp
import json
import pathlib
import re
import shutil
import time

import jax
import numpy as np
import pytest

from bench import (corpus, drivers, kernel_work, reference, registry, tiny,
                   tracing)
from bench import run as R

BENCH = registry.load_benchmark()


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_workload_resolves_to_its_files(workload):
  cell = registry.Cell(BENCH, workload)
  assert issubclass(drivers.driver_for(cell.traffic["kind"]), drivers.Driver)
  assert callable(drivers.corpus_draw(cell.config))
  if cell.traffic["kind"] == "epochs":
    assert callable(drivers.epoch_reference(cell.config))
  assert cell.config["name"] == cell.workload["config"]
  for key in cell.config_entry["reduced"]:
    assert key in cell.config
  assert any(m["name"] == "setup_s" for m in cell.end_to_end)
  assert len(cell.end_to_end) >= 2 and cell.per_layer
  for m in cell.per_layer:
    assert callable(cell.reader(m["name"]))
    assert m["moves"] in [e["name"] for e in cell.end_to_end]
  limits = json.loads((registry.BENCH / "limits" / f"{workload}.json")
                      .read_text())["limits"]
  assert limits and all(v >= 0 for v in limits.values())


def test_names_and_paths_follow_the_contract():
  for group in ("configs", "workloads", "end_to_end", "per_layer"):
    names = [e["name"] for e in BENCH[group]]
    assert len(names) == len(set(names))
    assert all(registry.NAME.fullmatch(n) for n in names)
  for c in BENCH["configs"]:
    assert c["file"].startswith("bench/")
  assert BENCH["paths"] == ["bench"]


def test_a_cell_added_as_files_alone_is_picked_up(tmp_path: pathlib.Path):
  root = tmp_path
  shutil.copytree(registry.BENCH, root / "bench")
  bench = json.loads((registry.ROOT / "BENCHMARK.json").read_text())
  (root / "bench" / "traffic" / "epochs_burst.json").write_text(
      json.dumps({"kind": "epochs"}))
  (root / "bench" / "metrics" / "new_count.epoch.py").write_text(
      "def read(ctx):\n  return ctx.counters.get('epochs')\n")
  (root / "bench" / "limits" / "tiny-epoch-burst.json").write_text(
      json.dumps({"limits": {"feat_gap": 0.0}}))
  bench["workloads"].append({"name": "tiny-epoch-burst",
                             "config": "tinyimages-3072",
                             "traffic": "epochs_burst", "chips": 1,
                             "why": "added as data"})
  bench["per_layer"].append({"name": "new_count.epoch", "unit": "epochs",
                             "better": "higher", "source": "program_counter",
                             "layer": "protocol and greedy",
                             "moves": "epoch_s",
                             "workloads": ["tiny-epoch-burst"]})
  bench["end_to_end"][0]["workloads"].append("tiny-epoch-burst")
  (root / "BENCHMARK.json").write_text(json.dumps(bench))
  cell = registry.Cell(registry.load_benchmark(root), "tiny-epoch-burst",
                       root=root)
  assert cell.traffic == {"kind": "epochs"}
  assert [m["name"] for m in cell.per_layer] == ["new_count.epoch"]
  assert {m["name"] for m in cell.end_to_end} == {"epoch_s", "setup_s"}

  class Ctx:
    counters = {"epochs": 12}
  assert cell.reader("new_count.epoch")(Ctx()) == 12
  with pytest.raises(KeyError):
    registry.Cell(registry.load_benchmark(root), "no-such-cell", root=root)


def _copy_bench(root: pathlib.Path) -> None:
  shutil.copytree(registry.BENCH, root / "bench",
                  ignore=shutil.ignore_patterns("__pycache__"))


def _unedited(root: pathlib.Path) -> bool:
  """Every file of the repository's bench/ is in the copy, byte for byte."""
  files = [p for p in registry.BENCH.rglob("*")
           if p.is_file() and "__pycache__" not in p.parts]
  return all(filecmp.cmp(p, root / "bench" / p.relative_to(registry.BENCH),
                         shallow=False) for p in files)


# each planted file appends its directory to <root>/used.txt when it runs
_USED = """
import pathlib
def _used(what):
  with open(pathlib.Path(__file__).resolve().parents[2] / "used.txt",
            "a") as f:
    f.write(what + "\\n")
"""
PLANTED = {
    "corpora/soft_clusters.py": _USED + """
import jax, jax.numpy as jnp
def draw(cfg, n, seed):
  _used("corpora")
  k = jax.random.PRNGKey(int(cfg["corpus"]["corpus_seed"]))
  z = jax.nn.softmax(jax.random.normal(k, (n, int(cfg["d"]) - 1)), axis=1)
  rows = jnp.concatenate([z, jnp.ones((n, 1), z.dtype)], axis=1)
  return rows, jnp.argmax(z, axis=1)
""",
    "objectives/saturated_coverage.py": _USED + """
import numpy as np
def epoch_numbers(x, rng, m, cfg, sel_gids, sel_feats, value):
  _used("objectives")
  sel = np.asarray(sel_gids, np.int64)
  return {"feat_gap": float(np.max(np.abs(np.asarray(sel_feats) - x[sel])))}
""",
    "kinds/soft_epochs.py": _USED + """
from bench import drivers
class Driver(drivers.Epochs):
  def setup(self):
    _used("kinds")
    super().setup()
    self.counters["objective"] = type(self.svc.objective).__name__
    self.counters["kernel_kwargs"] = self.svc.objective.kernel_kwargs
""",
    "work/soft_kernel.py": _USED + """
def work(operands, results):
  _used("work")
  return 197e6, 0.0
""",
}


def _plant_deployment(root: pathlib.Path) -> None:
  """A configuration whose epoch reference, corpus, traffic driver and
  kernel work are all new files, with its cell in BENCHMARK.json."""
  _copy_bench(root)
  b = root / "bench"
  for rel, text in PLANTED.items():
    (b / rel).parent.mkdir(exist_ok=True)
    (b / rel).write_text(text)
  cfg = json.loads((b / "configs" / "tinyimages-3072.json").read_text())
  cfg.update(name="soft-6", d=6, rows_per_chip=512, kappa=8, k_final=8,
             append_block=256, objective="saturated_coverage", kernel="rbf",
             kernel_kwargs={"h": 0.5},
             corpus={"kind": "soft_clusters", "corpus_seed": 3})
  (b / "configs" / "soft-6.json").write_text(json.dumps(cfg))
  (b / "traffic" / "soft_epochs.json").write_text(
      json.dumps({"kind": "soft_epochs"}))
  (b / "limits" / "soft-epoch.json").write_text(
      json.dumps({"limits": {"feat_gap": 0.0}}))
  bench = json.loads((registry.ROOT / "BENCHMARK.json").read_text())
  bench["configs"].append({"name": "soft-6", "source": "test",
                           "file": "bench/configs/soft-6.json",
                           "reduced": [], "why": "added as files"})
  bench["workloads"].append({"name": "soft-epoch", "config": "soft-6",
                             "traffic": "soft_epochs", "chips": 1,
                             "why": "added as files"})
  bench["end_to_end"][0]["workloads"].append("soft-epoch")
  (root / "BENCHMARK.json").write_text(json.dumps(bench))


def test_a_deployment_added_as_files_alone_runs(tmp_path: pathlib.Path):
  root = tmp_path
  _plant_deployment(root)
  cell = registry.Cell(registry.load_benchmark(root), "soft-epoch",
                       root=root)
  res = R.run(cell, 2 ** 31 + 4242, 0.5, False, jax.devices(),
              t_start=time.perf_counter())
  assert res["correct"], res["checks"]
  assert set(res["checks"]) == {"feat_gap"}
  assert res["counters"]["objective"] == "SaturatedCoverage"
  assert res["counters"]["kernel_kwargs"] == (("h", 0.5),)
  assert set(res["metrics"]) == {"epoch_s", "setup_s"}
  # the kernel's work, and a roofline read through it
  calls = {"soft_kernel.4": ([("f32", (8, 6))], [("f32", (8,))])}
  red = tracing.Reduction(window_s=1.0, busy_s=1.0, devices=1,
                          op_s={"soft_kernel.4": 1e-3},
                          op_count={"soft_kernel.4": 2}, calls=calls,
                          collective_s={}, gaps={})
  assert kernel_work.work("soft_kernel", *calls["soft_kernel.4"],
                          root) == (197e6, 0.0)
  ctx = R.Context(red, tracing.load_peaks("TPU v5 lite"), {}, root)
  assert ctx.roofline("soft_kernel") == pytest.approx(100 * 2e-6 / 1e-3)
  used = (root / "used.txt").read_text().split()
  assert set(used) == {"corpora", "objectives", "kinds", "work"}
  assert _unedited(root)


@pytest.mark.parametrize("what, find", [
    ("objectives", lambda root: drivers.epoch_reference(
        {"objective": "no_such"}, root)),
    ("corpora", lambda root: drivers.corpus_draw(
        {"corpus": {"kind": "no_such"}}, root)),
    ("kinds", lambda root: drivers.driver_for("no_such", root)),
    ("metrics", lambda root: registry.load(root, "metrics", "no_such")),
])
def test_an_unknown_name_names_the_file_it_sought(tmp_path, what, find):
  _copy_bench(tmp_path)
  with pytest.raises(KeyError, match=re.escape(
      str(tmp_path / "bench" / what / "no_such.py"))):
    find(tmp_path)


def test_an_unknown_kernel_has_no_work(tmp_path):
  _copy_bench(tmp_path)
  ops, res = [("f32", (8, 6)), ("f32", (8, 6))], [("f32", (8, 8))]
  assert kernel_work.work("no_such_kernel", ops, res, tmp_path) is None
  assert kernel_work.work("pairwise", ops, res, tmp_path) == \
      kernel_work.WORK["pairwise"](ops, res)


@pytest.mark.parametrize("entry", [c for c in BENCH["configs"]],
                         ids=lambda c: c["name"])
def test_facility_builtins_are_the_direct_calls(entry):
  cfg = json.loads((registry.ROOT / entry["file"]).read_text())
  assert cfg["objective"] == "facility"
  cfg.update(d=32, kappa=8)
  cfg["corpus"] = dict(cfg["corpus"], clusters=32)
  seed = 2 ** 31 + 99
  rows, labels = drivers.corpus_draw(cfg)(cfg, 256, seed)
  rows0, labels0 = corpus.draw(cfg, 256, seed)
  np.testing.assert_array_equal(np.asarray(rows), np.asarray(rows0))
  np.testing.assert_array_equal(np.asarray(labels), np.asarray(labels0))
  x = np.asarray(rows)
  rng = jax.random.PRNGKey(5)
  sel = reference.round1(x, rng, 1, 8)
  value = reference.facility_value(x, sel)
  got = drivers.epoch_reference(cfg)(x, rng, 1, cfg, sel, x[sel], value)
  assert got == reference.epoch_numbers(x, rng, 1, 8, sel, x[sel], value)


@pytest.mark.parametrize("kwargs, want", [({"h": 0.5}, (("h", 0.5),)),
                                          (None, ())])
def test_kernel_kwargs_reach_the_service(kwargs, want):
  cfg = json.loads((registry.ROOT / BENCH["configs"][0]["file"]).read_text())
  cfg.update(d=8, kappa=4, k_final=4, append_block=256, kernel="rbf")
  if kwargs is not None:
    cfg["kernel_kwargs"] = kwargs
  svc = drivers._service(cfg, drivers._mesh(jax.devices(), 1), 1024)
  assert svc.objective.kernel_kwargs == want


@pytest.mark.parametrize("workload", ["marco-ingest", "marco-query"])
def test_sieve_kinds_refuse_another_objective(monkeypatch, workload):
  def no_device_work(*a, **k):
    raise AssertionError("the corpus was drawn before the refusal")

  monkeypatch.setitem(drivers.CORPORA, "clusters", no_device_work)
  cell = tiny.cell(workload, objective="info_gain")
  drv = drivers.driver_for(cell.traffic["kind"])(
      cell.config, cell.traffic, 7, 1, jax.devices())
  with pytest.raises(ValueError, match="facility-location references"):
    drv.setup()
