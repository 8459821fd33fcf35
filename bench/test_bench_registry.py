"""Every cell resolves by name to its files, and a cell added as files
alone is picked up."""
import json
import pathlib
import re
import shutil

import pytest

from bench import drivers, registry

BENCH = registry.load_benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


@pytest.mark.parametrize("workload", [w["name"] for w in BENCH["workloads"]])
def test_workload_resolves_to_its_files(workload):
  cell = registry.Cell(BENCH, workload)
  assert cell.traffic["kind"] in drivers.KINDS
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
    assert all(NAME.match(n) for n in names)
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
