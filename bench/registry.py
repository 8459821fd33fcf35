"""Find a cell's configuration, traffic mix and per-layer metric readers by
the names in BENCHMARK.json, and the code a name stands for: a built-in
table first, then a file ``bench/<directory>/<name>.py`` of the cell's
tree (``lookup``)."""
from __future__ import annotations

import importlib.util
import json
import pathlib
import re

ROOT = pathlib.Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}")


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
  with open(root / "BENCHMARK.json") as f:
    return json.load(f)


def _by_name(entries: list[dict], name: str, what: str) -> dict:
  for e in entries:
    if e["name"] == name:
      return e
  raise KeyError(f"no {what} named {name!r} in BENCHMARK.json")


def load(root: pathlib.Path, directory: str, name: str):
  """The module ``bench/<directory>/<name>.py`` under ``root``; a KeyError
  naming that file where there is none."""
  path = root / "bench" / directory / f"{name}.py"
  if not NAME.fullmatch(name) or not path.is_file():
    raise KeyError(f"no {directory} entry {name!r}: {path} not found")
  spec = importlib.util.spec_from_file_location(
      f"bench_{directory}_{re.sub(r'[^A-Za-z0-9_]', '_', name)}", path)
  mod = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(mod)
  return mod


def lookup(table: dict, root: pathlib.Path, directory: str, name: str,
           attr: str):
  """``table[name]``, else ``attr`` of ``bench/<directory>/<name>.py``
  under ``root`` (a KeyError naming that file where there is none)."""
  if name in table:
    return table[name]
  return getattr(load(root, directory, name), attr)


class Cell:
  """One workload entry with its configuration, traffic mix and the
  metric entries that apply to it."""

  def __init__(self, bench: dict, workload: str,
               root: pathlib.Path = ROOT):
    self.root = root
    self.workload = _by_name(bench["workloads"], workload, "workload")
    self.config_entry = _by_name(bench["configs"], self.workload["config"],
                                 "config")
    with open(root / self.config_entry["file"]) as f:
      self.config = json.load(f)
    traffic = self.workload["traffic"]
    with open(root / "bench" / "traffic" / f"{traffic}.json") as f:
      self.traffic = json.load(f)
    self.chips = int(self.workload["chips"])
    self.run_seconds = int(bench["run_seconds"])
    name = self.workload["name"]
    self.end_to_end = [m for m in bench["end_to_end"]
                       if name in m.get("workloads", [name])]
    self.per_layer = [m for m in bench["per_layer"]
                      if name in m.get("workloads", [name])]

  @property
  def name(self) -> str:
    return self.workload["name"]

  def reader(self, metric: str):
    """The ``read(ctx)`` function of ``metrics/<metric>.py``."""
    return load(self.root, "metrics", metric).read
