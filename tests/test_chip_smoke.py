"""The chip smoke run's phases, at a tiny size on the CPU backend.

``chip_smoke.py`` drives the service's main path once and checks every
result against a host reference.  Its phase function takes sizes, so the
same checks run here on 2^11 rows; only ``main`` insists on a TPU.
"""
from __future__ import annotations

import importlib.util
import os
import subprocess
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _smoke():
  spec = importlib.util.spec_from_file_location(
      "chip_smoke", os.path.join(REPO, "chip_smoke.py"))
  mod = importlib.util.module_from_spec(spec)
  spec.loader.exec_module(mod)
  return mod


def test_one_chip_phases_pass_on_cpu():
  lines = []
  phases = _smoke().run_one_chip(n=2048, n_parity=256, log=lines.append)
  checks = [ln for ln in lines if ln.startswith("check ")]
  assert checks and all(": ok" in ln for ln in checks), lines
  for name in ("epoch 2 warm selection == cold selection",
               "main query_batch == sequential query()",
               "parity epoch 1 device vs cpu-ref",
               "exact tier values vs host f64"):
    assert any(ln.startswith(f"check {name}: ok") for ln in checks), name
  assert {"append 75%", "epoch 0", "query_batch exact"} <= set(phases)


def test_main_refuses_a_backend_that_is_not_tpu():
  out = subprocess.run(
      [sys.executable, os.path.join(REPO, "chip_smoke.py")],
      env=dict(os.environ, JAX_PLATFORMS="cpu"), capture_output=True,
      text=True, timeout=300)
  assert out.returncode != 0
  assert '"ok"' not in out.stdout
  assert "needs a TPU" in out.stderr


def test_compile_cache_goes_where_the_environment_says(monkeypatch, tmp_path):
  import jax

  from repro.util import compile_cache
  prev = jax.config.jax_compilation_cache_dir
  prev_meta = jax.config.jax_compilation_cache_include_metadata_in_key
  try:
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == prev  # left to JAX
    # a cached executable keeps the op names (scopes) of its own build
    assert jax.config.jax_compilation_cache_include_metadata_in_key
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache() == os.path.join(REPO, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == os.path.join(
        REPO, ".jax_cache")
  finally:
    jax.config.update("jax_compilation_cache_dir", prev)
    jax.config.update("jax_compilation_cache_include_metadata_in_key",
                      prev_meta)
