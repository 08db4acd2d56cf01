"""chip_smoke.py's control flow on the CPU, and the compile-cache rule.

The smoke runs as a child process (as it is run on the chip: one process,
its own JAX) with the compile cache pointed into the test's temp dir.
"""
from __future__ import annotations

import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

ROOT = Path(__file__).resolve().parents[1]
SMOKE = ROOT / "chip_smoke.py"


def _run(args, cwd, tmp_path, script=SMOKE):
    # the script finds the program beside itself, not on PYTHONPATH
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env.update(JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    return subprocess.run([sys.executable, str(script), *args], cwd=cwd,
                          env=env, capture_output=True, text=True,
                          timeout=600)


def test_smoke_reduced_runs_every_phase(tmp_path):
    r = _run(["--reduced"], ROOT, tmp_path)
    assert r.returncode == 0, r.stderr[-3000:]
    lines = r.stdout.strip().splitlines()
    assert json.loads(lines[-1]) == {
        "ok": True, "device": {"platform": "cpu", "kind": "cpu",
                               "count": 1}}
    out = "\n".join(lines[:-1])
    assert f"compile cache {tmp_path / 'cache'}" in out
    assert "served 8 requests x 148 tokens" in out
    assert "logits kernels vs jnp reference: max_abs_err=" in out
    assert "compilations=" in out


def test_smoke_without_tpu_fails_and_prints_no_result(tmp_path):
    r = _run([], ROOT, tmp_path)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout
    assert "no TPU" in r.stderr


def test_smoke_alone_fails(tmp_path):
    """Copied out of the checkout it finds none of the program."""
    alone = tmp_path / "alone"
    alone.mkdir()
    shutil.copy(SMOKE, alone)
    r = _run(["--reduced"], alone, tmp_path, script=alone / SMOKE.name)
    assert r.returncode != 0
    assert '"ok"' not in r.stdout


@pytest.fixture
def jax_cache_config():
    """Restores the cache settings ``enable_compile_cache`` changes."""
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs")
    before = {n: getattr(jax.config, n) for n in names}
    yield before
    for n, v in before.items():
        jax.config.update(n, v)


def test_compile_cache_follows_the_environment(monkeypatch,
                                               jax_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere")
    assert compile_cache.enable_compile_cache() == "/elsewhere"
    assert (jax.config.jax_compilation_cache_dir
            == jax_cache_config["jax_compilation_cache_dir"])
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0


def test_compile_cache_default_is_fixed_and_ignored(monkeypatch,
                                                    jax_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.enable_compile_cache()
    assert got == str(ROOT / ".jax_compile_cache")
    assert jax.config.jax_compilation_cache_dir == got
    ignored = (ROOT / ".gitignore").read_text().splitlines()
    assert any(l.strip("/") == ".jax_compile_cache" for l in ignored)
