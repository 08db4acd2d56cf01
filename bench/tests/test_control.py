"""The control comes out not correct: the plain reference with float8
weights, its first token put in the served token's place on the same
prompts and tokens, is judged by the same checks as a run, at the cell's
own limit, and fails them on each of three seeds, while the program passes
them (``bench/limits.py`` at the registry's reduced size on the CPU, with a
window long enough to judge as many steps as a run does). The cell's limit
itself is set from readings at the cell's size on the chip (PERF.md)."""
import json
import os
import subprocess
import sys
from pathlib import Path

from harness import spec

ROOT = Path(__file__).resolve().parents[2]


def test_fp8_control_is_not_correct_at_the_cells_limit():
    cell = spec.benchmark()["workloads"][0]["name"]
    lim = spec.limits(cell)
    p = subprocess.run(
        [sys.executable, "bench/limits.py", "--workload", cell, "--seeds",
         "21,22,23", "--control-seeds", "21,22,23", "--seconds", "4",
         "--rehearse"], cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-2000:]
    rows = [json.loads(x) for x in p.stdout.strip().splitlines()[:-1]]
    assert len(rows) == 3
    for r in rows:
        assert r["failed"] == 0
        assert r["served_tokens"] == lim["sample_steps"] * (144 + 48)
        assert r["limit"] == lim["max_logit_gap"]
        assert r["correct"] is True
        assert r["control_correct"] is False
        assert r["control_gap"] > r["limit"] > r["gap"]
