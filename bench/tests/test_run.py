"""``bench/run.py`` end to end on the CPU at the registry's reduced size
(``--rehearse``): it serves the fleet, judges the answers with the plain
reference and prints counts and checks, but no metric; asked for a real
cell without a TPU it fails and prints no result; with the tokens altered
where the engine produces them, ``correct`` comes out false; a cell whose
configuration asks for a ``model=4`` mesh runs on four host devices, the
weights drawn in shards and the reference judging the sharded engine. Each
run is a process of its own, so no compiled program carries over."""
import json
import os
import subprocess
import sys
from pathlib import Path

from harness import spec

ROOT = Path(__file__).resolve().parents[2]
CELL = spec.benchmark()["workloads"][0]["name"]
ENV = dict(os.environ, JAX_PLATFORMS="cpu")

# the engine's greedy choice, moved to the next token id where it is made:
# in the fused tick and in the first token after prefill
ALTER = """
import sys
sys.path[:0] = ["bench", "src"]
import jax.numpy as jnp
from repro.serving import sampler
greedy, sample_token = sampler.greedy, sampler.sample_token
V = 256
sampler.greedy = lambda logits, key=None: (greedy(logits) + 1) % V
sampler.sample_token = lambda logits, key, t=0.0, k=0: (
    sample_token(logits, key, t, k) + 1) % V
import run
sys.exit(run.main(sys.argv[1:]))
"""

# the first cell as a later PR would add a four-chip one: its file and entry
# say ``mesh_model`` 4 and ``chips`` 4, and nothing else changes
MESH4 = """
import sys
sys.path[:0] = ["bench", "src"]
from harness import spec
workload, config = spec.workload, spec.config
spec.workload = lambda bm, name: dict(workload(bm, name), chips=4)
spec.config = lambda name: dict(config(name), mesh_model=4)
import run
sys.exit(run.main(sys.argv[1:]))
"""


def bench(*extra, code=None, env=ENV):
    args = ["--workload", CELL, "--seed", str(2**31 + 77), "--seconds", "1",
            "--trace", "0", *extra]
    cmd = ([sys.executable, "-c", code] if code
           else [sys.executable, "bench/run.py"]) + args
    return subprocess.run(cmd, cwd=ROOT, env=env, capture_output=True,
                          text=True, timeout=900)


def test_rehearsal_prints_no_device_metric():
    p = bench("--rehearse")
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1 and line["compiles_in_window"] == 0
    assert "metrics" not in line and list(line)[-1] == "checks"
    bm = spec.benchmark()
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert m["name"] not in p.stdout
    assert "check max_logit_gap" in p.stderr.strip().splitlines()[-2]


def test_real_cell_without_tpu_fails_silently():
    p = bench()
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "no TPU" in p.stderr


def test_altered_tokens_are_not_correct():
    p = bench("--rehearse", code=ALTER)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    gap = line["checks"]["max_logit_gap"]
    assert line["correct"] is False and gap["value"] > gap["limit"]


def test_four_chip_mesh_rehearsal_is_correct():
    env = dict(ENV, XLA_FLAGS="--xla_force_host_platform_device_count=4")
    p = bench("--rehearse", code=MESH4, env=env)
    assert p.returncode == 0, p.stderr[-2000:]
    line = json.loads(p.stdout.strip().splitlines()[-1])
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] >= 1 and line["compiles_in_window"] == 0
    assert "on 4 chip(s)" in p.stderr
    assert "engine mesh (('model', 4),)" in p.stderr
    # the MLP width of 96 split over the mesh: 24 a chip
    assert "a chip holds (4, 64, 24) of wi (4, 64, 96)" in p.stderr
