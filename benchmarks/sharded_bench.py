"""Sharded-serving gates: bit-equal streams on a model=N mesh, per-shard
cache bytes at the projected 1/N slice, and a mesh-blind host scheduler.

Three gates (violations raise — the CI smoke for the shard_map-ped serving
engine; see docs/architecture.md §Sharded serving for the design):

1. **Bit-equality.** Greedy streams from ``ServingEngine(mesh=model:N)``
   must be identical to the single-device engine for N in {2, 4, 8}
   across (dense | paged) x (bf16 | int8 pool) x (chunked prefill |
   speculative decode) — sharding is an execution strategy, never a
   sampling change. The reduced config's 4 query / 2 KV heads shard at
   N=2 and hit the GQA-atomic replication fallback at N=4 and N=8, so
   both the partitioned and the replicated cache paths are exercised.
2. **Per-shard cache bytes.** On every paged sharded run the engine's
   measured ``cache_bytes_hwm_shard`` must not exceed the per-device
   figure projected by ``roofline.report.serving_projection`` from the
   same serving-rule table, plus one page of slack — i.e. exactly
   ``total / N`` when heads shard and ``total`` under the replication
   fallback. The accounting is measured from real shard buffers
   (``addressable_shards``), so a silent replication regression fails
   here rather than flattering the projection.
3. **Mesh-blind host policy.** ``serving/scheduler.py`` and
   ``serving/kv_pool.py`` must contain zero mesh- or shard-aware
   identifiers (AST scan of names, attributes, args and imports —
   docstrings may mention the concept). Admission, eviction, paging and
   SLO policy run on page *indices*; the mesh only ever decides how the
   arrays behind those indices are laid out.

Reported (not gated): tokens/s per (mode, N) and the projected
bandwidth-bound tick floor per device. Headline figures land in
``BENCH_sharded.json`` (schema in docs/benchmarks.md); ``perf_compare``
diffs them against the committed baseline. Needs >= 2 visible devices
and fails with fewer; on the CPU give it 8:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m benchmarks.run --only sharded
"""
from __future__ import annotations

import ast
import json
import os
import time

import jax
import numpy as np

from repro.configs import get_config
from repro.distributed.sharding import serving_rules
from repro.launch.mesh import make_serving_mesh
from repro.models import model as M
from repro.models.layers import ModelOptions
from repro.roofline.report import serving_projection
from repro.serving import Request, ServingEngine

DESCRIPTION = ("Sharded serving gates: greedy streams bit-equal "
               "single-device vs model=N mesh for N in {2,4,8} x "
               "{dense,paged} x {bf16,int8} x {chunked,spec_decode} "
               "(incl. the GQA-atomic replication fallback), per-shard "
               "cache_bytes_hwm at the serving_projection 1/N slice + one "
               "page slack, and an AST scan proving scheduler/kv_pool stay "
               "mesh-blind; writes BENCH_sharded.json")

ARCH = "smollm-135m"
MAX_SEQ = 64
PAGE_SIZE = 8
N_SLOTS = 2
N_REQS = 4
MAX_TOKENS = 8
MESH_SIZES = (2, 4, 8)

# every valid cell of {dense, paged} x {bf16, int8} x {chunked, spec};
# int8 pools require --paged, so the dense/int8 column is empty by
# construction. "paged" rides along as the plain-tick flagship.
_CHUNK = dict(chunked_prefill=True, chunk_size=PAGE_SIZE, token_budget=32)
_SPEC = dict(spec_decode=True, spec_k=3)
MODES = {
    "paged": dict(paged=True, page_size=PAGE_SIZE),
    "dense_chunked": dict(**_CHUNK),
    "dense_spec": dict(**_SPEC),
    "paged_chunked": dict(paged=True, page_size=PAGE_SIZE, **_CHUNK),
    "paged_spec": dict(paged=True, page_size=PAGE_SIZE, **_SPEC),
    "int8_chunked": dict(paged=True, page_size=PAGE_SIZE, kv_dtype="int8",
                         **_CHUNK),
    "int8_spec": dict(paged=True, page_size=PAGE_SIZE, kv_dtype="int8",
                      **_SPEC),
}

# host-side policy files the mesh must never leak into (gate 3)
MESH_BLIND_FILES = ("src/repro/serving/scheduler.py",
                    "src/repro/serving/kv_pool.py")

BENCH_PATH = os.path.join(os.environ.get("BENCH_DIR", "."),
                          "BENCH_sharded.json")


def _requests(cfg):
    rng = np.random.default_rng(0)
    return [rng.integers(2, cfg.vocab_size // 2,
                         size=int(rng.integers(5, 24))).astype(np.int32)
            for _ in range(N_REQS)]


def _run(cfg, opts, params, prompts, mesh=None, **kw):
    eng = ServingEngine(cfg, opts, params, n_slots=N_SLOTS, max_seq=MAX_SEQ,
                        eos=-999, fused=True, tick_tokens=4, mesh=mesh, **kw)
    for i, p in enumerate(prompts):
        eng.submit(Request(uid=i, prompt=p.copy(), max_tokens=MAX_TOKENS))
    t0 = time.perf_counter()
    done = eng.run()
    wall = time.perf_counter() - t0
    assert len(done) == len(prompts), "engine dropped requests"
    return {r.uid: r.out_tokens for r in done}, eng, wall


def _code_identifiers(path):
    """Every identifier the module's *code* mentions — names, attributes,
    call/def args, imports. Docstrings and comments are not code."""
    with open(path) as f:
        tree = ast.parse(f.read(), filename=path)
    idents = set()
    for node in ast.walk(tree):
        for field in ("id", "attr", "name", "arg", "module", "asname"):
            v = getattr(node, field, None)
            if isinstance(v, str):
                idents.add(v)
    return idents


def _gate_mesh_blind(emit):
    for rel in MESH_BLIND_FILES:
        path = os.path.join(os.path.dirname(__file__), os.pardir, rel)
        bad = sorted(i for i in _code_identifiers(path)
                     if "mesh" in i.lower() or "shard" in i.lower())
        assert not bad, (f"{rel} must stay mesh-blind but mentions "
                         f"{bad} — sharding belongs to the engine's "
                         f"device stages, never to host policy")
        emit(f"sharded/mesh_blind/{os.path.basename(rel)}", 0.0, "clean")


def run(emit) -> None:
    _gate_mesh_blind(emit)
    if jax.device_count() < 2:
        raise RuntimeError(
            f"the sharded bench needs >= 2 devices, JAX sees "
            f"{jax.device_count()}; on the CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count=8")
    sizes = tuple(n for n in MESH_SIZES if n <= jax.device_count())

    cfg = get_config(ARCH).reduced()
    opts = ModelOptions(remat=False)
    params = M.init_params(M.model_template(cfg), jax.random.PRNGKey(0))
    prompts = _requests(cfg)

    headline = {}
    for mode, kw in MODES.items():
        ref, _, _ = _run(cfg, opts, params, prompts, **kw)
        for n in sizes:
            got, eng, wall = _run(cfg, opts, params, prompts,
                                  mesh=make_serving_mesh(n), **kw)
            assert got == ref, (
                f"sharded greedy stream diverged: mode={mode} model={n}")
            st = eng.stats
            rules = serving_rules(n, cfg.num_heads, cfg.num_kv_heads)
            sharded = rules["kv_heads"] is not None
            if kw.get("paged"):
                # gate 2: measured per-shard bytes vs the rule-table
                # projection, one page of slack for allocator rounding
                proj = serving_projection(cfg, n, st.cache_bytes_hwm)
                assert proj.heads_sharded == sharded
                slack = eng._bytes_per_page_shard
                assert st.cache_bytes_hwm_shard <= (
                    proj.cache_bytes_per_dev + slack), (
                    f"mode={mode} model={n}: per-shard HWM "
                    f"{st.cache_bytes_hwm_shard} exceeds projected "
                    f"{proj.cache_bytes_per_dev} + page {slack}")
            toks = sum(len(v) for v in ref.values())
            emit(f"sharded/{mode}/model{n}", wall / toks * 1e6,
                 f"tok_s={toks / wall:.1f};"
                 f"{'heads_sharded' if sharded else 'replicated'};"
                 f"shard_hwm={st.cache_bytes_hwm_shard}")
            headline[f"{mode}_model{n}_tok_s"] = round(toks / wall, 2)

    proj2 = serving_projection(cfg, 2, 0.0)
    report = {"schema": 1, "bench": "sharded", "arch": ARCH,
              "mesh_sizes": list(sizes), "modes": sorted(MODES),
              "t_tick_proj_model2_us": round(proj2.t_tick_s * 1e6, 4),
              **headline}
    with open(BENCH_PATH, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
        f.write("\n")
    emit("sharded/bench_json", 1.0, f"path={BENCH_PATH};schema=1")
