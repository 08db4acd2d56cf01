"""Self-speculative decode gates: bit-equal streams, >= 2x tokens per
full-model pass, and clean pool accounting under rejection churn.

Three gates (violations raise — the CI smoke for the speculative tick; see
docs/speculative.md for the design and docs/benchmarks.md for how to read
the output):

1. **Bit-equality.** Speculative greedy streams must be identical to the
   plain fused engine on the same cache layout for every K in {2, 4, 8}
   across (dense, bf16), (paged, bf16) and (paged, int8) — speculation is
   an execution strategy, never a sampling change. The shallow 1-layer
   draft used here accepts rarely, so the reject/rollback path is what is
   actually being exercised.
2. **Tokens per full-model pass.** With the high-acceptance draft the
   design centers on (full-depth, int8 fake-quantized weights — the
   1-byte-weight draft stream), the decode-microbench workload must emit
   >= 2x tokens per full-model HBM pass (accepted-per-verify-pass >= 2.0
   at K=4), with streams still bit-equal to the non-speculative engine.
   Tokens per *pass* is the HBM-traffic proxy the paper's memory-bound
   decode phase cares about; wall-clock is reported, not gated.
3. **Pool accounting under rejection.** After a speculative run on the
   quantized paged pool (every verify pass up to K-1 rejected rows), the
   pool must drain to zero pages in use and accept a second identical
   round with identical output — and at the component level, a fully
   masked chunk write (``n_valid=0``) must leave a fresh pool bit-zero:
   masked rows land on the null page as zeros, never on a real page.

Reported (not gated): accepted-per-pass histograms, the draft/verify phase
split in full-model-pass equivalents, tokens/s, and the speculative
key-lane ratio. The headline figures are written to
``BENCH_spec_decode.json`` (schema in docs/benchmarks.md) so the perf
trajectory is tracked per-PR; ``perf_compare`` diffs it against the
committed baseline.
"""
from __future__ import annotations

import json
import os
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models import model as M
from repro.models.layers import ModelOptions, update_cache_paged_chunk
from repro.serving import Request, ServingEngine

DESCRIPTION = ("Self-speculative decode gates: greedy streams bit-equal to "
               "the plain fused engine for K in {2,4,8} x {dense, paged, "
               "int8 pool}, >= 2x tokens per full-model pass with the "
               "full-depth int8-weight draft at K=4, pool pages drained "
               "and null page bit-clean after rejection churn; reports "
               "accept histograms + draft/verify split into "
               "BENCH_spec_decode.json")

ARCH = "smollm-135m"
PAGE_SIZE = 8
MAX_SEQ = 64
N_SLOTS = 2

ACCEPT_GATE = 2.0           # gate 2: accepted tokens per verify pass, K=4

BENCH_PATH = os.path.join(os.environ.get("BENCH_DIR", "."),
                          "BENCH_spec_decode.json")


def _run(cfg, opts, params, reqs, *, paged=False, kv_dtype="bf16", **kw):
    eng = ServingEngine(cfg, opts, params, n_slots=N_SLOTS, max_seq=MAX_SEQ,
                        eos=-999, fused=True, tick_tokens=4, paged=paged,
                        page_size=PAGE_SIZE, kv_dtype=kv_dtype, **kw)
    for i, (p, m) in enumerate(reqs):
        eng.submit(Request(uid=i, prompt=p.copy(), max_tokens=m))
    t0 = time.perf_counter()
    done = eng.run()
    wall = time.perf_counter() - t0
    assert len(done) == len(reqs), "engine dropped requests"
    return {r.uid: r.out_tokens for r in done}, eng, wall


def _gate_bit_equality(cfg, opts, params, emit):
    rng = np.random.default_rng(0)
    reqs = [(rng.integers(0, cfg.vocab_size, int(rng.integers(6, 15)),
                          dtype=np.int32), int(rng.integers(5, 12)))
            for _ in range(4)]
    for mode, paged, kv_dtype in (("dense", False, "bf16"),
                                  ("paged", True, "bf16"),
                                  ("int8", True, "int8")):
        # the int8 reference must share the speculative engines' per-token
        # scale layout: bit-equality is a same-layout contract
        gran = {"scale_granularity": "token"} if kv_dtype == "int8" else {}
        ref, _, _ = _run(cfg, opts, params, reqs, paged=paged,
                         kv_dtype=kv_dtype, **gran)
        for K in (2, 4, 8):
            got, eng, wall = _run(cfg, opts, params, reqs, paged=paged,
                                  kv_dtype=kv_dtype, spec_decode=True,
                                  spec_k=K, draft_layers=1)
            assert got == ref, \
                f"spec stream diverged from fused ({mode}, K={K})"
            ph = eng.stats.phase_report()
            emit(f"spec_decode/{mode}/k{K}/accept_per_pass",
                 ph["spec_accept_per_pass"],
                 f"hist={ph['spec_accept_hist']};"
                 f"verify_passes={eng.stats.spec_verify_passes};"
                 f"bit_equal=True")
    emit("spec_decode/bit_equal", 1.0,
         "layouts=dense,paged,int8;k=2,4,8;streams_match=True")


def _gate_tokens_per_pass(cfg, opts, params, emit):
    # the decode-microbench workload shape: long prompts, decode-dominated
    rng = np.random.default_rng(1)
    reqs = [(rng.integers(0, cfg.vocab_size, 32, dtype=np.int32), 16)
            for _ in range(4)]
    ref, _, wall_ref = _run(cfg, opts, params, reqs)
    got, eng, wall = _run(cfg, opts, params, reqs, spec_decode=True,
                          spec_k=4, draft_layers=cfg.num_layers,
                          draft_quant="int8")
    assert got == ref, "full-depth int8-draft spec stream diverged"
    ph = eng.stats.phase_report()
    app = ph["spec_accept_per_pass"]
    n_tok = sum(len(v) for v in got.values())
    emit("spec_decode/int8_draft/accept_per_pass", app,
         f"gate>={ACCEPT_GATE};k=4;draft_layers={eng.draft_layers};"
         f"hist={ph['spec_accept_hist']}")
    emit("spec_decode/int8_draft/draft_split", ph["spec_draft_frac"],
         f"draft_pass_equiv={ph['spec_draft_pass_equiv']:.2f};"
         f"verify_passes={eng.stats.spec_verify_passes}")
    emit("spec_decode/int8_draft/decode", wall / n_tok * 1e6,
         f"tok_s={n_tok / wall:.1f};nonspec_tok_s={n_tok / wall_ref:.1f};"
         f"reported_not_gated=True")
    assert app >= ACCEPT_GATE, \
        (f"full-depth int8 draft accepted only {app:.2f} tokens per "
         f"full-model pass (< {ACCEPT_GATE}) — speculation is not paying "
         f"for its verify chunks")
    return ph, app, n_tok


def _gate_pool_accounting(cfg, opts, params, emit):
    # engine level: rejection churn (shallow draft) must drain cleanly and
    # leave full capacity behind
    rng = np.random.default_rng(2)
    reqs = [(rng.integers(0, cfg.vocab_size, int(rng.integers(6, 15)),
                          dtype=np.int32), int(rng.integers(5, 12)))
            for _ in range(5)]
    got, eng, _ = _run(cfg, opts, params, reqs, paged=True, kv_dtype="int8",
                       spec_decode=True, spec_k=4, draft_layers=1)
    assert eng.pool.pages_in_use == 0, \
        f"{eng.pool.pages_in_use} pool pages leaked after speculative drain"
    for i, (p, m) in enumerate(reqs):
        eng.submit(Request(uid=100 + i, prompt=p.copy(), max_tokens=m))
    done = {r.uid - 100: r.out_tokens for r in eng.run() if r.uid >= 100}
    assert done == got, "second round on the drained engine diverged"
    assert eng.pool.pages_in_use == 0, "second-round drain leaked pages"
    emit("spec_decode/pool/drained", 0.0,
         f"pages_hwm={eng.stats.pages_hwm};rounds=2;leaked=0")

    # component level: a fully masked chunk write (the shape of every
    # rejected draft row) must leave a fresh quantized pool bit-zero —
    # masked rows are routed to the null page as zeros, and the null
    # page's codes and scales stay zero
    K, h = cfg.num_kv_heads, cfg.head_dim
    pages = jnp.zeros((4, K, PAGE_SIZE, h), jnp.int8)
    scales = jnp.zeros((4, K), jnp.float32)
    pt = jnp.asarray([[1, 2]], jnp.int32)
    rows = jax.random.normal(jax.random.PRNGKey(0), (1, PAGE_SIZE, K, h))
    p2, s2 = update_cache_paged_chunk(pages, rows, pt, 0, n_valid=0,
                                      scales=scales)
    assert not int(jnp.abs(p2.astype(jnp.int32)).sum()), \
        "masked chunk write left nonzero codes in the pool"
    assert not float(jnp.abs(s2).sum()), \
        "masked chunk write perturbed pool scales"
    # sanity: the same write with valid rows does land on the real pages
    p3, s3 = update_cache_paged_chunk(pages, rows, pt, 0,
                                      n_valid=PAGE_SIZE, scales=scales)
    assert int(jnp.abs(p3[1].astype(jnp.int32)).sum()) > 0
    assert not int(jnp.abs(p3[0].astype(jnp.int32)).sum()), \
        "valid chunk write polluted the null page"
    assert not float(jnp.abs(s3[0]).sum())
    # same contract under per-token scales (the speculative pool layout)
    st = jnp.zeros((4, K, PAGE_SIZE), jnp.float32)
    p4, s4 = update_cache_paged_chunk(pages, rows, pt, 0, n_valid=0,
                                      scales=st)
    assert not int(jnp.abs(p4.astype(jnp.int32)).sum()), \
        "masked per-token chunk write left nonzero codes"
    assert not float(jnp.abs(s4).sum()), \
        "masked per-token chunk write perturbed scales"
    emit("spec_decode/pool/null_page_clean", 1.0,
         "masked_write=all_zero;valid_write=real_pages_only;"
         "granularities=head,token")


def run(emit):
    cfg = get_config(ARCH).reduced()
    opts = ModelOptions(remat=False)
    params = M.init_params(M.model_template(cfg), jax.random.PRNGKey(0),
                           jnp.float32)

    _gate_bit_equality(cfg, opts, params, emit)
    ph, app, n_tok = _gate_tokens_per_pass(cfg, opts, params, emit)
    _gate_pool_accounting(cfg, opts, params, emit)

    report = {
        "bench": "spec_decode",
        "schema": 1,
        "spec_k": 4,
        "draft_layers": 4,
        "draft_quant": "int8",
        "accept_per_pass": app,
        "accept_hist": ph["spec_accept_hist"],
        "draft_frac": ph["spec_draft_frac"],
        "draft_pass_equiv": ph["spec_draft_pass_equiv"],
        "spec_key_lane_ratio": ph.get("spec_key_lane_ratio", 1.0),
        "tokens": n_tok,
    }
    with open(BENCH_PATH, "w") as f:
        json.dump(report, f, indent=2, sort_keys=True)
        f.write("\n")
    emit("spec_decode/bench_json", 1.0, f"path={BENCH_PATH};schema=1")
