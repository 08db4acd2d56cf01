"""Paged vs dense KV cache: decode throughput, cache memory, prefix sharing,
and the quantized (int8/fp8) page-pool gates.

Six gates (violations raise, so this doubles as the CI smoke for the
paged-KV subsystem — see docs/benchmarks.md for how to read the output):

1. **Bit-equality.** Paged decode (page pool + per-slot page tables) must
   emit token streams bit-identical to the dense reference layout under
   greedy sampling, on both the fused and per-token engine paths.
2. **Memory proportionality.** Per-request cache memory under paging must
   scale with pages actually used (ceil(len/page_size) pages), not with the
   ``max_seq`` each dense slot over-allocates.
3. **Prefix caching.** Repeated prompts (the serving pattern for repeated
   robot observations) must hit the pool's prefix cache, and shared pages
   must be counted in ``EngineStats.prefix_hits``.
4. **Quantized greedy agreement.** int8 paged decode must emit greedy token
   streams identical to the bf16 paged engine on this workload (fp8
   agreement is reported but not gated — e4m3's 3-bit mantissa leaves less
   argmax margin, and a cross-platform near-tie must not flake CI).
5. **Quantized memory.** The int8/fp8 pool (1-byte codes + per-page-per-head
   f32 scales) must cost <= 0.55x the *bf16-equivalent* bytes per page (2
   bytes/element, the paper-facing comparison) and <= 0.30x the engine's
   actual f32 oracle pool, on both bytes-per-page and ``cache_bytes_hwm``.
6. **Logit error bound.** Stepwise decode logits of the quantized pool must
   stay within an absolute bound of the bf16 paged logits (int8 tighter
   than fp8), measured over a fresh prefill + decode rollout.

Reported rows: tokens/s per layout/dtype, per-request cache bytes, pool
high-water marks, quantized byte ratios and max logit errors.

Also reported (not gated): a **scale-granularity study** — the stored
prefix KV fake-quantized at per-page, per-(page, head) (the pool's shipped
format) and per-(page, head, token) scale granularity, teacher-forced
against the unquantized rollout. The rows quantify the accuracy/overhead
trade the per-(page, head) choice sits on: finer scales cost f32 sidecar
elements per page, coarser scales couple every head's range to the page's
loudest head.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.models import kv_quant
from repro.models import model as M
from repro.models.layers import ModelOptions
from repro.models.stacks import is_paged_leaf
from repro.serving import Request, ServingEngine
from repro.serving.engine import _scatter_pages, _scatter_slot

DESCRIPTION = ("Paged-vs-dense KV gates: greedy bit-equality, memory ~ pages "
               "used, prefix-cache hits, int8/fp8 quantized-pool agreement + "
               "<=0.55x bf16 bytes + logit error bounds")

ARCH = "smollm-135m"
PAGE_SIZE = 8
MAX_SEQ = 64
N_SLOTS = 2

# absolute logit-error bounds vs the bf16 paged rollout (gate 6); measured
# max errors on this workload are ~0.06 (int8) / ~0.22 (fp8), bounds carry
# ~3x margin so only a real regression (scale mishandling, drift) trips them
INT8_LOGIT_TOL = 0.2
FP8_LOGIT_TOL = 0.75


def _run_engine(cfg, opts, params, reqs, *, paged, fused=True,
                kv_dtype="bf16"):
    eng = ServingEngine(cfg, opts, params, n_slots=N_SLOTS, max_seq=MAX_SEQ,
                        eos=-999, fused=fused, tick_tokens=4,
                        paged=paged, page_size=PAGE_SIZE, kv_dtype=kv_dtype)
    for i, (p, m) in enumerate(reqs):
        eng.submit(Request(uid=i, prompt=p.copy(), max_tokens=m))
    t0 = time.perf_counter()
    done = eng.run()
    wall = time.perf_counter() - t0
    assert len(done) == len(reqs), "engine dropped requests"
    return {r.uid: r.out_tokens for r in done}, done, eng, wall


def _logit_rollout(cfg, opts, params, prompt, n_steps, kv_dtype,
                   force_tokens=None):
    """Prefill + n_steps decode against a hand-built page table; returns
    (per-step logits [n_steps, V], greedy tokens [n_steps]). Component-level
    (no engine) so the quantized-vs-bf16 comparison is purely about pool
    storage. ``force_tokens`` teacher-forces the fed tokens (pass the bf16
    rollout's greedy tokens) so a near-tie argmax flip in the quantized run
    cannot compound into unrelated downstream logits — the comparison then
    measures pure storage-induced drift at every step."""
    ps, npg = PAGE_SIZE, MAX_SEQ // PAGE_SIZE
    logits, cache1 = M.prefill(cfg, opts, params, {"tokens": prompt[None]},
                               MAX_SEQ, cache_dtype=jnp.float32)
    caches = M.init_caches(cfg, 1, MAX_SEQ, jnp.float32, opts, paged=True,
                           num_pages=npg + 1, page_size=ps,
                           kv_dtype=kv_dtype)
    # identity mapping: logical page i -> physical page i+1 (0 is the null
    # page); prefill pages scattered, decode-growth pages left zeroed
    pt = jnp.arange(1, npg + 1, dtype=jnp.int32)[None]
    dest = np.zeros(npg, np.int32)
    n_prompt_pages = len(prompt) // ps
    dest[:n_prompt_pages] = np.arange(1, n_prompt_pages + 1)
    caches = _scatter_pages(caches, cache1, jnp.asarray(dest), ps)
    caches = _scatter_slot(caches, cache1, 0, skip_paged=True)
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    out, greedy = [], []
    for i in range(n_steps):
        idx = jnp.asarray([len(prompt) + i], jnp.int32)
        logits, caches = M.decode_step(cfg, opts, params, tok, caches, idx,
                                       page_table=pt)
        out.append(logits[0, -1])
        nxt = int(jnp.argmax(logits[0, -1]))
        greedy.append(nxt)
        tok = jnp.asarray([[nxt if force_tokens is None
                            else force_tokens[i]]], jnp.int32)
    return jnp.stack(out), greedy


def _fake_quant_cache(caches, dtype, reduce_axes):
    """Round-trip every dense KV cache leaf through ``dtype`` codes with
    amax scales at a chosen granularity. Leaves are head-major
    ``[..., K, S, h]`` (kv-head, token, head-dim trailing); the token axis
    is reshaped to ``(num_pages, PAGE_SIZE)`` so ``reduce_axes`` — relative
    to the reshaped ``[..., K, np, ps, h]`` — selects the scale
    granularity: ``(-4, -2, -1)`` per-page, ``(-2, -1)`` per-(page, head)
    (the pool's format), ``(-1,)`` per-(page, head, token)."""
    def leaf(x):
        if not jnp.issubdtype(x.dtype, jnp.floating) or x.ndim < 3:
            return x
        S = x.shape[-2]
        y = x.reshape(x.shape[:-2] + (S // PAGE_SIZE, PAGE_SIZE)
                      + x.shape[-1:])
        a = jnp.max(jnp.abs(y.astype(jnp.float32)), axis=reduce_axes,
                    keepdims=True)
        scale = a / kv_quant.qmax(kv_quant.quant_dtype(dtype))
        y = kv_quant.decode(
            kv_quant.encode(y, scale, kv_quant.quant_dtype(dtype)), scale)
        return y.reshape(x.shape).astype(x.dtype)

    return jax.tree_util.tree_map(leaf, caches)


def _dense_rollout(cfg, opts, params, prompt, n_steps, quant=None,
                   force_tokens=None):
    """Prefill + teacher-forced decode on the dense cache layout; ``quant``
    fake-quantizes the prefill cache before decoding, so the logit delta vs
    the unquantized rollout isolates stored-prefix quantization error at the
    chosen granularity (decode-written rows stay full precision)."""
    logits, caches = M.prefill(cfg, opts, params, {"tokens": prompt[None]},
                               MAX_SEQ, cache_dtype=jnp.float32)
    if quant is not None:
        caches = quant(caches)
    tok = jnp.argmax(logits[:, -1], -1).astype(jnp.int32)[:, None]
    out, greedy = [], []
    for i in range(n_steps):
        idx = jnp.asarray([len(prompt) + i], jnp.int32)
        logits, caches = M.decode_step(cfg, opts, params, tok, caches, idx)
        out.append(logits[0, -1])
        nxt = int(jnp.argmax(logits[0, -1]))
        greedy.append(nxt)
        tok = jnp.asarray([[nxt if force_tokens is None
                            else force_tokens[i]]], jnp.int32)
    return jnp.stack(out), greedy


# (reduce_axes over [..., K, np, ps, h], f32 scale elements per page-head)
GRANULARITIES = (("per_page", (-4, -2, -1)),
                 ("per_page_head", (-2, -1)),
                 ("per_page_head_token", (-1,)))


def run(emit):
    cfg = get_config(ARCH).reduced()
    opts = ModelOptions(remat=False)
    params = M.init_params(M.model_template(cfg), jax.random.PRNGKey(0),
                           jnp.float32)
    rng = np.random.default_rng(0)

    # mixed lengths/budgets + one repeated observation (prefix-cache target)
    shared = rng.integers(0, cfg.vocab_size, 16, dtype=np.int32)
    reqs = [(shared, 6),
            (rng.integers(0, cfg.vocab_size, 9, dtype=np.int32), 8),
            (shared, 4),
            (rng.integers(0, cfg.vocab_size, 5, dtype=np.int32), 10),
            (shared, 7)]

    results = {}
    for mode, paged in (("dense", False), ("paged", True)):
        toks, done, eng, wall = _run_engine(cfg, opts, params, reqs,
                                            paged=paged)
        n_tok = sum(len(v) for v in toks.values())
        results[mode] = (toks, done, eng)
        emit(f"kv_cache/{mode}/decode", wall / n_tok * 1e6,
             f"tok_s={n_tok / wall:.1f};decode_syncs={eng.stats.decode_syncs}")

    # -- gate 1: bit-equality under greedy sampling ------------------------
    assert results["paged"][0] == results["dense"][0], \
        "paged decode diverged from the dense reference layout"
    ref_toks, _, _, _ = _run_engine(cfg, opts, params, reqs, paged=True,
                                    fused=False)
    assert ref_toks == results["dense"][0], \
        "per-token paged decode diverged from the dense reference layout"
    emit("kv_cache/paged/bit_equal", 1.0, "greedy_streams_match=True")

    # -- gate 2: per-request cache memory ~ pages used, not max_seq --------
    _, done_p, eng_p = results["paged"]
    bpp = eng_p._bytes_per_page
    dense_req_bytes = bpp * (MAX_SEQ // PAGE_SIZE)   # every slot, always
    for r in sorted(done_p, key=lambda r: r.uid):
        need = -(-(len(reqs[r.uid][0]) + len(r.out_tokens)) // PAGE_SIZE)
        got = r.pages_used
        assert 0 < got <= need + 1, \
            f"req {r.uid}: {got} pages held for {need} pages of tokens"
        emit(f"kv_cache/paged/req{r.uid}_bytes", float(got * bpp),
             f"pages={got};shared={r.pages_shared};"
             f"dense_bytes={dense_req_bytes}")
        assert got * bpp < dense_req_bytes, \
            f"req {r.uid}: paged cache not smaller than dense max_seq"
    emit("kv_cache/paged/pool_hwm_bytes", float(eng_p.stats.cache_bytes_hwm),
         f"pages_hwm={eng_p.stats.pages_hwm};"
         f"dense_total={bpp * N_SLOTS * (MAX_SEQ // PAGE_SIZE)}")

    # -- gate 3: prefix cache hits for the repeated observation ------------
    hits = eng_p.stats.prefix_hits
    assert hits >= 2 * (len(shared) // PAGE_SIZE), \
        f"repeated prompts produced only {hits} prefix-cache page hits"
    emit("kv_cache/paged/prefix_hits", float(hits),
         f"repeated_prompts=3;full_pages_each={len(shared) // PAGE_SIZE}")

    # -- gates 4+5: quantized pool — greedy agreement + memory -------------
    # the engine's unquantized pool stores f32 (the bit-equality oracle);
    # the paper-facing ratio compares against what bf16 storage would cost
    bf16_equiv_bpp = sum(
        leaf.size * 2 // eng_p.pool.num_pages for path, leaf in
        jax.tree_util.tree_leaves_with_path(eng_p.caches)
        if is_paged_leaf(path))
    for kv_dtype in ("int8", "fp8"):
        toks_q, done_q, eng_q, wall_q = _run_engine(
            cfg, opts, params, reqs, paged=True, kv_dtype=kv_dtype)
        n_tok = sum(len(v) for v in toks_q.values())
        match = [u for u in toks_q if toks_q[u] == results["paged"][0][u]]
        emit(f"kv_cache/{kv_dtype}/decode", wall_q / n_tok * 1e6,
             f"tok_s={n_tok / wall_q:.1f};"
             f"streams_matching_bf16={len(match)}/{len(reqs)}")
        if kv_dtype == "int8":
            assert toks_q == results["paged"][0], \
                "int8 paged greedy streams diverged from bf16 paged"
        assert eng_q.stats.prefix_hits == hits, \
            f"{kv_dtype}: quantized pool lost prefix-cache hits"
        bpp_q = eng_q._bytes_per_page
        ratio_bf16 = bpp_q / bf16_equiv_bpp
        ratio_f32 = bpp_q / bpp
        emit(f"kv_cache/{kv_dtype}/bytes_per_page", float(bpp_q),
             f"vs_bf16={ratio_bf16:.3f};vs_f32_oracle={ratio_f32:.3f}")
        assert ratio_bf16 <= 0.55, \
            f"{kv_dtype} pool costs {ratio_bf16:.3f}x bf16 (> 0.55x)"
        assert ratio_f32 <= 0.30, \
            f"{kv_dtype} pool costs {ratio_f32:.3f}x the f32 pool (> 0.30x)"
        assert eng_q.stats.pages_hwm == eng_p.stats.pages_hwm, \
            f"{kv_dtype}: page high-water diverged from bf16 paging"
        hwm_bf16_equiv = eng_p.stats.pages_hwm * bf16_equiv_bpp
        emit(f"kv_cache/{kv_dtype}/pool_hwm_bytes",
             float(eng_q.stats.cache_bytes_hwm),
             f"bf16_equiv_hwm={hwm_bf16_equiv};"
             f"ratio={eng_q.stats.cache_bytes_hwm / hwm_bf16_equiv:.3f}")
        assert eng_q.stats.cache_bytes_hwm <= 0.55 * hwm_bf16_equiv, \
            f"{kv_dtype} cache_bytes_hwm not <= 0.55x the bf16 paged figure"

    # -- gate 6: stepwise logit error vs the bf16 paged rollout ------------
    # teacher-forced on the bf16 greedy tokens: every step feeds the same
    # token to both pools, so the error measures storage drift alone
    prompt = rng.integers(0, cfg.vocab_size, 16, dtype=np.int32)
    ref_logits, ref_greedy = _logit_rollout(cfg, opts, params, prompt, 8,
                                            "bf16")
    for kv_dtype, tol in (("int8", INT8_LOGIT_TOL), ("fp8", FP8_LOGIT_TOL)):
        q_logits, q_greedy = _logit_rollout(cfg, opts, params, prompt, 8,
                                            kv_dtype,
                                            force_tokens=ref_greedy)
        err = float(jnp.max(jnp.abs(q_logits - ref_logits)))
        spread = float(jnp.max(ref_logits) - jnp.min(ref_logits))
        agree = sum(a == b for a, b in zip(ref_greedy, q_greedy))
        emit(f"kv_cache/{kv_dtype}/logit_err", err,
             f"tol={tol};logit_spread={spread:.2f};"
             f"greedy_agree={agree}/{len(ref_greedy)}")
        assert err <= tol, \
            f"{kv_dtype} decode logits drifted {err:.4f} from bf16 (> {tol})"

    # -- scale-granularity study (reported, not gated) ---------------------
    # same teacher-forced protocol as gate 6 but on the dense layout with
    # the prefix KV fake-quantized at three scale granularities; sidecar =
    # f32 scale elements per (page, layer, K/V) — the storage the finer
    # granularity buys its accuracy with (page rows are ps*h elements)
    g_logits, g_greedy = _dense_rollout(cfg, opts, params, prompt, 8)
    n_kv = cfg.num_kv_heads
    sidecar = {"per_page": 1, "per_page_head": n_kv,
               "per_page_head_token": PAGE_SIZE * n_kv}
    for kv_dtype in ("int8", "fp8"):
        for gran, axes in GRANULARITIES:
            q = lambda c: _fake_quant_cache(c, kv_dtype, axes)
            ql, qg = _dense_rollout(cfg, opts, params, prompt, 8, quant=q,
                                    force_tokens=g_greedy)
            err = float(jnp.max(jnp.abs(ql - g_logits)))
            agree = sum(a == b for a, b in zip(g_greedy, qg))
            emit(f"kv_cache/granularity/{kv_dtype}/{gran}", err,
                 f"greedy_agree={agree}/{len(g_greedy)};"
                 f"scale_elems_per_page={sidecar[gran]}")
