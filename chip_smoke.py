"""Bring-up check of the serving path on a TPU, in one process.

    python chip_smoke.py              # one chip: molmoact-7b, depth cut to 16
    python chip_smoke.py --chips 4    # model=4 sharded serving only
    JAX_PLATFORMS=cpu python chip_smoke.py --reduced   # CPU rehearsal

One chip: molmoact-7b at every published width (d_model 3584, 28/4 heads,
d_ff 18944, vocab 152064, the 24-layer ViT-L tower at 576 patches) with the
decoder cut to 16 of its 28 layers — all 28 layers in bf16 are ~15.8 GB of
weights, the whole 16 GB of one v5e. Random bf16 weights from ``--seed``
serve 8 requests over 4 slots through ``ServingEngine`` built as
``repro.launch.serve`` builds it (paged KV pool, chunked prefill, fused
tick): each request brings 576 patches and a 64-token prompt and generates
the config's 144 CoT + 48 action tokens. The logits of one request are then
teacher-forced through the engine's own compiled prefill and decode stages
twice — Pallas kernels, then the jnp reference cores — and compared.

``--chips 4`` runs only what exists across chips: (b) all 28 layers on a
``model=4`` engine serving the same requests, with per-chip peak bytes, and
(a) the 16-layer cut on ``model=4`` against the one-chip engine on the same
requests (greedy streams, and logits within the tolerance below).

The script exits non-zero when JAX finds no TPU (``--reduced`` runs the
same phases at the registry's reduced size on any platform) and catches no
phase's failure. Its last stdout line is the JSON verdict; everything else
is printed before it. Every phase runs in this process, which owns the
chip; nothing here starts a child process.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

from repro.configs import get_config  # noqa: E402
from repro.launch.compile_cache import (CompileCounter,  # noqa: E402
                                        enable_compile_cache)
from repro.launch.mesh import make_serving_mesh  # noqa: E402
from repro.launch.serve import (build_engine, build_parser,  # noqa: E402
                                serving_params)
from repro.models import model as M  # noqa: E402
from repro.models.layers import band_len, on_tpu, pallas_on  # noqa: E402
from repro.serving import Request  # noqa: E402

ARCH = "molmoact-7b"
DEPTH_CUT = 16         # decoder layers that fit one 16 GB chip with the cache
N_REQUESTS = 4 * 2     # two rounds over the engine's 4 slots
PROBE_STEPS = 8        # teacher-forced decode steps after the first position
# Kernel-vs-reference (and sharded-vs-one-chip) logits agree when the
# largest difference is at most this fraction of the reference's largest
# logit. Both sides hold bf16 activations (8-bit mantissa, relative
# rounding 2^-9) and round them at different points — the kernels keep the
# softmax and the PV product in f32 and round once, the jnp cores round the
# probabilities to bf16 first, a model=4 engine rounds each shard's partial
# sum before its psum — and these differences compound through every
# layer's residual stream (1.1-1.4% measured on the chip). Faults planted
# in a paged kernel (a head read from the wrong KV group, a decode mask
# one position off) moved the reduced model's logits 3-28x past this.
LOGIT_RTOL = 0.05

ENGINE_FLAGS = [
    "--paged", "--chunked-prefill", "--slots", "4", "--tick-tokens", "16",
    # the paged chunk kernel blocks keys per page: page == band on this path
    "--page-size", "32", "--prefill-band", "32", "--chunk-size", "128",
    # more than the 4 slots' whole prompts: every chunk is full, so the
    # chunk program's static key bound takes one value per chunk index
    # (5 compiles for a 640-position prompt, not one per partial chunk)
    "--token-budget", "4096",
]


def _print(*a):
    print(*a, flush=True)


def smoke_config(reduced: bool, depth: int | None):
    cfg = get_config(ARCH)
    if reduced:
        cfg = cfg.reduced()
    if depth is not None and depth < cfg.num_layers:
        cfg = dataclasses.replace(cfg, num_layers=depth)
    return cfg


def make_requests(cfg, seed: int):
    rng = np.random.default_rng(seed)
    v = cfg.vision
    n_new = cfg.n_cot_tokens + cfg.action.num_action_tokens
    return [dict(uid=i,
                 prompt=rng.integers(0, cfg.vocab_size, cfg.n_prompt_tokens,
                                     dtype=np.int32),
                 patches=rng.standard_normal((v.num_tokens, v.embed_dim),
                                             np.float32).astype(jnp.bfloat16),
                 max_tokens=n_new)
            for i in range(N_REQUESTS)]


def serve(cfg, params, reqs, max_seq: int, mesh=None):
    """Serve ``reqs`` through the engine ``repro.launch.serve`` builds;
    check every request came back whole. Returns (engine, uid -> tokens)."""
    args = build_parser().parse_args(ENGINE_FLAGS + ["--max-seq",
                                                     str(max_seq)])
    eng = build_engine(cfg, params, args, mesh)
    cores = ("Pallas kernels, compiled" if on_tpu() else
             "Pallas kernels, interpreted" if pallas_on(eng.opts) else
             "jnp cores")
    _print(f"[smoke] attention: {cores} on {jax.default_backend()}; "
           f"paged pool page_size="
           f"{eng.page_size} pages={eng.pool.num_pages}, chunked prefill "
           f"chunk={eng.chunk_size}, fused tick tokens={eng.tick_tokens}, "
           f"cache dtype={eng.cache_dtype}")
    t0 = time.perf_counter()
    for r in reqs:
        eng.submit(Request(**r))
    done = eng.run()
    wall = time.perf_counter() - t0
    n_prompt = cfg.vision.num_tokens + cfg.n_prompt_tokens
    want = reqs[0]["max_tokens"]
    toks = {r.uid: list(r.out_tokens) for r in done}
    if sorted(toks) != [r["uid"] for r in reqs]:
        raise RuntimeError(f"engine returned requests {sorted(toks)}")
    short = {u: len(t) for u, t in toks.items() if len(t) != want}
    if short:
        raise RuntimeError(f"requests returned {short} tokens, not {want}")
    bad = [u for u, t in toks.items()
           if not all(0 <= x < cfg.vocab_size for x in t)]
    if bad:
        raise RuntimeError(f"token ids out of the vocabulary in {bad}")
    st = eng.stats
    ph = st.phase_report()
    if not ph["vision"] > 0:
        raise RuntimeError("the vision tower never ran")
    if st.prefill_tokens != len(reqs) * n_prompt:
        raise RuntimeError(f"prefilled {st.prefill_tokens} positions, not "
                           f"{len(reqs)} x {n_prompt} (patches + prompt)")
    n_tok = sum(len(t) for t in toks.values())
    _print(f"[smoke] served {len(done)} requests x {want} tokens = {n_tok} "
           f"tokens, {n_prompt} prompt positions each (vision "
           f"{cfg.vision.num_tokens} + text {cfg.n_prompt_tokens}); wall "
           f"{wall:.3f}s incl. compilation (bring-up, not a benchmark); "
           f"vision {ph['vision']:.3f}s prefill {ph['prefill']:.3f}s; "
           f"{st.decode_syncs} decode syncs / {st.device_steps} device "
           f"steps; pages_hwm={st.pages_hwm}")
    return eng, toks


def teacher_forced_logits(eng, programs, req, served):
    """Logits of ``req`` at its first decode position and the next
    PROBE_STEPS positions, each fed the token the engine ``served`` at the
    position before, run through ``programs`` (``eng.stage_programs()``)
    over a fresh pool shaped — and sharded — like the engine's, chunked as
    the engine chunks. Two calls that differ only in their programs see
    the same inputs."""
    cfg, ps, max_seq = eng.cfg, eng.page_size, eng.max_seq
    vision, prefill_chunk, decode = programs
    prefix = vision(eng.params, jnp.asarray(req["patches"][None]))
    emb = M.embed_prompt(cfg, eng.opts, eng.params,
                         {"tokens": jnp.asarray(req["prompt"][None]),
                          "prefix": prefix})
    pool = jax.tree.map(lambda x: jnp.zeros_like(x, device=x.sharding),
                        eng.caches)
    pt = jnp.arange(1, max_seq // ps + 1, dtype=jnp.int32)[None]
    T, C = emb.shape[1], eng.chunk_size
    for start in range(0, T, C):
        n = min(C, T - start)
        x = jnp.zeros((1, C, emb.shape[-1]), emb.dtype)
        x = x.at[:, :n].set(emb[:, start:start + n])
        live = band_len(start + n, eng.opts.prefill_band, max_seq)
        logits, pool = prefill_chunk(eng.params, x, pool,
                                     jnp.asarray(start, jnp.int32),
                                     jnp.asarray(n, jnp.int32), pt, live)
    rows = [logits[0, -1]]
    for i in range(PROBE_STEPS):
        tok = jnp.asarray([[served[i]]], jnp.int32)
        logits, pool = decode(eng.params, tok, pool,
                              jnp.asarray([T + i], jnp.int32), pt)
        rows.append(logits[0, -1])
    return np.asarray(jnp.stack(rows).astype(jnp.float32))


def check_served(got, served):
    """The engine served greedy tokens from its own programs (the chunk
    program, then the fused tick) on the inputs the probe replays: each
    must be the probe's argmax, or within LOGIT_RTOL of it where a
    different program's rounding can flip a near-tie. A token from a wrong
    cache page or position sits at a random logit, far below the top."""
    served = np.asarray(served[:got.shape[0]])
    rows = np.arange(got.shape[0])
    gap = got.max(-1) - got[rows, served]
    slack = LOGIT_RTOL * float(np.max(np.abs(got)))
    _print(f"[smoke] served tokens vs probe argmax: "
           f"{int((got.argmax(-1) == served).sum())}/{len(served)} equal, "
           f"largest logit gap to the argmax {float(gap.max()):.6g} "
           f"(slack {slack:.6g}); first token {served[0]}, probe "
           f"{int(got[0].argmax())}")
    # the first token comes from the very program and inputs the probe
    # replays, so it matches exactly
    if served[0] != got[0].argmax() or not (gap <= slack).all():
        raise RuntimeError("the probe does not reproduce the tokens the "
                           "engine served")


def logit_error(got, ref, what: str):
    err = float(np.max(np.abs(got - ref)))
    scale = float(np.max(np.abs(ref)))
    ok = np.isfinite(got).all() and err <= LOGIT_RTOL * scale
    _print(f"[smoke] logits {what}: max_abs_err={err:.6g} over "
           f"{got.shape[0]} positions (ref max |logit| {scale:.6g}, "
           f"tolerance {LOGIT_RTOL} x {scale:.6g} = {LOGIT_RTOL * scale:.6g}"
           f"); argmax agreement "
           f"{int((got.argmax(-1) == ref.argmax(-1)).sum())}/{got.shape[0]}")
    if not ok:
        raise RuntimeError(f"logits {what} outside tolerance")


def peak_bytes(devices) -> list:
    stats = [d.memory_stats() or {} for d in devices]
    return [s.get("peak_bytes_in_use") for s in stats]


def param_bytes(params) -> int:
    return sum(x.nbytes for x in jax.tree.leaves(params))


def one_chip(args):
    cfg = smoke_config(args.reduced, DEPTH_CUT)
    full = get_config(ARCH)
    _print(f"[smoke] config {cfg.name}: d_model={cfg.d_model} heads="
           f"{cfg.num_heads}/{cfg.num_kv_heads} head_dim={cfg.head_dim} "
           f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} vision "
           f"{cfg.vision.num_layers}L x {cfg.vision.num_tokens} patches")
    if not args.reduced:
        _print(f"[smoke] depth cut: decoder {cfg.num_layers} of "
               f"{full.num_layers} layers — all {full.num_layers} in bf16 "
               f"are ~15.8 GB of weights, the whole 16 GB of one chip")
    params = serving_params(cfg, args.seed)
    _print(f"[smoke] params: bf16, seed {args.seed}, "
           f"{param_bytes(params) / 1e9:.3f} GB")
    reqs = make_requests(cfg, args.seed)
    eng, toks = serve(cfg, params, reqs, args.max_seq)

    # the engine's compiled kernel path against the jnp reference cores:
    # the same programs with only the attention cores swapped
    ref_opts = dataclasses.replace(eng.opts, use_pallas=False)
    req = reqs[0]
    served = toks[req["uid"]]
    got = teacher_forced_logits(eng, eng.stage_programs(), req, served)
    ref = teacher_forced_logits(eng, eng.stage_programs(ref_opts), req,
                                served)
    check_served(got, served)
    logit_error(got, ref, "kernels vs jnp reference")
    _print(f"[smoke] peak_bytes_in_use={peak_bytes(jax.devices()[:1])[0]}")


def four_chips(args):
    if jax.device_count() < 4:
        raise RuntimeError(f"--chips 4 needs 4 devices, JAX sees "
                           f"{jax.device_count()}")
    mesh = make_serving_mesh(4)

    # (b) all layers, sharded: first, so the peaks it prints are its own
    cfg = smoke_config(args.reduced, None)
    params = serving_params(cfg, args.seed, mesh=mesh)
    _print(f"[smoke] (b) {cfg.name} all {cfg.num_layers} layers on "
           f"model=4: params {param_bytes(params) / 1e9:.3f} GB in all")
    reqs = make_requests(cfg, args.seed)
    eng, _ = serve(cfg, params, reqs, args.max_seq, mesh)
    _print(f"[smoke] (b) per-chip peak_bytes_in_use="
           f"{peak_bytes(jax.devices()[:4])} cache_bytes_hwm_shard="
           f"{eng.stats.cache_bytes_hwm_shard}")
    del eng, params
    gc.collect()

    # (a) the one-chip cut against the same cut on model=4
    cfg = smoke_config(args.reduced, DEPTH_CUT)
    reqs = make_requests(cfg, args.seed)
    out = {}
    for name, m in (("one chip", None), ("model=4", mesh)):
        params = serving_params(cfg, args.seed, mesh=m)
        eng, toks = serve(cfg, params, reqs, args.max_seq, m)
        req = reqs[0]
        logits = teacher_forced_logits(eng, eng.stage_programs(), req,
                                       toks[req["uid"]])
        out[name] = (toks, logits)
        del eng, params
        gc.collect()
    (t1, l1), (t4, l4) = out["one chip"], out["model=4"]
    same = [u for u in t1 if t1[u] == t4[u]]
    first = [u for u in t1 if t1[u][0] == t4[u][0]]
    div = {u: next(i for i, (a, b) in enumerate(zip(t1[u], t4[u])) if a != b)
           for u in t1 if u not in same}
    _print(f"[smoke] (a) {cfg.num_layers}-layer cut, model=4 vs one chip: "
           f"greedy streams identical {len(same)}/{len(t1)}, first tokens "
           f"identical {len(first)}/{len(t1)}, first divergence by uid "
           f"{div or 'none'}")
    logit_error(l4, l1, "model=4 vs one chip")


def main(argv=None) -> int:
    p = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    p.add_argument("--chips", type=int, default=1, choices=(1, 4))
    p.add_argument("--reduced", action="store_true",
                   help="the registry's reduced config, on any platform: "
                        "a rehearsal of the control flow, not a chip check")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)
    args.max_seq = 256 if args.reduced else 1024

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.reduced:
        print(f"chip_smoke: JAX found no TPU (platform {dev.platform!r}); "
              f"use --reduced for a CPU rehearsal", file=sys.stderr)
        return 2
    _print(f"[smoke] device {dev.platform} {dev.device_kind} x "
           f"{jax.device_count()}")
    _print(f"[smoke] compile cache {enable_compile_cache()}")
    compiles = CompileCounter()
    t0 = time.perf_counter()
    (one_chip if args.chips == 1 else four_chips)(args)
    _print(f"[smoke] {compiles.summary()}; total wall "
           f"{time.perf_counter() - t0:.1f}s")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": jax.device_count()}}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
