"""Serve a small model with continuously-batched requests (vLLM-style slots,
per-slot cache positions) through the fused device-resident decode loop, and
report the phase latency decomposition plus the host-sync contract — the
paper's measurement, taken on our own serving engine.

    PYTHONPATH=src python examples/serve_batched.py [--reference]
"""
import argparse

import numpy as np

from repro.configs import get_config
from repro.launch.serve import serving_params
from repro.models.layers import ModelOptions
from repro.serving import Request, ServingEngine


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--reference", action="store_true",
                   help="per-token reference path (one host sync per token)")
    p.add_argument("--tick-tokens", type=int, default=8)
    p.add_argument("--paged", action="store_true",
                   help="paged KV cache with prefix sharing instead of "
                        "dense per-slot buffers")
    p.add_argument("--page-size", type=int, default=8,
                   help="tokens per KV page (small default so the 12-token "
                        "demo prompts span a full, shareable page)")
    p.add_argument("--kv-dtype", default="bf16",
                   choices=["bf16", "int8", "fp8"],
                   help="paged pool storage (int8/fp8 = quantized pages "
                        "with per-page scales; needs --paged)")
    p.add_argument("--chunked-prefill", action="store_true",
                   help="token-budget scheduler: chunked prefill packed "
                        "between decode ticks, prefix hits skip compute")
    p.add_argument("--chunk-size", type=int, default=8,
                   help="prefill chunk tokens (multiple of --page-size "
                        "when --paged)")
    p.add_argument("--token-budget", type=int, default=24,
                   help="tokens one tick may spend (decode + chunks)")
    p.add_argument("--prefill-band", type=int, default=32,
                   help="key-block size of the banded prefill attention "
                        "core (prefill key work ~ live prefix, not max_seq)")
    p.add_argument("--spec-decode", action="store_true",
                   help="self-speculative decode: draft K tokens with a "
                        "truncated/quantized pass of the same model, verify "
                        "them in one banded chunk (greedy only)")
    p.add_argument("--spec-k", type=int, default=4,
                   help="speculation depth (needs --spec-decode)")
    p.add_argument("--draft-layers", type=int, default=0,
                   help="draft decoder layers (0 = half the stack)")
    p.add_argument("--draft-quant", default="none",
                   choices=["none", "int8", "fp8"],
                   help="fake-quantize the draft pass's weights")
    args = p.parse_args(argv)

    cfg = get_config("qwen1.5-0.5b").reduced()
    # the platform picks the attention cores: compiled Pallas kernels on
    # the TPU, the jnp cores elsewhere
    opts = ModelOptions(remat=False, prefill_band=args.prefill_band)
    params = serving_params(cfg)
    eng = ServingEngine(cfg, opts, params, n_slots=4, max_seq=96, eos=-1,
                        fused=not args.reference,
                        tick_tokens=args.tick_tokens,
                        paged=args.paged, page_size=args.page_size,
                        kv_dtype=args.kv_dtype,
                        chunked_prefill=args.chunked_prefill,
                        chunk_size=args.chunk_size,
                        token_budget=args.token_budget,
                        spec_decode=args.spec_decode, spec_k=args.spec_k,
                        draft_layers=args.draft_layers or None,
                        draft_quant=(None if args.draft_quant == "none"
                                     else args.draft_quant))

    rng = np.random.default_rng(0)
    shared_prompt = rng.integers(0, cfg.vocab_size, 12, dtype=np.int32)
    for i in range(12):
        # every third request repeats the same observation -> prefix hits
        prompt = (shared_prompt.copy() if args.paged and i % 3 == 0 else
                  rng.integers(0, cfg.vocab_size, 12, dtype=np.int32))
        eng.submit(Request(uid=i, prompt=prompt,
                           max_tokens=int(rng.integers(6, 14))))
    done = eng.run()

    st = eng.stats
    toks = sum(len(r.out_tokens) for r in done)
    span = max(r.t_done for r in done) - min(r.t_submit for r in done)
    mode = "reference" if args.reference else "fused"
    print(f"{len(done)} requests, {toks} tokens, {toks/span:.1f} tok/s "
          f"aggregate with continuous batching ({mode} decode path)")
    contract = (f"host-sync contract: {st.decode_syncs} decode syncs for "
                f"{st.tokens_decoded} decoded tokens over "
                f"{st.device_steps} device steps")
    if not args.reference:
        contract += f" (reference path would pay {st.device_steps})"
    print(contract)
    ph = st.phase_report()
    print(f"engine phases: vision {ph['vision']:.3f}s | "
          f"prefill {ph['prefill']:.3f}s | decode {ph['decode']:.3f}s")
    if "prefill_key_lane_ratio" in ph:
        print(f"banded prefill (band {args.prefill_band}): key-lane ratio "
              f"{ph['prefill_key_lane_ratio']:.3f} vs the full max_seq view")
    if args.paged:
        print(f"paged KV pool ({args.kv_dtype}): pages_hwm {st.pages_hwm} | "
              f"cache_bytes_hwm {st.cache_bytes_hwm} | "
              f"prefix_hits {st.prefix_hits}")
    if args.chunked_prefill:
        ph = st.phase_report()
        print(f"scheduler: chunk {args.chunk_size} / budget "
              f"{args.token_budget} | prefill_tokens {st.prefill_tokens} "
              f"(+{st.prefill_skipped} skipped via prefix cache) | "
              f"ttft mean {np.mean(st.ttft_s):.3f}s | "
              f"decode tick p50/p99 "
              f"{ph.get('decode_tick_p50', 0.0) * 1e3:.1f}/"
              f"{ph.get('decode_tick_p99', 0.0) * 1e3:.1f} ms")
    if args.spec_decode:
        print(f"speculative decode (K {args.spec_k}, draft "
              f"{eng.draft_blocks} blocks, quant {args.draft_quant}): "
              f"{ph.get('spec_accept_per_pass', 0.0):.2f} tokens per "
              f"full-model pass | accept hist "
              f"{ph.get('spec_accept_hist', [])} | draft cost "
              f"{ph.get('spec_draft_frac', 0.0):.2f} of total passes")
    print("per-request phases (queue+prefill | decode):")
    for r in sorted(done, key=lambda r: r.uid)[:6]:
        print(f"  req {r.uid:2d}: {r.t_prefill - r.t_submit:6.3f}s | "
              f"{r.t_done - r.t_prefill:6.3f}s  ({len(r.out_tokens)} tok)")


if __name__ == "__main__":
    main()
