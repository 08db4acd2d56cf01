"""Top-level model API, driven entirely by ModelConfig.

    template = model_template(cfg)            # PSpec tree (shapes+axes+init)
    params   = init_params(template, key)     # concrete weights
    logits   = forward(cfg, opts, params, batch)            # train / scoring
    logits, caches = prefill(cfg, opts, params, batch, max_seq)
    logits, caches = decode_step(cfg, opts, params, tok, caches, index)

``batch`` is a dict: tokens [B,S] (+ 'frames' [B,T,e] for audio enc-dec,
'patches' [B,T,e] for VLMs — the stubbed modality frontends).
"""
from __future__ import annotations

from typing import Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.distributed.sharding import constrain
from repro.models import action as action_lib
from repro.models import stacks
from repro.models.layers import ModelOptions, apply_norm
from repro.models.params import PSpec, init_params, param_shapes  # re-export
from repro.models.stacks import init_caches  # re-export

__all__ = ["model_template", "forward", "prefill", "prefill_chunk",
           "embed_prompt", "decode_step", "draft_step", "verify_chunk",
           "decode_loop", "encode_vision", "init_params", "init_caches",
           "ModelOptions"]


def model_template(cfg: ModelConfig) -> Dict:
    d = cfg.d_model
    t: Dict = {
        "embed": PSpec((cfg.vocab_size, d), ("vocab", "embed"), fan_in=d),
        "decoder": stacks.decoder_template(cfg),
    }
    t.update(stacks._norm_template(cfg, "final_norm", d))
    if not cfg.tie_embeddings:
        # stored [V, D] like the embedding so the logits einsum contracts on
        # D and GSPMD keeps the vocab dim model-sharded (see §Perf iter 1:
        # a [D, V] layout + transpose made XLA compute full-vocab logits
        # per device)
        t["lm_head"] = PSpec((cfg.vocab_size, d), ("vocab", "embed"), fan_in=d)
    if cfg.pos == "absolute":
        # sized for the largest assigned decode shape (decode_32k)
        t["pos"] = PSpec((32_768, d), (None, None), "pos")
    if cfg.encoder is not None:
        t["encoder"] = stacks.tower_template(cfg.encoder, d)
    if cfg.vision is not None:
        t["vision"] = stacks.tower_template(cfg.vision, d)
    if cfg.action is not None and cfg.action.mode == "dit":
        t["action_dit"] = action_lib.dit_template(cfg.action, d)
    return t


def _embed_tokens(params, tokens, cfg: ModelConfig, positions=None,
                  shard_axis=None):
    emb = params["embed"]
    if shard_axis is not None and emb.shape[0] != cfg.vocab_size:
        # vocab-sharded trace (shard_map): shard i holds embedding rows
        # [i*vl, (i+1)*vl). Look up the local slice with out-of-range ids
        # masked to row 0, zero the misses, and psum — exactly one shard
        # contributes each token's row
        vl = emb.shape[0]
        i = jax.lax.axis_index(shard_axis)
        loc = tokens - i * vl
        ok = (loc >= 0) & (loc < vl)
        x = jnp.take(emb, jnp.where(ok, loc, 0), axis=0)
        x = jax.lax.psum(jnp.where(ok[..., None], x, jnp.zeros_like(x)),
                         shard_axis)
    else:
        x = jnp.take(emb, tokens, axis=0)
    if cfg.pos == "absolute":
        pos = positions if positions is not None else jnp.arange(tokens.shape[-1])
        x = x + jnp.take(params["pos"], pos, axis=0).astype(x.dtype)
    return x


def _encode_context(params, batch, cfg: ModelConfig, opts: ModelOptions):
    """Run the stubbed-frontend towers. Returns (cross_ctx, prefix_embeds)."""
    ctx = prefix = None
    if cfg.encoder is not None:  # whisper: cross-attention context
        ctx = stacks.apply_tower(params["encoder"], batch["frames"],
                                 cfg.encoder, opts)
    if "prefix" in batch:        # precomputed vision prefix (see encode_vision)
        prefix = batch["prefix"]
    elif cfg.vision is not None:
        if "patches" not in batch:
            raise KeyError("vision model needs batch['patches'] "
                           "(or a precomputed batch['prefix'])")
        # VLM: prefix tokens in the LM sequence
        prefix = stacks.apply_tower(params["vision"], batch["patches"],
                                    cfg.vision, opts)
    return ctx, prefix


def encode_vision(cfg: ModelConfig, opts: ModelOptions, params, patches):
    """Vision tower alone: patches [B,T,e] -> prefix embeds [B,T,d_model].
    ``prefill``/``forward`` accept the result as ``batch['prefix']``, so the
    serving engine can time the vision phase separately from prefill (the
    paper's phase decomposition)."""
    assert cfg.vision is not None, "encode_vision requires a vision tower"
    return stacks.apply_tower(params["vision"], patches, cfg.vision, opts)


def _logits(params, x, cfg: ModelConfig, shard_axis=None):
    x = apply_norm(params, x, cfg, "final_norm")
    head = params["embed"] if cfg.tie_embeddings else params["lm_head"]
    logits = jnp.einsum("bsd,vd->bsv", x, head)   # head [V, D]
    if shard_axis is not None and head.shape[0] != cfg.vocab_size:
        # vocab-sharded trace (shard_map): the single all-gather of the
        # sharded serving program — local [B,S,V/n] logit slices tile back
        # to the full vocab right before sampling
        logits = jax.lax.all_gather(logits, shard_axis, axis=logits.ndim - 1,
                                    tiled=True)
    return constrain(logits, "batch", "act_seq", "act_vocab")


def _sequence(params, batch, cfg, opts):
    """Token embeddings for full-sequence passes (vision prefix folded in)."""
    tokens = batch["tokens"]
    ctx, prefix = _encode_context(params, batch, cfg, opts)
    if prefix is not None:
        n_vis = prefix.shape[1]
        text = _embed_tokens(params, tokens, cfg,
                             shard_axis=opts.shard_axis)
        x = jnp.concatenate([prefix.astype(text.dtype), text], axis=1)
        S = x.shape[1]
    else:
        x = _embed_tokens(params, tokens, cfg, shard_axis=opts.shard_axis)
        S = x.shape[1]
    positions = jnp.broadcast_to(jnp.arange(S), (x.shape[0], S))
    return x, positions, ctx


def forward(cfg: ModelConfig, opts: ModelOptions, params, batch,
            train: bool = False):
    """Full-sequence forward -> logits [B, S_total, V]."""
    x, positions, ctx = _sequence(params, batch, cfg, opts)
    x = constrain(x, "batch", "act_seq", "act_embed")
    x, _ = stacks.apply_decoder(params["decoder"], x, cfg, opts, positions,
                                ctx=ctx, train=train)
    return _logits(params, x, cfg, shard_axis=opts.shard_axis)


def prefill(cfg: ModelConfig, opts: ModelOptions, params, batch,
            max_seq: int, cache_dtype=jnp.bfloat16, caches=None,
            cache_index=0, page_table=None, live_len=None,
            fresh_caches=None):
    """Process the prompt, filling a decode cache sized ``max_seq``.
    Returns (last-position logits [B,1,V], caches).

    ``cache_index > 0`` is prefill-from-position: ``batch['tokens']`` is a
    *suffix* starting at that position, written into the supplied ``caches``
    and attending to everything already there — the contract chunked prefill
    and prefix-cache compute skip build on (a prefix hit prefills only the
    non-shared suffix). Positioned prefill is tokens-only (a vision prefix
    lives at positions 0..n_vis-1, which a suffix by definition starts
    after) and needs ``caches`` from an earlier prefill or ``init_caches``.
    ``page_table`` [B, npg] routes the writes/reads through a paged pool
    (see serving.kv_pool).

    ``live_len`` (static int) bounds the banded chunk attention core's key
    axis to the live cache prefix ``[0, live_len)``; prefill-from-zero
    derives it from the prompt shape, positioned prefill derives it from a
    static ``cache_index``, and callers with a dynamic ``cache_index``
    (the serving engine) pass the bound explicitly. ``None`` with a
    dynamic index falls back to the full ``max_seq`` view — correct, just
    unbanded."""
    positioned = caches is not None or page_table is not None \
        or not (isinstance(cache_index, int) and cache_index == 0)
    if not positioned:
        x, positions, ctx = _sequence(params, batch, cfg, opts)
        # fresh_caches substitutes for the internally-allocated zeros on
        # this prefill-from-zero path (caller-shaped, e.g. per-shard head
        # slices inside a shard_map trace, where init_caches would build
        # the global head count); it must be a zeroed dense cache tree and
        # does not flip the call into positioned mode
        caches = (fresh_caches if fresh_caches is not None else
                  init_caches(cfg, x.shape[0], max_seq, cache_dtype, opts))
        if live_len is None:
            live_len = x.shape[1]
    else:
        if caches is None:
            raise ValueError("prefill from cache_index > 0 (or through a "
                             "page table) needs existing caches")
        if cfg.encoder is not None or "prefix" in batch or "patches" in batch:
            raise ValueError("positioned prefill is tokens-only; fold the "
                             "vision prefix in at cache_index == 0 (or use "
                             "prefill_chunk over precomputed embeddings)")
        tokens = batch["tokens"]
        B, S = tokens.shape
        positions = jnp.broadcast_to(
            jnp.asarray(cache_index, jnp.int32) +
            jnp.arange(S, dtype=jnp.int32), (B, S))
        x = _embed_tokens(params, tokens, cfg, positions=positions,
                          shard_axis=opts.shard_axis)
        ctx = None
        if live_len is None and isinstance(cache_index, int):
            live_len = cache_index + S
    x, caches = stacks.apply_decoder(params["decoder"], x, cfg, opts,
                                     positions, caches=caches,
                                     cache_index=cache_index, ctx=ctx,
                                     page_table=page_table,
                                     live_len=live_len)
    return _logits(params, x[:, -1:], cfg,
                   shard_axis=opts.shard_axis), caches


def embed_prompt(cfg: ModelConfig, opts: ModelOptions, params, batch):
    """Embedding sequence for a prompt exactly as ``prefill`` would build it
    (vision prefix folded in, absolute position table applied). The chunked
    scheduler computes this once per request and slices it into fixed-size
    ``prefill_chunk`` calls. Encoder-decoder models are not sliceable this
    way (their cross-attention context is whole-sequence state)."""
    if cfg.encoder is not None:
        raise ValueError("chunked prefill does not support encoder-decoder "
                         "models (whole-sequence cross-attention context)")
    x, _, _ = _sequence(params, batch, cfg, opts)
    return x


def prefill_chunk(cfg: ModelConfig, opts: ModelOptions, params, embeds,
                  caches, cache_index, n_valid=None, page_table=None,
                  live_len=None):
    """Positioned prefill over one chunk of precomputed embeddings
    (``embed_prompt`` output sliced to [B, C, d], zero-padded to C).
    Returns (last-valid-position logits [B, 1, V], caches).

    The chunk's queries attend to every cache position ``<=`` their own —
    earlier chunks, and prefix-cache pages the engine never recomputed —
    through the banded chunk core, whose key-axis work covers the live
    prefix ``[0, live_len)`` (``live_len``: static bound on
    ``cache_index + C``, rounded up by the caller to bound retraces; None
    falls back to the full cache view) instead of ``max_seq``. ``n_valid``
    (scalar) marks how many rows are real prompt: padding rows are masked
    out of the cache write path (dense writes dropped, paged writes routed
    to the null page). Only the row at ``n_valid - 1`` runs the lm-head
    projection — a full [C, vocab] projection per chunk would rival the
    chunk's transformer cost, and the caller samples from at most one
    position (the final chunk's last)."""
    B, C, _ = embeds.shape
    positions = jnp.broadcast_to(
        jnp.asarray(cache_index, jnp.int32) +
        jnp.arange(C, dtype=jnp.int32), (B, C))
    x = constrain(embeds, "batch", "act_seq", "act_embed")
    x, caches = stacks.apply_decoder(params["decoder"], x, cfg, opts,
                                     positions, caches=caches,
                                     cache_index=cache_index,
                                     page_table=page_table, n_valid=n_valid,
                                     live_len=live_len)
    last = C - 1 if n_valid is None else jnp.asarray(n_valid, jnp.int32) - 1
    x_last = jax.lax.dynamic_slice_in_dim(x, last, 1, axis=1)
    return _logits(params, x_last, cfg,
                   shard_axis=opts.shard_axis), caches


def decode_step(cfg: ModelConfig, opts: ModelOptions, params, token,
                caches, index, page_table=None):
    """One autoregressive step. token [B,1] int32; index: scalar position or
    per-slot [B] vector (continuous batching). ``page_table`` [B,npg]
    selects the paged cache layout: attention cache leaves are shared
    ``[num_pages, K, page_size, h]`` pools and positions resolve through the
    table (see serving.kv_pool); dense per-slot caches when None. A
    quantized pool (caches built with ``init_caches(kv_dtype="int8"/"fp8")``)
    needs no extra arguments — the int8/fp8 value leaves and their
    ``k_scale``/``v_scale`` siblings ride the cache pytree, and the
    attention layer de/requantizes from their presence alone.
    Returns (logits [B,1,V], new caches)."""
    B = token.shape[0]
    idx = jnp.asarray(index, jnp.int32)
    positions = (jnp.full((B, 1), idx, jnp.int32) if idx.ndim == 0
                 else idx[:, None])
    x = _embed_tokens(params, token, cfg, positions=positions,
                      shard_axis=opts.shard_axis)
    x = constrain(x, "batch", "act_seq", "act_embed")
    x, caches = stacks.apply_decoder(params["decoder"], x, cfg, opts,
                                     positions, caches=caches,
                                     cache_index=index,
                                     page_table=page_table)
    return _logits(params, x, cfg, shard_axis=opts.shard_axis), caches


def draft_step(cfg: ModelConfig, opts: ModelOptions, params, token, caches,
               index, draft_blocks: int, page_table=None, n_valid=None):
    """Layer-truncated decode step — the self-speculative *draft* pass.

    Like ``decode_step`` but only the leading ``draft_blocks`` scanned
    decoder blocks run (``stacks.apply_decoder(n_blocks=...)``); the
    truncated hidden state early-exits through the shared final norm +
    lm head. The draft writes its leading-layer KV into the *same* caches
    the verify pass will rewrite, so no separate draft cache exists —
    rejected positions are neutralized by the verify chunk's full-model
    re-write at those positions. ``n_valid`` (0/1 per slot) masks writes
    for dead slots and positions past the cache capacity (dense scatter
    drop / paged null-page sink). Returns (logits [B,1,V], caches)."""
    B = token.shape[0]
    idx = jnp.asarray(index, jnp.int32)
    positions = (jnp.full((B, 1), idx, jnp.int32) if idx.ndim == 0
                 else idx[:, None])
    x = _embed_tokens(params, token, cfg, positions=positions,
                      shard_axis=opts.shard_axis)
    x = constrain(x, "batch", "act_seq", "act_embed")
    x, caches = stacks.apply_decoder(params["decoder"], x, cfg, opts,
                                     positions, caches=caches,
                                     cache_index=index,
                                     page_table=page_table, n_valid=n_valid,
                                     n_blocks=draft_blocks)
    return _logits(params, x, cfg, shard_axis=opts.shard_axis), caches


def verify_chunk(cfg: ModelConfig, opts: ModelOptions, params, tokens,
                 caches, cache_index, n_valid=None, page_table=None,
                 live_len=None):
    """Speculative *verify* pass: K candidate tokens per slot through the
    full model in one banded chunk-prefill dispatch.

    Like ``prefill_chunk`` with three differences: ``tokens`` [B, K] int32
    are embedded here (the candidates are produced on device, not sliced
    from prompt embeddings); ``cache_index`` may be a per-slot [B] vector —
    each slot's chunk starts at its own live position (positions are
    ``cache_index[:, None] + arange(K)``); and the logits of *every* row
    come back as [B, K, V] — the acceptance rule needs all K next-token
    argmaxes, not just the last valid one (K is small, so the full-chunk
    lm-head projection is cheap, unlike prefill's C-sized chunks).
    ``n_valid`` (scalar or [B]) masks rows past a slot's cache capacity out
    of the write path; their logits are garbage and the engine's budget
    clamp guarantees the acceptance rule never consumes them. The chunk
    write rewrites **all** layers at positions ``cache_index ..
    cache_index+K-1``, which is what erases the draft pass's stale
    leading-layer KV (and any previous round's rejected rows) before
    anything reads those positions."""
    B, K = tokens.shape
    idx = jnp.asarray(cache_index, jnp.int32)
    start = jnp.broadcast_to(idx.reshape(-1, 1), (B, 1))
    positions = start + jnp.arange(K, dtype=jnp.int32)[None]
    x = _embed_tokens(params, tokens, cfg, positions=positions,
                      shard_axis=opts.shard_axis)
    x = constrain(x, "batch", "act_seq", "act_embed")
    x, caches = stacks.apply_decoder(params["decoder"], x, cfg, opts,
                                     positions, caches=caches,
                                     cache_index=cache_index,
                                     page_table=page_table, n_valid=n_valid,
                                     live_len=live_len)
    return _logits(params, x, cfg, shard_axis=opts.shard_axis), caches


def decode_loop(cfg: ModelConfig, opts: ModelOptions, params, token, caches,
                index, n_steps: int, sample_fn=None, page_table=None):
    """``n_steps`` autoregressive decode steps fused on-device via lax.scan —
    one XLA dispatch instead of ``n_steps`` host round-trips.

    index: scalar start position or per-slot [B] vector (continuous
    batching); advanced by 1 every step. ``sample_fn`` maps logits [B,1,V]
    -> tokens [B] (greedy when None). ``page_table`` as in ``decode_step``
    (the table is constant across the fused steps; callers pre-allocate
    pages covering index + n_steps). Quantized paged caches scan through
    unchanged — the int8/fp8 codes and scale leaves are ordinary carry
    state, and the per-step quantize-on-write keeps their dtypes fixed.
    Returns (tokens [B, n_steps], last_token [B,1], caches)."""
    idx = jnp.asarray(index, jnp.int32)

    def step(carry, _):
        tok, caches, idx = carry
        logits, caches = decode_step(cfg, opts, params, tok, caches, idx,
                                     page_table=page_table)
        nxt = (jnp.argmax(logits[:, -1], axis=-1).astype(jnp.int32)
               if sample_fn is None else sample_fn(logits))[:, None]
        return (nxt, caches, idx + 1), nxt[:, 0]

    (last, caches, _), toks = jax.lax.scan(step, (token, caches, idx),
                                           None, length=n_steps)
    return jnp.moveaxis(toks, 0, 1), last, caches


def generate_actions_dit(cfg: ModelConfig, params, cond_hidden, key):
    """Continuous trajectory via the DiT head (cfg.action.mode == 'dit')."""
    assert cfg.action is not None and cfg.action.mode == "dit"
    return action_lib.dit_generate(params["action_dit"], cond_hidden,
                                   cfg.action, key)
