"""Decoder stacks: templates + scan-over-layers application.

Every architecture is expressed as a repeating *pattern* of sub-layers of
period ``p`` (p=1 for uniform archs, 6 for gemma3's 5:1 local/global, 8 for
jamba's 7:1 mamba/attn). The stack scans over ``L // p`` blocks with stacked
params; the ``L % p`` tail layers are unrolled separately. Every sub-layer
position has a *static* attention window and structure, so sliding-window
layers get banded (linear-FLOP) attention and SSM layers get SSD.
"""
from __future__ import annotations

import functools
from dataclasses import dataclass
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import GLOBAL_WINDOW, ModelConfig, VisionConfig
from repro.distributed.sharding import constrain
from repro.models import kv_quant
from repro.models import layers as L
from repro.models.params import PSpec, stack


# ---------------------------------------------------------------------------
# pattern plan
# ---------------------------------------------------------------------------

@dataclass(frozen=True)
class SubKind:
    mixer: str          # 'attn' | 'mamba'
    ffn: str            # 'dense' | 'moe' | 'moe+dense' | 'none'
    cross: bool
    window: int


def _kind_for_layer(cfg: ModelConfig, i: int) -> SubKind:
    mixer = "attn" if cfg.is_attn_layer(i) else "mamba"
    if cfg.family == "ssm" or (mixer == "mamba" and cfg.d_ff == 0 and not cfg.num_experts):
        ffn = "none"
    elif cfg.is_moe_layer(i):
        ffn = "moe+dense" if cfg.dense_residual else "moe"
    elif cfg.d_ff:
        ffn = "dense"
    else:
        ffn = "none"
    window = cfg.layer_window(i) if mixer == "attn" else GLOBAL_WINDOW
    return SubKind(mixer=mixer, ffn=ffn, cross=(cfg.family == "encdec"),
                   window=window)


def stack_plan(cfg: ModelConfig) -> Tuple[int, int, int]:
    """(period, num_blocks, num_tail_layers)."""
    period = int(np.lcm.reduce([len(cfg.window_pattern),
                                max(cfg.attn_every, 1),
                                max(cfg.moe_every, 1)]))
    period = min(period, cfg.num_layers)
    return period, cfg.num_layers // period, cfg.num_layers % period


def sub_kinds(cfg: ModelConfig) -> Tuple[SubKind, ...]:
    period, _, _ = stack_plan(cfg)
    kinds = tuple(_kind_for_layer(cfg, i) for i in range(period))
    # pattern must be consistent across blocks
    for i in range(cfg.num_layers):
        assert _kind_for_layer(cfg, i) == kinds[i % period], (cfg.name, i)
    return kinds


# ---------------------------------------------------------------------------
# templates
# ---------------------------------------------------------------------------

def _norm_template(cfg: ModelConfig, prefix: str, d: int) -> Dict[str, PSpec]:
    t = {prefix + "_w": PSpec((d,), (None,), "ones")}
    if cfg.norm == "layernorm":
        t[prefix + "_b"] = PSpec((d,), (None,), "zeros")
    return t


def attn_template(cfg: ModelConfig, pre: str = "") -> Dict[str, PSpec]:
    d, n, k, h = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    t = {
        pre + "wq": PSpec((d, n, h), ("embed", "heads", "head_dim"), fan_in=d),
        pre + "wk": PSpec((d, k, h), ("embed", "kv_heads", "head_dim"), fan_in=d),
        pre + "wv": PSpec((d, k, h), ("embed", "kv_heads", "head_dim"), fan_in=d),
        pre + "wo": PSpec((n, h, d), ("heads", "head_dim", "embed"), fan_in=n * h),
    }
    if cfg.qkv_bias:
        t[pre + "bq"] = PSpec((n, h), ("heads", "head_dim"), "zeros")
        t[pre + "bk"] = PSpec((k, h), ("kv_heads", "head_dim"), "zeros")
        t[pre + "bv"] = PSpec((k, h), ("kv_heads", "head_dim"), "zeros")
    return t


def mlp_template(cfg: ModelConfig) -> Dict[str, PSpec]:
    d, f = cfg.d_model, cfg.d_ff
    t = {"wi": PSpec((d, f), ("embed", "mlp"), fan_in=d),
         "wo_mlp": PSpec((f, d), ("mlp", "embed"), fan_in=f)}
    if cfg.act in ("silu", "gelu"):
        t["wg"] = PSpec((d, f), ("embed", "mlp"), fan_in=d)
    return t


def moe_template(cfg: ModelConfig) -> Dict[str, PSpec]:
    d, f = cfg.d_model, cfg.moe_d_ff
    e = max(cfg.num_experts_padded, cfg.num_experts)
    return {
        "router": PSpec((d, e), ("embed", None), fan_in=d),
        "moe_wi": PSpec((e, d, f), ("experts", "embed", "mlp"), fan_in=d),
        "moe_wg": PSpec((e, d, f), ("experts", "embed", "mlp"), fan_in=d),
        "moe_wo": PSpec((e, f, d), ("experts", "mlp", "embed"), fan_in=f),
    }


def mamba_template(cfg: ModelConfig) -> Dict[str, PSpec]:
    d = cfg.d_model
    d_in, H, P, N, G, conv_ch = L.mamba_dims(cfg)
    return {
        "w_z": PSpec((d, d_in), ("embed", "ssm_inner"), fan_in=d),
        "w_xbc": PSpec((d, conv_ch), ("embed", "ssm_inner"), fan_in=d),
        "w_dt": PSpec((d, H), ("embed", None), fan_in=d),
        "conv_w": PSpec((cfg.ssm_conv, conv_ch), ("conv", "ssm_inner"),
                        fan_in=cfg.ssm_conv),
        "conv_b": PSpec((conv_ch,), ("ssm_inner",), "zeros"),
        "A_log": PSpec((H,), (None,), "ssm_a"),
        "dt_bias": PSpec((H,), (None,), "ssm_dt"),
        "d_skip": PSpec((H,), (None,), "ones"),
        "mamba_norm_w": PSpec((d_in,), (None,), "ones"),
        "w_out": PSpec((d_in, d), ("ssm_inner", "embed"), fan_in=d_in),
    }


def layer_template(cfg: ModelConfig, kind: SubKind) -> Dict[str, PSpec]:
    t: Dict[str, PSpec] = {}
    t.update(_norm_template(cfg, "ln1", cfg.d_model))
    if kind.mixer == "attn":
        t.update(attn_template(cfg))
        if kind.cross:
            t.update(_norm_template(cfg, "ln_cross", cfg.d_model))
            t.update(attn_template(cfg, pre="x"))
    else:
        t.update(mamba_template(cfg))
    if kind.ffn != "none":
        t.update(_norm_template(cfg, "ln2", cfg.d_model))
    if kind.ffn in ("dense", "moe+dense"):
        t.update(mlp_template(cfg))
    if kind.ffn in ("moe", "moe+dense"):
        t.update(moe_template(cfg))
    return t


def decoder_template(cfg: ModelConfig) -> Dict:
    period, nblocks, ntail = stack_plan(cfg)
    kinds = sub_kinds(cfg)
    block = {f"sub{j}": layer_template(cfg, kinds[j]) for j in range(period)}
    t = {"blocks": stack(block, nblocks, "layers")}
    if ntail:
        t["tail"] = {f"tail{j}": layer_template(cfg, kinds[j])
                     for j in range(ntail)}
    return t


def tower_template(enc: VisionConfig, d_out: int) -> Dict:
    """Vision/audio encoder tower (pre-LN MHA + plain-gelu MLP) + projector."""
    d, n, f = enc.d_model, enc.num_heads, enc.d_ff
    h = d // n
    layer = {
        "ln1_w": PSpec((d,), (None,), "ones"),
        "ln1_b": PSpec((d,), (None,), "zeros"),
        "wq": PSpec((d, n, h), ("embed", "heads", "head_dim"), fan_in=d),
        "wk": PSpec((d, n, h), ("embed", "heads", "head_dim"), fan_in=d),
        "wv": PSpec((d, n, h), ("embed", "heads", "head_dim"), fan_in=d),
        "wo": PSpec((n, h, d), ("heads", "head_dim", "embed"), fan_in=d),
        "ln2_w": PSpec((d,), (None,), "ones"),
        "ln2_b": PSpec((d,), (None,), "zeros"),
        "wi": PSpec((d, f), ("embed", "mlp"), fan_in=d),
        "wo_mlp": PSpec((f, d), ("mlp", "embed"), fan_in=f),
    }
    return {
        "in_proj": PSpec((enc.embed_dim, d), (None, "embed"), fan_in=enc.embed_dim),
        "pos": PSpec((enc.num_tokens, d), (None, None), "pos"),
        "stack": stack(layer, enc.num_layers, "layers"),
        "final_ln_w": PSpec((d,), (None,), "ones"),
        "final_ln_b": PSpec((d,), (None,), "zeros"),
        "out_proj": PSpec((d, d_out), ("embed", None), fan_in=d),
    }


# ---------------------------------------------------------------------------
# application
# ---------------------------------------------------------------------------

def apply_sublayer(p, x, cfg: ModelConfig, opts: L.ModelOptions, kind: SubKind,
                   positions, cache=None, cache_index=None, ctx=None,
                   page_table=None, n_valid=None, live_len=None):
    """One transformer sub-layer. Returns (x, new_cache_dict)."""
    new_cache: Dict = {}
    h = L.apply_norm(p, x, cfg, "ln1")
    if kind.mixer == "attn":
        attn_cache = None
        if cache is not None and "k" in cache:
            # quantized paged caches carry per-page scale siblings; the
            # 4-tuple form tells layers.attention to de/requantize
            attn_cache = (cache["k"], cache["v"])
            if "k_scale" in cache:
                attn_cache += (cache["k_scale"], cache["v_scale"])
        a, attn_cache = L.attention(p, h, cfg, opts, kind.window, positions,
                                    cache=attn_cache, cache_index=cache_index,
                                    page_table=page_table, n_valid=n_valid,
                                    live_len=live_len)
        if attn_cache is not None:
            new_cache["k"], new_cache["v"] = attn_cache[:2]
            if len(attn_cache) == 4:
                new_cache["k_scale"], new_cache["v_scale"] = attn_cache[2:]
        x = x + a
        if kind.cross:
            hc = L.apply_norm(p, x, cfg, "ln_cross")
            if cache is not None and "xk" in cache and ctx is None:
                kv = (cache["xk"], cache["xv"])
                new_cache["xk"], new_cache["xv"] = kv
            else:
                xk = jnp.einsum("btd,dkh->btkh", ctx, p["xwk"])
                xv = jnp.einsum("btd,dkh->btkh", ctx, p["xwv"])
                if cfg.qkv_bias:
                    xk = xk + p["xbk"].astype(xk.dtype)
                    xv = xv + p["xbv"].astype(xv.dtype)
                kv = (xk, xv)
                if cache is not None:
                    new_cache["xk"], new_cache["xv"] = kv
            a, _ = L.attention(p, hc, cfg, opts, GLOBAL_WINDOW, positions,
                               ctx=kv, ctx_prefix="x", causal=False)
            x = x + a
    else:
        state = cache.get("ssm") if cache else None
        conv_state = cache.get("conv") if cache else None
        decode = cache is not None and x.shape[1] == 1
        m, state, conv_state = L.mamba_block(p, h, cfg, opts,
                                             state=state,
                                             conv_state=conv_state,
                                             decode=decode)
        if cache is not None:
            new_cache["ssm"] = state.astype(cache["ssm"].dtype)
            new_cache["conv"] = conv_state.astype(cache["conv"].dtype)
        x = x + m

    if kind.ffn != "none":
        h = L.apply_norm(p, x, cfg, "ln2")
        y = 0.0
        if kind.ffn in ("dense", "moe+dense"):
            y = y + L.mlp(p, h, cfg, shard_axis=opts.shard_axis)
        if kind.ffn in ("moe", "moe+dense"):
            y = y + L.moe(p, h, cfg, opts)
        x = x + y
    x = constrain(x, "batch", "act_seq", "act_embed")
    return x, new_cache


def apply_decoder(params, x, cfg: ModelConfig, opts: L.ModelOptions,
                  positions, caches=None, cache_index=None, ctx=None,
                  train: bool = False, page_table=None, n_valid=None,
                  live_len=None, n_blocks: Optional[int] = None):
    """Run the full decoder stack. Returns (x, new_caches).

    ``page_table`` [B, npg] switches attention cache leaves to the paged
    layout (shared per-layer pools + per-slot tables); it is a single table
    shared by every layer, captured as a constant by the layer scan.
    ``n_valid`` masks a prefill chunk's padding rows out of the cache write
    path; ``live_len`` (static) bounds the banded chunk core's key axis to
    the live cache prefix (see layers.attention).

    ``n_blocks`` (static) truncates the stack to its leading ``n_blocks``
    scanned blocks — the self-speculative *draft* pass: the shallow model
    shares the full model's parameters and caches (its leading-layer KV
    writes land in the real cache, where the verify pass overwrites them),
    runs ``n_blocks / nblocks`` of the depth, and the caller early-exits
    through the final norm + lm head. The tail sublayers are skipped and
    their caches pass through untouched (the returned tree keeps the full
    structure, so jitted carries are stable)."""
    period, nblocks, ntail = stack_plan(cfg)
    kinds = sub_kinds(cfg)
    if n_blocks is not None:
        if not 0 < n_blocks <= nblocks:
            raise ValueError(f"n_blocks must be in 1..{nblocks}, "
                             f"got {n_blocks}")
        truncate = n_blocks < nblocks or ntail > 0
    else:
        truncate = False

    def block_body(x, block_params, block_caches):
        new_caches = {}
        for j in range(period):
            sub_c = block_caches.get(f"sub{j}") if block_caches else None
            sub_fn = functools.partial(
                apply_sublayer, cfg=cfg, opts=opts, kind=kinds[j],
                positions=positions, cache=sub_c, cache_index=cache_index,
                ctx=ctx, page_table=page_table, n_valid=n_valid,
                live_len=live_len)
            if train and opts.remat and opts.remat_sublayers and period > 1:
                sub_fn = jax.checkpoint(
                    sub_fn, policy=jax.checkpoint_policies.nothing_saveable)
            x, nc = sub_fn(block_params[f"sub{j}"], x)
            if nc:
                new_caches[f"sub{j}"] = nc
        return x, new_caches

    body = block_body
    if train and opts.remat:
        body = jax.checkpoint(block_body,
                              policy=jax.checkpoint_policies.nothing_saveable)

    block_caches = caches.get("blocks") if caches else None
    block_params = params["blocks"]
    if truncate:
        # leading-blocks draft: slice the stacked layer axis (static), scan
        # the shallow stack, splice its cache updates back into the full tree
        block_params = jax.tree_util.tree_map(lambda l: l[:n_blocks],
                                              block_params)
        if block_caches is not None:
            block_caches_in = jax.tree_util.tree_map(lambda l: l[:n_blocks],
                                                     block_caches)
    else:
        block_caches_in = block_caches
    n_eff = n_blocks if truncate else nblocks
    unroll = n_eff if opts.unroll_layers else 1
    if block_caches is None:
        # scan without cache xs
        def scan_nc(carry_x, bp):
            x, _ = body(carry_x, bp, None)
            return x, None
        x, _ = jax.lax.scan(scan_nc, x, block_params, unroll=unroll)
        new_caches = None
    else:
        def scan_c(carry_x, pc):
            bp, bc = pc
            x, nc = body(carry_x, bp, bc)
            return x, nc
        x, new_block_caches = jax.lax.scan(scan_c, x,
                                           (block_params, block_caches_in),
                                           unroll=unroll)
        if truncate:
            new_block_caches = jax.tree_util.tree_map(
                lambda full, new: full.at[:n_blocks].set(new),
                block_caches, new_block_caches)
        new_caches = {"blocks": new_block_caches}

    if truncate:
        if new_caches is not None and ntail and caches and "tail" in caches:
            new_caches["tail"] = caches["tail"]
        return x, new_caches

    if ntail:
        tail_new = {}
        for j in range(ntail):
            tc = caches["tail"].get(f"tail{j}") if caches else None
            x, nc = apply_sublayer(params["tail"][f"tail{j}"], x, cfg, opts,
                                   kinds[j], positions, cache=tc,
                                   cache_index=cache_index, ctx=ctx,
                                   page_table=page_table, n_valid=n_valid,
                                   live_len=live_len)
            if nc:
                tail_new[f"tail{j}"] = nc
        if new_caches is not None:
            new_caches["tail"] = tail_new
    return x, new_caches


def apply_tower(params, embeds, enc: VisionConfig, opts: L.ModelOptions):
    """Vision/audio tower over stubbed frontend embeddings [B,T,embed_dim]."""
    x = jnp.einsum("bte,ed->btd", embeds, params["in_proj"])
    x = x + params["pos"].astype(x.dtype)[None]
    n, d = enc.num_heads, enc.d_model
    h = d // n

    def body(x, p):
        y = L.layer_norm(x, p["ln1_w"], p["ln1_b"])
        q = jnp.einsum("btd,dnh->btnh", y, p["wq"])
        k = jnp.einsum("btd,dnh->btnh", y, p["wk"])
        v = jnp.einsum("btd,dnh->btnh", y, p["wv"])
        pos = jnp.arange(x.shape[1])
        a = L.attention_dense(q, k, v, pos, pos, GLOBAL_WINDOW, causal=False)
        x = x + jnp.einsum("btnh,nhd->btd", a, p["wo"])
        y = L.layer_norm(x, p["ln2_w"], p["ln2_b"])
        y = jax.nn.gelu(jnp.einsum("btd,df->btf", y, p["wi"]))
        x = x + jnp.einsum("btf,fd->btd", y, p["wo_mlp"])
        return x, None

    x, _ = jax.lax.scan(body, x, params["stack"])
    x = L.layer_norm(x, params["final_ln_w"], params["final_ln_b"])
    return jnp.einsum("btd,de->bte", x, params["out_proj"])


# ---------------------------------------------------------------------------
# caches
# ---------------------------------------------------------------------------

def cache_template(cfg: ModelConfig, batch: int, max_seq: int,
                   dtype=jnp.bfloat16, opts: Optional[L.ModelOptions] = None,
                   *, paged: bool = False, num_pages: int = 0,
                   page_size: int = 0, kv_dtype: str = "bf16",
                   scale_granularity: str = "head"):
    """Shape tree (PSpec) for the decode cache; concrete zeros via init_caches.

    Dense (default): attention K/V leaves are per-slot head-major
    ``[batch, K, seq, h]`` buffers over-allocated at ``max_seq``. Paged:
    attention K/V leaves become shared head-major pools
    ``[num_pages, K, page_size, h]`` addressed through a per-slot page table
    (see serving.kv_pool); only attention k/v move to the pool — SSM/conv
    state and cross-attention K/V keep the slot-batched layout (they are
    O(1) or prompt-sized per slot, not decode-growing).

    Head-major: a Pallas kernel's block must tile the array's last two
    dims by (8, 128) or span them whole, so one head's positions × head_dim
    — ``(rows, h)`` — is the block the TPU lowering accepts; with heads on
    the second-minor axis one head's rows would be a (1, h) block it
    refuses.

    ``kv_dtype`` (paged only) selects the pool storage: ``"bf16"`` keeps
    ``dtype``; ``"int8"``/``"fp8"`` store 1-byte codes and every K/V pool
    leaf gets a sibling float32 scale leaf (``k_scale``/``v_scale``) whose
    shape follows ``scale_granularity``: ``"head"`` -> ``[num_pages, K]``
    (per-page-per-head, the compact default), ``"token"`` ->
    ``[num_pages, K, page_size]`` (per-row — rewrite-stable, required by
    speculative decode; see models.kv_quant)."""
    period, nblocks, ntail = stack_plan(cfg)
    kinds = sub_kinds(cfg)
    opts = opts or L.ModelOptions()
    quantized = kv_quant.quant_dtype(kv_dtype) is not None
    if scale_granularity not in kv_quant.SCALE_GRANULARITIES:
        raise ValueError(f"scale_granularity must be one of "
                         f"{kv_quant.SCALE_GRANULARITIES}, "
                         f"got {scale_granularity!r}")
    if paged:
        if num_pages <= 0 or page_size <= 0:
            raise ValueError("paged cache_template needs num_pages/page_size")
        if opts.window_cache:
            raise ValueError("window_cache (per-layer ring buffers) and the "
                             "paged KV pool are mutually exclusive")
    elif quantized:
        raise ValueError("kv_dtype quantization requires the paged layout "
                         "(the page pool is the quantization boundary)")

    def sub_cache(kind: SubKind):
        c: Dict[str, PSpec] = {}
        if kind.mixer == "attn":
            if paged:
                c["k"] = PSpec((num_pages, cfg.num_kv_heads, page_size,
                                cfg.head_dim),
                               (None, "act_kv_heads", None, None))
                c["v"] = PSpec((num_pages, cfg.num_kv_heads, page_size,
                                cfg.head_dim),
                               (None, "act_kv_heads", None, None))
                if quantized:
                    sshape, sspec = ((num_pages, cfg.num_kv_heads),
                                     (None, "act_kv_heads"))
                    if scale_granularity == "token":
                        sshape = (num_pages, cfg.num_kv_heads, page_size)
                        sspec = (None, "act_kv_heads", None)
                    c["k_scale"] = PSpec(sshape, sspec)
                    c["v_scale"] = PSpec(sshape, sspec)
                if kind.cross and cfg.encoder:
                    c["xk"] = PSpec((batch, cfg.encoder.num_tokens,
                                     cfg.num_kv_heads, cfg.head_dim),
                                    ("batch", None, "act_kv_heads", None))
                    c["xv"] = PSpec((batch, cfg.encoder.num_tokens,
                                     cfg.num_kv_heads, cfg.head_dim),
                                    ("batch", None, "act_kv_heads", None))
                return c
            seq = max_seq
            if opts.window_cache and kind.window != GLOBAL_WINDOW:
                seq = min(max_seq, kind.window)
            c["k"] = PSpec((batch, cfg.num_kv_heads, seq, cfg.head_dim),
                           ("batch", "act_kv_heads", "kv_seq", None))
            c["v"] = PSpec((batch, cfg.num_kv_heads, seq, cfg.head_dim),
                           ("batch", "act_kv_heads", "kv_seq", None))
            if kind.cross and cfg.encoder:
                c["xk"] = PSpec((batch, cfg.encoder.num_tokens,
                                 cfg.num_kv_heads, cfg.head_dim),
                                ("batch", None, "act_kv_heads", None))
                c["xv"] = PSpec((batch, cfg.encoder.num_tokens,
                                 cfg.num_kv_heads, cfg.head_dim),
                                ("batch", None, "act_kv_heads", None))
        else:
            d_in, H, P, N, G, conv_ch = L.mamba_dims(cfg)
            c["ssm"] = PSpec((batch, H, P, N), ("batch", None, None, None))
            c["conv"] = PSpec((batch, cfg.ssm_conv - 1, conv_ch),
                              ("batch", None, "ssm_inner"))
        return c

    block = {f"sub{j}": sub_cache(kinds[j]) for j in range(period)}
    t = {"blocks": stack(block, nblocks, "layers")}
    if ntail:
        t["tail"] = {f"tail{j}": sub_cache(kinds[j]) for j in range(ntail)}
    return t


def cache_batch_axis(path) -> int:
    """Batch axis of a cache leaf, from its position in the cache pytree.

    Leaves under ``blocks`` are layer-stacked by ``stack(...)`` so batch sits
    behind the scan dim at axis 1; ``tail`` leaves carry batch at axis 0. This
    is the explicit annotation the serving engine's slot scatter relies on
    (shape inference breaks down when slot and prefill caches coincide, e.g.
    n_slots == 1)."""
    key = getattr(path[0], "key", path[0])
    return 1 if key == "blocks" else 0


def cache_dtype(path_key: str, dtype, kv_dtype: str = "bf16"):
    # SSM recurrent state is kept fp32 (it integrates over the whole stream);
    # quantization scales are fp32 metadata; quantized K/V pool leaves store
    # 1-byte codes (see models.kv_quant).
    if path_key == "ssm":
        return jnp.float32
    if path_key in ("k_scale", "v_scale"):
        return jnp.float32
    q = kv_quant.quant_dtype(kv_dtype)
    if q is not None and path_key in ("k", "v"):
        return q
    return dtype


def init_caches(cfg: ModelConfig, batch: int, max_seq: int,
                dtype=jnp.bfloat16, opts=None, *, paged: bool = False,
                num_pages: int = 0, page_size: int = 0,
                kv_dtype: str = "bf16", scale_granularity: str = "head"):
    t = cache_template(cfg, batch, max_seq, dtype, opts, paged=paged,
                       num_pages=num_pages, page_size=page_size,
                       kv_dtype=kv_dtype,
                       scale_granularity=scale_granularity)
    return jax.tree_util.tree_map_with_path(
        lambda path, s: jnp.zeros(s.shape, cache_dtype(path[-1].key, dtype,
                                                       kv_dtype)),
        t, is_leaf=lambda x: isinstance(x, PSpec))


def is_paged_leaf(path) -> bool:
    """Whether a cache-pytree leaf lives in the paged KV pool layout —
    attention ``k``/``v`` value leaves and their ``k_scale``/``v_scale``
    quantization-scale siblings (leading axis = num_pages) — rather than the
    slot-batched layout (``xk``/``xv``/``ssm``/``conv``, leading axis =
    batch). Only meaningful for caches built with ``paged=True``."""
    key = getattr(path[-1], "key", path[-1])
    return key in ("k", "v", "k_scale", "v_scale")


def is_scale_leaf(path) -> bool:
    """Whether a cache-pytree leaf is a quantization scale sibling of a
    paged K/V pool leaf (``[num_pages, K]`` float32 at ``"head"``
    granularity, ``[num_pages, K, page_size]`` at ``"token"``)."""
    key = getattr(path[-1], "key", path[-1])
    return key in ("k_scale", "v_scale")
