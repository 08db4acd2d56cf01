"""Layer math: norms, RoPE, attention (dense / banded / flash-ref / decode),
MLP, MoE (capacity-based dispatch + small-batch gather path), Mamba2 SSD.

Everything is a pure function over a param dict produced by the templates in
``stacks.py``. Compute dtype follows the inputs; softmax/log-sum-exp run fp32.
"""
from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import GLOBAL_WINDOW, ModelConfig
from repro.distributed.sharding import constrain

NEG_INF = -1e30


def on_tpu() -> bool:
    """Whether the process runs on the TPU backend: the Pallas kernels are
    compiled there and interpreted anywhere else."""
    return jax.default_backend() == "tpu"


def pallas_on(opts: Optional["ModelOptions"]) -> bool:
    """Whether the served attention cores (decode and chunk modes) run the
    Pallas kernels: the platform decides (compiled kernels on the TPU, jnp
    cores elsewhere) unless ``opts.use_pallas`` pins it."""
    if opts is None or opts.use_pallas is None:
        return on_tpu()
    return opts.use_pallas


def pallas_pinned(opts: Optional["ModelOptions"]) -> bool:
    """Whether the kernels off the served path run: fresh-mode flash
    attention (training, whole-buffer prefill), the MoE grouped matmul and
    the SSD scan. Only a pin (``use_pallas=True``) selects them, never the
    platform: flash has no backward rule and the SSD kernel's blocks are
    refused by the TPU lowering."""
    return opts is not None and opts.use_pallas is True


@dataclass(frozen=True)
class ModelOptions:
    """Runtime knobs (orthogonal to the architecture config)."""
    dense_attn_threshold: int = 2048   # use plain masked attention below this
    attn_chunk: int = 512              # q/kv chunk for banded/flash-ref paths
    use_pallas: Optional[bool] = None  # None: served attention follows
    #                                    the platform (see pallas_on), the
    #                                    other kernels stay off (see
    #                                    pallas_pinned). True runs every
    #                                    kernel (interpreted off the TPU:
    #                                    kernel tests); False keeps the jnp
    #                                    cores (the reference the kernels
    #                                    are checked against)
    moe_capacity_factor: float = 1.25
    moe_per_seq_dispatch: bool = False  # per-sequence-local slot assignment
    #                                     (no cross-device prefix sums; §Perf)
    moe_gather_decode: bool = False    # tiny-batch decode: gather the top-k
    #                                    experts' weights instead of running
    #                                    the all-expert capacity path (§Perf)
    remat: bool = True                 # checkpoint scanned layer bodies
    remat_sublayers: bool = False      # nested per-sublayer remat: backward
    #                                    recomputes one sublayer at a time, so
    #                                    peak temp = max (not sum) over the
    #                                    block's sublayers (§Perf, Cell C)
    causal_pairs: bool = False         # triangular chunk-pair flash (perf opt)
    prefill_band: int = 32             # key-block size for banded prefill-
    #                                    with-cache attention: key-axis work
    #                                    per chunk covers the live prefix
    #                                    [0, cache_index + S) rounded up to
    #                                    this block, not max_seq. One stack-
    #                                    wide constant — the blockwise online
    #                                    softmax makes results independent of
    #                                    chunking/view length, but only for a
    #                                    fixed absolute block partition
    window_cache: bool = False         # per-layer-window KV cache (perf opt)
    unroll_layers: bool = False        # unroll the layer scan (cost-analysis
    #                                    validation: XLA counts scan bodies once)
    shard_axis: Optional[str] = None   # shard_map mesh axis the serving
    #                                    engine runs this trace under: the
    #                                    attention/MLP output projections
    #                                    psum their partial sums over it and
    #                                    the lm head all-gathers, but only
    #                                    for params whose local shape is
    #                                    actually sharded (replicated
    #                                    fallbacks stay collective-free)


# ---------------------------------------------------------------------------
# norms / rope / small pieces
# ---------------------------------------------------------------------------

def rms_norm(x, w, eps=1e-6):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    normed = (x.astype(jnp.float32) * jax.lax.rsqrt(var + eps)).astype(x.dtype)
    return normed * w.astype(x.dtype)


def layer_norm(x, w, b, eps=1e-6):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    return (((xf - mu) * jax.lax.rsqrt(var + eps)).astype(x.dtype)
            * w.astype(x.dtype) + b.astype(x.dtype))


def apply_norm(p, x, cfg: ModelConfig, prefix: str):
    if cfg.norm == "layernorm":
        return layer_norm(x, p[prefix + "_w"], p[prefix + "_b"], cfg.norm_eps)
    return rms_norm(x, p[prefix + "_w"], cfg.norm_eps)


def rope(x, positions, theta: float):
    """Llama-style rotary embedding. x: [..., S, H, hd], positions: [..., S]."""
    hd = x.shape[-1]
    half = hd // 2
    freq = theta ** (-jnp.arange(0, half, dtype=jnp.float32) / half)
    angles = positions[..., :, None].astype(jnp.float32) * freq  # [..., S, half]
    cos = jnp.cos(angles)[..., None, :]   # [..., S, 1, half]
    sin = jnp.sin(angles)[..., None, :]
    x1, x2 = x[..., :half], x[..., half:]
    xf1, xf2 = x1.astype(jnp.float32), x2.astype(jnp.float32)
    out = jnp.concatenate([xf1 * cos - xf2 * sin, xf2 * cos + xf1 * sin], -1)
    return out.astype(x.dtype)


def _act(h, g, kind: str):
    if kind == "silu":
        return jax.nn.silu(g) * h
    if kind == "gelu":
        return jax.nn.gelu(g) * h
    return jax.nn.gelu(h)      # gelu_plain (no gate)


# ---------------------------------------------------------------------------
# attention
# ---------------------------------------------------------------------------

def qkv_proj(p, x, cfg_bias: bool):
    q = jnp.einsum("bsd,dnh->bsnh", x, p["wq"])
    k = jnp.einsum("bsd,dkh->bskh", x, p["wk"])
    v = jnp.einsum("bsd,dkh->bskh", x, p["wv"])
    if cfg_bias:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    return q, k, v


def _grouped_scores(q, k):
    """q [B,Sq,N,h], k [B,Sk,K,h] -> logits [B,K,G,Sq,Sk]. Query head n uses
    KV head n // G (standard llama/HF GQA convention)."""
    B, Sq, N, h = q.shape
    K = k.shape[2]
    G = N // K
    qg = q.reshape(B, Sq, K, G, h)
    return jnp.einsum("bskgh,btkh->bkgst", qg, k)


def _grouped_out(w, v):
    """w [B,K,G,Sq,Sk], v [B,Sk,K,h] -> [B,Sq,N,h]."""
    B, K, G, Sq, _ = w.shape
    out = jnp.einsum("bkgst,btkh->bskgh", w, v)
    return out.reshape(B, Sq, K * G, v.shape[-1])


def attention_dense(q, k, v, q_pos, k_pos, window: int, causal: bool = True):
    """Plain masked attention. q [B,Sq,N,h]; k,v [B,Sk,K,h]."""
    scale = float(1.0 / np.sqrt(q.shape[-1]))
    logits = _grouped_scores(q * scale, k).astype(jnp.float32)
    mask = jnp.ones((q.shape[1], k.shape[1]), bool)
    if causal:
        mask &= q_pos[:, None] >= k_pos[None, :]
    if window != GLOBAL_WINDOW:
        mask &= (q_pos[:, None] - k_pos[None, :]) < window
    logits = jnp.where(mask[None, None, None], logits, NEG_INF)
    w = jax.nn.softmax(logits, axis=-1).astype(q.dtype)
    return _grouped_out(w, v)


def attention_flash_ref(q, k, v, q_pos, k_pos, window: int, chunk: int,
                        causal_pairs: bool = False):
    """Memory-bounded attention: online softmax over KV chunks (pure jnp),
    scanned over q chunks so HLO size is O(1) in sequence length.

    Baseline scans every (q-chunk, kv-chunk) pair with masking (~2x causal
    FLOP overcount, like a naive flash schedule). ``causal_pairs=True``
    scans only the lower-triangular / in-window chunk pairs — the §Perf
    optimization that recovers the causal FLOP factor.
    """
    B, Sq, N, h = q.shape
    Sk = k.shape[1]
    K = k.shape[2]
    G = N // K
    nq, nk = Sq // chunk, Sk // chunk
    scale = float(1.0 / np.sqrt(h))
    qc = jnp.moveaxis((q * scale).reshape(B, nq, chunk, N, h), 1, 0)
    kc = k.reshape(B, nk, chunk, K, h)
    vc = v.reshape(B, nk, chunk, K, h)
    qpc = q_pos.reshape(nq, chunk)
    kpc = k_pos.reshape(nk, chunk)

    def pair(qi, kj, vj, m, l, acc, qp, kp):
        """One (q-chunk, kv-chunk) online-softmax update."""
        qg = qi.reshape(B, chunk, K, G, h)
        s = jnp.einsum("bskgh,btkh->bkgst", qg, kj).astype(jnp.float32)
        mask = qp[:, None] >= kp[None, :]
        if window != GLOBAL_WINDOW:
            mask &= (qp[:, None] - kp[None, :]) < window
        s = jnp.where(mask[None, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None]) * mask[None, None, None]
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        pv = jnp.einsum("bkgst,btkh->bkgsh", p.astype(qi.dtype), vj)
        acc_new = acc * corr[..., None].astype(acc.dtype) + pv
        return m_new, l_new, acc_new

    def zeros_state(n_rows):
        return (jnp.full((B, K, G, n_rows), NEG_INF, jnp.float32),
                jnp.zeros((B, K, G, n_rows), jnp.float32),
                jnp.zeros((B, K, G, n_rows, h), q.dtype))

    if causal_pairs:
        # flattened triangular/banded list of (iq, jk) chunk pairs, scanned;
        # per-q-chunk softmax state lives in [nq, ...] buffers updated at iq.
        pairs = []
        for iq in range(nq):
            lo = 0
            if window != GLOBAL_WINDOW:
                lo = max(0, (iq * chunk - (window - 1)) // chunk)
            pairs += [(iq, jk) for jk in range(lo, min(iq + 1, nk))]
        iq_arr = jnp.asarray([p[0] for p in pairs], jnp.int32)
        jk_arr = jnp.asarray([p[1] for p in pairs], jnp.int32)
        m0, l0, acc0 = jax.tree.map(
            lambda z: jnp.stack([z] * nq), zeros_state(chunk))

        def body(carry, idx):
            m_all, l_all, acc_all = carry
            iq, jk = idx
            qi = jax.lax.dynamic_index_in_dim(qc, iq, 0, keepdims=False)
            kj = jax.lax.dynamic_index_in_dim(kc, jk, 1, keepdims=False)
            vj = jax.lax.dynamic_index_in_dim(vc, jk, 1, keepdims=False)
            st = jax.tree.map(
                lambda b: jax.lax.dynamic_index_in_dim(b, iq, 0, False),
                (m_all, l_all, acc_all))
            qp = jax.lax.dynamic_index_in_dim(qpc, iq, 0, False)
            kp = jax.lax.dynamic_index_in_dim(kpc, jk, 0, False)
            st = pair(qi, kj, vj, *st, qp, kp)
            out = jax.tree.map(
                lambda b, s: jax.lax.dynamic_update_index_in_dim(b, s, iq, 0),
                (m_all, l_all, acc_all), st)
            return out, None

        (m_all, l_all, acc_all), _ = jax.lax.scan(
            body, (m0, l0, acc0), (iq_arr, jk_arr))
        out = acc_all / jnp.maximum(l_all, 1e-30)[..., None].astype(acc_all.dtype)
        out = jnp.moveaxis(out, 0, 3)                  # [B,K,G,nq,chunk,h]
        out = out.reshape(B, K, G, Sq, h)
        return jnp.moveaxis(out, (1, 2), (2, 3)).reshape(B, Sq, N, h)

    def run_q_chunk(carry, xs):
        qi, qp = xs

        def body(st, jk):
            m, l, acc = st
            kj = jax.lax.dynamic_index_in_dim(kc, jk, 1, False)
            vj = jax.lax.dynamic_index_in_dim(vc, jk, 1, False)
            kp = jax.lax.dynamic_index_in_dim(kpc, jk, 0, False)
            m2, l2, a2 = pair(qi, kj, vj, m, l, acc, qp, kp)
            keep = kp.min() <= qp.max()
            if window != GLOBAL_WINDOW:
                keep &= (qp.min() - kp.max()) < window
            return jax.tree.map(lambda new, old: jnp.where(keep, new, old),
                                (m2, l2, a2), (m, l, acc)), None

        (m, l, acc), _ = jax.lax.scan(body, zeros_state(chunk),
                                      jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None].astype(acc.dtype)
        return carry, out

    _, outs = jax.lax.scan(run_q_chunk, None, (qc, qpc))
    out = jnp.moveaxis(outs, 0, 3)                     # [B,K,G,nq,chunk,h]
    out = out.reshape(B, K, G, Sq, h)
    return jnp.moveaxis(out, (1, 2), (2, 3)).reshape(B, Sq, N, h)


def attention_banded(q, k, v, q_pos, k_pos, window: int, chunk: int):
    """Sliding-window attention with linear FLOPs: each q chunk attends to a
    fixed-size KV band gathered with dynamic_slice, scanned over q chunks."""
    B, Sq, N, h = q.shape
    K = k.shape[2]
    nq = Sq // chunk
    band = int(np.ceil(window / chunk) + 1) * chunk
    # left-pad KV so every band slice is in range
    pad = band - chunk
    kp = jnp.pad(k, ((0, 0), (pad, 0), (0, 0), (0, 0)))
    vp = jnp.pad(v, ((0, 0), (pad, 0), (0, 0), (0, 0)))
    kpos_p = jnp.pad(k_pos, (pad, 0), constant_values=-10**9)

    def one(_, iq):
        start = iq * chunk  # band start in the padded buffer
        q_i = jax.lax.dynamic_slice_in_dim(q, start, chunk, 1)
        k_i = jax.lax.dynamic_slice_in_dim(kp, start, band, 1)
        v_i = jax.lax.dynamic_slice_in_dim(vp, start, band, 1)
        kp_i = jax.lax.dynamic_slice_in_dim(kpos_p, start, band, 0)
        qp_i = jax.lax.dynamic_slice_in_dim(q_pos, start, chunk, 0)
        return None, attention_dense(q_i, k_i, v_i, qp_i, kp_i, window)

    _, outs = jax.lax.scan(one, None, jnp.arange(nq))  # [nq,B,chunk,N,h]
    return jnp.moveaxis(outs, 0, 1).reshape(B, Sq, N, h)


def band_len(live: int, band: int, limit: int) -> int:
    """Static key-axis length for a banded prefill-with-cache dispatch: the
    live prefix ``live`` rounded up to a whole key block, clamped to the
    cache capacity ``limit``. The band bound is a pure FLOP/bytes
    optimization — trailing blocks are exact no-ops in the blockwise online
    softmax — so any bound >= the true live length is correct."""
    return min(-(-live // band) * band, limit)


def live_bound(live_len, limit: int) -> int:
    """Normalize the ``live_len`` argument of the chunk dispatch to a single
    static key-axis bound. ``None`` means the whole cache view; an int is a
    batch-wide bound; a tuple/list gives one static bound *per slot* and
    collapses to its max here — the shared band slice must cover the oldest
    slot, while the kernels' per-slot ``[B]`` index vectors already make
    every block past a younger slot's own position an exact no-op for that
    slot. The tuple form therefore buys the tightest *shared* slice plus
    per-slot key-lane accounting at the caller; note a jitted caller should
    pre-collapse to the max (a per-slot tuple as a static jit argument
    would retrace on every distinct batch age mix)."""
    if live_len is None:
        return limit
    if isinstance(live_len, (tuple, list)):
        return max(live_len) if live_len else limit
    return live_len


def attention_chunk_banded(q, k_cache, v_cache, index, window: int,
                           band: int):
    """Banded chunk-prefill core (pure jnp; the Pallas twin is
    ``kernels/chunk_prefill``): one prefill chunk of S queries at positions
    ``index .. index+S-1`` attends against a live cache view, scanned over
    fixed ``band``-sized key blocks with an online softmax.

    q [B,S,N,h]; head-major cache view [B,K,L,h] (the caller slices L down
    to the banded live bound — see ``band_len``); index scalar or per-slot
    [B].

    The bit-stability contract the scheduler's equality gates build on: a
    key block that is fully masked for a query row updates that row's
    softmax state by *exactly* nothing (``corr == exp(0) == 1``, ``p == 0``
    — fp32-exact), so a query's result depends only on the absolute block
    partition of the keys at or before its own position. Chunking the
    prompt differently, or passing a longer (even stale/garbage-padded)
    cache view, changes only which blocks are no-ops — never the bits.
    """
    B, S, N, h = q.shape
    K, L = k_cache.shape[1], k_cache.shape[2]
    G = N // K
    Lp = -(-L // band) * band
    if Lp != L:                      # pad the view to whole blocks; padded
        pad = ((0, 0), (0, 0), (0, Lp - L), (0, 0))   # lanes sit past every
        k_cache = jnp.pad(k_cache, pad)               # query position and
        v_cache = jnp.pad(v_cache, pad)               # are masked exactly
    nk = Lp // band
    scale = float(1.0 / np.sqrt(h))
    qg = (q * scale).reshape(B, S, K, G, h)
    idx = jnp.broadcast_to(jnp.asarray(index, jnp.int32).reshape(-1), (B,))
    q_pos = idx[:, None] + jnp.arange(S, dtype=jnp.int32)     # [B, S]

    def block(st, jk):
        m, l, acc = st
        kj = jax.lax.dynamic_slice_in_dim(k_cache, jk * band, band, 2)
        vj = jax.lax.dynamic_slice_in_dim(v_cache, jk * band, band, 2)
        kpos = jk * band + jnp.arange(band)
        s = jnp.einsum("bskgh,bkth->bkgst", qg, kj).astype(jnp.float32)
        mask = kpos[None, None] <= q_pos[..., None]           # [B, S, band]
        if window != GLOBAL_WINDOW:
            mask &= (q_pos[..., None] - kpos[None, None]) < window
        s = jnp.where(mask[:, None, None], s, NEG_INF)
        m_new = jnp.maximum(m, s.max(-1))
        p = jnp.exp(s - m_new[..., None]) * mask[:, None, None]
        corr = jnp.exp(m - m_new)
        l_new = l * corr + p.sum(-1)
        pv = jnp.einsum("bkgst,bkth->bkgsh", p,
                        vj.astype(jnp.float32))
        acc_new = acc * corr[..., None] + pv
        return (m_new, l_new, acc_new), None

    st0 = (jnp.full((B, K, G, S), NEG_INF, jnp.float32),
           jnp.zeros((B, K, G, S), jnp.float32),
           jnp.zeros((B, K, G, S, h), jnp.float32))
    (m, l, acc), _ = jax.lax.scan(block, st0, jnp.arange(nk))
    out = acc / jnp.maximum(l, 1e-30)[..., None]
    return jnp.moveaxis(out, (1, 2), (2, 3)).reshape(B, S, N, h) \
        .astype(q.dtype)


def attention_decode(q, k_cache, v_cache, index, window: int):
    """Single-token decode against a cache (the jnp core; the Pallas twin is
    ``kernels/decode_attention``). q [B,1,N,h]; head-major cache
    [B,K,Smax,h]; index = current position — scalar int32 or per-slot [B]
    vector (continuous batching)."""
    B, _, N, h = q.shape
    K, Smax = k_cache.shape[1], k_cache.shape[2]
    G = N // K
    scale = float(1.0 / np.sqrt(h))
    qg = (q * scale).reshape(B, K, G, h)
    s = jnp.einsum("bkgh,bkth->bkgt", qg, k_cache).astype(jnp.float32)
    kpos = jnp.arange(Smax)
    idx = jnp.broadcast_to(jnp.asarray(index), (B,))
    valid = kpos[None] <= idx[:, None]                      # [B, Smax]
    if window != GLOBAL_WINDOW:
        valid &= (idx[:, None] - kpos[None]) < window
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgt,bkth->bkgh", w, v_cache)
    return out.reshape(B, 1, N, h)


def attention_decode_ring(q, k_cache, v_cache, index):
    """Decode against a ring-buffer KV cache of size == window (§Perf:
    window_cache). The ring holds exactly the last W positions, so the
    sliding-window mask is implicit; attention is permutation-invariant so
    slot order doesn't matter. Only slots not yet written (index < W) mask.
    """
    B, _, N, h = q.shape
    K, W = k_cache.shape[1], k_cache.shape[2]
    G = N // K
    scale = float(1.0 / np.sqrt(h))
    qg = (q * scale).reshape(B, K, G, h)
    s = jnp.einsum("bkgh,bkth->bkgt", qg, k_cache).astype(jnp.float32)
    slot = jnp.arange(W)
    idx = jnp.broadcast_to(jnp.asarray(index), (B,))
    valid = (slot[None] <= idx[:, None]) | (idx[:, None] >= W)
    s = jnp.where(valid[:, None, None], s, NEG_INF)
    w = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgt,bkth->bkgh", w, v_cache)
    return out.reshape(B, 1, N, h)


def update_cache(cache, new, index):
    """Write `new` [B,S,K,h] into the head-major `cache` [B,K,Smax,h] at
    position(s) `index` (scalar, or [B] per-slot vector for continuous
    batching)."""
    idx = jnp.asarray(index)
    new = new.swapaxes(1, 2).astype(cache.dtype)              # [B,K,S,h]
    if idx.ndim == 0:
        return jax.lax.dynamic_update_slice_in_dim(cache, new, idx, 2)
    return jax.vmap(
        lambda c, n, i: jax.lax.dynamic_update_slice_in_dim(c, n, i, 1))(
            cache, new, idx)


def update_cache_chunk(cache, new, index, n_valid=None):
    """Write a prefill chunk ``new`` [B,C,K,h] into the head-major ``cache``
    [B,K,Smax,h] at positions ``index .. index+C-1`` (``index`` scalar or
    [B]) via scatter rather than ``dynamic_update_slice``: rows at or past
    ``n_valid`` — the padding tail of a partial final chunk — get an
    out-of-bounds target index, which jax scatter *drops* (where a slice
    update would clamp the start and shift the whole write onto earlier,
    already-correct positions)."""
    B, C = new.shape[:2]
    smax = cache.shape[2]
    idx = (jnp.broadcast_to(jnp.asarray(index, jnp.int32).reshape(-1, 1),
                            (B, 1))
           + jnp.arange(C, dtype=jnp.int32)[None])            # [B,C]
    if n_valid is not None:
        nv = jnp.broadcast_to(jnp.asarray(n_valid, jnp.int32).reshape(-1, 1),
                              (B, 1))
        idx = jnp.where(jnp.arange(C)[None] < nv, idx, smax)
    # the indexed region of cache[b, :, idx] is [B, C, K, h], new's layout
    return cache.at[jnp.arange(B)[:, None], :, idx].set(
        new.astype(cache.dtype))


def update_cache_paged(pages, new, page_table, index, scales=None,
                       valid=None):
    """Write the decode token's KV into the page pool; quantize on write
    when the pool is quantized. Returns ``(pages, scales)`` (scales is None
    for unquantized pools).

    pages [num_pages, K, page_size, h] (head-major); new [B,1,K,h];
    page_table [B,npg] int32; index scalar or per-slot [B] vector; scales
    ``[num_pages, K]`` (per-(page, head) granularity) or
    ``[num_pages, K, page_size]`` (per-token granularity) float32 —
    quantized pools only, dispatched on
    ``scales.ndim``. ``valid`` (scalar or [B] bool, default
    all-true) additionally routes masked rows to the null-page sink as
    zeros — the chunked-prefill path uses it for the padding rows of a
    partial final chunk. Logical position ``i`` of slot ``b``
    lives at (page_table[b, i // page_size], i % page_size). Distinct live
    slots always own distinct write pages, so the scatter has no cross-slot
    collisions (retired slots' table rows point at the reserved null page 0,
    a write sink that is never read unmasked).

    Quantized write (monotone amax policy, see models.kv_quant): the touched
    page's scale grows to cover the new token's amax; since one scale covers
    the whole (page, head), a grown scale requantizes the page's existing
    codes (dequant under the old scale -> insert the token -> encode under
    the new). ``encode(decode(c)) == c`` exactly at a fixed scale, so
    repeated writes at a stable scale are drift-free — and the common case
    (no slot's scale grew this step) therefore skips the page round-trip
    entirely via ``lax.cond``: it encodes just the token row under the
    existing scale, bit-identical to what the requantizing branch would
    produce. Per-token scales (``scales.ndim == 3``) have no cross-row
    coupling at all: the write replaces the row's codes *and* its scale,
    touching nothing else — which makes position re-writes exact (the
    property the speculative tick's rejected-row rollback relies on).
    Retired slots (table row all null page 0) keep the null page's
    documented all-zero state: their token codes and scale updates are
    masked to zero, so page 0 always dequantizes to exactly 0.

    Indexing ``pages[pid, :, off]`` (advanced indices around the head
    slice) selects the [B, K, h] token rows, the layout of ``new[:, 0]``."""
    ps = pages.shape[2]
    B = new.shape[0]
    idx = jnp.broadcast_to(jnp.asarray(index, jnp.int32).reshape(-1), (B,))
    pid = jnp.take_along_axis(page_table, (idx // ps)[:, None], axis=1)[:, 0]
    off = idx % ps
    if valid is not None:
        live = jnp.broadcast_to(jnp.asarray(valid, bool).reshape(-1), (B,))
        pid = jnp.where(live, pid, 0)
        new = jnp.where(live[:, None, None, None], new, 0)
    if scales is None:
        return pages.at[pid, :, off].set(new[:, 0].astype(pages.dtype)), None
    from repro.models import kv_quant
    tok = new[:, 0].astype(jnp.float32)                       # [B,K,h]
    sink = (pid == 0)                                         # retired slot
    if scales.ndim == 3:
        # per-token granularity: independent row write, scale replaced
        tok = jnp.where(sink[:, None, None], 0.0, tok)
        row_scale = jnp.max(jnp.abs(tok), -1) / kv_quant.qmax(pages.dtype)
        codes = kv_quant.encode(tok, row_scale[:, :, None], pages.dtype)
        return (pages.at[pid, :, off].set(codes),
                scales.at[pid, :, off].set(row_scale))
    old_scale = scales[pid]                                   # [B,K]
    tok_scale = jnp.max(jnp.abs(tok), -1) / kv_quant.qmax(pages.dtype)
    new_scale = jnp.where(sink[:, None], old_scale,
                          jnp.maximum(old_scale, tok_scale))  # monotone
    tok = jnp.where(sink[:, None, None], 0.0, tok)            # sink stays 0

    def rescale(pages, scales):
        # some page's range grew: dequant -> insert token -> requant
        page_f = kv_quant.decode(pages[pid], old_scale[:, :, None, None])
        page_f = jax.vmap(
            lambda pg, t, r: jax.lax.dynamic_update_slice_in_dim(
                pg, t[:, None], r, 1))(page_f, tok, off)      # [B,K,ps,h]
        codes = kv_quant.encode(page_f, new_scale[:, :, None, None],
                                pages.dtype)
        return pages.at[pid].set(codes), scales.at[pid].set(new_scale)

    def row_only(pages, scales):
        # every scale unchanged: single-row write, no page round-trip
        codes = kv_quant.encode(tok, old_scale[:, :, None], pages.dtype)
        return pages.at[pid, :, off].set(codes), scales

    return jax.lax.cond(jnp.any(new_scale > old_scale), rescale, row_only,
                        pages, scales)


def update_cache_paged_chunk(pages, new, page_table, start, n_valid=None,
                             scales=None):
    """Page-wise scatter of one prefill chunk: write ``new`` [B,C,K,h] into
    the pool at logical positions ``start .. start+C-1`` of each slot
    (``start`` scalar or [B]). Rows at or past ``n_valid`` (the padding tail
    of a partial final chunk) are routed to the null page as zeros, so a
    chunk is always a fixed ``C``-shaped dispatch regardless of how much of
    it is real prompt. Returns ``(pages, scales)`` like ``update_cache_paged``.

    Unquantized pools and per-token-scale quantized pools
    (``scales.ndim == 3``) take one vectorized scatter (distinct valid rows
    hit distinct (page, offset) cells — a slot owns its pages and positions
    are consecutive; per-token scales make every row's encode independent,
    bit-identical to the decode write path's row encode). Per-(page, head)
    quantized pools (``scales.ndim == 2``) replay the rows through the
    per-token monotone-amax write so chunked prefill shares the exact
    growth semantics (and drift characteristics) of the decode write
    path."""
    B, C = new.shape[:2]
    start = jnp.broadcast_to(jnp.asarray(start, jnp.int32).reshape(-1), (B,))
    nv = jnp.broadcast_to(
        jnp.asarray(C if n_valid is None else n_valid, jnp.int32).reshape(-1),
        (B,))
    ps = pages.shape[2]
    if scales is None or scales.ndim == 3:
        idx = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None]   # [B,C]
        live = jnp.arange(C)[None] < nv[:, None]                      # [B,C]
        pid = jnp.take_along_axis(page_table, idx // ps, axis=1)
        pid = jnp.where(live, pid, 0)
        off = idx % ps
        rows = jnp.where(live[..., None, None], new, 0)
        # pages[pid, :, off] is the [B, C, K, h] region, rows' layout
        if scales is None:
            return pages.at[pid, :, off].set(rows.astype(pages.dtype)), None
        from repro.models import kv_quant
        rows = rows.astype(jnp.float32)
        row_scale = jnp.max(jnp.abs(rows), -1) / kv_quant.qmax(pages.dtype)
        codes = kv_quant.encode(rows, row_scale[..., None], pages.dtype)
        return (pages.at[pid, :, off].set(codes),
                scales.at[pid, :, off].set(row_scale))

    def body(i, carry):
        pages, scales = carry
        row = jax.lax.dynamic_slice_in_dim(new, i, 1, 1)              # [B,1]
        return update_cache_paged(pages, row, page_table, start + i,
                                  scales, valid=i < nv)

    return jax.lax.fori_loop(0, C, body, (pages, scales))


def attention_decode_paged(q, k_pages, v_pages, page_table, index,
                           window: int, k_scales=None, v_scales=None):
    """Single-token decode against a paged KV pool (the jnp core; the
    Pallas twin, ``kernels/decode_attention/paged.py``, gathers KV blocks
    and their scales through the page table inside the kernel). q
    [B,1,N,h]; pages [num_pages, K, page_size, h]; page_table [B,npg];
    index scalar or [B]; k/v_scales [num_pages, K] or
    [num_pages, K, page_size] float32 for quantized pools (None otherwise).

    It materializes the dense gather (dequantized, for quantized pools) and
    reuses ``attention_decode`` — bit-identical to the dense layout in the
    unquantized case, which is what the paged-vs-dense equivalence gates
    rely on."""
    from repro.kernels.decode_attention.ref import gather_dequant
    kd, vd = gather_dequant(k_pages, v_pages, page_table, k_scales, v_scales)
    return attention_decode(q, kd, vd, index, window)


# ---------------------------------------------------------------------------
# unified attention dispatch
# ---------------------------------------------------------------------------

def attention_route(mode: str, layout: str, *, S: int, Skv: int, window: int,
                    opts: ModelOptions, causal: bool = True) -> str:
    """The single routing decision for every attention dispatch:
    (mode × layout × shape) -> core name. ``attention()`` resolves its
    arguments to a (mode, layout) pair, asks this function for the core,
    and executes it via ``run_attention_core`` — there is no other
    attention if-ladder in the model stack. The full table is rendered in
    docs/architecture.md.

    Modes:
      - ``decode``  S == 1 against a cache (the paper's bottleneck phase)
      - ``chunk``   S > 1 prefill against a live cache view (chunked or
                    monolithic serving prefill; positioned or from zero)
      - ``fresh``   self-attention over exactly the new rows (training
                    forward, ring-buffer prefill, whole-buffer dryrun/cost
                    shapes — no earlier cache contents to see)
      - ``cross``   encoder context (never cached, never causal)

    Layouts: ``dense`` per-slot head-major [B, K, Smax, h] buffers;
    ``paged`` shared head-major page pools [num_pages, K, page_size, h]
    behind a per-slot table; ``ring`` per-layer-window ring buffers;
    ``none`` (no cache view).

    Kernel or jnp core: decode and chunk modes take ``pallas_on(opts)`` —
    the platform unless a test pins ``opts.use_pallas``; fresh mode takes
    the flash kernel only under that pin (``pallas_pinned``).

    Shape gates: the fresh Pallas flash kernel keeps its
    ``S % 128 == 0 and Sq == Skv`` tiling gate, but chunk mode has no such
    restriction — the banded chunk kernel takes any (padded) chunk length
    against any cache view, which is how the old Pallas gate generalizes
    to padded bands."""
    kernels = pallas_on(opts)
    if mode == "decode":
        if layout == "ring":
            return "decode_ring"
        if layout == "paged":
            return ("decode_paged_flash" if kernels
                    else "decode_paged_gather")
        return "decode_flash" if kernels else "decode_dense"
    if mode == "chunk":
        if layout == "paged":
            return ("chunk_paged_flash" if kernels
                    else "chunk_banded_gather")
        return "chunk_flash" if kernels else "chunk_banded"
    # fresh / cross: attention over exactly the new rows
    if pallas_pinned(opts) and causal and S % 128 == 0 and Skv == S:
        return "fresh_flash"
    if Skv <= opts.dense_attn_threshold or Skv % opts.attn_chunk \
            or not causal:
        return "fresh_dense"
    if window != GLOBAL_WINDOW and window <= Skv // 2:
        return "fresh_banded"
    return "fresh_flash_ref"


def run_attention_core(route: str, q, k, v, *, opts: ModelOptions,
                       window: int, causal: bool = True, q_pos=None,
                       k_pos=None, index=None, page_table=None,
                       k_scales=None, v_scales=None, live_len=None):
    """Execute one routed attention core. ``k``/``v`` are the new rows
    [B, S, K, h] (fresh/cross), the head-major cache view [B, K, Smax, h]
    (dense decode/chunk), or the page pools [num_pages, K, page_size, h]
    (paged routes, with ``page_table`` and optional quantization
    ``*_scales``). ``index`` is the decode position / chunk start (scalar
    or per-slot [B]); ``live_len`` (static int, per-slot tuple of ints, or
    None) bounds the banded chunk cores' key axis to the live prefix — see
    ``band_len`` and ``live_bound``. Kernels run interpreted off the TPU."""
    interpret = not on_tpu()
    # -- decode: one token against the cache --------------------------------
    if route == "decode_ring":
        return attention_decode_ring(q, k, v, index)
    if route == "decode_flash":
        from repro.kernels.decode_attention import ops as da_ops
        out = da_ops.decode_attention(q[:, 0], k, v, index, window=window,
                                      interpret=interpret)
        return out[:, None]
    if route == "decode_dense":
        return attention_decode(q, k, v, index, window)
    if route == "decode_paged_flash":
        from repro.kernels.decode_attention import ops as da_ops
        out = da_ops.paged_decode_attention(q[:, 0], k, v, page_table, index,
                                            k_scales=k_scales,
                                            v_scales=v_scales, window=window,
                                            interpret=interpret)
        return out[:, None]
    if route == "decode_paged_gather":
        return attention_decode_paged(q, k, v, page_table, index, window,
                                      k_scales=k_scales, v_scales=v_scales)
    # -- chunk: S > 1 prefill against a live cache view ---------------------
    band = opts.prefill_band
    if route in ("chunk_flash", "chunk_banded"):
        smax = k.shape[2]
        Lb = band_len(live_bound(live_len, smax), band, smax)
        kb, vb = k[:, :, :Lb], v[:, :, :Lb]
        if route == "chunk_flash":
            from repro.kernels.chunk_prefill import ops as cp_ops
            return cp_ops.chunk_prefill_attention(
                q, kb, vb, index, window=window, bk=band,
                interpret=interpret)
        return attention_chunk_banded(q, kb, vb, index, window, band)
    if route in ("chunk_paged_flash", "chunk_banded_gather"):
        ps, npg = k.shape[2], page_table.shape[1]
        Lb = band_len(live_bound(live_len, npg * ps), band, npg * ps)
        pt = page_table[:, :(Lb + ps - 1) // ps]
        if route == "chunk_paged_flash":
            from repro.kernels.chunk_prefill import ops as cp_ops
            return cp_ops.paged_chunk_prefill_attention(
                q, k, v, pt, index, k_scales=k_scales, v_scales=v_scales,
                window=window, interpret=interpret)
        from repro.kernels.decode_attention.ref import gather_dequant
        kd, vd = gather_dequant(k, v, pt, k_scales, v_scales)
        return attention_chunk_banded(q, kd, vd, index, window, band)
    # -- fresh / cross: exactly the new rows --------------------------------
    if route in ("fresh_flash", "fresh_dense", "fresh_banded",
                 "fresh_flash_ref"):
        q_pos = q_pos[0] if q_pos.ndim == 2 else q_pos
        k_pos = k_pos[0] if k_pos.ndim == 2 else k_pos
        if route == "fresh_flash":
            from repro.kernels.flash_attention import ops as fa_ops
            return fa_ops.flash_attention(q, k, v, window=window,
                                          interpret=interpret)
        if route == "fresh_dense":
            return attention_dense(q, k, v, q_pos, k_pos, window, causal)
        if route == "fresh_banded":
            return attention_banded(q, k, v, q_pos, k_pos, window,
                                    opts.attn_chunk)
        return attention_flash_ref(q, k, v, q_pos, k_pos, window,
                                   opts.attn_chunk,
                                   causal_pairs=opts.causal_pairs)
    raise ValueError(f"unknown attention route {route!r}")


def attention(p, x, cfg: ModelConfig, opts: ModelOptions, window: int,
              positions, cache=None, cache_index=None, ctx=None,
              ctx_prefix: str = "", causal: bool = True, page_table=None,
              n_valid=None, live_len=None):
    """Full attention sub-layer: projections + cache write path + the
    routed core (``attention_route`` / ``run_attention_core``) + output
    projection.

    Decode mode when ``cache`` is a (k,v) tuple of head-major
    [B, K, Smax, h] buffers and x has S==1.
    Cross-attention when ``ctx`` (encoder output) is given: K/V from ctx.
    With ``page_table`` [B,npg] the cache tuple is interpreted as paged
    pools [num_pages, K, page_size, h]; S>1 runs a prefill chunk that is
    scattered page-wise and attends through the pool.
    Prefill with a cache supports ``cache_index > 0`` (chunked prefill /
    prefill-from-position): the chunk is written at its positions and its
    queries attend against the live cache prefix through the banded chunk
    core, so earlier chunks — or prefix-cache pages the engine never
    recomputed — are visible, while key-axis work scales with
    ``live_len`` (a static bound on ``cache_index + S``; None means the
    whole view) instead of ``max_seq``. ``n_valid`` masks the padding tail
    of a partial final chunk out of the write path.
    Returns (out, new_cache).
    """
    pre = ctx_prefix
    B, S, D = x.shape
    q = jnp.einsum("bsd,dnh->bsnh", x, p[pre + "wq"])
    if cfg.qkv_bias:
        q = q + p[pre + "bq"].astype(q.dtype)
    if ctx is not None and pre:
        # cross-attention: cached encoder K/V precomputed by the caller
        k, v = ctx
    else:
        k = jnp.einsum("bsd,dkh->bskh", x, p[pre + "wk"])
        v = jnp.einsum("bsd,dkh->bskh", x, p[pre + "wv"])
        if cfg.qkv_bias:
            k = k + p[pre + "bk"].astype(k.dtype)
            v = v + p[pre + "bv"].astype(v.dtype)
    if cfg.pos == "rope" and not pre:
        q = rope(q, positions, cfg.rope_theta)
        k = rope(k, positions, cfg.rope_theta)
    q = constrain(q, "batch", "act_seq", "act_heads", None)

    new_cache = cache
    if cache is not None and not pre:
        if page_table is not None:
            # paged layout: cache leaves are shared pools, positions resolve
            # through the per-slot page table; a 4-tuple cache carries
            # per-page quantization scales (see models.kv_quant)
            k_sc, v_sc = cache[2:] if len(cache) == 4 else (None, None)
            if S == 1:
                # n_valid (0 or 1 per slot) masks speculative draft writes
                # for dead slots / positions past the cache into the
                # null-page sink — take_along_axis would otherwise *clamp*
                # an out-of-range page lookup onto the slot's last page
                valid = (jnp.asarray(n_valid) > 0) if n_valid is not None \
                    else None
                k_cache, k_sc = update_cache_paged(cache[0], k, page_table,
                                                   cache_index, k_sc,
                                                   valid=valid)
                v_cache, v_sc = update_cache_paged(cache[1], v, page_table,
                                                   cache_index, v_sc,
                                                   valid=valid)
            else:   # prefill chunk: page-wise scatter at cache_index
                k_cache, k_sc = update_cache_paged_chunk(
                    cache[0], k, page_table, cache_index, n_valid, k_sc)
                v_cache, v_sc = update_cache_paged_chunk(
                    cache[1], v, page_table, cache_index, n_valid, v_sc)
            new_cache = (k_cache, v_cache)
            if k_sc is not None:
                new_cache += (k_sc, v_sc)
            route = attention_route("decode" if S == 1 else "chunk", "paged",
                                    S=S, Skv=k_cache.shape[2], window=window,
                                    opts=opts, causal=causal)
            out = run_attention_core(route, q, k_cache, v_cache, opts=opts,
                                     window=window, index=cache_index,
                                     page_table=page_table, k_scales=k_sc,
                                     v_scales=v_sc, live_len=live_len)
        else:
            smax = cache[0].shape[2]
            ring = (window != GLOBAL_WINDOW and smax == window)
            if not ring and S > smax:
                raise ValueError(f"prefill length {S} exceeds cache {smax}")
            if ring:
                k_cache = update_cache(cache[0], k, cache_index % smax)
                v_cache = update_cache(cache[1], v, cache_index % smax)
            else:
                k_cache = update_cache_chunk(cache[0], k, cache_index,
                                             n_valid)
                v_cache = update_cache_chunk(cache[1], v, cache_index,
                                             n_valid)
            new_cache = (k_cache, v_cache)
            whole = (not ring and isinstance(cache_index, int)
                     and cache_index == 0 and S == smax)
            if S == 1:
                mode, layout = "decode", ("ring" if ring else "dense")
            elif ring or whole:
                # ring caches don't support positioned prefill, and a chunk
                # filling the whole buffer has no earlier cache contents —
                # both attend within the fresh chunk (flash/banded cores,
                # which also tile big dryrun/cost shapes the untiled chunk
                # cores would not)
                mode, layout = "fresh", ("ring" if ring else "dense")
            else:
                mode, layout = "chunk", "dense"
            route = attention_route(mode, layout, S=S, Skv=S, window=window,
                                    opts=opts, causal=causal)
            if mode == "fresh":
                out = run_attention_core(route, q, k, v, opts=opts,
                                         window=window, causal=causal,
                                         q_pos=positions, k_pos=positions)
            else:
                out = run_attention_core(route, q, k_cache, v_cache,
                                         opts=opts, window=window,
                                         index=cache_index,
                                         live_len=live_len)
    elif pre and ctx is not None:
        route = attention_route("cross", "none", S=S, Skv=k.shape[1],
                                window=GLOBAL_WINDOW, opts=opts, causal=False)
        out = run_attention_core(route, q, k, v, opts=opts,
                                 window=GLOBAL_WINDOW, causal=False,
                                 q_pos=positions, k_pos=jnp.arange(k.shape[1]))
    else:
        route = attention_route("fresh", "none", S=S, Skv=k.shape[1],
                                window=window, opts=opts, causal=causal)
        out = run_attention_core(route, q, k, v, opts=opts, window=window,
                                 causal=causal, q_pos=positions,
                                 k_pos=positions)
    out = jnp.einsum("bsnh,nhd->bsd", out, p[pre + "wo"])
    if (opts.shard_axis is not None and not pre
            and p["wo"].shape[0] != cfg.num_heads):
        # head-sharded trace (shard_map): each shard computed its heads'
        # slice of the output projection, a partial sum over the full
        # d_model — the Megatron row-parallel reduction point
        out = jax.lax.psum(out, opts.shard_axis)
    return out, new_cache


# ---------------------------------------------------------------------------
# MLP / MoE
# ---------------------------------------------------------------------------

def mlp(p, x, cfg: ModelConfig, prefix: str = "",
        shard_axis: Optional[str] = None):
    h = jnp.einsum("bsd,df->bsf", x, p[prefix + "wi"])
    if cfg.act in ("silu", "gelu"):
        g = jnp.einsum("bsd,df->bsf", x, p[prefix + "wg"])
        h = _act(h, g, cfg.act)
    else:
        h = _act(h, None, cfg.act)
    h = constrain(h, "batch", "act_seq", "act_mlp")
    out = jnp.einsum("bsf,fd->bsd", h, p[prefix + "wo_mlp"])
    if shard_axis is not None and p[prefix + "wo_mlp"].shape[0] != cfg.d_ff:
        # f-sharded trace (shard_map): partial sum over the hidden width
        out = jax.lax.psum(out, shard_axis)
    return out


def moe(p, x, cfg: ModelConfig, opts: ModelOptions):
    """Capacity-based top-k MoE (GShard/MaxText-style sort-free dispatch).

    x [B,S,D] -> [B,S,D]. Expert matmuls are [E,C,D]x[E,D,F] batched einsums
    (the shape our Pallas moe_gmm kernel implements); dispatch/combine are
    scatter/gather built from an exclusive cumsum of expert assignments.

    Two slot-assignment modes:
    - global (default): cumsum over all T=B*S tokens. Exact GShard capacity
      semantics, but with batch sharded over 'data' the prefix sum crosses
      devices.
    - per-sequence (opts.moe_per_seq_dispatch, §Perf): slots are assigned
      within each sequence (capacity C_seq = ceil(S*K/E * factor)), so the
      cumsum is local to each batch shard — no cross-device prefix sums —
      at the cost of slightly more padding slots.
    """
    B, S, D = x.shape
    E, K = max(cfg.num_experts_padded, cfg.num_experts), cfg.top_k
    T = B * S
    xt = x.reshape(T, D)
    logits = jnp.einsum("td,de->te", xt, p["router"]).astype(jnp.float32)
    if E > cfg.num_experts:   # mask padded experts out of routing
        pad_mask = jnp.arange(E) >= cfg.num_experts
        logits = jnp.where(pad_mask[None], NEG_INF, logits)
    probs = jax.nn.softmax(logits, -1)
    gates, expert_idx = jax.lax.top_k(probs, K)          # [T,K]
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    if opts.moe_gather_decode and T * K <= E:
        # decode with T*K « E: stream only the hit experts' weights
        # (bytes ~ k/E of the capacity path, the memory-roofline optimum
        # for the paper's bottleneck phase on MoE decoders)
        idx = expert_idx.reshape(-1)                     # [T*K]
        wi = jnp.take(p["moe_wi"], idx, 0)               # [T*K, D, F]
        wg = jnp.take(p["moe_wg"], idx, 0)
        wo = jnp.take(p["moe_wo"], idx, 0)
        xk = jnp.repeat(xt, K, axis=0)                   # [T*K, D]
        h = jnp.einsum("td,tdf->tf", xk, wi)
        g = jnp.einsum("td,tdf->tf", xk, wg)
        he = jnp.einsum("tf,tfd->td", _act(h, g, cfg.act), wo)
        out = (he.reshape(T, K, D)
               * gates[..., None].astype(he.dtype)).sum(1)
        return out.reshape(B, S, D)

    E_real = cfg.num_experts   # capacity sizes from the REAL expert count
    if opts.moe_per_seq_dispatch and B > 1:
        Cs = max(1, int(np.ceil(K * S / E_real * opts.moe_capacity_factor)))
        C = B * Cs
        e_seq = expert_idx.reshape(B, S * K)             # [B, S*K]
        onehot = jax.nn.one_hot(e_seq, E, dtype=jnp.int32)
        pos = jnp.cumsum(onehot, axis=1) - onehot        # local prefix sum
        slot_s = jnp.take_along_axis(pos, e_seq[..., None], 2)[..., 0]
        keep = (slot_s < Cs).reshape(-1)
        # global slot: expert-major, then (sequence, within-seq slot)
        b_of = jnp.repeat(jnp.arange(B), S * K)
        slot = (b_of * Cs + slot_s.reshape(-1))
        flat_e = e_seq.reshape(-1)
        dest = jnp.where(keep, flat_e * C + slot, E * C)
    else:
        C = max(1, int(np.ceil(K * T / E_real * opts.moe_capacity_factor)))
        flat_e = expert_idx.reshape(-1)                  # [T*K]
        # position within expert (stable order over tokens; global cumsum)
        onehot = jax.nn.one_hot(flat_e, E, dtype=jnp.int32)
        pos_in_e = (jnp.cumsum(onehot, axis=0) - onehot)
        slot = jnp.take_along_axis(pos_in_e, flat_e[:, None], 1)[:, 0]
        keep = slot < C
        dest = jnp.where(keep, flat_e * C + slot, E * C)

    token_of = jnp.repeat(jnp.arange(T), K)
    buf_tokens = jnp.zeros((E * C + 1,), jnp.int32).at[dest].set(token_of)
    buf_valid = jnp.zeros((E * C + 1,), x.dtype).at[dest].set(1.0)
    buf_tokens, buf_valid = buf_tokens[:-1], buf_valid[:-1]

    xe = xt[buf_tokens].reshape(E, C, D) * buf_valid.reshape(E, C, 1)
    xe = constrain(xe, "act_experts", "batch", None)
    if pallas_pinned(opts):
        from repro.kernels.moe_gmm import ops as gmm_ops
        he = gmm_ops.grouped_mlp(xe, p["moe_wi"], p["moe_wg"], p["moe_wo"],
                                 cfg.act, interpret=not on_tpu())
    else:
        h = jnp.einsum("ecd,edf->ecf", xe, p["moe_wi"])
        g = jnp.einsum("ecd,edf->ecf", xe, p["moe_wg"])
        he = jnp.einsum("ecf,efd->ecd", _act(h, g, cfg.act), p["moe_wo"])
    he = he.reshape(E * C, D)

    # combine: each (token, k) reads its slot if kept
    src = jnp.where(keep, flat_e * C + slot, 0)
    picked = he[src] * keep[:, None].astype(he.dtype)    # [T*K, D]
    picked = picked.reshape(T, K, D) * gates[..., None].astype(he.dtype)
    out = picked.sum(1).reshape(B, S, D)
    return out


# ---------------------------------------------------------------------------
# Mamba2 (SSD)
# ---------------------------------------------------------------------------

def mamba_dims(cfg: ModelConfig):
    d_in = cfg.ssm_expand * cfg.d_model
    H = d_in // cfg.ssm_head_dim
    P = cfg.ssm_head_dim
    N = cfg.ssm_state
    G = 1
    conv_ch = d_in + 2 * G * N
    return d_in, H, P, N, G, conv_ch


def _conv1d_causal(x, w, b):
    """Depthwise causal conv. x [B,S,C], w [K,C], b [C]."""
    K = w.shape[0]
    xp = jnp.pad(x, ((0, 0), (K - 1, 0), (0, 0)))
    out = sum(xp[:, i:i + x.shape[1], :] * w[i] for i in range(K))
    return out + b


def ssd_scan_ref(xs, dt, A_log, B_, C_):
    """Sequential SSD recurrence (oracle; O(S) scan).
    xs [B,S,H,P], dt [B,S,H], A_log [H], B_/C_ [B,S,G,N] with G=1.
    h_t = exp(A dt_t) h_{t-1} + dt_t * B_t outer x_t ; y_t = C_t . h_t
    """
    Bsz, S, H, P = xs.shape
    N = B_.shape[-1]
    A = -jnp.exp(A_log.astype(jnp.float32))

    def step(h, inp):
        x_t, dt_t, b_t, c_t = inp
        decay = jnp.exp(A[None] * dt_t)                       # [B,H]
        db = dt_t[..., None] * b_t[:, 0][:, None, :]          # [B,H,N]
        h = h * decay[..., None, None] + x_t[..., None] * db[..., None, :]
        y = jnp.einsum("bhpn,bn->bhp", h, c_t[:, 0])
        return h, y

    h0 = jnp.zeros((Bsz, H, P, N), jnp.float32)
    xs_t = jnp.moveaxis(xs.astype(jnp.float32), 1, 0)
    dt_t = jnp.moveaxis(dt.astype(jnp.float32), 1, 0)
    b_t = jnp.moveaxis(B_.astype(jnp.float32), 1, 0)
    c_t = jnp.moveaxis(C_.astype(jnp.float32), 1, 0)
    hT, ys = jax.lax.scan(step, h0, (xs_t, dt_t, b_t, c_t))
    return jnp.moveaxis(ys, 0, 1).astype(xs.dtype), hT


def ssd_chunked(xs, dt, A_log, B_, C_, chunk: int = 128, h0=None,
                head_chunk: int = 16):
    """Chunked SSD (state-space duality, Mamba2 paper alg. 1-3):
    quadratic intra-chunk attention-like term + linear inter-chunk recurrence.
    Heads are processed in blocks of `head_chunk` via lax.map so the
    [B,nc,Q,Q,Hc] intra-chunk tensor stays VMEM/HBM-bounded at scale.
    Returns (y [B,S,H,P], final_state [B,H,P,N])."""
    Bsz, S, H, P = xs.shape
    if h0 is None and H > head_chunk and S > chunk:
        nh = H // head_chunk if H % head_chunk == 0 else 1
        if nh > 1:
            xs_h = jnp.moveaxis(
                xs.reshape(Bsz, S, nh, head_chunk, P), 2, 0)
            dt_h = jnp.moveaxis(
                dt.reshape(Bsz, S, nh, head_chunk), 2, 0)
            A_h = A_log.reshape(nh, head_chunk)
            y_h, st_h = jax.lax.map(
                lambda args: ssd_chunked(args[0], args[1], args[2], B_, C_,
                                         chunk=chunk, head_chunk=H),
                (xs_h, dt_h, A_h))
            y = jnp.moveaxis(y_h, 0, 2).reshape(Bsz, S, H, P)
            st = jnp.moveaxis(st_h, 0, 1).reshape(Bsz, H, P, N_ := st_h.shape[-1])
            return y, st
    G, N = B_.shape[2], B_.shape[3]
    Q = min(chunk, S)
    nc = S // Q
    A = -jnp.exp(A_log.astype(jnp.float32))                   # [H]
    xs_c = xs.reshape(Bsz, nc, Q, H, P)
    dt_c = dt.reshape(Bsz, nc, Q, H).astype(jnp.float32)
    b_c = B_.reshape(Bsz, nc, Q, G, N)[:, :, :, 0]
    c_c = C_.reshape(Bsz, nc, Q, G, N)[:, :, :, 0]

    dA = dt_c * A[None, None, None, :]                        # [B,nc,Q,H]
    cum = jnp.cumsum(dA, axis=2)                              # within-chunk
    seg_end = cum[:, :, -1]                                   # [B,nc,H]

    # --- intra-chunk (quadratic in Q) ---
    # L[s,t] = exp(cum_s - cum_t) for s >= t (decay from t to s)
    Lexp = cum[:, :, :, None, :] - cum[:, :, None, :, :]      # [B,nc,Q,Q,H]
    causal = jnp.tril(jnp.ones((Q, Q), bool))
    L = jnp.where(causal[None, None, :, :, None], jnp.exp(Lexp), 0.0)
    cb = jnp.einsum("bcsn,bctn->bcst", c_c, b_c)              # [B,nc,Q,Q]
    w = cb[..., None] * L                                     # [B,nc,Q,Q,H]
    xdt = xs_c.astype(jnp.float32) * dt_c[..., None]          # [B,nc,Q,H,P]
    y_intra = jnp.einsum("bcsth,bcthp->bcshp", w, xdt)

    # --- chunk states ---
    decay_to_end = jnp.exp(seg_end[:, :, None] - cum)         # [B,nc,Q,H]
    states = jnp.einsum("bctn,bcth,bcthp->bchpn",
                        b_c, decay_to_end * dt_c, xs_c.astype(jnp.float32))

    # --- inter-chunk recurrence over nc chunks ---
    def step(h, inp):
        st, dec = inp                                         # dec [B,H]
        h_new = h * dec[..., None, None] + st
        return h_new, h                                       # emit state *before* chunk

    if h0 is None:
        h0 = jnp.zeros((Bsz, H, P, N), jnp.float32)
    st_t = jnp.moveaxis(states, 1, 0)
    dec_t = jnp.moveaxis(jnp.exp(seg_end), 1, 0)
    hT, h_prev = jax.lax.scan(step, h0, (st_t, dec_t))
    h_prev = jnp.moveaxis(h_prev, 0, 1)                       # [B,nc,H,P,N]

    # --- inter-chunk output ---
    decay_from_start = jnp.exp(cum)                           # [B,nc,Q,H]
    y_inter = jnp.einsum("bcsn,bcsh,bchpn->bcshp",
                         c_c, decay_from_start, h_prev)
    y = (y_intra + y_inter).reshape(Bsz, S, H, P).astype(xs.dtype)
    return y, hT


def mamba_block(p, x, cfg: ModelConfig, opts: ModelOptions,
                state=None, conv_state=None, decode: bool = False):
    """Mamba2 mixer. Returns (out, new_state, new_conv_state)."""
    d_in, H, P, N, G, conv_ch = mamba_dims(cfg)
    B, S, D = x.shape
    z = jnp.einsum("bsd,de->bse", x, p["w_z"])
    xBC = jnp.einsum("bsd,de->bse", x, p["w_xbc"])
    dt = jnp.einsum("bsd,dh->bsh", x, p["w_dt"])
    dt = jax.nn.softplus(dt.astype(jnp.float32) + p["dt_bias"].astype(jnp.float32))

    if decode:
        # conv via cached last (K-1) inputs
        Kc = p["conv_w"].shape[0]
        window = jnp.concatenate([conv_state, xBC], axis=1)   # [B,Kc,convch]
        xBC_c = (window * p["conv_w"][None].astype(window.dtype)).sum(1, keepdims=True) \
            + p["conv_b"].astype(window.dtype)
        new_conv_state = window[:, 1:]
    else:
        xBC_c = _conv1d_causal(xBC, p["conv_w"], p["conv_b"])
        Kc = p["conv_w"].shape[0]
        new_conv_state = xBC[:, -(Kc - 1):] if S >= Kc - 1 else \
            jnp.pad(xBC, ((0, 0), (Kc - 1 - S, 0), (0, 0)))
    xBC_c = jax.nn.silu(xBC_c)
    xs, B_, C_ = jnp.split(xBC_c, [d_in, d_in + G * N], axis=-1)
    xs = xs.reshape(B, -1, H, P)
    B_ = B_.reshape(B, -1, G, N)
    C_ = C_.reshape(B, -1, G, N)

    if decode:
        A = -jnp.exp(p["A_log"].astype(jnp.float32))
        dt1 = dt[:, 0]                                        # [B,H]
        decay = jnp.exp(A[None] * dt1)
        db = dt1[..., None] * B_[:, 0, 0][:, None, :]
        h = state * decay[..., None, None] + \
            xs[:, 0].astype(jnp.float32)[..., None] * db[..., None, :]
        y = jnp.einsum("bhpn,bn->bhp", h, C_[:, 0, 0])[:, None]
        new_state = h
    else:
        if pallas_pinned(opts):
            from repro.kernels.ssd import ops as ssd_ops
            y, new_state = ssd_ops.ssd(xs, dt, p["A_log"], B_, C_,
                                       interpret=not on_tpu())
        else:
            y, new_state = ssd_chunked(xs, dt, p["A_log"], B_, C_)
    y = y.astype(x.dtype) + xs.astype(x.dtype) * p["d_skip"].astype(x.dtype)[None, None, :, None]
    y = y.reshape(B, -1, d_in)
    y = rms_norm(y, p["mamba_norm_w"], cfg.norm_eps) * jax.nn.silu(z)
    out = jnp.einsum("bse,ed->bsd", y, p["w_out"])
    return out, new_state, new_conv_state
