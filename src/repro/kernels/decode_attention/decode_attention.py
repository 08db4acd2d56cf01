"""Flash-decode Pallas TPU kernel — the paper's action-generation bottleneck.

Single-token GQA attention against a long KV cache. This op is memory-bound
(intensity ~= 1 FLOP/byte « v5e ridge of 240), so the kernel is laid out for
*bandwidth*: the head-major KV cache ``[B, K, S, h]`` streams HBM->VMEM in
``(bk, h)`` tiles — positions on the sublane axis, the head dim on the lane
axis, the TPU's native tiling — and all G query heads of a KV group ride
along each tile (one cache read serves G heads, the GQA arithmetic-intensity
win). Online softmax state lives in VMEM scratch across the sequential
KV-block grid dimension.

The valid length (current decode position) arrives as a scalar-prefetch
operand, so fully-invalid KV blocks are skipped twice over: the BlockSpec
index map remaps them to block 0 (repeated index-map outputs elide the
HBM->VMEM DMA) and ``pl.when`` skips their compute — the same early-exit a
paged decode kernel does on GPU, re-expressed for the TPU's sequential grid.
``index`` may be a scalar or a per-slot ``[B]`` vector (continuous
batching): each batch row masks and early-exits against its own position.

``softmax_update`` is the single online-softmax arithmetic shared by every
decode and chunk-prefill kernel (dense and paged, ``chunk_prefill/``), so
the numerically-sensitive part lives in exactly one place. ``flash_block``
runs it once per grid cell, for the kernels that differ only in how a grid
cell's KV block is located (contiguous rows vs page-table index map) and in
where their query rows sit (decode: G heads at one position; chunk: S
positions of one head). The paged decode kernel (``paged.py``) runs it once
per page of a block of pages it gathered itself, batched over the KV heads.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.configs.base import GLOBAL_WINDOW

NEG_INF = -1e30


def block_live(index, last, k_start, bk: int, window: int):
    """Whether KV block [k_start, k_start+bk) has any unmasked lane for
    query rows at positions index..last (shared by kernel bodies and
    BlockSpec index maps). The causal bound uses the youngest row
    (``last``); the window bound uses the oldest (``index``) — a block too
    old even for it is too old for every row."""
    live = k_start <= last
    if window != GLOBAL_WINDOW:
        live = jnp.logical_and(live, (index - (k_start + bk - 1)) < window)
    return live


def live_pages(index, ps: int, window: int, xp=jnp):
    """First live page and live page count of a query at ``index``: the
    pages ``p`` for which ``block_live(index, index, p * ps, ps, window)``
    holds, a contiguous run that always includes ``index``'s own page.
    ``xp`` is ``jnp`` inside kernels and ``np`` for host-side counts."""
    first = 0
    if window != GLOBAL_WINDOW:
        first = xp.maximum(index - window + 1, 0) // ps
    return first, index // ps + 1 - first


def softmax_update(q, k, v, m, l, acc, *, index, last, k_start,
                   window: int, row_step: int, k_scale=None, v_scale=None):
    """The f32 arithmetic of one key block of the online softmax, on values.
    ``q`` [..., R, h] and ``k``/``v`` [..., bk, h] share their leading
    (batch) axes: none in ``flash_block``, the KV heads in the paged decode
    kernel. Key lane j sits at absolute position ``k_start + j``; query row
    r at ``index + row_step * r``, the youngest at ``last``. ``m``/``l``
    [..., R, 1] and ``acc`` [..., R, h] are the running max, sum and
    unnormalized output; returns their updates. ``k_scale``/``v_scale``
    ([..., 1, bk] f32) dequantize int8/fp8 codes as ``flash_block`` says.

    A block whose every lane is masked for a row is an exact no-op for it,
    whatever the block holds: scores are replaced (not multiplied) by the
    mask and V is zeroed on lanes past ``last``, so stale or NaN-padded
    rows cannot reach the accumulator."""
    nb = q.ndim - 2
    batch = tuple(range(nb))
    q = q.astype(jnp.float32)                          # [..., R, h]
    k = k.astype(jnp.float32)                          # [..., bk, h]
    v = v.astype(jnp.float32)
    s = jax.lax.dot_general(q, k, (((nb + 1,), (nb + 1,)), (batch, batch)),
                            preferred_element_type=jnp.float32)
    s = s * (1.0 / np.sqrt(q.shape[-1]))               # [..., R, bk]
    if k_scale is not None:
        s = s * k_scale
    q_pos = index + row_step * jax.lax.broadcasted_iota(
        jnp.int32, s.shape, nb)
    kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, s.shape, nb + 1)
    mask = kpos <= q_pos
    if window != GLOBAL_WINDOW:
        mask &= (q_pos - kpos) < window
    s = jnp.where(mask, s, NEG_INF)
    # lanes dead for every row may be out-of-bounds tail lanes —
    # NaN-padded in interpret mode, undefined on TPU — and 0 * NaN
    # would poison the accumulator
    bk = k.shape[-2]
    kcol = k_start + jax.lax.broadcasted_iota(jnp.int32, (bk, 1), 0)
    v = jnp.where(kcol <= last, v, 0.0)
    m_new = jnp.maximum(m, s.max(axis=-1, keepdims=True))
    p = jnp.exp(s - m_new) * mask
    corr = jnp.exp(m - m_new)
    l = l * corr + p.sum(axis=-1, keepdims=True)
    if v_scale is not None:
        p = p * v_scale
    acc = acc * corr + jax.lax.dot_general(
        p, v, (((nb + 1,), (nb,)), (batch, batch)),
        preferred_element_type=jnp.float32)
    return m_new, l, acc


def flash_block(index, ik, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                *, bk: int, nk: int, window: int, row_step: int,
                k_scale=None, v_scale=None):
    """One KV block of the online-softmax update. ``q_ref``/``o_ref`` are
    ``[1, 1, R, h]`` blocks of R query rows; row r sits at absolute
    position ``index + row_step * r`` (decode: ``row_step=0``, every row is
    one head at the same position; chunk prefill: ``row_step=1``, every row
    is one position of one head). ``k_ref``/``v_ref`` are ``[1, 1, bk, h]``
    head-major blocks covering key positions [ik*bk, (ik+1)*bk). Lanes past
    a row's position (stale cache rows, or out-of-bounds tail lanes of a
    non-aligned cache) are masked before they can contribute, and V is
    zeroed on lanes dead for every row so NaN-padded out-of-bounds tails
    cannot poison the accumulator.

    Bit-stability contract: the update for a block that is fully masked for
    a row is an *exact* no-op for it (``corr == exp(0) == 1``, ``p == 0``),
    so a row's result depends only on the absolute key-block partition up to
    its own position — never on how a prompt was chunked or how much
    trailing cache view the caller passed in.

    ``k_scale``/``v_scale`` (optional f32 ``[1, bk]`` rows, one scale per
    key position) dequantize an int8/fp8 block: a per-position K scale
    multiplies that position's score column, and a per-position V scale
    multiplies the matching probability column before the PV product, so
    HBM only ever streams 1-byte codes and the online softmax runs in
    fp32."""
    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    k_start = ik * bk
    rows = q_ref.shape[2]
    last = index + row_step * (rows - 1)

    @pl.when(block_live(index, last, k_start, bk, window))
    def _compute():
        m, l, acc = softmax_update(
            q_ref[0, 0], k_ref[0, 0], v_ref[0, 0],
            m_scr[...], l_scr[...], acc_scr[...], index=index, last=last,
            k_start=k_start, window=window, row_step=row_step,
            k_scale=k_scale, v_scale=v_scale)
        m_scr[...] = m
        l_scr[...] = l
        acc_scr[...] = acc

    @pl.when(ik == nk - 1)
    def _finalize():
        o_ref[0, 0] = softmax_result(l_scr[...], acc_scr[...], o_ref.dtype)


def softmax_result(l, acc, dtype):
    """The normalized output of a finished online softmax."""
    return (acc / jnp.maximum(l, 1e-30)).astype(dtype)


def softmax_scratch(rows, h: int):
    """VMEM scratch for ``flash_block``: running max, running sum, and the
    unnormalized output accumulator of R query rows (``rows`` R, or a
    tuple of leading axes ending in R)."""
    rows = rows if isinstance(rows, tuple) else (rows,)
    return [pltpu.VMEM(rows + (1,), jnp.float32),
            pltpu.VMEM(rows + (1,), jnp.float32),
            pltpu.VMEM(rows + (h,), jnp.float32)]


def _kernel(idx_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            bk: int, nk: int, window: int):
    flash_block(idx_ref[pl.program_id(0)], pl.program_id(2),
                q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                bk=bk, nk=nk, window=window, row_step=0)


def decode_attention_kernel(q, k_cache, v_cache, index, *,
                            window: int = GLOBAL_WINDOW, bk: int = 512,
                            interpret: bool = False):
    """q [B,N,h]; k/v cache [B,K,S,h] (head-major); index: int32 scalar or
    per-slot [B] vector of current positions (each must be < S). Returns
    [B,N,h].

    S need not divide by ``bk``: the grid covers ceil(S/bk) blocks and the
    tail block's out-of-bounds lanes carry positions > index, so the
    ``kpos <= index`` mask silently discards them — no KV positions are
    dropped and no padded copy of the cache is materialized.
    """
    B, N, h = q.shape
    K, S = k_cache.shape[1], k_cache.shape[2]
    G = N // K
    bk = min(bk, S)
    nk = pl.cdiv(S, bk)
    # view q as [B, K, G, h] so one grid cell covers a whole KV group
    # (head n = k*G + g)
    qg = q.reshape(B, K, G, h)
    idx = jnp.broadcast_to(jnp.asarray(index, jnp.int32).reshape(-1), (B,))

    def q_map(b, kh, ik, idx_ref):
        return b, kh, 0, 0

    def kv_map(b, kh, ik, idx_ref):
        # remap fully-invalid blocks (past the position, or entirely older
        # than the window) to block 0 so their DMA is elided (repeated
        # index-map outputs are not re-fetched); compute is pl.when-skipped.
        i = idx_ref[b]
        live = block_live(i, i, ik * bk, bk, window)
        return b, kh, jnp.where(live, ik, 0), 0

    kernel = functools.partial(_kernel, bk=bk, nk=nk, window=window)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, K, nk),
            in_specs=[pl.BlockSpec((1, 1, G, h), q_map),
                      pl.BlockSpec((1, 1, bk, h), kv_map),
                      pl.BlockSpec((1, 1, bk, h), kv_map)],
            out_specs=pl.BlockSpec((1, 1, G, h), q_map),
            scratch_shapes=softmax_scratch(G, h),
        ),
        out_shape=jax.ShapeDtypeStruct((B, K, G, h), q.dtype),
        interpret=interpret,
    )(idx, qg, k_cache, v_cache)
    return out.reshape(B, N, h)
