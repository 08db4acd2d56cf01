"""Paged flash-decode Pallas TPU kernel (bf16 or quantized int8/fp8 pages).

Same bandwidth-tuned single-token GQA attention as ``decode_attention`` —
the online-softmax body is literally shared (``flash_block``) — but the KV
cache lives in a shared head-major page pool ``[num_pages, K, page_size, h]``
instead of a dense per-slot ``[B, K, S, h]`` buffer. Each slot's logical
sequence is described by a row of the page table: logical positions
``[p*page_size, (p+1)*page_size)`` live in physical page ``page_table[b, p]``.

The page table and the per-slot positions arrive as scalar-prefetch
operands, so the *index map itself* gathers KV blocks through the page
table: grid cell ``(b, kv_head, p)`` DMAs the ``(page_size, h)`` tile of
physical page ``page_table[b, p]`` for that head from HBM. Fully-masked
pages (past a slot's position, or entirely older than its sliding window)
are remapped to the null page so their DMA is never issued, and their
compute is skipped by ``pl.when`` — vLLM's paged attention early-exit,
re-expressed for the TPU's sequential grid.

Quantized pools (``k_scales``/``v_scales`` given) stream 1-byte codes plus
one f32 scale array per pool — ``[num_pages, K]`` (per-(page, head)) or
``[num_pages, K, page_size]`` (per-token), dispatched on ndim. The kernel
reads per-token rows only: a per-(page, head) scale is broadcast over its
page's rows in the wrapper. A grid cell takes the page's ``(K, page_size)``
scale tile (a whole-array block on the tiled axes, gathered through the
same page-table index map), picks its head's row, and dequantizes in the
score and probability columns (see ``flash_block``), so HBM traffic per
token drops to ~1 byte per cache element while the online softmax stays
fp32. int8 pages need ``page_size`` to be a multiple of 32 on the TPU (the
int8 sublane tile).

Page 0 is the pool's reserved null page: padding entries in the table point
at it and its contribution is always masked.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.configs.base import GLOBAL_WINDOW
from repro.kernels.decode_attention.decode_attention import (block_live,
                                                             flash_block,
                                                             softmax_scratch)


def token_scales(scales, page_size: int):
    """Per-token ``[num_pages, K, page_size]`` f32 scales for the paged
    kernels: per-token scales pass through, per-(page, head) scales
    ``[num_pages, K]`` broadcast over their page's rows."""
    scales = jnp.asarray(scales, jnp.float32)
    if scales.ndim == 3:
        return scales
    return jnp.broadcast_to(scales[:, :, None], scales.shape + (page_size,))


def head_row(s_ref, kh):
    """Row ``kh`` of a ``[1, K, ps]`` scale block as a ``[1, ps]`` f32 row
    (a masked sublane sum: the head is a grid index, not a static slice)."""
    tile = s_ref[0]                                       # [K, ps]
    sel = jax.lax.broadcasted_iota(jnp.int32, tile.shape, 0) == kh
    return jnp.sum(jnp.where(sel, tile, 0.0), axis=0, keepdims=True)


def _paged_kernel(pt_ref, idx_ref, q_ref, k_ref, v_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, ps: int, npg: int, window: int):
    flash_block(idx_ref[pl.program_id(0)], pl.program_id(2),
                q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                bk=ps, nk=npg, window=window, row_step=0)


def _paged_quant_kernel(pt_ref, idx_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                        o_ref, m_scr, l_scr, acc_scr, *, ps: int, npg: int,
                        window: int):
    kh = pl.program_id(1)
    flash_block(idx_ref[pl.program_id(0)], pl.program_id(2),
                q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                bk=ps, nk=npg, window=window, row_step=0,
                k_scale=head_row(ks_ref, kh), v_scale=head_row(vs_ref, kh))


def paged_decode_attention_kernel(q, k_pages, v_pages, page_table, index, *,
                                  k_scales=None, v_scales=None,
                                  window: int = GLOBAL_WINDOW,
                                  interpret: bool = False):
    """q [B,N,h]; k/v pages [num_pages, K, page_size, h] (bf16/f32, or
    int8/fp8 codes when ``k_scales``/``v_scales`` f32 — ``[num_pages, K]``
    per-(page, head) or ``[num_pages, K, page_size]`` per-token — are
    given; pass both or neither); page_table [B, npg] int32 physical page
    ids; index int32 scalar or per-slot [B] vector of current positions
    (< npg * page_size). Returns [B,N,h] in q's dtype."""
    if (k_scales is None) != (v_scales is None):
        raise ValueError("pass both k_scales and v_scales, or neither")
    B, N, h = q.shape
    K, ps = k_pages.shape[1], k_pages.shape[2]
    npg = page_table.shape[1]
    G = N // K
    qg = q.reshape(B, K, G, h)
    pt = jnp.asarray(page_table, jnp.int32)
    idx = jnp.broadcast_to(jnp.asarray(index, jnp.int32).reshape(-1), (B,))

    def page(b, ip, pt_ref, idx_ref):
        # KV blocks are gathered *through the page table*: grid cell
        # (b, kh, ip) streams physical page pt[b, ip]. Fully-masked pages
        # are remapped to the null page 0, so their distinct-page DMA is
        # never issued (repeated index-map outputs elide the fetch).
        i = idx_ref[b]
        live = block_live(i, i, ip * ps, ps, window)
        return jnp.where(live, pt_ref[b, ip], 0)

    def q_map(b, kh, ip, pt_ref, idx_ref):
        return b, kh, 0, 0

    def kv_map(b, kh, ip, pt_ref, idx_ref):
        return page(b, ip, pt_ref, idx_ref), kh, 0, 0

    def scale_map(b, kh, ip, pt_ref, idx_ref):
        # the page's whole (K, ps) scale tile, remapped in lockstep with
        # kv_map so a dead page's scale DMA is elided with its value DMA
        return page(b, ip, pt_ref, idx_ref), 0, 0

    in_specs = [pl.BlockSpec((1, 1, G, h), q_map),
                pl.BlockSpec((1, 1, ps, h), kv_map),
                pl.BlockSpec((1, 1, ps, h), kv_map)]
    operands = [qg, k_pages, v_pages]
    if k_scales is None:
        kernel = functools.partial(_paged_kernel, ps=ps, npg=npg,
                                   window=window)
    else:
        kernel = functools.partial(_paged_quant_kernel, ps=ps, npg=npg,
                                   window=window)
        in_specs += [pl.BlockSpec((1, K, ps), scale_map),
                     pl.BlockSpec((1, K, ps), scale_map)]
        operands += [token_scales(k_scales, ps), token_scales(v_scales, ps)]
    out = pl.pallas_call(
        kernel,
        name="paged_decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, K, npg),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, G, h), q_map),
            scratch_shapes=softmax_scratch(G, h),
        ),
        out_shape=jax.ShapeDtypeStruct((B, K, G, h), q.dtype),
        interpret=interpret,
    )(pt, idx, *operands)
    return out.reshape(B, N, h)
