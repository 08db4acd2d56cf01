"""Paged banded chunk-prefill Pallas TPU kernel (bf16 or int8/fp8 pages).

Same banded chunk attention as ``chunk_prefill.py`` — the online-softmax
body is literally shared (``decode_attention.flash_block``) — but the KV
cache lives in a shared head-major page pool ``[num_pages, K, page_size,
h]`` addressed through a per-slot page table, like the paged flash-decode
kernel (``decode_attention/paged.py``). The page table and the
per-slot chunk start positions arrive as scalar-prefetch operands, so the
*index map itself* gathers KV pages: grid cell ``(b, head, p)`` DMAs the
``(page_size, h)`` tile of physical page ``page_table[b, p]`` from HBM — the
paged cache view needs **no host-side pool gather**. Pages past the chunk's
live prefix, or entirely older than its sliding window, are remapped to the
reserved null page 0 so their DMA is never issued, and their compute is
skipped by ``pl.when``.

Quantized pools (``k_scales``/``v_scales`` given) stream 1-byte codes plus
one f32 scale array per pool — ``[num_pages, K]`` per-(page, head) or
``[num_pages, K, page_size]`` per-token, read as per-token rows exactly as
in the paged decode kernel.

Partition caveat: this kernel blocks the key axis per *page* (one grid cell
per page — a BlockSpec gather cannot span non-contiguous pages), while the
dense chunk kernel blocks per ``bk``. The blockwise online softmax is only
bit-stable across dispatches that share one absolute partition, so engines
that compare paged-kernel streams against dense-kernel streams must run
with ``page_size == prefill_band`` (``ServingEngine`` enforces this for
chunked-prefill mode on the kernel path).
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
from repro.kernels.decode_attention.paged import token_scales


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
                bk=ps, nk=npg, window=window, row_step=1)


def _paged_quant_kernel(pt_ref, idx_ref, q_ref, k_ref, v_ref, ks_ref, vs_ref,
                        o_ref, m_scr, l_scr, acc_scr, *, ps: int, npg: int,
                        window: int, G: int):
    kh = pl.program_id(1) // G
    flash_block(idx_ref[pl.program_id(0)], pl.program_id(2),
                q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                bk=ps, nk=npg, window=window, row_step=1,
                k_scale=head_row(ks_ref, kh), v_scale=head_row(vs_ref, kh))


def paged_chunk_prefill_attention_kernel(q, k_pages, v_pages, page_table,
                                         index, *, k_scales=None,
                                         v_scales=None,
                                         window: int = GLOBAL_WINDOW,
                                         interpret: bool = False):
    """q [B,S,N,h] (one prefill chunk, already scattered into the pool);
    k/v pages [num_pages, K, page_size, h] (bf16/f32, or int8/fp8 codes
    when ``k_scales``/``v_scales`` f32 — ``[num_pages, K]`` per-(page,
    head) or ``[num_pages, K, page_size]`` per-token — are given; pass both
    or neither); page_table [B, npg] int32 physical page ids (the caller
    may pre-slice npg to the banded live bound); index int32 scalar or
    per-slot [B] vector of chunk start positions. Returns [B,S,N,h]."""
    if (k_scales is None) != (v_scales is None):
        raise ValueError("pass both k_scales and v_scales, or neither")
    B, S, N, h = q.shape
    K, ps = k_pages.shape[1], k_pages.shape[2]
    npg = page_table.shape[1]
    G = N // K
    qt = q.swapaxes(1, 2)                                 # [B, N, S, h]
    pt = jnp.asarray(page_table, jnp.int32)
    idx = jnp.broadcast_to(jnp.asarray(index, jnp.int32).reshape(-1), (B,))

    def page(b, ip, pt_ref, idx_ref):
        # gather through the page table; dead pages remap to the null page
        # so their distinct-page DMA is never issued
        i = idx_ref[b]
        live = block_live(i, i + S - 1, ip * ps, ps, window)
        return jnp.where(live, pt_ref[b, ip], 0)

    def q_map(b, n, ip, pt_ref, idx_ref):
        return b, n, 0, 0

    def kv_map(b, n, ip, pt_ref, idx_ref):
        return page(b, ip, pt_ref, idx_ref), n // G, 0, 0

    def scale_map(b, n, ip, pt_ref, idx_ref):
        return page(b, ip, pt_ref, idx_ref), 0, 0

    in_specs = [pl.BlockSpec((1, 1, S, h), q_map),
                pl.BlockSpec((1, 1, ps, h), kv_map),
                pl.BlockSpec((1, 1, ps, h), kv_map)]
    operands = [qt, k_pages, v_pages]
    if k_scales is None:
        kernel = functools.partial(_paged_kernel, ps=ps, npg=npg,
                                   window=window)
    else:
        kernel = functools.partial(_paged_quant_kernel, ps=ps, npg=npg,
                                   window=window, G=G)
        in_specs += [pl.BlockSpec((1, K, ps), scale_map),
                     pl.BlockSpec((1, K, ps), scale_map)]
        operands += [token_scales(k_scales, ps), token_scales(v_scales, ps)]
    out = pl.pallas_call(
        kernel,
        name="paged_chunk_prefill_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, N, npg),
            in_specs=in_specs,
            out_specs=pl.BlockSpec((1, 1, S, h), q_map),
            scratch_shapes=softmax_scratch(S, h),
        ),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        interpret=interpret,
    )(pt, idx, *operands)
    return out.swapaxes(1, 2)
