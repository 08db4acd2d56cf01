"""Paged flash-decode Pallas TPU kernel (bf16 or quantized int8/fp8 pages).

Single-token GQA attention against a shared page-major KV pool
``[num_pages, K, page_size, h]``: a slot's logical positions
``[p*page_size, (p+1)*page_size)`` live in physical page
``page_table[b, p]``, and all K heads of one page are one contiguous run of
HBM.

Grid and DMA pipeline. The grid has one step per slot, and a step covers
all K heads. The K and V pools stay in HBM (``memory_space=pl.ANY``); the
kernel gathers a slot's pages itself, a block of ``ppb`` pages at a time,
with one ``make_async_copy`` per page of its whole ``(K, page_size, h)``
slab into a double-buffered VMEM scratch ``(2, ppb, K, page_size, h)``.
Block j+1's copies start before block j is computed, and the last block of
a slot starts the next slot's first block, so the DMA engine runs on across
grid steps (which is why the grid axis is sequential, "arbitrary").

Live pages only. From the scalar-prefetched position and the sliding
window, ``live_pages`` (the ``block_live`` rule at one page) gives the
slot's first live page and live page count. The block loop's trip count is
dynamic, so dead pages past the position or older than the window cost no
grid step, and no page past the position is ever copied: the last block
copies only its live pages. The kernel reads every page that holds a live
position, whole, and nothing else.

``ppb`` comes from the shapes alone (``pages_per_block``): about
``BLOCK_KEYS`` = 256 key positions, no more than the table's pages, at most
``MAX_BLOCK_PAGES`` (the page loop is unrolled), and the four page buffers
(K and V, double-buffered) within ``KV_VMEM_BUDGET``. At molmoact-7b's
shapes (page 32, 4 KV heads of 128, bf16) that is 8 pages, 2 x 2 x 256 KB.

Numerics. Inside a block the online softmax is updated once per page, in
page order, by ``softmax_update`` (the arithmetic ``flash_block`` runs),
batched over the K heads: every query head of a group shares its group's
tile. So the absolute key-block partition stays at ``page_size``, and the
kernel agrees bit for bit with the dense decode kernel at
``bk = page_size`` and with the chunk-prefill kernels at
``prefill_band = page_size``. Buffer pages past a short last block hold
stale data; their update is ``softmax_update``'s exact no-op for
fully-masked blocks.

Quantized pools (``k_scales``/``v_scales`` given) stream 1-byte codes plus
one f32 scale array per pool, ``[num_pages, K]`` (per-(page, head)) or
``[num_pages, K, page_size]`` (per-token). The kernel reads per-token rows:
a per-(page, head) scale is broadcast over its page's rows in the wrapper.
Each page's ``(K, page_size)`` scale rows are copied beside its codes, and
dequantize the score and probability columns (see ``flash_block``). int8
pages need ``page_size`` to be a multiple of 32 on the TPU (the int8
sublane tile).

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
from repro.kernels.decode_attention.decode_attention import (
    NEG_INF, block_live, live_pages, softmax_result, softmax_scratch,
    softmax_update)

LANES = 128                     # the TPU's lane tile
BLOCK_KEYS = 256                # key positions a page block aims for
MAX_BLOCK_PAGES = 16            # the page loop is unrolled
KV_VMEM_BUDGET = 4 * 1024 * 1024  # K and V page buffers, double-buffered


def token_scales(scales, page_size: int):
    """Per-token ``[num_pages, K, page_size]`` f32 scales for the paged
    kernels: per-token scales pass through, per-(page, head) scales
    ``[num_pages, K]`` broadcast over their page's rows."""
    scales = jnp.asarray(scales, jnp.float32)
    if scales.ndim == 3:
        return scales
    return jnp.broadcast_to(scales[:, :, None], scales.shape + (page_size,))


def pages_per_block(npg: int, ps: int, K: int, h: int, itemsize: int) -> int:
    """Pages a grid step gathers per block, from the shapes alone."""
    page_bytes = 2 * 2 * K * ps * h * itemsize
    return max(1, min(npg, BLOCK_KEYS // ps, MAX_BLOCK_PAGES,
                      KV_VMEM_BUDGET // page_bytes))


def _paged_kernel(pt_ref, idx_ref, q_ref, *refs, ps: int, ppb: int,
                  window: int, n_streams: int):
    # refs: the pools in HBM (K, V[, K scales, V scales]), the output
    # block, their VMEM page buffers, the DMA semaphores, and the SMEM
    # index of the buffer that holds the next block to compute
    hbm = refs[:n_streams]
    o_ref = refs[n_streams]
    bufs = refs[n_streams + 1:2 * n_streams + 1]
    sems, cur_ref = refs[2 * n_streams + 1:]
    b, nb = pl.program_id(0), pl.num_programs(0)

    def copies(s, j, buf, p):
        # the copies of page p of slot s's j-th live block into buffer buf
        first, _ = live_pages(idx_ref[s], ps, window)
        page = pt_ref[s, first + j * ppb + p]
        return [pltpu.make_async_copy(src.at[page], dst.at[buf, p],
                                      sems.at[i, buf])
                for i, (src, dst) in enumerate(zip(hbm, bufs))]

    def block_pages(s, j):
        _, n = live_pages(idx_ref[s], ps, window)
        return jnp.minimum(ppb, n - j * ppb)

    def for_block(s, j, buf, op):
        n = block_pages(s, j)
        for p in range(ppb):
            @pl.when(p < n)
            def _():
                for c in copies(s, j, buf, p):
                    getattr(c, op)()

    @pl.when(b == 0)
    def _prime():
        for scale_buf in bufs[2:]:
            # stale scale rows meet p == 0 in softmax_update: keep them
            # finite, or 0 * NaN would reach the accumulator
            scale_buf[...] = jnp.zeros_like(scale_buf)
        cur_ref[0] = 0
        for_block(0, 0, 0, "start")

    index = idx_ref[b]
    first, n = live_pages(index, ps, window)
    n_blocks = (n + ppb - 1) // ppb
    q = q_ref[0]                                       # [K, G, h]
    K, G, h = q.shape

    def block(j, carry):
        buf = cur_ref[0]
        nxt = 1 - buf

        @pl.when(j + 1 < n_blocks)
        def _prefetch_own():
            for_block(b, j + 1, nxt, "start")

        @pl.when(jnp.logical_and(j + 1 == n_blocks, b + 1 < nb))
        def _prefetch_next_slot():
            for_block(b + 1, 0, nxt, "start")

        for_block(b, j, buf, "wait")
        m, l, acc = carry
        for p in range(ppb):
            scales = ([jnp.expand_dims(x[buf, p, :, :ps], 1)
                       for x in bufs[2:]] or [None] * 2)
            m, l, acc = softmax_update(
                q, bufs[0][buf, p], bufs[1][buf, p], m, l, acc,
                index=index, last=index,
                k_start=(first + j * ppb + p) * ps, window=window,
                row_step=0, k_scale=scales[0], v_scale=scales[1])
        cur_ref[0] = nxt
        return m, l, acc

    init = (jnp.full((K, G, 1), NEG_INF, jnp.float32),
            jnp.zeros((K, G, 1), jnp.float32),
            jnp.zeros((K, G, h), jnp.float32))
    _, l, acc = jax.lax.fori_loop(0, n_blocks, block, init)
    o_ref[0] = softmax_result(l, acc, o_ref.dtype)


def _page_grid_kernel(pt_ref, idx_ref, q_ref, k_ref, v_ref, *refs, ps: int,
                      npg: int, window: int):
    # one grid step per (slot, page), all K heads: the narrow-head path
    *scale_refs, o_ref, m_scr, l_scr, acc_scr = refs
    index, ip = idx_ref[pl.program_id(0)], pl.program_id(1)

    @pl.when(ip == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    @pl.when(block_live(index, index, ip * ps, ps, window))
    def _compute():
        scales = [jnp.expand_dims(x[0], 1) for x in scale_refs] or [None] * 2
        m, l, acc = softmax_update(
            q_ref[0], k_ref[0], v_ref[0], m_scr[...], l_scr[...],
            acc_scr[...], index=index, last=index, k_start=ip * ps,
            window=window, row_step=0, k_scale=scales[0],
            v_scale=scales[1])
        m_scr[...] = m
        l_scr[...] = l
        acc_scr[...] = acc

    @pl.when(ip == npg - 1)
    def _finalize():
        o_ref[0] = softmax_result(l_scr[...], acc_scr[...], o_ref.dtype)


def _page_grid_call(qg, pools, pt, idx, *, window: int, interpret: bool):
    """The paged decode for heads narrower than a 128-lane tile, which
    Mosaic cannot slice a page of out of an HBM pool: the BlockSpec index
    map gathers one whole ``(K, page_size, h)`` page per grid step through
    the page table, and dead pages are remapped to the null page so their
    copy is elided (repeated block indices are not fetched again)."""
    B, K, G, h = qg.shape
    ps, npg = pools[0].shape[2], pt.shape[1]

    def page(b, ip, pt_ref, idx_ref):
        i = idx_ref[b]
        return jnp.where(block_live(i, i, ip * ps, ps, window),
                         pt_ref[b, ip], 0)

    slot = pl.BlockSpec((1, K, G, h), lambda b, ip, *_: (b, 0, 0, 0))
    kv = pl.BlockSpec((1, K, ps, h), lambda b, ip, *r: (page(b, ip, *r), 0,
                                                        0, 0))
    scale = pl.BlockSpec((1, K, ps), lambda b, ip, *r: (page(b, ip, *r), 0,
                                                        0))
    kernel = functools.partial(_page_grid_kernel, ps=ps, npg=npg,
                               window=window)
    return pl.pallas_call(
        kernel,
        name="paged_decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, npg),
            in_specs=[slot, kv, kv] + [scale] * (len(pools) - 2),
            out_specs=slot,
            scratch_shapes=softmax_scratch((K, G), h),
        ),
        out_shape=jax.ShapeDtypeStruct(qg.shape, qg.dtype),
        interpret=interpret,
    )(pt, idx, qg, *pools)


def _gather_call(qg, pools, pt, idx, *, window: int, interpret: bool):
    """The paged decode with in-kernel page gathers (module docstring)."""
    B, K, G, h = qg.shape
    ps, npg = pools[0].shape[2], pt.shape[1]
    ppb = pages_per_block(npg, ps, K, h, pools[0].dtype.itemsize)
    if len(pools) > 2:
        # a page's scale rows are copied whole: lane-pad them to the
        # 128-lane tile that a copy of a (K, page_size) slice needs
        pad = ((0, 0), (0, 0), (0, -ps % LANES))
        pools = pools[:2] + [jnp.pad(x, pad) for x in pools[2:]]
    bufs = [pltpu.VMEM((2, ppb) + x.shape[1:], x.dtype) for x in pools]
    slot = pl.BlockSpec((1, K, G, h), lambda b, *_: (b, 0, 0, 0))
    hbm = pl.BlockSpec(memory_space=pltpu.HBM)
    kernel = functools.partial(_paged_kernel, ps=ps, ppb=ppb, window=window,
                               n_streams=len(pools))
    return pl.pallas_call(
        kernel,
        name="paged_decode_attention",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B,),
            in_specs=[slot] + [hbm] * len(pools),
            out_specs=slot,
            scratch_shapes=bufs + [
                pltpu.SemaphoreType.DMA((len(pools), 2)),
                pltpu.SMEM((1,), jnp.int32)],
        ),
        out_shape=jax.ShapeDtypeStruct(qg.shape, qg.dtype),
        # the page pipeline runs on across slots: keep them in order
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(pt, idx, qg, *pools)


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
    qg = q.reshape(B, K, N // K, h)
    pt = jnp.asarray(page_table, jnp.int32)
    idx = jnp.broadcast_to(jnp.asarray(index, jnp.int32).reshape(-1), (B,))
    pools = [k_pages, v_pages]
    if k_scales is not None:
        pools += [token_scales(k_scales, ps), token_scales(v_scales, ps)]
    call = _gather_call if h % LANES == 0 else _page_grid_call
    out = call(qg, pools, pt, idx, window=window, interpret=interpret)
    return out.reshape(B, N, h)
