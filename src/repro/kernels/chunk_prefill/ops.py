"""jit'd public wrappers for the chunk-prefill kernels (dense + paged)."""
from __future__ import annotations

import functools

import jax

from repro.configs.base import GLOBAL_WINDOW
from repro.kernels.chunk_prefill.chunk_prefill import (
    chunk_prefill_attention_kernel)
from repro.kernels.chunk_prefill.paged import (
    paged_chunk_prefill_attention_kernel)


@functools.partial(jax.jit, static_argnames=("window", "bk", "interpret"))
def chunk_prefill_attention(q, k_cache, v_cache, index, *,
                            window: int = GLOBAL_WINDOW, bk: int = 128,
                            interpret: bool = False):
    """Banded chunk-prefill attention. q [B,S,N,h]; cache view [B,K,L,h]
    (pre-slice L to the live band to bound key-axis work); index int32
    scalar or per-slot [B] vector of chunk start positions. Blocks past a
    chunk's live prefix never leave HBM (index-map remap) and never
    compute (pl.when)."""
    return chunk_prefill_attention_kernel(q, k_cache, v_cache, index,
                                          window=window, bk=bk,
                                          interpret=interpret)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_chunk_prefill_attention(q, k_pages, v_pages, page_table, index, *,
                                  k_scales=None, v_scales=None,
                                  window: int = GLOBAL_WINDOW,
                                  interpret: bool = False):
    """Banded chunk-prefill attention against a paged KV pool — the page
    table is gathered in the BlockSpec index map (scalar prefetch), so no
    host-side pool gather is materialized. q [B,S,N,h]; pages
    [num_pages, K, page_size, h]; page_table [B, npg] (pre-slice npg to
    the live band); index scalar or [B]. For quantized pools pass the
    sibling scales ([num_pages, K] or [num_pages, K, page_size] f32)."""
    return paged_chunk_prefill_attention_kernel(
        q, k_pages, v_pages, page_table, index, k_scales=k_scales,
        v_scales=v_scales, window=window, interpret=interpret)
