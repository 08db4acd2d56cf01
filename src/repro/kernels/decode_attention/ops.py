"""jit'd public wrappers for the decode attention kernels (dense + paged)."""
from __future__ import annotations

import functools

import jax

from repro.configs.base import GLOBAL_WINDOW
from repro.kernels.decode_attention.decode_attention import (
    decode_attention_kernel)
from repro.kernels.decode_attention.paged import (
    paged_decode_attention_kernel)


@functools.partial(jax.jit, static_argnames=("window", "bk", "interpret"))
def decode_attention(q, k_cache, v_cache, index, *,
                     window: int = GLOBAL_WINDOW, bk: int = 512,
                     interpret: bool = False):
    """Single-token flash-decode. q [B,N,h]; caches [B,K,S,h]; index int32
    position of the token being decoded — scalar or per-slot [B] vector
    (continuous batching). S that does not divide by bk is handled by a
    ceil-divided grid whose out-of-bounds tail lanes are masked in-kernel
    (no padded copy of the cache is materialized)."""
    return decode_attention_kernel(q, k_cache, v_cache, index, window=window,
                                   bk=bk, interpret=interpret)


@functools.partial(jax.jit, static_argnames=("window", "interpret"))
def paged_decode_attention(q, k_pages, v_pages, page_table, index, *,
                           k_scales=None, v_scales=None,
                           window: int = GLOBAL_WINDOW,
                           interpret: bool = False):
    """Single-token flash-decode against a paged KV pool. q [B,N,h]; pages
    [num_pages, K, page_size, h]; page_table [B, npg] int32; index scalar or
    per-slot [B] vector of current positions. For quantized (int8/fp8)
    pools pass the sibling scales ``k_scales``/``v_scales`` ([num_pages, K]
    or [num_pages, K, page_size] f32) — the kernel copies each page's scale
    rows beside its codes and dequantizes inside the VMEM tile."""
    return paged_decode_attention_kernel(q, k_pages, v_pages, page_table,
                                         index, k_scales=k_scales,
                                         v_scales=v_scales, window=window,
                                         interpret=interpret)
