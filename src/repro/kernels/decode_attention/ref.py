"""Pure-jnp oracles for the decode attention kernels (dense and paged)."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import GLOBAL_WINDOW


def decode_attention_ref(q, k_cache, v_cache, index,
                         window: int = GLOBAL_WINDOW):
    """q [B,N,h]; head-major caches [B,K,S,h]; index scalar or per-slot [B]
    vector. Returns [B,N,h]."""
    B, N, h = q.shape
    K, S = k_cache.shape[1], k_cache.shape[2]
    G = N // K
    qg = (q * (1.0 / np.sqrt(h))).reshape(B, K, G, h)
    s = jnp.einsum("bkgh,bkth->bkgt", qg, k_cache).astype(jnp.float32)
    kpos = jnp.arange(S)
    idx = jnp.broadcast_to(jnp.asarray(index, jnp.int32).reshape(-1), (B,))
    valid = kpos[None] <= idx[:, None]                      # [B, S]
    if window != GLOBAL_WINDOW:
        valid &= (idx[:, None] - kpos[None]) < window
    s = jnp.where(valid[:, None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgt,bkth->bkgh", w, v_cache)
    return out.reshape(B, N, h)


def gather_pages(pages, page_table):
    """Materialize the dense per-slot view of a paged cache.
    pages [num_pages, K, page_size, h]; page_table [B, npg] ->
    [B, K, npg*page_size, h] (logical position p*page_size + o at row p,
    offset o)."""
    B, npg = page_table.shape
    K, ps, h = pages.shape[1:]
    g = pages[page_table].swapaxes(1, 2)         # [B, K, npg, ps, h]
    return g.reshape(B, K, npg * ps, h)


def gather_scales(scales, page_table, page_size: int):
    """Materialize dense per-position scales from pool scales.
    scales [num_pages, K] (per-(page, head)) or [num_pages, K, page_size]
    (per-token); page_table [B, npg] -> [B, K, npg*page_size, 1], the factor
    that dequantizes the matching ``gather_pages`` output (under "head"
    granularity every position of logical page p carries that page's
    scale; under "token" each position carries its own)."""
    B, npg = page_table.shape
    g = scales[page_table].swapaxes(1, 2)   # [B,K,npg] or [B,K,npg,ps]
    if scales.ndim == 2:
        g = jnp.repeat(g[..., None], page_size, axis=-1)
    return g.reshape(B, scales.shape[1], npg * page_size)[..., None]


def gather_dequant(k_pages, v_pages, page_table, k_scales=None,
                   v_scales=None):
    """Materialize the dense per-slot K/V views of a paged pool,
    dequantizing (``code * scale`` in fp32) when per-page scales are given.
    The single definition of the gather(+dequant) prelude shared by the
    paged fallbacks (decode and banded chunk) and the paged oracles."""
    ps = k_pages.shape[2]
    kd = gather_pages(k_pages, page_table)
    vd = gather_pages(v_pages, page_table)
    if k_scales is not None:
        kd = kd.astype(jnp.float32) * gather_scales(k_scales, page_table, ps)
        vd = vd.astype(jnp.float32) * gather_scales(v_scales, page_table, ps)
    return kd, vd


def paged_decode_attention_ref(q, k_pages, v_pages, page_table, index,
                               window: int = GLOBAL_WINDOW):
    """Oracle for the paged kernel: gather pages into the dense layout, then
    run the dense oracle. q [B,N,h]; pages [num_pages, K, page_size, h];
    page_table [B, npg]; index scalar or [B]."""
    return decode_attention_ref(q, gather_pages(k_pages, page_table),
                                gather_pages(v_pages, page_table),
                                index, window=window)


def paged_decode_attention_quant_ref(q, k_pages, v_pages, k_scales, v_scales,
                                     page_table, index,
                                     window: int = GLOBAL_WINDOW):
    """Oracle for the quantized paged kernel: gather the int8/fp8 pages AND
    their scales through the page table, dequantize to fp32 (code * scale),
    then run the dense oracle. q [B,N,h]; pages [num_pages, K, page_size, h]
    int8/fp8; scales [num_pages, K] or [num_pages, K, page_size] f32;
    page_table [B, npg]; index scalar or [B]."""
    kd, vd = gather_dequant(k_pages, v_pages, page_table, k_scales, v_scales)
    return decode_attention_ref(q, kd, vd, index, window=window)
