"""Pure-jnp oracles for the chunk-prefill kernels (dense view and paged).

The oracle is the *mathematical* definition — one dense masked softmax over
the whole cache view with absolute positions — so kernel-vs-oracle tests
check the banded online softmax against an independent formulation rather
than against another copy of the same blockwise arithmetic."""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import GLOBAL_WINDOW
from repro.kernels.decode_attention.ref import gather_dequant


def chunk_prefill_ref(q, k_cache, v_cache, index,
                      window: int = GLOBAL_WINDOW):
    """q [B,S,N,h]; head-major cache view [B,K,L,h]; index scalar or
    per-slot [B] vector of chunk start positions (query row r of slot b
    sits at absolute position index[b] + r). Returns [B,S,N,h]."""
    B, S, N, h = q.shape
    K, L = k_cache.shape[1], k_cache.shape[2]
    G = N // K
    qg = (q * (1.0 / np.sqrt(h))).reshape(B, S, K, G, h)
    s = jnp.einsum("bskgh,bkth->bkgst", qg, k_cache).astype(jnp.float32)
    idx = jnp.broadcast_to(jnp.asarray(index, jnp.int32).reshape(-1), (B,))
    q_pos = idx[:, None] + jnp.arange(S)                    # [B, S]
    kpos = jnp.arange(L)
    mask = kpos[None, None] <= q_pos[..., None]             # [B, S, L]
    if window != GLOBAL_WINDOW:
        mask &= (q_pos[..., None] - kpos[None, None]) < window
    s = jnp.where(mask[:, None, None], s, -1e30)
    w = jax.nn.softmax(s, axis=-1).astype(q.dtype)
    out = jnp.einsum("bkgst,bkth->bkgsh", w, v_cache)
    return jnp.moveaxis(out, (1, 2), (2, 3)).reshape(B, S, N, h)


def paged_chunk_prefill_ref(q, k_pages, v_pages, page_table, index,
                            window: int = GLOBAL_WINDOW,
                            k_scales=None, v_scales=None):
    """Oracle for the paged kernel: gather the slot's pages (and, for
    quantized pools, their scales) into the dense view, dequantize, then
    run the dense oracle. q [B,S,N,h]; pages [num_pages, K, page_size, h];
    page_table [B, npg]; index scalar or [B]; scales [num_pages, K] or
    [num_pages, K, page_size] f32, or None."""
    kd, vd = gather_dequant(k_pages, v_pages, page_table, k_scales, v_scales)
    return chunk_prefill_ref(q, kd, vd, index, window=window)
