"""Banded chunk-prefill Pallas TPU kernel (dense cache view).

Prefill-with-cache attention for one chunk of ``S`` queries written at
positions ``index .. index+S-1`` against a live head-major KV cache view
``[B, K, L, h]``. The layout follows ``decode_attention``: the per-slot
start positions arrive as a scalar-prefetch operand, the KV-block grid
dimension is innermost and
sequential, and online-softmax state lives in VMEM scratch across it.
Blocks with no unmasked lane for *any* chunk row — past the chunk's last
position, or entirely older than its sliding window — are skipped twice
over: the BlockSpec index map remaps them to block 0 (repeated index-map
outputs elide the HBM->VMEM DMA) and ``pl.when`` skips their compute. Key-
axis work therefore scales with the live prefix ``[0, index + S)``, not
with the cache's allocated ``max_seq`` — the banded-chunk-attention item
the serving stack's prefill paths route through (see ``layers.attention``
and docs/scheduler.md).

Bit-stability contract (shared with the jnp fallback
``layers.attention_chunk_banded``): the online-softmax update for a block
that is fully masked for a given query row is an *exact* no-op
(``corr == exp(0) == 1``, ``p == 0``), so the result for any query depends
only on the absolute key-block partition up to its own position — never on
how the prompt was chunked or how much trailing cache view the caller
passed in.

The online-softmax body is ``decode_attention.flash_block``, shared with
the decode kernels and the paged variant (``paged.py``): a grid cell's
query rows are the chunk's S positions of one head (``row_step=1``), and
the kernels differ only in how a KV block is located (contiguous cache rows
vs a scalar-prefetched page-table gather).
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


def _kernel(idx_ref, q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            bk: int, nk: int, window: int):
    flash_block(idx_ref[pl.program_id(0)], pl.program_id(2),
                q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr,
                bk=bk, nk=nk, window=window, row_step=1)


def chunk_prefill_attention_kernel(q, k_cache, v_cache, index, *,
                                   window: int = GLOBAL_WINDOW, bk: int = 128,
                                   interpret: bool = False):
    """q [B,S,N,h] (one prefill chunk, already written to the cache);
    k/v cache view [B,K,L,h] (head-major; the caller may pre-slice L to the
    banded live bound — see layers.attention); index: int32 scalar or
    per-slot [B] vector of chunk start positions. Returns [B,S,N,h].

    L need not divide by ``bk``: the grid covers ceil(L/bk) blocks and the
    tail block's out-of-bounds lanes carry key positions past every query,
    so the causal mask (and the V zeroing) silently discards them."""
    B, S, N, h = q.shape
    K, L = k_cache.shape[1], k_cache.shape[2]
    G = N // K
    bk = min(bk, L)
    nk = pl.cdiv(L, bk)
    qt = q.swapaxes(1, 2)                                 # [B, N, S, h]
    idx = jnp.broadcast_to(jnp.asarray(index, jnp.int32).reshape(-1), (B,))

    def q_map(b, n, ik, idx_ref):
        return b, n, 0, 0

    def kv_map(b, n, ik, idx_ref):
        # remap fully-dead blocks to block 0 so their DMA is elided
        # (repeated index-map outputs are not re-fetched); compute is
        # pl.when-skipped. GQA: query head n reads KV head n // G.
        i = idx_ref[b]
        live = block_live(i, i + S - 1, ik * bk, bk, window)
        return b, n // G, jnp.where(live, ik, 0), 0

    kernel = functools.partial(_kernel, bk=bk, nk=nk, window=window)
    out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(B, N, nk),
            in_specs=[pl.BlockSpec((1, 1, S, h), q_map),
                      pl.BlockSpec((1, 1, bk, h), kv_map),
                      pl.BlockSpec((1, 1, bk, h), kv_map)],
            out_specs=pl.BlockSpec((1, 1, S, h), q_map),
            scratch_shapes=softmax_scratch(S, h),
        ),
        out_shape=jax.ShapeDtypeStruct(qt.shape, q.dtype),
        interpret=interpret,
    )(idx, qt, k_cache, v_cache)
    return out.swapaxes(1, 2)
