"""Flash attention Pallas TPU kernel (prefill / training).

Tiling: grid (B, N, Sq/bq, Sk/bk) with the KV-block dimension innermost and
sequential; VMEM scratch carries the online-softmax state (m, l, acc) across
KV blocks. Causal and sliding-window masks are applied per block, and blocks
that are *entirely* masked are skipped with pl.when — so the MXU only sees
the ~triangular (or banded) set of block pairs, matching the useful-FLOP
count rather than the naive S^2.

GQA is folded into the index maps: query head n reads KV head n // (N/K).
The wrapper moves heads ahead of positions (``[B, N, S, h]``), so every
block is a ``(rows, h)`` tile on the TPU's native (sublane, lane) tiling.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.configs.base import GLOBAL_WINDOW
from repro.kernels.decode_attention.decode_attention import softmax_scratch

NEG_INF = -1e30


def _kernel(q_ref, k_ref, v_ref, o_ref, m_scr, l_scr, acc_scr, *,
            bq: int, bk: int, nk: int, window: int, causal: bool):
    iq = pl.program_id(2)
    ik = pl.program_id(3)

    @pl.when(ik == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    q_start = iq * bq
    k_start = ik * bk

    # block-level skip decision (static per grid cell shape, dynamic values)
    run = True
    if causal:
        run = (k_start <= q_start + bq - 1)
    if window != GLOBAL_WINDOW:
        run = jnp.logical_and(run, (q_start - (k_start + bk - 1)) < window)

    @pl.when(run)
    def _compute():
        q = q_ref[0, 0].astype(jnp.float32)            # [bq, h]
        k = k_ref[0, 0].astype(jnp.float32)            # [bk, h]
        v = v_ref[0, 0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)
        s *= 1.0 / np.sqrt(q.shape[-1])
        qpos = q_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
        kpos = k_start + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
        mask = jnp.ones((bq, bk), jnp.bool_)
        if causal:
            mask &= qpos >= kpos
        if window != GLOBAL_WINDOW:
            mask &= (qpos - kpos) < window
        s = jnp.where(mask, s, NEG_INF)
        m_prev = m_scr[...]                            # [bq, 1]
        m_new = jnp.maximum(m_prev, s.max(axis=1, keepdims=True))
        p = jnp.exp(s - m_new) * mask                  # kill fully-masked rows
        corr = jnp.exp(m_prev - m_new)
        l_scr[...] = l_scr[...] * corr + p.sum(axis=1, keepdims=True)
        acc_scr[...] = acc_scr[...] * corr + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())), preferred_element_type=jnp.float32)
        m_scr[...] = m_new

    @pl.when(ik == nk - 1)
    def _finalize():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0, 0] = (acc_scr[...] / l).astype(o_ref.dtype)


def flash_attention_kernel(q, k, v, *, window: int = GLOBAL_WINDOW,
                           causal: bool = True, bq: int = 128, bk: int = 128,
                           interpret: bool = False):
    """q [B,S,N,h]; k,v [B,Sk,K,h] -> [B,S,N,h]."""
    B, S, N, h = q.shape
    Sk, K = k.shape[1], k.shape[2]
    G = N // K
    bq, bk = min(bq, S), min(bk, Sk)
    nq, nk = S // bq, Sk // bk
    grid = (B, N, nq, nk)

    kernel = functools.partial(_kernel, bq=bq, bk=bk, nk=nk,
                               window=window, causal=causal)
    out = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, bq, h), lambda b, n, iq, ik: (b, n, iq, 0)),
            pl.BlockSpec((1, 1, bk, h), lambda b, n, iq, ik: (b, n // G, ik, 0)),
            pl.BlockSpec((1, 1, bk, h), lambda b, n, iq, ik: (b, n // G, ik, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, bq, h), lambda b, n, iq, ik: (b, n, iq, 0)),
        out_shape=jax.ShapeDtypeStruct((B, N, S, h), q.dtype),
        scratch_shapes=softmax_scratch(bq, h),
        interpret=interpret,
    )(q.swapaxes(1, 2), k.swapaxes(1, 2), v.swapaxes(1, 2))
    return out.swapaxes(1, 2)
