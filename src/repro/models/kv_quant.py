"""Quantized KV page format: int8 / fp8 pool leaves with per-page scales.

The paged KV pool (``stacks.cache_template(paged=True)``) is the natural
quantization boundary for the paper's memory-bound action-generation phase:
decode streams the whole live KV cache per token, so storing pages at 1 byte
per element halves-to-quarters both ``cache_bytes_hwm`` and the bytes the
paged flash-decode kernel must move HBM->VMEM.

Format
------
- ``kv_dtype`` names the pool storage: ``"bf16"`` (unquantized — pages keep
  the cache dtype the caller picks; the serving engine uses the params'
  dtype), ``"int8"`` (symmetric, codes in
  [-127, 127]) or ``"fp8"`` (``float8_e4m3fn``, max 448).
- Every quantized K/V pool leaf ``[num_pages, K, page_size, h]`` gets a
  sibling float32 scale leaf whose shape is set by the pool's **scale
  granularity**: ``"head"`` stores ``[num_pages, K]`` (per-page,
  per-KV-head — one scale covers all ``page_size * h`` elements a
  (page, head) pair holds) and ``"token"`` stores
  ``[num_pages, K, page_size]`` (per-row: one scale per (page, head,
  token offset), covering ``h`` elements). A stored code ``c`` represents
  ``c * scale[...]`` under its covering scale.
- Scales are **amax-derived**: ``scale = max(|x|) / qmax`` over the covered
  elements. Under ``"head"`` granularity the scale grows monotonically on
  decode writes — a token whose amax exceeds the page's current range
  requantizes the already-stored codes under the new scale
  (``decode -> insert -> encode``, drift-free while the scale is unchanged
  because ``encode(decode(c)) == c`` exactly at a fixed scale). Under
  ``"token"`` granularity every row quantizes independently and a write
  simply *replaces* the row's codes and scale — no neighbour is ever
  requantized, so rewriting a position is exact regardless of write order.
  That rewrite-stability is what the speculative decode tick requires: its
  verify chunk re-writes positions that rejected draft rows already
  touched, and shared ``"head"`` scales would let a rejected row's amax
  leak into accepted rows on the same page (see docs/speculative.md).
- All-zero pages carry scale 0; ``encode`` guards the division so they
  produce code 0, and 0-codes dequantize to exactly 0 (unwritten rows of a
  partially-filled page never contribute garbage).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

KV_DTYPES = ("bf16", "int8", "fp8")
SCALE_GRANULARITIES = ("head", "token")

# smallest representable scale guard: avoids 0/0 on all-zero pages while
# keeping every real amax (>= ~1e-30 is far below KV magnitudes) intact
EPS = 1e-30


def quant_dtype(kv_dtype: str) -> Optional[jnp.dtype]:
    """Pool storage dtype for a ``kv_dtype`` name; None means unquantized."""
    if kv_dtype not in KV_DTYPES:
        raise ValueError(f"kv_dtype must be one of {KV_DTYPES}, "
                         f"got {kv_dtype!r}")
    if kv_dtype == "int8":
        return jnp.int8
    if kv_dtype == "fp8":
        return jnp.float8_e4m3fn
    return None


def is_quantized(dtype) -> bool:
    """Whether a concrete array dtype is a quantized pool storage dtype."""
    return jnp.dtype(dtype) in (jnp.dtype(jnp.int8),
                                jnp.dtype(jnp.float8_e4m3fn))


def qmax(dtype) -> float:
    """Largest code magnitude representable by a storage dtype (symmetric
    range: int8 uses [-127, 127], fp8 e4m3fn saturates at 448)."""
    if jnp.dtype(dtype) == jnp.dtype(jnp.int8):
        return 127.0
    if jnp.dtype(dtype) == jnp.dtype(jnp.float8_e4m3fn):
        return float(jnp.finfo(jnp.float8_e4m3fn).max)
    raise ValueError(f"not a quantized KV dtype: {dtype}")


def amax_scale(rows, dtype, granularity: str = "head"):
    """Amax scale for head-major page rows ``[..., K, ps, h]``: ``"head"``
    reduces the token and head-dim axes -> ``[..., K]``; ``"token"``
    reduces only the head-dim axis -> ``[..., K, ps]`` (one scale per
    row)."""
    axes = (-2, -1) if granularity == "head" else (-1,)
    a = jnp.max(jnp.abs(rows.astype(jnp.float32)), axis=axes)
    return a / qmax(dtype)


def encode(x, scale, dtype):
    """Quantize fp values ``x`` to codes under ``scale`` (broadcastable).
    int8 rounds-to-nearest and clips to [-127, 127]; fp8 casts (saturating).
    ``scale == 0`` (all-zero page) yields code 0."""
    y = x.astype(jnp.float32) / jnp.maximum(scale, EPS)
    if jnp.dtype(dtype) == jnp.dtype(jnp.int8):
        return jnp.clip(jnp.round(y), -127.0, 127.0).astype(jnp.int8)
    return y.astype(dtype)


def decode(codes, scale):
    """Dequantize codes back to float32 under ``scale`` (broadcastable)."""
    return codes.astype(jnp.float32) * scale


def quantize_page_rows(rows, dtype, granularity: str = "head"):
    """Quantize head-major page rows ``[..., K, ps, h]`` in one shot.
    Returns ``(codes, scales)`` with scales ``[..., K]`` (``"head"``) or
    ``[..., K, ps]`` (``"token"``) — the layouts the pool's sibling scale
    leaves store and the paged kernels read."""
    scales = amax_scale(rows, dtype, granularity)
    bcast = (scales[..., None, None] if granularity == "head"
             else scales[..., None])
    return encode(rows, bcast, dtype), scales


def fake_quantize_tree(params, kv_dtype: str):
    """Round-trip a parameter tree through ``kv_dtype`` codes — the
    self-speculative *weight-quantized draft*: the draft model runs in the
    original dtype but with weights carrying int8/fp8 precision, standing in
    for a deployment where the draft pass streams 1-byte weights from HBM.

    Per-output-channel symmetric scales (amax over every axis but the last)
    keep greedy argmax agreement with the full-precision model high — the
    property the speculative acceptance rate leans on. Only matrices
    (``ndim >= 2``) quantize; vectors (norm gains, biases) pass through
    unchanged, as do integer leaves. Returns a new tree with the original
    dtypes (fake quantization changes values, never types)."""
    dtype = quant_dtype(kv_dtype)
    if dtype is None:
        return params

    def leaf(x):
        if x.ndim < 2 or not jnp.issubdtype(x.dtype, jnp.floating):
            return x
        axes = tuple(range(x.ndim - 1))
        scale = (jnp.max(jnp.abs(x.astype(jnp.float32)), axis=axes,
                         keepdims=True) / qmax(dtype))
        return decode(encode(x, scale, dtype), scale).astype(x.dtype)

    return jax.tree_util.tree_map(leaf, params)
