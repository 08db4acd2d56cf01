"""Pallas TPU kernels for the compute hot-spots, each validated in
interpret mode against a pure-jnp oracle (ref.py); the attention kernels of
the served path also compile for a v5e in tests/test_tpu_compile.py:

- flash_attention: prefill/training attention (causal + sliding window, GQA)
- decode_attention: flash-decode over the KV cache (the paper's bottleneck),
  dense per-slot layout + paged variant (page-table gather, serving engine)
- chunk_prefill: banded chunk-prefill attention over a live cache view
  (serving prefill-with-cache; dense view + paged page-table-gather variant)
- ssd: Mamba2 chunked state-space-duality scan
- moe_gmm: grouped expert MLP (capacity-based MoE hot loop)
"""
from repro.kernels import (chunk_prefill, decode_attention, flash_attention,
                           moe_gmm, ssd)

__all__ = ["chunk_prefill", "decode_attention", "flash_attention", "moe_gmm",
           "ssd"]
