"""Operations and bytes the algorithm needs, from a configuration's shapes.

Counts are of the model's mathematics (a multiply-add is two operations)
and of the bytes a stage has to move through HBM at least once, not of what
a kernel happens to do: padding rows, masked blocks and recomputation do
not count. ``c`` is a configuration file's dict; bytes are bfloat16 (2) for
weights, activations and the KV cache, the served dtype of every cell.
"""
from __future__ import annotations

BF16 = 2


def _dims(c: dict):
    return (c["d_model"], c["num_heads"], c["num_kv_heads"], c["head_dim"],
            c["d_ff"], c["vocab_size"], c["num_layers"])


def layer_matmul_params(c: dict) -> int:
    """Weights one decoder layer multiplies by (projections and MLP)."""
    d, n, k, h, f, _, _ = _dims(c)
    return d * n * h + 2 * d * k * h + n * h * d + 3 * d * f


def decoder_weight_bytes(c: dict) -> int:
    """Bytes of every weight a decode step reads: all layers (matrices,
    biases, norm gains), the final norm and the output head."""
    d, n, k, h, f, V, L = _dims(c)
    per_layer = layer_matmul_params(c) + 2 * d
    if c["qkv_bias"]:
        per_layer += (n + 2 * k) * h
    return BF16 * (L * per_layer + d + V * d)


def attn_flops(c: dict, first: int, count: int) -> int:
    """Causal attention of ``count`` query rows at positions first.. over
    every key up to their own position, all layers: QK^T and PV."""
    _, n, _, h, _, _, L = _dims(c)
    keys = count * first + count * (count + 1) // 2
    return 4 * n * h * keys * L


def kv_bytes(c: dict, positions: int) -> int:
    """Bytes of K and V for ``positions`` cached positions, all layers."""
    _, _, k, h, _, _, L = _dims(c)
    return 2 * positions * k * h * BF16 * L


def decode_flops(c: dict, pos: int) -> int:
    """One decode step of one sequence whose new token sits at ``pos``."""
    d, _, _, _, _, V, L = _dims(c)
    return 2 * L * layer_matmul_params(c) + 2 * d * V + attn_flops(c, pos, 1)


def paged_decode_attn_bytes(c: dict, pos: int, page_size: int) -> int:
    """KV bytes the paged decode kernel reads for a token at ``pos``: every
    page that holds a live position 0..pos, whole."""
    pages = pos // page_size + 1
    return kv_bytes(c, pages * page_size)


def decode_attn_flops(c: dict, pos: int) -> int:
    return attn_flops(c, pos, 1)


def prefill_flops(c: dict, positions: int) -> int:
    """Decoder prefill of a whole prompt of ``positions`` (vision prefix
    included), with the output head at its last position only."""
    d, _, _, _, _, V, L = _dims(c)
    return (2 * L * layer_matmul_params(c) * positions + 2 * d * V
            + attn_flops(c, 0, positions))


def chunk_attn_flops(c: dict, start: int, rows: int) -> int:
    return attn_flops(c, start, rows)


def chunk_attn_bytes(c: dict, start: int, rows: int) -> int:
    """The chunk kernel's least traffic: the chunk's queries in and outputs
    out, and K/V of every position up to the chunk's last."""
    _, n, _, h, _, _, L = _dims(c)
    return 2 * rows * n * h * BF16 * L + kv_bytes(c, start + rows)


def vision_flops(c: dict) -> int:
    """The tower over one frame: input projection, layers, projector."""
    v = c["vision"]
    T, e, dv, f, Lv = (v["num_tokens"], v["embed_dim"], v["d_model"],
                       v["d_ff"], v["num_layers"])
    per_layer = 2 * T * (4 * dv * dv + 2 * dv * f) + 4 * T * T * dv
    return 2 * T * e * dv + Lv * per_layer + 2 * T * dv * c["d_model"]


def control_step_flops(c: dict, prompt: int, new_tokens: int) -> int:
    """One control step: the tower over a new frame, prefill of the whole
    prompt (frame + instruction; its last position yields token 0), then
    decode of tokens 1..new_tokens-1."""
    dec = sum(decode_flops(c, prompt + j) for j in range(new_tokens - 1))
    return vision_flops(c) + prefill_flops(c, prompt) + dec
