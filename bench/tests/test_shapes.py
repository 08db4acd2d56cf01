"""Operations and bytes from shapes, against counts made by hand for the
molmoact-7b (16-layer cut) configuration and InternVL2-1B's decoder; the
peak table knows the chip by its ``device_kind`` and refuses any other."""
import pytest

from harness import shapes, spec

MOLMO = spec.config("molmoact-7b-16L")
# InternVL2-1B's decoder (Qwen2-0.5B, arXiv:2404.16821): a second set of
# shapes, with tied embeddings and head_dim 64
INTERN = dict(num_layers=24, d_model=896, num_heads=14, num_kv_heads=2,
              head_dim=64, d_ff=4864, vocab_size=151655, qkv_bias=True,
              tie_embeddings=True)


def test_molmoact_layer_and_weights():
    # q 3584x28x128, k and v 3584x4x128, o 28x128x3584, MLP 3 x 3584x18944
    per_layer = (3584 * 3584 + 2 * 3584 * 512 + 3584 * 3584
                 + 3 * 3584 * 18944)
    assert shapes.layer_matmul_params(MOLMO) == per_layer == 233_046_016
    # + q/k/v biases (28+8) x 128 and two norm gains of 3584, 16 layers,
    # the final norm and the 152064 x 3584 head, 2 bytes each
    params = 16 * (per_layer + 36 * 128 + 2 * 3584) + 3584 + 152064 * 3584
    assert shapes.decoder_weight_bytes(MOLMO) == 2 * params == 8_547_851_264


def test_internvl_layer_and_weights():
    per_layer = 896 * 896 * 2 + 2 * 896 * 128 + 3 * 896 * 4864
    assert shapes.layer_matmul_params(INTERN) == per_layer == 14_909_440
    params = 24 * (per_layer + 18 * 64 + 2 * 896) + 896 + 151655 * 896
    assert shapes.decoder_weight_bytes(INTERN) == 2 * params == 987_561_984


def test_kv_bytes_per_position():
    assert shapes.kv_bytes(MOLMO, 1) == 2 * 4 * 128 * 2 * 16 == 32768
    assert shapes.kv_bytes(INTERN, 1) == 2 * 2 * 64 * 2 * 24 == 12288
    # position 639 reads pages 0..19 of 32 positions, whole
    assert shapes.paged_decode_attn_bytes(MOLMO, 639, 32) == 640 * 32768
    assert shapes.paged_decode_attn_bytes(MOLMO, 640, 32) == 672 * 32768


def test_decode_and_attention_flops():
    p = 700
    attn = 4 * 28 * 128 * (p + 1) * 16
    assert shapes.decode_attn_flops(MOLMO, p) == attn
    assert shapes.decode_flops(MOLMO, p) == (2 * 16 * 233_046_016
                                              + 2 * 3584 * 152064 + attn)
    # causal rows 128..255 attend 129..256 keys each
    keys = sum(q + 1 for q in range(128, 256))
    assert shapes.chunk_attn_flops(INTERN, 128, 128) == \
        4 * 14 * 64 * keys * 24
    assert shapes.chunk_attn_bytes(INTERN, 128, 128) == (
        2 * 128 * 14 * 64 * 2 * 24 + 256 * 12288)


def test_vision_and_prefill_flops():
    T, e, d, f = 576, 1024, 1024, 4096
    layer = 2 * T * (4 * d * d + 2 * d * f) + 4 * T * T * d
    vis = 2 * T * e * d + 24 * layer + 2 * T * d * 3584
    assert shapes.vision_flops(MOLMO) == vis == 385_943_076_864
    P = 640
    pre = (2 * 16 * 233_046_016 * P + 2 * 3584 * 152064
           + 4 * 28 * 128 * (P * (P + 1) // 2) * 16)
    assert shapes.prefill_flops(MOLMO, P) == pre
    step = vis + pre + sum(shapes.decode_flops(MOLMO, P + j)
                           for j in range(191))
    assert shapes.control_step_flops(MOLMO, P, 192) == step
    assert 6.5e12 < step < 7.0e12


def test_peaks_by_device_kind():
    pk = spec.peaks("TPU v5 lite")
    assert pk["bf16_flops_per_s"] == 197e12
    assert pk["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="not in"):
        spec.peaks("TPU v9 imaginary")
