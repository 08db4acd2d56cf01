"""Unified attention dispatch: routing decisions, the banded chunk core's
bit-stability contract, and chunk-prefill kernel equality at the layer
level. (Kernel-vs-oracle shape sweeps live in test_kernels.py.)"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.base import GLOBAL_WINDOW
from repro.models import layers as L
from repro.models.layers import ModelOptions

KEY = jax.random.PRNGKey(7)


# ---------------------------------------------------------------------------
# routing (pure decisions, the docs/architecture.md dispatch table)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode,layout,pallas,expect", [
    ("decode", "dense", False, "decode_dense"),
    ("decode", "dense", True, "decode_flash"),
    ("decode", "paged", False, "decode_paged_gather"),
    ("decode", "paged", True, "decode_paged_flash"),
    ("decode", "ring", False, "decode_ring"),
    ("decode", "ring", True, "decode_ring"),
    ("chunk", "dense", False, "chunk_banded"),
    ("chunk", "dense", True, "chunk_flash"),
    ("chunk", "paged", False, "chunk_banded_gather"),
    ("chunk", "paged", True, "chunk_paged_flash"),
])
def test_route_cache_modes(mode, layout, pallas, expect):
    opts = ModelOptions(use_pallas=pallas)
    route = L.attention_route(mode, layout, S=16, Skv=256,
                              window=GLOBAL_WINDOW, opts=opts)
    assert route == expect


@pytest.mark.parametrize("tpu", [False, True])
def test_route_platform_default(monkeypatch, tpu):
    """Unpinned options: the served modes (decode, chunk) follow the
    platform; fresh flash, the MoE grouped matmul and the SSD scan stay on
    jnp even on the TPU (no backward rule, a refused SSD block)."""
    monkeypatch.setattr(L, "on_tpu", lambda: tpu)
    opts = ModelOptions()
    route = lambda mode, layout, S: L.attention_route(
        mode, layout, S=S, Skv=S if mode == "fresh" else 256,
        window=GLOBAL_WINDOW, opts=opts)
    assert route("decode", "paged", 1) == ("decode_paged_flash" if tpu
                                           else "decode_paged_gather")
    assert route("chunk", "paged", 64) == ("chunk_paged_flash" if tpu
                                           else "chunk_banded_gather")
    assert route("fresh", "none", 256) == "fresh_dense"
    assert L.pallas_on(opts) is tpu
    assert not L.pallas_pinned(opts)


def test_route_fresh_shape_gates():
    """Fresh mode keeps the flash kernel's S % 128 == 0 / self-attention
    tiling gate; chunk mode has no such gate (the generalization to padded
    bands)."""
    opts = ModelOptions(use_pallas=True)
    assert L.attention_route("fresh", "none", S=256, Skv=256,
                             window=GLOBAL_WINDOW, opts=opts) == "fresh_flash"
    # not a multiple of 128 -> dense fallback even under use_pallas
    assert L.attention_route("fresh", "none", S=100, Skv=100,
                             window=GLOBAL_WINDOW, opts=opts) == "fresh_dense"
    # cross-attention shapes (Sq != Skv) never take the flash kernel
    assert L.attention_route("cross", "none", S=128, Skv=128,
                             window=GLOBAL_WINDOW, opts=opts,
                             causal=False) == "fresh_dense"
    # causal but not self-attention (Sq != Skv): the flash tiling gate the
    # old _core enforced via q.shape[1] == S must still hold
    assert L.attention_route("fresh", "none", S=128, Skv=256,
                             window=GLOBAL_WINDOW, opts=opts) != "fresh_flash"
    # chunk mode routes to the chunk kernel at any chunk length
    assert L.attention_route("chunk", "dense", S=5, Skv=256,
                             window=GLOBAL_WINDOW, opts=opts) == "chunk_flash"


def test_route_fresh_core_selection():
    """Large fresh shapes pick banded/flash-ref exactly as the old _core
    if-ladder did."""
    opts = ModelOptions(use_pallas=False, dense_attn_threshold=256,
                        attn_chunk=512)
    assert L.attention_route("fresh", "none", S=128, Skv=128,
                             window=GLOBAL_WINDOW, opts=opts) == "fresh_dense"
    assert L.attention_route("fresh", "none", S=1024, Skv=1024,
                             window=GLOBAL_WINDOW,
                             opts=opts) == "fresh_flash_ref"
    assert L.attention_route("fresh", "none", S=1024, Skv=1024, window=64,
                             opts=opts) == "fresh_banded"


def test_run_core_rejects_unknown_route():
    q = jnp.zeros((1, 1, 2, 4))
    with pytest.raises(ValueError, match="unknown attention route"):
        L.run_attention_core("nope", q, q, q, opts=ModelOptions(), window=0)


# ---------------------------------------------------------------------------
# banded chunk core: bit-stability contract
# ---------------------------------------------------------------------------

def _qkv(B, S, N, K, h, L_):
    ks = jax.random.split(KEY, 3)
    return (jax.random.normal(ks[0], (B, S, N, h)),
            jax.random.normal(ks[1], (B, K, L_, h)),
            jax.random.normal(ks[2], (B, K, L_, h)))


def test_banded_chunk_matches_dense_softmax_oracle():
    from repro.kernels.chunk_prefill.ref import chunk_prefill_ref
    q, kc, vc = _qkv(2, 9, 4, 2, 16, 80)
    idx = jnp.asarray([11, 37], jnp.int32)
    for w in (GLOBAL_WINDOW, 20):
        out = L.attention_chunk_banded(q, kc, vc, idx, w, 32)
        exp = chunk_prefill_ref(q, kc, vc, idx, window=w)
        np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                                   atol=2e-5, rtol=2e-5)


def test_banded_chunk_view_length_invariance():
    """Trailing fully-masked key blocks are exact no-ops: any cache view
    covering the live prefix gives bit-identical results — the structural
    fact the scheduler's bit-equality gates stand on."""
    q, kc, vc = _qkv(1, 6, 4, 2, 16, 96)
    idx = jnp.asarray(20, jnp.int32)              # live prefix = 26
    ref = L.attention_chunk_banded(q, kc, vc, idx, GLOBAL_WINDOW, 16)
    for view in (32, 48, 96):                     # all cover live=26
        out = L.attention_chunk_banded(q, kc[:, :, :view], vc[:, :, :view], idx,
                                       GLOBAL_WINDOW, 16)
        assert jnp.array_equal(out, ref), f"view {view} changed the bits"


def test_banded_chunk_chunking_invariance():
    """Splitting a prompt into chunks reproduces the monolithic result
    bit-for-bit (same absolute key-block partition, per-row no-ops)."""
    S = 13
    q, kc, vc = _qkv(1, S, 4, 2, 16, 64)
    base = jnp.asarray(7, jnp.int32)
    mono = L.attention_chunk_banded(q, kc, vc, base, GLOBAL_WINDOW, 16)
    for split in (1, 4, 9):
        a = L.attention_chunk_banded(q[:, :split], kc, vc, base,
                                     GLOBAL_WINDOW, 16)
        b = L.attention_chunk_banded(q[:, split:], kc, vc, base + split,
                                     GLOBAL_WINDOW, 16)
        assert jnp.array_equal(jnp.concatenate([a, b], 1), mono), \
            f"split at {split} changed the bits"


def test_banded_chunk_garbage_past_live_is_masked():
    """Lanes past a query's position may hold stale garbage (recycled cache
    rows, padded pages) — they must contribute exact zeros."""
    q, kc, vc = _qkv(1, 4, 4, 2, 16, 64)
    idx = jnp.asarray(10, jnp.int32)
    ref = L.attention_chunk_banded(q, kc, vc, idx, GLOBAL_WINDOW, 16)
    poisoned_k = kc.at[:, 14:].set(1e6)           # past live prefix (14)
    poisoned_v = vc.at[:, 14:].set(-1e6)
    out = L.attention_chunk_banded(q, poisoned_k, poisoned_v, idx,
                                   GLOBAL_WINDOW, 16)
    assert jnp.array_equal(out, ref)


def test_band_len():
    assert L.band_len(1, 32, 256) == 32
    assert L.band_len(32, 32, 256) == 32
    assert L.band_len(33, 32, 256) == 64
    assert L.band_len(300, 32, 256) == 256
    assert L.band_len(40, 32, 48) == 48           # clamp beats rounding


# ---------------------------------------------------------------------------
# layer-level: the routed attention() agrees across cores and live bounds
# ---------------------------------------------------------------------------

def _layer_params(D, N, K, h, key):
    ks = jax.random.split(key, 4)
    s = 0.2
    return {"wq": s * jax.random.normal(ks[0], (D, N, h)),
            "wk": s * jax.random.normal(ks[1], (D, K, h)),
            "wv": s * jax.random.normal(ks[2], (D, K, h)),
            "wo": s * jax.random.normal(ks[3], (N, h, D))}


def _mini_cfg():
    from repro.configs import get_config
    return get_config("smollm-135m").reduced()


def test_attention_live_len_bound_is_bitwise_noop():
    """attention(live_len=...) slices the banded view; any bound covering
    the live prefix must give bit-identical output AND identical cache."""
    cfg = _mini_cfg()
    D, N, K, h = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    p = _layer_params(D, N, K, h, KEY)
    opts = ModelOptions(remat=False)
    B, S, smax, start = 1, 8, 64, 10
    x = jax.random.normal(KEY, (B, S, D))
    cache = (jax.random.normal(KEY, (B, K, smax, h)),
             jax.random.normal(KEY, (B, K, smax, h)))
    positions = jnp.broadcast_to(start + jnp.arange(S), (B, S))
    outs = []
    for live in (start + S, 48, None):
        o, nc = L.attention(p, x, cfg, opts, GLOBAL_WINDOW, positions,
                            cache=cache, cache_index=jnp.asarray(start),
                            live_len=live)
        outs.append((o, nc))
    for o, nc in outs[1:]:
        assert jnp.array_equal(o, outs[0][0])
        for a, b in zip(nc, outs[0][1]):
            assert jnp.array_equal(a, b)


def test_attention_chunk_kernel_matches_banded_fallback():
    """use_pallas routes chunk mode through the chunk-prefill kernel; it
    must agree with the banded fallback to fp32-accumulate precision, on
    both layouts."""
    cfg = _mini_cfg()
    D, N, K, h = cfg.d_model, cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    p = _layer_params(D, N, K, h, KEY)
    ref_opts = ModelOptions(remat=False)
    ker_opts = ModelOptions(remat=False, use_pallas=True)
    B, S, smax, start, ps = 1, 8, 64, 10, 8
    x = jax.random.normal(KEY, (B, S, D))
    cache = (jax.random.normal(KEY, (B, K, smax, h)),
             jax.random.normal(KEY, (B, K, smax, h)))
    positions = jnp.broadcast_to(start + jnp.arange(S), (B, S))
    o_ref, _ = L.attention(p, x, cfg, ref_opts, GLOBAL_WINDOW, positions,
                           cache=cache, cache_index=jnp.asarray(start))
    o_ker, _ = L.attention(p, x, cfg, ker_opts, GLOBAL_WINDOW, positions,
                           cache=cache, cache_index=jnp.asarray(start))
    np.testing.assert_allclose(np.asarray(o_ker), np.asarray(o_ref),
                               atol=2e-5, rtol=2e-5)
    # paged: identity page table over the same contiguous rows
    npg = smax // ps
    pages = tuple(c[0].reshape(K, npg, ps, h).swapaxes(0, 1) for c in cache)
    pt = jnp.arange(npg, dtype=jnp.int32)[None]
    o_pref, _ = L.attention(p, x, cfg, ref_opts, GLOBAL_WINDOW, positions,
                            cache=pages, cache_index=jnp.asarray(start),
                            page_table=pt)
    o_pker, _ = L.attention(p, x, cfg, ker_opts, GLOBAL_WINDOW, positions,
                            cache=pages, cache_index=jnp.asarray(start),
                            page_table=pt)
    np.testing.assert_allclose(np.asarray(o_pker), np.asarray(o_pref),
                               atol=2e-5, rtol=2e-5)
    # and the unquantized paged fallback is bit-identical to the dense one
    assert jnp.array_equal(o_pref, o_ref)
