"""Per-kernel validation: sweep shapes/dtypes, assert_allclose against the
pure-jnp oracle (interpret=True executes the kernel body on CPU)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.chunk_prefill import (chunk_prefill_attention,
                                         paged_chunk_prefill_attention)
from repro.kernels.chunk_prefill import ref as cref
from repro.kernels.decode_attention import (decode_attention,
                                            paged_decode_attention)
from repro.kernels.decode_attention import ref as dref
from repro.kernels.flash_attention import flash_attention
from repro.kernels.flash_attention import ref as fref
from repro.kernels.moe_gmm import grouped_mlp
from repro.kernels.moe_gmm import ref as gref
from repro.kernels.ssd import ssd
from repro.kernels.ssd import ref as sref

KEY = jax.random.PRNGKey(42)


def _tol(dtype):
    return dict(atol=2e-2, rtol=2e-2) if dtype == jnp.bfloat16 \
        else dict(atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("B,S,N,K,h", [
    (2, 256, 4, 2, 64), (1, 256, 8, 8, 64), (2, 128, 6, 2, 32),
    (1, 512, 4, 1, 128), (2, 256, 16, 4, 64),
])
@pytest.mark.parametrize("window", [0, 128])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_flash_attention(B, S, N, K, h, window, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, N, h), dtype)
    k = jax.random.normal(ks[1], (B, S, K, h), dtype)
    v = jax.random.normal(ks[2], (B, S, K, h), dtype)
    out = flash_attention(q, k, v, window=window, interpret=True)
    exp = fref.attention_ref(q, k, v, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


@pytest.mark.parametrize("B,S,N,K,h,idx", [
    (2, 1024, 8, 2, 64, 700), (1, 512, 4, 4, 64, 511),
    (2, 1024, 16, 4, 128, 900), (1, 512, 8, 1, 64, 0),
    (3, 768, 6, 2, 32, 300),
])
@pytest.mark.parametrize("window", [0, 256])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_decode_attention(B, S, N, K, h, idx, window, dtype):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, N, h), dtype)
    kc = jax.random.normal(ks[1], (B, K, S, h), dtype)
    vc = jax.random.normal(ks[2], (B, K, S, h), dtype)
    out = decode_attention(q, kc, vc, idx, window=window, bk=256,
                           interpret=True)
    exp = dref.decode_attention_ref(q, kc, vc, idx, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


@pytest.mark.parametrize("B,S,N,K,h,bk", [
    (3, 768, 8, 2, 64, 256), (2, 512, 4, 4, 32, 512), (4, 384, 6, 2, 32, 128),
])
@pytest.mark.parametrize("window", [0, 200])
def test_decode_attention_per_slot_index(B, S, N, K, h, bk, window):
    """Per-slot [B] index vectors (continuous batching): every slot masks
    and early-exits against its own position."""
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, N, h))
    kc = jax.random.normal(ks[1], (B, K, S, h))
    vc = jax.random.normal(ks[2], (B, K, S, h))
    idx = jax.random.randint(ks[3], (B,), 0, S, jnp.int32)
    out = decode_attention(q, kc, vc, idx, window=window, bk=bk,
                           interpret=True)
    exp = dref.decode_attention_ref(q, kc, vc, idx, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("S,bk,idx", [
    (600, 512, 599),   # the regression: nk = S // bk used to drop 88 tail
    (600, 512, 100),   # positions silently whenever S % bk != 0
    (130, 64, 129),
    (48, 512, 47),     # bk > S: single padded block
])
@pytest.mark.parametrize("window", [0, 96])
def test_decode_attention_non_block_aligned(S, bk, idx, window):
    """S % bk != 0 must not drop the KV tail (positions >= (S//bk)*bk)."""
    B, N, K, h = 2, 4, 2, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, N, h))
    kc = jax.random.normal(ks[1], (B, K, S, h))
    vc = jax.random.normal(ks[2], (B, K, S, h))
    out = decode_attention(q, kc, vc, idx, window=window, bk=bk,
                           interpret=True)
    exp = dref.decode_attention_ref(q, kc, vc, idx, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("B,npg,ps,N,K,h", [
    (3, 8, 16, 8, 2, 64), (2, 4, 32, 4, 4, 32), (1, 16, 8, 6, 1, 32),
    # head_dim 128 takes the page-gather path: 9 and 33 pages are not a
    # multiple of its block (8 pages of 32, 16 of 8)
    (2, 9, 32, 8, 2, 128), (2, 33, 8, 4, 1, 128),
])
@pytest.mark.parametrize("window", [0, 40])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_paged_decode_attention(B, npg, ps, N, K, h, window, dtype):
    """Paged kernel (pages gathered through the scalar-prefetched page
    table) against the gather-then-dense oracle, with a scrambled page
    table so physical order != logical order."""
    P = B * npg + 3
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, N, h), dtype)
    kp = jax.random.normal(ks[1], (P, K, ps, h), dtype)
    vp = jax.random.normal(ks[2], (P, K, ps, h), dtype)
    # distinct physical pages, never the null page 0, scrambled order
    perm = jax.random.permutation(ks[3], jnp.arange(1, P))[:B * npg]
    pt = perm.reshape(B, npg).astype(jnp.int32)
    idx = jax.random.randint(ks[3], (B,), 0, npg * ps, jnp.int32)
    out = paged_decode_attention(q, kp, vp, pt, idx, window=window,
                                 interpret=True)
    exp = dref.paged_decode_attention_ref(q, kp, vp, pt, idx, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


def _quantized(kp, vp, kv):
    """bf16 pools, or int8/fp8 codes with their per-(page, head) scales
    (``"int8"``, ``"fp8"``) or per-token scales (``"int8_token"``)."""
    from repro.models import kv_quant
    if kv == "bf16":
        return kp.astype(jnp.bfloat16), vp.astype(jnp.bfloat16), {}
    dt = jnp.float8_e4m3fn if kv == "fp8" else jnp.int8
    gran = "token" if kv.endswith("_token") else "head"
    kq, ksc = kv_quant.quantize_page_rows(kp, dt, gran)
    vq, vsc = kv_quant.quantize_page_rows(vp, dt, gran)
    return kq, vq, dict(k_scales=ksc, v_scales=vsc)


@pytest.mark.parametrize("window", [0, 40])
@pytest.mark.parametrize("kv", ["bf16", "int8", "fp8", "int8_token"])
def test_paged_decode_attention_block_edges(window, kv):
    """The page-gather path at the edges of its page blocks (16 pages of
    16, 256 positions), K = 1: an idle slot at position 0, the last and
    first lane of a block, a slot inside its first page and one whose
    window leaves its whole first block dead (319 - 40 + 1 = 280 lies in
    page 17); bf16, int8 and fp8 pools, per-(page, head) and per-token
    scales, against the dequantizing oracle."""
    B, npg, ps, N, K, h = 5, 20, 16, 4, 1, 128
    P = B * npg + 1
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, N, h))
    kp, vp, scales = _quantized(jax.random.normal(ks[1], (P, K, ps, h)),
                                jax.random.normal(ks[2], (P, K, ps, h)), kv)
    perm = jax.random.permutation(ks[3], jnp.arange(1, P))
    pt = perm.reshape(B, npg).astype(jnp.int32)
    idx = jnp.asarray([0, 255, 256, 7, 319], jnp.int32)
    out = paged_decode_attention(q, kp, vp, pt, idx, window=window,
                                 interpret=True, **scales)
    if scales:
        exp = dref.paged_decode_attention_quant_ref(
            q, kp, vp, scales["k_scales"], scales["v_scales"], pt, idx,
            window=window)
    else:
        exp = dref.paged_decode_attention_ref(q, kp, vp, pt, idx,
                                              window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("h", [64, 128])
@pytest.mark.parametrize("window", [0, 40])
def test_paged_decode_bit_equals_dense_at_page_blocks(h, window):
    """The partition contract: over an identity page table the paged kernel
    equals the dense decode kernel at ``bk = page_size`` bit for bit, on
    both of its paths (page grid at head_dim 64, page gather at 128)."""
    B, S, N, K, ps = 3, 256, 8, 2, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, N, h))
    kc = jax.random.normal(ks[1], (B, K, S, h))
    vc = jax.random.normal(ks[2], (B, K, S, h))
    npg = S // ps
    pt = jnp.arange(B * npg, dtype=jnp.int32).reshape(B, npg)
    idx = jnp.asarray([255, 32, 31], jnp.int32)
    dense = decode_attention(q, kc, vc, idx, window=window, bk=ps,
                             interpret=True)
    paged = paged_decode_attention(q, _pages(kc, ps), _pages(vc, ps), pt,
                                   idx, window=window, interpret=True)
    assert jnp.array_equal(paged, dense)


@pytest.mark.parametrize("window", [0, 1, 40, 64])
def test_live_pages_is_the_block_live_run(window):
    """``live_pages`` names exactly the pages ``block_live`` keeps."""
    from repro.kernels.decode_attention.decode_attention import (block_live,
                                                                 live_pages)
    ps, npg = 16, 20
    index = np.arange(npg * ps)[:, None]
    page = np.arange(npg)[None]
    live = np.asarray(block_live(index, index, page * ps, ps, window))
    first, n = live_pages(index, ps, window, xp=np)
    assert np.array_equal(live, (page >= first) & (page < first + n))


def test_layers_decode_routes_through_kernels():
    """A test that pins ``use_pallas=True`` routes decode through the
    flash-decode kernels (interpreted off the TPU), and they match the jnp
    cores the platform picks on the CPU."""
    from repro.models.layers import (ModelOptions, attention_route,
                                     run_attention_core)
    opts = ModelOptions(use_pallas=True)
    B, S, N, K, h, ps = 2, 128, 4, 2, 32, 16
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, 1, N, h))
    kc = jax.random.normal(ks[1], (B, K, S, h))
    vc = jax.random.normal(ks[2], (B, K, S, h))
    idx = jnp.asarray([100, 7], jnp.int32)

    def route(layout, o):
        return attention_route("decode", layout, S=1, Skv=S, window=0,
                               opts=o)
    assert route("dense", opts) == "decode_flash"
    assert route("dense", ModelOptions()) == "decode_dense"
    assert route("paged", opts) == "decode_paged_flash"
    assert route("paged", ModelOptions()) == "decode_paged_gather"
    out = run_attention_core("decode_flash", q, kc, vc, opts=opts, window=0,
                             index=idx)
    exp = run_attention_core("decode_dense", q, kc, vc, opts=opts, window=0,
                             index=idx)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=2e-5, rtol=2e-5)
    npg = S // ps
    kp, vp = _pages(kc, ps), _pages(vc, ps)
    pt = jnp.arange(B * npg, dtype=jnp.int32).reshape(B, npg)
    out_p = run_attention_core("decode_paged_flash", q, kp, vp, opts=opts,
                               window=0, index=idx, page_table=pt)
    exp_p = run_attention_core("decode_paged_gather", q, kp, vp, opts=opts,
                               window=0, index=idx, page_table=pt)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(exp_p),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(out_p), np.asarray(exp),
                               atol=2e-5, rtol=2e-5)


def _pages(cache, ps):
    """Dense head-major [B, K, S, h] cache -> contiguous pool pages
    [B*S/ps, K, ps, h] (slot b's logical page p is physical b*npg + p)."""
    B, K, S, h = cache.shape
    pages = cache.reshape(B, K, S // ps, ps, h).swapaxes(1, 2)
    return pages.reshape(B * (S // ps), K, ps, h)


def test_paged_matches_dense_layout():
    """A paged cache whose table is the identity over contiguous pages is
    exactly the dense cache: both kernels and both oracles must agree."""
    B, S, N, K, h, ps = 2, 256, 4, 2, 32, 32
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, N, h))
    kc = jax.random.normal(ks[1], (B, K, S, h))
    vc = jax.random.normal(ks[2], (B, K, S, h))
    npg = S // ps
    kp, vp = _pages(kc, ps), _pages(vc, ps)
    pt = jnp.arange(B * npg, dtype=jnp.int32).reshape(B, npg)
    idx = jnp.asarray([200, 31], jnp.int32)
    dense = decode_attention(q, kc, vc, idx, bk=128, interpret=True)
    paged = paged_decode_attention(q, kp, vp, pt, idx, interpret=True)
    exp = dref.decode_attention_ref(q, kc, vc, idx)
    np.testing.assert_allclose(np.asarray(paged), np.asarray(exp),
                               atol=2e-5, rtol=2e-5)
    np.testing.assert_allclose(np.asarray(dense), np.asarray(exp),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("B,S,L,N,K,h,bk", [
    (2, 16, 256, 8, 2, 64, 128),   # chunk smaller than one band
    (1, 37, 200, 4, 4, 32, 64),    # odd chunk, L % bk != 0 (masked OOB tail)
    (2, 8, 96, 6, 2, 32, 32),      # several bands
    (1, 5, 64, 4, 1, 64, 128),     # bk > L: single clamped block
])
@pytest.mark.parametrize("window", [0, 48])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_chunk_prefill_attention(B, S, L, N, K, h, bk, window, dtype):
    """Banded chunk-prefill kernel vs the dense-softmax oracle, with
    per-slot start positions straddling band boundaries."""
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, S, N, h), dtype)
    kc = jax.random.normal(ks[1], (B, K, L, h), dtype)
    vc = jax.random.normal(ks[2], (B, K, L, h), dtype)
    idx = jax.random.randint(ks[3], (B,), 0, L - S, jnp.int32)
    out = chunk_prefill_attention(q, kc, vc, idx, window=window, bk=bk,
                                  interpret=True)
    exp = cref.chunk_prefill_ref(q, kc, vc, idx, window=window)
    np.testing.assert_allclose(np.asarray(out, np.float32),
                               np.asarray(exp, np.float32), **_tol(dtype))


@pytest.mark.parametrize("start", [0, 15, 16, 17, 31, 47])
def test_chunk_prefill_band_boundaries(start):
    """Sweep the chunk start across band-boundary straddles: the first,
    middle, and last rows of the chunk land in different key blocks."""
    B, S, L, N, K, h, bk = 1, 9, 64, 4, 2, 32, 16
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, S, N, h))
    kc = jax.random.normal(ks[1], (B, K, L, h))
    vc = jax.random.normal(ks[2], (B, K, L, h))
    out = chunk_prefill_attention(q, kc, vc, start, bk=bk, interpret=True)
    exp = cref.chunk_prefill_ref(q, kc, vc, start)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("B,S,npg,ps,N,K,h", [
    (2, 16, 8, 16, 8, 2, 64), (1, 7, 6, 8, 4, 4, 32), (3, 4, 4, 32, 6, 1, 32),
])
@pytest.mark.parametrize("window", [0, 40])
def test_paged_chunk_prefill_attention(B, S, npg, ps, N, K, h, window):
    """Paged chunk-prefill kernel (page-table gather in the index map, no
    host-side pool gather) vs the gather-then-dense oracle, scrambled
    physical page order."""
    P = B * npg + 3
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, S, N, h))
    kp = jax.random.normal(ks[1], (P, K, ps, h))
    vp = jax.random.normal(ks[2], (P, K, ps, h))
    perm = jax.random.permutation(ks[3], jnp.arange(1, P))[:B * npg]
    pt = perm.reshape(B, npg).astype(jnp.int32)
    idx = jax.random.randint(ks[3], (B,), 0, npg * ps - S, jnp.int32)
    out = paged_chunk_prefill_attention(q, kp, vp, pt, idx, window=window,
                                        interpret=True)
    exp = cref.paged_chunk_prefill_ref(q, kp, vp, pt, idx, window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=2e-5, rtol=2e-5)


@pytest.mark.parametrize("kv_dtype", [jnp.int8, jnp.float8_e4m3fn])
def test_paged_chunk_prefill_quantized(kv_dtype):
    """Quantized paged chunk kernel: codes + per-page-per-head scales
    gathered through the page table, dequantized in the VMEM tile."""
    from repro.models import kv_quant
    B, S, npg, ps, N, K, h = 2, 8, 6, 16, 4, 2, 64
    P = B * npg + 2
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, S, N, h))
    kp_f = jax.random.normal(ks[1], (P, K, ps, h))
    vp_f = jax.random.normal(ks[2], (P, K, ps, h))
    perm = jax.random.permutation(ks[3], jnp.arange(1, P))[:B * npg]
    pt = perm.reshape(B, npg).astype(jnp.int32)
    idx = jnp.asarray([3, 70], jnp.int32)
    kq, ksc = kv_quant.quantize_page_rows(kp_f, kv_dtype)
    vq, vsc = kv_quant.quantize_page_rows(vp_f, kv_dtype)
    out = paged_chunk_prefill_attention(q, kq, vq, pt, idx, k_scales=ksc,
                                        v_scales=vsc, interpret=True)
    exp = cref.paged_chunk_prefill_ref(q, kq, vq, pt, idx, k_scales=ksc,
                                       v_scales=vsc)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=1e-4, rtol=1e-4)
    full = cref.paged_chunk_prefill_ref(q, kp_f, vp_f, pt, idx)
    err = float(jnp.abs(out - full).max())
    budget = 0.05 if kv_dtype == jnp.int8 else 0.2
    assert err < budget, f"quantization error {err} above {budget}"


@pytest.mark.parametrize("B,S,H,P,N,Q", [
    (2, 256, 4, 64, 128, 128), (1, 128, 2, 32, 64, 64),
    (2, 512, 3, 64, 128, 128), (1, 256, 8, 16, 32, 64),
])
def test_ssd_vs_sequential(B, S, H, P, N, Q):
    ks = jax.random.split(KEY, 5)
    xs = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A_log = jax.random.uniform(ks[2], (H,), minval=0.0, maxval=1.5)
    B_ = 0.3 * jax.random.normal(ks[3], (B, S, 1, N), jnp.float32)
    C_ = 0.3 * jax.random.normal(ks[4], (B, S, 1, N), jnp.float32)
    y, st = ssd(xs, dt, A_log, B_, C_, Q=Q, interpret=True)
    y_ref, st_ref = sref.ssd_scan_ref(xs, dt, A_log, B_, C_)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_ref),
                               atol=5e-4, rtol=5e-3)
    np.testing.assert_allclose(np.asarray(st), np.asarray(st_ref),
                               atol=5e-4, rtol=5e-3)


def test_ssd_chunked_matches_kernel():
    """The XLA fallback (ssd_chunked) and the Pallas kernel agree."""
    ks = jax.random.split(KEY, 5)
    B, S, H, P, N = 2, 256, 4, 32, 64
    xs = jax.random.normal(ks[0], (B, S, H, P), jnp.float32)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, S, H)))
    A_log = jax.random.uniform(ks[2], (H,), minval=0.0, maxval=1.5)
    B_ = 0.3 * jax.random.normal(ks[3], (B, S, 1, N))
    C_ = 0.3 * jax.random.normal(ks[4], (B, S, 1, N))
    y1, s1 = ssd(xs, dt, A_log, B_, C_, interpret=True)
    y2, s2 = sref.ssd_chunked(xs, dt, A_log, B_, C_)
    np.testing.assert_allclose(np.asarray(y1), np.asarray(y2),
                               atol=2e-4, rtol=2e-3)
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s2),
                               atol=2e-4, rtol=2e-3)


@pytest.mark.parametrize("E,C,D,F,act", [
    (4, 128, 256, 512, "silu"), (8, 64, 128, 96, "gelu"),
    (2, 256, 64, 128, "gelu_plain"), (16, 32, 64, 64, "silu"),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_moe_gmm(E, C, D, F, act, dtype):
    ks = jax.random.split(KEY, 4)
    xe = 0.3 * jax.random.normal(ks[0], (E, C, D), dtype)
    wi = 0.3 * jax.random.normal(ks[1], (E, D, F), dtype)
    wg = 0.3 * jax.random.normal(ks[2], (E, D, F), dtype)
    wo = 0.3 * jax.random.normal(ks[3], (E, F, D), dtype)
    out = grouped_mlp(xe, wi, wg, wo, act, interpret=True)
    exp = np.asarray(gref.grouped_mlp_ref(xe, wi, wg, wo, act), np.float32)
    # bf16: the intermediate h is quantized in both kernel and ref; error
    # scales with output magnitude (two D/F-deep accumulations), so atol
    # scales with max|exp| (~bf16 eps of the output scale)
    tol = _tol(dtype)
    if dtype == jnp.bfloat16:
        tol = dict(atol=0.02 * float(np.abs(exp).max()) + 1e-3, rtol=5e-2)
    np.testing.assert_allclose(np.asarray(out, np.float32), exp, **tol)


@pytest.mark.parametrize("window", [0, 40])
@pytest.mark.parametrize("kv_dtype", [jnp.int8, jnp.float8_e4m3fn])
def test_paged_decode_attention_quantized(window, kv_dtype):
    """Quantized paged kernel: int8/fp8 pages + per-page-per-head scales
    gathered through the page table, dequantized in the VMEM tile. Must
    match the dequantize-then-dense oracle to fp32 accumulate precision,
    and stay close to the unquantized fp32 attention."""
    from repro.models import kv_quant
    B, npg, ps, N, K, h = 3, 8, 16, 8, 2, 64
    P = B * npg + 3
    ks = jax.random.split(KEY, 4)
    q = jax.random.normal(ks[0], (B, N, h))
    kp_f = jax.random.normal(ks[1], (P, K, ps, h))
    vp_f = jax.random.normal(ks[2], (P, K, ps, h))
    perm = jax.random.permutation(ks[3], jnp.arange(1, P))[:B * npg]
    pt = perm.reshape(B, npg).astype(jnp.int32)
    idx = jax.random.randint(ks[3], (B,), 0, npg * ps, jnp.int32)
    kq, ksc = kv_quant.quantize_page_rows(kp_f, kv_dtype)
    vq, vsc = kv_quant.quantize_page_rows(vp_f, kv_dtype)
    out = paged_decode_attention(q, kq, vq, pt, idx, k_scales=ksc,
                                 v_scales=vsc, window=window, interpret=True)
    exp = dref.paged_decode_attention_quant_ref(q, kq, vq, ksc, vsc, pt, idx,
                                                window=window)
    np.testing.assert_allclose(np.asarray(out), np.asarray(exp),
                               atol=1e-4, rtol=1e-4)
    full = dref.paged_decode_attention_ref(q, kp_f, vp_f, pt, idx,
                                           window=window)
    err = float(jnp.abs(out - full).max())
    budget = 0.05 if kv_dtype == jnp.int8 else 0.2
    assert err < budget, f"quantization error {err} above {budget}"


def test_quantize_page_rows_roundtrip():
    """encode/decode invariants the monotone-amax write policy relies on:
    dequantized values are within half a code of the input, all-zero pages
    get scale 0 and decode to exactly 0, and encode(decode(c)) == c at a
    fixed scale (drift-free rewrites)."""
    from repro.models import kv_quant
    rows = jax.random.normal(KEY, (5, 2, 8, 16)) * \
        jnp.asarray([0.1, 1.0, 10.0, 100.0, 0.0]).reshape(5, 1, 1, 1)
    for dt in (jnp.int8, jnp.float8_e4m3fn):
        codes, scales = kv_quant.quantize_page_rows(rows, dt)
        assert codes.dtype == dt and scales.shape == (5, 2)
        deq = kv_quant.decode(codes, scales[:, :, None, None])
        half_code = np.asarray(scales)[:, :, None, None] * \
            (0.51 if dt == jnp.int8 else 0.07 * kv_quant.qmax(dt))
        assert np.all(np.abs(np.asarray(deq - rows)) <= half_code + 1e-9)
        assert float(jnp.abs(deq[4]).max()) == 0.0      # zero page -> 0
        assert float(scales[4].max()) == 0.0
        again = kv_quant.encode(deq, scales[:, :, None, None], dt)
        assert jnp.array_equal(codes, again)
