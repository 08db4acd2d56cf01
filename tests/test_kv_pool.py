"""KV pool block allocator: refcounting, prefix cache, copy-on-write,
exhaustion — plus the paged serving engine end-to-end (paged decode must be
bit-identical to the dense layout under greedy sampling)."""
import warnings

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.serving import KVPool, PoolExhausted, Request, ServingEngine
from conftest import reduced_params


# ---------------------------------------------------------------------------
# allocator unit tests
# ---------------------------------------------------------------------------

def _pool(num_pages=9, page_size=4, n_slots=2, pages_per_slot=4):
    return KVPool(num_pages, page_size, n_slots, pages_per_slot)


def test_admit_allocates_and_free_returns():
    p = _pool()
    pages, shared = p.admit(0, seq_len=10)       # ceil(10/4) = 3 pages
    assert len(pages) == 3 and shared == 0
    assert p.pages_in_use == 3
    assert 0 not in pages                        # null page never handed out
    assert list(p.page_table[0][:3]) == pages
    assert all(x == 0 for x in p.page_table[0][3:])
    p.free_slot(0)
    assert p.pages_in_use == 0
    assert np.all(p.page_table[0] == 0)


def test_prefix_cache_shares_full_pages():
    p = _pool()
    keys = [b"page0", b"page1"]
    a, shared_a = p.admit(0, seq_len=10, prefix_keys=keys)  # 2 full + 1 tail
    assert shared_a == 0
    b, shared_b = p.admit(1, seq_len=10, prefix_keys=keys)
    assert shared_b == 2 and p.prefix_hits == 2
    assert b[:2] == a[:2] and b[2] != a[2]       # tail page stays private
    assert p.refcount[a[0]] == 2
    # only 4 pages total despite 6 logical pages
    assert p.pages_in_use == 4


def test_prefix_cache_retains_freed_pages():
    """A hashed page whose refcount drops to zero is retained (LRU) and the
    next identical prompt still hits it."""
    p = _pool()
    keys = [b"k0"]
    a, _ = p.admit(0, seq_len=4, prefix_keys=keys)
    p.free_slot(0)
    assert p.pages_in_use == 0 and p.cached_pages == 1
    b, shared = p.admit(1, seq_len=4, prefix_keys=keys)
    assert shared == 1 and b[0] == a[0]
    assert p.cached_pages == 0                   # revived


def test_prefix_break_stops_sharing():
    """Sharing stops at the first non-matching page (the prefix property)."""
    p = _pool(num_pages=12)
    a, _ = p.admit(0, seq_len=12, prefix_keys=[b"x0", b"x1", b"x2"])
    b, shared = p.admit(1, seq_len=12, prefix_keys=[b"x0", b"DIFF", b"x2"])
    assert shared == 1
    assert b[0] == a[0] and b[1] != a[1] and b[2] != a[2]


def test_exhaustion_is_atomic_and_reclaims_cached():
    p = _pool(num_pages=5, pages_per_slot=4)     # 4 allocatable pages
    p.admit(0, seq_len=12)                       # 3 pages
    with pytest.raises(PoolExhausted):
        p.admit(1, seq_len=9)                    # needs 3, only 1 left
    assert p.pages_in_use == 3                   # rollback complete
    p.free_slot(0)
    # retained cache pages are reclaimed under pressure
    p2 = _pool(num_pages=4, pages_per_slot=3)
    p2.admit(0, seq_len=8, prefix_keys=[b"a", b"b"])
    p2.free_slot(0)
    assert p2.cached_pages == 2
    pages, _ = p2.admit(1, seq_len=12)           # needs all 3 pages
    assert len(pages) == 3 and p2.cached_pages == 0


def test_can_admit_agrees_with_admit_on_cached_shared_pages():
    """can_admit must not double-count prefix pages sitting in the retained
    cache (they are shared AND would otherwise look reclaimable): whenever
    can_admit says yes, admit must succeed."""
    p = _pool(num_pages=4, pages_per_slot=4)     # 3 allocatable, page_size 4
    keys = [b"p0", b"p1"]
    p.admit(0, seq_len=9, prefix_keys=keys)      # 2 hashed full + 1 partial
    p.free_slot(0)
    assert p.cached_pages == 2 and len(p._free) == 1
    # 13 positions sharing the 8-token prefix: 4 pages, 2 shared-from-cache
    # -> 2 fresh needed but only 1 truly allocatable
    assert not p.can_admit(13, keys)
    with pytest.raises(PoolExhausted):
        p.admit(1, seq_len=13, prefix_keys=keys)
    # and a request that does fit is still admissible
    assert p.can_admit(9, keys)
    pages, shared = p.admit(1, seq_len=9, prefix_keys=keys)
    assert shared == 2


def test_prepare_write_rolls_back_on_exhaustion():
    """A COW that runs out of pages mid-range must undo completed swaps —
    otherwise the caller never copies pages the table already points at."""
    p = _pool(num_pages=6, pages_per_slot=4)     # 5 allocatable
    a, _ = p.admit(0, seq_len=16)                # 4 pages
    p.fork(0, 1)                                 # all shared, 1 page left
    before = list(p.slot_pages[1])
    with pytest.raises(PoolExhausted):
        p.prepare_write(1, start=0, end=16)      # needs 4 copies, has 1
    assert p.slot_pages[1] == before             # fully rolled back
    assert list(p.page_table[1][:4]) == before
    assert all(p.refcount[pid] == 2 for pid in before)
    assert p.pages_in_use == 4


def test_fork_and_copy_on_write():
    p = _pool()
    a, _ = p.admit(0, seq_len=6)                 # 2 pages, tail partial
    p.fork(0, 1)
    assert p.slot_pages[1] == a
    assert p.refcount[a[1]] == 2
    # writing into the shared tail page must COW it
    copies = p.prepare_write(1, start=6, end=7)
    assert len(copies) == 1 and copies[0][0] == a[1]
    assert p.slot_pages[1][1] == copies[0][1] != a[1]
    assert p.refcount[a[1]] == 1                 # slot 0 owns it again
    assert p.page_table[1][1] == copies[0][1]
    # a second write to the now-private page needs no copy
    assert p.prepare_write(1, start=7, end=8) == []


def test_prepare_write_private_pages_noop():
    p = _pool()
    p.admit(0, seq_len=8)
    assert p.prepare_write(0, start=8, end=12) == []


def test_copy_pages_device_side():
    """The jitted COW page copy writes dst <- src on every paged leaf and
    leaves slot-batched leaves and other pages untouched."""
    from repro.models import model as M
    from repro.models.stacks import is_paged_leaf
    from repro.serving.engine import _copy_pages
    cfg, _ = reduced_params("smollm-135m")
    from repro.models.layers import ModelOptions
    caches = M.init_caches(cfg, 2, 32, jnp.float32,
                           ModelOptions(remat=False), paged=True,
                           num_pages=6, page_size=8)
    # fill each page p with the constant p
    caches = jax.tree_util.tree_map_with_path(
        lambda path, leaf: (leaf + jnp.arange(6).reshape(
            (1, 6, 1, 1, 1) if leaf.ndim == 5 else (6, 1, 1, 1)))
        if is_paged_leaf(path) else leaf, caches)
    src = jnp.asarray([3, 0, 0, 0], jnp.int32)
    dst = jnp.asarray([5, 0, 0, 0], jnp.int32)
    out = _copy_pages(caches, src, dst)

    def check(path, leaf):
        if not is_paged_leaf(path):
            return
        pages = leaf if leaf.ndim == 4 else leaf[0]
        assert float(pages[5].min()) == 3.0, path     # copied
        assert float(pages[3].min()) == 3.0, path     # source intact
        assert float(pages[1].max()) == 1.0, path     # others untouched
    jax.tree_util.tree_map_with_path(check, out)


# ---------------------------------------------------------------------------
# paged engine end-to-end
# ---------------------------------------------------------------------------

def _streams(cfg, opts, params, reqs, *, paged, fused=True, n_slots=2,
             max_seq=48, page_size=8, **kw):
    eng = ServingEngine(cfg, opts, params, n_slots=n_slots, max_seq=max_seq,
                        eos=-999, fused=fused, tick_tokens=4, paged=paged,
                        page_size=page_size, **kw)
    for i, (prompt, m) in enumerate(reqs):
        eng.submit(Request(uid=i, prompt=prompt.copy(), max_tokens=m))
    done = eng.run()
    assert len(done) == len(reqs)
    return {r.uid: r.out_tokens for r in done}, eng


def test_paged_matches_dense_mixed_lengths(opts):
    """Paged == dense token-for-token across mixed prompt lengths, budgets,
    and mid-stream admission, on both the fused and per-token paths."""
    cfg, params = reduced_params("qwen1.5-0.5b")
    rng = np.random.default_rng(7)
    reqs = [(rng.integers(0, cfg.vocab_size, l, dtype=np.int32), m)
            for l, m in [(4, 7), (9, 3), (6, 12), (3, 5), (8, 9)]]
    dense, _ = _streams(cfg, opts, params, reqs, paged=False)
    for fused in (True, False):
        paged, eng = _streams(cfg, opts, params, reqs, paged=True,
                              fused=fused)
        assert paged == dense, f"paged (fused={fused}) diverged from dense"
        assert eng.stats.pages_hwm > 0
        assert eng.stats.pages_in_use == 0       # all freed at drain


def test_paged_prefix_sharing_and_stats(opts):
    """Identical prompts share full prefix pages; EngineStats counts the
    hits and the high-water marks reflect sharing."""
    cfg, params = reduced_params("smollm-135m")
    rng = np.random.default_rng(8)
    prompt = rng.integers(0, cfg.vocab_size, 16, dtype=np.int32)
    reqs = [(prompt, 5)] * 4
    dense, _ = _streams(cfg, opts, params, reqs, paged=False)
    paged, eng = _streams(cfg, opts, params, reqs, paged=True)
    assert paged == dense
    assert eng.stats.prefix_hits >= 3 * (16 // 8)   # 3 later reqs x 2 pages
    assert eng.stats.cache_bytes_hwm > 0
    by_uid = {r.uid: r for r in eng.finished}
    assert by_uid[0].pages_shared == 0
    assert all(by_uid[i].pages_shared == 2 for i in (1, 2, 3))


def test_paged_pool_exhaustion_defers_admission(opts):
    """An under-provisioned pool defers queued requests instead of crashing,
    and they complete once pages free up."""
    cfg, params = reduced_params("smollm-135m")
    rng = np.random.default_rng(9)
    reqs = [(rng.integers(0, cfg.vocab_size, 8, dtype=np.int32), 6)
            for _ in range(4)]
    # 2 slots but pages for ~1.5 requests at a time
    paged, eng = _streams(cfg, opts, params, reqs, paged=True, num_pages=6)
    dense, _ = _streams(cfg, opts, params, reqs, paged=False)
    assert paged == dense
    assert eng.stats.pages_hwm <= 5


def test_paged_vision_prefix_keys(opts):
    """VLM requests hash patches into the prefix keys: identical
    (patches, prompt) pairs share pages; different patches must not."""
    cfg, params = reduced_params("molmoact-7b")
    rng = np.random.default_rng(10)
    prompt = rng.integers(0, cfg.vocab_size, 8, dtype=np.int32)
    px1 = 0.1 * rng.standard_normal(
        (cfg.vision.num_tokens, cfg.vision.embed_dim)).astype(np.float32)
    px2 = px1 + 0.5

    def run(patches_list):
        eng = ServingEngine(cfg, opts, params, n_slots=2, max_seq=48,
                            eos=-999, paged=True, page_size=8)
        for i, px in enumerate(patches_list):
            eng.submit(Request(uid=i, prompt=prompt.copy(), max_tokens=4,
                               patches=px))
        eng.run()
        return eng

    same = run([px1, px1])
    assert same.stats.prefix_hits > 0
    diff = run([px1, px2])
    assert diff.stats.prefix_hits == 0


def test_budget_clamped_to_cache_capacity(opts):
    """max_tokens overflowing max_seq is clamped (with a warning) instead of
    silently corrupting the cache — and both layouts clamp identically, so
    the bit-equality contract holds for over-budget requests too."""
    cfg, params = reduced_params("smollm-135m")
    rng = np.random.default_rng(12)
    reqs = [(rng.integers(0, cfg.vocab_size, 28, dtype=np.int32), 10)]
    outs = {}
    for paged in (False, True):
        with pytest.warns(RuntimeWarning, match="exceeds cache capacity"):
            outs[paged], _ = _streams(cfg, opts, params, reqs, paged=paged,
                                      n_slots=1, max_seq=32, page_size=8)
    assert outs[True] == outs[False]
    # prefill token + (max_seq - prompt_len) decode tokens
    assert len(outs[True][0]) == 1 + (32 - 28)


def test_paged_growth_preemption_under_pressure(opts):
    """When decode growth exhausts the pool, a victim slot is preempted and
    retried rather than crashing run(); greedy streams still match dense."""
    cfg, params = reduced_params("smollm-135m")
    rng = np.random.default_rng(13)
    reqs = [(rng.integers(0, cfg.vocab_size, 8, dtype=np.int32), 17)
            for _ in range(2)]
    dense, _ = _streams(cfg, opts, params, reqs, paged=False, n_slots=2,
                        max_seq=32)
    # 5 allocatable pages, but both requests want 4 pages at full length
    paged, eng = _streams(cfg, opts, params, reqs, paged=True, n_slots=2,
                          max_seq=32, num_pages=6)
    assert paged == dense
    assert eng.stats.pages_hwm <= 5


def test_paged_request_that_never_fits_raises(opts):
    """A request needing more pages than the whole pool is a sizing error
    (raised), not a silent livelock of deferrals."""
    from repro.serving import PoolExhausted
    cfg, params = reduced_params("smollm-135m")
    rng = np.random.default_rng(14)
    eng = ServingEngine(cfg, opts, params, n_slots=2, max_seq=32, eos=-999,
                        paged=True, page_size=8, num_pages=3)
    eng.submit(Request(uid=0, prompt=rng.integers(
        0, cfg.vocab_size, 20, dtype=np.int32), max_tokens=4))
    with pytest.raises(PoolExhausted, match="too small"):
        eng.run()


def test_engine_pallas_kernel_path_matches_reference(opts):
    """With use_pallas the engine decodes through the flash-decode kernels
    (dense and paged, interpret mode); greedy streams must match the plain
    einsum engine."""
    from repro.models.layers import ModelOptions
    cfg, params = reduced_params("smollm-135m")
    popts = ModelOptions(remat=False, use_pallas=True)
    rng = np.random.default_rng(15)
    reqs = [(rng.integers(0, cfg.vocab_size, 9, dtype=np.int32), 4)
            for _ in range(2)]
    ref, _ = _streams(cfg, opts, params, reqs, paged=False, n_slots=1,
                      max_seq=32)
    for paged in (False, True):
        out, _ = _streams(cfg, popts, params, reqs, paged=paged, n_slots=1,
                          max_seq=32)
        assert out == ref, f"pallas engine path (paged={paged}) diverged"


def test_run_surfaces_exhausted_tick_budget(opts):
    """run(max_ticks) must warn and expose the pending count instead of
    silently returning with requests still queued/in flight."""
    cfg, params = reduced_params("smollm-135m")
    rng = np.random.default_rng(11)
    eng = ServingEngine(cfg, opts, params, n_slots=1, max_seq=48, eos=-999)
    for i in range(3):
        eng.submit(Request(uid=i, prompt=rng.integers(
            0, cfg.vocab_size, 6, dtype=np.int32), max_tokens=8))
    with pytest.warns(RuntimeWarning, match="tick budget"):
        done = eng.run(max_ticks=1)
    assert eng.pending == 3 - len(done) and eng.pending > 0
    # draining the rest clears the pending count, no warning
    with warnings.catch_warnings():
        warnings.simplefilter("error")
        eng.run()
    assert eng.pending == 0


def test_paged_rejects_bad_geometry(opts):
    cfg, params = reduced_params("smollm-135m")
    with pytest.raises(ValueError, match="must divide"):
        ServingEngine(cfg, opts, params, max_seq=50, paged=True,
                      page_size=16)


# ---------------------------------------------------------------------------
# quantized pool (int8/fp8 pages + per-page scale siblings)
# ---------------------------------------------------------------------------

def test_quantized_requires_paged(opts):
    """kv_dtype quantization without the paged layout is a config error, at
    both the template and the engine boundary."""
    from repro.models import stacks
    cfg, params = reduced_params("smollm-135m")
    with pytest.raises(ValueError, match="requires the paged layout"):
        stacks.cache_template(cfg, 1, 32, kv_dtype="int8")
    with pytest.raises(ValueError, match="requires paged"):
        ServingEngine(cfg, opts, params, max_seq=32, kv_dtype="int8")
    with pytest.raises(ValueError, match="kv_dtype"):
        stacks.cache_template(cfg, 1, 32, paged=True, num_pages=4,
                              page_size=8, kv_dtype="int4")


def test_quantized_cache_leaves_and_dtypes(opts):
    """Quantized paged caches carry int8/fp8 K/V pool leaves with f32
    per-page-per-head scale siblings [num_pages, K]; bf16 mode has none."""
    from repro.models import model as M
    from repro.models.stacks import is_paged_leaf, is_scale_leaf
    cfg, _ = reduced_params("smollm-135m")
    for kv_dtype, want in (("int8", jnp.int8), ("fp8", jnp.float8_e4m3fn)):
        caches = M.init_caches(cfg, 2, 32, jnp.float32, opts, paged=True,
                               num_pages=6, page_size=8, kv_dtype=kv_dtype)
        n_scale = n_val = 0
        for path, leaf in jax.tree_util.tree_leaves_with_path(caches):
            if is_scale_leaf(path):
                n_scale += 1
                assert leaf.dtype == jnp.float32
                assert leaf.shape[-2:] == (6, cfg.num_kv_heads) or \
                    leaf.shape == (6, cfg.num_kv_heads)
            elif is_paged_leaf(path):
                n_val += 1
                assert leaf.dtype == want, path
        assert n_scale == n_val > 0
    plain = M.init_caches(cfg, 2, 32, jnp.float32, opts, paged=True,
                          num_pages=6, page_size=8)
    assert not any(is_scale_leaf(p) for p, _ in
                   jax.tree_util.tree_leaves_with_path(plain))


def test_quantized_streams_match_bf16(opts):
    """int8 greedy streams match the unquantized paged engine on both the
    fused and per-token paths; the quantized pool is smaller and keeps its
    prefix hits. (fp8 agreement is workload-dependent; gated in the bench.)"""
    cfg, params = reduced_params("smollm-135m")
    rng = np.random.default_rng(21)
    shared = rng.integers(0, cfg.vocab_size, 16, dtype=np.int32)
    reqs = [(shared, 6),
            (rng.integers(0, cfg.vocab_size, 9, dtype=np.int32), 7),
            (shared, 5)]
    ref, eng_ref = _streams(cfg, opts, params, reqs, paged=True)
    for fused in (True, False):
        out, eng = _streams(cfg, opts, params, reqs, paged=True, fused=fused,
                            kv_dtype="int8")
        assert out == ref, f"int8 (fused={fused}) diverged from bf16 paged"
        assert eng.stats.prefix_hits == eng_ref.stats.prefix_hits
        assert eng.stats.pages_hwm == eng_ref.stats.pages_hwm
        assert eng.stats.cache_bytes_hwm < 0.3 * eng_ref.stats.cache_bytes_hwm
        assert eng.stats.pages_in_use == 0


def test_quantized_pool_exhaustion_and_preemption(opts):
    """Scale rows ride the page lifecycle through deferral and preemption:
    an under-provisioned int8 pool defers/preempts and the reallocated pages
    (whose scale rows held stale values from the evicted request) are
    rewritten on re-scatter, so streams still match the roomy pool."""
    cfg, params = reduced_params("smollm-135m")
    rng = np.random.default_rng(22)
    reqs = [(rng.integers(0, cfg.vocab_size, 8, dtype=np.int32), 17)
            for _ in range(2)]
    roomy, _ = _streams(cfg, opts, params, reqs, paged=True, n_slots=2,
                        max_seq=32, kv_dtype="int8")
    tight, eng = _streams(cfg, opts, params, reqs, paged=True, n_slots=2,
                          max_seq=32, num_pages=6, kv_dtype="int8")
    assert tight == roomy
    assert eng.stats.pages_hwm <= 5


def test_copy_pages_carries_scales():
    """The jitted COW page copy moves a page's scale row in lockstep with
    its values: after fork + prepare_write the copy must dequantize to the
    same numbers, even when the two pages' scales differ."""
    from repro.models import kv_quant
    from repro.models import model as M
    from repro.models.layers import ModelOptions
    from repro.models.stacks import is_paged_leaf, is_scale_leaf
    from repro.serving.engine import _copy_pages
    cfg, _ = reduced_params("smollm-135m")
    caches = M.init_caches(cfg, 2, 32, jnp.float32,
                           ModelOptions(remat=False), paged=True,
                           num_pages=6, page_size=8, kv_dtype="int8")
    # page p gets codes p and scale p/127 -> dequantized constant p*p/127
    caches = jax.tree_util.tree_map_with_path(
        lambda path, leaf:
        (leaf + (jnp.arange(6, dtype=jnp.float32) / 127.0).reshape(
            (1, 6, 1) if leaf.ndim == 3 else (6, 1)))
        if is_scale_leaf(path) else
        (leaf + jnp.arange(6, dtype=jnp.int8).reshape(
            (1, 6, 1, 1, 1) if leaf.ndim == 5 else (6, 1, 1, 1)))
        if is_paged_leaf(path) else leaf, caches)
    out = _copy_pages(caches, jnp.asarray([3, 0, 0, 0], jnp.int32),
                      jnp.asarray([5, 0, 0, 0], jnp.int32))

    def check(path, leaf):
        if is_scale_leaf(path):
            rows = leaf if leaf.ndim == 2 else leaf[0]
            np.testing.assert_allclose(np.asarray(rows[5]), 3 / 127.0,
                                       rtol=1e-6, err_msg=str(path))
            np.testing.assert_allclose(np.asarray(rows[1]), 1 / 127.0,
                                       rtol=1e-6, err_msg=str(path))
        elif is_paged_leaf(path):
            pages = leaf if leaf.ndim == 4 else leaf[0]
            assert int(pages[5].min()) == 3, path      # codes copied
            assert int(pages[3].min()) == 3, path      # source intact
            assert int(pages[1].max()) == 1, path      # others untouched
    jax.tree_util.tree_map_with_path(check, out)


def test_scatter_pages_quantizes_and_writes_scales(opts):
    """_scatter_pages encodes prefill KV into the int8 pool with
    amax-derived per-page-per-head scales: dequantized pages reconstruct the
    dense prefill rows, scales land only on the destination pages, and
    non-destination pages keep scale 0."""
    from repro.models import kv_quant
    from repro.models import model as M
    from repro.models.stacks import is_paged_leaf, is_scale_leaf
    from repro.serving.engine import _path_keys, _scatter_pages
    cfg, params = reduced_params("smollm-135m")
    ps, n_pages = 8, 6
    logits, cache1 = M.prefill(cfg, opts, params,
                               {"tokens": jnp.arange(16)[None]}, 16,
                               cache_dtype=jnp.float32)
    caches = M.init_caches(cfg, 1, 16, jnp.float32, opts, paged=True,
                           num_pages=n_pages, page_size=ps, kv_dtype="int8")
    dest = jnp.asarray([2, 4], jnp.int32)              # 16 tokens = 2 pages
    out = _scatter_pages(caches, cache1, dest, ps)
    flat1 = {_path_keys(p): l for p, l in
             jax.tree_util.tree_leaves_with_path(cache1)}
    checked = 0
    for path, leaf in jax.tree_util.tree_leaves_with_path(out):
        if not is_paged_leaf(path) or is_scale_leaf(path):
            continue
        keys = _path_keys(path)
        pages = leaf if leaf.ndim == 4 else leaf[0]    # [P, K, ps, h]
        scales = None
        for p2, l2 in jax.tree_util.tree_leaves_with_path(out):
            if _path_keys(p2) == keys[:-1] + (keys[-1] + "_scale",):
                scales = l2 if l2.ndim == 2 else l2[0]
        dense = flat1[keys]                            # [(nb,)1,K,S,h]
        dense = dense if dense.ndim == 4 else dense[0]
        K, h = dense.shape[1], dense.shape[3]
        rows = dense[0].reshape(K, 2, ps, h).swapaxes(0, 1)  # page-major
        for i, d in enumerate([2, 4]):
            deq = kv_quant.decode(pages[d], scales[d][:, None, None])
            np.testing.assert_allclose(np.asarray(deq), np.asarray(rows[i]),
                                       atol=float(scales[d].max()) * 0.51,
                                       err_msg=str(path))
        assert float(scales[1].max()) == 0.0           # non-dest untouched
        assert float(scales[5].max()) == 0.0
        checked += 1
    assert checked > 0


def test_update_cache_paged_quantized_monotone_scale():
    """Decode quantize-on-write: the page scale grows monotonically with the
    written token's amax, existing rows are requantized (not lost) when it
    grows, and a rewrite at an unchanged scale is drift-free."""
    from repro.models import kv_quant
    from repro.models.layers import update_cache_paged
    ps, K, h = 4, 2, 8
    pages = jnp.zeros((3, K, ps, h), jnp.int8)
    scales = jnp.zeros((3, K), jnp.float32)
    pt = jnp.asarray([[1, 2]], jnp.int32)
    small = jnp.full((1, 1, K, h), 0.5, jnp.float32)
    big = jnp.full((1, 1, K, h), 2.0, jnp.float32)
    pages, scales = update_cache_paged(pages, small, pt, 0, scales)
    s0 = np.asarray(scales[1]).copy()
    np.testing.assert_allclose(s0, 0.5 / 127.0, rtol=1e-6)
    pages, scales = update_cache_paged(pages, big, pt, 1, scales)
    np.testing.assert_allclose(np.asarray(scales[1]), 2.0 / 127.0, rtol=1e-6)
    # row 0 (written under the smaller scale) survived the requantization
    deq = kv_quant.decode(pages[1], np.asarray(scales[1])[:, None, None])
    np.testing.assert_allclose(np.asarray(deq[:, 0]), 0.5, atol=2.0 / 127.0)
    np.testing.assert_allclose(np.asarray(deq[:, 1]), 2.0, atol=2.0 / 127.0)
    # writing a smaller token later must not shrink the scale (monotone)...
    pages, scales = update_cache_paged(pages, small, pt, 2, scales)
    np.testing.assert_allclose(np.asarray(scales[1]), 2.0 / 127.0, rtol=1e-6)
    # ...and an identical rewrite is bit-stable (encode(decode(c)) == c)
    pages2, scales2 = update_cache_paged(pages, small, pt, 2, scales)
    assert jnp.array_equal(pages, pages2) and jnp.array_equal(scales, scales2)


def test_growth_pages_get_clean_scales(opts):
    """A page freed by one request and handed to another via decode growth
    must not leak its old scale into the new owner's quantize-on-write:
    streams from a pool with dirty history match a fresh pool's. Forced
    directly: poison every scale row, then check _ensure_pages growth resets
    exactly the grown pages' rows (COW-copied and held pages excluded)."""
    from repro.models.stacks import is_scale_leaf
    from repro.serving.engine import _reset_page_scales
    cfg, params = reduced_params("smollm-135m")
    rng = np.random.default_rng(23)
    prompt = rng.integers(0, cfg.vocab_size, 8, dtype=np.int32)
    eng = ServingEngine(cfg, opts, params, n_slots=1, max_seq=32, eos=-999,
                        paged=True, page_size=8, kv_dtype="int8")
    eng.submit(Request(uid=0, prompt=prompt.copy(), max_tokens=10))
    eng._admit()
    held = list(eng.pool.slot_pages[0])
    # poison: pretend every page once belonged to a large-scale request
    eng.caches = jax.tree_util.tree_map_with_path(
        lambda path, leaf: leaf + 7.0 if is_scale_leaf(path) else leaf,
        eng.caches)
    eng._ensure_pages(eng.tick_tokens)
    grown = [p for p in eng.pool.slot_pages[0] if p not in held]
    assert grown, "test setup: tick must require page growth"
    for path, leaf in jax.tree_util.tree_leaves_with_path(eng.caches):
        if not is_scale_leaf(path):
            continue
        rows = leaf if leaf.ndim == 2 else leaf[0]
        for p in grown:
            assert float(jnp.abs(rows[p]).max()) == 0.0, (path, p)
        for p in held:
            assert float(rows[p].min()) >= 7.0, (path, p)  # held: untouched
    # and the unit helper resets only what it is told to
    again = _reset_page_scales(eng.caches, jnp.asarray(held[:1], jnp.int32))
    for path, leaf in jax.tree_util.tree_leaves_with_path(again):
        if is_scale_leaf(path):
            rows = leaf if leaf.ndim == 2 else leaf[0]
            assert float(jnp.abs(rows[held[0]]).max()) == 0.0


def test_quantized_null_page_stays_zero(opts):
    """Retired/empty slots riding a fused tick write into null page 0; the
    quantized write masks their codes and scale updates, so page 0 keeps
    its documented all-zero, scale-0 state (unit: a page-table row of zeros
    is a sink; e2e: an engine run with an idle slot leaves page 0 clean)."""
    from repro.models.layers import update_cache_paged
    from repro.models.stacks import is_paged_leaf, is_scale_leaf
    pages = jnp.zeros((3, 2, 4, 8), jnp.int8)
    scales = jnp.zeros((3, 2), jnp.float32)
    pt = jnp.asarray([[0, 0], [1, 2]], jnp.int32)     # slot 0 retired
    new = jnp.full((2, 1, 2, 8), 3.0, jnp.float32)
    pages, scales = update_cache_paged(pages, new, pt, jnp.asarray([5, 1]),
                                       scales)
    assert int(jnp.abs(pages[0]).max()) == 0 and float(scales[0].max()) == 0
    assert float(scales[1].max()) > 0                 # live slot wrote
    cfg, params = reduced_params("smollm-135m")
    rng = np.random.default_rng(24)
    reqs = [(rng.integers(0, cfg.vocab_size, 8, dtype=np.int32), 6)]
    _, eng = _streams(cfg, opts, params, reqs, paged=True, n_slots=2,
                      kv_dtype="int8")
    for path, leaf in jax.tree_util.tree_leaves_with_path(eng.caches):
        if is_scale_leaf(path):
            p0 = leaf[:, 0] if leaf.ndim == 3 else leaf[0]
            assert float(jnp.abs(p0).max()) == 0.0, path
        elif is_paged_leaf(path):
            p0 = leaf[:, 0] if leaf.ndim == 5 else leaf[0]
            assert int(jnp.abs(p0.astype(jnp.int32)).max()) == 0, path


# ---------------------------------------------------------------------------
# decode-headroom reserve + chunk-granular prefix registration
# ---------------------------------------------------------------------------

def test_reserve_accounting_admission_vs_decode():
    """set_reserve fences the last pages off from admission-side allocation
    (admit / ensure(use_reserve=False)) while decode-side growth may still
    consume them — the pool-aware policy that keeps in-flight decodes from
    deadlocking behind fresh prompts."""
    p = _pool(num_pages=6, page_size=4, n_slots=2, pages_per_slot=5)  # 5 usable
    p.set_reserve(2)
    pages, _ = p.admit(0, seq_len=12)           # 3 pages: exactly the supply
    assert len(pages) == 3
    with pytest.raises(PoolExhausted):
        p.admit(1, seq_len=4)                   # admission blocked by reserve
    assert p.pages_in_use == 3                  # atomic: nothing leaked
    with pytest.raises(PoolExhausted):
        p.ensure(0, 16, use_reserve=False)      # prefill growth blocked too
    assert p.ensure(0, 16) and len(p.slot_pages[0]) == 4  # decode-side OK
    assert p.ensure(0, 20) and len(p.slot_pages[0]) == 5  # decode eats reserve
    with pytest.raises(PoolExhausted):
        p.ensure(1, 4)                          # genuinely empty now
    p.free_slot(0)
    assert p.pages_in_use == 0


def test_reserve_respected_by_can_admit():
    p = _pool(num_pages=6, page_size=4, n_slots=2, pages_per_slot=4)
    assert p.can_admit(16)                      # 4 pages of 5 usable
    p.set_reserve(2)
    assert not p.can_admit(16)                  # only 3 admissible now
    assert p.can_admit(12)
    with pytest.raises(ValueError):
        p.set_reserve(-1)
    with pytest.raises(ValueError):
        p.set_reserve(6)                        # > usable pages


def test_match_prefix_counts_leading_run():
    p = _pool()
    keys = [b"a", b"b", b"c"]
    assert p.match_prefix(keys) == 0
    p.admit(0, seq_len=8, prefix_keys=keys[:2])  # registers 2 full pages
    assert p.match_prefix(keys) == 2
    assert p.match_prefix([b"x", b"b"]) == 0     # prefix-closed: leading only


def test_admit_register_false_defers_registration():
    """Chunked admission must not register digests before the pages' KV is
    written: admit(register=False) leaves the prefix cache untouched and
    register_prefix_pages only registers pages the written span covers."""
    p = _pool()
    keys = [b"p0", b"p1"]
    pages, shared = p.admit(0, seq_len=10, prefix_keys=keys,
                            register=False)
    assert shared == 0 and p.match_prefix(keys) == 0
    assert p.register_prefix_pages(0, keys, n_written=5) == 1  # page 0 only
    assert p.match_prefix(keys) == 1
    assert p.register_prefix_pages(0, keys, n_written=10) == 1  # now page 1
    assert p.match_prefix(keys) == 2
    # idempotent, and never re-points an existing digest
    assert p.register_prefix_pages(0, keys, n_written=10) == 0
    b, shared_b = p.admit(1, seq_len=10, prefix_keys=keys)
    assert shared_b == 2 and b[:2] == pages[:2]
