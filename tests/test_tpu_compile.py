"""Compile the serving path for a TPU v5e that is described, not attached.

The TPU compiler refuses what interpret mode accepts: a Pallas block whose
last two dims do not tile by (8, 128), more VMEM than a kernel may use, a
program larger than the chip's memory. These tests compile the attention
kernels of the served path at molmoact-7b widths (28 query heads, 4 KV
heads, head_dim 128; bf16, and int8 pages for the paged kernels) and the
depth-cut decode step ``chip_smoke.py`` serves, so a change that would be
refused on the chip fails here at no chip time.

The topology is described inside a module fixture: only one process may
hold the TPU library, and pytest workers import every test file.
"""
from __future__ import annotations

import dataclasses
import functools

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.configs import get_config
from repro.kernels.chunk_prefill.chunk_prefill import \
    chunk_prefill_attention_kernel
from repro.kernels.chunk_prefill.paged import \
    paged_chunk_prefill_attention_kernel
from repro.kernels.decode_attention.decode_attention import \
    decode_attention_kernel
from repro.kernels.decode_attention.paged import paged_decode_attention_kernel
from repro.kernels.flash_attention.flash_attention import \
    flash_attention_kernel
from repro.models import layers
from repro.models import model as M
from repro.models.layers import ModelOptions
from repro.models.params import _init_tree
from repro.serving import engine as E
from repro.training import TrainConfig, init_train_state, make_train_step

CFG = get_config("molmoact-7b")
N, K, H = CFG.num_heads, CFG.num_kv_heads, CFG.head_dim
SLOTS, MAX_SEQ, PAGE = 4, 1024, 32          # chip_smoke.py's engine
NPG = MAX_SEQ // PAGE
POOL = 1 + SLOTS * NPG
DEPTH_CUT = 16                              # chip_smoke.DEPTH_CUT
CHIP_BYTES = 16e9                           # one v5e's HBM
BF16 = jnp.bfloat16


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler in this installation
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *shapes, sharding, **jit_kw):
    args = [jax.ShapeDtypeStruct(s, d, sharding=sharding) for s, d in shapes]
    return jax.jit(fn, **jit_kw).lower(*args).compile()


def _assert_kernel(compiled):
    assert "tpu_custom_call" in compiled.as_text(), \
        "the compiled program holds no Pallas kernel"


SCALES = {"bf16": None, "int8_head": (POOL, K), "int8_token": (POOL, K, PAGE)}


def _pool_shapes(kv):
    dt = BF16 if kv == "bf16" else jnp.int8
    shapes = [((POOL, K, PAGE, H), dt)] * 2
    if SCALES[kv] is not None:
        shapes += [(SCALES[kv], jnp.float32)] * 2
    return shapes


def _paged(kernel, kv):
    if SCALES[kv] is None:
        return lambda q, k, v, pt, i: kernel(q, k, v, pt, i)
    return lambda q, k, v, ks, vs, pt, i: kernel(q, k, v, pt, i,
                                                 k_scales=ks, v_scales=vs)


def test_decode_kernel_compiles(one_chip):
    _assert_kernel(_compile(
        decode_attention_kernel, ((SLOTS, N, H), BF16),
        ((SLOTS, K, MAX_SEQ, H), BF16), ((SLOTS, K, MAX_SEQ, H), BF16),
        ((SLOTS,), jnp.int32), sharding=one_chip))


@pytest.mark.parametrize("kv", sorted(SCALES))
def test_paged_decode_kernel_compiles(one_chip, kv):
    _assert_kernel(_compile(
        _paged(paged_decode_attention_kernel, kv), ((SLOTS, N, H), BF16),
        *_pool_shapes(kv), ((SLOTS, NPG), jnp.int32), ((SLOTS,), jnp.int32),
        sharding=one_chip))


# the fleet cell's decode batch (32 slots, 32 pages of 32) at molmoact-7b's
# heads (the page-gather path) and at internvl2-1b's head_dim 64 (the page
# grid), for every pool dtype
@pytest.mark.parametrize("heads", [(N, K, H), (14, 2, 64)])
@pytest.mark.parametrize("kv", sorted(SCALES))
def test_paged_decode_kernel_compiles_for_the_fleet(one_chip, kv, heads):
    n, k, h = heads
    slots, pool = 32, 1 + 32 * NPG
    dt = BF16 if kv == "bf16" else jnp.int8
    scales = {"bf16": [], "int8_head": [((pool, k), jnp.float32)] * 2,
              "int8_token": [((pool, k, PAGE), jnp.float32)] * 2}[kv]
    _assert_kernel(_compile(
        _paged(paged_decode_attention_kernel, kv), ((slots, n, h), BF16),
        *[((pool, k, PAGE, h), dt)] * 2, *scales,
        ((slots, NPG), jnp.int32), ((slots,), jnp.int32),
        sharding=one_chip))


# chunk 64, and 128: the largest chunk chip_smoke.py prefills with. The
# chunk kernels hold a whole chunk's query rows and softmax state in VMEM.
@pytest.mark.parametrize("chunk", [64, 128])
def test_chunk_prefill_kernel_compiles(one_chip, chunk):
    _assert_kernel(_compile(
        functools.partial(chunk_prefill_attention_kernel, bk=PAGE),
        ((1, chunk, N, H), BF16), ((1, K, MAX_SEQ, H), BF16),
        ((1, K, MAX_SEQ, H), BF16), ((1,), jnp.int32), sharding=one_chip))


@pytest.mark.parametrize("chunk", [64, 128])
@pytest.mark.parametrize("kv", sorted(SCALES))
def test_paged_chunk_prefill_kernel_compiles(one_chip, kv, chunk):
    _assert_kernel(_compile(
        _paged(paged_chunk_prefill_attention_kernel, kv),
        ((1, chunk, N, H), BF16), *_pool_shapes(kv),
        ((1, NPG), jnp.int32), ((1,), jnp.int32), sharding=one_chip))


def test_flash_kernel_compiles(one_chip):
    S = 640                              # 576 patches + a 64-token prompt
    _assert_kernel(_compile(
        flash_attention_kernel, ((1, S, N, H), BF16), ((1, S, K, H), BF16),
        ((1, S, K, H), BF16), sharding=one_chip))


def _abstract(tree, sharding):
    return jax.tree.map(lambda a: jax.ShapeDtypeStruct(
        a.shape, a.dtype, sharding=sharding), tree)


def _abstract_params(cfg, dtype, sharding):
    return _abstract(jax.eval_shape(functools.partial(
        _init_tree, M.model_template(cfg), dtype=dtype),
        jax.random.PRNGKey(0)), sharding)


def test_unpinned_train_step_compiles(one_chip, monkeypatch):
    """A train step with the platform's defaults compiles for the TPU: a
    causal 128-aligned sequence keeps the jnp attention core there (the
    flash kernel has no backward rule, so differentiating through it
    fails)."""
    monkeypatch.setattr(layers, "on_tpu", lambda: True)
    cfg = get_config("smollm-135m").reduced()
    tcfg = TrainConfig()
    params = _abstract_params(cfg, jnp.float32, one_chip)
    opt_state = _abstract(jax.eval_shape(
        functools.partial(init_train_state, cfg, tcfg), params), one_chip)
    tokens = jax.ShapeDtypeStruct((2, 128), jnp.int32, sharding=one_chip)
    jax.jit(make_train_step(cfg, ModelOptions(remat=False), tcfg)).lower(
        params, opt_state, {"tokens": tokens}).compile()


def test_unpinned_hybrid_prefill_compiles(one_chip, monkeypatch):
    """A hybrid attention/SSM/MoE prefill with the platform's defaults
    compiles for the TPU: the SSD scan and the MoE matmul stay on jnp
    there (the SSD kernel's one-head blocks are refused by the lowering)."""
    monkeypatch.setattr(layers, "on_tpu", lambda: True)
    cfg = get_config("jamba-1.5-large-398b").reduced()
    opts = ModelOptions(remat=False)
    params = _abstract_params(cfg, BF16, one_chip)
    tokens = jax.ShapeDtypeStruct((1, 128), jnp.int32, sharding=one_chip)
    jax.jit(lambda p, t: M.prefill(cfg, opts, p, {"tokens": t}, 256)).lower(
        params, tokens).compile()


def test_cut_depth_decode_step_fits_one_chip(one_chip, monkeypatch):
    """The served decode step of the depth-cut model, bf16 weights and a
    bf16 paged pool, compiles for one v5e with the compiled kernels (the
    platform check is steered to the TPU branch: this process runs on the
    CPU) and its arguments plus temporaries fit the chip's 16 GB."""
    monkeypatch.setattr(layers, "on_tpu", lambda: True)
    cfg = dataclasses.replace(CFG, num_layers=DEPTH_CUT)
    opts = ModelOptions(remat=False)

    params = _abstract_params(cfg, BF16, one_chip)
    caches = _abstract(jax.eval_shape(lambda: M.init_caches(
        cfg, SLOTS, MAX_SEQ, BF16, opts, paged=True, num_pages=POOL,
        page_size=PAGE)), one_chip)
    i32 = functools.partial(jax.ShapeDtypeStruct, dtype=jnp.int32,
                            sharding=one_chip)
    compiled = jax.jit(lambda p, t, c, i, pt: M.decode_step(
        cfg, opts, p, t, c, i, page_table=pt)).lower(
        params, i32((SLOTS, 1)), caches, i32((SLOTS,)),
        i32((SLOTS, NPG))).compile()
    _assert_kernel(compiled)
    ma = compiled.memory_analysis()
    used = ma.argument_size_in_bytes + ma.temp_size_in_bytes
    assert used < CHIP_BYTES, f"{used / 1e9:.2f} GB does not fit one chip"


def _stage_program(stage, sharding):
    """The engine's ``stage`` program (as ``ServingEngine`` binds it) for a
    paged, chunked bf16 engine at the registry's reduced molmoact-7b size,
    lowered and compiled for ``sharding``."""
    cfg = get_config("molmoact-7b").reduced()
    opts = ModelOptions(remat=False)
    slots, max_seq, page = 2, 64, 8
    npg = max_seq // page
    params = _abstract_params(cfg, BF16, sharding)
    caches = _abstract(jax.eval_shape(lambda: M.init_caches(
        cfg, slots, max_seq, BF16, opts, paged=True,
        num_pages=1 + slots * npg, page_size=page)), sharding)

    def arg(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)
    if stage == "tick":
        prog = E._jit_tick(cfg, opts, 4, 1, 0.0, 0, True)
        lowered = prog.lower(params, arg((slots, 1)), caches, arg((slots,)),
                             arg((slots,)), arg((slots,), jnp.bool_),
                             arg((2,), jnp.uint32), arg(()),
                             arg((slots, npg)))
    elif stage == "chunk":
        prog = E._jit_prefill_chunk(cfg, opts, True)
        lowered = prog.lower(params, arg((1, page, cfg.d_model), BF16),
                             caches, arg(()), arg(()), arg((1, npg)),
                             2 * page)
    else:
        prog = E._jit_vision(cfg, opts)
        lowered = prog.lower(params, arg(
            (1, cfg.vision.num_tokens, cfg.vision.embed_dim), BF16))
    return lowered.compile().as_text()


# what bench/harness/programs.py reads in a chip trace: the module names,
# the two paged kernels' custom-call names, and the vision weights' op names
@pytest.mark.parametrize("stage,marker", [
    ("tick", "%paged_decode_attention."),
    ("chunk", "%paged_chunk_prefill_attention."),
    ("vision", "p__vision__")])
def test_stage_programs_carry_their_names(one_chip, monkeypatch, stage,
                                          marker):
    monkeypatch.setattr(layers, "on_tpu", lambda: True)
    text = _stage_program(stage, one_chip)
    assert text.startswith(f"HloModule jit_serve_{stage},"), text[:80]
    assert marker in text
    assert stage == "vision" or "tpu_custom_call" in text
