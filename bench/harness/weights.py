"""Random weights of a configuration, drawn from the seed by the benchmark.

One jitted call makes every leaf on the device in the served dtype: each
leaf's float32 draw fuses into its cast, so no float32 copy of a leaf is
held. On a ``model=N`` mesh the call's output shardings are the serving
layout, so each chip draws only its own shard. The tree is laid out as the
engine takes its parameters (stacked decoder layers under
``decoder/blocks/sub0``, the tower under ``vision``); the plain reference
reads the same arrays by the same names, and nothing of it comes from the
program.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np


def leaf_shapes(c: dict) -> dict:
    """{path: (shape, fan_in or init kind)} of every weight of config ``c``."""
    d, n, k = c["d_model"], c["num_heads"], c["num_kv_heads"]
    h, f, V, L = c["head_dim"], c["d_ff"], c["vocab_size"], c["num_layers"]
    layer = {
        "ln1_w": ((d,), "gain"), "ln2_w": ((d,), "gain"),
        "wq": ((d, n, h), d), "wk": ((d, k, h), d), "wv": ((d, k, h), d),
        "wo": ((n, h, d), n * h),
        "wi": ((d, f), d), "wg": ((d, f), d), "wo_mlp": ((f, d), f),
    }
    if c["qkv_bias"]:
        layer.update({"bq": ((n, h), "bias"), "bk": ((k, h), "bias"),
                      "bv": ((k, h), "bias")})
    tree = {
        "embed": ((V, d), d),
        "final_norm_w": ((d,), "gain"),
        "decoder": {"blocks": {"sub0": {
            name: ((L,) + shape, init) for name, (shape, init)
            in layer.items()}}},
    }
    if not c["tie_embeddings"]:
        tree["lm_head"] = ((V, d), d)
    v = c["vision"]
    dv, nv, fv, Lv = v["d_model"], v["num_heads"], v["d_ff"], v["num_layers"]
    hv = dv // nv
    vl = {
        "ln1_w": ((dv,), "gain"), "ln1_b": ((dv,), "bias"),
        "ln2_w": ((dv,), "gain"), "ln2_b": ((dv,), "bias"),
        "wq": ((dv, nv, hv), dv), "wk": ((dv, nv, hv), dv),
        "wv": ((dv, nv, hv), dv), "wo": ((nv, hv, dv), dv),
        "wi": ((dv, fv), dv), "wo_mlp": ((fv, dv), fv),
    }
    tree["vision"] = {
        "in_proj": ((v["embed_dim"], dv), v["embed_dim"]),
        "pos": ((v["num_tokens"], dv), "pos"),
        "stack": {name: ((Lv,) + shape, init)
                  for name, (shape, init) in vl.items()},
        "final_ln_w": ((dv,), "gain"), "final_ln_b": ((dv,), "bias"),
        "out_proj": ((dv, d), dv),
    }
    return tree


def _is_leaf(x) -> bool:
    return isinstance(x, tuple) and len(x) == 2 and isinstance(x[0], tuple)


def _draw(key, shape, init, dtype):
    z = jax.random.normal(key, shape, jnp.float32)
    if init == "gain":
        return (1.0 + 0.1 * z).astype(dtype)
    if init == "bias":
        return (0.1 * z).astype(dtype)
    if init == "pos":
        return (0.02 * z).astype(dtype)
    return (z * (1.0 / np.sqrt(init))).astype(dtype)


def key_data(seed: int) -> np.ndarray:
    """Two uint32 words from any whole-number seed (wider than 32 bits)."""
    return np.random.default_rng(seed).integers(
        0, 2**32, size=2, dtype=np.uint32)


def make(c: dict, seed: int, dtype=jnp.bfloat16, shardings=None) -> dict:
    """The weights of ``c`` from ``seed``; ``shardings`` (a tree of
    ``NamedSharding`` like the weights', or None for one device) places
    each leaf."""
    shapes = leaf_shapes(c)
    leaves, treedef = jax.tree.flatten(shapes, is_leaf=_is_leaf)

    def init(kd):
        key = jax.random.wrap_key_data(kd)
        keys = jax.random.split(key, len(leaves))
        return jax.tree.unflatten(treedef, [
            _draw(kk, shape, how, dtype)
            for kk, (shape, how) in zip(keys, leaves)])

    return jax.jit(init, out_shardings=shardings)(
        jnp.asarray(key_data(seed)))
