"""Parameter templates: one source of truth for shape + init + logical axes.

``param_template(cfg)`` returns a pytree of ``PSpec``; from it we derive
``init_params`` (random init), ``param_shapes`` (ShapeDtypeStructs for AOT
lowering) and ``param_shardings`` (NamedShardings via the logical-axis rules).
"""
from __future__ import annotations

import dataclasses
import functools
from dataclasses import dataclass
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.distributed.sharding import sharding_for


@dataclass(frozen=True)
class PSpec:
    shape: Tuple[int, ...]
    axes: Tuple[Optional[str], ...]
    init: str = "normal"       # normal | zeros | ones | ssm_a | ssm_dt | pos
    fan_in: Optional[int] = None

    def __post_init__(self):
        assert len(self.shape) == len(self.axes), (self.shape, self.axes)


def is_pspec(x) -> bool:
    return isinstance(x, PSpec)


def _init_leaf(spec: PSpec, key, dtype):
    if spec.init == "zeros":
        return jnp.zeros(spec.shape, dtype)
    if spec.init == "ones":
        return jnp.ones(spec.shape, dtype)
    if spec.init == "ssm_a":
        # A_log init: A in [1, 16] -> log
        u = jax.random.uniform(key, spec.shape, jnp.float32, 1.0, 16.0)
        return jnp.log(u).astype(dtype)
    if spec.init == "ssm_dt":
        # dt bias such that softplus(dt) in [1e-3, 1e-1]
        u = jax.random.uniform(key, spec.shape, jnp.float32,
                               np.log(1e-3), np.log(1e-1))
        dt = jnp.exp(u)
        return (dt + jnp.log(-jnp.expm1(-dt))).astype(dtype)
    fan_in = spec.fan_in
    if fan_in is None:
        fan_in = spec.shape[0] if len(spec.shape) > 1 else spec.shape[-1]
    scale = 1.0 / np.sqrt(max(fan_in, 1))
    if spec.init == "pos":
        scale = 0.02
    return (jax.random.normal(key, spec.shape, jnp.float32) * scale).astype(dtype)


def _init_tree(template, key, dtype):
    leaves, treedef = jax.tree.flatten(template, is_leaf=is_pspec)
    keys = jax.random.split(key, len(leaves))
    return jax.tree.unflatten(
        treedef, [_init_leaf(l, k, dtype) for l, k in zip(leaves, keys)])


def init_params(template, key, dtype=jnp.float32, shardings=None):
    """Random weights for ``template``, drawn by one jitted program: each
    leaf's float32 draw fuses into its cast to ``dtype``, so the device
    never holds a float32 copy of a bf16 leaf. ``shardings`` (a tree of
    ``Sharding`` matching ``template``, or None for the default device)
    are the output shardings: under a mesh every device makes only its own
    shard, and no whole tree lands on one device first."""
    init = functools.partial(_init_tree, template, dtype=dtype)
    return jax.jit(init, out_shardings=shardings)(key)


def param_shapes(template, dtype=jnp.bfloat16):
    return jax.tree.map(
        lambda s: jax.ShapeDtypeStruct(s.shape, dtype), template,
        is_leaf=is_pspec)


def param_shardings(template, mesh):
    return jax.tree.map(
        lambda s: sharding_for(s.shape, s.axes, mesh), template,
        is_leaf=is_pspec)


def param_count(template) -> int:
    return sum(int(np.prod(l.shape))
               for l in jax.tree.leaves(template, is_leaf=is_pspec))


def stack(template, n: int, axis_name: Optional[str] = "layers"):
    """Prepend a stacked (scan) dimension to every leaf of a layer template."""
    return jax.tree.map(
        lambda s: dataclasses.replace(s, shape=(n,) + s.shape,
                                      axes=(axis_name,) + s.axes),
        template, is_leaf=is_pspec)
