"""Production meshes. A function (not a module-level constant) so importing
this module never touches jax device state."""
from __future__ import annotations

import jax


def _auto_kwargs(n):
    # shardings inside the programs are propagated by the compiler (Auto),
    # not carried in the types (Explicit)
    return {"axis_types": (jax.sharding.AxisType.Auto,) * n}


def _validate_axes(**sizes):
    for name, n in sizes.items():
        if not isinstance(n, int) or n < 1:
            raise ValueError(f"mesh axis {name!r} must be a positive int, "
                             f"got {n!r}")
    total = 1
    for n in sizes.values():
        total *= n
    avail = jax.device_count()
    if total > avail:
        raise ValueError(
            f"mesh {dict(sizes)} needs {total} devices but only {avail} "
            f"are visible (CPU runs: set XLA_FLAGS="
            f"--xla_force_host_platform_device_count=N before importing jax)")


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    _validate_axes(**dict(zip(axes, shape)))
    return jax.make_mesh(shape, axes, **_auto_kwargs(len(axes)))


def make_elastic_mesh(data: int, model: int = 16):
    """Reduced-data-axis mesh for elastic shrink after node loss."""
    _validate_axes(data=data, model=model)
    return jax.make_mesh((data, model), ("data", "model"), **_auto_kwargs(2))


def make_serving_mesh(model: int):
    """1-axis ('model',) mesh for tensor-parallel serving — sized for dev
    boxes and CI, not just the 16x16 production shapes (the old factories
    hardcoded model=16, so any small-mesh user had to monkey-patch).
    ``ServingEngine(mesh=...)`` shards attention heads, MLP width, vocab,
    and the paged KV pool's head axis over it (see docs/architecture.md)."""
    _validate_axes(model=model)
    return jax.make_mesh((model,), ("model",), **_auto_kwargs(1))


def make_dev_mesh(data: int = 1, model: int = 2):
    """Small 2-axis mesh for tests/examples on a dev box; validates against
    the visible device count instead of assuming a 256-chip slice."""
    _validate_axes(data=data, model=model)
    return jax.make_mesh((data, model), ("data", "model"), **_auto_kwargs(2))
