"""Production mesh builders.

``make_production_mesh`` is a FUNCTION (never a module-level constant) so
that importing this module never touches jax device state.  The dry-run
launcher sets ``XLA_FLAGS=--xla_force_host_platform_device_count=512``
before any jax import; everything else sees the real device count.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _n(shape) -> int:
    n = 1
    for s in shape:
        n *= s
    return n


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...],
              devices=None) -> Mesh:
    """The one mesh builder: the first ``prod(shape)`` devices (or
    ``devices``) with every axis Auto.  The installed JAX defaults to
    Explicit axes, which reject ``with_sharding_constraint``, ambiguous
    gathers and uneven slices — everything here is written for the
    compiler-propagated (Auto) sharding."""
    if devices is None:
        devices = jax.devices()[: _n(shape)]
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single-pod (16,16) ("data","model") or 2-pod (2,16,16)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int | None = None) -> Mesh:
    """Small mesh over whatever devices exist (CPU tests, demos)."""
    n = len(jax.devices())
    model = model or 1
    if n % model:
        model = 1
    return make_mesh((n // model, model), ("data", "model"))


def chips(mesh: Mesh) -> int:
    return mesh.devices.size


def legal_slice_shapes(max_chips: int = 512):
    """Legal v5e slice chip counts (the planner rounds c_n up to these)."""
    out = []
    c = 1
    while c <= max_chips:
        out.append(c)
        c *= 2
    return out
