"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — smoke tests must keep seeing 1 CPU device.
"""

from __future__ import annotations

import numpy as np

import jax


def _mesh(shape, axes):
    """jax.make_mesh with implicit (Auto) axis semantics."""
    shape, axes = tuple(shape), tuple(axes)
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips multi-pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_mesh(shape, axes):
    """Arbitrary mesh (elastic re-mesh after node loss uses this)."""
    return _mesh(shape, axes)


def parse_mesh_shape(spec: str) -> tuple[tuple[int, ...], tuple[str, ...]]:
    """``'4x2'`` -> ``((4, 2), ('data', 'model'))``; ``'2'`` -> ``((2,),
    ('data',))``. The serving ``--mesh dxm`` flag and the cross-mesh test
    harness share this one parser so their shapes cannot drift.
    """
    try:
        shape = tuple(int(part) for part in spec.lower().split("x"))
    except ValueError:
        raise ValueError(f"bad mesh spec {spec!r}: want 'd' or 'dxm'") from None
    if not shape or len(shape) > 2 or any(s < 1 for s in shape):
        raise ValueError(f"bad mesh spec {spec!r}: want 'd' or 'dxm' with "
                         f"positive sizes")
    return shape, ("data", "model")[: len(shape)]


def make_serving_mesh(spec: str):
    """Mesh for ``ServeSession`` waves from a ``'d'``/``'dxm'`` spec string.

    Raises with the available device count when the host cannot satisfy the
    shape (on CPU, force devices with
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N``).
    """
    shape, axes = parse_mesh_shape(spec)
    need = int(np.prod(shape))
    have = jax.device_count()
    if need > have:
        raise ValueError(
            f"mesh {spec!r} needs {need} devices, host has {have} "
            f"(XLA_FLAGS=--xla_force_host_platform_device_count={need} "
            f"simulates them on CPU)")
    return _mesh(shape, axes)
