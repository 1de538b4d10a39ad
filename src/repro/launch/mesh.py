"""Production mesh builders (functions only — importing this module never
touches jax device state)."""

from __future__ import annotations

import jax


def make_mesh(shape, axes):
    """``jax.make_mesh`` with Auto axis types (GSPMD propagation)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi_pod adds a leading pod=2 axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_test_mesh(n_devices: int | None = None, axes=("data", "model")):
    """Small mesh over however many (host) devices exist — for tests."""
    n = n_devices or len(jax.devices())
    a = 1
    while n % 2 == 0 and a * 2 <= n ** 0.5 + 1:
        a *= 2
        n //= 2
    shape = (a, (n_devices or len(jax.devices())) // a)
    return make_mesh(shape, axes)


def batch_axes(mesh) -> tuple[str, ...]:
    """Axes used for data parallelism on this mesh."""
    names = mesh.axis_names
    return tuple(a for a in ("pod", "data") if a in names)
