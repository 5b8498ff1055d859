"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module never
touches jax device state (the dry-run sets the 512-device XLA flag before
any jax initialization; tests/benches see 1 device).

Every mesh is built with ``AxisType.Auto`` axes: the sharding rules in
``repro.distributed.sharding`` place parameters and constrain activations
and leave the rest to the compiler's propagation, which the default
``Explicit`` axes of ``jax.make_mesh`` would refuse.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Arbitrary mesh (tests, elastic restarts, PP experiments)."""
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single pod (256 chips) or 2×16×16 dual-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(n_data: int | None = None, n_model: int = 1):
    """Small mesh over however many (host) devices exist — test helper."""
    n = jax.device_count()
    if n_data is None:
        n_data = n // n_model
    return make_mesh((n_data, n_model), ("data", "model"))
