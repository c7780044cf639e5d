"""Production mesh construction.

A FUNCTION, not a module-level constant — importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first jax init;
tests import this with 1 CPU device).

Every mesh here uses ``Auto`` axis types: the model and its sharding rules
leave propagation to the compiler, and ``jax.make_mesh``'s ``Explicit``
default rejects them (e.g. gathering the embedding by token ids).
"""

from __future__ import annotations

import jax


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """``jax.make_mesh`` with every axis ``Auto``."""
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 single-pod (256 chips) or 2×16×16 multi-pod (512 chips).

    Axes: ("data", "model") — FSDP/DP over "data", TP over "model";
    multi-pod adds a leading pure-DP "pod" axis (DCN-level).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_host_mesh(model: int = 1, data: int | None = None):
    """Small mesh over whatever devices exist (CPU tests, examples)."""
    n = len(jax.devices())
    if data is None:
        data = n // model
    return make_mesh((data, model), ("data", "model"))
