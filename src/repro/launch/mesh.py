"""Production mesh construction.

Defined as functions (never module-level constants) so importing this module
never touches jax device state — the dry-run driver sets
``XLA_FLAGS=--xla_force_host_platform_device_count=512`` before any jax
import, and everything else must see the real (single) device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; multi-pod adds the 2-pod axis (512)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """A mesh of the local devices whose axes are all ``Auto``: the
    sharding rules place parameters and constrain activations, and the
    compiler propagates the rest."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_host_mesh(model: int = 1):
    """Whatever devices exist locally (smoke tests: 1 CPU device)."""
    n = len(jax.devices())
    if model < 1 or n % model:
        raise ValueError(
            f"model axis {model} does not divide the {n} available "
            f"device(s); pass --model-axis dividing the device count")
    return make_mesh((n // model, model), ("data", "model"))
