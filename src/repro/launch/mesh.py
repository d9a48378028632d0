"""Production mesh construction.

A function (not a module-level constant) so importing never touches jax
device state.  Single pod: (16, 16) = 256 chips, ("data", "model").
Multi-pod: (2, 16, 16) = 512 chips, ("pod", "data", "model") — `pod` is an
outer data-parallel axis (DCN between pods, ICI inside).
"""
from __future__ import annotations

import jax


def _auto(n: int):
    """Auto axis types: the model code places arrays with sharding
    constraints, which ``jax.make_mesh``'s default Explicit axes refuse."""
    return (jax.sharding.AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_test_mesh(*, model: int = 4, data: int = 2):
    """Small mesh for subprocess tests (8 fake devices)."""
    return jax.make_mesh((data, model), ("data", "model"),
                         axis_types=_auto(2))


def stage_devices(n_stages: int):
    """One device per pipeline-parallel stage, round-robin over the
    local devices — on a single-device box every stage maps to device 0
    and the activation handoff degenerates to an on-device no-op, so
    the staged engine runs (and is testable) anywhere."""
    devs = jax.devices()
    return [devs[s % len(devs)] for s in range(max(1, int(n_stages)))]
