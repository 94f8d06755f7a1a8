"""Production mesh construction (multi-pod dry-run spec).

Functions, not module-level constants: importing this module never touches
jax device state (device count locks on first backend init).

Every mesh here has `Auto` axes: sharding is propagated by the compiler
and `with_sharding_constraint` is a hint, not an assertion (the
`Explicit` default of `jax.make_mesh` would turn it into one)."""
from __future__ import annotations

import jax


def auto_mesh(shape, axes, devices=None):
    """`jax.make_mesh` with every axis of type `Auto`."""
    return jax.make_mesh(shape, axes, devices=devices,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 = 256 chips per pod; 2 pods = 512 chips when multi_pod."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes)


def make_host_mesh(tp: int = 1):
    """Whatever this host has (smoke tests / examples)."""
    n = len(jax.devices())
    tp = min(tp, n)
    return auto_mesh((n // tp, tp), ("data", "model"))
