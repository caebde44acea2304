"""Production mesh construction (defined as functions — importing this
module never touches jax device state)."""

from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """16×16 = 256 chips per pod; ``multi_pod`` adds the 2-pod axis.

    Axes: ``data`` (batch / FSDP), ``model`` (tensor / expert / vocab),
    ``pod`` (pure data parallelism across pods, over DCI).
    """
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes,
                         axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_smoke_mesh():
    """1×1 mesh over the single CPU device (smoke tests / examples)."""
    return jax.make_mesh((1, 1), ("data", "model"),
                         axis_types=(jax.sharding.AxisType.Auto,) * 2)
