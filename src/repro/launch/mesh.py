"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — required because the
dry-run forces 512 host devices via XLA_FLAGS before first jax init, while
smoke tests must see the single real CPU device.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """16x16 single-pod (256 chips) or 2x16x16 multi-pod (512 chips)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes)


def make_host_mesh():
    """Degenerate 1x1 mesh over the real local device(s) for smoke runs.

    Auto axes: the model code places activations with
    ``with_sharding_constraint``, which JAX refuses on the Explicit axes
    ``make_mesh`` defaults to since JAX 0.9."""
    n = len(jax.devices())
    return jax.make_mesh((n, 1), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
