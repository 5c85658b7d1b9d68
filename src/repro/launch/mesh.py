"""The repo's one mesh constructor, and the production shapes built with it.

Every mesh states its axis types: ``AxisType.Auto`` on every axis, so
sharding propagates through the serve and train steps as GSPMD decides
and no op needs an explicit ``out_sharding`` (``jax.make_mesh`` defaults
to Explicit axes on current JAX, which the scatter-heavy KV cache code
does not use).

Defined as FUNCTIONS so importing this module never touches jax device
state; the dry-run sets ``--xla_force_host_platform_device_count=512``
before any jax import.
"""

from __future__ import annotations

from typing import Sequence

import jax
from jax.sharding import AxisType, Mesh


def _mesh(shape: Sequence[int], axes: Sequence[str]) -> Mesh:
    n = 1
    for s in shape:
        n *= s
    avail = jax.device_count()
    if n > avail:
        raise ValueError(
            f"mesh {'x'.join(map(str, shape))} needs {n} devices, have "
            f"{avail} — on CPU set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n} before importing jax"
        )
    return jax.make_mesh(
        tuple(shape), tuple(axes), axis_types=(AxisType.Auto,) * len(axes),
        devices=jax.devices()[:n],
    )


def make_mesh(data: int = 1, model: int = 1) -> Mesh:
    """A ``("data", "model")`` mesh over the first ``data*model`` devices."""
    return _mesh((data, model), ("data", "model"))


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """16×16 (data, model) single pod; 2×16×16 (pod, data, model) multi-pod."""
    if multi_pod:
        return _mesh((2, 16, 16), ("pod", "data", "model"))
    return _mesh((16, 16), ("data", "model"))
