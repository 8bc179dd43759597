"""Production mesh construction.

Defined as functions (never module-level constants) so importing this
module touches no JAX device state — the dry-run must set XLA_FLAGS before
first jax initialization.
"""

from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh


def _auto(n: int) -> tuple:
    """Auto axes: the sharding specs in ``distributed/sharding.py`` are
    propagated by the compiler (``jax.make_mesh`` defaults to explicit
    axes, under which an embedding gather across axes does not type)."""
    return (AxisType.Auto,) * n


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    """Single pod: 16x16 = 256 chips (data, model).
    Multi-pod: 2 pods x 256 = 512 chips (pod, data, model); the 'pod' axis
    is outer data-parallel by default (the PP schedule may claim it)."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return jax.make_mesh(shape, axes, axis_types=_auto(len(axes)))


def make_host_mesh(model_parallel: int = 1) -> Mesh:
    """Tiny mesh over whatever devices exist (tests, examples)."""
    n = len(jax.devices())
    assert n % model_parallel == 0
    return jax.make_mesh((n // model_parallel, model_parallel),
                         ("data", "model"), axis_types=_auto(2))
