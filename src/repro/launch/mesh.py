"""Production mesh construction.

``make_production_mesh`` is a FUNCTION (not a module constant) so that
importing this module never touches jax device state — required because
the dry-run must set XLA_FLAGS before any jax initialization.
"""
from __future__ import annotations

import jax

from repro.dist.sharding import make_mesh


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (16, 16) = 256 chips ("data", "model").
    Multi-pod:  (2, 16, 16) = 512 chips ("pod", "data", "model")."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_local_mesh(model_axis: int = 1):
    """Whatever devices exist, as ("data", "model") — for tests/examples."""
    n = len(jax.devices())
    assert n % model_axis == 0
    return make_mesh((n // model_axis, model_axis), ("data", "model"))
