"""Production mesh construction.

Defined as FUNCTIONS (not module-level constants) so importing this module
never touches jax device state.  Single pod: (16, 16) = 256 chips
(data, model).  Multi-pod: (2, 16, 16) = 512 chips (pod, data, model) — the
"pod" axis is the FL-client axis in the Helios datacenter mapping
(DESIGN.md §2).
"""
from __future__ import annotations

import os

import jax
import numpy as np


def _mesh(shape, axes):
    """``jax.make_mesh`` with Auto axes: its default is Explicit axes, under
    which ``with_sharding_constraint`` in the model layers raises."""
    return jax.make_mesh(shape, axes, axis_types=(
        jax.sharding.AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False):
    # REPRO_MESH="4x4" / "2x2x4" overrides the chip count for scaled-down CI
    # runs of the same code path (tests/test_dryrun_small.py).
    override = os.environ.get("REPRO_MESH")
    if override:
        shape = tuple(int(x) for x in override.split("x"))
        axes = ("pod", "data", "model") if len(shape) == 3 else \
            ("data", "model")
        return _mesh(shape, axes)
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_client_mesh(max_shards: int | None = None):
    """1-D ``("clients",)`` mesh for the client-sharded FL engine.

    Uses every visible device by default; ``max_shards`` caps the axis so a
    small cohort doesn't spread one client per device and pad the rest (the
    sharded engine pads the cohort up to a multiple of the axis size).
    Validated on CPU via the ``REPRO_HOST_DEVICES``-forced host-device
    pattern (tests/test_sharded_engine.py, benchmarks sharded_population).
    """
    devs = jax.devices()
    n = len(devs)
    if max_shards is not None:
        n = max(1, min(n, max_shards))
    return jax.sharding.Mesh(np.asarray(devs[:n]), ("clients",))


def make_debug_mesh(n_devices: int | None = None, *, multi_pod: bool = False):
    """Small mesh over however many (host) devices exist — used by tests."""
    n = n_devices or len(jax.devices())
    if multi_pod and n >= 8:
        return _mesh((2, 2, n // 4), ("pod", "data", "model"))
    if n >= 4:
        return _mesh((2, n // 2), ("data", "model"))
    return _mesh((1, n), ("data", "model"))
