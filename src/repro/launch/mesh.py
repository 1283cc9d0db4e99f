"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state — required because the 512-device dry-run must set
XLA_FLAGS before any jax initialization.
"""
from __future__ import annotations

import jax


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips (one v5e pod slice).
    Multi-pod: (pod=2, data=16, model=16) = 512 chips, DCN/ICI hierarchy on
    the leading axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return make_mesh(shape, axes)


def make_data_mesh(n_dev: int | None = None):
    """1-D ``data`` mesh over all (or the first ``n_dev``) devices — the
    shape the keyed/sharded dataplane runs on (docs/protocol.md §6): one
    owner shard per device, no model axis."""
    n = n_dev if n_dev is not None else len(jax.devices())
    return make_mesh((n,), ("data",))


def make_mesh(shape: tuple[int, ...], axes: tuple[str, ...]):
    """Mesh with Auto axes: jax.make_mesh defaults to Explicit, which would
    turn on sharding-in-types for every program built on it."""
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes)
    )
