"""Wall-clock vs sim-time measurement, kept in separate types so the two
domains cannot be conflated (docs/observability.md §1).

The discrete-event runtimes live entirely in **simulated** milliseconds
(``Sim.now``): every registry metric and trace record uses sim timestamps,
and nothing in a sim path may read the wall clock (that would break the
same-seed bit-identical guarantee).  Wall-clock timing exists only at the
edges — the real jitted dataplane in launch/stream.py, benchmark drivers —
and goes through :class:`WallTimer`, whose ``domain`` tag follows the
measurement into metric names and benchmark rows.

The third clock is the device's.  The jitted dataplane names its layers with
``jax.named_scope``s drawn from :data:`DATAPLANE_LAYERS`; they travel as op
metadata of the compiled program, so a profiler trace puts each device op
down to a layer on the device's own timeline (docs/observability.md §7).
"""
from __future__ import annotations

import time

# The dataplane's layers, outermost scope names of launch/stream.py.  Scopes
# nested inside one of them refine it and never count as layers of their own.
DATAPLANE_LAYERS = ("shuffle", "fold", "sync", "read")


class WallTimer:
    """Context-manager stopwatch over the host wall clock (``domain="wall"``).
    The only sanctioned ``time.time()`` in measurement paths — sim code uses
    :class:`SimTimer` (or ``Sim.now`` directly) instead."""

    domain = "wall"

    def __enter__(self) -> "WallTimer":
        self.t0 = time.time()
        self.dt = 0.0  # seconds (live until __exit__ freezes it)
        return self

    def __exit__(self, *exc) -> None:
        self.dt = time.time() - self.t0

    @property
    def dt_ms(self) -> float:
        return self.dt * 1e3


class SimTimer:
    """Context-manager stopwatch over a simulator clock (``domain="sim"``).
    ``dt`` is simulated seconds — deliberately the same attribute shape as
    :class:`WallTimer` so call sites swap domains without reshaping, but a
    distinct type so a reader (or grep) always knows which clock a number
    came from."""

    domain = "sim"

    def __init__(self, sim):
        self.sim = sim

    def __enter__(self) -> "SimTimer":
        self.t0 = self.sim.now
        self.dt = 0.0
        return self

    def __exit__(self, *exc) -> None:
        self.dt = (self.sim.now - self.t0) / 1e3  # sim ms -> "seconds"

    @property
    def dt_ms(self) -> float:
        return self.dt * 1e3
