"""Holoscope: deterministic telemetry for the Holon runtimes.

Three layers (docs/observability.md), all strictly passive — no RNG draws,
no wall-clock reads in sim paths, no simulator events that could perturb the
run being observed:

* **metrics registry** (obs/registry.py) — counters/gauges/histograms keyed
  by node/partition/class, snapshotted on sim-time intervals;
* **structured span tracing** (obs/records.py, obs/telemetry.py) — typed
  records of the full protocol lifecycle in a bounded ring buffer, exported
  to JSONL and Chrome trace-event format (Perfetto timelines);
* **protocol auditor** (obs/audit.py) — replays a trace and asserts the
  paper's invariants (exactly-once, monotone frontiers, causal domination,
  acked merges, bounded recovery), extracting time-to-recover and
  time-to-settle as first-class metrics;
* **critical-path analyzer** (obs/critpath.py) — reconstructs, per emitted
  window, the causal chain that gated the emission (fold → sync hops →
  merge → emit) and attributes its length to phases, per topology;
* **online monitor** (obs/monitor.py) — the auditor's invariants plus
  operational health alerts, incrementally in bounded memory over the live
  telemetry stream;
* **dataplane scopes** (obs/timing.py ``DATAPLANE_LAYERS``) — the layer
  names the jitted dataplane gives its device ops (docs/observability.md §7).

Determinism is the contract: a same-seed run exports a byte-identical
trace, which is what makes the trace auditable at all.
"""
from repro.obs.audit import AuditReport, audit, audit_harness
from repro.obs.critpath import (
    CritPath,
    CritPathReport,
    WatermarkTracker,
    analyze,
    analyze_harness,
)
from repro.obs.monitor import Alert, OnlineMonitor, replay
from repro.obs.records import (
    TraceBuffer,
    TraceEvent,
    event_json,
    from_jsonl,
    mkargs,
    to_chrome,
    to_jsonl,
)
from repro.obs.registry import Counter, Gauge, Histogram, MetricsRegistry, summary
from repro.obs.telemetry import Telemetry
from repro.obs.timing import DATAPLANE_LAYERS, SimTimer, WallTimer

__all__ = [
    "AuditReport",
    "audit",
    "audit_harness",
    "CritPath",
    "CritPathReport",
    "WatermarkTracker",
    "analyze",
    "analyze_harness",
    "Alert",
    "OnlineMonitor",
    "replay",
    "TraceBuffer",
    "TraceEvent",
    "event_json",
    "from_jsonl",
    "mkargs",
    "to_chrome",
    "to_jsonl",
    "Counter",
    "Gauge",
    "Histogram",
    "MetricsRegistry",
    "summary",
    "Telemetry",
    "DATAPLANE_LAYERS",
    "SimTimer",
    "WallTimer",
]
