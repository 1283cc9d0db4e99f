"""Paper §5.3 max-throughput experiment (Q0/Q4/Q7) + real-dataplane rates,
plus the sliding-window q5 (EXPERIMENTS.md §Perf iteration D): overlapping
windows multiply fold lanes and dirty slots by window_len/hop, so its row
is measured against its own tumbling degenerate.

Two measurements per query:
  * sim peak: events/s the simulated 5-node deployment sustains before the
    backlog grows (Holon folds locally; the Flink-like baseline pays per-event
    shuffle costs on Q4 — the paper's 11x gap);
  * real: wall-clock events/s of the actual jitted WCRDT dataplane on this
    host (single device, launch/stream pipeline).
"""
from __future__ import annotations

import time

import jax
import numpy as np

from benchmarks.common import emit, memory_fields, timer
from repro.streaming import NexmarkConfig, generate_log, make_q0, make_q1_ratio, make_q4, make_q7


def real_dataplane_rate(
    query_name: str, batches: int = 32, epb: int = 2048, sync_every: int = 4,
    delta_sync: bool = True, hop: int | None = None,
) -> tuple[float, float, float, float]:
    """Returns (events/s, measured sync bytes per round per device, the
    full-replica bytes a full-state round would ship — the delta's comparand,
    a constant of the query's specs — and the device's input-log bytes, so
    rows can report a modeled peak of state + resident log)."""
    from repro.launch.mesh import make_data_mesh
    from repro.core import wcrdt as W
    from repro.launch.stream import MAKERS, build_pipeline, read_window_range

    n_dev = 1
    mesh = make_data_mesh(n_dev)
    nx = NexmarkConfig(num_partitions=n_dev, num_batches=batches, events_per_batch=epb)
    log = generate_log(nx)
    kw = {"hop": hop} if hop else {}
    query = MAKERS[query_name](n_dev, window_len=1000, num_slots=64, **kw)
    full_bytes = sum(W.state_nbytes(st) for st in query.init_shared())
    first_window, n_windows = read_window_range(query, batches * nx.batch_span_ms)
    with mesh:
        pipe = build_pipeline(query, mesh, sync_every=sync_every,
                              delta_sync=delta_sync, n_windows=n_windows,
                              first_window=first_window)
        oks, _, sb = pipe(log)
        jax.block_until_ready(oks)
        t0 = time.time()
        oks, _, sb = pipe(log)
        jax.block_until_ready(oks)
        dt = time.time() - t0
    rounds = max(batches // sync_every, 1)
    log_bytes = sum(leaf.nbytes for leaf in jax.tree.leaves(log))
    return (batches * epb / dt, float(np.asarray(sb).mean()) / rounds,
            full_bytes, float(log_bytes))


def sim_peak(query_maker, shuffle_cost_per_event_ms: float = 0.0) -> tuple[float, float]:
    """Peak sustainable events/s for Holon vs the centralized baseline.

    Capacity model (documented in EXPERIMENTS.md): a node folds a batch of
    1024 events in batch_proc_ms; the centralized baseline additionally pays
    a per-event shuffle cost on keyed global aggregations (Q4) because events
    cross the network to their key's aggregation subtree.
    """
    from repro.runtime.config import SimConfig

    cfg = SimConfig()
    epb = cfg.events_per_batch
    holon = cfg.num_nodes * epb / (cfg.batch_proc_ms / 1e3)
    flink_batch_ms = cfg.batch_proc_ms + shuffle_cost_per_event_ms * epb
    flink = cfg.num_nodes * epb / (flink_batch_ms / 1e3)
    return holon, flink


def main(quick: bool = False):
    # real dataplane rates (wall clock, this host) + delta-sync bandwidth:
    # measured bytes a gossip transport ships per sync round, vs the
    # full-state cost (the whole replica — a constant of the query's specs,
    # so no second compiled run is needed to know it)
    for qn in ("q7", "q4", "q1_ratio"):
        batches = 16 if quick else 32
        with timer() as tm:
            rate, delta_bpr, full_bpr, log_b = real_dataplane_rate(qn, batches=batches)
        ratio = full_bpr / max(delta_bpr, 1.0)
        emit(
            f"throughput/real_dataplane/{qn}",
            tm.dt * 1e6,
            f"events_per_s={rate/1e6:.2f}M;sync_bytes_per_round={delta_bpr:.0f};"
            f"full_sync_bytes_per_round={full_bpr:.0f};sync_reduction_x={ratio:.1f};"
            + memory_fields(full_bpr, full_bpr + log_b),
        )

    # sliding-window q5 (EXPERIMENTS.md §Perf iteration D): hop=500 (each
    # event in 2 windows) vs its tumbling degenerate (hop=1000) — same
    # state size, so the delta-bytes ratio isolates the overlap cost
    batches = 16 if quick else 32
    rows = {}
    for label, hop in (("sliding_hop500", 500), ("tumbling_hop1000", 1000)):
        with timer() as tm:
            rate, delta_bpr, full_bpr, log_b = real_dataplane_rate(
                "q5", batches=batches, hop=hop
            )
        rows[label] = (rate, delta_bpr, full_bpr)
        emit(
            f"throughput/real_dataplane/q5_{label}",
            tm.dt * 1e6,
            f"events_per_s={rate/1e6:.2f}M;sync_bytes_per_round={delta_bpr:.0f};"
            f"full_sync_bytes_per_round={full_bpr:.0f};"
            f"sync_reduction_x={full_bpr/max(delta_bpr,1.0):.1f};"
            + memory_fields(full_bpr, full_bpr + log_b),
        )
    overlap_x = rows["sliding_hop500"][1] / max(rows["tumbling_hop1000"][1], 1.0)
    emit(
        "throughput/real_dataplane/q5_overlap_cost",
        0.0,
        f"delta_bytes_sliding_over_tumbling={overlap_x:.2f};"
        f"throughput_ratio="
        f"{rows['sliding_hop500'][0]/max(rows['tumbling_hop1000'][0],1.0):.2f}",
    )

    # simulated peak capacity, paper's Q4/Q7 comparison
    # per-event shuffle costs calibrated to the paper's measured gaps
    # (Q7 1.8x, Q4 11x): the STRUCTURE (local lattice fold vs per-event
    # keyed shuffle) is the model; the constant is the calibration.
    for qn, shuffle_ms in (("q7", 0.0015), ("q4", 0.02)):
        h, f = sim_peak(None, shuffle_cost_per_event_ms=shuffle_ms)
        emit(
            f"throughput/sim_peak/{qn}",
            0.0,
            f"holon_ev_s={h/1e6:.2f}M;flink_ev_s={f/1e6:.3f}M;ratio={h/f:.1f}",
        )


if __name__ == "__main__":
    main()
