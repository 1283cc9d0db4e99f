"""Production streaming driver: the whole Holon pipeline as ONE shard_map
program over the ``data`` mesh axis — partition-per-device, batched folds,
background sync as a lattice collective, windows emitted from the device.

This is the TPU-native deployment path (DESIGN.md §3): the discrete-event
harness in repro/runtime measures coordination behaviour; this driver is the
dataplane that would actually run on a pod, and what bench_throughput
measures for raw events/s.

Background sync is delta-state by default (DESIGN.md §6): each device carries
the shared post-last-sync baseline ``(folded, progress)``, extracts only the
ring slots its folds dirtied since then (``W.delta_since``), and the deltas
are exchanged and joined by the dirty-slot-gated merge kernel — slots with
``slot_wid < 0`` are skipped, not reduced.  The per-round shipped bytes
(``W.delta_nbytes``, what a real gossip transport would put on the wire
instead of the whole ring) come back as a pipeline output so the win is
measured, not asserted.  ``--full-sync`` restores the full-state lattice
all-reduce for comparison.

Usage:
  PYTHONPATH=src python -m repro.launch.stream --query q7 --batches 64
  (optionally XLA_FLAGS=--xla_force_host_platform_device_count=8 for a
   multi-device run on CPU)
"""
from __future__ import annotations

import argparse
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.core import wcrdt as W
from repro.core.window import as_assigner
from repro.launch.compile_cache import enable_compile_cache
from repro.launch.mesh import make_data_mesh
from repro.obs.timing import DATAPLANE_LAYERS, WallTimer
from repro.streaming.events import KIND_BID, EventBatch
from repro.streaming.generator import NexmarkConfig, batch_watermark, generate_log
from repro.streaming.queries import (
    Query,
    make_q0,
    make_q1_ratio,
    make_q4,
    make_q5,
    make_q7,
)

# the layer scopes every device op of the dataplane runs under
# (docs/observability.md §7)
SHUFFLE, FOLD, SYNC, READ = DATAPLANE_LAYERS


def _vary(tree):
    """Mark freshly built (device-invariant) replica state as varying over
    ``data`` — what shard_map's vma check requires of a per-device carry."""
    return jax.tree.map(lambda x: lax.pcast(x, ("data",), to="varying"), tree)


# every query the benchmarks import is runnable on the dataplane, including
# the shared-state-free q0 (sync rounds no-op) and the sliding-window q5
MAKERS = {
    "q0": make_q0,
    "q1_ratio": make_q1_ratio,
    "q4": make_q4,
    "q5": make_q5,
    "q7": make_q7,
}


def build_pipeline(
    query: Query, mesh, sync_every: int, delta_sync: bool = True,
    n_windows: int = 64, first_window: int = 0,
):
    """Returns a jitted fn: (log slice per device) -> (oks, vals, sync_bytes).

    Scans batches; every ``sync_every`` folds runs one background-sync
    exchange (delta-state by default, full-state all-reduce with
    ``delta_sync=False``); finally reads window ids ``first_window ..
    first_window + n_windows`` (overlapping assigners close a window every
    ``hop``, not every ``window_len``, and long runs evict the oldest ids
    from the ring — size and offset via ``read_window_range``).  A query
    with no shared state (q0) simply skips the exchange: the per-spec loop
    is empty and ``sync_bytes`` stays 0.  ``sync_bytes`` is each device's
    total modeled sync traffic in bytes.
    """

    def node_fn(log: EventBatch):
        p = jax.lax.axis_index("data")
        # mark replica state device-varying from the start (shard_map vma)
        shared = _vary(query.init_shared())
        local = _vary(query.init_local())
        baselines = tuple(W.baseline_of(st) for st in shared)
        sync_bytes = _vary(jnp.float32(0.0))

        def fold_one(carry, batch):
            # batch_idx advances the folded frontier — what delta_since diffs
            shared, local, idx = carry
            with jax.named_scope(FOLD):
                shared, local = query.fold(shared, local, batch, p, batch_idx=idx)
            return (shared, local, idx + 1), None

        def sync_chunk(carry, chunk):
            # sync_every folds, then one background-sync exchange
            shared, local, idx, baselines, sync_bytes = carry
            (shared, local, idx), _ = jax.lax.scan(
                fold_one, (shared, local, idx), chunk
            )
            synced, new_base = [], []
            with jax.named_scope(SYNC):
                for spec, st, (bf, bp) in zip(query.shared_specs, shared, baselines):
                    if delta_sync:
                        st, shipped = W.delta_axis_join(spec, st, bf, bp, "data")
                    else:
                        st = W.axis_join(spec, st, "data")
                        shipped = jnp.float32(W.state_nbytes(st))
                    sync_bytes = sync_bytes + shipped
                    synced.append(st)
                    new_base.append(W.baseline_of(st))
            return (tuple(synced), local, idx, tuple(new_base), sync_bytes), None

        log0 = jax.tree.map(lambda x: x[0], log)  # strip device-local lead dim
        nb = jax.tree.leaves(log0)[0].shape[0]
        n_outer = nb // sync_every
        chunked = jax.tree.map(
            lambda x: x[: n_outer * sync_every].reshape(
                n_outer, sync_every, *x.shape[1:]
            ),
            log0,
        )
        idx0 = _vary(jnp.int32(0))
        (shared, local, _, _, sync_bytes), _ = jax.lax.scan(
            sync_chunk, (shared, local, idx0, baselines, sync_bytes), chunked
        )

        def read(w):
            v, ok = query.read(shared, local, w)
            return jnp.where(ok, 1.0, 0.0), v

        with jax.named_scope(READ):
            oks, vals = jax.vmap(read)(first_window + jnp.arange(n_windows))
        return oks[None], vals[None], sync_bytes[None]

    log_specs = jax.tree.map(lambda _: P("data"), EventBatch(*([0] * 7)))
    return jax.jit(
        jax.shard_map(
            node_fn,
            mesh=mesh,
            in_specs=(log_specs,),
            out_specs=(P("data"), P("data"), P("data")),
        )
    )


def default_fold_schedule(num_shards: int, num_batches: int) -> np.ndarray:
    """Failure-free fold schedule for :func:`build_keyed_pipeline`: i32
    ``[num_shards, num_batches]`` — every device folds batch ``t`` at step
    ``t``.  Crash-recovery tests splice a replay (``[0..k, j..k, k+1..]``)
    into a device's row; the ``folded`` frontier makes re-folds no-ops, so
    the splice reproduces deterministic replay recovery byte-for-byte
    (docs/protocol.md §6)."""
    return np.tile(np.arange(num_batches, dtype=np.int32), (num_shards, 1))


def build_keyed_pipeline(
    mesh, shards: W.KeyShards, *, window_len: int = 1000,
    num_slots: int = 16, hop: int | None = None, sync_every: int = 4,
    n_windows: int = 8, first_window: int = 0,
):
    """Hash-sharded keyed dataplane (docs/protocol.md §6): per-auction bid
    counts + cross-shard hot-item reads over a key domain too large for any
    single device's dense ``[W, C]`` state.

    Jitted signature: ``(log, key_table, sched, wm_sync) -> (oks, vals,
    shuffle_bytes, sync_bytes)`` where

    * ``log`` — EventBatch ``[S, num_batches, B]``, sharded over ``data``;
    * ``key_table`` — ``shards.key_table()``, sharded over ``data`` (each
      device keeps only its own inverse row);
    * ``sched`` — replicated i32 ``[S, n_steps]`` fold schedule
      (:func:`default_fold_schedule`); column ``t`` names the batch index
      each device folds at step ``t``, so every device can label the lanes
      it RECEIVES with the sender's ``batch_idx`` without shipping it;
    * ``wm_sync`` — replicated bool ``[n_steps // sync_every]``; round
      ``r``'s watermark exchange runs only where True (False = partitioned:
      progress maps diverge and windows stall until heal).

    Unlike :func:`build_pipeline` (replicate-everywhere + lattice join),
    keys are ROUTED: device ``s`` owns key range ``{k : shards.shard_of(k)
    == s}``, each step all-to-alls the masked ``[S, B]`` routing matrix so
    every owner folds exactly the lanes it owns, and each device's state is
    ``[W, ceil(C/S)]`` — per-device state bytes scale ~1/S.  Ownership is
    exclusive, so the sync plane ships ONLY the ``[S]`` progress map (no
    slot deltas to reconcile); both modeled byte counters come back as
    outputs.  Final read: :func:`W.shard_topk_read` per window — one
    ``[S]``-candidate gather, never the full key range.
    """
    S = shards.num_shards
    assigner = as_assigner(window_len, hop if hop else window_len // 2)
    spec = W.wgcounter_sharded(window_len, num_slots, S, shards, assigner=assigner)
    wm_bytes = jnp.float32(S * 4)  # the [S] i32 progress map, per round

    def node_fn(log: EventBatch, key_table, sched, wm_sync):
        me = jax.lax.axis_index("data")
        state = _vary(spec.zero())
        log0 = jax.tree.map(lambda x: x[0], log)  # [num_batches, B] leaves
        table0 = key_table[0]  # u32 [width]; in_specs already mark it varying
        B = log0.ts.shape[1]
        rows = jnp.arange(S, dtype=jnp.int32)[:, None]  # [S, 1]
        a2a = lambda x: jax.lax.all_to_all(
            x, "data", split_axis=0, concat_axis=0, tiled=True
        )

        def fold_step(carry, sched_col):
            state, shuffle_bytes = carry
            with jax.named_scope(SHUFFLE):
                batch = jax.tree.map(lambda x: x[sched_col[me]], log0)
                is_bid = batch.valid & (batch.kind == KIND_BID)
                owner = shards.shard_of(batch.auction)
                local = shards.local_of(batch.auction)
                # routing matrix: row s = my lanes owned by device s
                m_sb = is_bid[None, :] & (owner[None, :] == rows)  # [S, B]
                r_ts = a2a(jnp.broadcast_to(batch.ts[None, :], (S, B)))
                r_loc = a2a(jnp.broadcast_to(local[None, :], (S, B)))
                r_mask = a2a(m_sb)
                # wire model: off-device lanes ship (ts, local) = 8 bytes each
                sent = m_sb & (rows != me)
                shuffle_bytes = shuffle_bytes + jnp.sum(sent) * jnp.float32(8.0)
            with jax.named_scope(FOLD):
                # after the exchange, row r holds lanes from source device r,
                # folded at r's scheduled batch index (sched is replicated)
                src = jnp.broadcast_to(rows, (S, B)).reshape(-1)
                bi = jnp.broadcast_to(sched_col[:, None], (S, B)).reshape(-1)
                state = W.insert(
                    spec, state, src, r_ts.reshape(-1), r_mask.reshape(-1),
                    batch_idx=bi, amounts=jnp.ones((S * B,), jnp.float32),
                    keys=r_loc.reshape(-1),
                )
                state = W.increment_watermark(spec, state, me, batch_watermark(batch))
            return (state, shuffle_bytes), None

        def sync_round(carry, round_in):
            chunk, wm_on = round_in
            state, shuffle_bytes, sync_bytes = carry
            (state, shuffle_bytes), _ = jax.lax.scan(
                fold_step, (state, shuffle_bytes), chunk
            )
            with jax.named_scope(SYNC):
                merged = jnp.where(wm_on, jax.lax.pmax(state.progress, "data"),
                                   state.progress)
                state = dataclasses.replace(state, progress=merged)
                sync_bytes = sync_bytes + jnp.where(wm_on, wm_bytes, 0.0)
            return (state, shuffle_bytes, sync_bytes), None

        n_steps = sched.shape[1]
        n_rounds = n_steps // sync_every
        chunks = (
            sched.T[: n_rounds * sync_every]
            .reshape(n_rounds, sync_every, S)
            .astype(jnp.int32)
        )
        zero = _vary(jnp.float32(0.0))
        (state, shuffle_bytes, sync_bytes), _ = jax.lax.scan(
            sync_round, (state, zero, zero), (chunks, wm_sync[:n_rounds])
        )

        def read(w):
            (cnt, key), ok = W.shard_topk_read(
                spec, state, w, table0, shards.num_keys, "data", k=1
            )
            val = jnp.stack([cnt[0], key[0].astype(jnp.float32)])
            return jnp.where(ok, 1.0, 0.0), val

        with jax.named_scope(READ):
            oks, vals = jax.vmap(read)(first_window + jnp.arange(n_windows))
        return oks[None], vals[None], shuffle_bytes[None], sync_bytes[None]

    log_specs = jax.tree.map(lambda _: P("data"), EventBatch(*([0] * 7)))
    return jax.jit(
        jax.shard_map(
            node_fn,
            mesh=mesh,
            in_specs=(log_specs, P("data"), P(), P()),
            out_specs=(P("data"), P("data"), P("data"), P("data")),
        )
    )


def read_window_range(query: Query, horizon_ts: float) -> tuple[int, int]:
    """``(first_wid, n_windows)`` worth reading after a ``horizon_ts`` run:
    the LAST ring-residency-capped window ids closing within the horizon —
    on long runs the earliest ids have been evicted from the ring and would
    read not-ok, so the range ends at the horizon rather than starting at 0.

    Residency is anchored at the NEWEST assigned wid, which under overlap
    runs ``K - 1`` ahead of the newest *complete* one — the usable span is
    ``num_slots - (K - 1)`` complete ids plus the one still-open id at the
    top of the range (reads not-ok; kept so the count is horizon-exact).
    """
    a = query.assigner
    closed = int(a.first_dirty_wid(horizon_ts))
    # residency is bounded by the SMALLEST ring a read touches — shared
    # AND local (q1_ratio-style reads consult both)
    rings = [st.num_slots for st in query.shared_specs]
    if query.local_spec is not None:
        rings.append(query.local_spec.num_slots)
    cap = min(rings) if rings else 64
    n = max(1, min(closed + 1, cap - (a.windows_per_event - 1)))
    return max(0, closed + 1 - n), n


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--query", default="q7", choices=sorted(MAKERS))
    ap.add_argument("--batches", type=int, default=64)
    ap.add_argument("--events-per-batch", type=int, default=1024)
    ap.add_argument("--window-len", type=int, default=1000)
    ap.add_argument("--hop", type=int, default=0,
                    help="hopping-window hop; 0 = the query's default "
                         "(tumbling, except q5 which slides by window/2)")
    ap.add_argument("--sync-every", type=int, default=4)
    ap.add_argument("--full-sync", action="store_true",
                    help="full-state lattice all-reduce instead of delta sync")
    args = ap.parse_args(argv)
    if not 1 <= args.sync_every <= args.batches:
        ap.error(f"--sync-every must be in [1, --batches]; got {args.sync_every}")

    enable_compile_cache()
    devices = jax.devices()
    n_dev = len(devices)
    mesh = make_data_mesh(n_dev)
    nx = NexmarkConfig(
        num_partitions=n_dev,
        num_batches=args.batches,
        events_per_batch=args.events_per_batch,
    )
    log = generate_log(nx)
    kw = {"hop": args.hop} if args.hop else {}
    query = MAKERS[args.query](n_dev, window_len=args.window_len, num_slots=64, **kw)
    horizon_ts = args.batches * nx.batch_span_ms
    first_window, n_windows = read_window_range(query, horizon_ts)

    with mesh:
        pipe = build_pipeline(query, mesh, args.sync_every,
                              delta_sync=not args.full_sync,
                              n_windows=n_windows, first_window=first_window)
        oks, vals, sb = pipe(log)  # compile+run
        jax.block_until_ready(oks)
        # wall-clock domain, explicitly: the dataplane is the one place this
        # driver may read the host clock (docs/observability.md §1)
        with WallTimer() as tm:
            oks, vals, sb = pipe(log)
            jax.block_until_ready(oks)
        dt = tm.dt

    total_events = n_dev * args.batches * args.events_per_batch
    done = int(np.asarray(oks).sum()) // n_dev
    rounds = max(args.batches // args.sync_every, 1)
    sync_per_round = float(np.asarray(sb).mean()) / rounds
    a = query.assigner
    # the device is named on the same line as the rate: a CPU run's
    # throughput is never mistaken for the chip's
    print(
        f"platform={devices[0].platform} device_kind={devices[0].device_kind} "
        f"devices={n_dev} events={total_events} wall={dt*1e3:.1f}ms "
        f"throughput={total_events/dt/1e6:.2f}M ev/s "
        f"window={a.window_len}/hop={a.hop} complete_windows={done} "
        f"sync={'full' if args.full_sync else 'delta'} "
        f"sync_bytes_per_round={sync_per_round:.0f}"
    )
    return total_events / dt


if __name__ == "__main__":
    main()
