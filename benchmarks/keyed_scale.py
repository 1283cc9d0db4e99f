"""Million-key keyed-state scaling sweep (docs/protocol.md §6).

Zipf-skewed per-auction bid counting over key domains C ∈ {1e4, 1e6, 1e7},
comparing

* **sharded** — the hash-partitioned keyed dataplane
  (``launch.stream.build_keyed_pipeline``): each device owns a
  ``[W, ceil(C/S)]`` key range, events ride one all-to-all per fold step,
  the sync plane ships only the ``[S]`` progress map;
* **dense**  — the replicate-everywhere ``build_pipeline`` + ``make_q5``
  path, where every device folds the full ``[W, S, C]`` keyed lattice and
  delta sync gathers replica stacks of it.

Rows report events/s, per-device state bytes, and shuffle/sync bytes per
round.  Dense runs above a host-memory budget are NOT attempted: the sync
gather alone would stack ``S`` full replicas per device (e.g. ~2 GB/device
at C=1e6 on 8 devices), so those rows carry ``skipped=1`` plus the byte
estimates that ruled them out — the sharded rows at the same (C, S) complete,
which is the point of the sweep.

On a TPU host every cell runs in this process on the real devices (a chip
belongs to one process: a child could not reach it).  On a CPU host the mesh
is 8 or 48 virtual devices, and each (C, S, mode) cell runs in a fresh child
with ``JAX_PLATFORMS=cpu``, because the virtual device count is fixed at jax
import time (same pattern as the multidevice tests).

Usage: PYTHONPATH=src python -m benchmarks.keyed_scale  (or via benchmarks.run)
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys

from benchmarks.common import emit, memory_fields

KEY_DOMAINS = (10_000, 1_000_000, 10_000_000)
MESH_SIZES = (8, 48)
KEY_SKEW = 1.1
WINDOW_LEN = 100
NUM_SLOTS = 8
SYNC_EVERY = 4
# dense-path budget: the delta-sync gather stacks S replicas of the [W, S, C]
# state on every device — refuse to attempt a dense cell whose modeled stack
# exceeds this (the host has ~1 core; thrashing tells us nothing new)
DENSE_BUDGET_BYTES = 1.5e9


def dense_state_bytes(n_dev: int, keys: int) -> float:
    """Per-device dense q5 keyed-lattice bytes: [W, S, C] f32."""
    return float(NUM_SLOTS * n_dev * keys * 4)


# one EventBatch lane on device: ts i32 + kind i32 + auction u32 + price f32
# + category i32 + bidder u32 + valid bool
EVENT_BYTES = 25


def modeled_peak_bytes(mode: str, n_dev: int, keys: int, batches: int,
                       epb: int, state_bytes: float) -> float:
    """Modeled per-device peak live bytes: resident window state + the
    device's input-log slice + the mode's dominant transient — sharded: the
    double-buffered ``[S, B]`` all-to-all routing matrices (ts/local i32 +
    mask bool, in + out); dense: the S-replica stack the sync gather
    materializes.  A model, like every byte counter here: CPU XLA reports
    no usable per-device temp stats to measure against (its compiled
    ``temp_size_in_bytes`` is 0), and the model is exactly what rules dense
    cells in or out of the sweep."""
    log_bytes = batches * epb * EVENT_BYTES
    if mode == "sharded":
        work = 2 * (4 + 4 + 1) * n_dev * epb
    else:
        work = state_bytes * n_dev
    return state_bytes + log_bytes + work


def measure_cell(S: int, C: int, mode: str, nb: int, epb: int,
                 key_skew: float = KEY_SKEW) -> dict:
    """One measured cell on this process's ``S`` devices."""
    import time

    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.core import wcrdt as W
    from repro.core.window import as_assigner
    from repro.launch.mesh import make_data_mesh
    from repro.launch.stream import (
        MAKERS, build_keyed_pipeline, build_pipeline, default_fold_schedule,
        read_window_range,
    )
    from repro.streaming.generator import NexmarkConfig, generate_log

    assert len(jax.devices()) == S, (len(jax.devices()), S)
    nx = NexmarkConfig(num_partitions=S, num_batches=nb, events_per_batch=epb,
                       num_auctions=C, key_skew=key_skew)
    log = generate_log(nx)
    horizon = nb * nx.batch_span_ms
    rounds = max(nb // SYNC_EVERY, 1)

    if mode == "sharded":
        shards = W.KeyShards(C, S)
        mesh = make_data_mesh(S)
        assigner = as_assigner(WINDOW_LEN, WINDOW_LEN // 2)
        spec = W.wgcounter_sharded(WINDOW_LEN, NUM_SLOTS, S, shards,
                                   assigner=assigner)
        closed = int(assigner.first_dirty_wid(horizon))
        n_win = max(1, min(closed, 2))
        first = max(0, closed - n_win)
        table = jnp.asarray(shards.key_table())
        sched = jnp.asarray(default_fold_schedule(S, nb))
        wm = jnp.ones((rounds,), bool)
        with mesh:
            pipe = build_keyed_pipeline(
                mesh, shards, window_len=WINDOW_LEN, num_slots=NUM_SLOTS,
                sync_every=SYNC_EVERY, n_windows=n_win, first_window=first,
            )
            oks, vals, shuf, sync = pipe(log, table, sched, wm)
            jax.block_until_ready(oks)
            t0 = time.time()
            oks, vals, shuf, sync = pipe(log, table, sched, wm)
            jax.block_until_ready(oks)
            dt = time.time() - t0
        out = {
            "events_per_s": S * nb * epb / dt,
            "state_bytes_per_dev": float(W.state_nbytes(spec.zero())),
            "shuffle_bytes_per_round": float(np.asarray(shuf).mean()) / rounds,
            "sync_bytes_per_round": float(np.asarray(sync).mean()) / rounds,
            "ok_windows": int(np.asarray(oks)[0].sum()),
            "width": shards.width,
        }
    else:  # dense
        mesh = make_data_mesh(S)
        query = MAKERS["q5"](S, window_len=WINDOW_LEN, num_slots=NUM_SLOTS,
                             num_auctions=C)
        first, n_win = read_window_range(query, horizon)
        with mesh:
            pipe = build_pipeline(query, mesh, SYNC_EVERY,
                                  n_windows=n_win, first_window=first)
            oks, vals, sb = pipe(log)
            jax.block_until_ready(oks)
            t0 = time.time()
            oks, vals, sb = pipe(log)
            jax.block_until_ready(oks)
            dt = time.time() - t0
        out = {
            "events_per_s": S * nb * epb / dt,
            "state_bytes_per_dev": float(
                sum(W.state_nbytes(st) for st in query.init_shared())
            ),
            "shuffle_bytes_per_round": 0.0,  # dense path never shuffles events
            "sync_bytes_per_round": float(np.asarray(sb).mean()) / rounds,
            "ok_windows": int(np.asarray(oks)[0].sum()),
            "width": C,
        }
    return out


def _on_tpu() -> bool:
    import jax

    return jax.default_backend() == "tpu"


def _run_cell(n_dev: int, keys: int, mode: str, batches: int, epb: int) -> dict:
    if _on_tpu():
        return measure_cell(n_dev, keys, mode, batches, epb)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={n_dev}"
    cmd = [
        sys.executable, "-m", "benchmarks.keyed_scale", "--worker",
        "--n-dev", str(n_dev), "--keys", str(keys), "--mode", mode,
        "--batches", str(batches), "--epb", str(epb),
        "--key-skew", str(KEY_SKEW),
    ]
    r = subprocess.run(cmd, capture_output=True, text=True, timeout=3600, env=env)
    for line in r.stdout.splitlines():
        if line.startswith("KEYED_RESULT "):
            return json.loads(line[len("KEYED_RESULT "):])
    raise RuntimeError(
        f"worker {mode} C={keys} S={n_dev} failed:\n"
        f"stdout={r.stdout[-1500:]}\nstderr={r.stderr[-1500:]}"
    )


def _label(keys: int, n_dev: int) -> str:
    return f"C{keys:.0e}_dev{n_dev}".replace("e+0", "e")


def main(quick: bool = False) -> None:
    from benchmarks.common import timer

    import jax

    batches, epb = (8, 128) if quick else (8, 256)
    meshes = (len(jax.devices()),) if _on_tpu() else MESH_SIZES
    state_by_c: dict[int, dict[int, float]] = {}
    for keys in KEY_DOMAINS:
        for n_dev in meshes:
            lbl = _label(keys, n_dev)
            with timer() as tm:
                res = _run_cell(n_dev, keys, "sharded", batches, epb)
            state_by_c.setdefault(keys, {})[n_dev] = res["state_bytes_per_dev"]
            emit(
                f"keyed/sharded/{lbl}",
                tm.dt * 1e6,
                f"events_per_s={res['events_per_s']:.0f};"
                + memory_fields(
                    res["state_bytes_per_dev"],
                    modeled_peak_bytes("sharded", n_dev, keys, batches, epb,
                                       res["state_bytes_per_dev"]),
                )
                + f";shuffle_bytes_per_round={res['shuffle_bytes_per_round']:.0f}"
                f";sync_bytes_per_round={res['sync_bytes_per_round']:.0f}"
                f";width={res['width']};ok_windows={res['ok_windows']}",
            )

            # dense comparand, only inside the memory budget: the sync
            # gather stacks S replicas of the per-device state
            est_state = dense_state_bytes(n_dev, keys)
            est_stack = est_state * n_dev
            if est_stack > DENSE_BUDGET_BYTES:
                emit(
                    f"keyed/dense/{lbl}", 0.0,
                    "skipped=1;"
                    + memory_fields(
                        est_state,
                        modeled_peak_bytes("dense", n_dev, keys, batches,
                                           epb, est_state),
                    )
                    + f";est_sync_stack_bytes={est_stack:.0f}",
                )
                continue
            with timer() as tm:
                res = _run_cell(n_dev, keys, "dense", batches, epb)
            emit(
                f"keyed/dense/{lbl}",
                tm.dt * 1e6,
                f"events_per_s={res['events_per_s']:.0f};"
                + memory_fields(
                    res["state_bytes_per_dev"],
                    modeled_peak_bytes("dense", n_dev, keys, batches, epb,
                                       res["state_bytes_per_dev"]),
                )
                + f";sync_bytes_per_round={res['sync_bytes_per_round']:.0f}"
                f";ok_windows={res['ok_windows']}",
            )

    # the headline scaling law: per-device state shrinks ~1/n_dev
    for keys, by_dev in state_by_c.items():
        if len(by_dev) == 2:
            lo, hi = min(by_dev), max(by_dev)
            emit(
                f"keyed/state_scaling/C{keys:.0e}".replace("e+0", "e"),
                0.0,
                f"dev{lo}_over_dev{hi}={by_dev[lo]/by_dev[hi]:.2f};"
                f"ideal={hi/lo:.2f}",
            )


if __name__ == "__main__":
    ap = argparse.ArgumentParser()
    ap.add_argument("--worker", action="store_true")
    ap.add_argument("--n-dev", type=int, default=8)
    ap.add_argument("--keys", type=int, default=10_000)
    ap.add_argument("--mode", choices=("sharded", "dense"), default="sharded")
    ap.add_argument("--batches", type=int, default=8)
    ap.add_argument("--epb", type=int, default=256)
    ap.add_argument("--key-skew", type=float, default=KEY_SKEW)
    ap.add_argument("--quick", action="store_true")
    args = ap.parse_args()
    if args.worker:
        res = measure_cell(args.n_dev, args.keys, args.mode, args.batches,
                           args.epb, args.key_skew)
        print("KEYED_RESULT " + json.dumps(res))
    else:
        print("name,us_per_call,derived")
        main(quick=args.quick)
