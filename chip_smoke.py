#!/usr/bin/env python3
"""Chip smoke test: the Holon dataplane end to end on TPU, at Nexmark scale.

One process owns the chip(s) and runs the production dataplane programs of
``repro.launch.stream`` through their normal builders, checking every output
against a plain numpy computation over the same generated log:

  (a) device check — fails unless JAX's platform is ``tpu``; never falls
      back to the CPU;
  (b) replicated dataplane (``build_pipeline``): q7 (highest bids, TopK
      lattice, generic join) and q4 (average price per category, Reduce
      lattices, Pallas ``gated_delta_merge``), each with delta sync and full
      sync — 64 batches x 65,536 events per device (4.2 M events, ~105 MB of
      log per device), Nexmark's 10 s tumbling windows closing within the
      run; delta and full sync must agree byte for byte, and both must equal
      a numpy per-window group-by;
  (c) keyed dataplane (``build_keyed_pipeline``): q5 hot items over 1e7
      auction ids with zipf 1.1 key skew, 10 s windows sliding by 5 s, a
      16-slot ring (a [16, 1e7] f32 state, 640 MB), 16 batches x 65,536
      events — equal to a numpy bincount and to ``q5_hot_oracle``.

Each phase compiles ahead of time, warms up once, then times one call with
``block_until_ready``; its line names the device and gives events/s, compile
seconds and peak device bytes (``memory_stats``).

  python chip_smoke.py                # one chip: (a), (b), (c)
  python chip_smoke.py --four-chips   # four chips: (b) on a 4-device mesh and
                                      # (c) at 1e8 keys ([16, 2.5e7] per chip)

The last line of stdout is ``{"ok": true, "device": {...}}``; any failure or
mismatch exits non-zero before it is printed.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
from jax.sharding import NamedSharding  # noqa: E402
from jax.sharding import PartitionSpec as P  # noqa: E402

from repro.core import wcrdt as W  # noqa: E402
from repro.core.window import as_assigner  # noqa: E402
from repro.launch.compile_cache import enable_compile_cache  # noqa: E402
from repro.launch.mesh import make_data_mesh  # noqa: E402
from repro.launch.stream import (  # noqa: E402
    MAKERS,
    build_keyed_pipeline,
    build_pipeline,
    default_fold_schedule,
    read_window_range,
)
from repro.streaming.events import KIND_BID  # noqa: E402
from repro.streaming.generator import NUM_CATEGORIES, NexmarkConfig, generate_log  # noqa: E402
from repro.streaming.queries import q5_hot_oracle  # noqa: E402

SEED = 0
WINDOW_MS = 10_000  # Nexmark's default window size (10 s)
HOP_MS = 5_000  # Nexmark's default q5 window period (5 s)
EVENTS_PER_BATCH = 65_536
REPLICATED_BATCHES = 64
REPLICATED_SLOTS = 64
KEYED_BATCHES = 16
KEYED_SLOTS = 16
KEY_SKEW = 1.1
SYNC_EVERY = 4
TOPK = 8
# q4's per-category sums add ~2e4 f32 prices per window in an order the
# device chooses; sqrt(2e4) * 2**-23 ~ 2e-5, so 1e-4 covers the reordering
# and is far below what one dropped or doubled batch moves an average (~1e-2)
Q4_RTOL = 1e-4


def check_devices(count: int) -> list:
    devices = jax.devices()
    d = devices[0]
    print(f"[a] jax.devices()={devices}")
    print(f"[a] platform={d.platform} device_kind={d.device_kind} count={len(devices)}")
    if d.platform != "tpu":
        sys.exit(f"chip_smoke: no TPU found (JAX platform is {d.platform!r}); nothing was run")
    if len(devices) != count:
        sys.exit(f"chip_smoke: this mode needs {count} TPU devices, JAX sees {len(devices)}")
    return devices


def _peaks(devices) -> list:
    return [d.memory_stats()["peak_bytes_in_use"] for d in devices]


def timed(tag: str, devices, fn, args, n_events: int):
    """Compile ahead of time, warm up once, time one call; returns the host
    outputs and the per-device bytes of the program's temporaries."""
    t0 = time.perf_counter()
    compiled = fn.lower(*args).compile()
    compile_s = time.perf_counter() - t0
    temp = compiled.memory_analysis().temp_size_in_bytes
    jax.block_until_ready(compiled(*args))
    t0 = time.perf_counter()
    out = compiled(*args)
    jax.block_until_ready(out)
    run_s = time.perf_counter() - t0
    d = devices[0]
    print(
        f"[{tag}] device={d.platform}:{d.device_kind}x{len(devices)} "
        f"events={n_events} compile_s={compile_s} run_s={run_s} "
        f"events_per_s={n_events / run_s} temp_bytes_per_device={temp} "
        f"peak_bytes_in_use={_peaks(devices)}"
    )
    return jax.device_get(out), temp


def _fail(msg: str):
    raise AssertionError(msg)


def _complete(h, end_ts: np.ndarray) -> np.ndarray:
    """Windows whose end the final global watermark has passed."""
    last = np.where(h.valid, h.ts, np.iinfo(np.int32).min).reshape(h.ts.shape[0], -1)
    return end_ts <= last.max(axis=1).min()


def _check_oks(tag, oks, want):
    if want.sum() < 1:
        _fail(f"[{tag}] no window closes within the run")
    if not (oks == want.astype(oks.dtype)[None]).all():
        _fail(f"[{tag}] window completion {oks.tolist()} != watermark rule {want.tolist()}")


def q4_reference(h, wids: np.ndarray) -> np.ndarray:
    """Average bid price per category per tumbling window, in float64."""
    bid = h.valid & (h.kind == KIND_BID)
    wid = (h.ts[bid] // WINDOW_MS).astype(np.int64)
    cat, price = h.category[bid].astype(np.int64), h.price[bid].astype(np.float64)
    inside = (wid >= wids[0]) & (wid <= wids[-1])
    cell = (wid[inside] - wids[0]) * NUM_CATEGORIES + cat[inside]
    n = len(wids) * NUM_CATEGORIES
    sums = np.bincount(cell, weights=price[inside], minlength=n)
    cnts = np.bincount(cell, minlength=n)
    return (sums / np.maximum(cnts, 1)).reshape(len(wids), NUM_CATEGORIES)


def q7_reference(h, wids: np.ndarray) -> np.ndarray:
    """Top-k distinct (price, auction) bids per tumbling window, descending
    by (price, auction), padded with (-inf, 0): ``[vals..., ids...]``."""
    bid = h.valid & (h.kind == KIND_BID)
    wid = h.ts[bid] // WINDOW_MS
    price, auction = h.price[bid], h.auction[bid]
    out = np.zeros((len(wids), 2 * TOPK), np.float32)
    for i, w in enumerate(wids):
        m = wid == w
        pairs = np.unique(np.stack([price[m].astype(np.float64), auction[m]], 1), axis=0)
        top = pairs[::-1][:TOPK]  # unique() sorts ascending by (price, id)
        vals = np.full(TOPK, -np.inf, np.float32)
        ids = np.zeros(TOPK, np.float32)
        vals[: len(top)], ids[: len(top)] = top[:, 0], top[:, 1]
        out[i] = np.concatenate([vals, ids])
    return out


def phase_replicated(devices) -> None:
    n = len(devices)
    mesh = make_data_mesh(n)
    nx = NexmarkConfig(num_partitions=n, num_batches=REPLICATED_BATCHES,
                       events_per_batch=EVENTS_PER_BATCH, seed=SEED)
    log = jax.device_put(generate_log(nx), NamedSharding(mesh, P("data")))
    h = jax.device_get(log)
    n_events = n * REPLICATED_BATCHES * EVENTS_PER_BATCH
    print(f"[b] log: {n} x {REPLICATED_BATCHES} x {EVENTS_PER_BATCH} events, "
          f"{sum(x.nbytes for x in jax.tree.leaves(h)) // n} bytes per device")
    for qname, reference in (("q7", q7_reference), ("q4", q4_reference)):
        query = MAKERS[qname](n, window_len=WINDOW_MS, num_slots=REPLICATED_SLOTS)
        first, n_win = read_window_range(query, REPLICATED_BATCHES * nx.batch_span_ms)
        wids = first + np.arange(n_win)
        outs = {}
        for sync in ("delta", "full"):
            pipe = build_pipeline(query, mesh, SYNC_EVERY, delta_sync=sync == "delta",
                                  n_windows=n_win, first_window=first)
            outs[sync], _ = timed(f"b:{qname}:{sync}", devices, pipe, (log,), n_events)
        (oks, vals, _), (oks_f, vals_f, _) = outs["delta"], outs["full"]
        if not (np.array_equal(oks, oks_f) and vals.tobytes() == vals_f.tobytes()):
            _fail(f"[b:{qname}] delta-sync and full-sync outputs differ")
        want_ok = _complete(h, (wids + 1) * WINDOW_MS)
        _check_oks(f"b:{qname}", oks, want_ok)
        want = reference(h, wids[want_ok])
        got = vals[:, want_ok]
        for d in range(n):
            if qname == "q7":
                good = np.array_equal(got[d], want)
            else:
                good = np.allclose(got[d], want, rtol=Q4_RTOL, atol=0)
            if not good:
                _fail(f"[b:{qname}] device {d} differs from the numpy reference:\n"
                      f"got={got[d][:2]}\nwant={want[:2]}")
        print(f"[b:{qname}] delta == full byte for byte; {int(want_ok.sum())} closed "
              f"windows x {n} replicas match the numpy reference")


def phase_keyed(devices, num_keys: int) -> None:
    S = len(devices)
    mesh = make_data_mesh(S)
    sharding = NamedSharding(mesh, P("data"))
    shards = W.KeyShards(num_keys, S)
    nx = NexmarkConfig(num_partitions=S, num_batches=KEYED_BATCHES,
                       events_per_batch=EVENTS_PER_BATCH, num_auctions=num_keys,
                       key_skew=KEY_SKEW, seed=SEED)
    log = jax.device_put(generate_log(nx), sharding)
    table = jax.device_put(shards.key_table(), sharding)
    assigner = as_assigner(WINDOW_MS, HOP_MS)
    closed = int(assigner.first_dirty_wid(KEYED_BATCHES * nx.batch_span_ms))
    n_win = min(closed, 4)
    first = closed - n_win
    wids = first + np.arange(n_win)
    sched = np.asarray(default_fold_schedule(S, KEYED_BATCHES))
    wm = np.ones(KEYED_BATCHES // SYNC_EVERY, bool)
    pipe = build_keyed_pipeline(
        mesh, shards, window_len=WINDOW_MS, num_slots=KEYED_SLOTS, hop=HOP_MS,
        sync_every=SYNC_EVERY, n_windows=n_win, first_window=first,
    )
    state_bytes = KEYED_SLOTS * shards.width * 4
    print(f"[c] keys={num_keys} skew={KEY_SKEW} shards={S} state per device "
          f"[{KEYED_SLOTS}, {shards.width}] f32 = {state_bytes} bytes")
    (oks, vals, _, _), temp = timed(f"c:q5:keys={num_keys}", devices, pipe,
                                    (log, table, sched, wm),
                                    S * KEYED_BATCHES * EVENTS_PER_BATCH)
    if temp < state_bytes:
        _fail(f"[c] the program's {temp} bytes of temporaries per device cannot "
              f"hold a {state_bytes}-byte state shard")
    # placement: every device holds its own slice of the log and key table
    # (state on device 0 alone would show as one device holding it all)
    shard_bytes = {}
    for arr in (*jax.tree.leaves(log), table):
        for s in arr.addressable_shards:
            shard_bytes[s.device] = shard_bytes.get(s.device, 0) + s.data.nbytes
    for d in devices:
        print(f"[c] {d}: input shard bytes={shard_bytes.get(d, 0)} "
              f"memory_stats={d.memory_stats()}")
    if sorted(shard_bytes.values()) != [shard_bytes[devices[0]]] * S:
        _fail(f"[c] inputs are not spread evenly over the {S} devices: {shard_bytes}")

    h = jax.device_get(log)
    _check_oks("c:q5", oks, _complete(h, wids * HOP_MS + WINDOW_MS))
    bid = h.valid & (h.kind == KIND_BID)
    for i, w in enumerate(wids):
        m = bid & (h.ts >= w * HOP_MS) & (h.ts < w * HOP_MS + WINDOW_MS)
        cnts = np.bincount(h.auction[m].astype(np.int64), minlength=num_keys)
        hot = int(np.argmax(cnts))  # ties -> lowest id
        want = np.array([cnts[hot], hot], np.float32)
        oracle = np.asarray(q5_hot_oracle(h, int(w), assigner, num_keys))
        if not np.array_equal(oracle, want):
            _fail(f"[c] window {w}: q5_hot_oracle {oracle} != numpy {want}")
        for d in range(S):
            if not np.array_equal(vals[d, i], want):
                _fail(f"[c] window {w} device {d}: {vals[d, i]} != {want}")
        print(f"[c] window {w}: hot auction {hot} with {int(cnts[hot])} bids on all {S} shards")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the cross-chip paths, on a 4-device mesh")
    args = ap.parse_args(argv)
    enable_compile_cache()
    count = 4 if args.four_chips else 1
    devices = check_devices(count)
    phase_replicated(devices)
    phase_keyed(devices, 100_000_000 if args.four_chips else 10_000_000)
    d = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d.platform, "kind": d.device_kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
