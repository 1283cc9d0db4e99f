"""Nexmark Q5 hot items over sliding (hopping) windows, end-to-end.

Acceptance for the window-assigner refactor: q5 runs on both the
discrete-event harness and the shard_map dataplane, byte-identical to its
plain-jnp oracle, including under crash/restart — and the tumbling
degenerate of every generalized query keeps matching its oracle.
"""
import dataclasses

import jax
import numpy as np
import pytest

from repro.runtime import FailureScenario, SimConfig, run_holon
from repro.runtime.flink_baseline import run_flink
from repro.streaming import NexmarkConfig, generate_log, make_q0, make_q5

CFG = SimConfig(
    num_nodes=3,
    num_partitions=6,
    num_batches=60,
    events_per_batch=256,
    rate_per_partition=10_000.0,
    window_len=500,
    num_slots=32,
    ckpt_interval_ms=300.0,
    sync_interval_ms=50.0,
)


def _log(cfg: SimConfig):
    return generate_log(NexmarkConfig(
        num_partitions=cfg.num_partitions, num_batches=cfg.num_batches,
        events_per_batch=cfg.events_per_batch,
        rate_per_partition=cfg.rate_per_partition, seed=cfg.seed,
    ))


def _q5(cfg: SimConfig, hop=None):
    return make_q5(cfg.num_partitions, window_len=cfg.window_len,
                   num_slots=cfg.num_slots, hop=hop)


def test_q5_harness_matches_oracle_byte_identical():
    q = _q5(CFG)
    assert q.assigner.windows_per_event == 2  # default hop = window/2
    c = run_holon(CFG, q)
    log = _log(CFG)
    wids = sorted({w for (_, w) in c.records})
    # overlapping windows close every hop: ids are dense, more than tumbling
    assert len(wids) > int(CFG.horizon_ms // CFG.window_len) - 1
    assert wids == list(range(len(wids)))
    assert len(c.records) == len(wids) * CFG.num_partitions
    for (pid, w), r in c.records.items():
        np.testing.assert_array_equal(
            np.asarray(r.value), np.asarray(q.oracle(log, w)), err_msg=str((pid, w))
        )


def test_q5_crash_restart_exactly_once():
    """Crash two nodes mid-stream, restart them, and require the overlapping-
    window output to be byte-identical to the failure-free oracle run."""
    q = _q5(CFG)
    oracle_run = run_holon(CFG, q)
    want = {k: np.asarray(r.value) for k, r in oracle_run.records.items()}
    assert want
    scen = FailureScenario.concurrent(t=600.0, nodes=(0, 1))
    got = run_holon(CFG, q, scen, horizon_ms=CFG.horizon_ms + 15_000)
    missing = set(want) - set(got.records)
    assert not missing, f"lost outputs {sorted(missing)[:5]}"
    for k, v in want.items():
        np.testing.assert_array_equal(np.asarray(got.records[k].value), v,
                                      err_msg=str(k))
    # and every emission matches the log oracle too
    log = _log(CFG)
    for (pid, w), r in got.records.items():
        np.testing.assert_array_equal(
            np.asarray(r.value), np.asarray(q.oracle(log, w))
        )


def test_q5_scale_out_in_exactly_once():
    """Elastic membership churn (scale-out then scale-in) over overlapping
    windows: deduplicated output equals the fixed-membership run."""
    from repro.runtime import Scenario

    q = _q5(CFG)
    want = {k: np.asarray(r.value)
            for k, r in run_holon(CFG, q).records.items()}
    scen = Scenario("elastic").scale_out(400.0, 3).scale_in(900.0, 3)
    got = run_holon(CFG, q, scen, horizon_ms=CFG.horizon_ms + 10_000)
    assert set(want) <= set(got.records)
    for k, v in want.items():
        np.testing.assert_array_equal(np.asarray(got.records[k].value), v)


def test_q5_sliding_latency_zero_point_is_window_end():
    """Consumer latency is measured from the assigner end_ts — window w
    closes at w*hop + window_len, not (w+1)*window_len."""
    q = _q5(CFG)
    c = run_holon(CFG, q)
    a = q.assigner
    some = next(iter(sorted(c.records)))
    rec = c.records[some]
    assert rec.latency >= 0.0
    assert c._close_ts(rec.window) == float(a.end_ts(rec.window))
    assert c._close_ts(1) == float(a.hop + a.window_len)


def test_q5_flink_baseline_runs_sliding():
    """The centralized baseline forwards per-assigner-complete windows, so
    the A/B comparison covers overlapping windows too (emission times only;
    the baseline models coordination, not values)."""
    q = _q5(CFG)
    c = run_flink(CFG, q)
    wids = sorted({w for (_, w) in c.records})
    assert len(wids) > int(CFG.horizon_ms // CFG.window_len) - 1


def test_q5_tumbling_degenerate_matches_oracle():
    """hop=window_len collapses q5 to tumbling and stays oracle-exact."""
    q = _q5(CFG, hop=CFG.window_len)
    assert q.assigner.windows_per_event == 1
    c = run_holon(CFG, q)
    log = _log(CFG)
    assert c.records
    for (pid, w), r in c.records.items():
        np.testing.assert_array_equal(
            np.asarray(r.value), np.asarray(q.oracle(log, w))
        )


def test_q0_harness_still_matches_oracle():
    """q0 (no shared state) under the generalized emission loop."""
    q = make_q0(CFG.num_partitions, window_len=CFG.window_len,
                num_slots=CFG.num_slots)
    c = run_holon(CFG, q)
    log = _log(CFG)
    assert c.records
    for (pid, w), r in c.records.items():
        np.testing.assert_array_equal(
            np.asarray(r.value).reshape(()),
            np.asarray(q.oracle(log, w, partition=pid)),
            err_msg=str((pid, w)),
        )


# ---------------------------------------------------------------------------
# shard_map dataplane (single device here; multidevice in the marked test)
# ---------------------------------------------------------------------------


def _dataplane_case(query_name: str, hop: int | None, delta_sync: bool = True):
    from repro.launch.mesh import make_data_mesh
    from repro.launch.stream import MAKERS, build_pipeline, read_window_range

    n_dev = 1
    batches, epb = 32, 1024
    mesh = make_data_mesh(n_dev)
    nx = NexmarkConfig(num_partitions=n_dev, num_batches=batches,
                       events_per_batch=epb)
    log = generate_log(nx)
    kw = {"hop": hop} if hop else {}
    q = MAKERS[query_name](n_dev, window_len=1000, num_slots=64, **kw)
    first, n_windows = read_window_range(q, batches * nx.batch_span_ms)
    assert first == 0  # short horizon: nothing evicted yet
    with mesh:
        oks, vals, sb = build_pipeline(
            q, mesh, sync_every=4, delta_sync=delta_sync, n_windows=n_windows
        )(log)
    return q, log, np.asarray(oks)[0], np.asarray(vals)[0], np.asarray(sb)


def test_q5_dataplane_matches_oracle_byte_identical():
    q, log, oks, vals, sb = _dataplane_case("q5", hop=None)
    assert q.assigner.windows_per_event == 2
    assert oks.sum() >= 4  # sliding windows close every hop
    for w in np.nonzero(oks)[0]:
        np.testing.assert_array_equal(vals[w], np.asarray(q.oracle(log, int(w))))
    assert float(sb.sum()) > 0  # sliding-window sync bytes are measured


def test_q0_dataplane_runs_without_shared_state():
    """MAKERS includes q0; the empty-shared sync path is a no-op (0 bytes)."""
    q, log, oks, vals, sb = _dataplane_case("q0", hop=None)
    assert oks.sum() >= 2
    for w in np.nonzero(oks)[0]:
        np.testing.assert_array_equal(
            vals[w].reshape(()), np.asarray(q.oracle(log, int(w), partition=0))
        )
    assert float(sb.sum()) == 0.0


@pytest.mark.multidevice
def test_q5_dataplane_multidevice_subprocess():
    """4-device shard_map run of the sliding q5: delta sync byte-identical
    to full-state sync, every complete window oracle-exact."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    script = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, numpy as np
from repro.launch.mesh import make_data_mesh
from repro.launch.stream import MAKERS, build_pipeline, read_window_range
from repro.streaming import NexmarkConfig, generate_log

n_dev = len(jax.devices()); assert n_dev == 4, n_dev
mesh = make_data_mesh(n_dev)
nx = NexmarkConfig(num_partitions=n_dev, num_batches=24, events_per_batch=512)
log = generate_log(nx)
q = MAKERS["q5"](n_dev, window_len=200, num_slots=64)
first, n_windows = read_window_range(q, 24 * nx.batch_span_ms)
assert first == 0
with mesh:
    od, vd, sd = build_pipeline(q, mesh, 4, delta_sync=True, n_windows=n_windows)(log)
    of, vf, sf = build_pipeline(q, mesh, 4, delta_sync=False, n_windows=n_windows)(log)
np.testing.assert_array_equal(np.asarray(od), np.asarray(of))
np.testing.assert_array_equal(np.asarray(vd), np.asarray(vf))
od, vd = np.asarray(od)[0], np.asarray(vd)[0]
assert od.sum() >= 4
for w in np.nonzero(od)[0]:
    np.testing.assert_array_equal(vd[w], np.asarray(q.oracle(log, int(w))))
print("MULTIDEV_Q5_OK")
"""
    src = Path(__file__).resolve().parents[1] / "src"
    env = dict(os.environ, PYTHONPATH=str(src))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", script],
                       capture_output=True, text=True, timeout=600, env=env)
    assert "MULTIDEV_Q5_OK" in r.stdout, (
        f"stdout={r.stdout[-2000:]}\nstderr={r.stderr[-2000:]}"
    )


def test_q5_delta_sync_matches_full_state_on_harness():
    """Sliding windows ride the delta protocol unchanged: identical outputs,
    fewer bytes (the generalized dirty rule stays exact)."""
    q = _q5(CFG)
    delta = run_holon(CFG, q)
    full = run_holon(dataclasses.replace(CFG, delta_sync=False), q)
    dv = {k: np.asarray(r.value) for k, r in delta.records.items()}
    fv = {k: np.asarray(r.value) for k, r in full.records.items()}
    assert set(dv) == set(fv) and dv
    for k in dv:
        np.testing.assert_array_equal(dv[k], fv[k], err_msg=str(k))
    assert delta.sync_bytes < 0.6 * delta.sync_bytes_full


def test_q7_sliding_topk_active_clamped_to_ring():
    """make_q7's K-scaled topk_active is clamped to num_slots (more active
    offsets than slots would alias wid % W and silently drop folds), and
    the clamped fast path matches the exact slow path fold-for-fold."""
    import jax.numpy as jnp

    from repro.core import wcrdt as W
    from repro.core.window import Hopping
    from repro.streaming import make_q7

    q = make_q7(1, window_len=1000, num_slots=16, hop=125)  # K=8 -> 4*8=32
    spec = q.shared_specs[0]
    assert spec.max_active_windows == 16  # clamped, not 32
    with pytest.raises(ValueError):
        W.wtopk(1000, 16, 1, k=4, max_active_windows=32)

    a = Hopping(1000, 125)
    fast = W.wtopk(1000, 16, 1, k=4, max_active_windows=16, assigner=a)
    slow = W.wtopk(1000, 16, 1, k=4, max_active_windows=None, assigner=a)
    rng = np.random.default_rng(0)
    n = 64
    ts = jnp.array(np.sort(rng.integers(0, 1500, size=n)).astype(np.int32))
    vals = jnp.array((rng.random(n) * 100).astype(np.float32))
    ids = jnp.array(rng.integers(0, 1000, size=n).astype(np.uint32))
    sf = W.insert(fast, fast.zero(), 0, ts, jnp.ones(n, bool), vals=vals, ids=ids)
    ss = W.insert(slow, slow.zero(), 0, ts, jnp.ones(n, bool), vals=vals, ids=ids)
    sf = W.increment_watermark(fast, sf, 0, 3000)
    ss = W.increment_watermark(slow, ss, 0, 3000)
    for wid in range(int(ts.max()) // 125 + 1):
        (fv, fi), fok = W.window_value(fast, sf, wid)
        (sv, si), sok = W.window_value(slow, ss, wid)
        assert bool(fok) == bool(sok)
        if bool(fok):
            np.testing.assert_array_equal(np.asarray(fv), np.asarray(sv), err_msg=str(wid))
