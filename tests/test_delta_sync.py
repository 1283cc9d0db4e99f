"""Delta-based WCRDT sync (paper §7 future work, DESIGN.md §6): incremental
deltas apply exactly like full-state merges while shipping only dirty window
slots — property-tested over randomized fold/watermark schedules, and
end-to-end through the runtime (crash mid-sync, restart, byte-identical
output)."""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import wcrdt as W
from repro.core import wgcounter, wtopk

settings.register_profile("ci-delta", max_examples=25, deadline=None)
settings.load_profile("ci-delta")


def leaves_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-6)


def test_delta_merge_equals_full_merge():
    spec = wgcounter(window_len=10, num_slots=16, num_partitions=2)
    a = spec.zero()  # partition 0's replica
    b = spec.zero()  # partition 1's replica (the receiver)

    # receiver knows a's state after 2 batches
    for idx, ts in enumerate(([1, 3, 7], [12, 15, 18])):
        t = jnp.array(ts, jnp.int32)
        a = W.insert(spec, a, 0, t, jnp.ones(len(ts), bool), batch_idx=idx,
                     actor=0, amounts=jnp.ones(len(ts)))
        a = W.increment_watermark(spec, a, 0, int(t.max()))
    b = W.merge(spec, b, a)
    baseline, base_prog = a.folded, a.progress

    # a folds two more batches (touching windows 1 and 2 only)
    for idx, ts in enumerate(([19, 22], [25, 29]), start=2):
        t = jnp.array(ts, jnp.int32)
        a = W.insert(spec, a, 0, t, jnp.ones(len(ts), bool), batch_idx=idx,
                     actor=0, amounts=jnp.ones(len(ts)))
        a = W.increment_watermark(spec, a, 0, int(t.max()))

    delta = W.delta_since(spec, a, baseline, base_prog)
    # delta carries fewer resident slots than the full state
    assert int((delta.slot_wid >= 0).sum()) < int((a.slot_wid >= 0).sum()) or \
        int((a.slot_wid >= 0).sum()) <= 2
    # merging the delta reproduces the full-state merge exactly
    via_delta = W.merge(spec, b, delta)
    via_full = W.merge(spec, b, a)
    leaves_equal(via_delta, via_full)

    # and the wire size is smaller than the full ring
    full_bytes = sum(l.nbytes for l in jax.tree.leaves(a.windows))
    d_bytes = float(W.delta_nbytes(delta))
    assert d_bytes < full_bytes


# ---------------------------------------------------------------------------
# Delta laws under randomized fold/watermark schedules
# ---------------------------------------------------------------------------

WL, SLOTS, PARTS = 10, 16, 3


def _spec(kind):
    if kind == "topk":
        return wtopk(WL, SLOTS, PARTS, k=4, max_active_windows=None)
    return wgcounter(WL, SLOTS, PARTS)


def _fold(spec, kind, state, p, ts, idx):
    t = jnp.array(ts, jnp.int32)
    m = jnp.ones(len(ts), bool)
    if kind == "topk":
        state = W.insert(spec, state, p, t, m, batch_idx=idx,
                         vals=jnp.arange(1.0, len(ts) + 1.0),
                         ids=jnp.arange(len(ts), dtype=jnp.uint32) + idx * 100)
    else:
        state = W.insert(spec, state, p, t, m, batch_idx=idx,
                         actor=p, amounts=jnp.ones(len(ts)))
    return W.increment_watermark(spec, state, p, int(max(ts)))


def _schedule(rng, n_batches):
    """Random in-order-per-partition fold schedule: (partition, [ts...])."""
    clock = [0] * PARTS
    out = []
    for _ in range(n_batches):
        p = rng.randint(0, PARTS - 1)
        n = rng.randint(1, 4)
        ts = []
        for _ in range(n):
            clock[p] += rng.randint(0, 7)
            ts.append(clock[p])
        out.append((p, ts))
    return out


@given(seed=st.integers(0, 2**20), kind=st.sampled_from(["gcounter", "topk"]),
       cut=st.integers(1, 6), extra=st.integers(1, 6))
def test_delta_merge_law_random_schedules(seed, kind, cut, extra):
    """merge(b, delta_since(a, base)) == merge(b, a) whenever b holds a's
    baseline state — for any in-order fold/watermark schedule."""
    import random

    rng = random.Random(seed)
    spec = _spec(kind)
    a = spec.zero()
    for idx, (p, ts) in enumerate(_schedule(rng, cut)):
        a = _fold(spec, kind, a, p, ts, idx)
    b = W.merge(spec, spec.zero(), a)  # receiver caught up to the baseline
    base_folded, base_prog = np.asarray(a.folded), np.asarray(a.progress)

    for idx, (p, ts) in enumerate(_schedule(rng, extra), start=cut):
        a = _fold(spec, kind, a, p, ts, idx)

    delta = W.delta_since(spec, a, base_folded, base_prog)
    via_delta = W.merge(spec, b, delta)
    via_full = W.merge(spec, b, a)
    leaves_equal(via_delta, via_full)
    # the delta is a point below a in the lattice: merging it into a is a no-op
    leaves_equal(W.merge(spec, a, delta), a)


@given(seed=st.integers(0, 2**20))
def test_delta_idempotent_and_commutes_with_concurrent_deltas(seed):
    """Applying a delta twice is a no-op, and concurrent senders' deltas
    merge to the same state in either order."""
    import random

    rng = random.Random(seed)
    spec = _spec("gcounter")

    def writer(p, n, off):
        s = spec.zero()
        for idx, (_, ts) in enumerate(_schedule(random.Random(seed + off), n)):
            s = _fold(spec, "gcounter", s, p, ts, idx)
        return s

    a = writer(0, rng.randint(1, 5), 1)
    c = writer(1, rng.randint(1, 5), 2)
    zb = W.zero_baseline(spec)
    da = W.delta_since(spec, a, *zb)
    dc = W.delta_since(spec, c, *zb)

    b = spec.zero()
    once = W.merge(spec, b, da)
    twice = W.merge(spec, once, da)
    leaves_equal(once, twice)

    ab = W.merge(spec, W.merge(spec, b, da), dc)
    ba = W.merge(spec, W.merge(spec, b, dc), da)
    leaves_equal(ab, ba)
    # and the pair of zero-baseline deltas reconstructs the full join
    leaves_equal(ab, W.merge(spec, a, c))


def test_delta_of_unchanged_state_is_identity_sized():
    spec = wgcounter(window_len=10, num_slots=16, num_partitions=2)
    a = spec.zero()
    t = jnp.array([1, 5], jnp.int32)
    a = W.insert(spec, a, 0, t, jnp.ones(2, bool), batch_idx=0, actor=0, amounts=jnp.ones(2))
    delta = W.delta_since(spec, a, a.folded, a.progress)  # receiver caught up
    assert int((delta.slot_wid >= 0).sum()) == 0  # no dirty slots
    # still merges as a no-op
    b = W.merge(spec, spec.zero(), a)
    b2 = W.merge(spec, b, delta)
    leaves_equal(b, b2)


# ---------------------------------------------------------------------------
# End-to-end: the runtime ships deltas; chaos mid-sync keeps exactly-once
# ---------------------------------------------------------------------------

from repro.runtime import FailureScenario, SimConfig, run_holon  # noqa: E402
from repro.streaming import make_q1_ratio, make_q7  # noqa: E402

CHAOS = SimConfig(
    num_nodes=3,
    num_partitions=6,
    num_batches=50,
    events_per_batch=256,
    rate_per_partition=10_000.0,
    window_len=500,
    num_slots=32,
    ckpt_interval_ms=250.0,
    sync_interval_ms=50.0,
)


def _values(consumer):
    return {k: np.asarray(r.value) for k, r in consumer.records.items()}


def test_runtime_delta_sync_matches_full_state_sync():
    """The delta protocol is pure optimization: identical outputs, a
    fraction of the sync bytes."""
    q = make_q7(CHAOS.num_partitions, window_len=CHAOS.window_len, num_slots=CHAOS.num_slots)
    delta = run_holon(CHAOS, q)
    full = run_holon(dataclasses.replace(CHAOS, delta_sync=False), q)
    dv, fv = _values(delta), _values(full)
    assert set(dv) == set(fv) and len(dv) > 0
    for k in dv:
        np.testing.assert_array_equal(dv[k], fv[k], err_msg=str(k))
    assert delta.sync_bytes < 0.25 * delta.sync_bytes_full
    assert full.sync_bytes == full.sync_bytes_full


def test_chaos_crash_mid_sync_exactly_once():
    """Crash a node while its deltas are still in flight (fail time lands
    between a sync publish and its deliveries), restart it, and require the
    consumer output to be byte-identical to the failure-free oracle."""
    q = make_q7(CHAOS.num_partitions, window_len=CHAOS.window_len, num_slots=CHAOS.num_slots)
    oracle = _values(run_holon(CHAOS, q))
    assert len(oracle) > 0
    # sync publishes land at k*sync_interval; broadcast_delay_ms = 5 puts
    # deliveries at +5 — failing at +2 kills the sender mid-flight
    mid_flight = 12 * CHAOS.sync_interval_ms + 2.0
    for scen in (
        FailureScenario(name="sender", fail_times_ms=(mid_flight,),
                        fail_nodes=(0,), restart_times_ms=(mid_flight + 700.0,)),
        FailureScenario(name="receiver", fail_times_ms=(mid_flight + 1.0,),
                        fail_nodes=(1,), restart_times_ms=(mid_flight + 900.0,)),
        FailureScenario(name="both", fail_times_ms=(mid_flight, mid_flight + 1.0),
                        fail_nodes=(0, 1),
                        restart_times_ms=(mid_flight + 700.0, mid_flight + 900.0)),
    ):
        got = _values(run_holon(CHAOS, q, scen))
        missing = set(oracle) - set(got)
        assert not missing, f"{scen.name}: lost outputs {sorted(missing)[:5]}"
        for k in oracle:
            np.testing.assert_array_equal(got[k], oracle[k],
                                          err_msg=f"{scen.name}:{k}")


def test_chaos_recovery_resyncs_after_stale_checkpoint():
    """A restarted node recovers an old checkpoint; peers' deltas assume a
    newer baseline, so the node must nack into a full resync — and outputs
    must still match the oracle (q1_ratio exercises local+shared state)."""
    q = make_q1_ratio(CHAOS.num_partitions, window_len=CHAOS.window_len,
                      num_slots=CHAOS.num_slots)
    cfg = dataclasses.replace(CHAOS, ckpt_interval_ms=600.0)  # stale ckpts
    oracle = _values(run_holon(cfg, q))
    mid_flight = 20 * cfg.sync_interval_ms + 2.0
    scen = FailureScenario(name="stale", fail_times_ms=(mid_flight,),
                           fail_nodes=(2,), restart_times_ms=(mid_flight + 1200.0,))
    c = run_holon(cfg, q, scen)
    got = _values(c)
    assert set(oracle) <= set(got)
    for k in oracle:
        np.testing.assert_array_equal(got[k], oracle[k], err_msg=str(k))


_MULTIDEV_SCRIPT = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import jax, numpy as np
from repro.launch.mesh import make_data_mesh
from repro.launch.stream import MAKERS, build_pipeline
from repro.streaming import NexmarkConfig, generate_log

n_dev = len(jax.devices()); assert n_dev == 4, n_dev
mesh = make_data_mesh(n_dev)
nx = NexmarkConfig(num_partitions=n_dev, num_batches=16, events_per_batch=512)
log = generate_log(nx)
for qn in ("q1_ratio", "q7"):
    q = MAKERS[qn](n_dev, window_len=1000, num_slots=64)
    with mesh:
        od, vd, sd = build_pipeline(q, mesh, 4, delta_sync=True)(log)
        of, vf, sf = build_pipeline(q, mesh, 4, delta_sync=False)(log)
    np.testing.assert_array_equal(np.asarray(od), np.asarray(of))
    np.testing.assert_array_equal(np.asarray(vd), np.asarray(vf))
    assert float(np.asarray(sd).mean()) < 0.25 * float(np.asarray(sf).mean()), qn
print("MULTIDEV_DELTA_OK")
"""


@pytest.mark.multidevice
def test_stream_delta_sync_multidevice_subprocess():
    """Multi-device shard_map run: dirty-slot-gated exchange is
    output-identical to the full-state all-reduce at a fraction of the
    bytes (q7's TopK rides the generic join; q1_ratio the gated kernel)."""
    import os
    import subprocess
    import sys
    from pathlib import Path

    src = Path(__file__).resolve().parents[1] / "src"
    env = dict(os.environ, PYTHONPATH=str(src))
    env.pop("XLA_FLAGS", None)  # the script sets its own device count
    r = subprocess.run(
        [sys.executable, "-c", _MULTIDEV_SCRIPT],
        capture_output=True, text=True, timeout=600, env=env,
    )
    assert "MULTIDEV_DELTA_OK" in r.stdout, (
        f"stdout={r.stdout[-2000:]}\nstderr={r.stderr[-2000:]}"
    )


def test_checkpoint_records_sync_baseline():
    """Checkpoints carry the delta-sync coverage marker of their snapshot."""
    from repro.runtime.harness import HolonHarness

    q = make_q7(CHAOS.num_partitions, window_len=CHAOS.window_len, num_slots=CHAOS.num_slots)
    h = HolonHarness(CHAOS, q)
    h.run()
    assert h.storage.has(0)
    ck = h.storage.get(0)
    assert ck.baseline is not None
    for (bf, bp), st in zip(ck.baseline, ck.shared):
        np.testing.assert_array_equal(bf, np.asarray(st.folded))
        np.testing.assert_array_equal(bp, np.asarray(st.progress))
