"""Exactly-once under failures (paper §3.3, §4.3).

Property: for ANY failure schedule (crashes, restarts, work stealing), the
deduplicated output stream equals the failure-free oracle run, and the system
keeps making progress as long as one node survives.
"""
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.runtime import FailureScenario, SimConfig, run_flink, run_holon
from repro.streaming import generate_log, make_q1_ratio, make_q4, make_q7, NexmarkConfig

settings.register_profile("ci-e2e", max_examples=5, deadline=None)
settings.load_profile("ci-e2e")

SMALL = SimConfig(
    num_nodes=3,
    num_partitions=6,
    num_batches=60,
    events_per_batch=512,
    rate_per_partition=10_000.0,
    window_len=500,
    num_slots=32,
    ckpt_interval_ms=300.0,
    sync_interval_ms=50.0,
)


def _records_by_key(consumer):
    return {k: np.asarray(r.value) for k, r in consumer.records.items()}


@pytest.fixture(scope="module")
def q7_baseline():
    q = make_q7(SMALL.num_partitions, window_len=SMALL.window_len, num_slots=SMALL.num_slots)
    return q, run_holon(SMALL, q)


def test_failure_free_matches_oracle(q7_baseline):
    q, consumer = q7_baseline
    nx = NexmarkConfig(
        num_partitions=SMALL.num_partitions,
        num_batches=SMALL.num_batches,
        events_per_batch=SMALL.events_per_batch,
        rate_per_partition=SMALL.rate_per_partition,
        seed=SMALL.seed,
    )
    log = generate_log(nx)
    assert len(consumer.records) > 0
    checked = 0
    for (p, w), rec in consumer.records.items():
        if p == 0 and w < 4:
            ov, oi = q.oracle(log, w)
            np.testing.assert_allclose(rec.value[:8], np.asarray(ov), rtol=1e-5)
            checked += 1
    assert checked > 0


@given(
    fail_t=st.floats(500.0, 1500.0),
    restart_dt=st.floats(300.0, 2000.0),
    node=st.integers(0, 2),
)
def test_exactly_once_single_failure(q7_baseline, fail_t, restart_dt, node):
    q, base = q7_baseline
    scen = FailureScenario(
        name="hyp",
        fail_times_ms=(fail_t,),
        fail_nodes=(node,),
        restart_times_ms=(fail_t + restart_dt,),
    )
    c = run_holon(SMALL, q, scen)
    ref = _records_by_key(base)
    got = _records_by_key(c)
    # every window emitted in the failure-free run is also emitted here, with
    # identical (deduplicated) values
    missing = set(ref) - set(got)
    assert not missing, f"lost outputs: {sorted(missing)[:5]}"
    for k in ref:
        np.testing.assert_allclose(got[k], ref[k], rtol=1e-5, err_msg=str(k))


def test_exactly_once_crash_without_restart(q7_baseline):
    q, base = q7_baseline
    scen = FailureScenario(
        name="crash1", fail_times_ms=(800.0,), fail_nodes=(0,), restart_times_ms=(-1.0,)
    )
    c = run_holon(SMALL, q, scen, horizon_ms=SMALL.horizon_ms + 10_000)
    ref = _records_by_key(base)
    got = _records_by_key(c)
    assert set(ref) <= set(got)
    for k in ref:
        np.testing.assert_allclose(got[k], ref[k], rtol=1e-5)


def test_duplicates_are_deduped(q7_baseline):
    """Concurrent processing of the same partition yields duplicate emissions
    that the consumer drops — outputs stay exactly-once."""
    q, base = q7_baseline
    scen = FailureScenario(
        name="both", fail_times_ms=(700.0, 900.0), fail_nodes=(0, 1),
        restart_times_ms=(1500.0, 1800.0),
    )
    c = run_holon(SMALL, q, scen)
    # duplicates may or may not occur, but records must match baseline values
    ref = _records_by_key(base)
    got = _records_by_key(c)
    for k in ref:
        assert k in got
        np.testing.assert_allclose(got[k], ref[k], rtol=1e-5)


def test_q4_and_ratio_exactly_once():
    for mk in (make_q4, make_q1_ratio):
        q = mk(SMALL.num_partitions, window_len=SMALL.window_len, num_slots=SMALL.num_slots)
        base = run_holon(SMALL, q)
        scen = FailureScenario.concurrent(t=800.0)
        c = run_holon(SMALL, q, scen)
        ref = _records_by_key(base)
        got = _records_by_key(c)
        assert set(ref) <= set(got)
        for k in ref:
            np.testing.assert_allclose(got[k], ref[k], rtol=1e-5)


def test_holon_progress_under_crash_flink_stalls():
    """Fig. 6 bottom-right: with both of two failed nodes never restarting,
    Holon reconfigures and keeps emitting; Flink (no spare slots) stops."""
    q = make_q7(SMALL.num_partitions, window_len=SMALL.window_len, num_slots=SMALL.num_slots)
    scen = FailureScenario.crash(t=800.0)
    ch = run_holon(SMALL, q, scen, horizon_ms=SMALL.horizon_ms + 15_000)
    cf = run_flink(SMALL, q, scen, horizon_ms=SMALL.horizon_ms + 15_000)
    horizon_windows = int(SMALL.horizon_ms / SMALL.window_len)
    late_holon = [w for (_, w) in ch.records if w > horizon_windows // 2]
    late_flink = [w for (_, w) in cf.records if w > horizon_windows // 2]
    assert late_holon, "holon should keep completing windows after the crash"
    assert not late_flink, "flink without spare slots must stall"
