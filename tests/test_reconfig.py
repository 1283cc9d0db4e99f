"""Elastic reconfiguration (docs/protocol.md §3): rendezvous placement laws,
scale-out/scale-in exactly-once (including a drain landing mid-delta-round),
graceful-handoff cheapness, and membership-epoch plumbing."""
import dataclasses

import numpy as np
from hypothesis import given, settings, strategies as st

from repro.runtime import Scenario, SimConfig, assignment, run_holon
from repro.runtime.harness import HolonHarness
from repro.streaming import make_q1_ratio, make_q7

settings.register_profile("ci-reconfig", max_examples=25, deadline=None)
settings.load_profile("ci-reconfig")

CFG = SimConfig(
    num_nodes=3,
    num_partitions=8,
    num_batches=40,
    events_per_batch=256,
    window_len=500,
    num_slots=32,
    sync_interval_ms=50.0,
    ckpt_interval_ms=300.0,
)


def _vals(consumer):
    return {k: np.asarray(r.value) for k, r in consumer.records.items()}


def _check_byte_identical(oracle, got):
    missing = set(oracle) - set(got)
    assert not missing, f"lost outputs: {sorted(missing)[:5]}"
    for k in oracle:
        np.testing.assert_array_equal(got[k], oracle[k], err_msg=str(k))


# ---------------------------------------------------------------------------
# Rendezvous placement laws
# ---------------------------------------------------------------------------


def test_rendezvous_total_and_deterministic():
    members = [0, 3, 7, 11]
    for pid in range(64):
        owner = assignment(pid, members)
        assert owner in members
        # membership order must not matter (peers sort their live views, but
        # the rule itself is order-free)
        assert assignment(pid, list(reversed(members))) == owner
    assert assignment(0, []) == -1


@given(
    members=st.lists(st.integers(0, 40), min_size=1, max_size=10, unique=True),
    joiner=st.integers(0, 40),
)
def test_rendezvous_join_moves_only_to_joiner(members, joiner):
    """Adding a node never moves a partition between two old nodes."""
    grown = sorted(set(members) | {joiner})
    for pid in range(32):
        before = assignment(pid, members)
        after = assignment(pid, grown)
        assert after == before or after == joiner


@given(
    members=st.lists(st.integers(0, 40), min_size=2, max_size=10, unique=True),
    victim_idx=st.integers(0, 9),
)
def test_rendezvous_leave_moves_only_victims_partitions(members, victim_idx):
    """Removing a node only reassigns the partitions it owned."""
    victim = sorted(members)[victim_idx % len(members)]
    shrunk = [n for n in members if n != victim]
    for pid in range(32):
        before = assignment(pid, members)
        if before != victim:
            assert assignment(pid, shrunk) == before


@given(seed=st.integers(0, 2**20))
def test_rendezvous_stable_under_churn(seed):
    """Along any churn path, a partition moves only at a step whose change
    explains the move: its current owner left, or the mover is the joiner."""
    import random

    rng = random.Random(seed)
    members = set(range(4))
    owners = {p: assignment(p, sorted(members)) for p in range(32)}
    for _ in range(rng.randint(1, 8)):
        gone = joined = None
        if rng.random() < 0.5 and len(members) > 1:
            gone = rng.choice(sorted(members))
            members.discard(gone)
        else:
            joined = rng.randint(0, 12)
            if joined in members:
                joined = None  # no-op add: nothing may move
            else:
                members.add(joined)
        for p in range(32):
            new = assignment(p, sorted(members))
            if new != owners[p]:
                assert owners[p] == gone or new == joined, (
                    f"p{p} moved {owners[p]}->{new} on gone={gone} joined={joined}"
                )
            owners[p] = new


# ---------------------------------------------------------------------------
# Elastic runs: byte-identical to the fixed-membership oracle
# ---------------------------------------------------------------------------


def test_scale_out_exactly_once():
    q = make_q7(CFG.num_partitions, window_len=CFG.window_len, num_slots=CFG.num_slots)
    oracle = _vals(run_holon(CFG, q))
    assert len(oracle) > 0
    got = _vals(run_holon(CFG, q, Scenario("out").scale_out(1200.0, 3, 4)))
    _check_byte_identical(oracle, got)


def test_scale_in_mid_delta_round_exactly_once():
    """Drain a node while its previous sync round's deltas are still in
    flight (sync publishes land at k*sync_interval, deliveries at +5 ms;
    draining at +2 ms puts the departure between publish and delivery) —
    outputs must stay byte-identical to the static-membership oracle."""
    q = make_q7(CFG.num_partitions, window_len=CFG.window_len, num_slots=CFG.num_slots)
    oracle = _vals(run_holon(CFG, q))
    mid_flight = 16 * CFG.sync_interval_ms + 2.0
    for victim in (0, 2):
        got = _vals(run_holon(CFG, q, Scenario("drain").scale_in(mid_flight, victim)))
        _check_byte_identical(oracle, got)
        assert set(got) == set(oracle)


def test_scale_in_then_out_rejoin_q1_ratio():
    """Drain then re-add the same node (local+shared state query): the
    rejoin rides the restart path and outputs match the oracle."""
    q = make_q1_ratio(
        CFG.num_partitions, window_len=CFG.window_len, num_slots=CFG.num_slots
    )
    oracle = _vals(run_holon(CFG, q))
    scen = Scenario("inout").scale_in(700.0, 1).scale_out(1600.0, 1)
    got = _vals(run_holon(CFG, q, scen))
    _check_byte_identical(oracle, got)


def test_double_resize_exactly_once():
    """3→5→3 round trip with a crash thrown in: still byte-identical."""
    q = make_q7(CFG.num_partitions, window_len=CFG.window_len, num_slots=CFG.num_slots)
    oracle = _vals(run_holon(CFG, q))
    scen = (
        Scenario("mix")
        .scale_out(600.0, 3, 4)
        .crash(1000.0, 0)
        .restart(1500.0, 0)
        .scale_in(1700.0, 3, 4)
    )
    got = _vals(run_holon(CFG, q, scen))
    _check_byte_identical(oracle, got)


# ---------------------------------------------------------------------------
# Drain handoff mechanics
# ---------------------------------------------------------------------------


def test_drain_hands_off_without_replay():
    """Graceful drain writes handoff checkpoints at the current frontier, so
    the takeover resumes from nxt_idx — the drained node's partitions see no
    duplicate emissions (replay would produce deduplicated duplicates)."""
    q = make_q7(CFG.num_partitions, window_len=CFG.window_len, num_slots=CFG.num_slots)
    h = HolonHarness(CFG, q)
    c = h.run(Scenario("drain").scale_in(1000.0, 1))
    assert all(r.duplicates == 0 for r in c.records.values()), "handoff replayed"
    # the drained node is gone from every live view and owns nothing
    n1 = h.nodes[1]
    assert not n1.alive and n1.departing and not n1.owned
    for nid in (0, 2):
        assert 1 not in h.nodes[nid]._live_view()


def test_join_bootstraps_full_state_from_peer():
    """A joiner requests a full-state sync from the first peer it hears; by
    run end it holds a converged replica and owns its rendezvous share."""
    q = make_q7(CFG.num_partitions, window_len=CFG.window_len, num_slots=CFG.num_slots)
    h = HolonHarness(CFG, q)
    h.run(Scenario("join").scale_out(1000.0, 7))
    joiner = h.nodes[7]
    assert joiner.alive and not joiner._bootstrap_pending
    expect = [
        p
        for p in range(CFG.num_partitions)
        if assignment(p, sorted(n.nid for n in h.nodes.values())) == 7
    ]
    assert joiner.owned == expect
    # replica converged with a veteran's (same folded frontier per spec)
    for a, b in zip(joiner.replica, h.nodes[0].replica):
        np.testing.assert_array_equal(np.asarray(a.folded), np.asarray(b.folded))


def test_multi_join_bootstraps_from_settled_peers_only():
    """In a multi-node scale-out, every joiner's §3.1 bootstrap handshake
    must be served by a settled node, never by an empty co-joiner (whose
    beacons carry joining=true)."""
    q = make_q7(CFG.num_partitions, window_len=CFG.window_len, num_slots=CFG.num_slots)
    h = HolonHarness(CFG, q)
    h.run(Scenario("multi").scale_out(1222.0, 3, 4, 5))
    served = dict(h.bootstrap_served)  # requester -> server
    assert set(served) == {3, 4, 5}, served
    assert all(server in (0, 1, 2) for server in served.values()), served


def test_decommission_crashed_node():
    """reconfigure(remove=...) of an already-crashed node closes its
    broadcast subscription (publishers stop paying for it) and the bumped
    epoch still reaches the live nodes."""
    q = make_q7(CFG.num_partitions, window_len=CFG.window_len, num_slots=CFG.num_slots)
    h = HolonHarness(CFG, q)
    c = h.run(Scenario("decomm").crash(1000.0, 1).scale_in(2000.0, 1))
    assert 1 in h.unsubscribed
    assert h.membership_epoch == 1
    for nid in (0, 2):
        assert h.nodes[nid].epoch == 1
        assert h.nodes[1] not in h.nodes[nid]._peers()
    # outputs unharmed (crash recovery already property-tested elsewhere)
    oracle = _vals(run_holon(CFG, q))
    _check_byte_identical(oracle, _vals(c))


def test_membership_epoch_reaches_checkpoints():
    """reconfigure bumps the epoch; it gossips through beacons and lands in
    the snapshot markers of every node's later checkpoints."""
    q = make_q7(CFG.num_partitions, window_len=CFG.window_len, num_slots=CFG.num_slots)
    h = HolonHarness(CFG, q)
    h.run(Scenario("epoch").scale_out(800.0, 3).scale_in(1500.0, 3))
    assert h.membership_epoch == 2
    epochs = [h.storage.get(p).epoch for p in range(CFG.num_partitions) if h.storage.has(p)]
    assert epochs and max(epochs) == 2
    # every surviving node gossiped up to the final epoch
    for nid in (0, 1, 2):
        assert h.nodes[nid].epoch == 2


def test_skewed_load_elastic_exactly_once():
    """Zipf-skewed partition load (generator pads cold partitions with
    invalid events): elasticity still byte-identical to the skewed oracle."""
    cfg = dataclasses.replace(CFG, skew=0.8)
    q = make_q7(cfg.num_partitions, window_len=cfg.window_len, num_slots=cfg.num_slots)
    oracle = _vals(run_holon(cfg, q))
    assert len(oracle) > 0
    scen = Scenario("skewed").scale_out(800.0, 3).scale_in(1500.0, 0)
    got = _vals(run_holon(cfg, q, scen))
    _check_byte_identical(oracle, got)
