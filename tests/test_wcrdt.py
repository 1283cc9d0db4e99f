"""Windowed-CRDT semantics (paper §3.3, §4.2).

Global determinism: once getWindowValue returns a value for window w, every
replica returns the SAME value for w, regardless of network order, delays,
or duplicated deliveries.  Incomplete windows read as not-ok (None).
"""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import wcrdt as W
from repro.core import wgcounter, wmaxreg, wtopk

settings.register_profile("ci-wcrdt", max_examples=30, deadline=None)
settings.load_profile("ci-wcrdt")

P = 3  # partitions
WL = 10  # window length
SLOTS = 8


def _mk_events(rng, n):
    """Per-partition ordered timestamps + values."""
    ts = np.sort(rng.integers(0, WL * 4, size=n)).astype(np.int32)
    vals = rng.random(n).astype(np.float32) * 10
    return ts, vals


@given(seed=st.integers(0, 2**20))
def test_global_determinism_gcounter(seed):
    rng = np.random.default_rng(seed)
    spec = wgcounter(WL, SLOTS, P)

    # each partition folds its own events into its replica
    replicas = []
    all_events = []
    for p in range(P):
        ts, vals = _mk_events(rng, int(rng.integers(4, 12)))
        all_events.append((ts, vals))
        s = spec.zero()
        s = W.insert(spec, s, p, jnp.array(ts), jnp.ones(len(ts), bool), actor=p, amounts=jnp.array(vals))
        s = W.increment_watermark(spec, s, p, int(ts.max()))
        replicas.append(s)

    # two different delivery orders (with duplication) must agree
    def sync(order, dup):
        states = [replicas[i] for i in range(P)]
        for src, dst in order:
            states[dst] = W.merge(spec, states[dst], states[src])
        for src, dst in dup:
            states[dst] = W.merge(spec, states[dst], states[src])
        return states

    full = [(i, j) for i in range(P) for j in range(P) if i != j]
    orderA = full
    orderB = full[::-1]
    dups = [full[rng.integers(0, len(full))] for _ in range(3)]
    sA = sync(orderA, dups)
    sB = sync(orderB, [])

    gwm = min(int(e[0].max()) for e in all_events)
    complete_windows = [w for w in range(4) if gwm >= (w + 1) * WL]
    for w in complete_windows:
        ref = None
        for states in (sA, sB):
            for s in states:
                v, ok = W.window_value(spec, s, w)
                assert bool(ok), f"window {w} should be complete"
                if ref is None:
                    ref = float(v)
                assert float(v) == ref
        # and it matches the oracle
        oracle = sum(
            float(vals[(ts >= w * WL) & (ts < (w + 1) * WL)].sum())
            for ts, vals in all_events
        )
        np.testing.assert_allclose(ref, oracle, rtol=1e-5)

    # incomplete windows read not-ok on every replica
    for w in range(4):
        if w not in complete_windows:
            for s in sA:
                _, ok = W.window_value(spec, s, w)
                assert not bool(ok)


@given(seed=st.integers(0, 2**20))
def test_watermark_monotone_and_safety(seed):
    rng = np.random.default_rng(seed)
    spec = wmaxreg(WL, SLOTS, P)
    s = spec.zero()
    last_gwm = -1
    for step in range(5):
        p = int(rng.integers(0, P))
        ts = np.sort(rng.integers(step * 5, step * 5 + 20, size=4)).astype(np.int32)
        s = W.insert(spec, s, p, jnp.array(ts), jnp.ones(4, bool), vals=jnp.array(rng.random(4), jnp.float32))
        s = W.increment_watermark(spec, s, p, int(ts.max()))
        gwm = int(W.global_watermark(spec, s))
        assert gwm >= last_gwm
        last_gwm = gwm
        # no window at/after the watermark reads complete
        w_edge = gwm // WL
        _, ok = W.window_value(spec, s, w_edge)  # window containing gwm
        if gwm < (w_edge + 1) * WL:
            assert not bool(ok)


def test_late_events_counted():
    spec = wgcounter(WL, SLOTS, P)
    s = spec.zero()
    s = W.increment_watermark(spec, s, 0, 25)
    ts = jnp.array([5, 30], jnp.int32)  # 5 is behind partition-0 watermark
    s = W.insert(spec, s, 0, ts, jnp.ones(2, bool), actor=0, amounts=jnp.ones(2))
    assert int(s.errors[W.ERR_LATE]) == 1
    # the late event must NOT be folded
    for p in range(P):
        s = W.increment_watermark(spec, s, p, 100)
    v, ok = W.window_value(spec, s, 0)
    assert bool(ok) and float(v) == 0.0


def test_ring_eviction_detected():
    spec = wgcounter(WL, 2, 1)  # tiny ring: 2 slots
    s = spec.zero()
    for w in range(4):  # windows 0..3 with ring of 2 -> evictions
        ts = jnp.array([w * WL + 1], jnp.int32)
        s = W.insert(spec, s, 0, ts, jnp.ones(1, bool), actor=0, amounts=jnp.ones(1))
    # window 0 evicted: value unreadable
    s = W.increment_watermark(spec, s, 0, 100)
    _, ok = W.window_value(spec, s, 0)
    assert not bool(ok)
    v3, ok3 = W.window_value(spec, s, 3)
    assert bool(ok3) and float(v3) == 1.0


@given(seed=st.integers(0, 2**20))
def test_topk_windowed_matches_oracle(seed):
    rng = np.random.default_rng(seed)
    k = 4
    spec = wtopk(WL, SLOTS, 2, k)
    sA, sB = spec.zero(), spec.zero()
    events = []
    for p, s_ in ((0, "A"), (1, "B")):
        n = int(rng.integers(5, 20))
        ts = np.sort(rng.integers(0, WL * 3, size=n)).astype(np.int32)
        vals = (rng.random(n) * 100).astype(np.float32)
        ids = rng.integers(0, 1000, size=n).astype(np.uint32)
        events.append((ts, vals, ids))
    sA = W.insert(spec, sA, 0, jnp.array(events[0][0]), jnp.ones(len(events[0][0]), bool),
                  vals=jnp.array(events[0][1]), ids=jnp.array(events[0][2]))
    sA = W.increment_watermark(spec, sA, 0, int(events[0][0].max()))
    sB = W.insert(spec, sB, 1, jnp.array(events[1][0]), jnp.ones(len(events[1][0]), bool),
                  vals=jnp.array(events[1][1]), ids=jnp.array(events[1][2]))
    sB = W.increment_watermark(spec, sB, 1, int(events[1][0].max()))
    m = W.merge(spec, sA, sB)

    gwm = min(int(events[0][0].max()), int(events[1][0].max()))
    for w in range(3):
        if gwm >= (w + 1) * WL:
            (vals, ids), ok = W.window_value(spec, m, w)
            assert bool(ok)
            pool = []
            for ts, vv, ii in events:
                sel = (ts >= w * WL) & (ts < (w + 1) * WL)
                pool += list(zip(vv[sel].tolist(), ii[sel].tolist()))
            pool.sort(key=lambda t: (-t[0], -t[1]))
            expect = [v for v, _ in pool[:k]]
            got = [v for v in np.asarray(vals).tolist() if v > -np.inf]
            np.testing.assert_allclose(got[: len(expect)], expect, rtol=1e-5)
