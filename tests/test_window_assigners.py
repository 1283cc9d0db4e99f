"""Window-assigner laws (DESIGN.md §8).

* ``Tumbling`` reproduces the pre-assigner integer division exactly;
* every (interior) event lands in exactly ``window_len // hop`` hopping
  windows, and ``assign``/``contains``/``first_dirty_wid`` agree;
* a complete window can never receive a later fold — completion is final;
* the evicted-window read path: ``window_value`` ok=False plus
  ``ERR_EVICT_INCOMPLETE`` / ``ERR_RING`` accounting, tumbling and
  overlapping alike.
"""
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings, strategies as st

from repro.core import wcrdt as W
from repro.core import wgcounter
from repro.core.window import Hopping, Tumbling, as_assigner, expand_events

settings.register_profile("ci-assigners", max_examples=40, deadline=None)
settings.load_profile("ci-assigners")


# ---------------------------------------------------------------------------
# Assigner laws
# ---------------------------------------------------------------------------


@given(window_len=st.integers(1, 500), ts=st.lists(st.integers(0, 10_000),
                                                   min_size=1, max_size=32))
def test_tumbling_matches_integer_division(window_len, ts):
    """Tumbling.assign is exactly the old ``ts // window_len`` rule: K == 1,
    every lane valid, and the wid equals the division."""
    a = Tumbling(window_len)
    t = jnp.array(ts, jnp.int32)
    wids, valid = a.assign(t)
    assert a.windows_per_event == 1 and wids.shape == (len(ts), 1)
    np.testing.assert_array_equal(np.asarray(wids[:, 0]), np.array(ts) // window_len)
    assert bool(valid.all())
    np.testing.assert_array_equal(np.asarray(a.window_of(t)), np.array(ts) // window_len)
    for x in ts:
        assert a.end_ts(x // window_len) == (x // window_len + 1) * window_len
    assert a == as_assigner(window_len) and a == as_assigner(window_len, window_len)


@given(hop=st.integers(1, 50), k=st.integers(1, 8),
       ts=st.lists(st.integers(0, 5_000), min_size=1, max_size=32))
def test_hopping_event_lands_in_exactly_k_windows(hop, k, ts):
    """An event at ``ts`` belongs to exactly ``min(K, ts // hop + 1)`` valid
    windows (K for every interior event), and each claimed window actually
    contains it while no unclaimed one does."""
    a = Hopping(hop * k, hop)
    assert a.windows_per_event == k
    t = jnp.array(ts, jnp.int32)
    wids, valid = a.assign(t)
    n_valid = np.asarray(valid.sum(axis=-1))
    np.testing.assert_array_equal(
        n_valid, np.minimum(k, np.array(ts) // hop + 1)
    )
    wids_np, valid_np = np.asarray(wids), np.asarray(valid)
    for i, x in enumerate(ts):
        claimed = set(wids_np[i][valid_np[i]].tolist())
        assert claimed == {w for w in range(x // hop + 1) if bool(a.contains(w, x))}
        for w in claimed:
            assert a.start_ts(w) <= x < a.end_ts(w)


@given(hop=st.integers(1, 50), k=st.integers(1, 8), gwm=st.integers(0, 5_000),
       ts=st.integers(0, 5_000))
def test_complete_window_never_receives_a_later_fold(hop, k, gwm, ts):
    """Completion is final: once ``complete(wid, gwm)``, no event at
    ``ts >= gwm`` (the only events a watermark-respecting fold can still
    see) is ever assigned to ``wid``."""
    a = Hopping(hop * k, hop)
    ts = max(ts, gwm)  # events below the watermark are late-dropped
    wids, valid = a.assign(jnp.int32(ts))
    assigned = set(np.asarray(wids)[np.asarray(valid)].tolist())
    for wid in assigned:
        assert not a.complete(wid, gwm), (wid, gwm, ts)
    # contrapositive via first_dirty_wid: every assigned wid is at/after it
    assert all(w >= a.first_dirty_wid(gwm) for w in assigned)


@given(hop=st.integers(1, 50), k=st.integers(1, 8), frontier=st.integers(0, 5_000))
def test_first_dirty_wid_is_tight(hop, k, frontier):
    """``first_dirty_wid(F)`` is the exact minimum of the windows reachable
    by events at ts >= F: the window it names contains F, and no smaller
    window contains any ts >= F."""
    a = Hopping(hop * k, hop)
    w0 = a.first_dirty_wid(frontier)
    assert bool(a.contains(w0, frontier)) or (frontier < a.start_ts(w0) == 0)
    if w0 > 0:
        assert a.end_ts(w0 - 1) <= frontier  # smaller windows already closed
    # tumbling degenerate equals the original delta dirty rule
    t = Tumbling(hop * k)
    assert t.first_dirty_wid(frontier) == frontier // (hop * k)


@given(hop=st.integers(1, 40), k=st.integers(2, 6), seed=st.integers(0, 2**20))
def test_hopping_insert_counts_match_oracle(hop, k, seed):
    """Multi-window insert: a windowed GCounter under Hopping counts every
    event once per containing window — matching a direct per-window count."""
    rng = np.random.default_rng(seed)
    a = Hopping(hop * k, hop)
    n = int(rng.integers(4, 24))
    ts = np.sort(rng.integers(0, hop * k * 3, size=n)).astype(np.int32)
    spec = wgcounter(hop * k, num_slots=4 * k + 8, num_partitions=1, assigner=a)
    s = spec.zero()
    s = W.insert(spec, s, 0, jnp.array(ts), jnp.ones(n, bool),
                 actor=0, amounts=jnp.ones(n))
    s = W.increment_watermark(spec, s, 0, int(ts.max()) + hop * k)
    for wid in range(int(ts.max()) // hop + 1):
        v, ok = W.window_value(spec, s, wid)
        assert bool(ok)
        want = int(((ts >= wid * hop) & (ts < wid * hop + hop * k)).sum())
        assert float(v) == want, (wid, float(v), want)


def test_expand_events_lane_layout():
    """expand_events flattens [B] events into [B*K] newest-first lanes with
    out-of-range (pre-t=0) windows masked — the layout _expand_payload's
    jnp.repeat must match."""
    a = Hopping(10, 5)
    ts = jnp.array([3, 12], jnp.int32)
    wid, mask = expand_events(a, ts, jnp.array([True, True]))
    np.testing.assert_array_equal(np.asarray(wid), [0, -1, 2, 1])
    np.testing.assert_array_equal(np.asarray(mask), [True, False, True, True])
    # a masked-out event contributes no lanes at all
    _, mask2 = expand_events(a, ts, jnp.array([True, False]))
    np.testing.assert_array_equal(np.asarray(mask2), [True, False, False, False])


# ---------------------------------------------------------------------------
# Evicted-window read path (ok=False + error accounting)
# ---------------------------------------------------------------------------


def _drive_overflow(assigner, num_slots):
    """Fold one event per window id far past the ring size, without ever
    advancing the watermark — every slot reuse evicts an incomplete window."""
    spec = wgcounter(assigner.window_len, num_slots, 1, assigner=assigner)
    s = spec.zero()
    n_windows = num_slots * 3
    last_start = (n_windows - 1) * assigner.hop
    for start in range(0, last_start + 1, assigner.hop):
        t = jnp.array([start], jnp.int32)
        s = W.insert(spec, s, 0, t, jnp.ones(1, bool), actor=0, amounts=jnp.ones(1))
    return spec, s, n_windows


def test_evicted_incomplete_window_accounting_tumbling():
    spec, s, n_windows = _drive_overflow(Tumbling(10), num_slots=2)
    # every slot reuse beyond the first ring fill evicted an incomplete window
    assert int(s.errors[W.ERR_EVICT_INCOMPLETE]) == n_windows - 2
    # completed-by-now early windows read ok=False: evicted before complete
    s = W.increment_watermark(spec, s, 0, 10 * n_windows)
    for wid in (0, 1, n_windows - 3):
        _, ok = W.window_value(spec, s, wid)
        assert not bool(ok), wid
    v, ok = W.window_value(spec, s, n_windows - 1)
    assert bool(ok) and float(v) == 1.0


def test_evicted_incomplete_window_accounting_hopping():
    """Same invariant under overlap: slot reuse before completion is counted,
    evicted windows read not-ok, resident complete windows still read."""
    a = Hopping(20, 5)  # K=4 concurrent windows per event
    spec, s, n_windows = _drive_overflow(a, num_slots=8)
    assert int(s.errors[W.ERR_EVICT_INCOMPLETE]) > 0
    s = W.increment_watermark(spec, s, 0, a.end_ts(n_windows))
    evicted = [w for w in range(n_windows)
               if int(s.slot_wid[w % spec.num_slots]) > w]
    assert evicted, "overflow must have evicted windows"
    for wid in evicted:
        _, ok = W.window_value(spec, s, wid)
        assert not bool(ok), wid
    # the newest windows are resident and complete; each saw K events
    # (one per hop) except near the stream tail
    wid = n_windows - a.windows_per_event
    v, ok = W.window_value(spec, s, wid)
    assert bool(ok) and float(v) == a.windows_per_event


def test_late_events_still_counted_per_event_under_overlap():
    """ERR_LATE counts events (not per-window copies) under a K>1 assigner."""
    a = Hopping(10, 5)
    spec = wgcounter(10, 8, 1, assigner=a)
    s = spec.zero()
    s = W.increment_watermark(spec, s, 0, 25)
    ts = jnp.array([5, 30], jnp.int32)  # 5 is behind the watermark
    s = W.insert(spec, s, 0, ts, jnp.ones(2, bool), actor=0, amounts=jnp.ones(2))
    assert int(s.errors[W.ERR_LATE]) == 1
    # the late event folded into no window; 30 folded into windows 5 and 6
    s = W.increment_watermark(spec, s, 0, 100)
    for wid, want in ((0, 0.0), (1, 0.0), (5, 1.0), (6, 1.0)):
        v, ok = W.window_value(spec, s, wid)
        assert bool(ok) and float(v) == want, (wid, float(v))


def test_ring_drop_counts_per_window_assignment():
    """ERR_RING counts dropped (event, window) assignments: an event whose
    older overlapping window was already evicted still folds into its newer
    windows, and only the stale lane is counted."""
    a = Hopping(10, 5)
    spec = wgcounter(10, 4, 1, assigner=a)
    s = spec.zero()
    # fill the ring far ahead: windows 10 and 11 occupy slots 2 and 3
    s = W.insert(spec, s, 0, jnp.array([55], jnp.int32), jnp.ones(1, bool),
                 actor=0, amounts=jnp.ones(1))
    # ts=47 -> windows 9 (slot 1) and 8 (slot 0): both fold fine; but ts=43
    # -> windows 8 (ok) and 7 (slot 3, evicted by tenant 11) -> 1 ring drop
    before = int(s.errors[W.ERR_RING])
    s = W.insert(spec, s, 0, jnp.array([43], jnp.int32), jnp.ones(1, bool),
                 actor=0, amounts=jnp.ones(1))
    assert int(s.errors[W.ERR_RING]) == before + 1
    s = W.increment_watermark(spec, s, 0, 200)
    v, ok = W.window_value(spec, s, 8)
    assert bool(ok) and float(v) == 1.0  # the newer lane still landed
