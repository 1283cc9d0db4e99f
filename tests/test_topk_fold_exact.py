"""TopK's fast window fold is exact: for every batch it returns the rows of
the set join of each slot's row with every masked lane of its window.

The fast fold pre-filters each window offset with ``lax.top_k``.  Lane order
and repeated bids must not reach the result: a tie between the k-th and the
(k+1)-th price, or a duplicate (price, id) pair among the k, sends the batch
through the exact join.  Prices are compared as bit patterns, so a subnormal
is above 0.0 and 0.0 above -0.0.
"""
import dataclasses
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.core.crdt import TopK, _topk_join_sorted
from repro.launch.mesh import make_data_mesh
from repro.launch.stream import build_pipeline
from repro.streaming.events import KIND_BID
from repro.streaming.generator import NexmarkConfig, generate_log
from repro.streaming.queries import make_q7

SUB = float(np.float32(1.4e-45))  # the least positive subnormal


def bits(x):
    return np.asarray(x, np.float32).view(np.uint32)


@partial(jax.jit, static_argnames=("k", "active", "W"))
def _folds(rows_v, rows_i, slot_ids, mask, vals, ids, *, k, active, W):
    """The fast fold from window 0, the fold over every slot (``lo=None``),
    and ``insert_batch`` of each slot's lanes into its row."""
    t = TopK(rows_v, rows_i)
    fast = t.fold_windows(slot_ids, mask, vals, ids, lo=jnp.int32(0), active=active)
    full = t.fold_windows(slot_ids, mask, vals, ids)
    per = [TopK(rows_v[w], rows_i[w]).insert_batch(vals, ids, mask & (slot_ids == w))
           for w in range(W)]
    ins = TopK(jnp.stack([p.vals for p in per]), jnp.stack([p.ids for p in per]))
    return fast, full, ins


def folds(lanes, k, W=2, rows=None, mask=None):
    """``lanes``: (price, id, slot) triples; every slot lies in the fast
    fold's ``W`` offsets."""
    v, i, s = (np.array(x) for x in zip(*lanes))
    if rows is None:
        rows = TopK.zero_windows(W, k)
    mask = np.ones(len(v), bool) if mask is None else np.asarray(mask)
    return _folds(rows.vals, rows.ids, jnp.asarray(s, jnp.int32), jnp.asarray(mask),
                  jnp.asarray(v, jnp.float32), jnp.asarray(i, jnp.uint32),
                  k=k, active=W, W=W)


def assert_same(a, b):
    np.testing.assert_array_equal(bits(a.vals), bits(b.vals))
    np.testing.assert_array_equal(np.asarray(a.ids), np.asarray(b.ids))


def rows_of(vals, ids):
    return TopK(jnp.asarray(vals, jnp.float32), jnp.asarray(ids, jnp.uint32))


# (name, lanes, k, want row of slot 0 as (prices, ids))
CASES = [
    # lane order decided the tie at the k-th place: the exact join keeps id 9
    ("tie_at_kth", [(5.0, 1, 0), (3.0, 2, 0), (3.0, 9, 0)], 2, ([5.0, 3.0], [1, 9])),
    # a repeated bid took a second place of the k and pushed (3, 9) out
    ("duplicate", [(5.0, 1, 0), (5.0, 1, 0), (3.0, 9, 0)], 2, ([5.0, 3.0], [1, 9])),
    ("fewer_than_k", [(2.0, 4, 0), (7.0, 1, 1)], 3,
     ([2.0, -np.inf, -np.inf], [4, 0, 0])),
    ("fewer_than_k_with_duplicates", [(2.0, 4, 0), (2.0, 4, 0), (1.0, 4, 0)], 3,
     ([2.0, 1.0, -np.inf], [4, 4, 0])),
    ("subnormal_above_zero", [(0.0, 3, 0), (SUB, 3, 0), (0.0, 3, 0), (0.0, 8, 0)], 2,
     ([SUB, 0.0], [3, 8])),
    ("signed_zeros", [(-0.0, 5, 0), (0.0, 5, 0), (-0.0, 5, 0), (-1.0, 6, 0)], 2,
     ([0.0, -0.0], [5, 5])),
    ("all_equal_prices", [(4.0, j % 5, 0) for j in range(12)], 3,
     ([4.0, 4.0, 4.0], [4, 3, 2])),
    ("a_valid_minus_inf", [(-np.inf, 7, 0), (1.0, 2, 0)], 3,
     ([1.0, -np.inf, -np.inf], [2, 7, 0])),
]


@pytest.mark.parametrize("name,lanes,k,want", CASES, ids=[c[0] for c in CASES])
def test_fast_fold_is_the_exact_join(name, lanes, k, want):
    fast, full, ins = folds(lanes, k)
    assert_same(fast, full)
    assert_same(fast, ins)
    np.testing.assert_array_equal(bits(fast.vals[0]), bits(want[0]))
    np.testing.assert_array_equal(np.asarray(fast.ids[0]), want[1])


def test_ties_at_kth_in_every_offset_against_their_rows():
    """Both offsets tie at the k-th place, against rows that already hold
    pairs of the batch's prices."""
    rows = rows_of([[6.0, 2.0], [9.0, 1.0]], [[3, 4], [1, 1]])
    lanes = [(2.0, 5, 0), (2.0, 1, 0), (6.0, 3, 0), (1.0, 9, 1), (1.0, 2, 1), (1.0, 8, 1)]
    fast, full, ins = folds(lanes, 2, rows=rows)
    assert_same(fast, full)
    assert_same(fast, ins)
    assert np.asarray(fast.ids).tolist() == [[3, 5], [1, 9]]


def test_all_lanes_masked_leaves_the_rows():
    rows = rows_of([[6.0, 2.0], [9.0, -np.inf]], [[3, 4], [1, 0]])
    lanes = [(8.0, 1, 0), (7.0, 2, 1), (8.0, 1, 1)]
    fast, full, _ = folds(lanes, 2, rows=rows, mask=[False] * 3)
    assert_same(fast, full)
    assert_same(fast, rows)


def test_unambiguous_batch_keeps_the_fast_join():
    """Distinct prices: the pre-filter's k lanes are the answer, whatever
    lies below them."""
    rng = np.random.default_rng(0)
    prices = rng.permutation(64).astype(np.float32)
    lanes = [(p, j, int(p) % 2) for j, p in enumerate(prices)]
    fast, full, _ = folds(lanes, 4)
    assert_same(fast, full)
    assert np.asarray(fast.vals).tolist() == [[62.0, 60.0, 58.0, 56.0], [63.0, 61.0, 59.0, 57.0]]


PRICES = [-1.0, -0.0, 0.0, SUB, 1.0, 2.0]
lane = st.tuples(st.sampled_from(PRICES), st.integers(0, 3), st.integers(0, 1), st.booleans())


@settings(max_examples=60, deadline=None)
@given(k=st.integers(1, 3), before=st.lists(lane, min_size=0, max_size=10),
       batch=st.lists(lane, min_size=1, max_size=10))
def test_ties_and_duplicates_property(k, before, batch):
    """Batches from a six-price alphabet, where ties and repeated bids are
    the rule, folded onto rows a first batch filled: the fast fold, the fold
    over every slot and ``insert_batch`` per slot agree bit for bit."""
    # padded to a fixed width, so the jitted folds compile once per k
    before, batch = ([*x, *[(0.0, 0, 0, False)] * (10 - len(x))] for x in (before, batch))
    rows, _, _ = folds([x[:3] for x in before], k, mask=[x[3] for x in before])
    fast, full, ins = folds([x[:3] for x in batch], k, rows=rows, mask=[x[3] for x in batch])
    assert_same(fast, full)
    assert_same(fast, ins)
    # and the result is the join of each row with its slot's lanes
    for w in range(2):
        m = np.array([x[3] and x[2] == w for x in batch])
        v = np.where(m, [x[0] for x in batch], -np.inf).astype(np.float32)
        i = np.where(m, [x[1] for x in batch], 0).astype(np.uint32)
        jv, ji = _topk_join_sorted(rows.vals[w], rows.ids[w], jnp.asarray(v), jnp.asarray(i), k)
        np.testing.assert_array_equal(bits(fast.vals[w]), bits(jv))
        np.testing.assert_array_equal(np.asarray(fast.ids[w]), np.asarray(ji))


# -- the dataplane -----------------------------------------------------------

WL, NB, EPB, K = 100, 16, 256, 8  # a window spans about four batches


def planted_log(plant: str):
    """A seeded one-partition log with, in each of the first windows, bids
    above every generated price: all at one price over k + 2 auctions
    (``ties``), k / 2 auctions at k distinct prices each bid twice
    (``duplicates``), or one price over k - 1 auctions, each bid more than
    once (``both``).  The window's first 2k bids take them, so one batch
    holds more than k of them, and 2k bids spread over its batches, so the
    rows join them too."""
    log = generate_log(NexmarkConfig(num_partitions=1, num_batches=NB,
                                     events_per_batch=EPB, num_auctions=1000))
    ts, kind = np.asarray(log.ts), np.asarray(log.kind)
    price, auction = np.array(log.price), np.array(log.auction)
    top = np.float32(price.max() + 100.0)
    for w in range(3):
        lanes = np.flatnonzero(((ts // WL) == w) & (kind == KIND_BID))
        pick = np.union1d(lanes[:2 * K],
                          lanes[np.linspace(0, len(lanes) - 1, 2 * K).astype(int)])
        j = np.arange(len(pick))
        if plant == "ties":
            price.flat[pick], auction.flat[pick] = top, 500 + j % (K + 2)
        elif plant == "duplicates":
            price.flat[pick], auction.flat[pick] = top + j % K, 500 + (j % K) // 2
        else:
            price.flat[pick], auction.flat[pick] = top, 500 + j % (K - 1)
    return dataclasses.replace(log, price=jnp.asarray(price), auction=jnp.asarray(auction))


@pytest.fixture(scope="module")
def pipelines():
    mesh = make_data_mesh(1)
    q = make_q7(1, window_len=WL, num_slots=16, k=K)
    return mesh, q, {d: build_pipeline(q, mesh, 4, delta_sync=d, n_windows=4)
                     for d in (True, False)}


@pytest.mark.parametrize("plant", ["ties", "duplicates", "both"])
def test_pipeline_matches_the_oracle_with_planted_ties(pipelines, plant):
    mesh, q, pipes = pipelines
    log = planted_log(plant)
    with mesh:
        (od, vd, _), (of, vf, _) = (pipes[d](log) for d in (True, False))
    np.testing.assert_array_equal(np.asarray(od), np.asarray(of))
    np.testing.assert_array_equal(bits(vd), bits(vf))
    closed = np.flatnonzero(np.asarray(od)[0] > 0.5)
    assert len(closed) >= 3
    for w in closed:
        ov, oi = q.oracle(log, int(w))
        got = np.asarray(vd)[0, w]
        np.testing.assert_array_equal(bits(got[:K]), bits(ov))
        np.testing.assert_array_equal(got[K:], np.asarray(oi, np.float32))
    # the planted bids lead each of the first windows
    planted = K - 1 if plant == "both" else K
    assert np.all(np.asarray(vd)[0, :3, K:K + planted] >= 500)
