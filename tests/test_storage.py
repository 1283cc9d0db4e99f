"""CheckpointStorage.put lattice-merge laws (Algorithm 2's "sometimes do").

Concurrent checkpointers of the same partition are allowed, so put must be a
join: the stored checkpoint's key ``(nxt_idx, coverage, epoch)`` (the exact
tie-break order implemented in storage.py) has to behave like a
join-semilattice — idempotent, commutative at the key level, and monotone
under any interleaving — or a slow checkpointer could regress recovery.
"""
from __future__ import annotations

import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

from repro.runtime.storage import CheckpointStorage, PartitionCheckpoint, _coverage

settings.register_profile("ci-storage", max_examples=40, deadline=None)
settings.load_profile("ci-storage")


def mk_ckpt(nxt_idx: int, folded: list[int], epoch: int = 0) -> PartitionCheckpoint:
    """A checkpoint whose coverage is sum(folded) — local/shared payloads are
    opaque to the merge rule, so a tag is enough to tell objects apart."""
    baseline = (
        (np.asarray(folded, dtype=np.float64), np.zeros(len(folded))),
    )
    return PartitionCheckpoint(
        nxt_idx=nxt_idx,
        nxt_odx=nxt_idx,
        emitted_upto=nxt_idx,
        shared=("shared", nxt_idx, tuple(folded), epoch),
        local=None,
        baseline=baseline,
        epoch=epoch,
    )


def key(ck: PartitionCheckpoint) -> tuple:
    return (ck.nxt_idx, _coverage(ck), ck.epoch)


CKPT = st.tuples(
    st.integers(0, 5),  # nxt_idx — small range to force ties
    st.lists(st.integers(0, 3), min_size=2, max_size=2),  # folded -> coverage
    st.integers(0, 2),  # epoch
)


def put_all(cks):
    s = CheckpointStorage()
    for ck in cks:
        s.put(0, ck)
    return s


def test_none_baseline_has_zero_coverage():
    assert _coverage(PartitionCheckpoint(0, 0, 0, None, None)) == 0.0


@given(c=CKPT)
def test_put_idempotent(c):
    ck = mk_ckpt(*c)
    s = put_all([ck])
    first = s.get(0)
    s.put(0, ck)
    assert s.get(0) is first  # re-putting the same snapshot changes nothing


@given(a=CKPT, b=CKPT)
def test_put_commutative_on_key(a, b):
    """put(a);put(b) and put(b);put(a) must agree on the stored *key* — the
    recovery-relevant ordering — for every pair, including exact key ties
    (where either equal-keyed object is a legal representative)."""
    ka, kb = key(mk_ckpt(*a)), key(mk_ckpt(*b))
    sab = put_all([mk_ckpt(*a), mk_ckpt(*b)])
    sba = put_all([mk_ckpt(*b), mk_ckpt(*a)])
    assert key(sab.get(0)) == key(sba.get(0)) == max(ka, kb)


@given(cs=st.lists(CKPT, min_size=1, max_size=6))
def test_put_monotone(cs):
    """Under any put sequence the stored key is the running max and never
    regresses — a stale checkpointer cannot undo a fresher snapshot."""
    s = CheckpointStorage()
    best = None
    for c in cs:
        ck = mk_ckpt(*c)
        s.put(0, ck)
        best = key(ck) if best is None else max(best, key(ck))
        assert key(s.get(0)) == best


@given(cs=st.lists(CKPT, min_size=2, max_size=5))
def test_put_order_invariant_key(cs):
    """Full permutation-independence at the key level: left-to-right and
    right-to-left interleavings converge to the same stored key."""
    fwd = put_all([mk_ckpt(*c) for c in cs])
    rev = put_all([mk_ckpt(*c) for c in reversed(cs)])
    assert key(fwd.get(0)) == key(rev.get(0))


def test_tiebreak_order_is_nxt_idx_then_coverage_then_epoch():
    lo = mk_ckpt(1, [9, 9], epoch=9)
    hi = mk_ckpt(2, [0, 0], epoch=0)
    s = put_all([lo, hi])
    assert s.get(0) is s._data[0] and s.get(0).nxt_idx == 2  # idx dominates
    # equal idx: coverage dominates epoch
    rich = mk_ckpt(2, [3, 3], epoch=0)
    s.put(0, rich)
    assert _coverage(s.get(0)) == 6.0
    poor_new_epoch = mk_ckpt(2, [0, 0], epoch=5)
    s.put(0, poor_new_epoch)
    assert _coverage(s.get(0)) == 6.0  # newer epoch cannot beat richer coverage
    # equal (idx, coverage): epoch breaks the tie
    newer = mk_ckpt(2, [3, 3], epoch=7)
    s.put(0, newer)
    assert s.get(0).epoch == 7


def test_get_and_has_roundtrip():
    s = CheckpointStorage()
    assert s.get(3) is None and not s.has(3)
    ck = mk_ckpt(0, [0, 0])
    s.put(3, ck)
    assert s.has(3) and s.get(3) is ck
    assert s.puts == 1 and s.gets == 2
