"""Property tests: every CRDT is a join-semilattice.

merge must be commutative, associative, and idempotent for arbitrary update
interleavings — the foundation of the paper's convergence guarantee (§4.2).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import example, given, settings, strategies as st

from repro.core import (
    GCounter,
    GSet,
    LWWReg,
    MaxReg,
    MinReg,
    PNCounter,
    TopK,
    join,
    join_many,
)

settings.register_profile("ci-laws", max_examples=40, deadline=None)
settings.load_profile("ci-laws")

N_ACTORS = 4


def leaves_equal(a, b):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), rtol=1e-6)


# ---- state generators ----


def gcounter_from(ops):
    s = GCounter.zero(N_ACTORS)
    for actor, amt in ops:
        s = s.add(actor % N_ACTORS, abs(amt))
    return s


def pncounter_from(ops):
    s = PNCounter.zero(N_ACTORS)
    for actor, amt in ops:
        s = s.add(actor % N_ACTORS, amt)
    return s


def maxreg_from(ops):
    s = MaxReg.zero(())
    for _, amt in ops:
        s = s.insert(jnp.float32(amt))
    return s


def minreg_from(ops):
    s = MinReg.zero(())
    for _, amt in ops:
        s = s.insert(jnp.float32(amt))
    return s


def gset_from(ops):
    s = GSet.zero(16)
    for actor, amt in ops:
        s = s.insert((actor + int(abs(amt))) % 16)
    return s


def lww_from(ops):
    s = LWWReg.zero(())
    for i, (actor, amt) in enumerate(ops):
        s = s.set_float(i * 7 + actor, amt)
    return s


def topk_from(ops):
    s = TopK.zero(4)
    for actor, amt in ops:
        s = s.insert_batch(
            jnp.array([amt], jnp.float32),
            jnp.array([actor], jnp.uint32),
            jnp.ones(1, bool),
        )
    return s


MAKERS = [gcounter_from, pncounter_from, maxreg_from, minreg_from, gset_from, lww_from, topk_from]

ops_strategy = st.lists(
    st.tuples(st.integers(0, 3), st.floats(-100, 100, allow_nan=False, width=32)),
    min_size=1,
    max_size=8,
)


@pytest.mark.parametrize("maker", MAKERS, ids=[m.__name__ for m in MAKERS])
@given(ops_a=ops_strategy, ops_b=ops_strategy, ops_c=ops_strategy)
# a subnormal against 0.0: equal under flush-to-zero float compares (CPU and
# TPU both flush), distinct as bit patterns — TopK must order on the bits
@example(
    ops_a=[(0, 0.0)],
    ops_b=[(0, 4.203895392974451e-45)],
    ops_c=[(0, 0.0)],
)
def test_lattice_laws(maker, ops_a, ops_b, ops_c):
    a, b, c = maker(ops_a), maker(ops_b), maker(ops_c)
    # commutativity
    leaves_equal(join(a, b), join(b, a))
    # associativity
    leaves_equal(join(join(a, b), c), join(a, join(b, c)))
    # idempotence
    leaves_equal(join(a, a), a)
    ab = join(a, b)
    leaves_equal(join(ab, b), ab)


@pytest.mark.parametrize("maker", MAKERS, ids=[m.__name__ for m in MAKERS])
@given(ops=st.lists(ops_strategy, min_size=2, max_size=5), seed=st.integers(0, 2**16))
def test_convergence_any_order(maker, ops, seed):
    """N replicas merged in any order converge to the same state."""
    states = [maker(o) for o in ops]
    ref = join_many(states)
    rng = np.random.default_rng(seed)
    perm = rng.permutation(len(states))
    shuffled = [states[i] for i in perm]
    # sequential left fold in shuffled order
    acc = shuffled[0]
    for s in shuffled[1:]:
        acc = join(acc, s)
    leaves_equal(acc, ref)


def test_gcounter_value():
    a = GCounter.zero(3).add(0, 5.0).add(1, 2.0)
    b = GCounter.zero(3).add(1, 2.0).add(2, 4.0)
    # slot 1 written by actor 1 in both with same total update history on b
    m = join(a, b)
    assert float(m.value) == 5.0 + 2.0 + 4.0


def test_pncounter_signed():
    a = PNCounter.zero(2).add(0, 5.0).add(0, -3.0)
    assert float(a.value) == 2.0


def test_topk_set_semantics():
    t = TopK.zero(3)
    t = t.insert_batch(jnp.array([5.0, 5.0]), jnp.array([7, 7], jnp.uint32), jnp.ones(2, bool))
    m = join(t, t)
    vals = np.asarray(m.vals)
    # duplicate (5.0, id 7) collapses to one entry
    assert (vals == 5.0).sum() == 1
