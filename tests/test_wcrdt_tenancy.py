"""Slot tenancy of ``W.insert``: the dense per-slot max gives, bit for bit,
what a scatter-max (``jax.ops.segment_max``) into the ring's slots gives.

The reference below is Algorithm 1's batched INSERT written plainly, with
the tenancy as a segment max; ``W.insert`` must agree with it on the slot
tenants, the error counters and every window leaf, whatever the batch: out
of time order, wider than the ring, masked, late, or landing on a ring whose
tenants partly advance.
"""
import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import wcrdt as W
from repro.core.window import Hopping, expand_events

WL, P, KEYS, B = 10, 2, 5, 96  # window length, partitions, keys, events


def reference_insert(spec, state, partition, ts, mask, **inputs):
    """``W.insert`` with its tenancy as a segment max over the ring's slots."""
    num_slots, K = spec.num_slots, spec.assigner.windows_per_event
    late = mask & (ts < state.progress[partition])
    mask = mask & ~late
    wid, mask = expand_events(spec.assigner, ts, mask)
    inputs = {k: jnp.repeat(v, K, axis=0) if jnp.ndim(v) else v
              for k, v in inputs.items()}
    slot = wid % num_slots
    seg_max = jax.ops.segment_max(jnp.where(mask, wid, W.NO_WID), slot,
                                  num_segments=num_slots)
    new_slot_wid = jnp.maximum(state.slot_wid, jnp.maximum(seg_max, W.NO_WID))
    advancing = new_slot_wid > state.slot_wid
    gwm_wid = spec.assigner.first_dirty_wid(W.global_watermark(spec, state))
    evict_bad = advancing & (state.slot_wid >= 0) & (state.slot_wid >= gwm_wid)
    windows = jax.tree.map(
        lambda leaf, z: jnp.where(
            advancing.reshape((-1,) + (1,) * (leaf.ndim - 1)), z, leaf),
        state.windows, spec.zero_windows())
    stale = mask & (wid < new_slot_wid[slot])
    windows = spec.fold(windows, slot, mask & ~stale, **inputs)
    counts = jnp.stack([jnp.sum(late), jnp.sum(stale), jnp.sum(evict_bad)])
    return dataclasses.replace(state, slot_wid=new_slot_wid, windows=windows,
                               errors=state.errors + counts.astype(jnp.int32))


def _bits(state):
    return [np.asarray(x).tobytes() for x in jax.tree.leaves(state)]


def _batches(case, num_slots, rng):
    """``(ts, mask, watermark)`` per step: fold ``ts`` under ``mask`` into
    partition 0, then raise both partitions' watermarks to ``watermark``."""
    span = num_slots * WL
    ones = np.ones(B, bool)
    fill = np.sort(rng.integers(0, 3 * WL, B))  # an occupied ring, in order
    # the newest window's one event sits in the last or the first lane
    if case == "unsorted":
        ts = rng.permutation(rng.integers(0, 4 * WL, B))
        return [(np.append(ts[1:], 4 * WL), ones, 0)]
    if case == "ring_wrap":  # more windows in one batch than the ring holds
        ts = rng.integers(0, 3 * span, B)
        return [(np.append(3 * span, ts[1:]), ones, 0)]
    if case == "all_masked":
        return [(fill, ones, 2 * WL), (rng.integers(0, span, B), np.zeros(B, bool), 0)]
    if case == "late":  # half below the watermark, some before t=0
        return [(fill, ones, 2 * WL),
                (rng.integers(-WL, 4 * WL, B), rng.random(B) < 0.8, 0)]
    assert case == "occupied"
    # the middle window's tenants stay; the first and last windows' lanes
    # go to their tenant or to its successor one ring turn later, so the
    # tenant's own lanes fall stale, and the last one was not yet complete
    again = rng.integers(0, 3 * WL, B)
    turn = np.where(again // WL == 1, 0, rng.choice([0, span], B))
    return [(fill, ones, WL), (again + turn, ones, 0)]


CASES = ["unsorted", "ring_wrap", "all_masked", "late", "occupied"]


@pytest.mark.parametrize("case", CASES)
@pytest.mark.parametrize("K", [1, 2])
@pytest.mark.parametrize("num_slots", [8, 16, 64])
def test_insert_matches_segment_max_tenancy(num_slots, K, case):
    assigner = Hopping(WL, WL // K)
    spec = W.wgcounter(WL, num_slots, P, key_shape=(KEYS,), assigner=assigner)
    rng = np.random.default_rng([num_slots, K, CASES.index(case)])
    got = want = spec.zero()
    ins = jax.jit(lambda s, ts, m, a, k: W.insert(
        spec, s, 0, ts, m, actor=0, amounts=a, keys=k))
    ref = jax.jit(lambda s, ts, m, a, k: reference_insert(
        spec, s, 0, ts, m, actor=0, amounts=a, keys=k))
    for ts, mask, wm in _batches(case, num_slots, rng):
        args = (jnp.asarray(ts, jnp.int32), jnp.asarray(mask),
                jnp.asarray(rng.random(B), jnp.float32),
                jnp.asarray(rng.integers(0, KEYS, B), jnp.int32))
        tenants = np.asarray(got.slot_wid)
        got, want = ins(got, *args), ref(want, *args)
        assert _bits(got) == _bits(want)
        for p in range(P):
            got = W.increment_watermark(spec, got, p, wm)
            want = W.increment_watermark(spec, want, p, wm)
    # the case reached what it names
    errors = np.asarray(got.errors)
    if case in ("unsorted", "ring_wrap"):
        assert (np.asarray(got.slot_wid) >= 0).sum() > 1
    if case == "ring_wrap":
        assert errors[W.ERR_RING] > 0
    if case == "all_masked":
        assert not errors.any()
    if case == "late":
        assert errors[W.ERR_LATE] > 0
    if case == "occupied":
        assert errors[W.ERR_RING] > 0 and errors[W.ERR_EVICT_INCOMPLETE] > 0
        after = np.asarray(got.slot_wid)[tenants >= 0]
        held = tenants[tenants >= 0]
        assert (after == held).any() and (after > held).any()
