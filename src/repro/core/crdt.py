"""CRDT catalog — state-based (convergent) replicated data types as JAX pytrees.

Every CRDT here is a join-semilattice: ``merge`` is commutative, associative
and idempotent (property-tested in tests/test_crdt_laws.py).  Design rules:

* State is dense arrays — maps keyed by node become fixed ``[num_actors]``
  slot vectors so merges vectorize and ride collectives (see lattice.py).
* Each class also provides *windowed* folds: the same CRDT stored with a
  leading ``[W]`` ring-slot axis, updated from a batch of timestamped events
  in one vectorized scatter (this is what the Pallas ``window_agg`` kernel
  accelerates on TPU).
* Grow-only slot counters require per-actor monotonicity: only actor ``p``
  writes slot ``p``, and contributions are non-negative (PN pairs handle
  signed values).
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core.lattice import (
    Reduce,
    float_to_ordered_u32,
    lattice_dataclass,
    lex_join,
    ordered_u32_to_float,
)

NEG_INF = jnp.float32(-jnp.inf)
# float_to_ordered_u32(-inf): the TopK padding value in the sort-key domain
_NEG_INF_KEY = 0x007FFFFF
# the NaN whose ordered-u32 key is 0: TopK's pre-filter puts masked lanes here,
# below every price, as lax.top_k orders floats by that key
_BELOW_EVERY_PRICE = np.array(0xFFFFFFFF, np.uint32).view(np.float32)


def _masked(vals: jax.Array, mask: jax.Array, fill) -> jax.Array:
    return jnp.where(mask, vals, jnp.asarray(fill, vals.dtype))


# ---------------------------------------------------------------------------
# GCounter — grow-only counter (optionally keyed, e.g. per Nexmark category).
# ---------------------------------------------------------------------------


@lattice_dataclass(slots=Reduce.MAX)
class GCounter:
    """slots[actor, *key_shape]; merge = elementwise max; value = sum(actors)."""

    slots: jax.Array

    @classmethod
    def zero(cls, num_actors: int, key_shape: tuple[int, ...] = (), dtype=jnp.float32):
        return cls(slots=jnp.zeros((num_actors, *key_shape), dtype=dtype))

    def add(self, actor, amount, key=None) -> "GCounter":
        """Add non-negative ``amount`` to this actor's slot (optionally keyed)."""
        if key is None:
            return GCounter(self.slots.at[actor].add(amount))
        return GCounter(self.slots.at[actor, key].add(amount))

    @property
    def value(self) -> jax.Array:
        return jnp.sum(self.slots, axis=0)

    # -- windowed ------------------------------------------------------------
    @classmethod
    def zero_windows(cls, W: int, num_actors: int, key_shape=(), dtype=jnp.float32):
        return cls(slots=jnp.zeros((W, num_actors, *key_shape), dtype=dtype))

    def fold_windows(
        self, slot_ids: jax.Array, mask: jax.Array, actor, amounts: jax.Array,
        keys: jax.Array | None = None,
    ) -> "GCounter":
        amounts = _masked(amounts.astype(self.slots.dtype), mask, 0)
        if keys is None:
            new = self.slots.at[slot_ids, actor].add(amounts)
        else:
            new = self.slots.at[slot_ids, actor, keys].add(amounts)
        return GCounter(new)

    def window_value(self, slot) -> jax.Array:
        return jnp.sum(self.slots[slot], axis=0)


@lattice_dataclass(pos=Reduce.MAX, neg=Reduce.MAX)
class PNCounter:
    """Positive/negative GCounter pair — supports signed updates."""

    pos: jax.Array
    neg: jax.Array

    @classmethod
    def zero(cls, num_actors: int, key_shape: tuple[int, ...] = (), dtype=jnp.float32):
        z = jnp.zeros((num_actors, *key_shape), dtype=dtype)
        return cls(pos=z, neg=z)

    def add(self, actor, amount, key=None) -> "PNCounter":
        up = jnp.maximum(amount, 0)
        dn = jnp.maximum(-amount, 0)
        if key is None:
            return PNCounter(self.pos.at[actor].add(up), self.neg.at[actor].add(dn))
        return PNCounter(
            self.pos.at[actor, key].add(up), self.neg.at[actor, key].add(dn)
        )

    @property
    def value(self) -> jax.Array:
        return jnp.sum(self.pos, axis=0) - jnp.sum(self.neg, axis=0)

    @classmethod
    def zero_windows(cls, W: int, num_actors: int, key_shape=(), dtype=jnp.float32):
        z = jnp.zeros((W, num_actors, *key_shape), dtype=dtype)
        return cls(pos=z, neg=z)

    def fold_windows(self, slot_ids, mask, actor, amounts, keys=None) -> "PNCounter":
        amounts = _masked(amounts.astype(self.pos.dtype), mask, 0)
        up, dn = jnp.maximum(amounts, 0), jnp.maximum(-amounts, 0)
        if keys is None:
            return PNCounter(
                self.pos.at[slot_ids, actor].add(up),
                self.neg.at[slot_ids, actor].add(dn),
            )
        return PNCounter(
            self.pos.at[slot_ids, actor, keys].add(up),
            self.neg.at[slot_ids, actor, keys].add(dn),
        )

    def window_value(self, slot) -> jax.Array:
        return jnp.sum(self.pos[slot], axis=0) - jnp.sum(self.neg[slot], axis=0)


# ---------------------------------------------------------------------------
# Max / Min registers.
# ---------------------------------------------------------------------------


@lattice_dataclass(v=Reduce.MAX)
class MaxReg:
    v: jax.Array

    @classmethod
    def zero(cls, key_shape: tuple[int, ...] = (), dtype=jnp.float32):
        return cls(v=jnp.full(key_shape, -jnp.inf, dtype=dtype))

    def insert(self, x, key=None) -> "MaxReg":
        if key is None:
            return MaxReg(jnp.maximum(self.v, x))
        return MaxReg(self.v.at[key].max(x))

    @property
    def value(self) -> jax.Array:
        return self.v

    @classmethod
    def zero_windows(cls, W: int, key_shape=(), dtype=jnp.float32):
        return cls(v=jnp.full((W, *key_shape), -jnp.inf, dtype=dtype))

    def fold_windows(self, slot_ids, mask, vals, keys=None) -> "MaxReg":
        vals = _masked(vals.astype(self.v.dtype), mask, -jnp.inf)
        if keys is None:
            return MaxReg(self.v.at[slot_ids].max(vals))
        return MaxReg(self.v.at[slot_ids, keys].max(vals))

    def window_value(self, slot) -> jax.Array:
        return self.v[slot]


@lattice_dataclass(v=Reduce.MIN)
class MinReg:
    v: jax.Array

    @classmethod
    def zero(cls, key_shape: tuple[int, ...] = (), dtype=jnp.float32):
        return cls(v=jnp.full(key_shape, jnp.inf, dtype=dtype))

    def insert(self, x, key=None) -> "MinReg":
        if key is None:
            return MinReg(jnp.minimum(self.v, x))
        return MinReg(self.v.at[key].min(x))

    @property
    def value(self) -> jax.Array:
        return self.v

    @classmethod
    def zero_windows(cls, W: int, key_shape=(), dtype=jnp.float32):
        return cls(v=jnp.full((W, *key_shape), jnp.inf, dtype=dtype))

    def fold_windows(self, slot_ids, mask, vals, keys=None) -> "MinReg":
        vals = _masked(vals.astype(self.v.dtype), mask, jnp.inf)
        if keys is None:
            return MinReg(self.v.at[slot_ids].min(vals))
        return MinReg(self.v.at[slot_ids, keys].min(vals))

    def window_value(self, slot) -> jax.Array:
        return self.v[slot]


# ---------------------------------------------------------------------------
# G-Set over a bounded domain (bitmap).
# ---------------------------------------------------------------------------


@lattice_dataclass(bits=Reduce.OR)
class GSet:
    bits: jax.Array  # u8[domain] (0/1; uint8 so scatter-max == or)

    @classmethod
    def zero(cls, domain: int):
        return cls(bits=jnp.zeros((domain,), dtype=jnp.uint8))

    def insert(self, elem) -> "GSet":
        return GSet(self.bits.at[elem].set(jnp.uint8(1)))

    @property
    def value(self) -> jax.Array:
        return self.bits.astype(jnp.bool_)

    @property
    def size(self) -> jax.Array:
        return jnp.sum(self.bits.astype(jnp.int32))

    @classmethod
    def zero_windows(cls, W: int, domain: int):
        return cls(bits=jnp.zeros((W, domain), dtype=jnp.uint8))

    def fold_windows(self, slot_ids, mask, elems) -> "GSet":
        # scatter-or == scatter-max on {0,1} uint8
        return GSet(self.bits.at[slot_ids, elems].max(mask.astype(jnp.uint8)))

    def window_value(self, slot) -> jax.Array:
        return self.bits[slot].astype(jnp.bool_)


# ---------------------------------------------------------------------------
# LWW register — lexicographic (ts, payload) lattice; custom merge.
# ---------------------------------------------------------------------------


@lattice_dataclass(ts="custom", val="custom")
class LWWReg:
    ts: jax.Array  # i32[*key_shape]
    val: jax.Array  # ordered-u32 payload

    @classmethod
    def zero(cls, key_shape: tuple[int, ...] = ()):
        return cls(
            ts=jnp.full(key_shape, -(2**31), dtype=jnp.int32),
            val=jnp.zeros(key_shape, dtype=jnp.uint32),
        )

    def merge(self, other: "LWWReg") -> "LWWReg":
        ts, val = lex_join(self.ts, self.val, other.ts, other.val)
        return LWWReg(ts, val)

    def set_float(self, ts, x, key=None) -> "LWWReg":
        u = float_to_ordered_u32(jnp.asarray(x, jnp.float32))
        return self._set(ts, u, key)

    def set_u32(self, ts, x, key=None) -> "LWWReg":
        return self._set(ts, jnp.asarray(x, jnp.uint32), key)

    def _set(self, ts, u, key) -> "LWWReg":
        ts = jnp.asarray(ts, jnp.int32)
        if key is None:
            nts, nval = lex_join(self.ts, self.val, ts, u)
            return LWWReg(nts, nval)
        nts, nval = lex_join(self.ts[key], self.val[key], ts, u)
        return LWWReg(self.ts.at[key].set(nts), self.val.at[key].set(nval))

    @property
    def value_float(self) -> jax.Array:
        return ordered_u32_to_float(self.val)

    @property
    def value_u32(self) -> jax.Array:
        return self.val


# ---------------------------------------------------------------------------
# Bounded Top-K (set semantics) — Q7 "highest bids" lattice.
# ---------------------------------------------------------------------------


def _topk_join_sorted(vals_a, ids_a, vals_b, ids_b, k: int):
    """Join two top-k sets (desc-sorted, -inf padded) into the top-k union.

    Set semantics: exact (val, id) duplicates collapse, so the join is
    idempotent.  Order and duplicates are decided on the ordered-u32 bit
    pattern of ``val`` (as ``LWWReg`` does), never on float comparison: a
    backend that flushes subnormals compares 4e-45 equal to 0.0, and a float
    sort would then keep whichever of the two came first.
    """
    keys = float_to_ordered_u32(jnp.concatenate([vals_a, vals_b], axis=-1))
    ids = jnp.concatenate([ids_a, ids_b], axis=-1)
    # ascending lexicographic sort by (val bits, id)
    skeys, sids = lax.sort((keys, ids), dimension=-1, num_keys=2)
    # mark duplicates of their left neighbour
    dup = jnp.zeros(skeys.shape, dtype=bool)
    dup = dup.at[..., 1:].set(
        (skeys[..., 1:] == skeys[..., :-1]) & (sids[..., 1:] == sids[..., :-1])
    )
    skeys = jnp.where(dup, _NEG_INF_KEY, skeys)
    sids = jnp.where(dup, 0, sids)
    skeys, sids = lax.sort((skeys, sids), dimension=-1, num_keys=2)
    # top-k = last k ascending, reversed to descending
    top_v = ordered_u32_to_float(skeys[..., -k:][..., ::-1])
    top_i = sids[..., -k:][..., ::-1]
    return top_v, top_i


@lattice_dataclass(vals="custom", ids="custom")
class TopK:
    """Top-k (value, id) pairs, descending, padded with (-inf, 0)."""

    vals: jax.Array  # f32[..., k]
    ids: jax.Array  # u32[..., k]

    @classmethod
    def zero(cls, k: int, key_shape: tuple[int, ...] = ()):
        return cls(
            vals=jnp.full((*key_shape, k), -jnp.inf, dtype=jnp.float32),
            ids=jnp.zeros((*key_shape, k), dtype=jnp.uint32),
        )

    @property
    def k(self) -> int:
        return self.vals.shape[-1]

    def merge(self, other: "TopK") -> "TopK":
        v, i = _topk_join_sorted(self.vals, self.ids, other.vals, other.ids, self.k)
        return TopK(v, i)

    def insert_batch(self, vals: jax.Array, ids: jax.Array, mask: jax.Array) -> "TopK":
        vals = _masked(vals.astype(jnp.float32), mask, -jnp.inf)
        ids = jnp.where(mask, ids.astype(jnp.uint32), 0)
        v, i = _topk_join_sorted(self.vals, self.ids, vals, ids, self.k)
        return TopK(v, i)

    @property
    def value(self) -> tuple[jax.Array, jax.Array]:
        return self.vals, self.ids

    # -- windowed ------------------------------------------------------------
    @classmethod
    def zero_windows(cls, W: int, k: int):
        return cls(
            vals=jnp.full((W, k), -jnp.inf, dtype=jnp.float32),
            ids=jnp.zeros((W, k), dtype=jnp.uint32),
        )

    def fold_windows(self, slot_ids, mask, vals, ids, lo=None, active: int = 8) -> "TopK":
        """Per-window top-k fold of a batch: each slot's row joined with the
        batch's masked lanes of that slot's window.

        Fast path (``lo`` given, from WSpec.max_active_windows): a partition-
        ordered batch spans only a few windows, so fold just ``active`` window
        offsets starting at the batch's lowest window id.  Per offset,
        ``prefilter`` keeps the batch's k + 1 highest lanes with
        ``lax.top_k``, which orders floats by the join's own key (their
        ordered-u32 bits), masked lanes below every price; ``setjoin`` joins
        the first k with the row in a 2k sort.  That equals the join with
        every lane unless a lane left out can still reach the row's top k: a
        tie between the k-th and (k+1)-th key, or a duplicate (price, id)
        pair among the k that frees a place.  A batch with such an offset
        takes the ``exact`` join of every lane for every offset, under one
        ``lax.cond`` outside the vmap, so the common batch runs one branch.
        Prices are not NaN.  This is the jnp analogue of the Pallas
        ``topk_window`` kernel.
        Fallback: masked join vmapped over every ring slot.
        """
        W = self.vals.shape[0]
        vals = vals.astype(jnp.float32)
        ids = ids.astype(jnp.uint32)
        k = self.k

        if lo is None:
            def per_slot(w, sv, si):
                m = mask & (slot_ids == w)
                bv = jnp.where(m, vals, -jnp.inf)
                bi = jnp.where(m, ids, 0)
                return _topk_join_sorted(sv, si, bv, bi, k)

            v, i = jax.vmap(per_slot)(jnp.arange(W), self.vals, self.ids)
            return TopK(v, i)

        wid_of_slot = lo + jnp.arange(active, dtype=jnp.int32)
        slots = wid_of_slot % W
        rows_v, rows_i = self.vals[slots], self.ids[slots]

        def lanes(w, slot):
            return mask & (slot_ids == slot) & (w >= 0)

        def prefilter(w, slot):
            m, v, i = lanes(w, slot), vals, ids
            if v.shape[0] <= k:  # top_k needs k + 1 lanes: pad with masked ones
                pad = k + 1 - v.shape[0]
                m, v, i = jnp.pad(m, (0, pad)), jnp.pad(v, (0, pad)), jnp.pad(i, (0, pad))
            top, at = lax.top_k(jnp.where(m, v, _BELOW_EVERY_PRICE), k + 1)
            key = float_to_ordered_u32(top)  # 0: a masked lane
            cand_v = jnp.where(key > 0, top, -jnp.inf)
            cand_i = jnp.where(key > 0, i[at], 0)
            # every live lane is a candidate, or every lane left out lies
            # strictly below k distinct candidates
            upper = jnp.arange(k)[:, None] < jnp.arange(k)[None, :]
            dup = jnp.any(upper & (key[:k, None] == key[None, :k])
                          & (cand_i[:k, None] == cand_i[None, :k]))
            ok = (key[k] == 0) | ((key[k - 1] > key[k]) & ~dup)
            return cand_v[:k], cand_i[:k], ok

        def exact():
            with jax.named_scope("exact"):
                def per_off(w, slot, sv, si):
                    m = lanes(w, slot)
                    return _topk_join_sorted(sv, si, jnp.where(m, vals, -jnp.inf),
                                             jnp.where(m, ids, 0), k)

                return jax.vmap(per_off)(wid_of_slot, slots, rows_v, rows_i)

        with jax.named_scope("prefilter"):
            cand_v, cand_i, ok = jax.vmap(prefilter)(wid_of_slot, slots)
            unambiguous = jnp.all(ok)
        with jax.named_scope("setjoin"):
            fast = _topk_join_sorted(rows_v, rows_i, cand_v, cand_i, k)
        v, i = lax.cond(unambiguous, lambda: fast, exact)
        with jax.named_scope("setjoin"):
            # offsets map to distinct slots (active <= W); scatter rows back
            return TopK(self.vals.at[slots].set(v), self.ids.at[slots].set(i))

    def window_value(self, slot) -> tuple[jax.Array, jax.Array]:
        return self.vals[slot], self.ids[slot]


CRDT = Any  # any of the classes above
