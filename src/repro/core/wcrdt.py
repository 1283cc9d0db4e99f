"""Windowed CRDTs — Algorithm 1 of the paper, vectorized for JAX.

A WCRDT wraps any CRDT from ``crdt.py`` with:

* a ring of ``W`` window slots (every CRDT leaf gains a leading ``[W]`` axis),
* ``slot_wid[W]`` recording which window id each slot currently holds,
* a ``progress[P]`` map of per-partition local watermarks (event timestamps),
* monotone error counters (late drops, incomplete evictions, ring overflows).

Semantics (paper §4.2):
  - ``insert`` folds a *batch* of timestamped events into their window slots
    (one vectorized scatter instead of the paper's per-event loop — the TPU
    adaptation of the hot path; see kernels/window_agg).  Each slot's new
    tenant is the newest window among the lanes it receives, a dense masked
    max over the ring's ``W`` slots: no scatter.
  - ``increment_watermark`` raises this partition's progress entry.
  - ``global_watermark`` = min over all progress entries.
  - ``window_value(wid)`` is readable iff the global watermark has passed the
    window's end — at that point the value is final and identical on every
    replica (*global determinism*).
  - ``merge`` is a join: slots ordered lexicographically by (wid, CRDT join),
    progress joined by elementwise max.  Commutative / associative /
    idempotent, hence convergent under any gossip or collective schedule.

Deviation from the paper (recorded in DESIGN.md §3): the paper keys progress
by *node*; we key it by *partition*.  With work stealing a node may die and
its partitions move — a node-keyed map would freeze the global watermark on
the dead node's stale entry, while the partition-keyed map travels with the
stolen partition state.  The paper's evaluation (fixed partition count) is
unaffected.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from repro.core import crdt as crdts
from repro.core.lattice import Reduce, join, join_stacked, lattice_dataclass
from repro.core.window import Hopping, Tumbling, WindowAssigner, expand_events

NO_WID = jnp.int32(-1)
ERR_LATE = 0  # events older than the partition's own watermark (paper: error)
ERR_RING = 1  # events whose window had already been evicted from the ring
ERR_EVICT_INCOMPLETE = 2  # slot reused before its window completed (W too small)
NUM_ERRS = 3


@lattice_dataclass(
    slot_wid="custom", windows="custom", progress="custom", folded="custom",
    errors="custom",
)
class WState:
    """Replica state of one Windowed CRDT.

    ``folded`` is the per-partition *batch frontier*: the number of input-log
    batches already folded for that partition, merged by max.  It makes
    ``insert`` idempotent under deterministic replay — a recovering node that
    replays batches its pre-crash gossip already delivered folds nothing
    (Algorithm 2's "largest nxtIdx wins" applied inside the WCRDT; this
    closed a measured exactly-once violation where the boundary event with
    ts == progress[p] was re-folded into the merged slot)."""

    slot_wid: jax.Array  # i32[W], window id held by each ring slot (-1 empty)
    windows: Any  # CRDT pytree, leaves [W, ...]
    progress: jax.Array  # i32[P], per-partition local watermark (timestamps)
    folded: jax.Array  # i32[P], per-partition batch frontier
    errors: jax.Array  # i32[NUM_ERRS], monotone counters

    def merge(self, other: "WState") -> "WState":
        return _merge_wstate(self, other)


def _merge_wstate(a: WState, b: WState) -> WState:
    """Slot-aware lattice join.

    Per slot: larger wid wins outright (the smaller is a stale ring tenant);
    equal wids join the underlying CRDT.  This is the product of the
    lexicographic-by-wid order with the CRDT lattice — still a semilattice.
    """
    a_newer = a.slot_wid > b.slot_wid
    same = a.slot_wid == b.slot_wid
    joined = join(a.windows, b.windows)

    def pick(la, lb, lj):
        # broadcast slot masks over trailing dims
        extra = (1,) * (la.ndim - 1)
        newer = a_newer.reshape((-1, *extra))
        eq = same.reshape((-1, *extra))
        return jnp.where(eq, lj, jnp.where(newer, la, lb))

    windows = jax.tree.map(pick, a.windows, b.windows, joined)
    return WState(
        slot_wid=jnp.maximum(a.slot_wid, b.slot_wid),
        windows=windows,
        progress=jnp.maximum(a.progress, b.progress),
        folded=jnp.maximum(a.folded, b.folded),
        errors=jnp.maximum(a.errors, b.errors),
    )


@dataclasses.dataclass(frozen=True)
class WSpec:
    """Static spec of a Windowed CRDT (hashable; safe as a jit static arg)."""

    window_len: int  # window length in timestamp units
    num_slots: int  # ring size W (must exceed max watermark lag, in windows)
    num_partitions: int  # P — progress map size
    zero_windows: Callable[[], Any]  # () -> CRDT pytree with [W] leading axis
    fold: Callable[..., Any]  # (windows, slot_ids, mask, **inputs) -> windows
    read: Callable[[Any, jax.Array], Any]  # (windows, slot) -> value
    # Fast-fold hint: partition-ordered batches span few windows; when set,
    # insert() computes the batch's lowest window id and the fold only visits
    # this many window offsets (events beyond are dropped + counted ERR_RING).
    max_active_windows: int | None = None
    # Window shape (DESIGN.md §8): Tumbling reproduces the paper's
    # ``ts // window_len`` bit-for-bit; Hopping(window_len, hop) maps each
    # event into window_len // hop overlapping windows.  None -> Tumbling.
    assigner: WindowAssigner | None = None
    # Ring-slot reset: (windows, advancing bool[W]) -> windows with the
    # advancing slots zeroed.  None -> a ``jnp.where`` against
    # ``zero_windows()`` over each leaf's leading [W] axis; a spec whose
    # leaves have no such axis (the flat keyed ring) brings its own.
    reset: Callable[[Any, jax.Array], Any] | None = None

    def __post_init__(self):
        if self.assigner is None:
            object.__setattr__(self, "assigner", Tumbling(self.window_len))
        elif self.assigner.window_len != self.window_len:
            raise ValueError(
                f"assigner window_len {self.assigner.window_len} != spec "
                f"window_len {self.window_len}"
            )
        if self.assigner.windows_per_event > self.num_slots:
            # one event's K concurrent windows can never all be resident:
            # every fold would evict incomplete windows and reads would
            # return ok=False with no hint why — reject up front
            raise ValueError(
                f"assigner spans {self.assigner.windows_per_event} concurrent "
                f"windows per event but the ring has only {self.num_slots} "
                "slots; raise num_slots or the hop"
            )

    def window_of(self, ts: jax.Array) -> jax.Array:
        """Newest window containing ``ts`` (the only one, under Tumbling)."""
        return self.assigner.window_of(ts)

    def zero(self) -> WState:
        return WState(
            slot_wid=jnp.full((self.num_slots,), NO_WID, dtype=jnp.int32),
            windows=self.zero_windows(),
            progress=jnp.zeros((self.num_partitions,), dtype=jnp.int32),
            folded=jnp.zeros((self.num_partitions,), dtype=jnp.int32),
            errors=jnp.zeros((NUM_ERRS,), dtype=jnp.int32),
        )


# ---------------------------------------------------------------------------
# Operations (pure; all jit / vmap friendly; spec is static)
# ---------------------------------------------------------------------------


def _expand_payload(x, B: int, K: int):
    """Repeat an event-aligned ``[B, ...]`` payload into ``[B*K, ...]`` lanes;
    scalars (e.g. ``actor=partition``) pass through untouched."""
    if getattr(x, "ndim", 0) >= 1 and x.shape[0] == B:
        return jnp.repeat(jnp.asarray(x), K, axis=0)
    return x


def insert(
    spec: WSpec, state: WState, partition, ts: jax.Array, mask: jax.Array,
    batch_idx=None, **inputs
) -> WState:
    """Fold a batch of events (timestamps ``ts``, payload ``inputs``) into the
    window ring for ``partition``.

    Batched Algorithm-1 INSERT: events below the partition's own watermark are
    dropped and counted (the paper raises an error); ring-slot reuse resets the
    slot's CRDT to zero first; events for already-evicted windows are dropped
    and counted.  A slot's tenant advances to the newest window among its
    unmasked lanes, taken as a max over a ``[W, lanes]`` compare that the
    compiler fuses into the reduce (a scatter-max serialises: every lane of a
    batch lands in one of the few slots its windows occupy).  It assumes no
    batch order or span: lanes beyond the ring's reach lose to their slot's
    newest window and count as ERR_RING.

    Under an overlapping assigner (DESIGN.md §8) each event multi-emits into
    its ``windows_per_event`` windows: the batch expands into ``[B*K]`` lanes
    (window ids + repeated payloads) and the same vectorized scatter folds
    them all — ERR_LATE stays per *event*, ERR_RING counts dropped
    (event, window) assignments.  Tumbling keeps the single-lane graph.

    ``batch_idx`` (optional): this batch's index in the partition's input log.
    When given, the fold is a no-op unless ``batch_idx >= folded[partition]``
    — replay-idempotence for exactly-once recovery (see WState.folded).
    """
    W = spec.num_slots
    ts = ts.astype(jnp.int32)
    if batch_idx is not None:
        fresh = jnp.asarray(batch_idx, jnp.int32) >= state.folded[partition]
        mask = mask & fresh

    # Algorithm 1 line 5: ts < progress[self] is an error -> count as late.
    # Per-event (before multi-window expansion) so each event counts once.
    late = mask & (ts < state.progress[partition])
    mask = mask & ~late
    n_late = jnp.sum(late).astype(jnp.int32)

    K = spec.assigner.windows_per_event
    if K == 1:
        wid = spec.assigner.window_of(ts)
    else:
        B = ts.shape[0]
        wid, mask = expand_events(spec.assigner, ts, mask)
        inputs = {k: _expand_payload(v, B, K) for k, v in inputs.items()}
    slot = wid % W

    # Sub-scopes of the dataplane's fold layer (docs/observability.md §7):
    # slot tenancy, ring-slot reset, and the scatter of the events.
    with jax.named_scope("tenancy"):
        # Newest incoming window id per slot (see the docstring); masked
        # lanes and empty slots give NO_WID.
        hit = mask & (slot == jnp.arange(W, dtype=slot.dtype)[:, None])
        seg_max = jnp.max(jnp.where(hit, wid, NO_WID), axis=1, initial=NO_WID)
        new_slot_wid = jnp.maximum(state.slot_wid, seg_max)

        # Reset slots whose tenant window advances.
        advancing = new_slot_wid > state.slot_wid
        # eviction-safety diagnostic: old tenant not yet complete?
        gwm_wid = spec.assigner.first_dirty_wid(global_watermark(spec, state))
        evict_bad = advancing & (state.slot_wid >= 0) & (state.slot_wid >= gwm_wid)

    def reset(leaf, zleaf):
        extra = (1,) * (leaf.ndim - 1)
        adv = advancing.reshape((-1, *extra))
        return jnp.where(adv, zleaf, leaf)

    with jax.named_scope("reset"):
        if spec.reset is None:
            windows = jax.tree.map(reset, state.windows, spec.zero_windows())
        else:
            windows = spec.reset(state.windows, advancing)

    with jax.named_scope("scatter"):
        # Valid events: belong to the (new) tenant window of their slot.
        stale = mask & (wid < new_slot_wid[slot])
        valid = mask & ~stale
        n_ring = jnp.sum(stale).astype(jnp.int32)

        if spec.max_active_windows is not None:
            span = spec.max_active_windows
            lo = jnp.min(jnp.where(valid, wid, jnp.int32(2**31 - 1)))
            over = valid & (wid >= lo + span)
            valid = valid & ~over
            n_ring = n_ring + jnp.sum(over).astype(jnp.int32)
            windows = spec.fold(windows, slot, valid, lo=lo, **inputs)
        else:
            windows = spec.fold(windows, slot, valid, **inputs)

    errors = state.errors
    errors = errors.at[ERR_LATE].add(n_late)
    errors = errors.at[ERR_RING].add(n_ring)
    errors = errors.at[ERR_EVICT_INCOMPLETE].add(jnp.sum(evict_bad).astype(jnp.int32))

    folded = state.folded
    if batch_idx is not None:
        folded = folded.at[partition].max(jnp.asarray(batch_idx, jnp.int32) + 1)
    return WState(
        slot_wid=new_slot_wid, windows=windows, progress=state.progress,
        folded=folded, errors=errors,
    )


def increment_watermark(spec: WSpec, state: WState, partition, ts) -> WState:
    ts = jnp.asarray(ts, jnp.int32)
    new = state.progress.at[partition].max(ts)
    return dataclasses.replace(state, progress=new)


def global_watermark(spec: WSpec, state: WState) -> jax.Array:
    return jnp.min(state.progress)


def window_complete(spec: WSpec, state: WState, wid) -> jax.Array:
    """A window is complete once the global watermark passes its end (the
    assigner-provided extent — ``(wid+1)*window_len`` under Tumbling)."""
    wid = jnp.asarray(wid, jnp.int32)
    return global_watermark(spec, state) >= spec.assigner.end_ts(wid)


def window_value(spec: WSpec, state: WState, wid):
    """Unsafe-mode read: (value, ok).  ok=False means not complete (None in
    the paper) or already evicted from the ring.

    A complete window whose ring slot holds an OLDER tenant (or nothing) is
    globally EMPTY — inserts happen-before watermark bumps within one replica
    and merges carry both atomically, so completeness implies every
    partition's events for this window are visible.  Empty windows therefore
    read as the CRDT's zero aggregate, ok=True.
    """
    wid = jnp.asarray(wid, jnp.int32)
    slot = wid % spec.num_slots
    tenant = state.slot_wid[slot]
    resident = tenant == wid
    evicted = tenant > wid
    ok = window_complete(spec, state, wid) & ~evicted
    val = spec.read(state.windows, slot)
    zero_val = spec.read(spec.zero_windows(), slot)
    val = jax.tree.map(
        lambda v, z: jnp.where(resident, v, z), val, zero_val
    )
    return val, ok


def merge(spec: WSpec, a: WState, b: WState) -> WState:
    return _merge_wstate(a, b)


def axis_join(spec: WSpec, state: WState, axis_name: str) -> WState:
    """Background sync as a single collective across ``axis_name``.

    Generic path: all_gather + log-depth vectorized join (handles replicas at
    different ring positions).  The production metrics path uses
    ``axis_join_aligned`` which assumes lockstep slot_wid and rides pure
    pmax/pmin all-reduces (cheaper: no gather buffer).
    """
    gathered = jax.tree.map(lambda x: lax.all_gather(x, axis_name), state)
    return join_stacked(gathered, merge_fn=_merge_wstate)


def axis_join_aligned(spec: WSpec, state: WState, axis_name: str) -> WState:
    """Collective join assuming all replicas hold identical slot_wid (lockstep
    windows — true for the step-windowed training-metrics lattice).  Each leaf
    joins with its elementwise reduce: one fused all-reduce, no gather."""
    from repro.core.lattice import axis_reduce_leaf, field_kinds

    kinds = field_kinds(state.windows)
    joined = {}
    for name, kind in kinds.items():
        leaf = getattr(state.windows, name)
        if isinstance(kind, Reduce):
            joined[name] = jax.tree.map(
                lambda x, k=kind: axis_reduce_leaf(k, x, axis_name), leaf
            )
        else:
            # custom-merge sub-lattice (e.g. TopK): gather + fold
            g = jax.tree.map(lambda x: lax.all_gather(x, axis_name), leaf)
            n = jax.tree.leaves(g)[0].shape[0]
            parts = [jax.tree.map(lambda x: x[i], g) for i in range(n)]
            rebuilt = [
                dataclasses.replace(state.windows, **{name: p}) for p in parts
            ]
            from repro.core.lattice import join_many

            joined[name] = getattr(join_many(rebuilt), name)
    windows = dataclasses.replace(state.windows, **joined)
    return WState(
        slot_wid=lax.pmax(state.slot_wid, axis_name),
        windows=windows,
        progress=lax.pmax(state.progress, axis_name),
        folded=lax.pmax(state.folded, axis_name),
        errors=lax.pmax(state.errors, axis_name),
    )


# ---------------------------------------------------------------------------
# Delta-based synchronization (paper §7 future work, implemented)
# ---------------------------------------------------------------------------


def delta_since(
    spec: WSpec, state: WState, baseline_folded: jax.Array,
    baseline_progress: jax.Array,
) -> WState:
    """Extract an incremental sync delta: only ring slots that may have
    changed since the receiver's known ``(folded, progress)`` baseline.

    The delta IS a valid (partial) WState — untouched slots carry
    slot_wid = -1 and zero contents, which are the identities of the
    slot-aware join — so ``merge(remote, delta)`` applies exactly the dirty
    windows.  Determinism/convergence are unchanged (the delta is a point
    below ``state`` in the lattice); only sync bandwidth drops: for a
    window_len ≫ batch_span stream, one or two dirty slots per period instead
    of the whole ring (measured in tests/test_delta_sync.py).

    Dirty rule: events folded after the baseline have ts >= that partition's
    BASELINE watermark (older ones are late-dropped), so a slot is dirty iff
    its tenant window contains/exceeds the oldest baseline watermark among
    partitions whose batch frontier advanced — i.e. its tenant wid reaches
    ``assigner.first_dirty_wid(frontier)``, the smallest window any post-
    baseline event can land in (docs/protocol.md §2; under Tumbling this is
    the original ``frontier // window_len``).  Conservative and exact for
    in-order streams, overlapping windows included.
    """
    advanced = state.folded > baseline_folded
    any_adv = jnp.any(advanced)
    frontier_ts = jnp.min(
        jnp.where(advanced, baseline_progress, jnp.int32(2**31 - 1))
    )
    dirty_wid = spec.assigner.first_dirty_wid(jnp.maximum(frontier_ts, 0))
    dirty = (state.slot_wid >= dirty_wid) & any_adv

    zeros = spec.zero_windows()

    def pick(leaf, z):
        extra = (1,) * (leaf.ndim - 1)
        d = dirty.reshape((-1, *extra))
        return jnp.where(d, leaf, z)

    return WState(
        slot_wid=jnp.where(dirty, state.slot_wid, NO_WID),
        windows=jax.tree.map(pick, state.windows, zeros),
        progress=state.progress,  # tiny; always shipped
        folded=state.folded,
        errors=state.errors,
    )


def delta_nbytes(delta: WState) -> jax.Array:
    """Wire-size estimate of a delta: bytes of dirty slots + metadata.
    (The simulator charges this instead of the full-state size.)"""
    dirty = (delta.slot_wid >= 0).astype(jnp.float32)
    per_slot = sum(
        float(np.prod(l.shape[1:])) * l.dtype.itemsize
        for l in jax.tree.leaves(delta.windows)
    )
    meta = delta.progress.nbytes + delta.folded.nbytes + delta.errors.nbytes
    return jnp.sum(dirty) * per_slot + meta


def state_nbytes(state: WState) -> float:
    """Full-replica wire size (every leaf shipped) — the delta's comparand."""
    return float(sum(l.nbytes for l in jax.tree.leaves(state)))


def baseline_of(state: WState) -> tuple[jax.Array, jax.Array]:
    """The (folded, progress) marker summarizing what ``state`` covers — the
    receiver-side baseline that ``delta_since`` diffs against."""
    return (state.folded, state.progress)


def zero_baseline(spec: WSpec) -> tuple[np.ndarray, np.ndarray]:
    """Baseline of a peer known to hold nothing: the next delta is the full
    resident state."""
    z = np.zeros((spec.num_partitions,), dtype=np.int32)
    return (z, z.copy())


def merge_delta_stack(
    spec: WSpec, stacked: WState, use_pallas: bool | None = None,
    interpret: bool = False,
) -> WState:
    """Join an ``[R]``-stacked pile of deltas (from all_gather) slot-aware.

    Elementwise window lattices ride the gated delta-merge kernel: per ring
    slot, replicas whose tenant window trails the newest (including clean
    slots, ``slot_wid == -1``) are skipped instead of joined.  Custom window
    lattices (TopK) fall back to the log-depth vectorized pairwise join.
    """
    from repro.core.lattice import field_kinds

    kinds = field_kinds(stacked.windows)
    if not all(isinstance(k, Reduce) for k in kinds.values()):
        return join_stacked(stacked, merge_fn=_merge_wstate)

    from repro.kernels.ops import gated_delta_merge

    wid_stack = stacked.slot_wid  # [R, W]
    merged = {
        name: jax.tree.map(
            lambda x, k=kind: gated_delta_merge(
                wid_stack, x, op=k.value, use_pallas=use_pallas,
                interpret=interpret,
            ),
            getattr(stacked.windows, name),
        )
        for name, kind in kinds.items()
    }
    return WState(
        slot_wid=jnp.max(wid_stack, axis=0),
        windows=type(stacked.windows)(**merged),
        progress=jnp.max(stacked.progress, axis=0),
        folded=jnp.max(stacked.folded, axis=0),
        errors=jnp.max(stacked.errors, axis=0),
    )


def delta_axis_join(
    spec: WSpec, state: WState, baseline_folded: jax.Array,
    baseline_progress: jax.Array, axis_name: str,
    use_pallas: bool | None = None, interpret: bool = False,
) -> tuple[WState, jax.Array]:
    """Dirty-slot-gated background sync across ``axis_name``.

    Each replica extracts ``delta_since`` the shared post-last-sync baseline
    (after a sync round every replica holds the identical merged state, so
    its delta is exactly its own new contributions), the deltas are
    all-gathered, and the stack is joined by the gated delta-merge — clean
    slots are skipped rather than joined.  Returns ``(merged_state,
    shipped_nbytes)`` where the second is this replica's modeled wire cost
    (what a real transport would put on the network instead of the full
    ring; measured by benchmarks/throughput.py).
    """
    # sub-scopes of the dataplane's sync layer (docs/observability.md §7)
    with jax.named_scope("extract"):
        delta = delta_since(spec, state, baseline_folded, baseline_progress)
        shipped = delta_nbytes(delta)
    with jax.named_scope("exchange"):
        gathered = jax.tree.map(lambda x: lax.all_gather(x, axis_name), delta)
    with jax.named_scope("merge"):
        merged = merge_delta_stack(
            spec, gathered, use_pallas=use_pallas, interpret=interpret
        )
        return _merge_wstate(state, merged), shipped



# ---------------------------------------------------------------------------
# Spec constructors for the CRDT catalog
# ---------------------------------------------------------------------------


def wgcounter(
    window_len: int, num_slots: int, num_partitions: int, key_shape=(), dtype=jnp.float32,
    assigner: WindowAssigner | None = None,
) -> WSpec:
    return WSpec(
        window_len=window_len,
        assigner=assigner,
        num_slots=num_slots,
        num_partitions=num_partitions,
        zero_windows=partial(
            crdts.GCounter.zero_windows, num_slots, num_partitions, key_shape, dtype
        ),
        fold=lambda w, s, m, actor, amounts, keys=None: w.fold_windows(
            s, m, actor, amounts, keys
        ),
        read=lambda w, slot: w.window_value(slot),
    )


def wpncounter(
    window_len: int, num_slots: int, num_partitions: int, key_shape=(), dtype=jnp.float32,
    assigner: WindowAssigner | None = None,
) -> WSpec:
    return WSpec(
        window_len=window_len,
        assigner=assigner,
        num_slots=num_slots,
        num_partitions=num_partitions,
        zero_windows=partial(
            crdts.PNCounter.zero_windows, num_slots, num_partitions, key_shape, dtype
        ),
        fold=lambda w, s, m, actor, amounts, keys=None: w.fold_windows(
            s, m, actor, amounts, keys
        ),
        read=lambda w, slot: w.window_value(slot),
    )


def wmaxreg(
    window_len: int, num_slots: int, num_partitions: int, key_shape=(), dtype=jnp.float32,
    assigner: WindowAssigner | None = None,
) -> WSpec:
    return WSpec(
        window_len=window_len,
        assigner=assigner,
        num_slots=num_slots,
        num_partitions=num_partitions,
        zero_windows=partial(crdts.MaxReg.zero_windows, num_slots, key_shape, dtype),
        fold=lambda w, s, m, vals, keys=None: w.fold_windows(s, m, vals, keys),
        read=lambda w, slot: w.window_value(slot),
    )


def wminreg(
    window_len: int, num_slots: int, num_partitions: int, key_shape=(), dtype=jnp.float32,
    assigner: WindowAssigner | None = None,
) -> WSpec:
    return WSpec(
        window_len=window_len,
        assigner=assigner,
        num_slots=num_slots,
        num_partitions=num_partitions,
        zero_windows=partial(crdts.MinReg.zero_windows, num_slots, key_shape, dtype),
        fold=lambda w, s, m, vals, keys=None: w.fold_windows(s, m, vals, keys),
        read=lambda w, slot: w.window_value(slot),
    )


def wtopk(
    window_len: int, num_slots: int, num_partitions: int, k: int,
    max_active_windows: int | None = 8,
    assigner: WindowAssigner | None = None,
) -> WSpec:
    aw = max_active_windows
    if aw is not None and aw > num_slots:
        # TopK's fast fold scatters one row per active window offset; more
        # offsets than ring slots would alias (wid % W) and silently drop
        # folds — reject instead (use num_slots, or None for the slow path)
        raise ValueError(
            f"max_active_windows={aw} exceeds num_slots={num_slots}"
        )
    return WSpec(
        window_len=window_len,
        assigner=assigner,
        num_slots=num_slots,
        num_partitions=num_partitions,
        zero_windows=partial(crdts.TopK.zero_windows, num_slots, k),
        fold=(
            (lambda w, s, m, vals, ids, lo: w.fold_windows(s, m, vals, ids, lo=lo, active=aw))
            if aw is not None
            else (lambda w, s, m, vals, ids: w.fold_windows(s, m, vals, ids))
        ),
        read=lambda w, slot: w.window_value(slot),
        max_active_windows=aw,
    )


# ---------------------------------------------------------------------------
# Hash-sharded keyed state (docs/protocol.md §6)
# ---------------------------------------------------------------------------


def _shard_multiplier(num_keys: int) -> int:
    """Largest ``a`` with ``a * num_keys < 2**31`` and ``gcd(a, num_keys) == 1``
    — so ``p(k) = (k * a) % num_keys`` is an i32-safe bijection on [0, C)."""
    import math

    a = max((2**31 - 1) // num_keys, 1)
    while math.gcd(a, num_keys) != 1:
        a -= 1
    return a


@dataclasses.dataclass(frozen=True)
class KeyShards:
    """Hash routing of a keyed domain [0, C) over S owner shards
    (docs/protocol.md §6).

    The "hash" is a multiplicative permutation ``p(k) = (k * mult) % C``
    (bijective because ``gcd(mult, C) == 1``, i32-safe because
    ``mult * C < 2**31`` — jax runs with x64 disabled); ``owner = p % S``
    spreads consecutive (zipf-hot) keys across shards and ``local = p // S``
    is a dense O(1) index into the owner's ``[W, ceil(C/S)]`` key range — no
    per-key hash table.  The inverse (local -> global key, needed by the
    cross-shard top-k read) is the precomputed :meth:`key_table`, shipped as
    a device-sharded input rather than recomputed on device (the modular
    inverse would overflow i32).

    Hashable and static — safe to close over in a jitted dataplane.
    """

    num_keys: int  # C — global keyed domain size
    num_shards: int  # S — owner shards (= mesh data-axis size)
    mult: int = 0  # permutation multiplier; 0 = derive in __post_init__

    def __post_init__(self):
        if self.mult == 0:
            object.__setattr__(self, "mult", _shard_multiplier(self.num_keys))

    @property
    def width(self) -> int:
        """Local key-range size ceil(C/S) — every shard's state is padded to
        this so the sharded WState has one static shape."""
        return -(-self.num_keys // self.num_shards)

    def perm(self, keys: jax.Array) -> jax.Array:
        return (keys.astype(jnp.int32) * jnp.int32(self.mult)) % jnp.int32(self.num_keys)

    def shard_of(self, keys: jax.Array) -> jax.Array:
        """Owner shard id per key (the hash-routing rule)."""
        return self.perm(keys) % jnp.int32(self.num_shards)

    def local_of(self, keys: jax.Array) -> jax.Array:
        """Dense index into the owner's local key range."""
        return self.perm(keys) // jnp.int32(self.num_shards)

    def num_local(self, shard: int) -> int:
        """Real (unpadded) key count of ``shard``'s range."""
        return (self.num_keys - shard + self.num_shards - 1) // self.num_shards

    def key_table(self) -> np.ndarray:
        """u32[S, width] inverse map ``(shard, local) -> global key``; padded
        entries (locals past the shard's real range) carry the sentinel C."""
        C, S = self.num_keys, self.num_shards
        p = (np.arange(C, dtype=np.int64) * self.mult) % C
        inv = np.empty(C, dtype=np.uint32)
        inv[p] = np.arange(C, dtype=np.uint32)
        table = np.full((S, self.width), C, dtype=np.uint32)
        for s in range(S):
            n = self.num_local(s)
            table[s, :n] = inv[s + S * np.arange(n, dtype=np.int64)]
        return table


def _fold_flat(width, width_p, windows, slot_ids, mask, amounts, keys):
    """Scatter-add ``amounts`` at ``slot * width_p + key``.  Masked lanes and
    keys outside ``[0, width)`` are sent one past the ring and dropped, so
    the padding never takes a count."""
    n = windows.shape[0]
    keys = keys.astype(jnp.int32)
    keep = mask & (keys >= 0) & (keys < width)
    idx = jnp.where(keep, slot_ids * jnp.int32(width_p) + keys, jnp.int32(n))
    return windows.at[idx].add(amounts.astype(windows.dtype), mode="drop")


def _read_flat(width, width_p, windows, slot):
    """Slot ``slot``'s ``[width]`` counts.  The slice takes the whole
    tile-aligned row and drops the padding after it: a slice of ``width``
    alone ends inside a tile, which the chip's compiler relayouts row by row."""
    return lax.dynamic_slice(windows, (slot * width_p,), (width_p,))[:width]


def _reset_flat(width_p, windows, advancing):
    """Zero the advancing slots' rows in place, one row write per advancing
    slot; the other rows are neither read nor written."""
    zeros = jnp.zeros((width_p,), windows.dtype)

    def row(s, w):
        return lax.cond(
            advancing[s],
            lambda w: lax.dynamic_update_slice(w, zeros, (s * width_p,)),
            lambda w: w,
            w,
        )

    return lax.fori_loop(0, advancing.shape[0], row, windows)


def wgcounter_sharded(
    window_len: int, num_slots: int, num_partitions: int, shards: KeyShards,
    dtype=jnp.float32, assigner: WindowAssigner | None = None,
) -> WSpec:
    """Keyed grow-only counter over ONE shard's key range
    (docs/protocol.md §6).

    The windows are one flat ``[num_slots * width_p]`` leaf: slot ``s``'s
    counts for this shard's ``width = ceil(C/S)`` locals are the row
    ``[s * width_p, s * width_p + width)``, where ``width_p`` is ``width``
    rounded up to a multiple of 1024.  Rows start on whole TPU tiles, so the
    fold scatters into the ring where it lies (the chip's compiler copies a
    ``[W, 1, width]`` ring to a flat buffer and back around every scatter),
    and the spec's ``reset`` zeroes only the advancing slots' rows.
    The padding past ``width`` in each row stays zero.  There is no actor
    axis: folds are owner-exclusive, every event for a key routed to its
    single owner (replay idempotence still comes from the ``folded``
    frontier, which keeps all ``num_partitions`` source entries, as does
    ``progress``).

    ``insert``, ``increment_watermark``, ``window_value`` and
    :func:`shard_topk_read` serve this state; ``window_value`` returns the
    slot's ``[width]`` counts.  The slot-wise join machinery (``merge``,
    ``delta_since`` and the syncs built on them) expects a CRDT pytree with
    a leading ``[W]`` axis on every leaf and raises on this state; none of
    it is needed, since owners never reconcile slots and the keyed
    dataplane syncs only ``progress``.  Fold inputs: ``amounts`` per lane
    plus ``keys`` = LOCAL indices (route with :meth:`KeyShards.local_of`
    first).
    """
    width = shards.width
    width_p = -(-width // 1024) * 1024  # rows start on whole 1-D tiles
    if num_slots * width_p >= 2**31:
        raise ValueError(
            f"num_slots * width_p = {num_slots * width_p} overflows i32 ring "
            "indices; shard the key range over more devices"
        )
    return WSpec(
        window_len=window_len,
        assigner=assigner,
        num_slots=num_slots,
        num_partitions=num_partitions,
        zero_windows=partial(jnp.zeros, (num_slots * width_p,), dtype),
        fold=partial(_fold_flat, width, width_p),
        read=partial(_read_flat, width, width_p),
        reset=partial(_reset_flat, width_p),
    )


def shard_topk_read(
    spec: WSpec, state: WState, wid, key_table_row: jax.Array, num_keys: int,
    axis_name: str, k: int = 1,
):
    """Cross-shard top-k window read over a sharded keyed counter — no full
    gather (docs/protocol.md §6).

    Each shard reduces its own ``[width]`` key range to k ``(count, key)``
    candidates (padded locals masked via the ``key_table_row`` sentinel),
    the ``[S, k]`` candidate sets ride one small ``all_gather``, and the
    global top-k is selected by (count desc, key asc).  ``k=1`` reproduces
    ``jnp.argmax`` over the unsharded count vector exactly: ties break to
    the lowest GLOBAL key id (not local index — the routing permutation is
    not monotone).  Returns ``((counts f32[k], keys u32[k]), ok)``; ``ok``
    requires the window complete and unevicted on every shard.
    """
    counts, ok = window_value(spec, state, wid)
    live = key_table_row < jnp.uint32(num_keys)
    sentinel_key = jnp.uint32(num_keys)
    if k == 1:
        masked = jnp.where(live, counts, -jnp.inf)
        cmax = jnp.max(masked)
        ckey = jnp.min(jnp.where(masked == cmax, key_table_row, sentinel_key))
        cand_c = lax.all_gather(cmax, axis_name)  # [S]
        cand_k = lax.all_gather(ckey, axis_name)
        gmax = jnp.max(cand_c)
        gkey = jnp.min(jnp.where(cand_c == gmax, cand_k, sentinel_key))
        top = (gmax[None], gkey[None])
    else:
        masked = jnp.where(live, counts, -jnp.inf)
        cv, ci = lax.top_k(masked, k)
        ck = jnp.where(cv > -jnp.inf, key_table_row[ci], sentinel_key)
        cand_v = lax.all_gather(cv, axis_name).reshape(-1)  # [S*k]
        cand_k = lax.all_gather(ck, axis_name).reshape(-1)
        # (count desc, key asc): sort ascending on the negated count first
        sv, sk = lax.sort((-cand_v, cand_k), dimension=0, num_keys=2)
        top = (-sv[:k], sk[:k])
    ok = jnp.min(lax.all_gather(ok.astype(jnp.int32), axis_name)) > 0
    return top, ok


def wgset(
    window_len: int, num_slots: int, num_partitions: int, domain: int,
    assigner: WindowAssigner | None = None,
) -> WSpec:
    return WSpec(
        window_len=window_len,
        assigner=assigner,
        num_slots=num_slots,
        num_partitions=num_partitions,
        zero_windows=partial(crdts.GSet.zero_windows, num_slots, domain),
        fold=lambda w, s, m, elems: w.fold_windows(s, m, elems),
        read=lambda w, slot: w.window_value(slot),
    )
