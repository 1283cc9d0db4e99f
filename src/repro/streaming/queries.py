"""Nexmark global-aggregation queries over Windowed CRDTs (paper §5.1).

Each query is a :class:`Query`: per-partition replica state split into

* ``shared`` — a tuple of WCRDT replicas (synchronized by lattice joins in the
  background, never by shuffles), and
* ``local``  — partition-local windowed state (the paper's ``WLocal``; realized
  as a WCRDT with a single progress entry, i.e. ``P=1``).

``fold`` consumes one input batch (insert + increment_watermark), ``merge``
joins two replicas' shared parts, ``read`` returns a completed window's value.
The queries:

* **Q0**  pass-through (stateless; per-window event counts via WLocal).
* **Q4**  average price per category — global keyed aggregation *without* a
  shuffle: per-category sum and count lattices.
* **Q7**  highest bids — global top-k lattice per window.
* **Q1-ratio** — the paper's running example (Listing 2): partition-local bid
  count over global bid count.
* **Q5**  hot items — per-auction bid counts + top-1 over an overlapping
  sliding (hopping) window, the classic Nexmark query tumbling windows
  cannot express.

Windowing is a first-class :class:`~repro.core.window.WindowAssigner`
(DESIGN.md §8): every maker takes ``hop`` (None/0/window_len = tumbling,
anything else = hopping), and every oracle masks events with
``assigner.contains(wid, ts)`` so ground truth generalizes with the query.

Every query also ships an ``oracle``: the same aggregation computed directly
over the whole log with plain jnp — the ground truth for exactly-once and
determinism tests.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core import wcrdt as W
from repro.core.lattice import float_to_ordered_u32, ordered_u32_to_float
from repro.core.wcrdt import WSpec, WState
from repro.core.window import WindowAssigner, as_assigner
from repro.streaming.events import KIND_BID, EventBatch
from repro.streaming.generator import NUM_CATEGORIES, batch_watermark


@dataclasses.dataclass(frozen=True)
class Query:
    name: str
    num_partitions: int
    window_len: int
    assigner: WindowAssigner
    shared_specs: tuple[WSpec, ...]
    local_spec: WSpec | None
    init_shared: Callable[[], tuple[WState, ...]]
    init_local: Callable[[], WState | None]
    fold: Callable[..., tuple[tuple[WState, ...], WState | None]]
    read: Callable[..., tuple[Any, jax.Array]]
    oracle: Callable[..., Any]
    out_width: int  # flattened f32 output lanes per (partition, window)

    # ---- generic helpers ----
    def merge_shared(self, a: tuple[WState, ...], b: tuple[WState, ...]):
        return tuple(
            W.merge(spec, x, y) for spec, x, y in zip(self.shared_specs, a, b)
        )

    def global_watermark(self, shared, local) -> jax.Array:
        if self.shared_specs:
            return W.global_watermark(self.shared_specs[0], shared[0])
        return W.global_watermark(self.local_spec, local)

    def window_of(self, ts):
        """Newest window containing ``ts`` (the only one, under Tumbling)."""
        return self.assigner.window_of(jnp.asarray(ts, jnp.int32))


def _mk_local_spec(kind: str, window_len: int, num_slots: int, **kw) -> WSpec:
    ctor = {"gcounter": W.wgcounter, "maxreg": W.wmaxreg}[kind]
    return ctor(window_len, num_slots, 1, **kw)


# ---------------------------------------------------------------------------
# Q0: pass-through
# ---------------------------------------------------------------------------


def make_q0(
    num_partitions: int, window_len: int = 1000, num_slots: int = 16,
    hop: int | None = None,
) -> Query:
    assigner = as_assigner(window_len, hop)
    lspec = _mk_local_spec("gcounter", window_len, num_slots, assigner=assigner)

    def init_local():
        return lspec.zero()

    def fold(shared, local, batch: EventBatch, partition, batch_idx=None):
        amounts = jnp.ones_like(batch.price)
        local = W.insert(
            lspec, local, 0, batch.ts, batch.valid, batch_idx=batch_idx,
            actor=0, amounts=amounts,
        )
        local = W.increment_watermark(lspec, local, 0, batch_watermark(batch))
        return shared, local

    def read(shared, local, wid):
        v, ok = W.window_value(lspec, local, wid)
        return jnp.reshape(v, (1,)), ok

    def oracle(log: EventBatch, wid, partition=None):
        m = log.valid & assigner.contains(wid, log.ts)
        if partition is not None:
            m = m[partition]
        return jnp.sum(m.astype(jnp.float32))

    return Query(
        name="q0",
        num_partitions=num_partitions,
        window_len=window_len,
        assigner=assigner,
        shared_specs=(),
        local_spec=lspec,
        init_shared=lambda: (),
        init_local=init_local,
        fold=fold,
        read=read,
        oracle=oracle,
        out_width=1,
    )


# ---------------------------------------------------------------------------
# Q4: average price per category (global, keyed, no shuffle)
# ---------------------------------------------------------------------------


def make_q4(
    num_partitions: int,
    window_len: int = 1000,
    num_slots: int = 16,
    num_categories: int = NUM_CATEGORIES,
    hop: int | None = None,
) -> Query:
    assigner = as_assigner(window_len, hop)
    sum_spec = W.wgcounter(window_len, num_slots, num_partitions,
                           key_shape=(num_categories,), assigner=assigner)
    cnt_spec = W.wgcounter(window_len, num_slots, num_partitions,
                           key_shape=(num_categories,), assigner=assigner)

    def init_shared():
        return (sum_spec.zero(), cnt_spec.zero())

    def fold(shared, local, batch: EventBatch, partition, batch_idx=None):
        s, c = shared
        is_bid = batch.valid & (batch.kind == KIND_BID)
        wm = batch_watermark(batch)
        s = W.insert(
            sum_spec, s, partition, batch.ts, is_bid, batch_idx=batch_idx,
            actor=partition, amounts=batch.price, keys=batch.category,
        )
        s = W.increment_watermark(sum_spec, s, partition, wm)
        c = W.insert(
            cnt_spec, c, partition, batch.ts, is_bid, batch_idx=batch_idx,
            actor=partition, amounts=jnp.ones_like(batch.price), keys=batch.category,
        )
        c = W.increment_watermark(cnt_spec, c, partition, wm)
        return (s, c), local

    def read(shared, local, wid):
        s, c = shared
        sv, ok1 = W.window_value(sum_spec, s, wid)
        cv, ok2 = W.window_value(cnt_spec, c, wid)
        avg = sv / jnp.maximum(cv, 1.0)
        return avg, ok1 & ok2

    def oracle(log: EventBatch, wid, partition=None):
        m = log.valid & (log.kind == KIND_BID) & assigner.contains(wid, log.ts)
        cat_onehot = jax.nn.one_hot(log.category, num_categories, dtype=jnp.float32)
        w = m.astype(jnp.float32)[..., None] * cat_onehot
        sums = jnp.sum(w * log.price[..., None], axis=tuple(range(w.ndim - 1)))
        cnts = jnp.sum(w, axis=tuple(range(w.ndim - 1)))
        return sums / jnp.maximum(cnts, 1.0)

    return Query(
        name="q4",
        num_partitions=num_partitions,
        window_len=window_len,
        assigner=assigner,
        shared_specs=(sum_spec, cnt_spec),
        local_spec=None,
        init_shared=init_shared,
        init_local=lambda: None,
        fold=fold,
        read=read,
        oracle=oracle,
        out_width=num_categories,
    )


# ---------------------------------------------------------------------------
# Q7: highest bids (global top-k per window)
# ---------------------------------------------------------------------------


def make_q7(
    num_partitions: int, window_len: int = 1000, num_slots: int = 16, k: int = 8,
    topk_active: int = 4, hop: int | None = None,
) -> Query:
    """``topk_active``: window offsets folded per batch.  A partition-ordered
    batch spans ceil(batch_span/window_len)+1 windows; 2 suffices for the
    default rates (batch span ~0.1-0.2 windows) and is 1.7x faster than 8
    (EXPERIMENTS.md §Perf iteration C); 4 is the safe default.  Under a
    hopping assigner each event multi-emits into window_len // hop windows,
    so the active span grows by that factor — clamped to the ring size,
    since TopK's fast fold requires distinct slots per active offset
    (offsets beyond the ring would alias and drop folds)."""
    assigner = as_assigner(window_len, hop)
    if topk_active is not None:  # None = wtopk's exact unbounded fold path
        topk_active = min(topk_active * assigner.windows_per_event, num_slots)
    topk_spec = W.wtopk(window_len, num_slots, num_partitions, k,
                        max_active_windows=topk_active, assigner=assigner)

    def init_shared():
        return (topk_spec.zero(),)

    def fold(shared, local, batch: EventBatch, partition, batch_idx=None):
        (t,) = shared
        is_bid = batch.valid & (batch.kind == KIND_BID)
        t = W.insert(
            topk_spec, t, partition, batch.ts, is_bid, batch_idx=batch_idx,
            vals=batch.price, ids=batch.auction,
        )
        t = W.increment_watermark(topk_spec, t, partition, batch_watermark(batch))
        return (t,), local

    def read(shared, local, wid):
        (t,) = shared
        (vals, ids), ok = W.window_value(topk_spec, t, wid)
        out = jnp.concatenate([vals, ids.astype(jnp.float32)])
        return out, ok

    def oracle(log: EventBatch, wid, partition=None):
        m = log.valid & (log.kind == KIND_BID) & assigner.contains(wid, log.ts)
        prices = jnp.where(m, log.price, -jnp.inf).reshape(-1)
        ids = jnp.where(m, log.auction, 0).reshape(-1).astype(jnp.uint32)
        # ordered on the price's bits, as the lattice is (a float sort puts a
        # subnormal level with 0.0); a bid repeated on one auction at one
        # price counts once
        sk, si = jax.lax.sort((float_to_ordered_u32(prices), ids), num_keys=2)
        dup = jnp.concatenate([jnp.zeros(1, bool),
                               (sk[1:] == sk[:-1]) & (si[1:] == si[:-1])])
        pad = float_to_ordered_u32(jnp.float32(-jnp.inf))
        sk, si = jax.lax.sort((jnp.where(dup, pad, sk), jnp.where(dup, 0, si)),
                              num_keys=2)
        return ordered_u32_to_float(sk[-k:][::-1]), si[-k:][::-1]

    return Query(
        name="q7",
        num_partitions=num_partitions,
        window_len=window_len,
        assigner=assigner,
        shared_specs=(topk_spec,),
        local_spec=None,
        init_shared=init_shared,
        init_local=lambda: None,
        fold=fold,
        read=read,
        oracle=oracle,
        out_width=2 * k,
    )


# ---------------------------------------------------------------------------
# Query 1 (paper Listing 2): local/global bid-count ratio
# ---------------------------------------------------------------------------


def make_q1_ratio(
    num_partitions: int, window_len: int = 1000, num_slots: int = 16,
    hop: int | None = None,
) -> Query:
    assigner = as_assigner(window_len, hop)
    gspec = W.wgcounter(window_len, num_slots, num_partitions, assigner=assigner)
    lspec = _mk_local_spec("gcounter", window_len, num_slots, assigner=assigner)

    def init_shared():
        return (gspec.zero(),)

    def init_local():
        return lspec.zero()

    def fold(shared, local, batch: EventBatch, partition, batch_idx=None):
        (g,) = shared
        is_bid = batch.valid & (batch.kind == KIND_BID)
        wm = batch_watermark(batch)
        ones = jnp.ones_like(batch.price)
        g = W.insert(gspec, g, partition, batch.ts, is_bid, batch_idx=batch_idx,
                     actor=partition, amounts=ones)
        g = W.increment_watermark(gspec, g, partition, wm)
        local = W.insert(lspec, local, 0, batch.ts, is_bid, batch_idx=batch_idx,
                         actor=0, amounts=ones)
        local = W.increment_watermark(lspec, local, 0, wm)
        return (g,), local

    def read(shared, local, wid):
        (g,) = shared
        gv, ok1 = W.window_value(gspec, g, wid)
        lv, ok2 = W.window_value(lspec, local, wid)
        ratio = lv / jnp.maximum(gv, 1.0)
        return jnp.reshape(ratio, (1,)), ok1 & ok2

    def oracle(log: EventBatch, wid, partition=None):
        m = log.valid & (log.kind == KIND_BID) & assigner.contains(wid, log.ts)
        total = jnp.sum(m.astype(jnp.float32))
        if partition is None:
            return total
        loc = jnp.sum(m[partition].astype(jnp.float32))
        return loc / jnp.maximum(total, 1.0)

    return Query(
        name="q1_ratio",
        num_partitions=num_partitions,
        window_len=window_len,
        assigner=assigner,
        shared_specs=(gspec,),
        local_spec=lspec,
        init_shared=init_shared,
        init_local=init_local,
        fold=fold,
        read=read,
        oracle=oracle,
        out_width=1,
    )

# ---------------------------------------------------------------------------
# Q5: hot items — top-1 auction by bid count over a sliding (hopping) window
# ---------------------------------------------------------------------------


def make_q5(
    num_partitions: int, window_len: int = 1000, num_slots: int = 16,
    hop: int | None = None, num_auctions: int = 64,
) -> Query:
    """Nexmark Q5: which auction received the most bids in each sliding
    window?  The query overlapping windows exist for — a tumbling window
    misses bursts straddling window edges.

    Defaults to ``hop = window_len // 2`` (each event lives in 2 windows);
    pass ``hop=window_len`` for the tumbling degenerate.  State is one
    per-auction keyed count lattice (GCounter, no shuffle); the read takes
    the argmax — output lanes are ``[count, auction_bucket]``.  Auction ids
    are bucketed ``auction % num_auctions`` to keep the keyed state dense
    (DESIGN.md §8 records the deviation); the oracle buckets identically,
    and counts are small integers, exact in f32 — so replica reads are
    byte-identical to the oracle under any merge order.
    """
    hop = window_len // 2 if hop is None else hop
    assigner = as_assigner(window_len, hop)
    cnt_spec = W.wgcounter(window_len, num_slots, num_partitions,
                           key_shape=(num_auctions,), assigner=assigner)

    def init_shared():
        return (cnt_spec.zero(),)

    def fold(shared, local, batch: EventBatch, partition, batch_idx=None):
        (c,) = shared
        is_bid = batch.valid & (batch.kind == KIND_BID)
        bucket = (batch.auction % num_auctions).astype(jnp.int32)
        c = W.insert(
            cnt_spec, c, partition, batch.ts, is_bid, batch_idx=batch_idx,
            actor=partition, amounts=jnp.ones_like(batch.price), keys=bucket,
        )
        c = W.increment_watermark(cnt_spec, c, partition, batch_watermark(batch))
        return (c,), local

    def read(shared, local, wid):
        (c,) = shared
        counts, ok = W.window_value(cnt_spec, c, wid)
        hot = jnp.argmax(counts)  # ties -> lowest bucket, same as the oracle
        out = jnp.stack([counts[hot], hot.astype(jnp.float32)])
        return out, ok

    def oracle(log: EventBatch, wid, partition=None):
        m = log.valid & (log.kind == KIND_BID) & assigner.contains(wid, log.ts)
        bucket = (log.auction % num_auctions).astype(jnp.int32)
        onehot = jax.nn.one_hot(bucket, num_auctions, dtype=jnp.float32)
        cnts = jnp.sum(
            m.astype(jnp.float32)[..., None] * onehot,
            axis=tuple(range(onehot.ndim - 1)),
        )
        hot = jnp.argmax(cnts)
        return jnp.stack([cnts[hot], hot.astype(jnp.float32)])

    return Query(
        name="q5",
        num_partitions=num_partitions,
        window_len=window_len,
        assigner=assigner,
        shared_specs=(cnt_spec,),
        local_spec=None,
        init_shared=init_shared,
        init_local=lambda: None,
        fold=fold,
        read=read,
        oracle=oracle,
        out_width=2,
    )


def q5_hot_oracle(
    log: EventBatch, wid, assigner: WindowAssigner, num_keys: int
) -> jax.Array:
    """Sparse Q5 ground truth over the FULL auction-id domain — the oracle
    for the hash-sharded keyed dataplane (docs/protocol.md §6), which routes
    real ids instead of bucketing them ``% num_auctions`` like
    :func:`make_q5`.  Segment-sum instead of a ``[B, C]`` one-hot, so it
    stays cheap at C = 1e6+.  Returns ``[count, auction_id]``; ties break to
    the lowest id (``argmax``), the same rule :func:`W.shard_topk_read`
    implements shard-side, and counts are small integers exact in f32 — so
    sharded reads are byte-identical to this oracle.
    """
    m = log.valid & (log.kind == KIND_BID) & assigner.contains(wid, log.ts)
    cnts = jax.ops.segment_sum(
        m.astype(jnp.float32).reshape(-1),
        log.auction.astype(jnp.int32).reshape(-1),
        num_segments=num_keys,
    )
    hot = jnp.argmax(cnts)
    return jnp.stack([cnts[hot], hot.astype(jnp.float32)])
