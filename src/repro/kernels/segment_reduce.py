"""Pallas TPU kernel: sorted segment reduce (the sparse keyed fold path).

The dense keyed ``window_agg`` kernel contracts a ``[bt, C]`` one-hot per
event tile — O(B·C) work and a ``[W, C]`` VMEM-resident accumulator — which
stops winning (and then stops fitting) as the key cardinality C grows past a
few thousand.  This kernel is the million-key replacement (DESIGN.md §5):

  1. the wrapper maps masked lanes to a sentinel segment and sorts the
     ``(segment, value)`` pairs by segment id (``lax.sort_key_val`` — one
     O(B log B) pass, done in XLA where the TPU sort is already tuned),
  2. a prefix-sum/searchsorted pass turns the sorted stream into per-output-
     tile ``(start, count)`` event ranges, shipped as scalar-prefetch args,
  3. the kernel grid runs one program per *segment tile* of ``seg_tile``
     outputs; each program walks only its own event range in fixed ``bt``
     chunks (dynamic ``pl.ds`` loads, aligned down to a ``bt`` boundary,
     from the VMEM-resident ``[L, 1]`` sorted column) and reduces each chunk
     against a ``[bt, seg_tile]`` relative one-hot.

Work is O(events · seg_tile / bt) + two partial chunks per non-empty tile —
independent of total C — and VMEM holds one ``[seg_tile]`` accumulator
instead of the whole ``[W, C]`` state, so the output can be arbitrarily
large (it streams through HBM tile by tile).  Empty tiles never enter the
chunk loop and just write the neutral element.

``kernels/ops.py`` dispatches the keyed ``window_agg`` here above
``SPARSE_KEY_THRESHOLD`` keys with ``segment = slot * C + key``; the sharded
keyed dataplane (docs/protocol.md §6) keeps per-device C small enough that
its ``[W, C/n_dev]`` range stays VMEM-resident anyway.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEUTRAL = {"sum": 0.0, "count": 0.0, "max": -jnp.inf, "min": jnp.inf}


def _kernel(
    start_ref, count_ref, vals_ref, segs_ref, out_ref, *,
    op: str, seg_tile: int, bt: int,
):
    j = pl.program_id(0)
    base = start_ref[j]
    end = base + count_ref[j]
    # chunks start on a bt boundary (the TPU compiler only accepts dynamic
    # slices it can prove aligned); lanes of the first chunk before ``base``
    # and of the last past ``end`` belong to other tiles and are masked
    lo = (base // bt) * bt
    tile_lo = j * seg_tile
    neutral = jnp.float32(NEUTRAL[op])

    def chunk(i, acc):
        off = pl.multiple_of(lo + i * bt, bt)
        v = vals_ref[pl.ds(off, bt), :]  # [bt, 1]
        if op == "count":
            v = jnp.ones_like(v)
        pos = off + jax.lax.broadcasted_iota(jnp.int32, (bt, 1), 0)
        live = (pos >= base) & (pos < end)
        rel = segs_ref[pl.ds(off, bt), :] - tile_lo
        oh = (rel == jax.lax.broadcasted_iota(jnp.int32, (bt, seg_tile), 1)) & live
        contrib = jnp.where(oh, v, neutral)
        if op in ("sum", "count"):
            return acc + jnp.sum(contrib, axis=0, keepdims=True)
        if op == "max":
            return jnp.maximum(acc, jnp.max(contrib, axis=0, keepdims=True))
        return jnp.minimum(acc, jnp.min(contrib, axis=0, keepdims=True))

    acc0 = jnp.full((1, seg_tile), neutral, dtype=jnp.float32)
    n_chunks = jnp.where(end > base, pl.cdiv(end - lo, bt), 0)
    out_ref[...] = jax.lax.fori_loop(0, n_chunks, chunk, acc0)


def segment_reduce_pallas(
    vals: jax.Array,  # [B] any numeric dtype
    segs: jax.Array,  # i32[B] in [0, n_seg)
    mask: jax.Array,  # bool[B]
    n_seg: int,
    op: str = "sum",
    seg_tile: int = 512,
    bt: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Returns f32[n_seg]: per-segment sum/count/max/min of the masked lanes.

    Segments no lane touches read the op's neutral element (0 for sum/count,
    ∓inf for max/min) — same convention as ``ref.segment_reduce_ref``.
    Input order is irrelevant: the wrapper sorts by segment id before the
    kernel runs, so callers may hand over raw scatter streams.
    """
    B = vals.shape[0]
    n_tiles = pl.cdiv(n_seg, seg_tile)
    n_seg_pad = n_tiles * seg_tile
    sentinel = jnp.int32(n_seg_pad)  # beyond every tile: masked lanes sort last
    seg_m = jnp.where(mask, segs.astype(jnp.int32), sentinel)
    sseg, sval = jax.lax.sort_key_val(seg_m, vals.astype(jnp.float32))
    # pad to whole chunks so the last aligned load never runs off the stream;
    # the stream is a [L, 1] column (events on sublanes), VMEM-resident
    L = pl.cdiv(B, bt) * bt
    edges = jnp.searchsorted(
        sseg, jnp.arange(n_tiles + 1, dtype=jnp.int32) * seg_tile, side="left"
    ).astype(jnp.int32)
    starts, counts = edges[:-1], edges[1:] - edges[:-1]
    col = lambda x, fill: jnp.pad(x, (0, L - B), constant_values=fill).reshape(L, 1)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(n_tiles,),
        in_specs=[
            pl.BlockSpec((L, 1), lambda j, *_: (0, 0)),
            pl.BlockSpec((L, 1), lambda j, *_: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, seg_tile), lambda j, *_: (0, j)),
    )
    out = pl.pallas_call(
        functools.partial(_kernel, op=op, seg_tile=seg_tile, bt=bt),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((1, n_seg_pad), jnp.float32),
        interpret=interpret,
    )(starts, counts, col(sval, 0.0), col(sseg, n_seg_pad))
    return out[0, :n_seg]
