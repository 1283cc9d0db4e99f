"""Pallas TPU kernel: windowed event aggregation (the WCRDT fold hot path).

TPU adaptation of the paper's per-event insert loop (DESIGN.md §5): the
scatter becomes a **one-hot contraction** so the MXU does the segment
reduction —

    sum/count :  out[w(,c)] += Σ_b onehot_w[b,w] · v[b] (· onehot_c[b,c])
                 → a [bt,W]ᵀ×[bt,C] matmul per event tile (MXU), or a
                   masked-broadcast reduce for the unkeyed case (VPU),
    max/min   :  masked broadcast + reduce over the event tile (VPU).

Grid: one program per event tile of ``bt`` events; the [W(,C)] window state
stays resident in VMEM across the whole grid (accumulator revisiting), so
HBM traffic is events-in + state once.

Tiling: events arrive as ``[bt, 1]`` column tiles (events on sublanes, bt a
multiple of 8), so every per-event operand broadcasts along lanes against the
``[bt, W]`` / ``[bt, C]`` one-hots without a reshape inside the kernel — the
TPU compiler refuses a 1-D ``[bt]`` block cast to ``[bt, 1]``.  The mask
travels as i32 (no i1 memrefs); fp32 accumulation.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEUTRAL = {"sum": 0.0, "count": 0.0, "max": -jnp.inf, "min": jnp.inf}


def _tile_values(vals_ref, op: str):
    v = vals_ref[...].astype(jnp.float32)  # [bt, 1]
    return jnp.ones_like(v) if op == "count" else v


def _kernel_unkeyed(vals_ref, slots_ref, mask_ref, out_ref, *, op: str, W: int):
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, NEUTRAL[op])

    v = _tile_values(vals_ref, op)
    bt = v.shape[0]
    onehot = (slots_ref[...] == jax.lax.broadcasted_iota(jnp.int32, (bt, W), 1)) & (
        mask_ref[...] != 0
    )  # [bt, W]
    tile = jnp.where(onehot, v, NEUTRAL[op])
    if op in ("sum", "count"):
        out_ref[...] += jnp.sum(tile, axis=0, keepdims=True)
    elif op == "max":
        out_ref[...] = jnp.maximum(out_ref[...], jnp.max(tile, axis=0, keepdims=True))
    else:
        out_ref[...] = jnp.minimum(out_ref[...], jnp.min(tile, axis=0, keepdims=True))


def _kernel_keyed(vals_ref, slots_ref, keys_ref, mask_ref, out_ref, *, op: str, W: int, C: int):
    """Keyed sum via MXU: out[W, C] += onehot_wᵀ @ (v ⊙ onehot_c)."""
    i = pl.program_id(0)

    @pl.when(i == 0)
    def _init():
        out_ref[...] = jnp.full_like(out_ref, NEUTRAL[op])

    v = _tile_values(vals_ref, op)
    bt = v.shape[0]
    slots, live = slots_ref[...], mask_ref[...] != 0  # [bt, 1]
    oh_c = keys_ref[...] == jax.lax.broadcasted_iota(jnp.int32, (bt, C), 1)
    if op in ("sum", "count"):
        oh_w = (slots == jax.lax.broadcasted_iota(jnp.int32, (bt, W), 1)) & live
        rhs = jnp.where(oh_c, v, 0.0)  # [bt, C]
        out_ref[...] += jax.lax.dot_general(
            oh_w.astype(jnp.float32),
            rhs,
            (((0,), (0,)), ((), ())),
            # f32 passes on the MXU: the default rounds v to bf16
            precision=jax.lax.Precision.HIGHEST,
            preferred_element_type=jnp.float32,
        )
    else:
        # max/min: VPU masked reduce, strip-mined one W row at a time — the
        # live intermediate is [bt, C], never the [bt, W, C] broadcast that
        # would OOM at moderate C (peak pinned by tests/test_segment_reduce.py)
        for w in range(W):
            strip = jnp.where((slots == w) & live & oh_c, v, NEUTRAL[op])
            row = out_ref[pl.ds(w, 1), :]
            if op == "max":
                out_ref[pl.ds(w, 1), :] = jnp.maximum(row, jnp.max(strip, axis=0, keepdims=True))
            else:
                out_ref[pl.ds(w, 1), :] = jnp.minimum(row, jnp.min(strip, axis=0, keepdims=True))


def window_agg_pallas(
    vals: jax.Array,
    slots: jax.Array,
    mask: jax.Array,
    W: int,
    op: str = "sum",
    keys: jax.Array | None = None,
    C: int = 1,
    block_b: int = 256,
    interpret: bool = False,
) -> jax.Array:
    """Returns [W] (unkeyed) or [W, C] (keyed) fp32 aggregates.

    Accepts any event-lane count ``B`` — in particular the ``B*K`` expanded
    multi-emit stream of an overlapping window assigner (DESIGN.md §8),
    which is rarely a block multiple.  Lanes are padded up to ``block_b``
    with ``mask=False`` (inert under every op's neutral element), so the
    fold is agnostic to whether lanes came from distinct events or one
    event multi-emitted into several windows.
    """
    B = vals.shape[0]
    pad = (-B) % block_b
    if pad:
        vals = jnp.pad(vals, (0, pad))
        slots = jnp.pad(slots, (0, pad))  # slot 0; dead under mask=False
        mask = jnp.pad(mask, (0, pad))  # False
        if keys is not None:
            keys = jnp.pad(keys, (0, pad))
        B += pad
    grid = (B // block_b,)
    col = lambda x: x.reshape(B, 1)
    ev_spec = pl.BlockSpec((block_b, 1), lambda i: (i, 0))
    ev = [col(vals), col(slots.astype(jnp.int32))]
    if keys is not None:
        ev.append(col(keys.astype(jnp.int32)))
    ev.append(col(mask.astype(jnp.int32)))
    if keys is None:
        fn = functools.partial(_kernel_unkeyed, op=op, W=W)
        out_shape = (1, W)
    else:
        fn = functools.partial(_kernel_keyed, op=op, W=W, C=C)
        out_shape = (W, C)
    out = pl.pallas_call(
        fn,
        grid=grid,
        in_specs=[ev_spec] * len(ev),
        out_specs=pl.BlockSpec(out_shape, lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct(out_shape, jnp.float32),
        interpret=interpret,
    )(*ev)
    return out[0] if keys is None else out
