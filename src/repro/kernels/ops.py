"""Jitted public wrappers for the Pallas kernels.

Dispatch policy: `pl.pallas_call` lowers natively on TPU; elsewhere the
wrappers fall back to the jnp reference (bit-identical semantics), keeping
the 512-device CPU dry-run pure XLA.  Tests exercise the kernels with
``interpret=True`` against the refs across shape/dtype sweeps.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels import ref as _ref
from repro.kernels.crdt_merge import crdt_merge_pallas, gated_delta_merge_pallas
from repro.kernels.segment_reduce import segment_reduce_pallas
from repro.kernels.topk_window import topk_window_pallas
from repro.kernels.window_agg import window_agg_pallas

# Keyed cardinality above which the dense one-hot MXU kernel loses to the
# sorted segment-reduce kernel: the dense path does O(B·C) work per tile and
# needs a [W, C] VMEM accumulator, while the sparse path's work is
# C-independent (DESIGN.md §5).  Below the threshold the dense kernel keeps
# its MXU contraction AND its bit-identical small-C behaviour.
SPARSE_KEY_THRESHOLD = 1024


def _on_tpu() -> bool:
    return jax.default_backend() == "tpu"


@partial(jax.jit, static_argnames=("n_seg", "op", "use_pallas", "interpret"))
def segment_reduce(
    vals, segs, mask, n_seg: int, op: str = "sum",
    use_pallas: bool | None = None, interpret: bool = False,
):
    """Per-segment sum/count/max/min of the masked lanes -> f32[n_seg].

    Pallas on TPU (sorted one-pass reduce, kernels/segment_reduce.py), jnp
    segment ops elsewhere; untouched segments read the op's neutral element.
    """
    use = _on_tpu() if use_pallas is None else use_pallas
    if use:
        return segment_reduce_pallas(vals, segs, mask, n_seg, op=op, interpret=interpret)
    return _ref.segment_reduce_ref(vals, segs, mask, n_seg, op=op)


@partial(jax.jit, static_argnames=("W", "op", "C", "use_pallas", "interpret"))
def window_agg(
    vals, slots, mask, W: int, op: str = "sum", keys=None, C: int = 1,
    init=None, use_pallas: bool | None = None, interpret: bool = False,
):
    use = _on_tpu() if use_pallas is None else use_pallas
    if use:
        if keys is not None and C >= SPARSE_KEY_THRESHOLD:
            # high-cardinality keyed fold: flatten (slot, key) into segment
            # ids and ride the sorted segment-reduce kernel — the dense
            # [bt, C] one-hot would do O(B·C) work and outgrow VMEM
            if W * C >= 2**31:
                raise ValueError(
                    f"W*C = {W * C} overflows i32 segment ids; shard the key "
                    "range first (docs/protocol.md §6)"
                )
            seg = slots * jnp.int32(C) + keys
            out = segment_reduce_pallas(
                vals, seg, mask, W * C, op=op, interpret=interpret
            ).reshape(W, C)
        else:
            out = window_agg_pallas(
                vals, slots, mask, W, op=op, keys=keys, C=C, interpret=interpret
            )
        if init is not None:
            if op in ("sum", "count"):
                out = out + init
            elif op == "max":
                out = jnp.maximum(out, init)
            else:
                out = jnp.minimum(out, init)
        return out
    return _ref.window_agg_ref(vals, slots, mask, W, op=op, keys=keys, C=C, init=init)


@partial(jax.jit, static_argnames=("op", "use_pallas", "interpret"))
def crdt_merge(stack, op: str = "max", use_pallas: bool | None = None, interpret: bool = False):
    """Join [R, ...] replica stack along axis 0 (flattens trailing dims)."""
    use = _on_tpu() if use_pallas is None else use_pallas
    if not use:
        return _ref.crdt_merge_ref(stack, op=op)
    R = stack.shape[0]
    trailing = stack.shape[1:]
    flat = stack.reshape(R, -1)
    F = flat.shape[1]
    tile = 1024
    pad = (-F) % tile
    if pad:
        fill = {"max": -jnp.inf, "min": jnp.inf, "or": 0}[op]
        if not jnp.issubdtype(flat.dtype, jnp.floating):
            fill = 0
        flat = jnp.pad(flat, ((0, 0), (0, pad)), constant_values=fill)
    out = crdt_merge_pallas(flat, op=op, tile_f=tile, interpret=interpret)
    return out[:F].reshape(trailing)


@partial(jax.jit, static_argnames=("op", "use_pallas", "interpret"))
def gated_delta_merge(
    wid_stack, leaf_stack, op: str = "max", use_pallas: bool | None = None,
    interpret: bool = False,
):
    """Slot-aware join of [R]-stacked delta replicas (delta-state sync).

    ``wid_stack`` i32[R, W] carries each replica's ring tenant wids (-1 for
    clean slots); ``leaf_stack`` [R, W, ...] the matching window leaf.  Per
    slot only newest-tenant replicas contribute; all-clean tiles are copied,
    not reduced (the Pallas kernel's skip path).
    """
    use = _on_tpu() if use_pallas is None else use_pallas
    if not use:
        return _ref.gated_delta_merge_ref(wid_stack, leaf_stack, op=op)
    R, W = wid_stack.shape
    trailing = leaf_stack.shape[2:]
    flat = leaf_stack.reshape(R, W, -1)
    F = flat.shape[2]
    tile_w = 8 if W % 8 == 0 else W  # (8, 128) rule: a multiple of 8, or all
    tile_f = 128
    pad_f = (-F) % tile_f
    if pad_f:
        # pad lanes join to garbage that is sliced away; 0 keeps math finite
        flat = jnp.pad(flat, ((0, 0), (0, 0), (0, pad_f)))
    out = gated_delta_merge_pallas(
        wid_stack, flat, op=op, tile_w=tile_w, tile_f=tile_f, interpret=interpret
    )
    return out[:, :F].reshape(W, *trailing)


@partial(jax.jit, static_argnames=("use_pallas", "interpret"))
def topk_window(
    state_vals, state_ids, vals, ids, slots, mask,
    use_pallas: bool | None = None, interpret: bool = False,
):
    use = _on_tpu() if use_pallas is None else use_pallas
    if use:
        return topk_window_pallas(
            state_vals, state_ids, vals, ids, slots, mask, interpret=interpret
        )
    return _ref.topk_window_ref(state_vals, state_ids, vals, ids, slots, mask)
