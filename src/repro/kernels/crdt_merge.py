"""Pallas TPU kernel: tiled lattice join of replica stacks.

The gossip/merge hot path (DESIGN.md §5): join R replica states leaf-by-leaf
with an elementwise MAX / MIN / OR reduction over the replica axis.  The
feature dimension is tiled [tile_f] along VMEM lanes; each grid program loads
an [R, tile_f] block and reduces it in registers — HBM traffic is exactly
read-once + write-once.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.ref import gated_neutral


def _kernel(stack_ref, out_ref, *, op: str):
    x = stack_ref[...]  # [R, tile_f]
    if op == "max":
        out_ref[...] = jnp.max(x, axis=0)
    elif op == "min":
        out_ref[...] = jnp.min(x, axis=0)
    elif op == "or":
        r = x[0]
        for i in range(1, x.shape[0]):
            r = jnp.bitwise_or(r, x[i])
        out_ref[...] = r
    else:
        raise ValueError(op)


def crdt_merge_pallas(
    stack: jax.Array,  # [R, F] (leaf flattened by ops.py)
    op: str = "max",
    tile_f: int = 1024,
    interpret: bool = False,
) -> jax.Array:
    R, F = stack.shape
    assert F % tile_f == 0, (F, tile_f)
    grid = (F // tile_f,)
    return pl.pallas_call(
        functools.partial(_kernel, op=op),
        grid=grid,
        in_specs=[pl.BlockSpec((R, tile_f), lambda i: (0, i))],
        out_specs=pl.BlockSpec((tile_f,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((F,), stack.dtype),
        interpret=interpret,
    )(stack)


# ---------------------------------------------------------------------------
# Gated delta merge: slot-aware join of delta-state replicas (DESIGN.md §6).
#
# Delta sync ships rings whose untouched slots carry slot_wid = -1 and zero
# contents.  Joining R such deltas per slot means: replicas whose tenant
# window trails the per-slot max (stale tenants and clean slots alike) must
# NOT contribute — their content belongs to an older window.  The kernel
# loads a [R, tile_w, tile_f] block plus its [R, tile_w, 1] wid column,
# computes the per-slot winner mask on the VPU, and reduces gated lanes in
# registers.  Slots sit on sublanes and features on lanes, so the wid column
# broadcasts along lanes (a [R, tile_w] wid row would put slots on lanes,
# which the TPU's (8, 128) block rule refuses for tile_w < 128).  Blocks whose
# every slot is clean skip the masked reduce entirely and copy replica 0 (all
# deltas hold the identical deterministic zero-state there).
# ---------------------------------------------------------------------------


def _gated_kernel(wid_ref, stack_ref, out_ref, *, op: str):
    wid = wid_ref[...]  # i32[R, tile_w, 1]
    top = jnp.max(wid, axis=0)  # i32[tile_w, 1]
    any_dirty = jnp.max(top) >= 0

    @pl.when(any_dirty)
    def _dirty():
        x = stack_ref[...]  # [R, tile_w, tile_f]
        gate = wid == top[None]  # [R, tile_w, 1]
        xg = jnp.where(gate, x, gated_neutral(op, x.dtype))
        if op == "max":
            out_ref[...] = jnp.max(xg, axis=0)
        elif op == "min":
            out_ref[...] = jnp.min(xg, axis=0)
        elif op == "or":
            r = xg[0]
            for i in range(1, xg.shape[0]):
                r = jnp.bitwise_or(r, xg[i])
            out_ref[...] = r
        else:
            raise ValueError(op)

    @pl.when(jnp.logical_not(any_dirty))
    def _clean():
        # every replica's block is clean zero-state: copy, skip the reduce
        out_ref[...] = stack_ref[0]


def gated_delta_merge_pallas(
    wid_stack: jax.Array,  # i32[R, W]
    stack: jax.Array,  # [R, W, F] (trailing dims flattened by ops.py)
    op: str = "max",
    tile_w: int = 8,
    tile_f: int = 128,
    interpret: bool = False,
) -> jax.Array:
    """``[W, F]`` gated join of ``R`` delta replicas.

    ``tile_w`` must divide ``W`` and be a multiple of 8 or equal ``W``;
    ``tile_f`` must divide ``F`` and be a multiple of 128 or equal ``F``
    (the TPU block rule).  Legal inside ``shard_map``: the output carries
    the inputs' device-variance (vma), as ``check_vma`` requires.
    """
    R, W, F = stack.shape
    assert wid_stack.shape == (R, W), (wid_stack.shape, stack.shape)
    assert W % tile_w == 0 and F % tile_f == 0, (W, F, tile_w, tile_f)
    vma = jax.typeof(wid_stack).vma | jax.typeof(stack).vma
    grid = (W // tile_w, F // tile_f)
    return pl.pallas_call(
        functools.partial(_gated_kernel, op=op),
        grid=grid,
        in_specs=[
            pl.BlockSpec((R, tile_w, 1), lambda i, j: (0, i, 0)),
            pl.BlockSpec((R, tile_w, tile_f), lambda i, j: (0, i, j)),
        ],
        out_specs=pl.BlockSpec((tile_w, tile_f), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((W, F), stack.dtype, vma=vma),
        interpret=interpret,
    )(wid_stack[..., None], stack)
