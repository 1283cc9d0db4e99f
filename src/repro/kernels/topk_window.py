"""Pallas TPU kernel: per-window bounded top-k merge (Q7 "highest bids").

Grid: one program per tile of ``tw`` windows (windows on sublanes).  Each
program masks the event row to each of its windows — a ``[tw, B]`` candidate
block — and folds it into the windows' running top-k by k rounds of
max-extraction (k <= 16, so k sequential VPU reductions beat a full sort;
lexicographic (val, id) order keeps the lattice deterministic).  The running
state and the batch candidates stay two separate blocks (no unaligned
concatenation), and round ``j`` writes output column ``j`` with a select on
a lane iota (no scatter inside the kernel).  State blocks are ``[tw, k]``
with ``tw`` a multiple of 8 (or all of W), meeting the TPU's (8, 128) block
rule; events stream once per window tile.  Mosaic has no unsigned
reductions, so ids travel as i32 keys ``id ^ 2**31`` (order-preserving):
u32 id 0, the padding id, is the key ``-2**31``.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

NEG = float("-inf")  # python literal: pallas kernels must not capture arrays
ID0 = -(2**31)  # i32 key of u32 id 0


def _kernel(sv_ref, si_ref, vals_ref, ids_ref, slots_ref, mask_ref, ov_ref, oi_ref,
            *, k: int, tw: int):
    B = vals_ref.shape[1]
    wid = pl.program_id(0) * tw + jax.lax.broadcasted_iota(jnp.int32, (tw, B), 0)
    m = (mask_ref[...] != 0) & (slots_ref[...] == wid)  # [tw, B]
    bv = jnp.where(m, vals_ref[...].astype(jnp.float32), NEG)
    bi = jnp.where(m, ids_ref[...], ID0)
    sv, si = sv_ref[...], si_ref[...]  # [tw, k]
    col = jax.lax.broadcasted_iota(jnp.int32, (tw, k), 1)

    def top(x):
        return jnp.max(x, axis=1, keepdims=True)  # [tw, 1]

    out_v = jnp.zeros((tw, k), jnp.float32)
    out_i = jnp.full((tw, k), ID0, jnp.int32)
    for j in range(k):  # k rounds of lexicographic argmax-extract
        # order by (val, id): strictly larger val wins; ties -> larger id
        best_v = jnp.maximum(top(sv), top(bv))
        s_best, b_best = sv == best_v, bv == best_v
        best_i = jnp.maximum(
            top(jnp.where(s_best, si, ID0)),
            top(jnp.where(b_best, bi, ID0)),
        )
        out_v = jnp.where(col == j, best_v, out_v)
        out_i = jnp.where(col == j, best_i, out_i)
        # remove exactly the taken entries (dedups identical (v, id) pairs —
        # set semantics of the TopK lattice)
        s_taken, b_taken = s_best & (si == best_i), b_best & (bi == best_i)
        sv, si = jnp.where(s_taken, NEG, sv), jnp.where(s_taken, ID0, si)
        bv, bi = jnp.where(b_taken, NEG, bv), jnp.where(b_taken, ID0, bi)
    ov_ref[...] = out_v
    oi_ref[...] = out_i


def topk_window_pallas(
    state_vals: jax.Array,  # f32[W, k]
    state_ids: jax.Array,  # u32[W, k]
    vals: jax.Array,  # f32[B]
    ids: jax.Array,  # u32[B]
    slots: jax.Array,  # i32[B]
    mask: jax.Array,  # bool[B]
    interpret: bool = False,
):
    W, k = state_vals.shape
    B = vals.shape[0]
    tw = 8 if W % 8 == 0 else W
    row = lambda x, dt: x.astype(dt).reshape(1, B)
    key = lambda u: jax.lax.bitcast_convert_type(
        u.astype(jnp.uint32) ^ jnp.uint32(2**31), jnp.int32
    )
    ev = pl.BlockSpec((1, B), lambda w: (0, 0))
    st = pl.BlockSpec((tw, k), lambda w: (w, 0))
    ov, oi = pl.pallas_call(
        functools.partial(_kernel, k=k, tw=tw),
        grid=(W // tw,),
        in_specs=[st, st, ev, ev, ev, ev],
        out_specs=[st, st],
        out_shape=[
            jax.ShapeDtypeStruct((W, k), jnp.float32),
            jax.ShapeDtypeStruct((W, k), jnp.int32),
        ],
        interpret=interpret,
    )(state_vals, key(state_ids), row(vals, jnp.float32), row(key(ids), jnp.int32),
      row(slots, jnp.int32), row(mask, jnp.int32))
    return ov, jax.lax.bitcast_convert_type(oi, jnp.uint32) ^ jnp.uint32(2**31)
