"""Kernel-layer microbenchmarks on the TPU: the Pallas fold / merge / top-k
kernels at B=4096 events, W=64 ring slots.

Each kernel runs natively (``use_pallas=True``), is checked against its
``kernels/ref.py`` reference on the same inputs, then timed with
``block_until_ready``.  Every row names the device.  Off the TPU the section
refuses to run: the ops would dispatch to the jnp references, and their CPU
times are not kernel times.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit
from repro.kernels import ref
from repro.kernels.ops import (
    crdt_merge,
    gated_delta_merge,
    segment_reduce,
    topk_window,
    window_agg,
)


def _time(fn, reps=20):
    out = fn()
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn()
    jax.block_until_ready(out)
    return out, (time.perf_counter() - t0) / reps * 1e6  # us


def _check(name, got, want, exact: bool):
    for g, w in zip(jax.tree.leaves(got), jax.tree.leaves(want)):
        g, w = np.asarray(g), np.asarray(w)
        ok = np.array_equal(g, w) if exact else np.allclose(g, w, rtol=1e-5, atol=1e-5)
        if not ok:
            raise AssertionError(f"{name}: Pallas kernel differs from its reference")


def main(quick: bool = False):
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        raise RuntimeError(
            f"kernel benchmarks need a TPU (JAX platform is {dev.platform!r})"
        )
    label = f"device={dev.platform}:{dev.device_kind}"
    rng = np.random.default_rng(0)
    B, W, C, k = 4096, 64, 8, 8
    vals = jnp.array(rng.random(B, dtype=np.float32))
    slots = jnp.array(rng.integers(0, W, B).astype(np.int32))
    keys = jnp.array(rng.integers(0, C, B).astype(np.int32))
    mask = jnp.array(rng.random(B) > 0.1)
    ev_s = lambda us: f"{label};ev_per_s={B / us * 1e6}"

    for op in ("sum", "max"):
        got, us = _time(lambda: window_agg(vals, slots, mask, W, op=op, use_pallas=True))
        _check(f"window_agg_{op}", got, ref.window_agg_ref(vals, slots, mask, W, op=op), op != "sum")
        emit(f"kernels/window_agg_{op}_B{B}_W{W}", us, ev_s(us))
    got, us = _time(lambda: window_agg(vals, slots, mask, W, op="sum", keys=keys, C=C,
                                       use_pallas=True))
    _check("window_agg_keyed", got,
           ref.window_agg_ref(vals, slots, mask, W, op="sum", keys=keys, C=C), False)
    emit(f"kernels/window_agg_keyed_B{B}_W{W}_C{C}", us, ev_s(us))

    n_seg = W * 2048
    segs = jnp.array(rng.integers(0, n_seg, B).astype(np.int32))
    got, us = _time(lambda: segment_reduce(vals, segs, mask, n_seg, op="max", use_pallas=True))
    _check("segment_reduce_max", got, ref.segment_reduce_ref(vals, segs, mask, n_seg, op="max"), True)
    emit(f"kernels/segment_reduce_max_B{B}_S{n_seg}", us, ev_s(us))

    stack = jnp.array(rng.random((16, 1 << 16), dtype=np.float32))
    got, us = _time(lambda: crdt_merge(stack, op="max", use_pallas=True))
    _check("crdt_merge", got, ref.crdt_merge_ref(stack, op="max"), True)
    emit("kernels/crdt_merge_R16_F65536", us, f"{label};GBps={stack.nbytes / us * 1e6 / 1e9}")

    R, F = 4, 256
    wid = jnp.array(rng.integers(-1, 5, size=(R, W)).astype(np.int32))
    leaf = jnp.where((wid < 0)[..., None], 0.0,
                     jnp.array(rng.standard_normal((R, W, F)).astype(np.float32)))
    got, us = _time(lambda: gated_delta_merge(wid, leaf, op="max", use_pallas=True))
    _check("gated_delta_merge", got, ref.gated_delta_merge_ref(wid, leaf, op="max"), True)
    emit(f"kernels/gated_delta_merge_R{R}_W{W}_F{F}", us,
         f"{label};GBps={leaf.nbytes / us * 1e6 / 1e9}")

    sv = jnp.full((W, k), -jnp.inf, jnp.float32)
    si = jnp.zeros((W, k), jnp.uint32)
    ids = jnp.array(rng.integers(0, 1000, B).astype(np.uint32))
    got, us = _time(lambda: topk_window(sv, si, vals, ids, slots, mask, use_pallas=True))
    _check("topk_window", got, ref.topk_window_ref(sv, si, vals, ids, slots, mask), True)
    emit(f"kernels/topk_window_B{B}_W{W}_k{k}", us, ev_s(us))


if __name__ == "__main__":
    main()
