"""The benchmark's own Nexmark event generator and window arithmetic.

This is the yardstick's copy of the program's generator: the same draws, in
the same order, as ``repro.streaming.generator._gen_batch``, so that a cell's
traffic does not move when the program's generator does.  It imports nothing
of the program.  Events are a struct of arrays with the fields, dtypes and
order of the program's ``EventBatch``.

The generator runs on the device, in one jitted call per pool chunk; its seed
is a traced argument, so one compiled program serves every seed.  The numpy
helpers below are the window rules that the plain references share.
"""
from __future__ import annotations

from functools import partial

import numpy as np

KIND_PERSON, KIND_AUCTION, KIND_BID = 0, 1, 2
NUM_CATEGORIES = 5  # Nexmark's default category count
FIELDS = ("ts", "kind", "auction", "price", "category", "bidder", "valid")


def batch_span_ms(traffic: dict) -> float:
    """Event time one batch of one partition spans."""
    return 1000.0 * traffic["events_per_batch"] / traffic["rate_per_partition"]


def chunk_seed(seed: int, chunk: int) -> int:
    """Generator seed of pool chunk ``chunk`` under run seed ``seed``: any
    whole number, of any size, maps to a non-negative int32."""
    state = np.random.SeedSequence([int(seed), int(chunk)]).generate_state(1)
    return int(state[0] & 0x7FFFFFFF)


def _gen_batch(jnp, jax, traffic, num_auctions, key0, partition, batch_idx):
    """One partition's batch; the program's ``_gen_batch`` draw for draw."""
    B = traffic["events_per_batch"]
    key = jax.random.fold_in(jax.random.fold_in(key0, partition), batch_idx)
    k_price, k_auct, k_bidder, k_jit = jax.random.split(key, 4)

    span = batch_span_ms(traffic)
    base = jnp.float32(0.0) + batch_idx.astype(jnp.float32) * span
    offs = jnp.arange(B, dtype=jnp.float32) * (span / B)
    jitter = jax.random.uniform(k_jit, (B,), minval=0.0, maxval=span / B)
    ts = jnp.sort(base + offs + jitter).astype(jnp.int32)

    lane = jnp.arange(B) % 50  # 1 person : 3 auctions : 46 bids
    kind = jnp.where(lane == 0, KIND_PERSON,
                     jnp.where(lane < 4, KIND_AUCTION, KIND_BID))

    s = float(traffic["key_skew"])
    if s == 0.0:
        auction = jax.random.randint(k_auct, (B,), 0, num_auctions).astype(jnp.uint32)
    else:
        # inverse CDF of the continuous power law x^-s on [1, N + 1)
        N = float(num_auctions)
        u = jax.random.uniform(k_auct, (B,))
        if s == 1.0:
            x = jnp.exp(u * jnp.log(N + 1.0))
        else:
            x = (u * ((N + 1.0) ** (1.0 - s) - 1.0) + 1.0) ** (1.0 / (1.0 - s))
        auction = jnp.clip(jnp.floor(x) - 1.0, 0.0, N - 1.0).astype(jnp.uint32)
    category = (auction % NUM_CATEGORIES).astype(jnp.int32)
    price = jnp.exp(jax.random.normal(k_price, (B,)) * 1.0 + 4.0).astype(jnp.float32)
    bidder = jax.random.randint(k_bidder, (B,), 0, 10_000).astype(jnp.uint32)
    valid = jnp.ones((B,), jnp.bool_)  # every partition carries full load
    return (ts, kind.astype(jnp.int32), auction, price, category, bidder, valid)


def make_chunk_fn(traffic: dict, num_auctions: int, num_partitions: int):
    """Jitted ``seed (int32) -> tuple of [num_partitions, batches, B]``
    arrays in ``FIELDS`` order: one call to the program for one chunk."""
    import jax
    import jax.numpy as jnp

    nb = traffic["batches_per_call"]

    @jax.jit
    def chunk(seed):
        key0 = jax.random.PRNGKey(seed)
        one = partial(_gen_batch, jnp, jax, traffic, num_auctions, key0)
        parts, batches = jnp.arange(num_partitions), jnp.arange(nb)
        return jax.vmap(lambda p: jax.vmap(lambda b: one(p, b))(batches))(parts)

    return chunk


def closed_windows(ts: np.ndarray, starts: np.ndarray, window_len: int) -> np.ndarray:
    """Which windows ``[start, start + window_len)`` the final global
    watermark has passed: the watermark is the least, over partitions, of
    each partition's largest event time (``ts`` is ``[P, ...]``)."""
    wm = ts.reshape(ts.shape[0], -1).max(axis=1).min()
    return starts + window_len <= wm


def read_windows(config: dict, traffic: dict) -> tuple[int, int]:
    """``(first, n)``: the window ids a call reads, every id ``w >= 0`` whose
    window ``[w * hop, w * hop + window)`` starts before the call's last
    event.  The closed ones are compared; the rest must read as not closed."""
    hop, length = config["hop_ms"], config["window_ms"]
    horizon = traffic["batches_per_call"] * batch_span_ms(traffic)
    n = int(np.ceil(horizon / hop))
    per_event = -(-length // hop)
    if n > config["ring_slots"] - (per_event - 1):
        raise ValueError(f"{n} windows per call do not fit a ring of "
                         f"{config['ring_slots']} slots")
    return 0, n
