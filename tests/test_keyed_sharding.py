"""Hash-sharded keyed WCRDT state (docs/protocol.md §6): routing laws, the
shard-and-merge law against the dense keyed counter, and the sharded q5
dataplane against the sparse oracle — clean, under crash-replay, and under
partition.
"""
import os
import subprocess
import sys
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import wcrdt as W
from repro.core.window import as_assigner


@pytest.mark.parametrize("C,S", [(10, 4), (1000, 8), (1_000_000, 48), (97, 5), (1, 1)])
def test_keyshards_routing_laws(C, S):
    """The multiplicative permutation is a bijection; (shard_of, local_of)
    round-trips through key_table; range sizes partition the domain."""
    sh = W.KeyShards(C, S)
    keys = jnp.arange(C, dtype=jnp.uint32)
    p = np.asarray(sh.perm(keys))
    assert np.array_equal(np.sort(p), np.arange(C))  # bijection
    own, loc = np.asarray(sh.shard_of(keys)), np.asarray(sh.local_of(keys))
    table = sh.key_table()
    assert table.shape == (S, sh.width)
    np.testing.assert_array_equal(table[own, loc], np.arange(C, dtype=np.uint32))
    assert sum(sh.num_local(s) for s in range(S)) == C
    for s in range(S):
        n = sh.num_local(s)
        assert (table[s, :n] < C).all()
        np.testing.assert_array_equal(table[s, n:], C)  # sentinel padding


def test_shard_and_merge_law():
    """Folding a keyed stream through S sharded [W, C/S] states and scattering
    the reads back through key_table reconstructs the dense [W, C] keyed
    counter exactly — sharding changes layout, never values."""
    C, S, wl, slots = 1000, 4, 100, 8
    assigner = as_assigner(wl, wl)
    sh = W.KeyShards(C, S)
    dense = W.wgcounter(wl, slots, 1, key_shape=(C,), assigner=assigner)
    sharded = W.wgcounter_sharded(wl, slots, 1, sh, assigner=assigner)

    rng = np.random.default_rng(0)
    B, nb = 128, 6
    dstate = dense.zero()
    sstates = [sharded.zero() for _ in range(S)]
    for b in range(nb):
        ts = jnp.sort(jnp.asarray(rng.integers(b * 50, (b + 1) * 50, B), jnp.int32))
        keys = jnp.asarray(rng.zipf(1.3, B) % C, jnp.uint32)
        amounts = jnp.ones((B,), jnp.float32)
        mask = jnp.asarray(rng.random(B) < 0.9)
        dstate = W.insert(dense, dstate, 0, ts, mask, batch_idx=b, actor=0,
                          amounts=amounts, keys=keys.astype(jnp.int32))
        dstate = W.increment_watermark(dense, dstate, 0, int(ts.max()))
        own, loc = sh.shard_of(keys), sh.local_of(keys)
        for s in range(S):
            sstates[s] = W.insert(
                sharded, sstates[s], 0, ts, mask & (own == s), batch_idx=b,
                amounts=amounts, keys=loc,
            )
            sstates[s] = W.increment_watermark(sharded, sstates[s], 0, int(ts.max()))

    table = sh.key_table()
    for wid in range(3):
        dv, dok = W.window_value(dense, dstate, wid)
        recon = np.zeros(C, np.float32)
        for s in range(S):
            sv, sok = W.window_value(sharded, sstates[s], wid)
            assert bool(sok) == bool(dok)
            n = sh.num_local(s)
            recon[table[s, :n]] = np.asarray(sv)[:n]
        np.testing.assert_array_equal(recon, np.asarray(dv))


@pytest.mark.parametrize(
    "C,S,slots,nb,hop",
    [
        (1000, 4, 8, 6, 50),  # hopping, K = 2; the ring never wraps
        (1000, 4, 4, 14, 50),  # K = 2, more batches than slots: rows reset mid-run
        (5000, 3, 3, 10, 100),  # tumbling; width 1667 padded to 2048
        (2048, 1, 4, 10, 50),  # one shard, width exactly two tiles
    ],
    ids=["hop-k2", "hop-k2-ring-wraps", "tumbling-padded-wraps", "one-shard-aligned"],
)
def test_flat_ring_matches_dense_counter(C, S, slots, nb, hop):
    """The flat keyed ring against the dense keyed counter: after every batch,
    each window the ring reads as complete holds, key for key, exactly the
    dense counter's counts; every window that completes is read so at least
    once; masked lanes count nowhere; row padding stays zero."""
    wl = 100
    assigner = as_assigner(wl, hop)
    sh = W.KeyShards(C, S)
    width_p = -(-sh.width // 1024) * 1024
    n_wids = nb * 50 // hop + 2
    dense = W.wgcounter(wl, n_wids + 2, 1, key_shape=(C,), assigner=assigner)
    flat = W.wgcounter_sharded(wl, slots, 1, sh, assigner=assigner)
    insert = jax.jit(W.insert, static_argnums=0)
    value = jax.jit(W.window_value, static_argnums=0)
    table = sh.key_table()

    rng = np.random.default_rng(C + slots)
    dstate, fstates = dense.zero(), [flat.zero() for _ in range(S)]
    checked = set()
    B = 128
    for b in range(nb):
        ts = jnp.sort(jnp.asarray(rng.integers(b * 50, (b + 1) * 50, B), jnp.int32))
        keys = jnp.asarray(rng.zipf(1.3, B) % C, jnp.uint32)
        mask = rng.random(B) < 0.85
        mask[0] = False  # at least one masked-out lane per batch
        mask = jnp.asarray(mask)
        amounts = jnp.ones((B,), jnp.float32)
        wm = int(ts.max())
        dstate = insert(dense, dstate, 0, ts, mask, batch_idx=b, actor=0,
                        amounts=amounts, keys=keys.astype(jnp.int32))
        dstate = W.increment_watermark(dense, dstate, 0, wm)
        own, loc = sh.shard_of(keys), sh.local_of(keys)
        for s in range(S):
            fstates[s] = insert(flat, fstates[s], 0, ts, mask & (own == s),
                                batch_idx=b, amounts=amounts, keys=loc)
            fstates[s] = W.increment_watermark(flat, fstates[s], 0, wm)
        for wid in range(n_wids):
            dv, dok = value(dense, dstate, wid)
            reads = [value(flat, st, wid) for st in fstates]
            if not all(bool(ok) for _, ok in reads):
                continue
            assert bool(dok), wid
            recon = np.zeros(C, np.float32)
            for s, (sv, _) in enumerate(reads):
                assert sv.shape == (sh.width,)
                n = sh.num_local(s)
                recon[table[s, :n]] = np.asarray(sv)[:n]
            np.testing.assert_array_equal(recon, np.asarray(dv))
            checked.add(wid)

    closed = [w for w in range(n_wids) if bool(value(dense, dstate, w)[1])]
    assert len(closed) >= n_wids - 4 and set(closed) <= checked, (closed, checked)
    for st in fstates:
        np.testing.assert_array_equal(np.asarray(st.errors), 0)
        rows = np.asarray(st.windows).reshape(slots, width_p)
        np.testing.assert_array_equal(rows[:, sh.width:], 0)
    assert int(np.asarray(dstate.errors).sum()) == 0


def test_flat_ring_layout_and_refusals():
    """What ``wgcounter_sharded``'s docstring states: one flat
    ``[W * width_p]`` leaf with tile-aligned rows, a reset that zeroes only
    the advancing rows, a ``[width]`` read, an i32 guard, and the slot-wise
    join machinery refusing the state."""
    sh = W.KeyShards(5000, 3)  # width 1667
    spec = W.wgcounter_sharded(100, 4, 2, sh, assigner=as_assigner(100, 50))
    state = spec.zero()
    assert state.windows.shape == (4 * 2048,)

    ring = jnp.arange(4 * 2048, dtype=jnp.float32) + 1
    out = np.asarray(spec.reset(ring, jnp.array([False, True, False, True])))
    rows, before = out.reshape(4, 2048), np.asarray(ring).reshape(4, 2048)
    np.testing.assert_array_equal(rows[[1, 3]], 0)
    np.testing.assert_array_equal(rows[[0, 2]], before[[0, 2]])

    ts = jnp.array([10, 20, 60], jnp.int32)
    keys = jnp.array([0, 1666, 5], jnp.int32)
    state = W.insert(spec, state, 0, ts, jnp.ones(3, bool), batch_idx=0,
                     amounts=jnp.ones(3, jnp.float32), keys=keys)
    v, _ = W.window_value(spec, state, 0)
    assert v.shape == (1667,)
    np.testing.assert_array_equal(np.flatnonzero(np.asarray(v)), [0, 5, 1666])

    with pytest.raises((KeyError, TypeError, ValueError)):
        W.merge(spec, state, state)
    with pytest.raises((KeyError, TypeError, ValueError)):
        W.delta_since(spec, state, *W.zero_baseline(spec))
    with pytest.raises(ValueError, match="overflows i32"):
        W.wgcounter_sharded(100, 16, 1, W.KeyShards(2**27, 1))


def _run_child(script: str, sentinel: str, timeout: int = 600):
    src = Path(__file__).resolve().parents[1] / "src"
    env = dict(os.environ, PYTHONPATH=str(src))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", script],
                       capture_output=True, text=True, timeout=timeout, env=env)
    assert sentinel in r.stdout, (
        f"stdout={r.stdout[-2000:]}\nstderr={r.stderr[-2000:]}"
    )


_CHILD_COMMON = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=%(S)d"
import jax, numpy as np
import jax.numpy as jnp
from repro.core import wcrdt as W
from repro.core.window import as_assigner
from repro.launch.mesh import make_data_mesh
from repro.launch.stream import build_keyed_pipeline, default_fold_schedule
from repro.streaming.generator import NexmarkConfig, generate_log
from repro.streaming.queries import q5_hot_oracle

S, C, nb, epb, wl = %(S)d, %(C)d, %(nb)d, %(epb)d, 100
shards = W.KeyShards(C, S)
mesh = make_data_mesh(S)
nx = NexmarkConfig(num_partitions=S, num_batches=nb, events_per_batch=epb,
                   num_auctions=C, key_skew=1.1)
log = generate_log(nx)
assigner = as_assigner(wl, wl // 2)
closed = int(assigner.first_dirty_wid(nb * nx.batch_span_ms))
n_win = min(closed, 4); first = max(0, closed - n_win)
table = jnp.asarray(shards.key_table())

def run(sched_np, wm_np, sync_every=4):
    with mesh:
        pipe = build_keyed_pipeline(mesh, shards, window_len=wl, num_slots=16,
                                    sync_every=sync_every, n_windows=n_win,
                                    first_window=first)
        oks, vals, shuf, sync = pipe(log, table, jnp.asarray(sched_np),
                                     jnp.asarray(wm_np))
    return (np.asarray(oks), np.asarray(vals), np.asarray(shuf), np.asarray(sync))

base = default_fold_schedule(S, nb)
oks0, vals0, shuf0, sync0 = run(base, np.ones(nb // 4, bool))
assert oks0.sum() == S * n_win, oks0
for i, w in enumerate(range(first, first + n_win)):
    want = np.asarray(q5_hot_oracle(log, w, assigner, C))
    for d in range(S):
        np.testing.assert_array_equal(vals0[d, i], want)
"""


def test_keyed_dataplane_2dev_oracle_smoke():
    """Tier-1 gate: the sharded q5 dataplane on a 2-device mesh at 1e4 keys
    reads byte-identical to the single-process sparse jnp oracle."""
    script = _CHILD_COMMON % dict(S=2, C=10_000, nb=8, epb=256) + """
assert shuf0.ravel().sum() > 0  # cross-device routing actually happened
print("KEYED_2DEV_OK")
"""
    _run_child(script, "KEYED_2DEV_OK")


@pytest.mark.multidevice
def test_keyed_dataplane_8dev_crash_and_partition():
    """8-way sharded q5 under chaos: a crash-replay fold schedule and a
    partitioned-then-healed watermark plane both end byte-identical to the
    clean run (and hence to the oracle); a never-healed partition stalls
    every window rather than emitting a wrong value."""
    script = _CHILD_COMMON % dict(S=8, C=10_000, nb=12, epb=256) + """
# crash at step 8, deterministic replay from batch 5 (re-folds are no-ops
# under the folded frontier)
crash = np.concatenate([np.arange(9), np.arange(5, 9), np.arange(9, 12)])
crash = np.tile(crash.astype(np.int32), (S, 1))
oks1, vals1, _, _ = run(crash, np.ones(crash.shape[1] // 4, bool))
np.testing.assert_array_equal(oks1, oks0)
np.testing.assert_array_equal(vals1, vals0)

# partition rounds 1-2 of 6 (watermark exchange suppressed), then heal
wm = np.ones(6, bool); wm[1:3] = False
oks2, vals2, _, sync2 = run(base, wm, sync_every=2)
np.testing.assert_array_equal(oks2, oks0)
np.testing.assert_array_equal(vals2, vals0)
assert sync2.ravel()[0] == 4 * S * 4.0  # 4 healthy rounds x [S] i32 map

# never healed: progress maps stay diverged, every window stalls (not-ok)
oks3, _, _, _ = run(base, np.zeros(6, bool), sync_every=2)
assert oks3.sum() == 0.0, oks3
print("KEYED_8DEV_CHAOS_OK")
"""
    _run_child(script, "KEYED_8DEV_CHAOS_OK")
