"""Ahead-of-time compiles for a described TPU v5e:2x2 — nothing runs.

The TPU compiler is installed even where no chip is attached, and it refuses
what interpret mode accepts: unaligned blocks, unaligned dynamic slices, 1-D
event blocks reshaped inside a kernel, a ``pallas_call`` whose output lacks
the device-variance ``shard_map`` checks.  These compiles pin each Pallas
kernel at real widths and the delta-sync dataplane (the path that carries
``gated_delta_merge``) on a four-chip mesh.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and the test workers all import
this file.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh, NamedSharding, SingleDeviceSharding
from jax.sharding import PartitionSpec as P

B, W = 4096, 64  # event lanes per fold call, ring slots


@pytest.fixture(scope="module")
def topo():
    from jax.experimental import topologies

    try:
        desc = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:  # no TPU compiler here: nothing to rehearse
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # compiles for a described chip cannot be read back from a persistent
    # cache without one; keep the cache out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield desc
    jax.config.update("jax_enable_compilation_cache", was)


@pytest.fixture(scope="module")
def one_chip(topo):
    return SingleDeviceSharding(topo.devices[0])


def _compile(fn, *args):
    """Compiled HLO text; raises what the chip's compiler would raise."""
    return jax.jit(fn).lower(*args).compile().as_text()


def _tenancy_scatters(text):
    """Scatter instructions, fused or not, in the insert's slot tenancy: the
    dense per-slot max leaves none."""
    return [l for l in text.splitlines()
            if " scatter(" in l and "/fold/tenancy/" in l]


def _event_log(sharding, shape):
    """EventBatch of ``shape`` operands, one per field, on ``sharding``."""
    from repro.streaming.events import EventBatch

    dtypes = dict(ts=jnp.int32, kind=jnp.int32, auction=jnp.uint32,
                  price=jnp.float32, category=jnp.int32, bidder=jnp.uint32,
                  valid=jnp.bool_)
    return EventBatch(**{
        f: jax.ShapeDtypeStruct(shape, dt, sharding=sharding)
        for f, dt in dtypes.items()
    })


def test_gated_delta_merge_compiles(one_chip):
    from repro.kernels.ops import gated_delta_merge

    R, F = 4, 256
    text = _compile(
        lambda w, x: gated_delta_merge(w, x, op="max", use_pallas=True),
        jax.ShapeDtypeStruct((R, W), jnp.int32, sharding=one_chip),
        jax.ShapeDtypeStruct((R, W, F), jnp.float32, sharding=one_chip),
    )
    assert "tpu_custom_call" in text


def _events(one_chip, *dtypes):
    """One ``[B]`` event-lane operand per dtype."""
    return [jax.ShapeDtypeStruct((B,), dt, sharding=one_chip) for dt in dtypes]


@pytest.mark.parametrize("op", ["sum", "max"])
@pytest.mark.parametrize("keyed", [False, True])
def test_window_agg_compiles(one_chip, op, keyed):
    from repro.kernels.window_agg import window_agg_pallas

    C = 128
    if keyed:
        fn = lambda v, s, k, m: window_agg_pallas(v, s, m, W, op=op, keys=k, C=C)
        args = _events(one_chip, jnp.float32, jnp.int32, jnp.int32, jnp.bool_)
    else:
        fn = lambda v, s, m: window_agg_pallas(v, s, m, W, op=op)
        args = _events(one_chip, jnp.float32, jnp.int32, jnp.bool_)
    assert "tpu_custom_call" in _compile(fn, *args)


def test_topk_window_compiles(one_chip):
    from repro.kernels.topk_window import topk_window_pallas

    k = 8
    state = [
        jax.ShapeDtypeStruct((W, k), jnp.float32, sharding=one_chip),
        jax.ShapeDtypeStruct((W, k), jnp.uint32, sharding=one_chip),
    ]
    ev = _events(one_chip, jnp.float32, jnp.uint32, jnp.int32, jnp.bool_)
    assert "tpu_custom_call" in _compile(topk_window_pallas, *state, *ev)


@pytest.mark.parametrize("op", ["sum", "max"])
def test_segment_reduce_compiles(one_chip, op):
    from repro.kernels.segment_reduce import segment_reduce_pallas

    n_seg = W * 2048  # above SPARSE_KEY_THRESHOLD keys per window
    fn = lambda v, g, m: segment_reduce_pallas(v, g, m, n_seg, op=op)
    args = _events(one_chip, jnp.float32, jnp.int32, jnp.bool_)
    assert "tpu_custom_call" in _compile(fn, *args)


def test_q4_delta_sync_pipeline_compiles_on_four_chips(topo, monkeypatch):
    """The dataplane's default path for a Reduce-lattice query: delta sync
    joins all-gathered deltas with the Pallas gated merge inside shard_map."""
    from repro.kernels import ops
    from repro.launch.stream import build_pipeline
    from repro.streaming.queries import make_q4

    monkeypatch.setattr(ops, "_on_tpu", lambda: True)
    jax.clear_caches()  # no CPU-dispatch trace of the kernel wrappers reused
    n_dev, nb, epb = 4, 8, B
    mesh = Mesh(np.array(topo.devices[:n_dev]), ("data",),
                axis_types=(jax.sharding.AxisType.Auto,))
    log = _event_log(NamedSharding(mesh, P("data")), (n_dev, nb, epb))
    query = make_q4(n_dev, window_len=10_000, num_slots=W)
    pipe = build_pipeline(query, mesh, sync_every=4, n_windows=4)
    compiled = pipe.lower(log).compile()
    text = compiled.as_text()
    assert "tpu_custom_call" in text  # the gated merge kernel is in
    assert "all-gather" in text  # and the deltas cross chips
    assert not _tenancy_scatters(text)
    jax.clear_caches()


@pytest.mark.parametrize("n_windows", [11, 1])
def test_keyed_q5_ring_stays_in_the_scatters_layout(topo, n_windows):
    """The one-chip q5 cell's keyed dataplane at its real size: 2.5e7 keys, a
    16-slot ring, 10 s windows sliding by 5 s, 8 x 65,536 events per call.
    The ring is one flat tile-aligned ``f32[16 * width_p]`` buffer that the
    scatter adds into where it lies: no ``[16, 1, 2.5e7]`` ring, no flat copy
    of it, and no ring-sized dynamic-update-slice outside the slot reset.
    With one window read, the program holds the ring and little else; with
    the cell's 11, the read adds the zero ring and two ``[11, width_p]``
    buffers (tiled to 16 rows), and still no buffer of the fold's."""
    from repro.core.wcrdt import KeyShards
    from repro.launch.stream import build_keyed_pipeline

    nb, epb = 8, 65_536
    shards = KeyShards(25_000_000, 1)
    width_p = -(-shards.width // 1024) * 1024
    ring = f"f32[{16 * width_p}]"
    mesh = Mesh(np.array(topo.devices[:1]), ("data",),
                axis_types=(jax.sharding.AxisType.Auto,))
    shard, rep = NamedSharding(mesh, P("data")), NamedSharding(mesh, P())
    log = _event_log(shard, (1, nb, epb))
    pipe = build_keyed_pipeline(mesh, shards, window_len=10_000, num_slots=16,
                                hop=5_000, sync_every=4, n_windows=n_windows)
    compiled = pipe.lower(
        log,
        jax.ShapeDtypeStruct((1, shards.width), jnp.uint32, sharding=shard),
        jax.ShapeDtypeStruct((1, nb), jnp.int32, sharding=rep),
        jax.ShapeDtypeStruct((nb // 4,), jnp.bool_, sharding=rep),
    ).compile()
    text = compiled.as_text()

    assert "16,1,25000000" not in text and "f32[400000000]" not in text
    assert any(ring in l and " scatter(" in l for l in text.splitlines())
    for line in text.splitlines():
        rhs = line.partition(" = ")[2]
        if rhs.startswith(ring) and " dynamic-update-slice(" in rhs:
            assert "/fold/reset/" in rhs, line[:200]
    ring_bytes = 16 * width_p * 4
    held = ring_bytes * (1 if n_windows == 1 else 4) + 100e6
    assert compiled.memory_analysis().temp_size_in_bytes <= held


def test_q7_pipeline_compiles_at_the_cells_shapes(topo):
    """The one-chip q7 cell's dataplane: 32 x 65,536 events per call, 10 s
    tumbling windows, k = 8 over a 64-slot ring, delta sync every 4 batches.
    Each batch's TopK fold pre-filters with the chip's TopK op, not a sort of
    the batch, and sorts the batch's lanes only in the exact branch of its
    conditional."""
    from repro.launch.stream import build_pipeline
    from repro.streaming.queries import make_q7

    nb, epb = 32, 65_536
    mesh = Mesh(np.array(topo.devices[:1]), ("data",),
                axis_types=(jax.sharding.AxisType.Auto,))
    log = _event_log(NamedSharding(mesh, P("data")), (1, nb, epb))
    query = make_q7(1, window_len=10_000, num_slots=64, k=8, topk_active=4)
    text = build_pipeline(query, mesh, sync_every=4, n_windows=21).lower(log) \
        .compile().as_text()
    topk = [l for l in text.splitlines() if 'custom_call_target="TopK"' in l]
    assert topk and all("/fold/scatter/prefilter/" in l for l in topk)
    assert " conditional(" in text
    # the exact join sorts each offset's k state pairs with every lane
    lane_sorts = [l for l in text.splitlines()
                  if " sort(" in l and f",{epb + 8}]" in l.partition(" = ")[2][:40]]
    assert lane_sorts and all("/fold/scatter/" in l and "/exact/" in l for l in lane_sorts)
    assert not _tenancy_scatters(text)
