"""The program's entry for keyed Nexmark q5: the jitted hash-sharded
dataplane of ``repro.launch.stream.build_keyed_pipeline``, with its key
table, fold schedule and all-true watermark sync placed once at set-up and
one chunk of ``[chips, batches, B]`` events fed per call."""
from types import SimpleNamespace

from chipbench.nexmark import read_windows


def build(config: dict, traffic: dict, devices: list, num_auctions: int):
    import jax
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.core.wcrdt import KeyShards
    from repro.launch.mesh import make_data_mesh
    from repro.launch.stream import build_keyed_pipeline, default_fold_schedule
    from repro.streaming.events import EventBatch

    S = len(devices)
    mesh = make_data_mesh(S)
    first, n = read_windows(config, traffic)
    shards = KeyShards(num_auctions, S)
    fn = build_keyed_pipeline(
        mesh, shards, window_len=config["window_ms"],
        num_slots=config["ring_slots"], hop=config["hop_ms"],
        sync_every=config["sync_every"], n_windows=n, first_window=first,
    )
    sharding = NamedSharding(mesh, P("data"))
    nb = traffic["batches_per_call"]
    replicated = NamedSharding(mesh, P())
    static = (
        jax.device_put(shards.key_table(), sharding),
        jax.device_put(default_fold_schedule(S, nb), replicated),
        jax.device_put(np.ones(nb // config["sync_every"], bool), replicated),
    )
    return SimpleNamespace(
        fn=fn,
        static=static,
        place=lambda chunk: (jax.device_put(EventBatch(*chunk), sharding),),
        emit=lambda out: out[:2],  # (oks [S, n], vals [S, n, (count, id)])
    )
