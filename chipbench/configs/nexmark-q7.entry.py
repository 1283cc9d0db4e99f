"""The program's entry for Nexmark q7: the jitted replicated dataplane of
``repro.launch.stream.build_pipeline`` over the ``TopK`` window lattice, with
delta sync, one partition per chip, fed one chunk of ``[chips, batches, B]``
events per call."""
from types import SimpleNamespace

from chipbench.nexmark import read_windows


def build(config: dict, traffic: dict, devices: list, num_auctions: int):
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.launch.mesh import make_data_mesh
    from repro.launch.stream import build_pipeline
    from repro.streaming.events import EventBatch
    from repro.streaming.queries import make_q7

    mesh = make_data_mesh(len(devices))
    first, n = read_windows(config, traffic)
    query = make_q7(len(devices), window_len=config["window_ms"],
                    num_slots=config["ring_slots"], k=config["k"],
                    topk_active=config["topk_active"])
    fn = build_pipeline(query, mesh, config["sync_every"],
                        delta_sync=config["delta_sync"], n_windows=n,
                        first_window=first)
    sharding = NamedSharding(mesh, P("data"))
    return SimpleNamespace(
        fn=fn,
        static=(),
        place=lambda chunk: (jax.device_put(EventBatch(*chunk), sharding),),
        emit=lambda out: out[:2],  # (oks [S, n], vals [S, n, 2k])
    )
