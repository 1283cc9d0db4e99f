"""Small versions of the benchmark's cells for the CPU self-tests, and a
helper that runs one through ``run.main`` on this machine's devices."""
import contextlib
import io
import json
from unittest import mock

from chipbench import harness, run
from chipbench.harness import Cell

# rate 1,000 events/s stretches 4,096 events over 4.1 s of event time, so
# 8 batches close several 10 s windows and a zipf hot item gets ~1e3 bids,
# more than bfloat16 counts exactly
TRAFFIC = {"events_per_batch": 4096, "batches_per_call": 8,
           "rate_per_partition": 1000.0, "pool_chunks": 2}
CONFIG = {"q4-1chip": {}, "q5-zipf-1chip": {"keys_per_chip": 1000},
          "q5-zipf-4chip": {"keys_per_chip": 1000},
          "q5-uniform-1chip": {"keys_per_chip": 100_000}}
CELLS = sorted(CONFIG)


def small_cell(name: str) -> Cell:
    """The cell ``name`` with its sizes cut for the CPU."""
    cell = Cell(name)
    cell.config |= CONFIG[name]
    cell.traffic |= TRAFFIC
    return cell


@contextlib.contextmanager
def on_cpu():
    """``run`` and ``readings`` past the chip check, with small cells."""
    with mock.patch.object(harness, "Cell", small_cell), \
            mock.patch.object(run, "tpu_devices", lambda jax, chips: jax.devices()[:chips]):
        yield


def run_cell(cell: str, seed: int = 3_000_000_007, seconds: float = 0.3,
             trace: int = 0) -> dict:
    out = io.StringIO()
    with on_cpu(), contextlib.redirect_stdout(out):
        rc = run.main(["--workload", cell, "--seed", str(seed), "--seconds",
                       str(seconds), "--trace", str(trace)])
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])
