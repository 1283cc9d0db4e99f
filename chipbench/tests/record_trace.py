#!/usr/bin/env python3
"""Record the small chip trace that ``test_trace_reduce.py`` reads.

    python3 chipbench/tests/record_trace.py --out <dir>

On the chip, runs the small q4 cell of ``small.py`` for a short window with
a traced stretch of three calls, and writes to ``<dir>`` the trace
(``q4-small.xplane.pb``) and what ``trace_reduce`` read from it when it was
recorded (``q4-small.summary.json``).  Copy both into ``tests/data/``.
"""
import argparse
import dataclasses
import json
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    import jax

    from chipbench import trace_reduce
    from chipbench.harness import set_up
    from chipbench.run import tpu_devices
    from chipbench.tests.small import small_cell

    devices = tpu_devices(jax, 1)
    if devices is None:
        return 2
    runner, pool, _ = set_up(small_cell("q4-1chip"), devices, 3_000_000_017, {})
    tmp = tempfile.mkdtemp(prefix="chipbench-record-")
    try:
        win = runner.window(pool, 0.0, trace_dir=tmp, call_s=10.0)
        out = Path(args.out)
        out.mkdir(parents=True, exist_ok=True)
        shutil.copy(win.trace_file, out / "q4-small.xplane.pb")
        s = trace_reduce.reduce(win.trace_file, runner.module, [devices[0].id],
                                len(win.traced))
        summary = {"module": runner.module, "device_ids": [devices[0].id],
                   **dataclasses.asdict(s)}
        (out / "q4-small.summary.json").write_text(json.dumps(summary, indent=1))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
