#!/usr/bin/env python3
"""Record the small scoped chip trace that ``test_scopes.py`` reads.

    python3 chipbench/tests/record_scoped_trace.py --out <dir>

On the chip, runs the small q4 cell of ``small.py`` for a short window with
a traced stretch of three calls, and writes to ``<dir>`` the trace
(``q4-small-scoped.xplane.pb.gz``), the HLO text of the executable it ran
(``q4-small-scoped.hlo.txt.gz``), and what ``scopes`` read from them when
they were recorded (``q4-small-scoped.summary.json``).  Copy all three into
``tests/data/``.  ``record_trace.py`` recorded the trace of an older program,
whose ops carry no layer scopes.
"""
import argparse
import dataclasses
import gzip
import json
import shutil
import sys
import tempfile
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]
STEM = "q4-small-scoped"


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--out", required=True)
    args = ap.parse_args()
    import jax
    from jax.profiler import ProfileData

    from chipbench import scopes
    from chipbench.harness import set_up
    from chipbench.run import tpu_devices
    from chipbench.tests.small import small_cell

    devices = tpu_devices(jax, 1)
    if devices is None:
        return 2
    runner, pool, _ = set_up(small_cell("q4-1chip"), devices, 3_000_000_019, {})
    text = runner.compiled.as_text()
    tmp = tempfile.mkdtemp(prefix="chipbench-record-")
    try:
        win = runner.window(pool, 0.0, trace_dir=tmp, call_s=10.0)
        out = Path(args.out)
        out.mkdir(parents=True, exist_ok=True)
        raw = Path(win.trace_file).read_bytes()
        (out / f"{STEM}.xplane.pb.gz").write_bytes(gzip.compress(raw))
        (out / f"{STEM}.hlo.txt.gz").write_bytes(gzip.compress(text.encode()))
        ids = [devices[0].id]
        got = scopes.reduce_scopes(ProfileData.from_file(win.trace_file).planes,
                                   runner.module, ids, len(win.traced),
                                   scopes.scope_map(text))
        summary = {"module": runner.module, "device_ids": ids,
                   "calls": len(win.traced), **dataclasses.asdict(got)}
        (out / f"{STEM}.summary.json").write_text(json.dumps(summary, indent=1))
    finally:
        shutil.rmtree(tmp, ignore_errors=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
