#!/usr/bin/env python3
"""Run one benchmark cell on the chips of this machine.

    python3 chipbench/run.py --workload q4-1chip --seed 7 --seconds 10 --trace 0

One process holds the cell's chips.  It exits non-zero and prints no result
unless JAX's platform is ``tpu`` with at least as many chips as the cell asks
for.  Set-up (imports, traffic, static inputs, compilation into the
persistent cache, one warm-up call) is ``setup_s``; then the closed loop runs
for ``--seconds``.  With ``--trace 0`` the result carries the cell's
end-to-end metrics, with ``--trace 1`` its per-layer metrics, read from a
profiler trace of a short stretch of whole calls.  Either way every call of
the window is compared with the plain reference, and ``correct`` says
whether every number compared stayed within its limit.  Diagnostics go to
standard error, the numbers compared last; the last line of standard output
is one JSON object.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def tpu_devices(jax, chips: int):
    """The cell's chips, or ``None`` where this machine has too few TPUs of
    a kind that ``peaks.json`` knows."""
    from chipbench.harness import log, peaks

    devices = jax.devices()
    d = devices[0]
    log(f"devices: platform={d.platform} kind={d.device_kind} count={len(devices)}")
    if d.platform != "tpu":
        log(f"no TPU: JAX's platform is {d.platform!r}; nothing was run")
        return None
    if len(devices) < chips:
        log(f"the cell needs {chips} chips; JAX sees {len(devices)}")
        return None
    if d.device_kind not in peaks():
        log(f"no peaks for device kind {d.device_kind!r} in chipbench/peaks.json")
        return None
    return devices[:chips]


def enable_cache(jax) -> str:
    """JAX's persistent compilation cache, at a fixed path in the checkout
    unless ``JAX_COMPILATION_CACHE_DIR`` names one."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(ROOT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


def peak_bytes(device) -> int:
    """The chip's peak: buffers in use plus the region reserved for program
    temporaries, which ``peak_bytes_in_use`` leaves out on the TPU."""
    stats = device.memory_stats() or {}
    return stats.get("peak_bytes_in_use", 0) + stats.get("peak_bytes_reserved", 0)


def open_cell(workload: str):
    """The cell, its chips, the compile cache's directory and the set-up
    split so far (``imports``, ``tpu_init``: seconds since the process
    started), or ``None`` where this machine lacks the chips."""
    from chipbench import harness
    import jax

    cell = harness.Cell(workload)
    cache = enable_cache(jax)
    t = time.perf_counter()
    devices = tpu_devices(jax, cell.chips)
    split = {"imports": t - T_START, "tpu_init": time.perf_counter() - t}
    return None if devices is None else (cell, devices, cache, split)


def main(argv=None) -> int:
    args = parse(argv)
    from chipbench import harness, report
    from chipbench.harness import log

    opened = open_cell(args.workload)
    if opened is None:
        return 2
    cell, devices, cache, split = opened
    runner, pool, call_s = harness.set_up(cell, devices, args.seed, split)
    setup_s = time.perf_counter() - T_START
    report.log_setup(runner, setup_s, cache)

    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-") if args.trace else None
    try:
        win = runner.window(pool, args.seconds, trace_dir, call_s)
        peak = max(peak_bytes(d) for d in devices)
        result = report.result(runner, win, setup_s, peak, trace=bool(args.trace))
    finally:
        if trace_dir:
            shutil.rmtree(trace_dir, ignore_errors=True)
    checks, failed = runner.check(pool, win)
    correct = failed == 0 and len(win.calls) > 0 and all(
        c["value"] <= c["limit"] for c in checks.values())
    result = {"correct": correct, "attempted": len(win.calls), "failed": failed,
              **result, "checks": checks}
    log(f"window: {len(win.calls)} calls in {win.seconds} s, "
        f"{win.compiles} compilations inside it")
    report.log_calls(win)
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
