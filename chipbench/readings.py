#!/usr/bin/env python3
"""The readings that the limits of ``correct`` are set from, in one process.

    python3 chipbench/readings.py --workload q4-1chip --seeds 12 --control-seeds 3

For each of ``--seeds`` seeds the program's own timed path runs a short
window over that seed's pool, and every call is compared with the plain
reference, as a run compares it: the largest reading over the seeds is the
lower reading of each number.  Then each control of the configuration's
reference (``CONTROLS``: the reference one precision down, or with one
stated guarantee broken) takes the program's place on ``--control-seeds``
seeds: the smallest of its readings is the upper reading.  Benchmark runs
never run this.  One JSON line per reading goes to standard output, and the
summary last.
"""
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

FIRST_SEED = 3_000_000_000  # above 2**31, as the benchmark's seeds may be


def main(argv=None) -> int:
    import argparse

    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--control-seeds", type=int, default=3)
    ap.add_argument("--seconds", type=float, default=2.0)
    ap.add_argument("--first-seed", type=int, default=FIRST_SEED)
    args = ap.parse_args(argv)

    from chipbench.harness import control_program, log, set_up
    from chipbench.run import open_cell

    opened = open_cell(args.workload)
    if opened is None:
        return 2
    cell, devices, _, split = opened
    seeds = [args.first_seed + i for i in range(args.seeds)]
    runner, first_pool, _ = set_up(cell, devices, seeds[0], split)
    lower, upper = {}, {}

    def record(kind: str, seed: int, pool, win) -> dict:
        checks, failed = runner.check(pool, win)
        row = {"kind": kind, "seed": seed, "calls": len(win.calls), "failed": failed,
               **{k: c["value"] for k, c in checks.items()}}
        print(json.dumps(row), flush=True)
        return checks

    for n, seed in enumerate(seeds):
        pool = first_pool if n == 0 else runner.make_pool(seed)
        for k, c in record("program", seed, pool, runner.window(pool, args.seconds)).items():
            lower[k] = max(lower.get(k, c["value"]), c["value"])
    for name in cell.ref.CONTROLS:
        program = control_program(cell, name)
        for seed in seeds[: args.control_seeds]:
            pool = runner.make_pool(seed)
            t = time.perf_counter()
            win = runner.window(pool, args.seconds, program=program)
            checks = record(f"control:{name}", seed, pool, win)
            log(f"control {name} seed {seed}: {len(win.calls)} calls in "
                f"{time.perf_counter() - t} s")
            for k, c in checks.items():
                key = f"{name}:{k}"
                upper[key] = min(upper.get(key, c["value"]), c["value"])
    limits = {k: lim for k, (_, lim) in cell.ref.CHECKS.items()}
    print(json.dumps({"workload": cell.name, "seeds": len(seeds), "lower": lower,
                      "upper": upper, "limits": limits}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
