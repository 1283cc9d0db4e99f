"""The closed loop that a cell runs, and the comparison that decides
``correct``.

A cell is one entry of ``workloads`` in ``BENCHMARK.json``.  Everything that
belongs to it is found by name: its configuration's sizes
(``configs/<config>.json``), the program's entry for it
(``configs/<modules>.entry.py``), its plain reference
(``configs/<modules>.reference.py``), where ``<modules>`` is the
configuration's own name unless its file names another (two deployments of
one query share their modules), its traffic mix
(``traffic/<traffic>.json``) and one reader per per-layer metric
(``metrics/<metric>.py``).  Adding a cell adds files and entries; no file
here changes.

A run is a closed loop with one caller, the way a micro-batch engine hands
its stream to a dataplane.  Each call feeds the next chunk of a pool of
host-resident event chunks to the cell's chips (``device_put``), runs the
program's entry on it, and fetches the closed windows to the host.  Every
call's windows are kept and, once the window of the run has closed, compared
with the plain reference of the chunk the call was fed.
"""
from __future__ import annotations

import gc
import importlib.util
import json
import math
import sys
import time
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

from chipbench import nexmark

ROOT = Path(__file__).resolve().parent.parent
HERE = ROOT / "chipbench"
TRACE_SECONDS = 1.0  # a traced stretch spans about this much, in whole calls
TRACE_CALLS = (3, 20)  # and at least / at most this many calls
TRACE_SKIP = 2  # calls of the window before the traced stretch


def peaks() -> dict:
    """Published peaks per ``device_kind``, with their source."""
    return json.loads((HERE / "peaks.json").read_text())


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


def load_module(path: Path):
    spec = importlib.util.spec_from_file_location(
        "chipbench_" + path.name.replace("-", "_").replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", [cell])


class Cell:
    """One workload of ``BENCHMARK.json`` with everything it names loaded."""

    def __init__(self, name: str):
        bench = json.loads((ROOT / "BENCHMARK.json").read_text())
        by_name = {w["name"]: w for w in bench["workloads"]}
        if name not in by_name:
            raise SystemExit(f"unknown workload {name!r}; known: {sorted(by_name)}")
        w = by_name[name]
        cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
        cfg_file = ROOT / cfg["file"]
        self.name, self.chips = name, w["chips"]
        self.config = json.loads(cfg_file.read_text())
        self.traffic = json.loads((HERE / "traffic" / f"{w['traffic']}.json").read_text())
        stem = cfg_file.with_name(self.config.get("modules", cfg_file.stem))
        self.entry = load_module(stem.with_name(stem.name + ".entry.py"))
        self.ref = load_module(stem.with_name(stem.name + ".reference.py"))
        self.end_to_end = [m for m in bench["end_to_end"] if _applies(m, name)]
        self.per_layer = [m for m in bench["per_layer"] if _applies(m, name)]

    @property
    def partitions(self) -> int:
        return self.chips * self.config["partitions_per_chip"]

    @property
    def num_auctions(self) -> int:
        return self.ref.num_auctions(self.config, self.chips)

    @property
    def events_per_call(self) -> int:
        return (self.partitions * self.traffic["batches_per_call"]
                * self.traffic["events_per_batch"])

    def reader(self, metric: str):
        return load_module(HERE / "metrics" / f"{metric}.py")


@dataclass
class Call:
    chunk: int  # index into the pool
    out: tuple  # the emitted windows, on the host
    phases: tuple  # host seconds of feed, dispatch, emit
    start: float  # host clock at the start of the feed
    end: float  # host clock once the windows are on the host

    @property
    def seconds(self) -> float:
        return self.end - self.start


@dataclass
class Window:
    calls: list
    seconds: float
    compiles: int  # compilations inside the window: should be 0
    traced: list = field(default_factory=list)  # the calls in the trace
    trace_file: str | None = None


class Runner:
    """Builds a cell's entry on ``devices`` and drives its closed loop.

    ``program`` replaces the entry for a control run: a function of the host
    chunk that returns what the entry would emit."""

    def __init__(self, cell: Cell, devices: list):
        import jax

        self.cell, self.devices, self.jax = cell, devices, jax
        self.split: dict[str, float] = {}
        self._counting = False
        self._compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_duration)

    def _on_duration(self, event: str, duration: float, **_) -> None:
        if self._counting and event.startswith("/jax/core/compile/"):
            self._compiles += 1

    def _timed(self, part: str, fn, *args):
        t = time.perf_counter()
        out = fn(*args)
        self.split[part] = self.split.get(part, 0.0) + time.perf_counter() - t
        return out

    def make_pool(self, seed: int) -> list:
        """The cell's pool of event chunks for ``seed``: made on the device,
        then pulled to host memory once."""
        c = self.cell
        fn = nexmark.make_chunk_fn(c.traffic, c.num_auctions, c.partitions)

        def make():
            return [tuple(np.asarray(x) for x in self.jax.device_get(
                fn(np.int32(nexmark.chunk_seed(seed, i)))))
                for i in range(c.traffic["pool_chunks"])]

        return self._timed("traffic", make)

    def build(self, example: tuple) -> None:
        """The entry, its static inputs, and its compiled program."""
        c = self.cell
        self.entry = self._timed("static_inputs", c.entry.build, c.config,
                                 c.traffic, self.devices, c.num_auctions)

        def compile_():
            args = (*self.entry.place(example), *self.entry.static)
            return self.entry.fn.lower(*args).compile()

        self.compiled = self._timed("compile", compile_)
        first = self.compiled.as_text().split("\n", 1)[0]
        self.module = first.split()[1].rstrip(",") if first.startswith("HloModule") else None
        self.memory = self.compiled.memory_analysis()

    def call(self, i: int, pool: list, program=None) -> Call:
        jax = self.jax
        k = i % len(pool)
        ann = jax.profiler.TraceAnnotation
        t0 = time.perf_counter()
        if program is not None:
            out = program(pool[k])
            t = time.perf_counter()
            return Call(k, out, (0.0, t - t0, 0.0), t0, t)
        with ann("feed"):
            placed = self.entry.place(pool[k])
            jax.block_until_ready(placed)
        t1 = time.perf_counter()
        with ann("dispatch"):
            out = self.compiled(*placed, *self.entry.static)
        t2 = time.perf_counter()
        with ann("emit"):
            host = tuple(np.asarray(x) for x in jax.device_get(self.entry.emit(out)))
        t3 = time.perf_counter()
        return Call(k, host, (t1 - t0, t2 - t1, t3 - t2), t0, t3)

    def warm_up(self, pool: list) -> float:
        """Every chunk of the pool fed once, then one whole call: every
        shape and every host buffer the window uses.  Returns the call's
        time."""
        def warm():
            for chunk in pool:
                self.jax.block_until_ready(self.entry.place(chunk))
            seconds = self.call(0, pool).seconds
            # the objects of set-up (JAX's own among them) go to the
            # permanent generation, so a full collection in the window does
            # not walk them: one took ~0.1 s and stalled a call
            gc.collect()
            gc.freeze()
            return seconds

        return self._timed("warm_up", warm)

    def window(self, pool: list, seconds: float, trace_dir: str | None = None,
               call_s: float = 1.0, program=None) -> Window:
        """Calls back to back until ``seconds`` have passed.  With
        ``trace_dir``, a stretch of whole calls after the first few is
        traced with the profiler."""
        jax = self.jax
        n_trace = min(max(math.ceil(TRACE_SECONDS / max(call_s, 1e-6)),
                          TRACE_CALLS[0]), TRACE_CALLS[1])
        span = (TRACE_SKIP, TRACE_SKIP + n_trace) if trace_dir else None
        calls: list[Call] = []
        self._compiles, self._counting = 0, True
        t_start = time.perf_counter()
        try:
            i = 0
            while True:
                if span and i == span[0]:
                    jax.profiler.start_trace(trace_dir)
                calls.append(self.call(i, pool, program))
                i += 1
                if span and i == span[1]:
                    jax.profiler.stop_trace()
                if (time.perf_counter() - t_start >= seconds
                        and (not span or i >= span[1])):
                    break
        finally:
            self._counting = False
        win = Window(calls, calls[-1].end - t_start, self._compiles)
        if span:
            win.traced = calls[span[0]:span[1]]
            found = sorted(Path(trace_dir).glob("**/*.xplane.pb"))
            win.trace_file = str(found[-1]) if found else None
        return win

    def check(self, pool: list, win: Window) -> tuple[dict, int]:
        """Every call's windows against the plain reference of its chunk.
        Returns ``{number: {"value", "limit"}}`` and the count of calls with
        any number past its limit."""
        c = self.cell
        want = [c.ref.expected(c.config, c.traffic, dict(zip(nexmark.FIELDS, ch)))
                for ch in pool]
        per_call = [c.ref.compare(c.config, call.out, want[call.chunk])
                    for call in win.calls]
        checks, bad = {}, np.zeros(len(per_call), bool)
        for name, (agg, limit) in c.ref.CHECKS.items():
            vals = np.array([p[name] for p in per_call], np.float64)
            bad |= vals > limit
            value = int(vals.sum()) if agg == "sum" else float(vals.max(initial=0.0))
            checks[name] = {"value": value, "limit": limit}
        return checks, int(bad.sum())


def set_up(cell: Cell, devices: list, seed: int, split: dict):
    """A runner for ``cell`` on ``devices`` with ``seed``'s pool, its entry
    compiled and warmed up; ``split`` is the set-up's split so far.  Returns
    the runner, the pool and the warm-up call's seconds."""
    runner = Runner(cell, devices)
    runner.split.update(split)
    pool = runner.make_pool(seed)
    runner.build(pool[0])
    return runner, pool, runner.warm_up(pool)


def control_program(cell: Cell, name: str):
    """The reference's control ``name`` in the program's place: a function
    of a host chunk that returns what the entry would emit."""
    fn = cell.ref.CONTROLS[name]
    return lambda chunk: fn(cell.config, cell.traffic,
                            dict(zip(nexmark.FIELDS, chunk)), cell.chips)
