"""From a profiler trace (``.xplane.pb``) to the numbers the per-layer
readers take and to the run's ``breakdown``.

The trace is read with ``jax.profiler.ProfileData``.  Each chip is a plane
``/device:TPU:<id>``: its line ``XLA Ops`` holds one event per HLO op run,
named by the whole HLO instruction, with a loop's body nested inside the
loop's own event; ``Async XLA Ops`` holds the start-to-done spans of async
ops (copies, and on several chips collectives); ``XLA Modules`` holds one
event per executable run.  Busy time is the union of ``XLA Ops`` events,
collective time that of the collective ops of both op lines.  The host plane
``/host:CPU`` holds the harness's ``TraceAnnotation`` spans ``feed``,
``dispatch`` and ``emit``, on the same clock.  The traced window runs from
the first ``feed`` to the last ``emit``; device time outside it is left out.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass, field

import numpy as np

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PHASES = ("feed", "dispatch", "emit")
ASYNC_LINE = "Async XLA Ops"
COLLECTIVE = re.compile(
    r"^(all-gather|all-to-all|all-reduce|collective-permute|reduce-scatter)"
    r"(-start|-done)?$")
TOP = 10


def union(iv: np.ndarray) -> np.ndarray:
    """Disjoint, sorted union of ``[start, end)`` rows."""
    if len(iv) == 0:
        return np.zeros((0, 2))
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out)


def length(iv: np.ndarray) -> float:
    return float((iv[:, 1] - iv[:, 0]).sum()) if len(iv) else 0.0


def clip(iv: np.ndarray, lo: float, hi: float) -> np.ndarray:
    if len(iv) == 0:
        return iv
    iv = np.stack([np.maximum(iv[:, 0], lo), np.minimum(iv[:, 1], hi)], 1)
    return iv[iv[:, 1] > iv[:, 0]]


def intersect(a: np.ndarray, b: np.ndarray) -> np.ndarray:
    """Intersection of two disjoint sorted unions."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        s, e = max(a[i, 0], b[j, 0]), min(a[i, 1], b[j, 1])
        if e > s:
            out.append((s, e))
        if a[i, 1] < b[j, 1]:
            i += 1
        else:
            j += 1
    return np.asarray(out).reshape(-1, 2)


def hlo_parts(text: str) -> tuple[str, str]:
    """``(instruction name, opcode)`` of an op event, whose name on the TPU
    is the whole HLO instruction: ``%name = <shape> opcode(operands), ...``
    (a tuple shape is parenthesised and holds spaces)."""
    name, _, rest = text.partition(" = ")
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                break
        rest = rest[i + 1:]
    else:
        rest = rest.partition(" ")[2]
    return name.lstrip("%"), rest.strip().partition("(")[0]


def is_collective(opcode: str) -> bool:
    return bool(COLLECTIVE.match(opcode))


def self_times(events: list) -> dict:
    """Seconds per op name, each op less the ops nested inside it (a
    ``while`` holds its body's ops on the same line)."""
    out = defaultdict(float)
    stack: list = []  # (end, name) of the ops enclosing the current one
    for s, e, name in sorted(events, key=lambda x: (x[0], -x[1])):
        while stack and stack[-1][0] <= s:
            stack.pop()
        out[name] += e - s
        if stack:
            out[stack[-1][1]] -= e - s
        stack.append((e, name))
    return out


@dataclass
class Summary:
    """Per-chip seconds of one traced stretch of ``calls`` calls."""

    calls: int
    window_s: float
    busy_s: list  # union of all op intervals, per chip
    module_busy_s: list  # union of the entry executable's op intervals
    collective_busy_s: list  # union of the collective ops' intervals
    op_s: dict = field(default_factory=dict)  # op name -> seconds, mean per chip
    gap_s: dict = field(default_factory=dict)  # host phase -> idle seconds, mean per chip

    @staticmethod
    def mean_over_devices(values: list) -> float:
        return float(np.mean(values)) if values else 0.0

    def breakdown(self) -> dict:
        def top(d):
            return [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:TOP]]
        return {"device_ops": top(self.op_s), "idle_gaps": top(self.gap_s)}


def _events(line):
    for e in line.events if line is not None else ():
        yield e.name, e.start_ns * 1e-9, (e.start_ns + e.duration_ns) * 1e-9


def _host_spans(planes) -> dict:
    spans = defaultdict(list)
    for plane in planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            for name, s, e in _events(line):
                if name in HOST_PHASES:
                    spans[name].append((s, e))
    return {k: np.asarray(sorted(v)) for k, v in spans.items()}


def _device_planes(planes, device_ids) -> list:
    want = {f"/device:TPU:{i}" for i in device_ids}
    return [p for p in planes if p.name in want]


def label_gaps(gaps: np.ndarray, phases: list) -> tuple[list, list]:
    """Names each idle gap by the host phase its midpoint falls in
    (``between calls`` where none does); returns names and seconds."""
    starts = np.asarray([p[0] for p in phases])
    names, secs = [], []
    for s, e in gaps:
        mid = 0.5 * (s + e)
        k = int(np.searchsorted(starts, mid, side="right")) - 1
        inside = k >= 0 and phases[k][1] > mid
        names.append(phases[k][2] if inside else "between calls")
        secs.append(float(e - s))
    return names, secs


def reduce_planes(planes, module: str | None, device_ids, calls: int) -> Summary:
    planes = list(planes)
    host = _host_spans(planes)
    if "feed" not in host or "emit" not in host:
        raise ValueError("the trace holds no feed/emit spans of the harness")
    lo, hi = float(host["feed"][0, 0]), float(host["emit"][-1, 1])
    phases = sorted((s, e, name) for name, iv in host.items() for s, e in iv)
    summary = Summary(calls, hi - lo, [], [], [])
    op_s, gap_s = defaultdict(float), defaultdict(float)
    devs = _device_planes(planes, device_ids)
    if not devs:
        raise ValueError(f"the trace holds no plane of devices {list(device_ids)}")
    for plane in devs:
        lines = {line.name: line for line in plane.lines}
        ops, coll, named = [], [], []
        for line_name in (OPS_LINE, ASYNC_LINE):
            for text, s, e in _events(lines.get(line_name)):
                if e <= lo or s >= hi:
                    continue
                s, e = max(s, lo), min(e, hi)
                name, opcode = hlo_parts(text)
                if is_collective(opcode):
                    coll.append((s, e))
                if line_name == OPS_LINE:
                    ops.append((s, e))
                    named.append((s, e, name))
        for name, sec in self_times(named).items():
            op_s[name] += sec / len(devs)
        mods = [(s, e) for name, s, e in _events(lines.get(MODULES_LINE))
                if module is None or name.split("(")[0] == module]
        busy = clip(union(np.asarray(ops).reshape(-1, 2)), lo, hi)
        summary.busy_s.append(length(busy))
        summary.module_busy_s.append(length(intersect(busy, clip(
            union(np.asarray(mods).reshape(-1, 2)), lo, hi))))
        summary.collective_busy_s.append(length(clip(
            union(np.asarray(coll).reshape(-1, 2)), lo, hi)))
        edges = np.concatenate([[lo], busy.reshape(-1), [hi]]).reshape(-1, 2)
        gaps = edges[edges[:, 1] > edges[:, 0]]
        for name, sec in zip(*label_gaps(gaps, phases)):
            gap_s[name] += sec / len(devs)
    summary.op_s, summary.gap_s = dict(op_s), dict(gap_s)
    return summary


def reduce(path: str | None, module: str | None, device_ids, calls: int) -> Summary | None:
    """The summary of the trace at ``path``, or ``None`` where no trace was
    written."""
    if not path:
        return None
    from jax.profiler import ProfileData

    return reduce_planes(ProfileData.from_file(path).planes, module, device_ids, calls)
