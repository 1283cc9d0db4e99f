"""The reduction from a profiler trace to the per-layer numbers."""
import dataclasses
import gzip
import json
from pathlib import Path
from types import SimpleNamespace as NS

import pytest

from chipbench import trace_reduce as T


def ev(name, start_us, dur_us, **stats):
    return NS(name=name, start_ns=start_us * 1e3, duration_ns=dur_us * 1e3,
              stats=stats)


def hlo(name, opcode="fusion"):
    """An op event's name as the TPU writes it: the whole instruction."""
    return f"%{name} = f32[64,1,5]{{2,0,1:T(8,128)}} {opcode}(f32[8]{{0}} %p.1), calls=%c"


def plane(name, **lines):
    return NS(name=name, lines=[NS(name=k.replace("_", " "), events=v)
                                for k, v in lines.items()])


def synthetic():
    """Two traced calls, 0-100 us and 100-200 us, on one chip."""
    host = plane("/host:CPU", python=[
        ev("feed", 0, 20), ev("dispatch", 20, 5), ev("emit", 25, 75),
        ev("feed", 100, 20), ev("dispatch", 120, 5), ev("emit", 125, 75)])
    ops = [ev(hlo("while.9", "while"), 30, 45),  # holds the next four
           ev(hlo("fusion.1"), 30, 15), ev(hlo("fusion.2"), 45, 15),
           ev(hlo("all-to-all.3", "all-to-all"), 60, 10),
           ev(hlo("copy.4", "copy"), 70, 5),
           ev(hlo("fusion.1"), 130, 40), ev(hlo("stray"), 300, 10)]
    async_ops = [ev("%all-gather-start.2 = (f32[8]{0}, f32[32]{0}) "
                    "all-gather-start(f32[8]{0} %p), dimensions={0}", 68, 12),
                 ev(hlo("copy-start.5", "copy-start"), 0, 200)]
    mods = [ev("jit_node_fn(7)", 29, 47), ev("jit_node_fn(7)", 129, 42),
            ev("jit_other(3)", 300, 10)]
    dev = plane("/device:TPU:0", XLA_Ops=ops, XLA_Modules=mods,
                Async_XLA_Ops=async_ops)
    other = plane("/device:TPU:1", XLA_Ops=[ev(hlo("fusion.9"), 0, 200)])
    return [host, dev, other]


def test_synthetic_trace_reduces_exactly():
    s = T.reduce_planes(synthetic(), "jit_node_fn", [0], calls=2)
    us = 1e-6
    assert s.window_s == pytest.approx(200 * us)
    assert s.busy_s == [pytest.approx(85 * us)]  # 30-75 and 130-170
    assert s.module_busy_s == [pytest.approx(85 * us)]
    # the all-to-all (60-70) and the async all-gather (68-80), not the copies
    assert s.collective_busy_s == [pytest.approx(20 * us)]
    assert s.op_s["fusion.1"] == pytest.approx(55 * us)
    # the loop's own time: 45 us less its body's 15 + 15 + 10 + 5
    assert s.op_s["while.9"] == pytest.approx(0.0)
    assert "stray" not in s.op_s  # outside the traced window
    gaps = s.gap_s
    # idle: 0-30 (feed 0-20, dispatch 20-25, emit 25-30: midpoint 15 in feed),
    # 75-130 (midpoint 102.5 in the second feed), 170-200 (emit)
    assert gaps == {"feed": pytest.approx(85 * us), "emit": pytest.approx(30 * us)}
    assert sum(gaps.values()) + s.busy_s[0] == pytest.approx(s.window_s)
    b = s.breakdown()
    assert b["device_ops"][0] == ["fusion.1", pytest.approx(55 * us)]
    assert len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10


def test_only_the_cells_chips_count():
    s = T.reduce_planes(synthetic(), "jit_node_fn", [0, 1], calls=2)
    assert len(s.busy_s) == 2 and s.busy_s[1] == pytest.approx(200e-6)
    assert s.module_busy_s[1] == 0.0  # TPU:1 ran no module of the entry


def test_a_trace_without_the_harness_spans_is_refused():
    planes = synthetic()[1:]
    with pytest.raises(ValueError):
        T.reduce_planes(planes, "jit_node_fn", [0], calls=2)


def test_collective_opcodes():
    for op in ("all-to-all", "all-gather-start", "all-reduce-done",
               "collective-permute", "reduce-scatter"):
        assert T.is_collective(op)
    for op in ("fusion", "all-gatherer", "copy-start", "scatter"):
        assert not T.is_collective(op)


def test_hlo_parts():
    assert T.hlo_parts(hlo("fusion.100")) == ("fusion.100", "fusion")
    tup = ("%while.106 = (s32[]{:T(128)}, f32[64,1,5]{2,0,1:T(8,128)S(1)}) "
           "while((s32[]{:T(128)}, f32[64,1,5]{2,0,1}) %tuple.360), condition=%c")
    assert T.hlo_parts(tup) == ("while.106", "while")


DATA = Path(__file__).resolve().parent / "data"


@pytest.fixture(scope="module")
def recorded():
    """A chip trace of three calls of the small q4 cell, recorded by
    ``record_trace.py`` on one TPU v5e, and what was read from it then."""
    from jax.profiler import ProfileData

    raw = gzip.decompress((DATA / "q4-small.xplane.pb.gz").read_bytes())
    want = json.loads((DATA / "q4-small.summary.json").read_text())
    return ProfileData.from_serialized_xspace(raw), want


def test_recorded_trace_reduces_as_recorded(recorded):
    pd, want = recorded
    s = T.reduce_planes(pd.planes, want["module"], want["device_ids"], want["calls"])
    got = dataclasses.asdict(s)
    for key in ("calls", "window_s", "busy_s", "module_busy_s", "collective_busy_s"):
        assert got[key] == pytest.approx(want[key], rel=1e-9), key
    assert got["op_s"] == pytest.approx(want["op_s"], rel=1e-9, abs=1e-15)
    assert got["gap_s"] == pytest.approx(want["gap_s"], rel=1e-9, abs=1e-15)


def test_recorded_trace_adds_up(recorded):
    pd, want = recorded
    s = T.reduce_planes(pd.planes, want["module"], want["device_ids"], want["calls"])
    assert 0 < s.module_busy_s[0] <= s.busy_s[0] < s.window_s
    assert s.collective_busy_s == [0.0]  # one chip: XLA drops the collectives
    assert sum(s.gap_s.values()) + s.busy_s[0] == pytest.approx(s.window_s)
    assert set(s.gap_s) <= {"feed", "dispatch", "emit", "between calls"}
    # self times tile the busy time: nested ops are not counted twice
    assert sum(s.op_s.values()) == pytest.approx(s.busy_s[0], rel=1e-6)
    assert all(" = " not in name for name in s.op_s)
