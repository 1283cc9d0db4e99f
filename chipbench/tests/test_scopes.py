"""The attribution of device ops to the program's layers, and the bytes the
collectives delivered."""
import gzip
import json
from pathlib import Path
from types import SimpleNamespace as NS

import jax
import pytest

from chipbench import scopes as S
from chipbench import trace_reduce as T
from chipbench.tests.small import small_cell
from chipbench.tests.test_trace_reduce import ev, plane

P = "jit(node_fn)/while/body/closed_call"
HLO = f"""HloModule jit_node_fn, is_scheduled=true, entry_computation_layout={{(f32[8]{{0}})->f32[8]{{0}}}}

FileNames
1 "/src/repro/launch/stream.py"

StackFrames
1 1 0

%fused_computation.1 (param_0: f32[8]) -> f32[8] {{
  %param_0 = f32[8]{{0}} parameter(0)
  %reshape.0 = f32[8]{{0}} reshape(f32[8]{{0}} %param_0), metadata={{op_name="{P}/fold/scatter/select_n"}}
  ROOT %scatter.1 = f32[8]{{0}} scatter(f32[8]{{0}} %reshape.0)
}}

%relayout (q: (s32[], f32[64])) -> (s32[], f32[64]) {{
  %q = (s32[], f32[64]{{0}}) parameter(0)
  %gte.2 = f32[64]{{0}} get-tuple-element((s32[], f32[64]{{0}}) %q), index=1
  %dynamic-update-slice.12 = f32[64]{{0}} dynamic-update-slice(f32[64]{{0}} %gte.2, f32[8]{{0}} %gte.2)
  ROOT %tuple.14 = (s32[], f32[64]{{0}}) tuple(s32[] %gte.2, f32[64]{{0}} %dynamic-update-slice.12)
}}

%body (p: (s32[], f32[8])) -> (s32[], f32[8]) {{
  %p = (s32[], f32[8]{{0}}) parameter(0)
  %gte.1 = f32[8]{{0}} get-tuple-element((s32[], f32[8]{{0}}) %p), index=1
  %fusion.1 = f32[8]{{0:T(8,128)}} fusion(f32[8]{{0}} %gte.1), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{P}/fold/scatter/scatter-add" stack_frame_id=1}}
  %copy.2 = f32[8]{{0}} copy(f32[8]{{0:T(8,128)}} %fusion.1)
  %fusion.8 = f32[8]{{0}} fusion(f32[8]{{0}} %gte.1), kind=kLoop, calls=%fused_computation.1
  %fusion.6 = s32[64]{{0}} fusion(), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="{P}/fold/tenancy/jit(_where)/select_n"}}
  %constant.0 = s32[] constant(0), metadata={{op_name="jit(node_fn)/read/vmap()/concatenate"}}
  %broadcast.13 = f32[64]{{0}} broadcast(s32[] %constant.0), dimensions={{}}
  %tuple.11 = (s32[], f32[64]{{0}}) tuple(s32[] %constant.0, f32[64]{{0}} %fusion.6, f32[64]{{0}} %broadcast.13)
  %while.10 = (s32[], f32[64]{{0}}) while((s32[], f32[64]{{0}}) %tuple.11), condition=%cond, body=%relayout
  %all-to-all.3 = s32[4,16]{{1,0}} all-to-all(s32[4,16]{{1,0}} %copy.2), metadata={{op_name="{P}/shuffle/all_to_all"}}
  %all-gather-start.4 = (f32[8]{{0}}, f32[32]{{0}}) all-gather-start(f32[8]{{0}} %copy.2), metadata={{op_name="{P}/sync/exchange/all_gather"}}
  %all-gather-done.4 = f32[32]{{0}} all-gather-done((f32[8]{{0}}, f32[32]{{0}}) %all-gather-start.4), metadata={{op_name="{P}/sync/exchange/all_gather"}}
  ROOT %tuple.7 = (s32[], f32[8]{{0}}) tuple(s32[] %gte.1, f32[8]{{0}} %copy.2)
}}

ENTRY %main.8 (x: f32[8]) -> f32[8] {{
  %x = f32[8]{{0}} parameter(0), metadata={{op_name="x"}}
  %fusion.18 = f32[8]{{0}} fusion(f32[8]{{0}} %x), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="jit(node_fn)/shuffle/select_n"}}
  %constant.15 = f32[] constant(0)
  %broadcast.16 = f32[64]{{0}} broadcast(f32[] %constant.15), dimensions={{}}
  %tuple.17 = (f32[64]{{0}}, f32[8]{{0}}) tuple(f32[64]{{0}} %broadcast.16, f32[8]{{0}} %fusion.18)
  %while.9 = (s32[], f32[8]{{0}}) while((f32[64]{{0}}, f32[8]{{0}}) %tuple.17), condition=%cond, body=%body, metadata={{op_name="jit(node_fn)/while"}}
  %fusion.5 = f32[8]{{0}} fusion(%while.9), kind=kLoop, calls=%fused_computation.1, metadata={{op_name="jit(node_fn)/read/vmap()/reduce_max;jit(node_fn)/read/vmap()/gather"}}
  ROOT %stray = f32[8]{{0}} copy(f32[8]{{0}} %x)
}}
"""


def op(name, start_us, dur_us):
    """An ``XLA Ops`` event named by the instruction's line."""
    line = next(ln for ln in HLO.splitlines() if f"%{name} = " in ln)
    return ev(line.strip().partition(", metadata")[0], start_us, dur_us)


def synthetic():
    """Two traced calls, 0-100 us and 100-200 us, on one chip."""
    host = plane("/host:CPU", python=[
        ev("feed", 0, 20), ev("dispatch", 20, 5), ev("emit", 25, 75),
        ev("feed", 100, 20), ev("dispatch", 120, 5), ev("emit", 125, 75)])
    ops = [op("broadcast.16", 29, 1),
           op("while.9", 30, 40),  # holds the next ten
           op("fusion.1", 30, 10), op("copy.2", 40, 4), op("fusion.6", 44, 6),
           op("all-to-all.3", 50, 10), op("all-gather-start.4", 60, 1),
           op("all-gather-done.4", 61, 1), op("fusion.8", 62, 1),
           op("broadcast.13", 63, 1), op("while.10", 64, 5),  # holds the next
           op("dynamic-update-slice.12", 64, 4),
           op("fusion.5", 70, 5),
           op("fusion.1", 130, 20), op("stray", 150, 10),
           op("fusion.1", 300, 10)]  # outside the traced window
    mods = [ev("jit_node_fn(7)", 29, 47), ev("jit_node_fn(7)", 129, 32)]
    other = [ev("jit_other(3)", 170, 10)]
    ops.append(ev("%fusion.99 = f32[8]{0} fusion(f32[8]{0} %y), calls=%c", 171, 8))
    dev = plane("/device:TPU:0", XLA_Ops=ops, XLA_Modules=mods + other)
    return [host, dev]


def test_scope_map_rules():
    m = S.scope_map(HLO)
    assert m["fusion.1"] == "fold/scatter"  # a fusion counts as its root
    assert m["fusion.8"] == "fold/scatter"  # no metadata: its computation's
    assert m["copy.2"] == "fold/scatter"  # inherited from its operand
    assert m["fusion.6"] == "fold/tenancy"  # transformations are left out
    assert m["all-to-all.3"] == "shuffle"
    assert m["all-gather-start.4"] == m["all-gather-done.4"] == "sync/exchange"
    assert m["fusion.5"] == "read"  # the first merged op_name with a layer
    # the first operand with a layer: not the constant the compiler shared
    assert m["tuple.7"] == "fold/scatter" and m["tuple.11"] == "fold/tenancy"
    assert m["while.10"] == "fold/tenancy"  # a loop the compiler built
    assert m["dynamic-update-slice.12"] == "fold/tenancy"  # its body, no metadata
    assert m["broadcast.13"] == "fold/tenancy"  # from its user
    # a scan's loop, and the state it starts from, hold every layer it runs
    assert m["while.9"] == m["tuple.17"] == m["broadcast.16"] == "unscoped"
    assert m["fusion.18"] == "shuffle"
    assert m["stray"] == "unscoped"
    assert "FileNames" not in m and "1" not in m


def test_scope_paths():
    assert S.scope_path("jit(node_fn)/while/body/fold/jit(remainder)/rem") == "fold"
    assert S.scope_path("jit(node_fn)/shard_map/read/vmap()/all_gather") == "read"
    assert S.scope_path("jit(node_fn)/while/body/closed_call") is None
    assert S.scope_path("fold/max") == "fold"  # inside a called computation
    assert S.scope_path("jit(node_fn)/fold") is None  # a primitive's own name


def test_synthetic_trace_reduces_by_scope():
    planes = synthetic()
    want = T.reduce_planes(planes, "jit_node_fn", [0], calls=2)
    got = S.reduce_scopes(planes, "jit_node_fn", [0], 2, S.scope_map(HLO))
    ms = 1e-3
    assert got.ms == {
        "unscoped": pytest.approx((1 + 1 + 10) / 2 * ms),  # its state, the scan, stray
        "fold/scatter": pytest.approx((10 + 4 + 1 + 20) / 2 * ms),
        "fold/tenancy": pytest.approx((6 + 1 + 1 + 4) / 2 * ms),
        "shuffle": pytest.approx(10 / 2 * ms),
        "sync/exchange": pytest.approx(2 / 2 * ms),
        "read": pytest.approx(5 / 2 * ms),
    }
    assert got.layer_ms("fold") == pytest.approx(47 / 2 * ms)
    # the layers and unscoped tile the entry's device time: the other
    # module's fusion.99 and the op outside the window are left out
    assert sum(got.ms.values()) == pytest.approx(1e3 * want.module_busy_s[0] / 2)
    # all-to-all s32[4,16] and the all-gather's done half f32[32], not its start
    assert got.collective_mb == pytest.approx((4 * 16 * 4 + 32 * 4) / 1e6 / 2)


def test_result_bytes():
    assert S.result_bytes("%a = pred[4,4,16384]{2,1,0:T(4,128)(4,1)S(1)} "
                          "all-to-all(pred[4,4,16384]{2,1,0} %r)") == 4 * 4 * 16384
    assert S.result_bytes("%b = (bf16[2,3]{1,0}, u32[]) all-reduce-done(%c)") == 16
    assert S.delivers("all-to-all") and S.delivers("all-gather-done")
    assert not S.delivers("all-gather-start") and not S.delivers("copy-done")


def test_layer_names_are_the_programs():
    from repro.obs import DATAPLANE_LAYERS

    assert S.LAYERS == DATAPLANE_LAYERS


@pytest.mark.parametrize("cell", ["q4-1chip", "q5-zipf-4chip"])
def test_compiled_cells_name_their_layers(cell):
    """The small cells' executables, compiled here on the CPU: every layer
    the cell runs has instructions, and the keyed shuffle's all-to-alls lie
    in it."""
    c = small_cell(cell)
    text = S.compiled_text(c, jax.devices()[:c.chips])
    m = S.scope_map(text)
    layers = {S.layer_of(p) for p in m.values()}
    want = {"fold", "sync", "read", "unscoped"}
    assert layers == (want | {"shuffle"} if cell.startswith("q5") else want)
    a2a = [n for n in m if n.startswith("all-to-all") or n.startswith("all_to_all")]
    assert all(m[n] == "shuffle" for n in a2a)
    assert len(a2a) == (3 if cell == "q5-zipf-4chip" else 0)


def test_a_program_without_scopes_gives_no_text():
    fn = jax.jit(lambda x: x + 1.0)
    entry = NS(fn=fn, static=(), place=lambda chunk: (chunk[3],))
    cell = small_cell("q4-1chip")
    cell.entry = NS(build=lambda *args: entry)

    def read():  # as a metric's reader calls it: a frame named like a layer
        return S.compiled_text(cell, jax.devices()[:1])
    assert read() is None


def test_a_scope_less_build_in_the_cache_is_compiled_past(tmp_path):
    """JAX keys its persistent cache without debug information, so it hands
    a program with scopes the executable of the same program built without
    them; the reader compiles past it."""
    import jax.numpy as jnp
    from jax.experimental.compilation_cache import compilation_cache as cc

    def f(x):
        return jnp.cumsum(x * 2.0) + 1.0
    plain = jax.jit(f)

    def f(x):  # noqa: F811 -- the same name, so the same cache key
        with jax.named_scope("fold"):
            y = x * 2.0
        with jax.named_scope("read"):
            return jnp.cumsum(y) + 1.0
    x = jnp.ones(1000)
    entry = NS(fn=jax.jit(f), static=(), place=lambda chunk: (x,))
    cell = small_cell("q4-1chip")
    cell.entry = NS(build=lambda *args: entry)
    keys = ("jax_enable_compilation_cache", "jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    was = {k: getattr(jax.config, k) for k in keys}
    for k, v in zip(keys, (True, str(tmp_path), 0, 0)):
        jax.config.update(k, v)
    cc.reset_cache()
    try:
        plain.lower(x).compile()
        assert not S.has_scopes(entry.fn.lower(x).compile().as_text())
        jax.clear_caches()
        text = S.compiled_text(cell, jax.devices()[:1])
        assert S.has_scopes(text)
        assert jax.config.jax_enable_compilation_cache  # restored
    finally:
        for k, v in was.items():
            jax.config.update(k, v)
        cc.reset_cache()
        jax.clear_caches()


@pytest.fixture(scope="module")
def recorded():
    """A chip trace of three calls of the small q4 cell of a program with
    layer scopes, the HLO text of the executable it ran, and what was read
    from them, recorded by ``record_scoped_trace.py`` on one TPU v5e."""
    from jax.profiler import ProfileData

    data = Path(__file__).resolve().parent / "data"
    raw = gzip.decompress((data / "q4-small-scoped.xplane.pb.gz").read_bytes())
    text = gzip.decompress((data / "q4-small-scoped.hlo.txt.gz").read_bytes()).decode()
    want = json.loads((data / "q4-small-scoped.summary.json").read_text())
    return ProfileData.from_serialized_xspace(raw), text, want


def test_recorded_scoped_trace_reduces_as_recorded(recorded):
    pd, text, want = recorded
    got = S.reduce_scopes(pd.planes, want["module"], want["device_ids"], want["calls"],
                          S.scope_map(text))
    assert got.ms == pytest.approx(want["ms"], rel=1e-9, abs=1e-12)
    assert got.collective_mb == want["collective_mb"] == 0.0  # one chip


def test_recorded_scoped_trace_adds_up(recorded):
    pd, text, want = recorded
    paths = S.scope_map(text)
    s = T.reduce_planes(pd.planes, want["module"], want["device_ids"], want["calls"])
    assert set(s.op_s) <= set(paths)  # the trace's op names are the text's
    got = S.reduce_scopes(pd.planes, want["module"], want["device_ids"],
                          want["calls"], paths)
    total = 1e3 * s.module_busy_s[0] / s.calls
    assert sum(got.ms.values()) == pytest.approx(total, rel=1e-6)
    # the insert's tenancy segment_max and scatter-add fusions are nearly all
    # of it, each in its sub-scope; the compiler-made scatter roots included
    assert got.ms["fold/tenancy"] + got.ms["fold/scatter"] > 0.9 * total
    assert got.layer_ms("sync") > 0 and got.layer_ms("read") > 0
    assert got.ms[S.UNSCOPED] < 0.05 * total
