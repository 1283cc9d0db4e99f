"""The dataplane's layer scopes (docs/observability.md §7): where each layer's
known work lands in the compiled program's op metadata.

Named ops only: the compiler splits some reductions and leaves a half
without metadata, which the benchmark's trace reduction puts down to its
first operand's layer.  Collectives over one device are dropped or kept
depending on the op, so the cross-device ops are pinned on four virtual
devices in a child process.
"""
import json
import os
import re
import subprocess
import sys
from pathlib import Path

import jax.numpy as jnp
import pytest

from repro.core import wcrdt as W
from repro.launch.mesh import make_data_mesh
from repro.launch.stream import (
    build_keyed_pipeline,
    build_pipeline,
    default_fold_schedule,
)
from repro.obs import DATAPLANE_LAYERS
from repro.streaming.generator import NexmarkConfig, generate_log
from repro.streaming.queries import make_q4, make_q7

ROOT = Path(__file__).resolve().parents[1]
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?(\S+) = ")
_OPCODE = re.compile(r"[\]})] ([a-z][\w-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$")


def named_ops(hlo_text: str) -> list[tuple[str, set, str]]:
    """``(opcode, opcodes of the fused computation, op_name)`` of every
    instruction of a compiled HLO text that carries an op_name."""
    bodies: dict[str, set] = {}
    current, ops = None, []
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head and " = " not in line:
            current = bodies.setdefault(head.group(1), set())
            continue
        if not _INSTR.match(line):
            continue
        opcode = _OPCODE.search(line.partition(" = ")[2])
        opcode = opcode.group(1) if opcode else ""
        if current is not None:
            current.add(opcode)
        name = _OP_NAME.search(line)
        calls = _CALLS.search(line) if opcode == "fusion" else None
        if name:
            ops.append((opcode, calls.group(1) if calls else None, name.group(1)))
    return [(op, bodies.get(c, set()) if c else set(), n) for op, c, n in ops]


def layers_of(op_name: str) -> list[str]:
    return [p for name in op_name.split(";") for p in name.split("/")[:-1]
            if p in DATAPLANE_LAYERS]


def layer(op_name: str) -> str | None:
    found = layers_of(op_name)
    return found[0] if found else None


def _events(n_dev=1, nb=8, epb=256):
    nx = NexmarkConfig(num_partitions=n_dev, num_batches=nb, events_per_batch=epb,
                       num_auctions=1000)
    return generate_log(nx)


def _replicated(make):
    def compiled():
        mesh = make_data_mesh(1)
        with mesh:
            pipe = build_pipeline(make(1, window_len=1000, num_slots=16), mesh, 4,
                                  n_windows=4)
            return pipe.lower(_events()).compile().as_text()
    return compiled


def _keyed():
    mesh = make_data_mesh(1)
    shards = W.KeyShards(1000, 1)
    with mesh:
        pipe = build_keyed_pipeline(mesh, shards, window_len=100, num_slots=16,
                                    n_windows=4)
        return pipe.lower(
            _events(), jnp.asarray(shards.key_table()),
            jnp.asarray(default_fold_schedule(1, 8)), jnp.ones(2, bool),
        ).compile().as_text()


PIPELINES = {"q4": _replicated(make_q4), "q7": _replicated(make_q7), "keyed": _keyed}


@pytest.fixture(scope="module")
def compiled():
    cache = {}

    def get(name):
        if name not in cache:
            cache[name] = named_ops(PIPELINES[name]())
        return cache[name]
    return get


@pytest.mark.parametrize("pipeline", sorted(PIPELINES))
def test_no_op_holds_two_layers(compiled, pipeline):
    ops = compiled(pipeline)
    assert ops
    for _, _, name in ops:
        assert len(set(layers_of(name))) <= 1, name


@pytest.mark.parametrize("pipeline", sorted(PIPELINES))
def test_scatters_fold(compiled, pipeline):
    """Every scatter, fused or not, folds: the insert's event scatter.  The
    slot tenancy is a dense per-slot max and scatters nothing."""
    scatters = [n for op, fused, n in compiled(pipeline)
                if op == "scatter" or "scatter" in fused]
    assert scatters and all(layer(n) == "fold" for n in scatters), scatters
    assert not any("/fold/tenancy/" in n for n in scatters), scatters
    assert any("/fold/scatter/" in n for n in scatters), scatters


@pytest.mark.parametrize("pipeline", ["q4", "q7"])
def test_replicated_sync_and_read(compiled, pipeline):
    ops = compiled(pipeline)
    gathers = [n for op, _, n in ops if op == "all-gather"]
    assert gathers and all("/sync/exchange/" in n for n in gathers), gathers
    merge = [n for _, _, n in ops if "/merge/" in n]
    assert merge and all(layer(n) == "sync" for n in merge), merge
    assert any(layer(n) == "read" for _, _, n in ops)


def test_keyed_read_gathers_and_watermark_sync(compiled):
    ops = compiled("keyed")
    gathers = [n for op, _, n in ops if op == "all-gather"]
    assert gathers and all(layer(n) == "read" for n in gathers), gathers
    pmax = [n for op, _, n in ops if op == "all-reduce"]
    assert pmax and all(layer(n) == "sync" for n in pmax), pmax


_CHILD = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import json
import jax.numpy as jnp
from repro.core import wcrdt as W
from repro.launch.mesh import make_data_mesh
from repro.launch.stream import build_keyed_pipeline, build_pipeline, default_fold_schedule
from repro.streaming.generator import NexmarkConfig, generate_log
from repro.streaming.queries import make_q4

S = 4
mesh = make_data_mesh(S)
log = generate_log(NexmarkConfig(num_partitions=S, num_batches=8,
                                 events_per_batch=256, num_auctions=1000))
shards = W.KeyShards(1000, S)
with mesh:
    q4 = build_pipeline(make_q4(S, window_len=1000, num_slots=16), mesh, 4, n_windows=4)
    keyed = build_keyed_pipeline(mesh, shards, window_len=100, num_slots=16, n_windows=4)
    texts = {
        "q4": q4.lower(log).compile().as_text(),
        "keyed": keyed.lower(log, jnp.asarray(shards.key_table()),
                             jnp.asarray(default_fold_schedule(S, 8)),
                             jnp.ones(2, bool)).compile().as_text(),
    }
print("TEXTS=" + json.dumps(texts))
"""


@pytest.mark.multidevice
def test_collectives_on_four_devices():
    """Across devices: the keyed shuffle's three all-to-alls, the delta
    sync's all-gathers, the keyed watermark pmax and the read's candidate
    gathers each lie in their own layer."""
    env = dict(os.environ, PYTHONPATH=str(ROOT / "src"))
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _CHILD], capture_output=True,
                       text=True, timeout=600, env=env)
    line = [ln for ln in r.stdout.splitlines() if ln.startswith("TEXTS=")]
    assert line, f"stdout={r.stdout[-2000:]}\nstderr={r.stderr[-2000:]}"
    texts = json.loads(line[0][len("TEXTS="):])
    by_op = {}
    for pipeline, text in texts.items():
        for op, _, n in named_ops(text):
            assert len(set(layers_of(n))) <= 1, n
            by_op.setdefault((pipeline, op.removesuffix("-start")), []).append(n)
    a2a = by_op[("keyed", "all-to-all")]
    assert len(a2a) >= 1 and all(layer(n) == "shuffle" for n in a2a), a2a
    assert all(layer(n) == "read" for n in by_op[("keyed", "all-gather")])
    assert all(layer(n) == "sync" for n in by_op[("keyed", "all-reduce")])
    q4 = by_op[("q4", "all-gather")]
    assert q4 and all("/sync/exchange/" in n for n in q4), q4


def test_documented_layers_are_the_programs():
    """docs/observability.md §7 lists the layers ``DATAPLANE_LAYERS`` holds,
    in its order."""
    doc = (ROOT / "docs" / "observability.md").read_text()
    section = doc.split("## §7", 1)[1].split("\n## ", 1)[0]
    rows = re.findall(r"^\| `(\w+)` \|", section, flags=re.M)
    assert tuple(rows) == DATAPLANE_LAYERS
