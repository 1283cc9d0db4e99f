"""Device time per program layer, and the bytes the collectives delivered,
read from a traced run.

The dataplane names its layers with ``jax.named_scope``s: ``shuffle``,
``fold``, ``sync`` and ``read``.  A scope travels as op metadata
(``metadata={op_name="jit(node_fn)/.../fold/scatter/..."}``) of the compiled
executable, not in the trace, so the executable's own HLO text maps each
instruction name that the trace's ``XLA Ops`` events carry to a scope path:

- the layer is the first component of the op_name that is a layer name, and
  the path runs from it through the scopes nested in it (``fold/scatter``),
  leaving out transformations (``vmap()``, ``jit(_where)``, ``while``) and
  the primitive's own name;
- a fusion carries its root's metadata, so it counts where its root does;
  where that names no layer (the compiler made the root), it counts where
  the root, or else the first instruction, of its fused computation does;
- an instruction whose op_name names no layer inherits the layer of the
  first of its operands' producers that has one, which puts the copies,
  bitcasts and layout changes that the compiler inserts, and the loops it
  builds around them, where the value they move was made; a constant passes
  on nothing, since the compiler shares one constant among ops of any layer;
- an instruction still without a layer in a computation that a loop or a
  fusion runs counts where that loop or fusion does (the body of a relayout
  loop the compiler built carries no metadata at all);
- one still without a layer counts where its first user does (the
  zero-filled buffer of such a loop);
- a loop of the program's own whose op_name names no layer (a scan), and the
  state it starts from, take no layer by these rules: a scan holds every
  layer it runs;
- what is still unattributed is ``unscoped``.

Each layer's time is the sum of the ops' self times (``trace_reduce``), so a
loop and its body are not counted twice, over the ops that ran inside the
entry's executable; the layers and ``unscoped`` tile ``dataplane_ms``.  The
benchmark keeps its own copy of the layer names, as it keeps ``HOST_PHASES``:
it takes nothing from the program but the system under test.  A program
without the scopes (an older one) gives no layer time, and its readers give
nothing.
"""
from __future__ import annotations

import re
from collections import defaultdict
from dataclasses import dataclass

import numpy as np

from chipbench import trace_reduce as T

LAYERS = ("shuffle", "fold", "sync", "read")
UNSCOPED = "unscoped"
# name stack components that are transformations, not scopes
_TRANSFORMS = {"while", "body", "cond", "closed_call", "checkpoint", "remat",
               "shard_map", "pjit"}
_INSTR = re.compile(r"^\s*(?:ROOT\s+)?%?([^\s=]+) = (.*)$")
_OP_NAME = re.compile(r'metadata=\{[^{}]*?op_name="([^"]*)"')
_OPERAND = re.compile(r"%([^\s,()]+)")
_CALLS = re.compile(r"\bcalls=%?([^\s,]+)")
_CALLEE = re.compile(r"\b(?:calls|body|condition)=%?([^\s,]+)")
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?(\S+) \(.*\{$")
_ARRAY = re.compile(r"\b(pred|bf16|[suf]\d+|c64|c128)\[([\d,]*)\]")
_BITS = {"pred": 8, "bf16": 16}
# a location of the lowered program that names a scope: loc("name"(child))
_NAME_LOC = re.compile(r'loc\("([^"]+)"\(')
_SYNC_COLLECTIVE = re.compile(
    r"^(all-gather|all-to-all|all-reduce|collective-permute|reduce-scatter)$")


def scope_path(op_name: str) -> str | None:
    """The scope path of one op_name (``a;b`` where the compiler merged
    ops: the first that names a layer), or ``None``."""
    for name in op_name.split(";"):
        parts = name.split("/")
        for i, part in enumerate(parts[:-1]):
            if part in LAYERS:
                nested = [p for p in parts[i + 1:-1]
                          if "(" not in p and p not in _TRANSFORMS]
                return "/".join([part, *nested])
    return None


def _after_shape(rest: str) -> str:
    """``opcode(operands), ...`` of an instruction's right-hand side, whose
    shape (a tuple is parenthesised and holds spaces) comes first."""
    if rest.startswith("("):
        depth = 0
        for i, ch in enumerate(rest):
            depth += (ch == "(") - (ch == ")")
            if depth == 0:
                return rest[i + 1:].strip()
    return rest.partition(" ")[2]


def _operands(call: str) -> list[str]:
    """The operands of ``opcode(operands), attributes``: the ``%name``s
    inside the opcode's own parentheses."""
    start = call.find("(")
    if start < 0:
        return []
    depth = 0
    for i in range(start, len(call)):
        depth += (call[i] == "(") - (call[i] == ")")
        if depth == 0:
            return _OPERAND.findall(call, start, i)
    return []


@dataclass
class _Instr:
    name: str
    computation: str | None
    path: str | None  # its own, from its op_name
    operands: list
    fused: str | None  # the computation a fusion calls
    callees: list  # every computation it runs
    root: bool
    constant: bool
    scan: bool  # a loop of the program's own that names no layer


def _parse(hlo_text: str) -> list[_Instr]:
    out, current = [], None
    for line in hlo_text.splitlines():
        head = _COMPUTATION.match(line)
        if head and " = " not in line:
            current = head.group(1)
            continue
        m = _INSTR.match(line)
        if not m:
            continue
        name, rest = m.groups()
        meta = _OP_NAME.search(rest)
        call = _after_shape(rest)
        fused = _CALLS.search(call)
        path = scope_path(meta.group(1)) if meta else None
        out.append(_Instr(name, current, path, _operands(call),
                          fused.group(1) if fused else None, _CALLEE.findall(call),
                          line.lstrip().startswith("ROOT"), call.startswith("constant("),
                          bool(meta) and path is None and call.startswith("while(")))
    return out


def scope_map(hlo_text: str) -> dict:
    """Instruction name -> scope path (or ``unscoped``) for every
    instruction of a compiled executable's HLO text, by the rules above.
    Computations are printed before their callers and operands before their
    users, so each rule is one pass."""
    instrs = _parse(hlo_text)
    path = {i.name: i.path for i in instrs}
    passed: dict[str, str | None] = {}  # what each instruction passes on
    computed: dict[str, str] = {}  # computation -> its root's, or first, path
    # a scan's loop and the state it starts from hold every layer it runs
    scans = {i.name for i in instrs if i.scan}
    scans |= {o for i in instrs if i.scan for o in i.operands}
    for i in instrs:  # fusions, then operands
        if path[i.name] is None and i.fused:
            path[i.name] = computed.get(i.fused)
        if path[i.name] is None and i.name not in scans:
            path[i.name] = next((passed[o] for o in i.operands if passed.get(o)), None)
        p = path[i.name]
        if p is not None and i.computation is not None and (
                i.root or i.computation not in computed):
            computed[i.computation] = p
        passed[i.name] = None if i.constant else p
    caller = {c: i.name for i in instrs for c in i.callees}
    order = list(dict.fromkeys(i.computation for i in instrs))
    rank = {c: k for k, c in enumerate(order)}
    for i in sorted(instrs, key=lambda i: -rank[i.computation]):  # callers first
        if path[i.name] is None and i.computation in caller:
            path[i.name] = path[caller[i.computation]]
    users: dict[str, str] = {}
    for i in reversed(instrs):  # users, last to first
        if path[i.name] is None and i.name not in scans:
            path[i.name] = users.get(i.name)
        if path[i.name] is not None and not i.constant:
            for o in i.operands:
                users[o] = path[i.name]
    return {name: p or UNSCOPED for name, p in path.items()}


def layer_of(path: str) -> str:
    return path.partition("/")[0]


def result_bytes(text: str) -> int:
    """Bytes of the result shape of an op event, whose name is the whole
    HLO instruction (a tuple's elements summed)."""
    rest = text.partition(" = ")[2]
    shape = rest[:len(rest) - len(_after_shape(rest))]
    total = 0
    for dtype, dims in _ARRAY.findall(shape):
        bits = _BITS.get(dtype) or int(dtype.lstrip("sufc"))
        total += bits // 8 * int(np.prod([int(d) for d in dims.split(",") if d]))
    return total


def delivers(opcode: str) -> bool:
    """A collective op whose result is what the chip received: a
    synchronous one, or the ``-done`` half of an asynchronous one."""
    if _SYNC_COLLECTIVE.match(opcode):
        return True
    return opcode.endswith("-done") and T.is_collective(opcode)


@dataclass
class Scoped:
    """Per-call milliseconds of each scope path, mean over chips, and the
    megabytes per call the collectives delivered, mean over chips."""

    ms: dict
    collective_mb: float

    def layer_ms(self, layer: str) -> float:
        return sum(v for k, v in self.ms.items() if layer_of(k) == layer)


def reduce_scopes(planes, module: str | None, device_ids, calls: int,
                  scopes: dict) -> Scoped:
    """The traced window's device time per scope path and its collectives'
    delivered bytes.  The window and the ops counted are those of
    ``trace_reduce.reduce_planes``; layer time counts the ops that ran
    inside the ``module``'s executable."""
    planes = list(planes)
    host = T._host_spans(planes)
    if "feed" not in host or "emit" not in host:
        raise ValueError("the trace holds no feed/emit spans of the harness")
    lo, hi = float(host["feed"][0, 0]), float(host["emit"][-1, 1])
    devs = T._device_planes(planes, device_ids)
    if not devs:
        raise ValueError(f"the trace holds no plane of devices {list(device_ids)}")
    seconds, delivered = defaultdict(float), 0.0
    for plane in devs:
        lines = {line.name: line for line in plane.lines}
        mods = T.union(np.asarray(
            [(s, e) for name, s, e in T._events(lines.get(T.MODULES_LINE))
             if module is None or name.split("(")[0] == module]).reshape(-1, 2))
        named = []
        for text, s, e in T._events(lines.get(T.OPS_LINE)):
            if e <= lo or s >= hi:
                continue
            s, e = max(s, lo), min(e, hi)
            name, opcode = T.hlo_parts(text)
            if delivers(opcode):
                delivered += result_bytes(text) / len(devs)
            mid = 0.5 * (s + e)
            k = int(np.searchsorted(mods[:, 0], mid, side="right")) - 1
            if k >= 0 and mods[k, 1] > mid:
                named.append((s, e, name))
        for name, sec in T.self_times(named).items():
            seconds[scopes.get(name, UNSCOPED)] += sec / len(devs)
    n = max(calls, 1)
    return Scoped({k: 1e3 * v / n for k, v in seconds.items()}, delivered / 1e6 / n)


def lowered_has_scopes(lowered) -> bool:
    """Whether the program's lowering names any layer in its name stacks.
    A location also names each Python frame of the op's traceback, by its
    function's name and without a ``/`` (a reader's own ``read``): those do
    not count."""
    names = _NAME_LOC.findall(lowered.as_text(debug_info=True))
    return any("/" in name and scope_path(name + "/op") for name in names)


def has_scopes(hlo_text: str) -> bool:
    return any(scope_path(m) for m in _OP_NAME.findall(hlo_text))


def compiled_text(cell, devices) -> str | None:
    """The HLO text of the executable a run of ``cell`` on ``devices``
    executes, or ``None`` where the program names no layer.

    The entry is built and compiled again as ``harness.Runner.build`` does;
    the persistent cache gives back the executable that ran.  JAX keys that
    cache without debug information, so where it holds the same program
    compiled without scopes (an older program's), the text is compiled again
    past the cache: the instructions and their names are the same."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    from chipbench import nexmark

    entry = cell.entry.build(cell.config, cell.traffic, devices, cell.num_auctions)
    chunk = nexmark.make_chunk_fn(cell.traffic, cell.num_auctions, cell.partitions)(
        np.int32(0))

    def compile_():
        lowered = entry.fn.lower(*entry.place(chunk), *entry.static)
        if not lowered_has_scopes(lowered):
            return None
        return lowered.compile().as_text()

    text = compile_()
    if text is None or has_scopes(text):
        return text
    # JAX keeps the lowering with the executable it got: drop both
    jax.clear_caches()
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    try:
        return compile_()
    finally:
        jax.config.update("jax_enable_compilation_cache", was)
        cc.reset_cache()


_READ: dict = {}


def read(ctx) -> Scoped | None:
    """The reading of this run's trace, made once per trace and logged on
    standard error; ``None`` where the run was not traced.  Its ``ms`` is
    empty where the program names no layer."""
    win = ctx.window
    if ctx.trace is None or not win.trace_file:
        return None
    if win.trace_file not in _READ:
        _READ[win.trace_file] = _read(ctx)
    return _READ[win.trace_file]


def _read(ctx) -> Scoped:
    import jax
    from jax.profiler import ProfileData

    from chipbench.harness import log

    cell, t = ctx.cell, ctx.trace
    devices = jax.devices()[:cell.chips]
    text = compiled_text(cell, devices)
    first = (text or "").split("\n", 1)[0]
    module = first.split()[1].rstrip(",") if first.startswith("HloModule") else None
    planes = ProfileData.from_file(ctx.window.trace_file).planes
    paths = scope_map(text or "")
    got = reduce_scopes(planes, module, [d.id for d in devices], t.calls, paths)
    log(f"collectives delivered {got.collective_mb} MB per call")
    if text is None:
        got.ms = {}
        log("scopes: the program names no dataplane layer; no layer time")
        return got
    missing = [n for n in t.op_s if n not in paths]
    if missing:
        log(f"scopes: {len(missing)} traced ops not in the executable's text, "
            f"{1e3 * sum(t.op_s[n] for n in missing) / max(t.calls, 1)} ms per call")
    dataplane = 1e3 * t.mean_over_devices(t.module_busy_s) / max(t.calls, 1)
    layers = {layer: got.layer_ms(layer) for layer in (*LAYERS, UNSCOPED)}
    log(f"scopes ms per call: {dict(sorted(got.ms.items(), key=lambda kv: -kv[1]))}")
    log(f"layers ms per call: {layers}; sum {sum(layers.values())} of "
        f"dataplane_ms {dataplane}")
    return got
