"""What a run prints: the set-up split and memory on standard error, and the
result's metrics and device, each metric from its reader in ``metrics/``."""
from __future__ import annotations

from types import SimpleNamespace

import numpy as np

from chipbench import trace_reduce
from chipbench.harness import log, peaks


def log_setup(runner, setup_s: float, cache: str) -> None:
    parts = " ".join(f"{k}={v}" for k, v in runner.split.items())
    log(f"setup_s={setup_s} split: {parts} (compile cache {cache})")
    m = runner.memory
    need = (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes + m.generated_code_size_in_bytes
            - m.alias_size_in_bytes)
    hbm = peaks().get(runner.devices[0].device_kind, {}).get("hbm_bytes")
    share = f"{100.0 * need / hbm}% of the chip's {hbm:.0f}" if hbm else "no peak known"
    for d in runner.devices:
        log(f"memory {d}: the dataplane program holds {need} bytes "
            f"(arguments {m.argument_size_in_bytes}, temporaries "
            f"{m.temp_size_in_bytes}), {share}")


def log_calls(win) -> None:
    """How the window's call times spread, and where the slow ones spent
    their time (host phases feed, dispatch, emit)."""
    t = np.array([c.seconds for c in win.calls]) * 1e3
    ph = np.array([c.phases for c in win.calls]) * 1e3
    q = np.percentile(t, [50, 95, 99])
    slow = t > q[1]
    log(f"calls ms: mean {t.mean()} p50 {q[0]} p95 {q[1]} p99 {q[2]} max {t.max()}; "
        f"{int(slow.sum())} above p95 hold {t[slow].sum() - q[1] * slow.sum()} ms "
        f"beyond it; phase means feed/dispatch/emit {ph.mean(axis=0).tolist()}, "
        f"of the slow calls {ph[slow].mean(axis=0).tolist() if slow.any() else []}; "
        f"slowest (index, ms): {[(int(i), float(t[i])) for i in np.argsort(t)[::-1][:5]]}")


def result(runner, win, setup_s: float, peak: int, trace: bool) -> dict:
    """``metrics`` and ``device`` (and ``breakdown`` when traced)."""
    cell, d = runner.cell, runner.devices[0]
    summary = None
    if trace:
        summary = trace_reduce.reduce(win.trace_file, runner.module,
                                      [x.id for x in runner.devices], len(win.traced))
    ctx = SimpleNamespace(cell=cell, window=win, setup_s=setup_s, trace=summary)
    metrics = {}
    for m in cell.per_layer if trace else cell.end_to_end:
        value = cell.reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {"platform": d.platform, "kind": d.device_kind,
              "count": len(runner.devices), "memory_peak_bytes": peak}
    out = {"metrics": metrics, "device": device}
    if summary is not None:
        device["busy_s"] = summary.mean_over_devices(summary.busy_s)
        device["window_s"] = summary.window_s
        out["breakdown"] = summary.breakdown()
        log(f"trace: {summary.calls} calls, window {summary.window_s} s, "
            f"busy {device['busy_s']} s per chip")
    return out
