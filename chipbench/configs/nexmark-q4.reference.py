"""Plain reference of Nexmark q4 (average bid price per category per
tumbling window), in numpy over the same host chunk the program was fed.

Imports nothing of the program.  The comparison takes every call of the
window and every device's replica:

* ``open_mismatch``: windows whose closed flag differs from the watermark
  rule (a window is closed once every partition's newest event has passed
  its end).  Exact, so the limit is 0.
* ``max_rel_err``: the largest relative gap between a closed window's
  average and the float64 group-by.  Limit 4e-4, set from the readings on
  one v5e (PERF.md): the program's float32 sums of ~2e4 prices read at most
  8.7e-6 over 12 seeds, the bfloat16 control at least 2.8e-3 over 3.
"""
import numpy as np
from ml_dtypes import bfloat16

from chipbench.nexmark import KIND_BID, closed_windows, read_windows

CHECKS = {"open_mismatch": ("sum", 0), "max_rel_err": ("max", 4e-4)}


def num_auctions(config: dict, chips: int) -> int:
    return config["num_auctions"]


def _group(config, traffic, chunk, price):
    first, n = read_windows(config, traffic)
    C = config["num_categories"]
    bid = chunk["valid"] & (chunk["kind"] == KIND_BID)
    wid = chunk["ts"][bid].astype(np.int64) // config["window_ms"] - first
    cat = chunk["category"][bid].astype(np.int64)
    inside = (wid >= 0) & (wid < n)
    cell = wid[inside] * C + cat[inside]
    sums = np.bincount(cell, weights=price[bid][inside], minlength=n * C)
    cnts = np.bincount(cell, minlength=n * C)
    return (sums / np.maximum(cnts, 1)).reshape(n, C)


def expected(config: dict, traffic: dict, chunk: dict) -> dict:
    first, n = read_windows(config, traffic)
    starts = (first + np.arange(n)) * config["hop_ms"]
    return {
        "oks": closed_windows(chunk["ts"], starts, config["window_ms"]),
        "vals": _group(config, traffic, chunk, chunk["price"].astype(np.float64)),
    }


def control(config: dict, traffic: dict, chunk: dict, chips: int) -> tuple:
    """The reference in the program's place one precision down: bfloat16
    prices and averages, float32 accumulation (as the MXU would)."""
    want = expected(config, traffic, chunk)
    price = chunk["price"].astype(bfloat16).astype(np.float32).astype(np.float64)
    vals = _group(config, traffic, chunk, price).astype(np.float32)
    vals = vals.astype(bfloat16).astype(np.float32)
    oks = want["oks"].astype(np.float32)
    return np.stack([oks] * chips), np.stack([vals] * chips)


CONTROLS = {"bf16": control}


def compare(config: dict, got: tuple, want: dict) -> dict:
    """Per-call numbers for one call: ``got`` is the program's emitted
    ``(oks [S, n], vals [S, n, C])``."""
    oks, vals = np.asarray(got[0]), np.asarray(got[1], np.float64)
    closed = want["oks"]
    mismatch = int(((oks > 0.5) != closed[None]).sum())
    ref = want["vals"][closed]
    gap = np.abs(vals[:, closed] - ref[None]) / np.maximum(np.abs(ref[None]), 1e-30)
    return {"open_mismatch": mismatch, "max_rel_err": float(gap.max(initial=0.0))}
