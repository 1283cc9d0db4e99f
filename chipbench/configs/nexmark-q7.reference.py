"""Plain reference of Nexmark q7 (the highest bids of each tumbling window),
in numpy over the same host chunk the program was fed.

Imports nothing of the program.  Per window it sorts the window's bids on
(price, auction), the price ordered by its bits (a subnormal above 0.0, 0.0
above -0.0), collapses repeated pairs, and keeps the k highest, descending,
padded with (-inf, 0): ``[prices (k), auction ids as float32 (k)]``, the
2k numbers the program emits per window.  The comparison takes every call of
the window and every replica:

* ``open_mismatch``: windows whose closed flag differs from the watermark
  rule.  Exact, limit 0.
* ``wrong_top``: (replica, closed window) rows whose 2k numbers differ from
  the reference in any bit.  Exact, limit 0.
"""
import numpy as np
from ml_dtypes import bfloat16

from chipbench.nexmark import KIND_BID, closed_windows, read_windows

CHECKS = {"open_mismatch": ("sum", 0), "wrong_top": ("sum", 0)}


def num_auctions(config: dict, chips: int) -> int:
    return config["num_auctions"]


def _ordered(price: np.ndarray) -> np.ndarray:
    """The price's bits as a uint64 that sorts as the price does."""
    bits = np.asarray(price, np.float32).view(np.uint32).astype(np.uint64)
    return np.where(bits >> 31, ~bits & 0xFFFFFFFF, bits | 0x80000000)


def _top(config, traffic, chunk, price):
    first, n = read_windows(config, traffic)
    k = config["k"]
    bid = chunk["valid"] & (chunk["kind"] == KIND_BID)
    ts, price = chunk["ts"][bid], np.asarray(price, np.float32)[bid]
    pair = _ordered(price) << 32 | chunk["auction"][bid].astype(np.uint64)
    out = np.zeros((n, 2 * k), np.float32)
    out[:, :k] = -np.inf
    for i in range(n):
        start = (first + i) * config["hop_ms"]
        m = np.flatnonzero((ts >= start) & (ts < start + config["window_ms"]))
        top, at = np.unique(pair[m], return_index=True)  # ascending, distinct
        top, at = top[::-1][:k], m[at[::-1][:k]]
        out[i, :len(top)] = price[at]
        out[i, k:k + len(top)] = (top & 0xFFFFFFFF).astype(np.float32)
    return out


def expected(config: dict, traffic: dict, chunk: dict) -> dict:
    first, n = read_windows(config, traffic)
    starts = (first + np.arange(n)) * config["hop_ms"]
    return {
        "oks": closed_windows(chunk["ts"], starts, config["window_ms"]),
        "vals": _top(config, traffic, chunk, chunk["price"]),
    }


def control(config: dict, traffic: dict, chunk: dict, chips: int) -> tuple:
    """The reference in the program's place one precision down: prices
    rounded to bfloat16."""
    want = expected(config, traffic, chunk)
    price = chunk["price"].astype(bfloat16).astype(np.float32)
    vals = _top(config, traffic, chunk, price)
    return np.stack([want["oks"].astype(np.float32)] * chips), np.stack([vals] * chips)


CONTROLS = {"bf16": control}


def compare(config: dict, got: tuple, want: dict) -> dict:
    """Per-call numbers for one call: ``got`` is the program's emitted
    ``(oks [S, n], vals [S, n, 2k])``."""
    oks, vals = np.asarray(got[0]), np.asarray(got[1], np.float32)
    closed = want["oks"]
    mismatch = int(((oks > 0.5) != closed[None]).sum())
    ref = want["vals"][closed].view(np.uint32)
    wrong = (vals[:, closed].view(np.uint32) != ref[None]).any(axis=-1)
    return {"open_mismatch": mismatch, "wrong_top": int(wrong.sum())}
