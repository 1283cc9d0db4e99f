"""Plain reference of keyed Nexmark q5 (hot items over 10 s windows sliding
by 5 s), in numpy over the same host chunk the program was fed.

Imports nothing of the program.  Per window it counts that window's bids
with ``np.unique`` and takes the largest count, ties to the lowest auction
id.  The program emits ``(count, id)`` as float32, so the reference's pair
is cast to float32 before the exact comparison.  The comparison takes every
call of the window and every chip:

* ``open_mismatch``: windows whose closed flag differs from the watermark
  rule.  Exact, limit 0.
* ``wrong_hot_items``: closed windows whose ``(count, id)`` differs from the
  reference.  Exact, limit 0.
"""
import numpy as np
from ml_dtypes import bfloat16

from chipbench.nexmark import KIND_BID, closed_windows, read_windows

CHECKS = {"open_mismatch": ("sum", 0), "wrong_hot_items": ("sum", 0)}


def num_auctions(config: dict, chips: int) -> int:
    return config["keys_per_chip"] * chips


def _hot(config, traffic, chunk, count_dtype, lowest_on_ties=True):
    first, n = read_windows(config, traffic)
    bid = chunk["valid"] & (chunk["kind"] == KIND_BID)
    ts, auction = chunk["ts"][bid], chunk["auction"][bid]
    out = np.zeros((n, 2), np.float32)
    for i in range(n):
        start = (first + i) * config["hop_ms"]
        m = (ts >= start) & (ts < start + config["window_ms"])
        if not m.any():
            continue
        ids, cnts = np.unique(auction[m], return_counts=True)
        cnts = cnts.astype(count_dtype).astype(np.float64)
        top = cnts.max()
        hot = ids[cnts == top]
        out[i] = (top, hot[0] if lowest_on_ties else hot[-1])
    return out


def expected(config: dict, traffic: dict, chunk: dict) -> dict:
    first, n = read_windows(config, traffic)
    starts = (first + np.arange(n)) * config["hop_ms"]
    return {
        "oks": closed_windows(chunk["ts"], starts, config["window_ms"]),
        "vals": _hot(config, traffic, chunk, np.int64),
    }


def control(config: dict, traffic: dict, chunk: dict, chips: int) -> tuple:
    """The reference in the program's place one precision down: bid counts
    held in bfloat16."""
    want = expected(config, traffic, chunk)
    vals = _hot(config, traffic, chunk, bfloat16)
    return np.stack([want["oks"].astype(np.float32)] * chips), np.stack([vals] * chips)


def control_ties(config: dict, traffic: dict, chunk: dict, chips: int) -> tuple:
    """The reference in the program's place with the tie guarantee broken:
    equal counts go to the highest auction id.  Under uniform keys every
    count is a small integer that bfloat16 holds exactly, so this is the
    control that such traffic can fail."""
    want = expected(config, traffic, chunk)
    vals = _hot(config, traffic, chunk, np.int64, lowest_on_ties=False)
    return np.stack([want["oks"].astype(np.float32)] * chips), np.stack([vals] * chips)


CONTROLS = {"bf16": control, "ties": control_ties}


def compare(config: dict, got: tuple, want: dict) -> dict:
    """Per-call numbers for one call: ``got`` is the program's emitted
    ``(oks [S, n], vals [S, n, 2])``."""
    oks, vals = np.asarray(got[0]), np.asarray(got[1], np.float32)
    closed = want["oks"]
    mismatch = int(((oks > 0.5) != closed[None]).sum())
    wrong = (vals[:, closed] != want["vals"][closed][None]).any(axis=-1)
    return {"open_mismatch": mismatch, "wrong_hot_items": int(wrong.sum())}
