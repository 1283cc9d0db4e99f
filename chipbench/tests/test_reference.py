"""Each configuration's plain reference equals the program's output at a
small size, for every cell (keyed q5 also on four virtual devices), through
the whole run: feed, entry, emit and the comparison that decides
``correct``."""
import numpy as np
import pytest

from chipbench import nexmark
from chipbench.harness import Cell
from chipbench.tests.small import CELLS, run_cell, small_cell


@pytest.mark.parametrize("cell", CELLS)
def test_program_matches_reference(cell):
    res = run_cell(cell)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert list(res)[-1] == "checks"
    assert res["device"]["count"] == Cell(cell).chips


@pytest.mark.parametrize("cell", CELLS)
def test_reference_closes_windows(cell):
    """The small cells still close windows, so the comparison has work."""
    c = small_cell(cell)
    chunk = nexmark.make_chunk_fn(c.traffic, c.num_auctions, c.partitions)(np.int32(5))
    want = c.ref.expected(c.config, c.traffic, dict(zip(nexmark.FIELDS, map(np.asarray, chunk))))
    assert 2 <= want["oks"].sum() < len(want["oks"])
    assert np.all(want["vals"][want["oks"]][..., 0] > 0)


def test_q4_reference_is_a_group_by():
    c = small_cell("q4-1chip")
    rng = np.random.default_rng(0)
    ts = np.array([[100, 9_999, 10_000, 25_000, 40_000]], np.int32)
    chunk = {"ts": ts, "kind": np.full_like(ts, nexmark.KIND_BID),
             "category": np.array([[0, 0, 1, 1, 4]], np.int32),
             "price": np.array([[1.0, 3.0, 5.0, 7.0, 9.0]], np.float32),
             "valid": np.ones_like(ts, bool), "auction": rng.integers(0, 9, ts.shape)}
    want = c.ref.expected(c.config, c.traffic, chunk)
    assert want["oks"][:4].tolist() == [True, True, True, True]
    assert want["vals"][0, 0] == 2.0 and want["vals"][1, 1] == 5.0
    assert want["vals"][2, 1] == 7.0 and want["vals"][3].sum() == 0.0


def test_q5_reference_ties_to_the_lowest_id():
    c = small_cell("q5-uniform-1chip")
    ts = np.array([[1_000, 2_000, 3_000, 4_000, 6_000, 7_000]], np.int32)
    chunk = {"ts": ts, "kind": np.full_like(ts, nexmark.KIND_BID),
             "auction": np.array([[9, 4, 9, 4, 7, 7]], np.uint32),
             "valid": np.ones_like(ts, bool)}
    want = c.ref.expected(c.config, c.traffic, chunk)
    # window 0 = [0, 10 s): 9, 4 and 7 twice each -> 4; window 1 = [5, 15 s): 7
    assert want["vals"][0].tolist() == [2.0, 4.0]
    assert want["vals"][1].tolist() == [2.0, 7.0]
