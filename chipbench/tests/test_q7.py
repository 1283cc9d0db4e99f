"""The q7 cell at a small size on the CPU: the program is correct against
its plain reference, the reference's control and planted faults are not,
the reference keeps its tie and duplicate rules, and the compiled program
names the TopK fold's scopes."""
import contextlib
import io
import json
from types import SimpleNamespace as NS
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import harness, nexmark, run
from chipbench import scopes as S
from chipbench.harness import Cell, Runner, control_program
from repro.core import wcrdt as W

CELL = "q7-1chip"
# rate 1,000 events/s stretches 4,096 events over 4.1 s of event time, so a
# call of 8 batches reads 4 windows of 10 s and closes 3
TRAFFIC = {"events_per_batch": 4096, "batches_per_call": 8,
           "rate_per_partition": 1000.0, "pool_chunks": 2}


def small_cell(name: str) -> Cell:
    cell = Cell(name)
    cell.traffic |= TRAFFIC
    return cell


def run_small(seed: int = 3_000_000_019) -> dict:
    out = io.StringIO()
    with mock.patch.object(harness, "Cell", small_cell), \
            mock.patch.object(run, "tpu_devices", lambda jax, chips: jax.devices()[:chips]), \
            contextlib.redirect_stdout(out):
        rc = run.main(["--workload", CELL, "--seed", str(seed), "--seconds", "0.3",
                       "--trace", "0"])
    assert rc == 0
    return json.loads(out.getvalue().strip().splitlines()[-1])


def test_program_matches_reference():
    res = run_small()
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0
    assert set(res["checks"]) == {"open_mismatch", "wrong_top"}


def test_bf16_control_is_not_correct():
    c = small_cell(CELL)
    runner = Runner(c, jax.devices()[:1])
    pool = runner.make_pool(3_000_000_011)
    win = runner.window(pool, 0.0, program=control_program(c, "bf16"))
    checks, failed = runner.check(pool, win)
    assert failed == len(win.calls) > 0
    assert checks["wrong_top"]["value"] > 0 and checks["open_mismatch"]["value"] == 0


_insert, _value = W.insert, W.window_value


def unchanged(spec, state, *args, **kwargs):
    return state


def half_batch(spec, state, partition, ts, mask, *args, **kwargs):
    keep = jnp.arange(mask.shape[0]) < mask.shape[0] // 2
    return _insert(spec, state, partition, ts, mask & keep, *args, **kwargs)


def eighth_dropped(spec, state, wid):
    (vals, ids), ok = _value(spec, state, wid)
    return (vals.at[-1].set(-jnp.inf), ids.at[-1].set(0)), ok


@pytest.mark.parametrize("fault", [("insert", unchanged), ("insert", half_batch),
                                   ("window_value", eighth_dropped)],
                         ids=["state_unchanged", "half_batch", "eighth_pair_dropped"])
def test_fault_is_not_correct(fault, monkeypatch):
    monkeypatch.setattr(W, *fault)
    res = run_small()
    assert not res["correct"], res["checks"]
    assert res["failed"] == res["attempted"] > 0


def test_reference_ties_to_the_higher_id_and_collapses_repeats():
    c = small_cell(CELL)
    price = [5.0, 7.0, 7.0, 7.0, 7.0, 1.0, 0.0, 1.4e-45, -0.0, 9.0]
    auction = [1, 3, 8, 3, 2, 4, 6, 6, 6, 9]
    ts = [1_000] * 9 + [15_000]
    chunk = {"ts": np.array([ts], np.int32),
             "kind": np.full((1, 10), nexmark.KIND_BID, np.int32),
             "price": np.array([price], np.float32),
             "auction": np.array([auction], np.uint32),
             "valid": np.ones((1, 10), bool)}
    want = c.ref.expected(c.config, c.traffic, chunk)
    k = c.config["k"]
    w0 = want["vals"][0]
    # the tie at 7.0 goes to the higher id; (7.0, 3) bid twice counts once;
    # the subnormal ranks above 0.0 and 0.0 above -0.0
    assert w0[k:].tolist() == [8, 3, 2, 1, 4, 6, 6, 6]
    assert w0[:k].view(np.uint32).tolist() == np.array(
        [7, 7, 7, 5, 1, 1.4e-45, 0.0, -0.0], np.float32).view(np.uint32).tolist()
    w1 = want["vals"][1]
    assert w1[:2].tolist() == [9.0, -np.inf] and w1[k:k + 2].tolist() == [9, 0]


def test_prefilter_and_setjoin_readers():
    got = S.Scoped({"fold/scatter/prefilter": 1.5, "fold/scatter/setjoin": 0.25,
                    "fold/scatter/branch_0_fun/exact": 4.0, "fold/tenancy": 9.0,
                    "read/prefilter": 7.0}, 0.0)
    ctx = NS(cell=None)
    with mock.patch.object(S, "read", lambda ctx: got):
        assert Cell(CELL).reader("prefilter_ms").read(ctx) == 1.5
        assert Cell(CELL).reader("setjoin_ms").read(ctx) == 0.25
    with mock.patch.object(S, "read", lambda ctx: S.Scoped({"fold/scatter": 2.0}, 0.0)):
        assert Cell(CELL).reader("prefilter_ms").read(ctx) is None
    with mock.patch.object(S, "read", lambda ctx: None):
        assert Cell(CELL).reader("setjoin_ms").read(ctx) is None


def test_compiled_cell_names_the_topk_scopes():
    c = small_cell(CELL)
    paths = set(S.scope_map(S.compiled_text(c, jax.devices()[:1])).values())
    assert {"fold/scatter/prefilter", "fold/scatter/setjoin"} <= paths
    assert any(p.startswith("fold/scatter/") and p.endswith("/exact") for p in paths)
