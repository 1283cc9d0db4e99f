"""A run with the timed path broken underneath comes out as not correct,
for each fault a cell can have: a fold that returns its state unchanged,
half of each batch left out, the exchange between chips left out, and an
answer altered where it is produced."""
import jax
import jax.numpy as jnp
import pytest

from chipbench.tests.small import CELLS, run_cell
from repro.core import wcrdt as W

_insert, _value, _topk = W.insert, W.window_value, W.shard_topk_read


def unchanged(spec, state, *args, **kwargs):
    return state


def half_batch(spec, state, partition, ts, mask, *args, **kwargs):
    keep = jnp.arange(mask.shape[0]) < mask.shape[0] // 2
    return _insert(spec, state, partition, ts, mask & keep, *args, **kwargs)


def altered_value(spec, state, wid):
    v, ok = _value(spec, state, wid)
    return v + 1.0, ok


def altered_topk(*args, **kwargs):
    (cnt, key), ok = _topk(*args, **kwargs)
    return (cnt + 1.0, key), ok


FAULTS = {
    "state_unchanged": [(W, "insert", unchanged)],
    "half_batch": [(W, "insert", half_batch)],
    "answer_altered": [(W, "window_value", altered_value), (W, "shard_topk_read", altered_topk)],
}
CASES = [(f, c) for f in FAULTS for c in CELLS]
# the exchange between chips exists only where a cell has more than one chip
CASES += [("no_exchange", "q5-zipf-4chip")]


@pytest.mark.parametrize("fault,cell", CASES)
def test_fault_is_not_correct(fault, cell, monkeypatch):
    if fault == "no_exchange":
        monkeypatch.setattr(jax.lax, "all_to_all", lambda x, *a, **k: x)
    else:
        for mod, name, fn in FAULTS[fault]:
            monkeypatch.setattr(mod, name, fn)
    res = run_cell(cell)
    assert not res["correct"], res["checks"]
    assert res["failed"] == res["attempted"] > 0
