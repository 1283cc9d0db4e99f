"""Each cell's control, the reference in the program's place one precision
down (or, where the cell's answers are small integers that every precision
holds, with the stated tie guarantee broken), comes out as not correct."""
import json

import pytest

from chipbench.harness import Runner, control_program
from chipbench.tests.small import small_cell

import jax

CONTROL = {"q4-1chip": "bf16", "q5-zipf-1chip": "bf16", "q5-zipf-4chip": "bf16",
           "q5-uniform-1chip": "ties"}


@pytest.mark.parametrize("cell", sorted(CONTROL))
def test_control_is_not_correct(cell):
    c = small_cell(cell)
    runner = Runner(c, jax.devices()[: c.chips])
    pool = runner.make_pool(3_000_000_011)
    win = runner.window(pool, 0.0, program=control_program(c, CONTROL[cell]))
    checks, failed = runner.check(pool, win)
    assert failed == len(win.calls) > 0
    assert any(v["value"] > v["limit"] for v in checks.values()), checks


def test_readings_separate_program_from_control(capsys):
    """``readings.py`` reads the program below each limit and its control
    above one, through the same set-up as a run."""
    from chipbench import readings
    from chipbench.tests.small import on_cpu

    with on_cpu():
        assert readings.main(["--workload", "q4-1chip", "--seeds", "2",
                              "--control-seeds", "1", "--seconds", "0.1"]) == 0
    summary = json.loads(capsys.readouterr().out.strip().splitlines()[-1])
    limits = summary["limits"]
    assert all(summary["lower"][k] <= limits[k] for k in limits)
    assert any(v > limits[k.split(":")[1]] for k, v in summary["upper"].items())
