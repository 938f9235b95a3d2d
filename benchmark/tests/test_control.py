"""Each cell's comparison passes the reference's own answers and fails
the control's, at a size a test run can hold (the chip runs are at the
cell's own size: PERF.md section 2)."""

import pytest

from benchmark import contract
from benchmark.control import control

CELLS = [w["name"] for w in contract.load_benchmark()["workloads"]]


@pytest.mark.parametrize("seed", [1, 2_147_483_659, 77])
@pytest.mark.parametrize("cell", CELLS)
def test_control_comes_out_not_correct(cell, seed):
    out = control(cell, seed, small=True)
    assert out["sound_wrong"] == 0
    assert out["control_wrong"] > 0
    assert out["passed"]
