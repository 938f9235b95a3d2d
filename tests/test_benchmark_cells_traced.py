"""The traced half of ``test_benchmark_cells.py``: every cell of
``BENCHMARK.json`` rehearsed on the CPU with ``--trace 1``, every
per-layer metric of its line above 0. A file of its own so that
``--dist loadfile`` gives it a worker of its own."""

import pytest

from tests.test_benchmark_cells import CELLS, rehearse


@pytest.mark.parametrize("trace", [1], ids=["traced"])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses(cell, trace):
    rehearse(cell, trace)
