"""``BENCHMARK.json`` against the files it names: what ``selfcheck.py``
checks before a chip call, held by a test, one case a claim."""

import importlib
import json
import os

import pytest

from benchmark import contract, selfcheck

BENCH = contract.load_benchmark()
HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CELLS = {w["name"] for w in BENCH["workloads"]}
METRICS = [(g, f, m) for g, f in (("end_to_end", "end_to_end"),
                                  ("per_layer", "layer_metrics"))
           for m in BENCH[g]]


def test_every_file_the_index_names_is_there():
    assert selfcheck.check_files(BENCH) == []


@pytest.mark.parametrize("group, folder, m", METRICS,
                         ids=[m["name"] for _g, _f, m in METRICS])
def test_metric_has_a_reader_that_imports_and_cells_that_exist(
        group, folder, m):
    with open(os.path.join(HERE, folder, m["name"] + ".json")) as f:
        spec = json.load(f)
    reader = importlib.import_module("benchmark.readers." + spec["reader"])
    assert callable(reader.read)
    assert set(m.get("workloads", [])) <= CELLS
    if group == "per_layer":
        assert m["moves"] in {e["name"] for e in BENCH["end_to_end"]}


@pytest.mark.parametrize("m", BENCH["end_to_end"],
                         ids=[m["name"] for m in BENCH["end_to_end"]])
def test_bound_is_one_the_driver_takes(m):
    assert 0.01 <= m["bound"] <= 0.25
    if m["name"] == "setup_s":
        assert m["bound"] == 0.25


def test_run_seconds_fits_a_check_of_24_cells():
    # 2 + 14 x 24 runs of run_seconds + 60 s, 24 x 180 s of compiling and
    # 1200 s spare have to fit into 43200 s
    assert (2 + 14 * 24) * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200 \
        <= 43200
