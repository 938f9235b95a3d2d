"""The validator of the last line accepts what the driver reads and
refuses what it refused in PR 22."""

import copy

import pytest

from benchmark import contract

BENCH = contract.load_benchmark()
CELL = BENCH["workloads"][0]["name"]


def good(trace: bool) -> dict:
    units = contract.cell_metrics(BENCH, CELL, trace)
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
              "memory_peak_bytes": 1_000_000}
    if trace:
        device.update(window_s=45.2, busy_s=0.31)
    return contract.build(
        correct=True, attempted=40, failed=0,
        metrics={n: 1.5 for n in units}, units=units, device=device,
        breakdown={"device_ops": [["fusion.1", 0.2]],
                   "idle_gaps": [["a -> b", 40.0]]} if trace else None,
        compared={"answers_wrong": [0, 0]})


@pytest.mark.parametrize("trace", [False, True])
def test_good_line_passes(trace):
    contract.validate(good(trace), BENCH, CELL, trace)


def _drop_metric(line):
    line["metrics"].pop(next(iter(line["metrics"])))


def _compared_first(line):
    rest = {k: line.pop(k) for k in list(line) if k != "compared"}
    line.update(rest)


def _set(path, value):
    def edit(line):
        d = line
        for k in path[:-1]:
            d = d[k]
        d[path[-1]] = value
    return edit


BREAKS = {
    "a metric of the cell is missing": (True, _drop_metric),
    "busy_s is 0 (PR 22: no device work in the window)":
        (True, _set(("device", "busy_s"), 0.0)),
    "busy_s exceeds window_s": (True, _set(("device", "busy_s"), 50.0)),
    "window_s is missing": (True, lambda ln: ln["device"].pop("window_s")),
    "memory_peak_bytes is missing":
        (False, lambda ln: ln["device"].pop("memory_peak_bytes")),
    "correct is not a boolean": (False, _set(("correct",), "true")),
    "an unknown key": (False, _set(("note",), "x")),
    "a metric the cell does not have":
        (False, _set(("metrics", "made_up"), {"value": 1, "unit": "s"})),
    "an end-to-end metric is 0":
        (False, _set(("metrics", "setup_s", "value"), 0)),
    "a value is NaN":
        (False, _set(("metrics", "setup_s", "value"), float("nan"))),
    "a unit differs from BENCHMARK.json":
        (False, _set(("metrics", "setup_s", "unit"), "ms")),
    "failed exceeds attempted": (False, _set(("failed",), 41)),
    "breakdown in an untraced run":
        (False, _set(("breakdown",), {"device_ops": []})),
    "compared is not the line's last key": (False, _compared_first),
    "a number compared has no limit":
        (False, _set(("compared", "answers_wrong"), [0, None])),
    "a breakdown list of 11":
        (True, _set(("breakdown", "device_ops"), [["op", 0.1]] * 11)),
}


@pytest.mark.parametrize("why", sorted(BREAKS))
def test_broken_line_is_refused(why):
    trace, edit = BREAKS[why]
    line = copy.deepcopy(good(trace))
    edit(line)
    with pytest.raises(contract.Malformed):
        contract.validate(line, BENCH, CELL, trace)


def test_every_cell_reports_what_the_contract_asks():
    for w in BENCH["workloads"]:
        e2e = contract.cell_metrics(BENCH, w["name"], False)
        assert "setup_s" in e2e and len(e2e) >= 2, w["name"]
        assert contract.cell_metrics(BENCH, w["name"], True), w["name"]
