"""``spread.py``: quartiles, spread and bound of a fixed list of last
lines, computed by hand."""

import json
import math

import pytest

from benchmark import spread


def line(q6: float, setup: float = 130.0, correct: bool = True) -> str:
    return json.dumps({
        "correct": correct, "attempted": 10, "failed": 0,
        "metrics": {"q6_p50_ms": {"value": q6, "unit": "ms"},
                    "setup_s": {"value": setup, "unit": "s"}},
        "device": {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
                   "memory_peak_bytes": 1}})


# 8 runs: the exclusive quartiles lie at positions 2.25 and 6.75 of the
# sorted runs: 5.02 + 0.25 * 0.02 = 5.025 and 5.10 + 0.75 * 0.04 = 5.13;
# the median is (5.06 + 5.08) / 2 = 5.07.
RUNS = [5.00, 5.02, 5.04, 5.06, 5.08, 5.10, 5.14, 5.20]


def test_quartiles_and_spread_by_hand():
    s = spread.spread(RUNS[::-1])
    assert s["n"] == 8
    assert s["median"] == pytest.approx(5.07)
    assert (s["q1"], s["q3"]) == (pytest.approx(5.025), pytest.approx(5.13))
    assert s["spread"] == pytest.approx(0.105 / 5.07)


def test_bound_lies_midway_between_the_drivers_limits():
    j = spread.judge([RUNS])
    # loose: all 8 runs, 0.105 / 5.07 = 0.020710. tight: without 5.20, the
    # run farthest from 5.07: quartiles 5.02 and 5.10 of 7, median 5.06,
    # 0.08 / 5.06 = 0.015810. 4 x sqrt(0.020710 x 0.015810) = 0.07238
    assert j["loose"] == pytest.approx(0.105 / 5.07)
    assert j["tight"] == pytest.approx(0.08 / 5.06)
    assert j["bound"] == 0.075
    assert not j["too_tight"] and not j["too_loose"]
    assert 2 * j["tight"] < j["bound"] < 8 * j["loose"]


@pytest.mark.parametrize("x, want", [
    (0.0201, 0.025), (0.025, 0.025), (0.0250001, 0.03), (0.004, 0.005)])
def test_round_up(x, want):
    assert spread.round_up(x) == want


@pytest.mark.parametrize("s, want", [
    (0.004 / 4, 0.01),          # 0.004 -> the floor
    (0.0201 / 4, 0.025), (0.0, 0.01), (0.09, 0.25)])
def test_bound_floor_and_ceiling(s, want):
    assert spread.bound_for(s, s) == want
    assert spread.bound_for(s / 2, s * 2) == want


def test_fewer_than_four_runs_are_refused(tmp_path, capsys):
    with pytest.raises(spread.TooFew):
        spread.spread([1.0, 2.0, 3.0])
    f = tmp_path / "three"
    f.write_text("\n".join(line(x) for x in RUNS[:3]))
    assert spread.main([str(f)]) == 1
    assert "3 runs" in capsys.readouterr().err


def test_two_sets_take_the_wider_and_skip_what_is_no_last_line(
        tmp_path, capsys):
    quiet = [5.0, 5.001, 5.002, 5.003, 5.004, 5.005]
    a = tmp_path / "a"
    a.write_text("[  1.0s] a log line\n{not json\n"
                 + "\n".join(line(x) for x in RUNS))
    b = tmp_path / "b"
    b.write_text("\n".join(line(x) for x in quiet))
    assert spread.main([str(a), str(b)]) == 0
    out = capsys.readouterr().out
    assert "14 runs in 2 set(s), 0 of them not correct" in out
    assert "q6_p50_ms: these runs tight " in out and " loose 0.0" in out
    assert "setup_s: bound 0.25 (not by spread)" in out
    j = spread.report([spread.read_set(str(a)), spread.read_set(str(b))])
    assert j["q6_p50_ms"]["second_median_off"] == pytest.approx(
        5.0025 / 5.07 - 1)


def test_a_run_that_is_not_correct_fails_the_report(tmp_path):
    f = tmp_path / "runs"
    f.write_text("\n".join([line(x) for x in RUNS]
                           + [line(5.0, correct=False)]))
    assert spread.main([str(f)]) == 1


def test_the_drivers_two_readings():
    # one far-off run in a set does no harm to tightness: it is left out
    sets = [[5.0, 5.01, 5.02, 5.03, 5.04, 6.0]] * 2
    j = spread.judge(sets)
    assert j["tight"] < j["sets"][0]["spread"] / 5
    assert math.isclose(j["tight"],
                        spread.spread([5.0, 5.01, 5.02, 5.03, 5.04])["spread"])


def test_the_record_widens_tight_and_narrows_loose():
    quiet = spread.judge([RUNS])
    record = [{"tight": 0.03, "from": "a driver's note"},
              {"loose": 0.018, "from": "a line's spread"},
              {"tight": 0.001}, {"loose": 0.5}]
    j = spread.judge([RUNS], record)
    assert (j["runs_tight"], j["runs_loose"]) == (quiet["tight"],
                                                  quiet["loose"])
    assert (j["tight"], j["loose"]) == (0.03, 0.018)
    # 4 x sqrt(0.03 x 0.018) = 0.09295
    assert j["bound"] == 0.095 > quiet["bound"]


def test_the_committed_record_and_bounds_agree_with_the_rule():
    """Each bound in BENCHMARK.json lies between the limits the record's
    readings alone would set: over twice its widest tight, under eight
    times its narrowest loose."""
    import json
    import os
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    with open(os.path.join(here, "spread_record.json")) as f:
        record = json.load(f)
    with open(os.path.join(os.path.dirname(here), "BENCHMARK.json")) as f:
        bounds = {m["name"]: m["bound"] for m in json.load(f)["end_to_end"]}
    for name, readings in record.items():
        if name == "what":
            continue
        tight = max(r["tight"] for r in readings if "tight" in r)
        loose = min(r["loose"] for r in readings if "loose" in r)
        assert 2 * tight < bounds[name] < 8 * loose, name
