"""``spread.py``: quartiles, spread and bound of a fixed list of last
lines, computed by hand; the driver's sentences, letter for letter; the
merge of a ledger into the record; the committed record against the
committed bounds."""

import io
import json
import math
import os

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


def rd(kind: str, share: float, by: str = "driver", **more) -> dict:
    """A reading on record that is ``share`` of the median it was read at."""
    return dict({"kind": kind, "by": by, "value": share, "unit": "",
                 "median": 1.0}, **more)


def test_the_record_takes_the_widest_tight_and_the_middle_loose():
    quiet = spread.judge([RUNS])
    record = [rd("tight", 0.03), rd("loose", 0.018), rd("tight", 0.001),
              rd("loose", 0.5), rd("loose", 0.002)]
    j = spread.judge([RUNS], record)
    assert (j["runs_tight"], j["runs_loose"]) == (quiet["tight"],
                                                  quiet["loose"])
    # neither the one quiet line (0.002) nor the one wild one (0.5)
    assert (j["tight"], j["loose"]) == (0.03, 0.018)
    # 4 x sqrt(0.03 x 0.018) = 0.09295
    assert j["bound"] == 0.095 > quiet["bound"]
    # of an even number the lower of the two in the middle, a reading
    assert spread.on_record(record + [rd("loose", 0.4)])["loose"] == 0.018


# -- the driver's readings alone ----------------------------------------------

QUIET = [5.0, 5.001, 5.002, 5.003, 5.004, 5.005]    # a builder's host


def test_with_a_drivers_tight_and_loose_the_builders_sets_stay_out():
    record = [rd("tight", 0.036), rd("loose", 0.026),
              rd("tight", 0.0001, "builder"), rd("loose", 0.0001, "builder")]
    j = spread.judge([QUIET, QUIET], record)
    assert j["drivers_alone"]
    assert j["runs_loose"] < 0.001          # would pull the bound to the floor
    assert (j["tight"], j["loose"]) == (0.036, 0.026)
    assert j["bound"] == spread.bound_for(0.036, 0.026) == 0.125
    assert j["over"] == [False, False]
    assert not j["too_tight"] and not j["too_loose"]
    # a set as wide as the bound is marked: the sets are a check
    wide = [5.0, 5.0, 5.4, 5.8, 6.2, 6.2]
    assert spread.judge([QUIET, wide], record)["over"] == [False, True]


@pytest.mark.parametrize("record", [
    [], [rd("tight", 0.03)], [rd("loose", 0.02)],
    [rd("tight", 0.0004, "builder"), rd("loose", 0.03, "builder")]],
    ids=["empty", "tight_only", "loose_only", "builders_only"])
def test_without_both_of_the_drivers_the_builders_sets_enter(record):
    j = spread.judge([RUNS], record)
    assert not j["drivers_alone"]
    tights = [j["runs_tight"]] + [r["value"] for r in record
                                  if r["kind"] == "tight"]
    looses = [j["runs_loose"]] + [r["value"] for r in record
                                  if r["kind"] == "loose"]
    assert (j["tight"], j["loose"]) == (
        max(tights), sorted(looses)[(len(looses) - 1) // 2])
    assert j["tight"] >= j["runs_tight"] and j["loose"] <= j["runs_loose"]


# -- the driver's sentences ---------------------------------------------------

NOTE_40 = (
    "with the change the runs of q1_p50_ms on workload tpch_power_q1q6 "
    "spread by 0.1638 ms, more than 50% of what the bound there will be if "
    "it is accepted: 0.20275 ms, 3% of their median. Checks of later PRs may "
    "then be unable to tell whether q1_p50_ms changed. A PR of kind "
    "'benchmark' should steady the workload (more load, a longer window) or "
    "set a wider bound")
REASON_45 = (
    "cannot tell whether q1_p50_ms on workload tpch_power_q1q6 changed: the "
    "spread is 0.1275 ms at the parent and 0.245135 ms with the change, and "
    "the bound is 0.207499 ms, 3% of 6.91663 ms. A steadier benchmark or "
    "longer runs would be needed to tell. A spread leaves out the run "
    "farthest from its median where that narrows it")
NOTE_RATE = (
    "with the change the runs of throughput_ops_s on workload "
    "tpch_throughput_q1q6 spread by 3.09368 ops/s, more than 50% of what the "
    "bound there will be if it is accepted: 1.31044 ops/s, 1% of their median")


def test_the_note_of_pr_40_parses_letter_for_letter():
    assert spread.parse_text(NOTE_40) == [{
        "metric": "q1_p50_ms", "cell": "tpch_power_q1q6", "side": "change",
        "value": 0.1638, "unit": "ms"}]


def test_the_reason_of_pr_45_parses_to_two_readings():
    assert spread.parse_text(REASON_45) == [
        {"metric": "q1_p50_ms", "cell": "tpch_power_q1q6", "side": "parent",
         "value": 0.1275, "unit": "ms"},
        {"metric": "q1_p50_ms", "cell": "tpch_power_q1q6", "side": "change",
         "value": 0.245135, "unit": "ms"}]


def test_a_rates_note_parses_with_its_unit():
    assert spread.parse_text(NOTE_RATE) == [{
        "metric": "throughput_ops_s", "cell": "tpch_throughput_q1q6",
        "side": "change", "value": 3.09368, "unit": "ops/s"}]


REFUSED_46 = (
    "Why: the benchmark is too noisy for its own bound on q6_p50_ms on "
    "workload tpch_power_q1q6: in two sets of runs of the same code the "
    "spread is 0.473857 and 0.155135 ms, and the bound is 0.465222 ms, 9% of "
    "5.16913 ms. A spread leaves out the run farthest from its median where "
    "that narrows it.\n"
    "- tpch_power_q1q6 / q1_p50_ms: the middle half of 6 runs spread "
    "0.501078 and 0.204058 ms in the two sets, of each side's runs the one "
    "farthest from its median left out; the bound is 0.559699 ms (8% of the "
    "median, 6.99623 ms)")


def test_a_refused_checks_two_sets_parse_to_their_mean():
    """The driver holds the MEAN of its two sets' spreads against half the
    bound, so that is the tight reading of a benchmark PR's own check."""
    got = spread.parse_text(REFUSED_46)
    assert [(f["metric"], f["cell"], f["side"], f["unit"]) for f in got] == [
        ("q6_p50_ms", "tpch_power_q1q6", "check", "ms"),
        ("q1_p50_ms", "tpch_power_q1q6", "check", "ms")]
    assert got[0]["value"] == pytest.approx((0.473857 + 0.155135) / 2)
    assert got[1]["value"] == pytest.approx((0.501078 + 0.204058) / 2)


# -- the merge ----------------------------------------------------------------

UNITS = {"q1_p50_ms": "ms", "q6_p50_ms": "ms", "throughput_ops_s": "ops/s",
         "setup_s": "s"}
KEYS = {"kind", "by", "pr", "cell", "side", "value", "unit", "median"}


def ledger_line(pr, cell, e2e, spreads, verdict="accepted", **more):
    return dict({"pr": pr, "verdict": verdict, "workload": cell,
                 "end_to_end": e2e, "spread": spreads}, **more)


LEDGER = [
    ledger_line(40, "tpch_power_q1q6",
                {"q1_p50_ms": [7.2536, 6.7583], "setup_s": [130.67, 129.99]},
                {"q1_p50_ms": 0.054, "setup_s": 0.071}),
    {"pr": 40, "verdict": "accepted", "workload": None, "notes": [NOTE_40]},
    ledger_line(44, "tpch_power_q1q6",
                {"q1_p50_ms": [6.7859, 6.8327], "setup_s": [129.1, 131.94]},
                {"q1_p50_ms": 0.028, "setup_s": 0.06}),
    ledger_line(44, "tpch_throughput_q1q6",
                {"throughput_ops_s": [172.46, 171.1]},
                {"throughput_ops_s": 0.031}),
    ledger_line(45, "tpch_power_q1q6",
                {"q1_p50_ms": [6.9166, 6.9676], "setup_s": [132.05, 134.25]},
                {"q1_p50_ms": 0.047, "setup_s": 0.078},
                verdict="unresolved", reason=REASON_45),
]


def merged(lines, record=None):
    record = {} if record is None else record
    err = io.StringIO()
    added = spread.merge_ledger(record, lines, UNITS, err=err)
    return record, added, err.getvalue()


def by_key(record, metric):
    return {spread.key_of(r): r for r in record["readings"][metric]}


def test_the_merge_takes_loose_and_tight_with_pr_cell_value_and_median():
    record, added, said = merged(LEDGER)
    assert len(added) == 7         # q1: 3 loose, 3 tight; the rate: 1 loose
    assert said == ""
    assert "setup_s" not in record["readings"]     # not by spread
    assert all(set(r) == KEYS for rs in record["readings"].values()
               for r in rs)                        # and no share is stored
    q1 = by_key(record, "q1_p50_ms")
    r = q1[("tight", "driver", 40, "tpch_power_q1q6", "change")]
    assert (r["value"], r["unit"], r["median"]) == (0.1638, "ms", 6.7583)
    r = q1[("tight", "driver", 45, "tpch_power_q1q6", "parent")]
    assert (r["value"], r["median"]) == (0.1275, 6.9166)
    r = q1[("tight", "driver", 45, "tpch_power_q1q6", "change")]
    assert (r["value"], r["median"]) == (0.245135, 6.9676)
    r = q1[("loose", "driver", 45, "tpch_power_q1q6", "change")]
    assert r["value"] == pytest.approx(0.047 * 6.9676) and r["unit"] == "ms"
    # the newest ACCEPTED median: PR 45 is unresolved and sets none
    assert record["medians"]["q1_p50_ms"]["tpch_power_q1q6"] == {
        "median": 6.8327, "pr": 44}
    # a metric BENCHMARK.json does not hold is not read
    record, added, _said = merged([ledger_line(
        46, "a_cell", {"read_p95_ms": [1.0, 1.0]}, {"read_p95_ms": 0.02})])
    assert not added and not record.get("readings")


def test_a_latency_is_taken_over_the_newest_median_a_rate_is_not():
    record, _added, _said = merged(LEDGER)
    meds = record["medians"]
    q1 = by_key(record, "q1_p50_ms")
    loose_40 = q1[("loose", "driver", 40, "tpch_power_q1q6", "change")]
    tight_45 = q1[("tight", "driver", 45, "tpch_power_q1q6", "change")]
    rate = by_key(record, "throughput_ops_s")[
        ("loose", "driver", 44, "tpch_throughput_q1q6", "change")]
    # 0.054 of 6.7583 ms is 0.36495 ms, and 0.05341 of today's 6.8327
    assert spread.share(loose_40, meds["q1_p50_ms"]) == pytest.approx(
        0.054 * 6.7583 / 6.8327)
    assert spread.share(tight_45, meds["q1_p50_ms"]) == pytest.approx(
        0.245135 / 6.8327)
    assert spread.share(rate, meds["throughput_ops_s"]) == pytest.approx(0.031)
    took = spread.on_record(record["readings"]["q1_p50_ms"],
                            meds["q1_p50_ms"])
    assert took["tight"] == pytest.approx(0.245135 / 6.8327)
    # the middle of PR 44's 0.028, PR 45's 0.047 and PR 40's 0.054
    assert took["loose"] == pytest.approx(0.047 * 6.9676 / 6.8327)
    # a faster program: the same milliseconds are a larger share, and the
    # readings on record stay as they were read
    before = json.loads(json.dumps(record["readings"]))
    faster = LEDGER + [ledger_line(
        47, "tpch_power_q1q6", {"q1_p50_ms": [6.8, 3.4]},
        {"q1_p50_ms": 0.5}), ledger_line(
        47, "tpch_throughput_q1q6", {"throughput_ops_s": [171.0, 342.0]},
        {"throughput_ops_s": 0.5})]
    merged(faster, record)
    for m, rs in before.items():
        assert record["readings"][m][:len(rs)] == rs
    assert spread.share(tight_45, meds["q1_p50_ms"]) == pytest.approx(
        0.245135 / 3.4)
    assert spread.share(rate, meds["throughput_ops_s"]) == pytest.approx(0.031)
    # a time in a cell with no accepted median yet: over its own
    assert spread.share(dict(tight_45, cell="a_new_cell"),
                        meds["q1_p50_ms"]) == pytest.approx(0.245135 / 6.9676)


def test_a_text_that_names_a_metric_and_does_not_parse_is_reported():
    odd = "the runs of q1_p50_ms on workload tpch_power_q1q6 spread a lot"
    lines = LEDGER + [
        {"pr": 46, "verdict": "accepted", "workload": None, "notes": [odd]},
        {"pr": 42, "verdict": "refused_in_review", "workload": None,
         "reason": "what the review found stood after the fix session"}]
    _record, added, said = merged(lines)
    assert len(added) == 7                    # nothing guessed from it
    assert "PR 46 notes names a metric and does not parse" in said
    assert "PR 42" not in said                # names no metric: silent


# PR 25's lines as the ledger of commit 91e1aac holds them: the parent is
# the program before it (174 ms), and 0.002 of the change's 19.976 ms is
# 0.04 ms, a quarter of the 0.15636 ms the driver named for the same runs
# WITHOUT the farthest.
NOTE_25 = (
    "with the change the runs of q1_p50_ms on workload tpch_power_q1q6 "
    "spread by 0.15636 ms, more than 50% of what the bound there will be if "
    "it is accepted: 0.19976 ms, 1% of their median")
LINES_25 = [
    ledger_line(25, "tpch_power_q1q6",
                {"q1_p50_ms": [174.27, 19.976], "q6_p50_ms": [97.397, 7.325]},
                {"q1_p50_ms": 0.002, "q6_p50_ms": 0.0032}),
    {"pr": 25, "verdict": "accepted", "workload": None, "notes": [NOTE_25]}]


def test_a_loose_under_its_own_prs_tight_is_reported_and_skipped():
    record, added, said = merged(LINES_25)
    assert [spread.key_of(r) for r in record["readings"]["q1_p50_ms"]] == [
        ("tight", "driver", 25, "tpch_power_q1q6", "change")]
    assert "PR 25 tpch_power_q1q6 q1_p50_ms: spread 0.002 of 19.976" in said
    assert "skipped" in said
    # q6 has no tight here to be held against: kept as it reads
    assert len(added) == 2 and "q6_p50_ms loose PR 25" in added[1]
    # whichever of the two lines comes first
    again, _added, _said = merged(LINES_25[::-1])
    assert again == record


def test_lines_before_the_records_since_are_not_read():
    old = ledger_line(24, "tpch_power_q1q6", {"q1_p50_ms": [173.82, 174.03]},
                      {"q1_p50_ms": 0.00033})
    record, added, _said = merged([old] + LEDGER, {"since": 25})
    assert len(added) == 7
    assert 24 not in {r["pr"] for r in record["readings"]["q1_p50_ms"]}
    record, added, _said = merged([old] + LEDGER)
    assert len(added) == 8


def test_a_second_merge_changes_nothing_and_none_removes(tmp_path, capsys):
    """``--ledger`` on this tree's ``PERF_LEDGER.jsonl``, twice, from a
    record that holds two readings the ledger has dropped."""
    old = {"what": "kept", "since": 25, "readings": {"q1_p50_ms": [
        rd("tight", 0.15636, pr=25, cell="tpch_power_q1q6", side="change",
           unit="ms", median=19.976),
        rd("loose", 0.179091, pr=28, cell="tpch_power_q1q6", side="change",
           unit="ms", median=16.281)]}}
    rec = tmp_path / "record.json"
    rec.write_text(json.dumps(old))
    ledger = os.path.join(spread.ROOT, "PERF_LEDGER.jsonl")
    assert spread.main(["--ledger", ledger, "--record", str(rec)]) == 0
    assert "record.json rewritten" in capsys.readouterr().out
    once = rec.read_text()
    assert spread.main(["--ledger", ledger, "--record", str(rec)]) == 0
    assert rec.read_text() == once
    assert "rewritten" not in capsys.readouterr().out
    got = json.loads(once)
    assert got["readings"]["q1_p50_ms"][:2] == old["readings"]["q1_p50_ms"]
    assert len(got["readings"]["q1_p50_ms"]) > 2
    assert (got["what"], got["since"]) == ("kept", 25)
    # an empty ledger after a full one, and the same file twice in one call
    (tmp_path / "empty").write_text("")
    assert spread.main(["--ledger", str(tmp_path / "empty"), "--ledger",
                        ledger, "--ledger", ledger, "--record", str(rec)]) == 0
    assert rec.read_text() == once


# -- what is committed --------------------------------------------------------

def committed_record() -> dict:
    with open(os.path.join(spread.ROOT, "benchmark",
                           "spread_record.json")) as f:
        return json.load(f)


def took_of(metric: str) -> dict:
    record = committed_record()
    return spread.on_record(record["readings"][metric],
                            record["medians"][metric])


def test_the_committed_record_holds_what_benchmark_json_bounds_by_spread():
    _units, bounds = spread.committed()
    record = committed_record()
    assert set(record["readings"]) == set(bounds) - set(spread.NOT_BY_SPREAD)
    assert record["since"] == 25
    assert all(set(r) == KEYS and r["by"] == "driver"
               for rs in record["readings"].values() for r in rs)


@pytest.mark.parametrize("name", ["q1_p50_ms", "q6_p50_ms",
                                  "throughput_ops_s"])
def test_the_committed_record_and_bounds_agree_with_the_rule(name):
    """The bound in BENCHMARK.json lies between the limits the record's
    readings set: over twice its widest tight, under eight times its
    middle loose, every reading the ledger has held since PR 25 and the
    check of PR 46 in the record; Q1's and Q6's are the ones the rule prints
    from them, the rates' stays UNDER the rule's 0.15 (ISSUE 46 left it at
    0.10, the check of PR 46 put it inside its range, 4.81% to 25%, and a
    bound that the rule would only widen stays as it is)."""
    _units, bounds = spread.committed()
    took = took_of(name)
    assert took["drivers_alone"]
    assert 2 * took["tight"] < bounds[name] < 8 * took["loose"]
    rule = spread.bound_for(took["tight"], took["loose"])
    assert bounds[name] == rule or (name, bounds[name], rule) == (
        "throughput_ops_s", 0.10, 0.15)


@pytest.mark.parametrize("name, least", [
    ("q1_p50_ms", 0.0103), ("throughput_ops_s", 0.0069)])
def test_the_narrowest_loose_would_leave_no_bound_between_the_limits(
        name, least):
    """Why the rule takes the middle loose: with the narrowest (Q1: PR 38's
    0.01 of 7.0405 ms; the rates: 0.0069, key-value, PR 34) eight times it
    lies UNDER twice the widest tight on record, so every bound is too tight
    or too loose or both. The check of PR 46 found Q1's so."""
    record = committed_record()
    looses = [spread.share(r, record["medians"][name])
              for r in record["readings"][name] if r["kind"] == "loose"]
    assert min(looses) == pytest.approx(least, abs=5e-5)
    assert 8 * min(looses) < 2 * took_of(name)["tight"]


def test_the_committed_bounds():
    _units, bounds = spread.committed()
    assert 0.075 <= bounds["q1_p50_ms"] <= 0.21
    # the ranges the check of PR 46 gave, well inside: 10.1%, 12.2%, 4.81%
    # to 25%
    assert (bounds["q1_p50_ms"], bounds["q6_p50_ms"],
            bounds["throughput_ops_s"], bounds["setup_s"]) == (
                0.155, 0.195, 0.10, 0.25)


def test_the_committed_record_prints_no_bound_outside_its_limits(capsys):
    _units, bounds = spread.committed()
    spread.print_record(committed_record(), bounds)
    out = {ln.split(":")[0]: ln for ln in capsys.readouterr().out.splitlines()}
    for name in ("q1_p50_ms", "q6_p50_ms"):
        assert f"-> bound {bounds[name]}; BENCHMARK.json has" in out[name]
    assert "-> bound 0.15; BENCHMARK.json has 0.1" in out["throughput_ops_s"]
    assert not any("TOO" in ln for ln in out.values())


def test_this_trees_ledger_leaves_the_committed_record_as_it_is():
    """ISSUE 46's acceptance: ``--ledger PERF_LEDGER.jsonl`` on the PR's
    tree changes nothing. (A later PR's lines add readings and remove none:
    only a ``benchmark`` PR writes the record.)"""
    record = committed_record()
    with open(os.path.join(spread.ROOT, "PERF_LEDGER.jsonl")) as f:
        lines = [json.loads(ln) for ln in f if ln.strip()]
    newest = max(ln["pr"] for ln in lines)
    units, _bounds = spread.committed()
    got = json.loads(json.dumps(record))
    added = spread.merge_ledger(got, lines, units, err=io.StringIO())
    for m, rs in record["readings"].items():
        assert got["readings"][m][:len(rs)] == rs, m
    if newest <= 46:       # the ledger as this PR's tree holds it
        assert not added and got == record


@pytest.mark.parametrize("kind, pr, side", [
    ("tight", 25, "change"), ("tight", 40, "change"),
    ("tight", 45, "parent"), ("tight", 45, "change"),
    ("tight", 46, "check")]
    + [("loose", pr, "change") for pr in (
        27, 28, 29, 31, 32, 33, 34, 37, 38, 39, 40, 43, 44, 45)])
def test_the_committed_record_holds_q1s_reading(kind, pr, side):
    q1 = by_key(committed_record(), "q1_p50_ms")
    r = q1[(kind, "driver", pr, "tpch_power_q1q6", side)]
    assert r["unit"] == "ms" and r["value"] > 0 and r["median"] > 0
