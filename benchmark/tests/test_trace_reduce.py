"""The reduction from device events to busy time, per-program time and
the breakdown, against a small trace recorded on the chip whose expected
figures were worked out by hand (``testdata/recorded_trace.json``)."""

import json
import os

import pytest

from benchmark import trace_reduce
from benchmark.readers import trace_module_time

HERE = os.path.dirname(os.path.abspath(__file__))


@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(HERE, "..", "testdata",
                           "recorded_trace.json")) as f:
        return json.load(f)


def test_recorded_trace_reduces_to_the_recorded_figures(recorded):
    got = trace_reduce.reduce_events(recorded["trace"], recorded["window_s"])
    exp = recorded["expect"]
    assert got["busy_s"] == pytest.approx(exp["busy_s"], abs=1e-12)
    assert got["devices"] == exp["devices"]
    for name, s in exp["module_s"].items():
        assert got["module_s"][name] == pytest.approx(s, abs=1e-12)
    assert got["module_calls"] == exp["module_calls"]
    assert got["busy_s"] <= recorded["window_s"]
    assert len(got["breakdown"]["device_ops"]) <= 10
    assert len(got["breakdown"]["idle_gaps"]) <= 10


def test_overlapping_ops_are_counted_once():
    trace = {"devices": {"/device:TPU:0": {
        "modules": [["jit_f", 0.0, 10e9]],
        "ops": [["a", 0.0, 4e9], ["b", 2e9, 4e9], ["c", 8e9, 1e9]]}}}
    got = trace_reduce.reduce_events(trace, 10.0)
    assert got["busy_s"] == pytest.approx(7.0)   # [0,6] and [8,9]
    assert got["breakdown"]["device_ops"][0] == ["a", 4.0]


def test_two_devices_average_and_an_idle_one_is_left_out():
    trace = {"devices": {
        "/device:TPU:0": {"modules": [], "ops": [["a", 0.0, 2e9]]},
        "/device:TPU:1": {"modules": [], "ops": [["a", 0.0, 4e9]]},
        "/device:TPU:2": {"modules": [], "ops": []}}}
    got = trace_reduce.reduce_events(trace, 10.0)
    assert got["devices"] == 2 and got["busy_s"] == pytest.approx(3.0)


def test_module_names_lose_their_program_id():
    assert trace_reduce.module_name("jit_replay_flush(83012)") == \
        "jit_replay_flush"
    assert trace_reduce.module_name("jit__unknown") == "jit__unknown"


def test_reader_finds_nothing_without_a_trace_or_a_match():
    ctx = {"trace": None, "client": {"done_s": {"read": []}}}
    args = {"modules": ["jit_replay_flush"], "per": "call"}
    assert trace_module_time.read(args, ctx) is None
    ctx["trace"] = {"module_s": {"jit_other": 1.0},
                    "module_calls": {"jit_other": 2}, "traced_s": 5.0}
    assert trace_module_time.read(args, ctx) is None
    ctx["trace"]["module_s"]["jit_replay_flush"] = 0.5
    ctx["trace"]["module_calls"]["jit_replay_flush"] = 4
    assert trace_module_time.read(args, ctx) == pytest.approx(125.0)


def test_reader_holds_unnamed_programs_to_the_traffic():
    """Two programs a query: a window that another unnamed program
    shares reads nothing (and the run is refused for the missing metric)."""
    args = {"modules": ["jit__unknown"], "per": "operation",
            "calls_per_operation": [1.7, 2.4]}
    ctx = {"client": {"done_s": {"q1": [1.0, 2.0], "q6": [3.0, 99.0]}},
           "trace": {"module_s": {"jit__unknown": 0.6},
                     "module_calls": {"jit__unknown": 6}, "traced_s": 5.0}}
    assert trace_module_time.read(args, ctx) == pytest.approx(200.0)
    ctx["trace"]["module_calls"]["jit__unknown"] = 9
    assert trace_module_time.read(args, ctx) is None


@pytest.mark.parametrize("streams,rate", [
    ([[10, 5.0], [10, 10.0]], 2.0),
    ([[10, 5.0], [0, 20.0]], 0.5),    # a client that stalled to the end
    ([], None)])
def test_rate_counts_all_the_work_and_all_the_time(streams, rate):
    from benchmark.readers import window_rate

    assert window_rate.read({}, {"client": {"streams": streams}}) == rate


def test_what_the_profiler_recorded_past_the_window_is_cut_off():
    """A busy chip's trace runs on until stop_trace has taken effect: the
    window is window_s from the first device event, and busy_s cannot
    pass it."""
    trace = {"devices": {"/device:TPU:0": {
        "modules": [["jit_f", 1e9, 6e9], ["jit_f", 7e9, 6e9],
                    ["jit_f", 13e9, 1e9]],
        "ops": [["a", 1e9, 6e9], ["a", 7e9, 6e9], ["a", 13e9, 1e9]]}}}
    got = trace_reduce.reduce_events(trace, 10.0)     # window [1, 11] s
    assert got["busy_s"] == pytest.approx(10.0) and got["busy_s"] <= 10.0
    assert got["module_s"] == {"jit_f": pytest.approx(10.0)}
    assert got["module_calls"] == {"jit_f": 2}
    assert got["recorded_s"] == pytest.approx(13.0)
