"""The per-layer metrics that read the program's spans and named device
programs: each reader's arithmetic on a small ``ctx``, what a program
built before the spans reads (0, so that its traced line is still
valid), and a rehearsal line that carries every one of them."""

import json
import os
import subprocess
import sys

import pytest

from benchmark import contract
from benchmark.readers import (named_program_time, program_roofline,
                               span_histogram_mean)

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

NEW = ("pg_statement_ms", "pg_scan_wait_ms", "rpc_queue_ms",
       "engine_issue_ms", "engine_wait_fetch_ms", "engine_finish_ms")
POWER_ONLY = ("q1_program_device_ms.power", "q6_program_device_ms.power",
              "agg_hbm_roofline_pct.power")


def series(name, value, **labels):
    return {(name, tuple(sorted(labels.items()))): value}


def test_roofline_is_bytes_a_call_over_seconds_a_call_over_the_peak():
    before = {**series("yb_device_dispatches", 10, entry="grouped_aggregate"),
              **series("yb_device_program_read_bytes", 1e9,
                       entry="grouped_aggregate")}
    after = {**series("yb_device_dispatches", 110,
                      entry="grouped_aggregate"),
             **series("yb_device_program_read_bytes", 1e9 + 100 * 40e6,
                      entry="grouped_aggregate"),
             # another entry's programs are not in the figure
             **series("yb_device_dispatches", 7, entry="flat_aggregate"),
             **series("yb_device_program_read_bytes", 7e9,
                      entry="flat_aggregate")}
    ctx = {"registry": (before, after), "peaks": {"hbm_bytes_per_s": 800e9},
           "trace": {"module_s": {"jit_grouped_aggregate_a": 1.6,
                                  "jit_grouped_aggregate_b": 0.4,
                                  "jit_replay_flush": 9.0},
                     "module_calls": {"jit_grouped_aggregate_a": 20,
                                      "jit_grouped_aggregate_b": 20,
                                      "jit_replay_flush": 1}}}
    args = {"entries": ["grouped_aggregate"],
            "modules": ["jit_grouped_aggregate_*"]}
    # 40 MB a call over 50 ms a call is 0.8 GB/s: 0.1% of 800 GB/s
    assert program_roofline.read(args, ctx) == pytest.approx(0.1)
    ctx["trace"]["module_s"] = {"jit_replay_flush": 9.0}
    ctx["trace"]["module_calls"] = {"jit_replay_flush": 1}
    assert program_roofline.read(args, ctx) is None
    assert program_roofline.read(args, dict(ctx, trace=None)) is None
    same = dict(ctx, registry=(after, after))      # no dispatch in the window
    assert program_roofline.read(args, same) is None


def test_a_program_built_before_the_spans_reads_zero():
    """The parent of the PR that adds a metric runs with this benchmark
    laid over it; its line must stay valid, so what it cannot measure
    reads 0 and not nothing."""
    old = ({}, series("rpc_latency_us_count", 5, method="ts.scan"))
    trace = {"module_s": {"jit__unknown": 1.0},
             "module_calls": {"jit__unknown": 10}, "traced_s": 10.0}
    ctx = {"registry": old, "trace": trace, "peaks": {"hbm_bytes_per_s": 1},
           "client": {"done_s": {}}}
    assert span_histogram_mean.read(
        {"name": "yb_span_us", "labels": {"span": "pg.scan_wait"}}, ctx) == 0
    assert named_program_time.read({"modules": ["jit_grouped_x"]}, ctx) == 0
    assert program_roofline.read(
        {"entries": ["grouped_aggregate"], "modules": ["jit_g*"]}, ctx) == 0


def test_a_span_that_is_there_and_never_entered_reads_nothing():
    before = series("yb_span_us_count", 4, span="pg.scan_wait")
    ctx = {"registry": (before, dict(before))}
    args = {"name": "yb_span_us", "labels": {"span": "pg.scan_wait"},
            "scale": 0.001}
    assert span_histogram_mean.read(args, ctx) is None
    after = {**series("yb_span_us_count", 8, span="pg.scan_wait"),
             **series("yb_span_us_sum", 6000, span="pg.scan_wait"),
             **series("yb_span_us_count", 99, span="other"),
             **series("yb_span_us_sum", 99e6, span="other")}
    before.update(series("yb_span_us_sum", 2000, span="pg.scan_wait"))
    ctx = {"registry": (before, after)}
    assert span_histogram_mean.read(args, ctx) == pytest.approx(1.0)  # ms


def test_named_program_time_is_per_call_of_the_exact_name():
    reg = ({}, series("yb_device_dispatches", 3, entry="grouped_aggregate"))
    trace = {"module_s": {"jit_grouped_aggregate_q1": 0.8,
                          "jit_grouped_aggregate_q6": 0.44},
             "module_calls": {"jit_grouped_aggregate_q1": 10,
                              "jit_grouped_aggregate_q6": 10},
             "traced_s": 10.0}
    ctx = {"registry": reg, "trace": trace, "client": {"done_s": {}}}
    assert named_program_time.read(
        {"modules": ["jit_grouped_aggregate_q1"]}, ctx) == pytest.approx(80)
    assert named_program_time.read(
        {"modules": ["jit_grouped_aggregate_q6"]}, ctx) == pytest.approx(44)
    assert named_program_time.read({"modules": ["jit_gone"]}, ctx) is None
    assert named_program_time.read(
        {"modules": ["jit_gone"]}, dict(ctx, trace=None)) is None


@pytest.mark.parametrize("cell,suffix", [
    ("tpch_power_q1q6", "power"), ("tpch_throughput_q1q6", "streams")])
def test_a_rehearsal_line_carries_every_new_metric(cell, suffix):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", "2147483777", "--seconds", "6",
         "--trace", "1", "--rehearse-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    assert proc.returncode == 0, proc.stderr[-2000:]
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    contract.validate(line, contract.load_benchmark(ROOT), cell, True)
    assert line["correct"]
    got = line["metrics"]
    want = [f"{m}.{suffix}" for m in NEW]
    if suffix == "power":
        want += POWER_ONLY
    for name in want:
        assert got[name]["value"] > 0, name
    # the timers the spans sit under (a rehearsal: the order, not sizes)
    phases = sum(got[f"engine_{p}_ms.{suffix}"]["value"]
                 for p in ("issue", "wait_fetch", "finish"))
    rpc = got[f"tserver_read_rpc_ms.{suffix}"]["value"]
    assert phases <= rpc <= got[f"pg_statement_ms.{suffix}"]["value"]
    assert not any("jit__unknown" in gap
                   for gap, _s in line["breakdown"]["idle_gaps"])
    if suffix == "power":
        parts = got["q1_program_device_ms.power"]["value"] \
            + got["q6_program_device_ms.power"]["value"]
        # (two stand-in events a call on the CPU; two programs a query)
        assert parts == pytest.approx(
            got["agg_device_ms_per_query.power"]["value"] / 2, rel=0.25)
