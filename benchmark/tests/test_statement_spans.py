"""The per-layer metrics of a statement outside the tserver's handler
(the PG frontend's five parts, the caller's side of a tablet RPC and the
reply's serialisation, the mesh request's issue in two, the CQL
frontend's statement and queue): what each reads through its own file
from the registry of a program built before its span, which the driver
runs under this benchmark. Seventeen sit on series such a program does
not publish and read 0 there, so that its traced line stays valid; four
read series it has fed all along. That a rehearsal line carries every
one above 0, and that the parts add up, is
``tests/test_benchmark_cells_traced.py``'s."""

import json
import os

import pytest

from benchmark import contract
from benchmark.readers import span_histogram_mean

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = contract.load_benchmark()

NEW_SERIES = ("yb_pg_statement_part_us", "rpc_call_us", "rpc_respond_us",
              "yb_mesh_issue_part_us")
ON_NEW_SERIES = (
    "pg_parse_ms.power", "pg_plan_ms.power", "pg_scans_ms.power",
    "pg_combine_ms.power", "pg_reply_ms.power", "rpc_call_ms.power",
    "rpc_respond_ms.power", "pg_parse_ms.streams", "pg_scans_ms.streams",
    "rpc_call_ms.streams", "pg_plan_ms.mesh", "pg_scans_ms.mesh",
    "rpc_call_ms.mesh", "mesh_issue_lower_ms.mesh",
    "mesh_issue_dispatch_ms.mesh", "rpc_call_write_ms.kv",
    "rpc_call_read_ms.kv")
ON_OLD_SERIES = ("cql_statement_ms.kv", "cql_queue_ms.kv", "rpc_queue_ms.kv",
                 "raft_replicate_ms.kv")


def series(name, value, **labels):
    return {(name, tuple(sorted(labels.items()))): value}


def spec_of(metric):
    with open(os.path.join(HERE, "layer_metrics", metric + ".json")) as f:
        spec = json.load(f)
    # (data, not code: a new reader module would be benchmark code)
    assert spec["reader"] == "span_histogram_mean"
    return spec


def grown(name, labels, mean, n=40):
    """A histogram of ``n`` observations of ``mean`` under the first
    value of each label the file asks for, as before and after."""
    ls = {k: (v[0] if isinstance(v, list) else v) for k, v in labels.items()}
    return ({**series(name + "_count", 2, **ls),
             **series(name + "_sum", 2 * mean, **ls)},
            {**series(name + "_count", 2 + n, **ls),
             **series(name + "_sum", (2 + n) * mean, **ls)})


# What a program at PR 38 publishes around these metrics' series: the
# queue and the handler of every method, the frontends' statements, the
# spans without a histogram of their own.
def parent_registry():
    before, after = {}, {}
    for name, labels, mean in (
            ("rpc_queue_us", {"method": "cql"}, 40_000.0),
            ("rpc_queue_us", {"method": "ts.write"}, 300.0),
            ("rpc_queue_us", {"method": "ts.scan"}, 440.0),
            ("rpc_latency_us", {"method": "ts.scan"}, 3_200.0),
            ("yb_request_latency_seconds", {"proto": "cql"}, 0.015),
            ("yb_request_latency_seconds", {"proto": "pg"}, 0.0063),
            ("yb_span_us", {"span": "raft.replicate"}, 9_000.0),
            ("yb_span_us", {"span": "pg.scan_wait"}, 190.0),
            ("yb_engine_phase_us", {"phase": "issue", "route": "mesh"},
             3_600.0)):
        b, a = grown(name, labels, mean)
        before.update(b)
        after.update(a)
    return before, after


def test_the_lists_are_the_index_s():
    mine = set(ON_NEW_SERIES) | set(ON_OLD_SERIES)
    assert len(mine) == 21
    by_name = {m["name"]: m for m in BENCH["per_layer"]}
    assert mine <= set(by_name)
    for name in mine:
        assert by_name[name]["source"] == "program_span"
        assert len(by_name[name]["workloads"]) == 1


@pytest.mark.parametrize("metric", ON_NEW_SERIES)
def test_a_program_built_before_the_span_reads_zero(metric):
    spec = spec_of(metric)
    assert spec["args"]["name"] in NEW_SERIES
    ctx = {"registry": parent_registry()}
    assert span_histogram_mean.read(spec["args"], ctx) == 0
    # and the mean of its own series, where the program has the span
    before, after = grown(spec["args"]["name"],
                          spec["args"].get("labels", {}), 1500.0)
    ctx = {"registry": (before, after)}
    assert span_histogram_mean.read(spec["args"], ctx) == pytest.approx(1.5)
    # entered never in the window: nothing, and the run is refused
    assert span_histogram_mean.read(
        spec["args"], {"registry": (after, after)}) is None


@pytest.mark.parametrize("metric, want_ms", zip(
    ON_OLD_SERIES, (15.0, 40.0, 0.37, 9.0)))   # (ts.write's and ts.scan's pooled)
def test_a_series_the_parent_feeds_reads_its_value_there(metric, want_ms):
    spec = spec_of(metric)
    assert spec["args"]["name"] not in NEW_SERIES
    ctx = {"registry": parent_registry()}
    assert span_histogram_mean.read(spec["args"], ctx) == \
        pytest.approx(want_ms)
