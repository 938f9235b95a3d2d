"""Every cell of ``BENCHMARK.json`` rehearsed on the CPU, plain here and
traced in ``test_benchmark_cells_traced.py`` (one file for both was 330 s
of one worker with four cells, more than the rest of tier-1 takes).

What ``benchmark/selfcheck.py`` does before a chip call, one case a run:
``run.py --rehearse-cpu`` in a process of its own, its last line through
``contract.validate``. The driver runs the cells on the chip only after
these tests, and a run that exits 1 there (a load generator that died, a
warm-up that failed, a listed metric missing, an exception) costs the
whole PR; most of those show here in half a minute. A rehearsal proves
the harness and the served path's control flow, and nothing about the
chip: no number of its line is a device number.

``span_histogram_mean`` and ``named_program_time`` read 0 from a program
that lacks their series, so ``validate`` alone lets a renamed span, label
or device program through. Every per-layer metric of both cells reads
above 0 on the CPU backend, so a 0 in a traced line is such a rename.
"""

import json
import os
import subprocess
import sys

import pytest

from benchmark import contract
from benchmark.selfcheck import SECONDS  # the window's length, by --trace

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = contract.load_benchmark(ROOT)
SEED = {0: 2_147_483_659, 1: 2_147_483_660}  # selfcheck.py's, by --trace


CELLS = [w["name"] for w in BENCH["workloads"]]

# The statement outside the handler, part by part (a rehearsal: the
# order, not the sizes): in each pair the first, summed, is no more than
# the second, summed; each part lies inside what it is a part of.
PARTS = {
    "tpch_power_q1q6": [
        (["pg_parse_ms.power", "pg_plan_ms.power", "pg_scans_ms.power",
          "pg_combine_ms.power", "pg_reply_ms.power"],
         ["pg_statement_ms.power"]),
        (["rpc_queue_ms.power", "tserver_read_rpc_ms.power",
          "rpc_respond_ms.power"], ["rpc_call_ms.power"]),
        (["rpc_call_ms.power"], ["pg_scans_ms.power"])],
    "tpch_throughput_q1q6": [
        (["pg_parse_ms.streams", "pg_scans_ms.streams"],
         ["pg_statement_ms.streams"]),
        (["rpc_queue_ms.streams", "tserver_read_rpc_ms.streams"],
         ["rpc_call_ms.streams"])],
    "tpch_mesh_q1_4chip": [
        (["pg_plan_ms.mesh", "pg_scans_ms.mesh"], ["pg_statement_ms.mesh"]),
        (["rpc_queue_ms.mesh", "tserver_multi_agg_rpc_ms.mesh"],
         ["rpc_call_ms.mesh"]),
        (["rpc_call_ms.mesh"], ["pg_scans_ms.mesh"]),
        (["mesh_issue_lower_ms.mesh", "mesh_issue_dispatch_ms.mesh"],
         ["mesh_issue_ms.mesh"])],
    "tpch_q1q6_after_refresh": [
        (["pg_plan_ms.refresh", "pg_scans_ms.refresh"],
         ["pg_statement_ms.refresh"]),
        (["rpc_queue_ms.refresh", "tserver_read_rpc_ms.refresh"],
         ["rpc_call_ms.refresh"]),
        (["rpc_call_ms.refresh"], ["pg_scans_ms.refresh"]),
        (["engine_issue_plan_ms.refresh", "engine_issue_dispatch_ms.refresh",
          "engine_issue_copy_out_ms.refresh"], ["engine_issue_ms.refresh"]),
        (["engine_issue_ms.refresh", "engine_wait_fetch_ms.refresh",
          "engine_finish_ms.refresh"], ["tserver_read_rpc_ms.refresh"])],
    "kv_mixed_flush": [
        (["tserver_write_rpc_ms.kv"], ["rpc_call_write_ms.kv"]),
        (["tserver_read_rpc_ms.kv"], ["rpc_call_read_ms.kv"]),
        (["raft_replicate_ms.kv"], ["tserver_write_rpc_ms.kv"])],
}


def rehearse(cell, trace):
    proc = subprocess.run(
        [sys.executable, os.path.join(ROOT, "benchmark", "run.py"),
         "--workload", cell, "--seed", str(SEED[trace]),
         "--seconds", SECONDS[trace], "--trace", str(trace),
         "--rehearse-cpu"],
        cwd=ROOT, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    err = proc.stderr[-800:]
    assert proc.returncode == 0, f"exit {proc.returncode}: {err}"
    line = json.loads(proc.stdout.strip().splitlines()[-1])
    contract.validate(line, BENCH, cell, bool(trace))
    assert line["correct"] is True, err
    assert line["failed"] == 0, err
    if trace:
        dark = sorted(n for n, m in line["metrics"].items()
                      if not m["value"] > 0)
        assert not dark, f"per-layer metrics that read nothing: {dark}: {err}"
        got = {n: m["value"] for n, m in line["metrics"].items()}
        for parts, whole in PARTS[cell]:
            assert sum(got[n] for n in parts) <= sum(got[n] for n in whole), \
                (parts, whole, got)


# Who moved a reply (PR 40): the labelled counter's growth over the
# window, through the reader that was there. {metric: (series, by)}
REPLY_COUNTERS = {
    "rpc_reply_writes_worker.power": ("rpc_reply_writes", "worker"),
    "rpc_reply_reads_caller.power": ("rpc_reply_reads", "caller"),
    "rpc_reply_reads_caller.kv": ("rpc_reply_reads", "caller"),
    "rpc_reply_reads_peer.kv": ("rpc_reply_reads", "peer"),
}


@pytest.mark.parametrize("metric", sorted(REPLY_COUNTERS))
def test_reply_counter_metric_resolves_and_reads_its_label(metric):
    """Each entry resolves to its data file and to a reader the
    benchmark had; on a registry without the series (the parent commit
    under this benchmark) it reads 0, so that the parent's traced line
    stays valid (``contract.validate`` refuses a line that lacks a
    listed metric); on one with it, the growth of its own label."""
    import importlib

    series, by = REPLY_COUNTERS[metric]
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    assert entry["source"] == "program_counter"
    assert entry["layer"] == "RPC + tablet"
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           metric + ".json")) as f:
        spec = json.load(f)
    assert spec["reader"] == "span_counter_delta"
    assert spec["args"] == {"name": series, "labels": {"by": by}}
    read = importlib.import_module(
        "benchmark.readers." + spec["reader"]).read
    other = {("rpc_call_us_count", (("method", "ts.scan"),)): 7.0}
    assert read(spec["args"], {"registry": (other, other)}) == 0
    rest = "reactor" if by == "worker" else \
        ("peer" if by == "caller" else "caller")
    before = {(series, (("by", by),)): 5.0, (series, (("by", rest),)): 1.0}
    after = {(series, (("by", by),)): 47.0, (series, (("by", rest),)): 4.0}
    assert read(spec["args"], {"registry": (before, after)}) == 42


def _layer_metric(name):
    with open(os.path.join(ROOT, "benchmark", "layer_metrics",
                           name + ".json")) as f:
        return json.load(f)


def test_lookback_scans_metric_reads_the_resolve_counter():
    """``overlay_delta_lookback_scans.refresh`` (PR 44) is data alone: the
    reader that was there over ``yb_grouped_resolve{form="lookback"}``.
    From a program without the series (the parent commit under this
    benchmark) it reads 0 and the parent's traced line stays valid; from
    one with it, the growth of its own label and of no other."""
    import importlib

    metric = "overlay_delta_lookback_scans.refresh"
    entry = next(m for m in BENCH["per_layer"] if m["name"] == metric)
    assert entry == BENCH["per_layer"][-1]
    assert entry == {
        "name": metric, "unit": "programs", "better": "higher",
        "source": "program_counter", "layer": "device programs",
        "moves": "q6_p50_ms", "workloads": ["tpch_q1q6_after_refresh"]}
    spec = _layer_metric(metric)
    assert spec["layer"] == entry["layer"]
    assert spec["reader"] == "span_counter_delta"
    assert spec["args"] == {"name": "yb_grouped_resolve",
                            "labels": {"form": "lookback"}}
    read = importlib.import_module(
        "benchmark.readers." + spec["reader"]).read
    other = {("yb_grouped_buckets", (("form", "direct"),)): 7.0}
    assert read(spec["args"], {"registry": (other, other)}) == 0

    def series(flat, lookback, segmented):
        return {("yb_grouped_resolve", (("form", f),)): float(n)
                for f, n in (("flat", flat), ("lookback", lookback),
                             ("segmented", segmented))}

    assert read(spec["args"], {"registry": (series(9, 5, 1),
                                            series(99, 47, 3))}) == 42


def test_the_mini_runs_programs_are_the_modules_the_metrics_name():
    """``overlay_delta_program_ms.refresh`` and
    ``overlay_delta_hbm_roofline_pct.refresh`` name the mini-run's two
    modules exactly, and ``named_program_time`` refuses a traced run in
    which neither ran: the tag takes no ``lookback``, so the programs of
    the benchmark's two statements over a run that is not flat carry
    those names whichever way they resolve (and the flat ones cell 1's)."""
    from tests.test_group_agg import benchmark_signatures
    from yugabyte_db_tpu.ops import group_agg

    def modules(metric):
        return _layer_metric(metric)["args"]["modules"]

    def names(**form):
        return ["jit_" + group_agg.compiled_grouped(sig).__name__
                for sig in benchmark_signatures(4, 2048, **form)]

    assert names(flat=False, lookback=2) \
        == names(flat=False, lookback=32) == names(flat=False) \
        == modules("overlay_delta_program_ms.refresh") \
        == modules("overlay_delta_hbm_roofline_pct.refresh")
    assert names() == modules("q1_program_device_ms.power") \
        + modules("q6_program_device_ms.power") \
        == modules("overlay_base_program_ms.refresh")


def test_a_statement_after_a_refresh_counts_its_resolve_forms():
    """``yb_grouped_resolve{form}``, one a dispatch of an ops.group_agg
    program (Q6's ungrouped one too): a multi-source Q1 or Q6 counts one
    ``flat`` (the masked primary) and one ``lookback`` (the mini-run,
    two versions a dirty key at most), ``segmented`` none."""
    from tests.test_overlay_grouped import SPECS, Pair
    from yugabyte_db_tpu.storage.row_version import MAX_HT
    from yugabyte_db_tpu.utils import metrics

    p = Pair(orders=60)
    try:
        p.refresh()
        for name in ("q1", "q6"):
            before = metrics.grouped_resolve()
            p.same(name)
            assert metrics.grouped_resolve() == dict(
                before, flat=before["flat"] + 1,
                lookback=before["lookback"] + 1)
            delta = p.tpu._overlay_cache[3].delta
            spec = SPECS[name](MAX_HT)
            _kind, (sig, _params) = p.tpu._grouped_prep(delta, spec,
                                                        spec.predicates)
            assert (sig.flat, sig.lookback) == (False, 2)
        text = metrics.process_registry().prometheus_text()
        for form in metrics.GROUPED_RESOLVE_FORMS:
            assert f'yb_grouped_resolve{{form="{form}"}}' in text
    finally:
        p.close()


@pytest.mark.parametrize("trace", [0], ids=["plain"])
@pytest.mark.parametrize("cell", CELLS)
def test_cell_rehearses(cell, trace):
    rehearse(cell, trace)


# -- tpch_q1q6_after_refresh: the refresh reference, by brute force -----------

def _refresh_config():
    from benchmark.run import load_json, merged

    cfg = load_json("benchmark", "configs", "tpch_lineitem_refresh_rf3.json")
    return merged(cfg, cfg["rehearsal"])


@pytest.mark.parametrize("seed", [1, 2_147_483_659, 3_000_043_001])
def test_refresh_reference_is_base_less_deleted_plus_inserted(seed):
    """At the rehearsal's size, row by row in Python: the table the
    refresh reference answers from is the base population's rows without
    every line of the deleted orders, and RF1's rows (what
    ``batches("burst")`` hands the loader) after them; Q1 and Q6 over it
    are the plain sums."""
    from benchmark.references import tpch_lineitem, tpch_lineitem_refresh

    cfg = _refresh_config()
    base = [r for rows in tpch_lineitem.Reference(cfg, seed).batches()
            for r in rows]
    ref = tpch_lineitem_refresh.Reference(cfg, seed)
    assert [r for rows in ref.batches() for r in rows] == base
    burst = [r for rows in ref.batches("burst") for r in rows]
    deleted = dict(tpch_lineitem_refresh.delete_keys(cfg, seed))
    orders = cfg["scale"]["refresh_pairs"] * cfg["scale"]["refresh_orders"]
    assert len(deleted) == orders == 24
    assert sorted(deleted) == sorted({r["l_orderkey"] for r in base})[:orders]
    # RF1: as many new orders, 1-7 lines, keys the population leaves free
    new_orders = {r["l_orderkey"] for r in burst}
    assert len(new_orders) == orders
    assert all(8 < k % 32 <= 16 for k in new_orders)
    assert not new_orders & {r["l_orderkey"] for r in base}
    assert all(1 <= n <= 7 for n in (
        sum(r["l_orderkey"] == k for r in burst) for k in new_orders))
    table = [r for r in base if r["l_orderkey"] not in deleted] + burst
    assert len(base) - len(table) + len(burst) == sum(deleted.values())
    assert ref.filled == len(table)
    assert (ref.inserted, ref.deleted) == (len(burst), sum(deleted.values()))
    for c in tpch_lineitem.COLS:
        want = [ord(r[c]) if isinstance(r[c], str) else r[c] for r in table]
        assert ref.col[c][:ref.filled].tolist() == want, c
    hit = [r for r in table if 8766 <= r["l_shipdate"] < 9131
           and 4 <= r["l_discount"] <= 6 and r["l_quantity"] < 25]
    assert ref.q6(8766, 9131, 4, 6, 25) == [[sum(
        r["l_extendedprice"] * r["l_discount"] for r in hit)]] and hit
    groups = {}
    for r in table:
        if r["l_shipdate"] <= 10471:
            g = groups.setdefault((r["l_returnflag"], r["l_linestatus"]),
                                  [0, 0, 0])
            g[0] += r["l_quantity"]
            g[1] += r["l_extendedprice"]
            g[2] += 1
    assert [(row[0], row[1], row[2], row[3], row[8])
            for row in ref.q1(10471)] == [
        (f, s, q, p, n) for (f, s), (q, p, n) in sorted(groups.items())]


def test_refresh_generator_deletes_what_the_reference_deleted():
    """The load generator takes its DELETE statements from the reference
    module, from the plan's seed and configuration alone (no table)."""
    from benchmark.generators import sql_streams_refresh
    from benchmark.references import tpch_lineitem_refresh
    from benchmark.run import load_json

    cfg = _refresh_config()
    traffic = load_json("benchmark", "traffic",
                        "tpch_streams_1_after_refresh.json")
    assert traffic["generator"] == "sql_streams_refresh"
    plan = {"params": traffic["params"], "seed": 77, "workers": 1,
            "worker": 0, "addr": {"pg": ["127.0.0.1", 1]},
            "config": {k: cfg[k] for k in ("schema", "scale", "load")}}
    gen = sql_streams_refresh.Generator(plan)
    assert gen.refresh_connections == 6
    assert gen.refresh_keys == tpch_lineitem_refresh.delete_keys(cfg, 77)
    ref = tpch_lineitem_refresh.Reference(cfg, 77)
    ref.fill()
    assert ref.deleted == sum(lines for _k, lines in gen.refresh_keys)
    # the statements of the window are cell 1's, letter for letter
    assert traffic["params"]["statements"] == load_json(
        "benchmark", "traffic", "tpch_streams_1.json")["params"]["statements"]


@pytest.mark.parametrize("seed", [1, 2_147_483_659, 77])
def test_refresh_control_fails_both_ways(seed):
    """``benchmark/control.py`` for the new cell: the reference's own
    answers pass, the float32 sums fail AND the table without RF2 fails."""
    from benchmark.control import control

    out = control("tpch_q1q6_after_refresh", seed, small=True)
    assert out["sound_wrong"] == 0
    assert out["control_wrong_float32"] > 0
    assert out["control_wrong_without_rf2"] > 0
    assert out["passed"]
