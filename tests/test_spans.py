"""The span system from the PG wire to the device fetch (utils/trace.py
and the read path it is called from): one request id across the hops,
the engine's phases once a batch on every route, the RPC layer's queue
wait, names for the device programs, and the bytes a program reads.
docs/observability.md lists what is asserted here, span by span.
"""

import json
import subprocess
import sys
import threading
import time

import pytest

from tests.test_gather import _key_lower, _load
from yugabyte_db_tpu.models.encoding import prefix_successor
from yugabyte_db_tpu.storage import AggSpec, Predicate, ScanSpec
from yugabyte_db_tpu.utils import jitting, metrics, trace
from yugabyte_db_tpu.utils.fault_injection import arm_fault_once

PHASES = ("issue", "wait_fetch", "finish")
ISSUE_PARTS = ("plan", "dispatch", "copy_out")


# -- the primitive ------------------------------------------------------------

def test_span_without_a_trace_costs_its_histogram_only():
    h = metrics.span_histogram("unit.alone")
    ring = len(trace.TRACE_EVENTS.dump()["traceEvents"])
    n = h.count
    with trace.span("unit.alone"):
        pass
    assert h.count == n + 1
    assert len(trace.TRACE_EVENTS.dump()["traceEvents"]) == ring


def test_span_lands_in_trace_histogram_and_ring_with_its_parent():
    h = metrics.span_histogram("unit.inner")
    n = h.count
    with trace.trace_request("svc.method") as t:
        with trace.span("unit.outer", tablet="t1") as sp:
            with trace.span("unit.inner"):
                time.sleep(0.002)
            sp.labels["rows"] = 3    # a label known only at the end
    assert h.count == n + 1 and h.sum >= 2000     # microseconds
    spans = {s["name"]: s for s in t.dump()["spans"]}
    assert spans["unit.inner"]["parent"] == "unit.outer"
    assert spans["unit.outer"]["parent"] == "svc.method"
    assert spans["unit.outer"]["tablet"] == "t1"
    assert spans["unit.outer"]["rows"] == 3
    assert spans["unit.outer"]["duration_us"] >= \
        spans["unit.inner"]["duration_us"] >= 2000
    assert spans["unit.inner"]["start_us"] >= 0
    mine = [e for e in trace.TRACE_EVENTS.dump()["traceEvents"]
            if e["name"] == "unit.inner"]
    assert mine[-1]["args"]["trace_id"] == t.trace_id
    # the ring's clock is the wall clock, in microseconds
    assert abs(mine[-1]["ts"] / 1e6 - time.time()) < 60


def test_a_mesh_phase_hook_names_a_part_of_the_issue_apart():
    """``tserver/mesh_scan.py:_phase`` is the hook
    ``sharded_grouped_aggregate`` times itself with: a phase goes to
    ``yb_engine_phase_us{phase, route="mesh"}``, a part of the issue to
    ``yb_mesh_issue_part_us{part}`` as span ``engine.issue.<part>``,
    each published where it ends, under the request that is open."""
    from yugabyte_db_tpu.tserver.mesh_scan import _phase

    phase_h = metrics.engine_phase_histogram("issue", "mesh")
    part_h = metrics.mesh_issue_part_histogram("lower")
    n_phase, n_part = phase_h.count, part_h.count
    with trace.trace_request("ts.multi_agg_scan") as t:
        with _phase("issue"):
            with _phase("issue", "lower"):
                pass
            assert (phase_h.count, part_h.count) == (n_phase, n_part + 1)
    assert (phase_h.count, part_h.count) == (n_phase + 1, n_part + 1)
    spans = {s["name"]: s for s in t.dump()["spans"]}
    assert spans["engine.issue.lower"]["parent"] == "engine.issue"
    assert spans["engine.issue"]["parent"] == "ts.multi_agg_scan"
    assert spans["engine.issue.lower"]["route"] == "mesh"


def test_the_ring_is_dumped_while_many_threads_record_into_it():
    """The ring is on every span's path and a dump is an operator's
    click: a dump taken while eight threads record loses nothing it
    should hold and never raises, whatever the interleaving."""
    import sys

    ring = trace.TraceEventLog(capacity=512)
    stop = threading.Event()
    dumps, errors = [], []

    def write(k):
        for i in range(20_000):
            ring.record("unit.ring", i, k, None, "t%d" % k)

    def read():
        try:
            while not stop.is_set():
                dumps.append(len(ring.dump()["traceEvents"]))
        except Exception as e:  # noqa: BLE001 - the test's whole point
            errors.append(e)

    interval = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        reader = threading.Thread(target=read)
        writers = [threading.Thread(target=write, args=(k,))
                   for k in range(8)]
        reader.start()
        for w in writers:
            w.start()
        for w in writers:
            w.join(timeout=60)
        stop.set()
        reader.join(timeout=60)
    finally:
        sys.setswitchinterval(interval)
    assert not errors, errors
    assert not reader.is_alive() and not any(w.is_alive() for w in writers)
    assert dumps and max(dumps) <= 512
    last = ring.dump()["traceEvents"]
    assert len(last) == 512
    assert {e["args"]["trace_id"] for e in last} <= {"t%d" % k
                                                    for k in range(8)}


def test_span_takes_a_histogram_of_its_own_in_seconds():
    h = metrics.jit_compile_histogram("unit_entry")
    trace.record_span("engine.compile", time.time_ns(), 250_000, h,
                      seconds=True, entry="unit_entry")
    assert h.count == 1 and h.sum == pytest.approx(0.25)
    text = metrics.process_registry().prometheus_text()
    assert 'yb_jit_compile_seconds_count{entry="unit_entry"} 1' in text


def test_trace_id_is_injected_and_adopted():
    payload = {"tablet_id": "x"}
    trace.inject(payload)              # no active trace: no keys
    assert payload == {"tablet_id": "x"}
    with trace.trace_request("pg.statement") as t:
        with trace.span("client.call"):
            trace.inject(payload)
    assert payload["trace_id"] == t.trace_id
    assert payload["parent_span"] == "client.call"
    with trace.adopted("ts.scan", payload) as server:
        assert trace._current.get() is server
    assert server.trace_id == t.trace_id
    assert server.dump()["parent_span"] == "client.call"
    with trace.adopted("raft.append", ["not", "a", "dict"]) as own:
        pass
    assert own.trace_id != t.trace_id


def test_in_context_carries_the_trace_to_a_pool_thread():
    from concurrent.futures import ThreadPoolExecutor

    def work():
        trace.record_span("unit.pooled", time.time_ns(), 7)
        out: dict = {}
        trace.inject(out)
        return out

    with ThreadPoolExecutor(max_workers=1) as pool:
        with trace.trace_request("pg.statement") as t:
            bare = pool.submit(work).result()
            carried = pool.submit(trace.in_context(work)).result()
    assert bare == {}
    assert carried["trace_id"] == t.trace_id
    assert [s["name"] for s in t.dump()["spans"]] == ["unit.pooled"]


def test_statement_feeds_the_frontend_histogram_and_rpcz():
    def count():
        return metrics._REQ_LATENCY_ENTITIES["unitproto"].histogram(
            "yb_request_latency_seconds").count

    with trace.statement("unitproto"):
        pass
    n = count()
    with trace.statement("unitproto", n=3) as t:    # a batch of three
        time.sleep(0.001)
    assert count() == n + 3
    sample = trace.FRONTEND_RPCZ.dump()["methods"]["unitproto.statement"][-1]
    assert sample["trace_id"] == t.trace_id
    assert sample["duration_us"] >= 1000


# -- one id from the PG frontend to the engine ---------------------------------

DDL = ("CREATE TABLE lineitem (l_orderkey BIGINT, l_linenumber INT, "
       "l_quantity INT, l_extendedprice BIGINT, l_discount TINYINT, "
       "l_tax TINYINT, l_returnflag TEXT, l_linestatus TEXT, "
       "l_shipdate INT, PRIMARY KEY ((l_orderkey), l_linenumber))")


PG_PARTS = ("parse", "plan", "scans", "combine", "reply")


def _new_series_counts(method):
    return {
        **{"pg." + p: metrics.pg_statement_part_histogram(p).count
           for p in PG_PARTS},
        **{"mesh." + p: metrics.mesh_issue_part_histogram(p).count
           for p in ("lower", "dispatch")},
        "rpc.call": metrics.rpc_call_histogram(method).count,
        "rpc.respond": metrics.rpc_respond_histogram(method).count,
        "pg.scan_wait": metrics.span_histogram("pg.scan_wait").count,
        **{"writes." + by: n for by, n in metrics.rpc_reply_writes().items()},
        **{"reads." + by: n for by, n in metrics.rpc_reply_reads().items()},
    }


@pytest.fixture(scope="module", params=[1, 8])
def pg_q6(request, tmp_path_factory):
    """ONE Q6 over a flushed two-tablet LINEITEM, PG wire -> pg-docop
    worker -> socket RPC -> the one tserver that leads both tablets
    (``tests/test_mesh_route.py``'s cluster), and what it left behind:
    the frontend's /rpcz sample, the tserver's /rpcz over HTTP, how far
    each histogram grew. ``request.param`` is the chips the tserver
    reports: with one, a ``ts.scan`` a tablet; with several, ONE
    ``ts.multi_agg_scan``."""
    from yugabyte_db_tpu.drivers.minipg import PgConnection
    from yugabyte_db_tpu.integration import MiniCluster
    from yugabyte_db_tpu.tools.admin_client import AdminClient
    from yugabyte_db_tpu.tserver.tablet_server import TabletServer
    from yugabyte_db_tpu.yql.pgsql import tpch

    chips = request.param
    method, sent = (("ts.scan", 2) if chips == 1
                    else ("ts.multi_agg_scan", 1))
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(TabletServer, "local_chips", lambda self: chips)
        mc = MiniCluster(str(tmp_path_factory.mktemp(f"pg_q6_{chips}")),
                         num_masters=1, num_tservers=1,
                         transport="socket").start()
        srv = None
        try:
            mc.wait_tservers_registered()
            srv, addr = mc.start_pg_server(engine="tpu", num_tablets=2,
                                           replication_factor=1,
                                           rpc_timeout_s=120)
            conn = PgConnection(*addr, timeout=120)
            conn.execute(DDL)
            cols = ("l_orderkey", "l_linenumber", "l_quantity",
                    "l_extendedprice", "l_discount", "l_tax",
                    "l_returnflag", "l_linestatus", "l_shipdate")
            values = ",".join(
                "(" + ",".join(repr(r[c]) for c in cols) + ")"
                for r in tpch.generate_lineitem(120))
            conn.execute(f"INSERT INTO lineitem ({','.join(cols)}) "
                         f"VALUES {values}")
            assert AdminClient(mc.transport, mc.master_uuids).flush_table(
                "lineitem") == 2
            before = _new_series_counts(method)
            conn.execute(tpch.q6_sql())
            conn.close()
            after = _new_series_counts(method)
            ts = next(iter(mc.tservers.values()))
            import urllib.request

            host, port = mc.start_webservers()[ts.uuid]
            with urllib.request.urlopen(f"http://{host}:{port}/rpcz",
                                        timeout=5) as r:
                rpcz = json.load(r)
            front = trace.FRONTEND_RPCZ.dump()["methods"][
                "pg.statement"][-1]
            yield {
                "chips": chips, "method": method, "sent": sent,
                "grew": {k: after[k] - before[k] for k in after},
                "front": front, "rpcz": rpcz,
                "mine": [s for s in rpcz["methods"][method]
                         if s["trace_id"] == front["trace_id"]],
                "tserver_text": ts.metrics.prometheus_text(),
            }
        finally:
            if srv is not None:
                srv.shutdown()
            mc.shutdown()


def test_request_id_survives_frontend_pool_rpc_and_tserver(pg_q6):
    """PG frontend -> pg-docop worker -> RPC payload ->
    TabletServer.handle: one /rpcz sample of the tserver holds the
    engine's three phases under the id the frontend gave the statement.
    On a node with one chip that is one ``ts.scan`` a tablet; where the
    tserver reports several and leads both tablets, ONE
    ``ts.multi_agg_scan`` with the mesh's phases."""
    method, sent = pg_q6["method"], pg_q6["sent"]
    # a unit a scan: two tablets through the pg-docop pool, or the
    # leader's group as one
    assert pg_q6["grew"]["pg.scan_wait"] == sent
    names = [s["name"] for s in pg_q6["front"]["spans"]]
    assert names.count("pg.scan_wait") == sent and "rpc.queue" in names
    assert pg_q6["rpcz"]["frontends"]["methods"]["pg.statement"]
    assert len(pg_q6["mine"]) == sent
    for sample in pg_q6["mine"]:
        assert sample["parent_span"] == "pg.statement"
        spans = {s["name"]: s for s in sample["spans"]}
        for phase in PHASES:
            assert spans["engine." + phase]["parent"] == method
            assert spans["engine." + phase]["duration_us"] >= 0
            if pg_q6["chips"] > 1:
                assert spans["engine." + phase]["route"] == "mesh"
        assert spans["rpc.queue"]["duration_us"] >= 0
    assert f'rpc_queue_us_count{{daemon="tserver",method="{method}"' \
        in pg_q6["tserver_text"]


def test_a_pg_statement_outside_its_handlers_is_five_parts(pg_q6):
    """Exactly one ``pg.parse``, ``pg.plan``, ``pg.scans``,
    ``pg.combine``, ``pg.reply`` under the statement, each observed
    once in ``yb_pg_statement_part_us{part}``; together no more than
    the statement, in the order a statement takes them."""
    front = pg_q6["front"]
    parts = [s for s in front["spans"]
             if s["name"] in {"pg." + p for p in PG_PARTS}]
    assert [s["name"] for s in parts] == ["pg." + p for p in PG_PARTS]
    for s in parts:
        assert s["parent"] == "pg.statement"
        assert pg_q6["grew"][s["name"]] == 1
    assert sum(s["duration_us"] for s in parts) <= front["duration_us"]
    by = {s["name"]: s for s in parts}
    assert by["pg.scans"]["units"] == pg_q6["sent"]
    assert by["pg.scans"]["duration_us"] > 0
    starts = [s["start_us"] for s in parts]
    assert starts == sorted(starts)
    # the last unit's answer in hand, not before
    assert by["pg.combine"]["start_us"] >= \
        by["pg.scans"]["start_us"] + by["pg.scans"]["duration_us"] - 1


def test_each_unit_has_its_callers_round_trip_and_a_reply(pg_q6):
    """One ``rpc.call`` a unit in the statement's own sample (the
    pg-docop worker runs under its Trace), no shorter than what the
    tserver's sample of that call holds; ``rpc_respond_us{method}``
    grows once a call, ``rpc_call_us{method}`` too."""
    method, sent = pg_q6["method"], pg_q6["sent"]
    calls = [s for s in pg_q6["front"]["spans"]
             if s["name"] == "rpc.call" and s["method"] == method]
    assert len(calls) == sent
    assert pg_q6["grew"]["rpc.call"] == sent
    assert pg_q6["grew"]["rpc.respond"] == sent
    served = sorted(s["duration_us"] for s in pg_q6["mine"])
    assert len(served) == sent
    for call, handler in zip(sorted(s["duration_us"] for s in calls),
                             served):
        assert call >= handler


def test_who_moved_each_reply_is_counted_by_label(pg_q6):
    """``rpc_reply_writes{by}`` grows once a reply of a socket server
    (the units' and the PG frontend's own to its client among them),
    ``rpc_reply_reads{by}`` once a call over a socket that got its
    reply; both series stand in the process registry under both
    labels."""
    grew, sent = pg_q6["grew"], pg_q6["sent"]
    assert grew["writes.worker"] + grew["writes.reactor"] >= sent + 1
    assert grew["writes.worker"] >= 1
    assert grew["reads.caller"] + grew["reads.peer"] >= sent
    assert grew["reads.caller"] >= 1
    text = metrics.process_registry().prometheus_text()
    for series, labels in (("rpc_reply_writes", metrics.RPC_REPLY_WRITERS),
                           ("rpc_reply_reads", metrics.RPC_REPLY_READERS)):
        assert f"# TYPE {series} counter" in text
        for by in labels:
            assert f'{series}{{by="{by}"}} ' in text


def test_a_mesh_request_issues_in_two_parts(pg_q6):
    """``engine.issue.lower`` and ``engine.issue.dispatch`` once each
    inside ``engine.issue`` of a ``ts.multi_agg_scan``; a per-tablet
    scan has neither."""
    grew = (pg_q6["grew"]["mesh.lower"], pg_q6["grew"]["mesh.dispatch"])
    if pg_q6["chips"] == 1:
        assert grew == (0, 0)
        return
    assert grew == (1, 1)
    (sample,) = pg_q6["mine"]
    spans = {s["name"]: s for s in sample["spans"]}
    lower, disp = spans["engine.issue.lower"], spans["engine.issue.dispatch"]
    issue = spans["engine.issue"]
    assert lower["parent"] == disp["parent"] == "engine.issue"
    assert lower["route"] == disp["route"] == "mesh"
    assert lower["duration_us"] + disp["duration_us"] <= issue["duration_us"]
    assert issue["start_us"] <= lower["start_us"] <= disp["start_us"]


def test_a_local_call_observes_no_rpc_call(tmp_path):
    """Over ``LocalTransport`` there is no ``Proxy.call`` and no
    ``Messenger._dispatch``: no ``rpc.call``, no ``rpc.respond``, as
    there is no ``rpc.queue``."""
    from yugabyte_db_tpu.integration import MiniCluster

    def series():
        text = metrics.process_registry().prometheus_text()
        return sorted(line for line in text.splitlines()
                      if line.startswith(("rpc_call_us_count",
                                          "rpc_respond_us_count")))

    mc = MiniCluster(str(tmp_path), num_masters=1, num_tservers=1).start()
    try:
        mc.wait_tservers_registered()
        before = series()
        with trace.trace_request("unit.local") as t:
            assert len(mc.client().list_tservers()) == 1
        assert series() == before
        assert not [s for s in t.dump()["spans"]
                    if s["name"].startswith("rpc.")]
    finally:
        mc.shutdown()


# -- the engine's phases, once a batch, on every route -------------------------

def _phase_counts(route):
    return [metrics.engine_phase_histogram(p, route).count for p in PHASES]


def _specs_for(route, schema, tpu, ht):
    lo = _key_lower(schema, 40)
    if route == "host":           # exact-key read without a page plan
        return [ScanSpec(lower=lo, upper=prefix_successor(lo),
                         read_ht=ht + 1)]
    if route == "page":
        return [ScanSpec(lower=lo, read_ht=ht + 1, limit=20,
                         predicates=[Predicate("d", ">=", 30)],
                         projection=["k", "r", "a", "d"])]
    if route == "gather":
        return [ScanSpec(read_ht=ht + 1,
                         predicates=[Predicate("d", ">=", 30)],
                         projection=["k", "r", "a"])]
    if route == "agg_deferred":
        return [ScanSpec(read_ht=ht + 1, aggregates=[
            AggSpec("count", None), AggSpec("sum", "a")])]
    if route == "grouped_deferred":
        return [ScanSpec(read_ht=ht + 1, group_by=["d"],
                         aggregates=[AggSpec("count", None)])]
    if route == "issued":         # overlay aggregate: run + live memtable
        from yugabyte_db_tpu.storage.row_version import RowVersion

        cid = {c.name: c.col_id for c in schema.columns}
        tpu.apply([RowVersion(lo, ht=ht + 5, liveness=True,
                              columns={cid["a"]: 5, cid["d"]: 1})])
        return [ScanSpec(read_ht=ht + 10, aggregates=[
            AggSpec("count", None), AggSpec("sum", "a")])]
    if route == "overlay_deferred":   # grouped: run + live memtable
        _specs_for("issued", schema, tpu, ht)
        return [ScanSpec(read_ht=ht + 10, group_by=["d"],
                         aggregates=[AggSpec("count", None)])]
    if route == "mixed":
        return _specs_for("page", schema, tpu, ht) \
            + _specs_for("agg_deferred", schema, tpu, ht)
    raise AssertionError(route)


@pytest.mark.parametrize("route", [
    "host", "page", "gather", "agg_deferred", "grouped_deferred", "issued",
    "overlay_deferred", "mixed", "breaker_host"])
def test_each_engine_phase_is_observed_once_a_batch(route):
    schema, _cpu, tpu, ht = _load(300)
    if route == "breaker_host":
        specs = _specs_for("agg_deferred", schema, tpu, ht)
        arm_fault_once("fault.tpu_dispatch")
    else:
        specs = _specs_for(route, schema, tpu, ht)
    plans = [tpu._plan_scan(s)[0] for s in specs]
    if route not in ("mixed", "breaker_host"):
        assert plans == [route]     # the tag is _plan_scan's own
    before = _phase_counts(route)
    with trace.trace_request("ts.scan") as t:
        results = tpu.scan_batch(specs)
    assert len(results) == len(specs)
    assert _phase_counts(route) == [n + 1 for n in before]
    engine = [s for s in t.dump()["spans"] if s["name"].startswith("engine.")
              and s["name"] != "engine.compile"
              and s["name"] != "engine.upload"]
    parts = [s for s in engine if s["name"].startswith("engine.issue.")]
    spans = [s for s in engine if s not in parts]
    assert sorted(s["name"] for s in spans) == sorted(
        "engine." + p for p in PHASES)
    assert {s["route"] for s in spans} == {route}
    # The issue phase's three parts, once a batch that reached the
    # device path, inside engine.issue and adding up to less than it.
    assert sorted(s["name"] for s in parts) == (
        [] if route == "breaker_host" else sorted(
            "engine.issue." + p for p in ISSUE_PARTS))
    assert {s["parent"] for s in parts} <= {"engine.issue"}
    issue = next(s for s in spans if s["name"] == "engine.issue")
    assert sum(s["duration_us"] for s in parts) <= issue["duration_us"]
    if route == "breaker_host":
        assert tpu.breaker.stats()["last_error"] is not None


def test_a_fault_at_the_fetch_moves_the_batch_to_the_breaker_route(
        monkeypatch):
    schema, _cpu, tpu, ht = _load(200)
    specs = _specs_for("agg_deferred", schema, tpu, ht)
    tpu.scan_batch(specs)    # compiled and resident
    batch = tpu.scan_batch_async(specs)
    assert batch.route == "agg_deferred"

    def boom(_tree):
        raise RuntimeError("device lost")

    monkeypatch.setattr(batch, "_fetch", boom)
    before = _phase_counts("breaker_host")
    assert batch.finish()[0].rows
    assert _phase_counts("breaker_host")[1:] == [n + 1 for n in before[1:]]


def _moved(entry):
    text = metrics.process_registry().prometheus_text()
    return tuple(_series(text, name, entry=entry, **labels) for name, labels
                 in (("yb_device_dispatches", {}),
                     ("yb_device_transfers", {"dir": "h2d"}),
                     ("yb_device_transfers", {"dir": "d2h"})))


@pytest.mark.parametrize("n_specs,entry", [(1, "grouped_aggregate"),
                                           (3, "batched_grouped")])
def test_a_grouped_batch_moves_one_array_each_way(n_specs, entry):
    """A grouped scan costs the runtime three operations: its parameters
    go up as one array, one program runs, its answer comes down as one
    (``yb_device_transfers`` over ``yb_device_dispatches`` = 1 and 1),
    alone or as the lanes of one vmapped dispatch; and the issue phase
    opens its three parts once a batch."""
    import jax

    schema, cpu, tpu, ht = _load(300)
    specs = [ScanSpec(read_ht=ht + 1, group_by=["d"],
                      predicates=[Predicate("a", ">=", 10 * i)],
                      aggregates=[AggSpec("count", None),
                                  AggSpec("sum", "a")])
             for i in range(n_specs)]
    tpu.scan_batch(specs)    # compiled and resident
    before = _moved(entry)
    parts = [metrics.engine_issue_part_histogram(p).count
             for p in ISSUE_PARTS]
    batch = tpu.scan_batch_async(specs)
    outs = {id(o): o for _pi, o, _fin in batch.issued_outs}
    assert len(outs) == 1                       # the lanes share it
    (out,) = jax.tree.leaves(list(outs.values()))
    assert isinstance(out, jax.Array) and out.dtype == "int32"
    assert out.ndim == (1 if n_specs == 1 else 2)
    got = batch.finish()
    assert [m - b for m, b in zip(_moved(entry), before)] == [1, 1, 1]
    assert [metrics.engine_issue_part_histogram(p).count
            for p in ISSUE_PARTS] == [n + 1 for n in parts]
    assert [r.rows for r in got] == [cpu.scan(s).rows for s in specs]


@pytest.mark.parametrize("encoding,form", [("auto", "direct"),
                                           ("off", "hashed")])
def test_a_q1_shaped_batch_counts_its_bucket_form_once_a_dispatch(
        encoding, form):
    """``yb_grouped_buckets{form}`` beside ``yb_device_dispatches``: a
    GROUP BY over two string columns is addressed by their dictionary
    codes where the resident run holds "dict" leaves (uploaded encoded)
    and hashed where it holds plain prefix planes (--tpu_plane_encoding
    off); either way one array goes up, one program runs, one array
    comes down, and the answer is the CPU engine's. An ungrouped
    expression aggregate (Q6's shape) counts in neither form."""
    from tests.test_group_agg import Q1_AGGS, Q6_AGGS
    from tests.test_group_agg import _load as load_lineitem_like
    from yugabyte_db_tpu.ops import encodings
    from yugabyte_db_tpu.utils.flags import FLAGS

    old = FLAGS.get("tpu_plane_encoding")
    FLAGS.set("tpu_plane_encoding", encoding)
    try:
        cpu, tpu, ht = load_lineitem_like(num=400, host_flush=True)
        spec = ScanSpec(read_ht=ht + 1, group_by=["flag", "status"],
                        aggregates=list(Q1_AGGS),
                        predicates=[Predicate("d", "<", 900)])
        q6 = ScanSpec(read_ht=ht + 1, aggregates=list(Q6_AGGS),
                      predicates=[Predicate("qty", "<", 25)])
        tpu.scan_batch([spec])    # compiled and resident
        cmp = tpu.runs[0].dev.arrays["cols"][
            tpu._name_to_id["flag"]]["cmp"]
        assert encodings.leaf_kind(cmp) == (
            "dict" if form == "direct" else None)
        before, moved = metrics.grouped_buckets(), _moved("grouped_aggregate")
        fallbacks = metrics.grouped_agg_fallbacks()
        got = tpu.scan_batch([spec])
        assert metrics.grouped_buckets() == dict(before,
                                                 **{form: before[form] + 1})
        assert [m - b for m, b in zip(_moved("grouped_aggregate"),
                                      moved)] == [1, 1, 1]
        assert got[0].rows == cpu.scan(spec).rows and len(got[0].rows) == 6
        assert tpu.scan_batch([q6])[0].rows == cpu.scan(q6).rows
        assert metrics.grouped_buckets() == dict(before,
                                                 **{form: before[form] + 1})
        assert metrics.grouped_agg_fallbacks() == fallbacks
    finally:
        FLAGS.set("tpu_plane_encoding", old)
    text = metrics.process_registry().prometheus_text()
    for f in ("direct", "hashed"):
        assert f'yb_grouped_buckets{{form="{f}"}}' in text


def test_first_scan_records_upload_and_compile():
    schema, _cpu, tpu, ht = _load(150, seed=23)
    uploads = metrics.device_upload_histogram().count
    text = metrics.process_registry().prometheus_text()
    up_bytes = _series(text, "yb_device_upload_bytes")
    tpu.runs[0].invalidate_device()
    with trace.trace_request("ts.scan") as t:
        tpu.scan(ScanSpec(read_ht=ht + 1, group_by=["d"], aggregates=[
            AggSpec("sum", "a"), AggSpec("count", None)]))
    assert metrics.device_upload_histogram().count == uploads + 1
    text = metrics.process_registry().prometheus_text()
    assert _series(text, "yb_device_upload_bytes") > up_bytes
    names = [s["name"] for s in t.dump()["spans"]]
    assert "engine.upload" in names
    up = next(s for s in t.dump()["spans"] if s["name"] == "engine.upload")
    assert up["bytes"] == tpu.runs[0].dev.nbytes


def _series(text, name, **labels):
    total = 0.0
    for line in text.splitlines():
        if line.startswith(name + "{") or line.startswith(name + " "):
            if all(f'{k}="{v}"' in line for k, v in labels.items()):
                total += float(line.rsplit(" ", 1)[1])
    return total


def test_compile_seconds_are_recorded_beside_the_count():
    import jax.numpy as jnp

    @jitting.compile_contract("test_compile_seconds", max_compiles=2)
    def factory():
        return jitting.jit(lambda x: x + 1, "test_compile_seconds")

    fn = factory()
    h = metrics.jit_compile_histogram("test_compile_seconds")
    with trace.trace_request("ts.scan") as t:
        fn(jnp.ones(3))
        fn(jnp.ones(3))      # a hit: no compile, no span
    assert metrics.jit_compiles("test_compile_seconds") == 1
    assert h.count == 1 and h.sum > 0
    spans = [s for s in t.dump()["spans"] if s["name"] == "engine.compile"]
    assert len(spans) == 1 and spans[0]["entry"] == "test_compile_seconds"
    assert spans[0]["duration_us"] > 0


# -- the RPC layer's queue ------------------------------------------------------

def test_rpc_queue_grows_when_the_worker_pool_is_held_busy():
    from yugabyte_db_tpu.rpc import Messenger
    from yugabyte_db_tpu.rpc.proxy import Proxy

    hist = metrics.MetricRegistry().entity(method="m").histogram(
        "rpc_queue_us")
    waits = []

    def handler(method, body):
        with trace.trace_request(method) as t:
            trace.record_queue_wait(hist)
            waits.append([s["duration_us"] for s in t.dump()["spans"]
                          if s["name"] == "rpc.queue"])
        time.sleep(0.15)
        return body

    m = Messenger("queue-test", num_workers=1)
    try:
        host, port = m.listen("127.0.0.1", 0, handler)
        proxies = [Proxy(host, port) for _ in range(3)]
        threads = [threading.Thread(target=p.call, args=("m", i))
                   for i, p in enumerate(proxies)]
        for th in threads:
            th.start()
        for th in threads:
            th.join()
        for p in proxies:
            p.close()
    finally:
        m.shutdown()
    assert hist.count == 3
    flat = sorted(w[0] for w in waits)
    assert flat[0] < 100_000            # the first found the worker free
    assert flat[1] >= 100_000           # the others waited out a handler
    assert flat[2] >= 250_000
    assert hist.sum >= 350_000
    # a handler's thread keeps no stamp for the next call
    assert trace._arrival.get() is None


def test_a_local_call_has_no_queue_wait():
    hist = metrics.MetricRegistry().entity().histogram("rpc_queue_us")
    with trace.trace_request("ts.scan"):
        trace.record_queue_wait(hist)
    assert hist.count == 0


# -- names for the device programs ---------------------------------------------

def _build_entries():
    """One jitted callable of every @compile_contract entry, built (not
    compiled) from a small signature."""
    import jax
    import numpy as np
    from jax.sharding import Mesh

    from yugabyte_db_tpu.parallel import sharded
    from yugabyte_db_tpu.ops import (agg_fold, compact, flat_fold, flush,
                                     group_agg, lookback_fold, row_gather,
                                     scan as dscan, seg_fold)
    from yugabyte_db_tpu.storage.tpu_engine import TpuStorageEngine as E
    from yugabyte_db_tpu.yql.pgsql import tpch

    schema = tpch.lineitem_schema()
    eng = E(schema, {"rows_per_block": 64})
    cols = eng._col_sigs()
    sig = dscan.ScanSig(B=8, R=64, K=8, cols=cols, preds=(),
                        aggs=(dscan.AggSig("count", None, None),),
                        apply_preds=True, flat=True)
    multi = dscan.ScanSig(B=8, R=64, K=8, cols=cols, preds=(),
                          aggs=sig.aggs, apply_preds=True, lookback=2)
    gsig = group_agg.GroupAggSig(
        B=8, R=64, K=8, NB=512, cols=cols, preds=(), apply_preds=True,
        flat=True, group_cols=(), aggs=(group_agg.GAgg("count", None),))
    gather = row_gather.GatherSig(B=8, R=64, K=8, M=64, cols=cols,
                                  preds=(), apply_preds=True, out_cols=())
    mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("t", "b"))
    return {
        "dist_agg": sharded._compiled_dist_agg(sig, mesh, ((), ()), 1, 4),
        "dist_page": sharded._compiled_dist_page(gather, mesh, ((), ()),
                                                 1, 4),
        "dist_grouped_aggregate": sharded._compiled_dist_grouped(
            gsig, mesh, ((), ()), 1, 4),
        "stack_update": sharded._compiled_stack_update(2, 8, 64, ()),
        "flat_aggregate": flat_fold.compiled_flat_aggregate(sig),
        "lookback_aggregate":
            lookback_fold.compiled_lookback_aggregate(multi),
        "seg_aggregate": seg_fold.compiled_seg_aggregate(multi),
        "full_aggregate": agg_fold.compiled_full_aggregate(sig),
        "scan_window": dscan.compiled_scan(sig),
        "grouped_aggregate": group_agg.compiled_grouped(gsig),
        "gather_batch": row_gather.compiled_gather_batch(gather, 2),
        "gc_mask": compact.compiled_gc_mask(3, 512),
        "resident_gc_mask": compact.resident_gc_mask,
        "replay_flush": flush.replay_flush,
        "batched_grouped": E._batched_grouped_fn(gsig),
        "batched_agg": E._batched_agg_fn("flat", sig),
        "scatter_invalid": E.scatter_invalid,
        "scatter_invalid_bits": E.scatter_invalid_bits,
    }


def test_every_entry_names_its_program_after_itself():
    built = _build_entries()
    # (other tests declare toy entries, all called test_* or toy_*)
    declared = {e for e in jitting.declared_contracts()
                if not e.startswith(("test_", "toy_"))}
    assert declared == set(built), sorted(declared ^ set(built))
    for entry, fn in built.items():
        name = fn.__name__
        assert name.startswith(entry), (entry, name)
        assert "unknown" not in name and name != "fn"
    # the module a device trace shows is jit_<that name>
    import jax.numpy as jnp

    text = built["stack_update"].lower(
        jnp.zeros((2, 8)), jnp.zeros((1, 8)), 0).as_text()
    assert text.splitlines()[0].startswith("module @jit_stack_update_c0_")
    text = built["scatter_invalid"].lower(
        jnp.ones((2, 4), bool), jnp.zeros(1, jnp.int32)).as_text()
    assert text.splitlines()[0].startswith("module @jit_scatter_invalid ")


def test_q1_and_q6_get_different_names_that_keep_the_old_patterns():
    from yugabyte_db_tpu.storage.tpu_engine import TpuStorageEngine
    from yugabyte_db_tpu.yql.pgsql import tpch

    schema = tpch.lineitem_schema()
    eng = TpuStorageEngine(schema, {"rows_per_block": 64})
    ht = tpch.load_engine(eng, schema, 300)
    names = []
    for spec in (tpch.q1_spec(ht + 1), tpch.q6_spec(ht + 1)):
        _kind, (sig, _params) = eng._grouped_prep(eng.runs[0], spec,
                                                  spec.predicates)
        from yugabyte_db_tpu.ops import group_agg

        names.append(group_agg.compiled_grouped(sig).__name__)
        # the name does not move with the run's size
        import dataclasses

        assert dataclasses.replace(sig, B=sig.B * 4, R=128).tag() == \
            sig.tag()
    assert names[0] != names[1]
    assert names[0].startswith("grouped_aggregate_g2a5p1f1_")
    assert names[1].startswith("grouped_aggregate_g0a1p5f1_")


def test_two_processes_give_the_same_tag():
    code = (
        "from yugabyte_db_tpu.ops import group_agg, scan\n"
        "cols = (scan.ColSig(3, 'i64'), scan.ColSig(4, 'str'))\n"
        "sig = group_agg.GroupAggSig(B=64, R=2048, K=8, NB=512, cols=cols,"
        " preds=(scan.PredSig(3, 'i64', '<='),), apply_preds=True,"
        " flat=True, group_cols=((4, 2),), aggs=(group_agg.GAgg("
        "'sum_prod', 3, planes=2, factors=(('-', ('k', 100), ('c', 5)),),"
        " need_cols=(3, 5)), group_agg.GAgg('count', None)))\n"
        "print(sig.tag())\n")
    tags = set()
    for seed in ("1", "2"):
        out = subprocess.run(
            [sys.executable, "-c", code], capture_output=True, text=True,
            env={"PYTHONHASHSEED": seed, "JAX_PLATFORMS": "cpu",
                 "PATH": "/usr/bin:/bin"}, check=True, cwd=".")
        tags.add(out.stdout.strip())
    assert len(tags) == 1
    assert tags.pop().startswith("g1a2p1f1_")


# -- the bytes a program reads --------------------------------------------------

def test_read_bytes_count_the_columns_a_q6_shaped_signature_names():
    """A Q6-shaped aggregate over a 16-column LINEITEM reads the value
    planes of its 4 columns, not of all 14 value columns."""
    from yugabyte_db_tpu.models.datatypes import DataType
    from yugabyte_db_tpu.models.partition import compute_hash_code
    from yugabyte_db_tpu.models.schema import (ColumnKind, ColumnSchema,
                                               Schema)
    from yugabyte_db_tpu.ops.device_run import _tree_nbytes
    from yugabyte_db_tpu.storage import make_engine
    from yugabyte_db_tpu.storage.row_version import RowVersion
    from yugabyte_db_tpu.yql.pgsql import tpch

    extra = [ColumnSchema(n, t) for n, t in (
        ("l_partkey", DataType.INT32), ("l_suppkey", DataType.INT32),
        ("l_commitdate", DataType.INT32),
        ("l_receiptdate", DataType.INT32),
        ("l_shipinstruct", DataType.STRING),
        ("l_shipmode", DataType.STRING), ("l_comment", DataType.STRING))]
    schema = Schema(list(tpch.LINEITEM_COLUMNS) + extra,
                    table_id="lineitem16")
    assert len(schema.columns) == 16
    eng = make_engine("tpu", schema, {"rows_per_block": 64})
    cid = {c.name: c.col_id for c in schema.columns}
    rows = []
    for i, row in enumerate(tpch.generate_lineitem(500)):
        kv = {"l_orderkey": row["l_orderkey"],
              "l_linenumber": row["l_linenumber"]}
        vals = {cid[k]: v for k, v in row.items() if k not in kv}
        vals.update({cid["l_partkey"]: i, cid["l_suppkey"]: i % 7,
                     cid["l_commitdate"]: 9000 + i % 50,
                     cid["l_receiptdate"]: 9100 + i % 60,
                     cid["l_shipinstruct"]: "NONE",
                     cid["l_shipmode"]: "AIR",
                     cid["l_comment"]: f"comment {i}"})
        rows.append(RowVersion(
            schema.encode_primary_key(kv, compute_hash_code(schema, kv)),
            ht=100 + i, liveness=True, columns=vals))
    eng.apply(rows)
    eng.flush()

    def counters():
        text = metrics.process_registry().prometheus_text()
        return (_series(text, "yb_device_dispatches",
                        entry="grouped_aggregate"),
                _series(text, "yb_device_program_read_bytes",
                        entry="grouped_aggregate"))

    calls, nbytes = counters()
    res = eng.scan(tpch.q6_spec(10_000))
    assert res.rows
    calls2, nbytes2 = counters()
    assert calls2 == calls + 1

    arrays = eng.runs[0].dev.arrays
    named = ["l_shipdate", "l_discount", "l_quantity", "l_extendedprice"]
    value_cols = [c.col_id for c in schema.value_columns]
    assert len(value_cols) == 14
    mvcc = sum(_tree_nbytes(arrays[n]) for n in (
        "valid", "tomb", "live", "ht_hi", "ht_lo", "exp_hi", "exp_lo"))
    presence = sum(_tree_nbytes(arrays["cols"][c][p])
                   for c in value_cols for p in ("set", "isnull"))
    cmp4 = sum(_tree_nbytes(arrays["cols"][cid[n]]["cmp"]) for n in named)
    cmp_all = sum(_tree_nbytes(arrays["cols"][c]["cmp"])
                  for c in value_cols)
    assert nbytes2 - nbytes == mvcc + presence + cmp4
    assert cmp4 < cmp_all / 2
    assert nbytes2 - nbytes < eng.runs[0].dev.nbytes
