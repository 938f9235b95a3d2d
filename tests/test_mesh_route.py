"""One by-leader routing for multi-tablet aggregates, used by the client
API and the PG frontend (client/mesh_route.py): a leader's tablets go as
ONE ``ts.multi_agg_scan`` where its node reports more than one chip,
GROUP BY and all, and as one ``ts.scan`` a tablet everywhere else; a
reply that is not ``ok`` demotes the group and the answer is the same.
"""

import re

import pytest

from yugabyte_db_tpu.client import mesh_route
from yugabyte_db_tpu.client.meta_cache import TabletLocation
from yugabyte_db_tpu.utils import metrics

pytestmark = pytest.mark.mesh

DDL = ("CREATE TABLE lineitem (l_orderkey BIGINT, l_linenumber INT, "
       "l_quantity INT, l_extendedprice BIGINT, l_discount TINYINT, "
       "l_tax TINYINT, l_returnflag TEXT, l_linestatus TEXT, "
       "l_shipdate INT, PRIMARY KEY ((l_orderkey), l_linenumber))")
COLS = ("l_orderkey", "l_linenumber", "l_quantity", "l_extendedprice",
        "l_discount", "l_tax", "l_returnflag", "l_linestatus", "l_shipdate")


def _loc(tid, leader, chips):
    return TabletLocation(tid, 0, 1, ["ts-0", "ts-1"], leader, {},
                          {"ts-0": chips, "ts-1": 1})


@pytest.mark.parametrize("engine,chips,grouped", [
    ("tpu", 4, ["a", "c"]), ("tpu", 1, []), ("cpu", 4, [])])
def test_leader_groups_rule(engine, chips, grouped):
    """Two or more tablets of one leader whose node has several chips;
    nothing on a one-chip node, on a CPU table, for a lone tablet or one
    without a leader."""
    tablets = [_loc("a", "ts-0", chips), _loc("b", "ts-1", chips),
               _loc("c", "ts-0", chips), _loc("d", None, chips)]
    groups, rest = mesh_route.leader_groups(tablets, engine)
    assert [t.tablet_id for _l, g in groups for t in g] == grouped
    assert [t.tablet_id for t in rest] == [
        t.tablet_id for t in tablets if t.tablet_id not in grouped]
    assert all(leader == "ts-0" for leader, _g in groups)


def _scan_rpcs(ts):
    out = {}
    for line in ts.metrics.prometheus_text().splitlines():
        m = re.match(r'rpc_requests_total\{.*method="(ts\.[a-z_]*scan[a-z_]*)"'
                     r'.*\} (\d+)', line)
        if m:
            out[m.group(1)] = out.get(m.group(1), 0) + int(m.group(2))
    return out


def _dispatches():
    text = metrics.process_registry().prometheus_text()
    return {m.group(1): int(m.group(2)) for m in re.finditer(
        r'yb_device_dispatches\{entry="([a-z_]+)"\} (\d+)', text)}


def _delta(after, before):
    return {k: v - before.get(k, 0) for k, v in after.items()
            if v - before.get(k, 0)}


@pytest.fixture
def pg_cluster(tmp_path, monkeypatch, request):
    """One tserver leading both tablets of a flushed LINEITEM behind the
    PG wire; ``request.param`` is the chip count it reports."""
    from yugabyte_db_tpu.drivers.minipg import PgConnection
    from yugabyte_db_tpu.integration import MiniCluster
    from yugabyte_db_tpu.tools.admin_client import AdminClient
    from yugabyte_db_tpu.tserver.tablet_server import TabletServer
    from yugabyte_db_tpu.yql.pgsql import tpch

    monkeypatch.setattr(TabletServer, "local_chips",
                        lambda self: request.param)
    mc = MiniCluster(str(tmp_path), num_masters=1, num_tservers=1,
                     transport="socket").start()
    srv = None
    try:
        mc.wait_tservers_registered()
        srv, addr = mc.start_pg_server(engine="tpu", num_tablets=2,
                                       replication_factor=1,
                                       rpc_timeout_s=120)
        conn = PgConnection(*addr, timeout=120)
        conn.execute(DDL)
        rows = list(tpch.generate_lineitem(400))
        values = ",".join("(" + ",".join(repr(r[c]) for c in COLS) + ")"
                          for r in rows)
        conn.execute(f"INSERT INTO lineitem ({','.join(COLS)}) "
                     f"VALUES {values}")
        assert AdminClient(mc.transport, mc.master_uuids).flush_table(
            "lineitem") == 2
        yield mc, conn, rows, next(iter(mc.tservers.values()))
        conn.close()
    finally:
        if srv is not None:
            srv.shutdown()
        mc.shutdown()


def _q1_want(rows, cutoff=10471):
    want = {}
    for r in rows:
        if r["l_shipdate"] > cutoff:
            continue
        acc = want.setdefault((r["l_returnflag"], r["l_linestatus"]),
                              [0, 0, 0, 0, 0])
        disc = r["l_extendedprice"] * (100 - r["l_discount"])
        acc[0] += r["l_quantity"]
        acc[1] += r["l_extendedprice"]
        acc[2] += disc
        acc[3] += disc * (100 + r["l_tax"])
        acc[4] += 1
    return [(f, s, a[0], a[1], a[2], a[3], a[0] / a[4], a[1] / a[4], a[4])
            for (f, s), a in sorted(want.items())]


@pytest.mark.parametrize("pg_cluster", [8], indirect=True)
def test_pg_q1_and_q6_ride_the_mesh_on_a_node_with_several_chips(pg_cluster):
    """Each statement is ONE ``ts.multi_agg_scan`` and no ``ts.scan``:
    one ``dist_grouped_aggregate`` program, none of the per-tablet
    entries; the tablet's planes are not uploaded a second time."""
    from yugabyte_db_tpu.yql.pgsql import tpch

    mc, conn, rows, ts = pg_cluster
    for sql, check in (
            (tpch.q1_sql(), lambda got: got == _q1_want(rows)),
            (tpch.q6_sql(), lambda got: got == [(sum(
                r["l_extendedprice"] * r["l_discount"] for r in rows
                if 9131 <= r["l_shipdate"] < 9131 + 365
                and 5 <= r["l_discount"] <= 7 and r["l_quantity"] < 24),)])):
        rpcs, progs = _scan_rpcs(ts), _dispatches()
        scans = metrics.mesh_scans()
        got = conn.execute(sql).rows
        assert check([tuple(r) for r in got]), got
        assert _delta(_scan_rpcs(ts), rpcs) == {"ts.multi_agg_scan": 1}
        assert _delta(_dispatches(), progs) == {"dist_grouped_aggregate": 1}
        assert _delta(metrics.mesh_scans(), scans) == {("agg", "served"): 1}
    assert ts.mesh_scan.served == 2 and ts.mesh_scan.fallbacks == 0
    for peer in ts.tablet_manager.peers():
        assert peer.tablet.engine.runs[0].peek_device() is None
    text = metrics.process_registry().prometheus_text()
    assert 'yb_mesh_stack_builds{how="build"}' in text
    assert 'yb_engine_phase_us_count{phase="wait_fetch",route="mesh"}' in text


@pytest.mark.parametrize("pg_cluster", [1], indirect=True)
def test_pg_q1_and_q6_on_one_chip_send_what_they_always_sent(pg_cluster):
    """The node reports one chip: one ``ts.scan`` a tablet through the
    pg-docop pool, one ``grouped_aggregate`` program each, no mesh
    request and no mesh program."""
    from yugabyte_db_tpu.yql.pgsql import tpch

    mc, conn, rows, ts = pg_cluster
    for sql in (tpch.q1_sql(), tpch.q6_sql()):
        rpcs, progs = _scan_rpcs(ts), _dispatches()
        got = conn.execute(sql).rows
        if sql == tpch.q1_sql():
            assert [tuple(r) for r in got] == _q1_want(rows)
        assert _delta(_scan_rpcs(ts), rpcs) == {"ts.scan": 2}
        assert _delta(_dispatches(), progs) == {"grouped_aggregate": 2}
    assert ts.mesh_scan.served == 0 and ts.mesh_scan.fallbacks == 0
    assert not ts.mesh_scan._stacks


@pytest.mark.parametrize("pg_cluster", [8], indirect=True)
def test_an_ineligible_group_is_served_a_tablet_at_a_time(pg_cluster):
    """What ops.group_agg does not lower (a grouped min) answers
    ``ineligible``; so does a table with rows in its memtable. The group
    is demoted to one ``ts.scan`` a tablet and the answer is the host's
    own."""
    mc, conn, rows, ts = pg_cluster
    sql = ("SELECT l_returnflag, min(l_quantity) AS q FROM lineitem "
           "GROUP BY l_returnflag ORDER BY l_returnflag")
    want = {}
    for r in rows:
        want[r["l_returnflag"]] = min(want.get(r["l_returnflag"], 99),
                                      r["l_quantity"])
    rpcs, scans = _scan_rpcs(ts), metrics.mesh_scans()
    assert [tuple(r) for r in conn.execute(sql).rows] == sorted(want.items())
    assert _delta(_scan_rpcs(ts), rpcs) == {"ts.multi_agg_scan": 1,
                                            "ts.scan": 2}
    assert _delta(metrics.mesh_scans(), scans) == {("agg", "ineligible"): 1}
    # a write leaves memtable data in range: Q6 falls back, still exact
    from yugabyte_db_tpu.yql.pgsql import tpch

    conn.execute("INSERT INTO lineitem (l_orderkey, l_linenumber, "
                 "l_quantity, l_extendedprice, l_discount, l_tax, "
                 "l_returnflag, l_linestatus, l_shipdate) VALUES "
                 "(900001, 1, 3, 7000, 6, 1, 'N', 'O', 9200)")
    before = sum(r["l_extendedprice"] * r["l_discount"] for r in rows
                 if 9131 <= r["l_shipdate"] < 9131 + 365
                 and 5 <= r["l_discount"] <= 7 and r["l_quantity"] < 24)
    rpcs = _scan_rpcs(ts)
    assert conn.execute(tpch.q6_sql()).rows[0][0] == before + 7000 * 6
    assert _delta(_scan_rpcs(ts), rpcs)["ts.scan"] == 2


@pytest.mark.parametrize("pg_cluster", [8], indirect=True)
def test_a_mesh_request_waits_for_the_sessions_acked_write(pg_cluster,
                                                           monkeypatch):
    """A write is acked at COMMIT and applied after; until the apply
    lands the tablets still look flushed and idle. A mesh request reads
    at a fresh read point, so like ``ts.scan``'s gate it waits for safe
    time to reach what the session observed (``propagated_ht``): the
    statement after an INSERT counts the inserted row, however late the
    apply (here 0.3 s: the mesh answered from below the write before)."""
    import time

    from yugabyte_db_tpu.storage.tpu_engine import TpuStorageEngine
    from yugabyte_db_tpu.yql.pgsql import tpch

    mc, conn, rows, ts = pg_cluster
    before = conn.execute(tpch.q6_sql()).rows[0][0]
    apply = TpuStorageEngine.apply

    def late_apply(self, versions, *args, **kwargs):
        time.sleep(0.3)
        return apply(self, versions, *args, **kwargs)

    monkeypatch.setattr(TpuStorageEngine, "apply", late_apply)
    conn.execute("INSERT INTO lineitem (l_orderkey, l_linenumber, "
                 "l_quantity, l_extendedprice, l_discount, l_tax, "
                 "l_returnflag, l_linestatus, l_shipdate) VALUES "
                 "(900002, 1, 3, 7000, 6, 1, 'N', 'O', 9200)")
    rpcs = _scan_rpcs(ts)
    assert conn.execute(tpch.q6_sql()).rows[0][0] == before + 7000 * 6
    # (the memtable holds the row once the wait is over: demoted)
    assert _delta(_scan_rpcs(ts), rpcs) == {"ts.multi_agg_scan": 1,
                                            "ts.scan": 2}


def test_a_tserver_reports_its_chips_and_the_master_hands_them_on(tmp_path):
    """``local_chips`` rides the heartbeat; ``get_table_locations`` gives
    it for every replica (1 for a server that runs no TPU engine)."""
    import jax

    from yugabyte_db_tpu.integration import MiniCluster
    from yugabyte_db_tpu.models.datatypes import DataType
    from yugabyte_db_tpu.models.schema import ColumnKind, ColumnSchema

    mc = MiniCluster(str(tmp_path), num_masters=1, num_tservers=1).start()
    try:
        mc.wait_tservers_registered()
        client = mc.client("chips")
        cols = [ColumnSchema("k", DataType.INT64, ColumnKind.HASH),
                ColumnSchema("v", DataType.INT64)]
        ts = next(iter(mc.tservers.values()))
        assert ts.local_chips() == 1          # no TPU engine yet
        client.create_table("on_tpu", cols, num_tablets=2,
                            replication_factor=1, engine="tpu")
        assert ts.local_chips() == len(jax.local_devices()) > 1
        ts.heartbeater.trigger()
        import time

        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            locs = client.meta_cache.locations("on_tpu", refresh=True)
            if all(t.replica_chips.get(ts.uuid) == ts.local_chips()
                   for t in locs.tablets):
                break
            time.sleep(0.1)
        else:
            raise AssertionError([t.replica_chips for t in locs.tablets])
    finally:
        mc.shutdown()


class _ScriptedTransport:
    """Replies to ``ts.multi_agg_scan`` from a script, in order."""

    def __init__(self, replies):
        self.replies = list(replies)
        self.sent = []

    def send(self, target, method, payload, timeout=None):
        self.sent.append((target, method, timeout))
        return self.replies.pop(0)


@pytest.mark.parametrize("hint,sent,served", [
    ("ts-0", 3, True),      # the leader, between two lease renewals
    (None, 3, True),
    ("ts-1", 1, False)])    # leadership moved: the grouping is stale
def test_a_lease_lapse_is_asked_again_and_not_demoted(hint, sent, served):
    """``not_leader`` from a server that names itself (or nobody) is a
    stalled heartbeat round: the mesh request goes again under the
    client's retry policy, within the caller's budget, where a demotion
    would send per-tablet programs this node may never have compiled. A
    hint that names another server demotes at once."""
    from yugabyte_db_tpu.client.client import YBClient
    from yugabyte_db_tpu.storage import wire
    from yugabyte_db_tpu.storage.scan_spec import (AggSpec, ScanResult,
                                                   ScanSpec)

    ok = dict(wire.encode_result(ScanResult(["count"], [(7,)], None, 7)),
              code="ok", read_ht=1234)
    lapse = {"code": "not_leader", "tablet_id": "a", "leader_hint": hint}
    tr = _ScriptedTransport([lapse, lapse, ok])
    client = YBClient(tr, ["m-0"])
    before = metrics.swallowed_errors()
    resp = mesh_route.multi_agg_scan(
        client, "ts-0", [_loc("a", "ts-0", 4), _loc("c", "ts-0", 4)],
        ScanSpec(aggregates=[AggSpec("count", None)]), timeout_s=30.0)
    assert [(t, m) for t, m, _s in tr.sent] == [
        ("ts-0", "ts.multi_agg_scan")] * sent
    assert tr.sent[0][2] == 30.0 and all(0 < s <= 30.0 for _t, _m, s
                                         in tr.sent)
    assert (resp is not None) == served
    if served:
        assert wire.decode_result(resp).rows == [(7,)]
        assert client.last_observed_ht >= 1234
    assert metrics.swallowed_errors() == before


@pytest.mark.parametrize("pg_cluster", [8], indirect=True)
def test_pg_statement_rides_the_mesh_through_a_lease_lapse(pg_cluster):
    """The served path: a leader that has lost its lease for one
    heartbeat round answers ``not_leader`` once; the PG statement asks the
    mesh again and sends no ``ts.scan`` (the demotion it replaces compiled
    a per-tablet program inside a measured window on the chip)."""
    from yugabyte_db_tpu.yql.pgsql import tpch

    mc, conn, rows, ts = pg_cluster
    conn.execute(tpch.q6_sql())            # stack built, program compiled
    peer = ts.tablet_manager.peers()[0]
    real, lapses = peer.raft.has_lease, [0]

    def lapsed_once():
        if lapses[0] == 0:
            lapses[0] = 1
            return False
        return real()

    peer.raft.has_lease = lapsed_once
    try:
        rpcs, progs = _scan_rpcs(ts), _dispatches()
        got = conn.execute(tpch.q1_sql()).rows
    finally:
        peer.raft.has_lease = real
    assert lapses[0] == 1
    assert [tuple(r) for r in got] == _q1_want(rows)
    assert _delta(_scan_rpcs(ts), rpcs) == {"ts.multi_agg_scan": 2}
    assert _delta(_dispatches(), progs) == {"dist_grouped_aggregate": 1}
