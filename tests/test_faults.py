"""SyncPoint interleaving control, fault injection, MemTrackers.

Reference analogs: src/yb/util/sync_point.h:61 (LoadDependency),
fault_injection.h:49 + FLAGS_respond_write_failed_probability
(tablet_service.cc:784), and the MemTracker hierarchy + shared
memstore budget (mem_tracker.h, docdb_rocksdb_util.cc:437).
"""

import tempfile
import threading

import pytest

from yugabyte_db_tpu.models.datatypes import DataType
from yugabyte_db_tpu.models.partition import compute_hash_code
from yugabyte_db_tpu.models.schema import ColumnKind, ColumnSchema, Schema
from yugabyte_db_tpu.storage import Predicate, ScanSpec, make_engine
from yugabyte_db_tpu.storage.row_version import RowVersion
from yugabyte_db_tpu.utils.fault_injection import (arm_fault_once,
                                                   clear_faults)
from yugabyte_db_tpu.utils.flags import FLAGS
from yugabyte_db_tpu.utils.memtracker import MemTracker, root_tracker
from yugabyte_db_tpu.utils.sync_point import SYNC_POINT, sync_point


def _schema():
    return Schema([
        ColumnSchema("k", DataType.STRING, ColumnKind.HASH),
        ColumnSchema("v", DataType.INT64),
    ], table_id="t")


def _key(schema, i):
    return schema.encode_primary_key(
        {"k": f"k{i:04d}"}, compute_hash_code(schema, {"k": f"k{i:04d}"}))


# -- SyncPoint ---------------------------------------------------------------

def test_sync_point_orders_threads():
    order = []
    SYNC_POINT.load_dependency([("a:done", "b:start")])
    SYNC_POINT.enable()
    try:
        def thread_b():
            sync_point("b:start")   # blocks until a:done processed
            order.append("b")

        t = threading.Thread(target=thread_b)
        t.start()
        import time

        time.sleep(0.05)            # give b a chance to run early (it must not)
        order.append("a")
        sync_point("a:done")
        t.join(timeout=5)
        assert order == ["a", "b"]
    finally:
        SYNC_POINT.disable_and_clear()


def test_sync_point_timeout_and_disable(monkeypatch):
    # The production hook and its timeout path, at 0.2 s instead of the
    # hook's 10 s default (process(point, arg=None, timeout_s=10.0)).
    monkeypatch.setattr(type(SYNC_POINT).process, "__defaults__",
                        (None, 0.2))
    SYNC_POINT.load_dependency([("never", "waits")])
    SYNC_POINT.enable()
    try:
        with pytest.raises(TimeoutError):
            sync_point("waits")
    finally:
        SYNC_POINT.disable_and_clear()
    sync_point("waits")  # disabled: free


def test_sync_point_flush_scan_interleaving():
    """Deterministically force a flush into the window between a scan's
    memtable snapshot and its execution — the exact race the plan-time
    snapshot defends against; results must include every pre-scan row."""
    import yugabyte_db_tpu.storage.tpu_engine  # noqa: F401

    schema = _schema()
    cid = schema.column("v").col_id
    eng = make_engine("tpu", schema, {"rows_per_block": 16})
    eng.apply([RowVersion(_key(schema, i), ht=10 + i, liveness=True,
                          columns={cid: i}) for i in range(20)])
    eng.flush()
    # memtable rows that a racing flush would move into a run mid-scan
    eng.apply([RowVersion(_key(schema, i), ht=100 + i, liveness=True,
                          columns={cid: 1000 + i}) for i in range(20, 30)])

    SYNC_POINT.load_dependency([
        ("tpu_engine:plan:mem_snapshotted", "tpu_engine:flush:start")])
    SYNC_POINT.enable()
    results = {}
    try:
        def flusher():
            eng.flush()   # blocks until the scan snapshotted its sources
            results["flushed"] = True

        ft = threading.Thread(target=flusher)
        ft.start()
        res = eng.scan(ScanSpec(read_ht=10_000, projection=["k", "v"]))
        ft.join(timeout=10)
        results["rows"] = res.rows
    finally:
        SYNC_POINT.disable_and_clear()
    assert results.get("flushed")
    got = dict(results["rows"])
    assert len(got) == 30
    assert got["k0025"] == 1025


# -- fault injection ---------------------------------------------------------

def test_write_respond_failed_is_exactly_once():
    """The injected 'applied but responded failure' fault: the client
    retries with the same request id and the dedup registry returns the
    original result — the row exists exactly once."""
    from yugabyte_db_tpu.client.client import YBClient
    from yugabyte_db_tpu.client.session import YBSession
    from yugabyte_db_tpu.integration.mini_cluster import MiniCluster

    with tempfile.TemporaryDirectory() as root:
        mc = MiniCluster(root, num_tservers=3).start()
        try:
            mc.wait_tservers_registered()
            client = mc.client()
            client.create_table("kv", [
                ColumnSchema("k", DataType.STRING, ColumnKind.HASH),
                ColumnSchema("v", DataType.INT64)], num_tablets=1)
            table = client.open_table("kv")
            s = YBSession(client)
            s.insert(table, {"k": "a", "v": 1})
            s.flush()

            arm_fault_once("fault.ts_write_respond_failed")
            s.insert(table, {"k": "b", "v": 2})
            s.flush()  # first response injected-fails; retry dedups

            res = s.scan(table, ScanSpec(projection=["k", "v"]))
            assert sorted(res.rows) == [("a", 1), ("b", 2)]
            # exactly-once: one version of 'b' in the whole tablet
            versions = 0
            for ts in mc.tservers.values():
                for peer in ts.tablet_manager.peers():
                    if not peer.is_leader():
                        continue
                    eng = peer.tablet.engine
                    for key, vers in eng.dump_entries():
                        versions += len(vers)
                    versions += sum(
                        len(eng.memtable.versions(k))
                        for k in eng.memtable.scan_keys(b"", b""))
            assert versions == 2  # 'a' and 'b', one version each
        finally:
            clear_faults()
            mc.shutdown()


def test_wal_sync_fault_fails_write_then_recovers():
    from yugabyte_db_tpu.tablet.tablet import Tablet, TabletMetadata
    from yugabyte_db_tpu.utils.fault_injection import FaultInjected

    schema = _schema()
    cid = schema.column("v").col_id
    with tempfile.TemporaryDirectory() as root:
        meta = TabletMetadata("t-0001", "t", schema, 0, 65536)
        t = Tablet.create(meta, root, fsync=False)
        arm_fault_once("fault.wal_sync_failed")
        with pytest.raises(FaultInjected):
            t.write([RowVersion(_key(schema, 1), ht=0, liveness=True,
                                columns={cid: 1})])
        # the fault was one-shot: the next write lands
        t.write([RowVersion(_key(schema, 2), ht=0, liveness=True,
                            columns={cid: 2})])
        res = t.scan(ScanSpec(read_ht=t.read_time().value,
                              projection=["k"]))
        assert [r[0] for r in res.rows] == ["k0002"]
        t.close()


# -- MemTracker --------------------------------------------------------------

def test_memtracker_hierarchy():
    root = MemTracker("r")
    a = root.child("a")
    b = root.child("b", limit=100)
    a.consume(50)
    b.consume(150)
    assert root.consumption == 200 and root.peak == 200
    assert b.over_limit()
    b.release(100)
    assert root.consumption == 100 and b.consumption == 50
    assert root.peak == 200
    a.detach()
    assert root.consumption == 50
    assert root.child("b") is b  # child() returns the existing node


def test_global_memstore_budget_triggers_flush():
    import yugabyte_db_tpu.storage.tpu_engine  # noqa: F401

    schema = _schema()
    cid = schema.column("v").col_id
    memstore = root_tracker().child("memstore")
    # The budget flush only fires for the LARGEST memstore consumer, so
    # sibling trackers left behind by earlier tests (unclosed engines,
    # cluster teardowns still draining) can starve this engine's flush.
    # Park the strays out of the comparison before measuring.
    for stray in list(memstore._children.values()):
        stray.detach()
    baseline = memstore.consumption
    old = FLAGS.get("global_memstore_limit_bytes")
    FLAGS.set("global_memstore_limit_bytes", baseline + 2000, force=True)
    try:
        eng = make_engine("cpu", schema)
        # each row ~80+ bytes: crossing the budget must auto-flush
        for i in range(200):
            eng.apply([RowVersion(_key(schema, i), ht=10 + i,
                                  liveness=True, columns={cid: i})])
        assert len(eng.runs) >= 1          # budget forced a flush
        assert eng.memtable.approx_bytes < 2000
        res = eng.scan(ScanSpec(read_ht=10_000))
        assert len(res.rows) == 200        # nothing lost across flushes
        eng.close()
        # Engine-scoped: close() released every byte THIS engine held
        # (the parent count can move under a detached straggler).
        assert eng.mem_tracker.consumption == 0
    finally:
        FLAGS.set("global_memstore_limit_bytes", old, force=True)
