"""Tablet snapshots: create / restore / delete, replicated + replayed.

Reference analogs: Tablet::CreateCheckpoint (tablet.h:348) over hard-link
checkpoints (rocksdb checkpoint.cc:53) and the TabletSnapshotOp
CREATE/RESTORE/DELETE RPCs (tserver/backup.proto).
"""

import tempfile

import pytest

from yugabyte_db_tpu.client.session import YBSession
from yugabyte_db_tpu.integration.mini_cluster import MiniCluster
from yugabyte_db_tpu.models.datatypes import DataType
from yugabyte_db_tpu.models.schema import ColumnKind, ColumnSchema
from yugabyte_db_tpu.storage.scan_spec import ScanSpec
from yugabyte_db_tpu.tools.admin_client import AdminClient


def _rows(client, table, read_names=("k", "v")):
    s = YBSession(client)
    res = s.scan(table, ScanSpec(projection=list(read_names)))
    return sorted(res.rows)


def test_snapshot_create_restore_delete_cluster():
    with tempfile.TemporaryDirectory() as root:
        mc = MiniCluster(root, num_tservers=3).start()
        try:
            mc.wait_tservers_registered()
            client = mc.client()
            client.create_table("kv", [
                ColumnSchema("k", DataType.STRING, ColumnKind.HASH),
                ColumnSchema("v", DataType.INT64),
            ], num_tablets=4)
            table = client.open_table("kv")
            s = YBSession(client)
            for i in range(30):
                s.insert(table, {"k": f"a{i:03d}", "v": i})
            s.flush()

            admin = AdminClient(mc.transport.bind("admin"),
                                mc.master_uuids)
            n = admin.snapshot_table("kv", "snap1", "create_snapshot")
            assert n == 4
            snaps = admin.list_snapshots("kv")
            assert all(s == ["snap1"] for s in snaps.values())

            # diverge: overwrite some rows, add others, delete one
            for i in range(10):
                s.insert(table, {"k": f"a{i:03d}", "v": -1})
            for i in range(30, 40):
                s.insert(table, {"k": f"a{i:03d}", "v": i})
            s.delete(table, {"k": "a020"})
            s.flush()
            before = _rows(client, table)
            assert len(before) == 39 and ("a000", -1) in before

            admin.snapshot_table("kv", "snap1", "restore_snapshot")
            after = _rows(client, table)
            assert after == [(f"a{i:03d}", i) for i in range(30)]

            admin.snapshot_table("kv", "snap1", "delete_snapshot")
            assert all(s == [] for s in
                       admin.list_snapshots("kv").values())
            # restoring a deleted snapshot fails cleanly
            from yugabyte_db_tpu.tools.admin_client import AdminError
            with pytest.raises(AdminError):
                admin.snapshot_table("kv", "snap1", "restore_snapshot")
        finally:
            mc.shutdown()


def test_snapshot_survives_restart():
    with tempfile.TemporaryDirectory() as root:
        mc = MiniCluster(root, num_tservers=3).start()
        try:
            mc.wait_tservers_registered()
            client = mc.client()
            client.create_table("kv", [
                ColumnSchema("k", DataType.STRING, ColumnKind.HASH),
                ColumnSchema("v", DataType.INT64),
            ], num_tablets=2)
            table = client.open_table("kv")
            s = YBSession(client)
            for i in range(10):
                s.insert(table, {"k": f"k{i}", "v": i})
            s.flush()
            admin = AdminClient(mc.transport.bind("admin2"),
                                mc.master_uuids)
            admin.snapshot_table("kv", "s1", "create_snapshot")
            for i in range(10):
                s.insert(table, {"k": f"k{i}", "v": i * 100})
            s.flush()

            victim = next(iter(mc.tservers))
            mc.stop_tserver(victim)
            mc.restart_tserver(victim)
            mc.wait_tservers_registered()

            # snapshot still listed after restart + WAL replay
            snaps = admin.list_snapshots("kv")
            assert all("s1" in v for v in snaps.values())
            admin.snapshot_table("kv", "s1", "restore_snapshot")
            assert _rows(client, table) == [(f"k{i}", i)
                                            for i in range(10)]
        finally:
            mc.shutdown()


def test_snapshot_local_tablet_both_engines():
    import os

    from yugabyte_db_tpu.models.partition import compute_hash_code
    from yugabyte_db_tpu.models.schema import Schema
    from yugabyte_db_tpu.storage.row_version import RowVersion
    from yugabyte_db_tpu.tablet.tablet import Tablet, TabletMetadata

    for engine in ("cpu", "tpu"):
        if engine == "tpu":
            import yugabyte_db_tpu.storage.tpu_engine  # noqa: F401
        with tempfile.TemporaryDirectory() as root:
            schema = Schema([
                ColumnSchema("k", DataType.STRING, ColumnKind.HASH),
                ColumnSchema("v", DataType.INT64),
            ], table_id="t")
            cid = schema.column("v").col_id
            meta = TabletMetadata("t-0001", "t", schema, 0, 65536,
                                  engine=engine)
            t = Tablet.create(meta, root, fsync=False)

            def key(i):
                return schema.encode_primary_key(
                    {"k": f"x{i}"},
                    compute_hash_code(schema, {"k": f"x{i}"}))

            t.write([RowVersion(key(i), ht=0, liveness=True,
                                columns={cid: i}) for i in range(8)])
            t.snapshot_op("create_snapshot", "base")
            t.write([RowVersion(key(i), ht=0, liveness=True,
                                columns={cid: -i}) for i in range(8)])
            res = t.scan(ScanSpec(read_ht=t.read_time().value,
                                  projection=["k", "v"]))
            assert all(v <= 0 for _k, v in res.rows)
            t.snapshot_op("restore_snapshot", "base")
            res = t.scan(ScanSpec(read_ht=t.read_time().value,
                                  projection=["k", "v"]))
            assert sorted(v for _k, v in res.rows) == list(range(8))
            assert t.list_snapshots() == ["base"]
            t.snapshot_op("delete_snapshot", "base")
            assert t.list_snapshots() == []
            assert os.path.isdir(t.dir)
            t.close()


def test_master_coordinated_cluster_snapshot():
    """The master drives create/restore/delete across every tablet and
    tracks snapshot state in the replicated sys catalog (reference:
    CreateSnapshot/RestoreSnapshot master RPCs over backup.proto ops);
    the registry survives a full cluster kill + restart, and restore
    after the restart still rolls data back."""
    with tempfile.TemporaryDirectory() as root:
        mc = MiniCluster(root, num_tservers=3).start()
        try:
            mc.wait_tservers_registered()
            client = mc.client()
            client.create_table("kv", [
                ColumnSchema("k", DataType.STRING, ColumnKind.HASH),
                ColumnSchema("v", DataType.INT64),
            ], num_tablets=4)
            table = client.open_table("kv")
            s = YBSession(client)
            for i in range(40):
                s.insert(table, {"k": f"a{i:03d}", "v": i})
            s.flush()
            baseline = _rows(client, table)

            admin = AdminClient(mc.transport.bind("admin2"),
                                mc.master_uuids)
            resp = admin.cluster_snapshot("create", "kv", "cs1")
            assert resp["tablets"] == 4
            reg = admin.cluster_snapshot("list")["snapshots"]
            assert reg["cs1"]["state"] == "COMPLETE"
            assert reg["cs1"]["table"] == "kv"

            # unknown snapshot / double create fail cleanly
            with pytest.raises(Exception):
                admin.cluster_snapshot("restore", snapshot_id="nope")
            with pytest.raises(Exception):
                admin.cluster_snapshot("create", "kv", "cs1")

            # diverge
            for i in range(20):
                s.insert(table, {"k": f"a{i:03d}", "v": i + 1000})
            for i in range(40, 55):
                s.insert(table, {"k": f"a{i:03d}", "v": i})
            s.flush()
            diverged = _rows(client, table)
            assert diverged != baseline

            # kill the whole cluster; registry must survive the restart
            mc.shutdown()
            mc = MiniCluster(root, num_tservers=3).start()
            mc.wait_tservers_registered()
            client = mc.client("after-restart")
            table = client.open_table("kv")
            admin = AdminClient(mc.transport.bind("admin3"),
                                mc.master_uuids)
            reg = admin.cluster_snapshot("list")["snapshots"]
            assert reg["cs1"]["state"] == "COMPLETE"

            # The master asks each replica of a tablet once for its
            # leader; a scan returns once every tablet has elected one
            # after the restart.
            assert _rows(client, table) == diverged
            admin.cluster_snapshot("restore", snapshot_id="cs1")
            assert _rows(client, table) == baseline

            admin.cluster_snapshot("delete", snapshot_id="cs1")
            assert admin.cluster_snapshot("list")["snapshots"] == {}
            with pytest.raises(Exception):
                admin.cluster_snapshot("restore", snapshot_id="cs1")
        finally:
            mc.shutdown()
