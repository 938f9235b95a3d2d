"""Tablet layer tests: WAL, bootstrap replay, flush frontier, MVCC manager.

Reference test analog: src/yb/consensus/log-test.cc,
src/yb/tablet/tablet_bootstrap-test.cc, mvcc-test.cc.
"""

import os
import threading

import pytest

from yugabyte_db_tpu.models.datatypes import DataType
from yugabyte_db_tpu.models.partition import compute_hash_code
from yugabyte_db_tpu.models.schema import ColumnKind, ColumnSchema, Schema
from yugabyte_db_tpu.storage import RowVersion, ScanSpec
from yugabyte_db_tpu.storage.row_version import MAX_HT
from yugabyte_db_tpu.tablet import Log, LogEntry, MvccManager, OpId, Tablet, TabletMetadata
from yugabyte_db_tpu.utils.hybrid_time import HybridClock, HybridTime


def make_schema():
    return Schema([
        ColumnSchema("k", DataType.STRING, ColumnKind.HASH),
        ColumnSchema("r", DataType.INT64, ColumnKind.RANGE),
        ColumnSchema("v", DataType.STRING),
    ], table_id="t")


def enc(schema, k, r):
    return schema.encode_primary_key(
        {"k": k, "r": r}, compute_hash_code(schema, {"k": k}))


# -- WAL -------------------------------------------------------------------

def test_wal_roundtrip(tmp_path):
    log = Log(str(tmp_path / "wal"), fsync=False)
    for i in range(1, 21):
        log.append(LogEntry(OpId(1, i), ht=100 + i, op_type="write",
                            body=[b"key", i, {"x": [1, 2.5, None]}]))
    log.sync()
    log.close()
    log2 = Log(str(tmp_path / "wal"), fsync=False)
    entries = list(log2.read_all())
    assert [e.op_id.index for e in entries] == list(range(1, 21))
    assert entries[3].body == [b"key", 4, {"x": [1, 2.5, None]}]
    assert log2.last_appended == OpId(1, 20)


def test_wal_rejects_non_monotonic(tmp_path):
    log = Log(str(tmp_path / "wal"), fsync=False)
    log.append(LogEntry(OpId(1, 5), 1, "write", []))
    with pytest.raises(ValueError):
        log.append(LogEntry(OpId(1, 5), 2, "write", []))


def test_wal_torn_tail_recovery(tmp_path):
    log = Log(str(tmp_path / "wal"), fsync=False)
    for i in range(1, 6):
        log.append(LogEntry(OpId(1, i), i, "write", [i]))
    log.sync()
    log.close()
    # Corrupt: truncate mid-record (simulated crash during write).
    path = log.segment_paths()[0]
    size = os.path.getsize(path)
    with open(path, "r+b") as f:
        f.truncate(size - 3)
    entries = list(Log(str(tmp_path / "wal"), fsync=False).read_all())
    assert [e.body for e in entries] == [[1], [2], [3], [4]]  # last dropped


def test_wal_segment_roll_and_gc(tmp_path):
    log = Log(str(tmp_path / "wal"), segment_bytes=256, fsync=False)
    for i in range(1, 51):
        log.append(LogEntry(OpId(1, i), i, "write", ["x" * 30]))
    log.sync()
    assert len(log.segment_paths()) > 2
    deleted = log.gc(min_retained_index=30)
    assert deleted > 0
    entries = list(log.read_all(30))
    assert [e.op_id.index for e in entries][:1] == [30] or \
        entries[0].op_id.index < 30  # segment granularity keeps extra entries
    assert [e.op_id.index for e in entries][-1] == 50
    # everything >= 30 must survive
    idxs = {e.op_id.index for e in log.read_all()}
    assert set(range(30, 51)) <= idxs


@pytest.mark.parametrize("gone", ["first", "middle"])
def test_wal_read_tolerates_a_segment_gc_unlinked_after_the_listing(
        tmp_path, monkeypatch, gone):
    """``read_all`` lists the segments and opens them one by one; a
    ``gc`` on another thread may unlink one in between (ROADMAP C17: a
    FileNotFoundError inside restart_tserver). The reader goes on with
    what is left, as if the segment had gone before the listing."""
    log = Log(str(tmp_path / "wal"), segment_bytes=256, fsync=False)
    for i in range(1, 51):
        log.append(LogEntry(OpId(1, i), i, "write", ["x" * 30]))
    log.sync()
    listed = log.segment_paths()
    assert len(listed) > 3
    victim = listed[0 if gone == "first" else 2]
    lost = {e.op_id.index for e in Log._read_segment(victim, 0)[0]}
    read_segment = Log._read_segment
    opened = []

    def unlink_then_read(path, min_index):
        # (between the listing and this open: what gc does)
        if path == victim and os.path.exists(victim):
            os.unlink(victim)
        opened.append(path)
        return read_segment(path, min_index)

    monkeypatch.setattr(Log, "_read_segment",
                        staticmethod(unlink_then_read))
    got = [e.op_id.index for e in log.read_all()]
    assert opened == listed                 # every listed segment tried
    assert got == [i for i in range(1, 51) if i not in lost]
    assert lost and not os.path.exists(victim)


# -- MvccManager -----------------------------------------------------------

def test_mvcc_safe_time_blocks_on_pending():
    clock = HybridClock(now_micros=lambda: 1000)
    m = MvccManager(clock)
    ht1 = clock.now()
    m.add_pending(ht1)
    assert m.safe_time().value == ht1.value - 1
    m.replicated(ht1)
    # Reads at the replicated ht are safe; observing must not issue an HT.
    assert m.safe_time() >= ht1
    assert m.safe_time() >= ht1  # stable across repeated observation
    assert m.last_replicated_ht == ht1


def test_mvcc_wait_for_safe_time():
    clock = HybridClock(now_micros=lambda: 1000)
    m = MvccManager(clock)
    ht = clock.now()
    m.add_pending(ht)
    done = []

    def waiter():
        done.append(m.wait_for_safe_time(ht, timeout=5))

    t = threading.Thread(target=waiter)
    t.start()
    m.replicated(ht)
    t.join(timeout=5)
    assert done == [True]


# -- Tablet end-to-end -----------------------------------------------------

@pytest.mark.parametrize("engine", ["cpu", "tpu"])
def test_tablet_write_read_restart(tmp_path, engine):
    schema = make_schema()
    ids = {c.name: c.col_id for c in schema.value_columns}
    meta = TabletMetadata("t1", "tbl", schema, 0, 65536, engine=engine)
    tab = Tablet.create(meta, str(tmp_path), fsync=False)
    for i in range(30):
        tab.write([RowVersion(enc(schema, "a", i), ht=0, liveness=True,
                              columns={ids["v"]: f"val{i}"})])
    res = tab.scan(ScanSpec(read_ht=tab.read_time().value))
    assert len(res.rows) == 30
    tab.close()

    # Restart WITHOUT flush: everything must come back from the WAL.
    tab2 = Tablet.open("t1", str(tmp_path), fsync=False)
    assert tab2._replayed_on_bootstrap == 30
    res2 = tab2.scan(ScanSpec(read_ht=MAX_HT))
    assert res2.rows == res.rows
    tab2.close()


@pytest.mark.parametrize("engine", ["cpu", "tpu"])
def test_tablet_flush_frontier_and_wal_gc(tmp_path, engine):
    schema = make_schema()
    ids = {c.name: c.col_id for c in schema.value_columns}
    meta = TabletMetadata("t2", "tbl", schema, 0, 65536, engine=engine)
    tab = Tablet.create(meta, str(tmp_path), fsync=False)
    tab.log.segment_bytes = 512  # force rolls
    for i in range(60):
        tab.write([RowVersion(enc(schema, "a", i), ht=0, liveness=True,
                              columns={ids["v"]: f"v{i}"})])
    tab.flush()
    assert tab.meta.flushed_op_index == 60
    for i in range(60, 80):
        tab.write([RowVersion(enc(schema, "a", i), ht=0, liveness=True,
                              columns={ids["v"]: f"v{i}"})])
    tab.close()

    tab2 = Tablet.open("t2", str(tmp_path), fsync=False)
    # Only the 20 post-flush writes replay; flushed data loads from runs.
    assert tab2._replayed_on_bootstrap == 20
    res = tab2.scan(ScanSpec(read_ht=MAX_HT, projection=["r"]))
    assert [r[0] for r in res.rows] == list(range(80))
    tab2.close()


def test_tablet_mvcc_snapshot_after_restart(tmp_path):
    schema = make_schema()
    ids = {c.name: c.col_id for c in schema.value_columns}
    meta = TabletMetadata("t3", "tbl", schema, 0, 65536, engine="cpu")
    tab = Tablet.create(meta, str(tmp_path), fsync=False)
    key = enc(schema, "a", 1)
    ht1 = tab.write([RowVersion(key, ht=0, liveness=True, columns={ids["v"]: "x"})])
    ht2 = tab.write([RowVersion(key, ht=0, columns={ids["v"]: "y"})])
    tab.write([RowVersion(key, ht=0, tombstone=True)])
    tab.close()
    tab2 = Tablet.open("t3", str(tmp_path), fsync=False)
    assert tab2.scan(ScanSpec(read_ht=ht1.value)).rows == [("a", 1, "x")]
    assert tab2.scan(ScanSpec(read_ht=ht2.value)).rows == [("a", 1, "y")]
    assert tab2.scan(ScanSpec(read_ht=MAX_HT)).rows == []
    # Clock must have ratcheted past replayed HTs: new writes get larger HTs.
    ht4 = tab2.write([RowVersion(key, ht=0, liveness=True, columns={ids["v"]: "z"})])
    assert ht4 > ht2
    tab2.close()


def test_codec_roundtrip():
    from yugabyte_db_tpu.utils import codec
    cases = [
        None, True, False, 0, 1, -1, 2 ** 62, -(2 ** 62), 2 ** 80, -(2 ** 80),
        1.5, -0.0, "héllo", b"\x00\xff", [1, [2, [3]]],
        {"a": 1, "b": [None, {"c": b"x"}]}, [],
    ]
    for v in cases:
        assert codec.decode(codec.encode(v)) == v


def test_intra_batch_write_id_ordering(tmp_path):
    """Two writes to the SAME key in ONE batch share a hybrid time; the
    write_id sub-ordering (DocHybridTime's write_id component,
    src/yb/common/doc_hybrid_time.h) makes the LATER one win — on both
    engines, before and after flush."""
    import yugabyte_db_tpu.storage.tpu_engine  # noqa: F401
    from yugabyte_db_tpu.models.partition import compute_hash_code

    for engine in ("cpu", "tpu"):
        schema = make_schema()
        cid = {c.name: c.col_id for c in schema.columns}
        meta = TabletMetadata(f"t-{engine}", "t", schema, 0, 65536,
                              engine=engine)
        t = Tablet.create(meta, str(tmp_path / engine), fsync=False)
        key = schema.encode_primary_key(
            {"k": "dup", "r": 0},
            compute_hash_code(schema, {"k": "dup"}))
        t.write([
            RowVersion(key, ht=0, liveness=True, columns={cid["v"]: "a"}),
            RowVersion(key, ht=0, liveness=True, columns={cid["v"]: "b"}),
            RowVersion(key, ht=0, columns={cid["v"]: "c"}),  # UPDATE-style
        ])
        for label in ("memtable", "flushed"):
            res = t.scan(ScanSpec(read_ht=t.read_time().value,
                                  projection=["k", "v"]))
            assert res.rows == [("dup", "c")], (engine, label, res.rows)
            t.flush()
        # same-batch DELETE shadows same-ht writes regardless of position
        # (the device kernel's <= tombstone rule; scan.py:182)
        t.write([
            RowVersion(key, ht=0, liveness=True, columns={cid["v"]: "z"}),
            RowVersion(key, ht=0, tombstone=True),
        ])
        res = t.scan(ScanSpec(read_ht=t.read_time().value))
        assert res.rows == [], (engine, res.rows)
        t.close()
