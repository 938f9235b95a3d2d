"""Size-tiered compaction off the apply path.

(a) the picker, a pure function over run sizes; (b) a compaction of any
age-adjacent subset of runs against the CPU oracle, both mask routes;
(c) a compaction held mid-way while applies, flushes and reads go on;
(d) a reopen after a crash at each step of the subset replace; (e) a
tablet under the trigger never sees any of it; (f) replicas that compact
at different moments hold the same rows.
"""

import os
import random
import subprocess
import sys
import threading
import time

import pytest

from tests.test_device_compact import _entries_signature, _mk_engines
from yugabyte_db_tpu.models.datatypes import DataType
from yugabyte_db_tpu.models.partition import compute_hash_code
from yugabyte_db_tpu.models.schema import ColumnKind, ColumnSchema
from yugabyte_db_tpu.storage import ScanSpec, make_engine
from yugabyte_db_tpu.storage.engine import pick_compaction
from yugabyte_db_tpu.storage.residency import hbm_cache
from yugabyte_db_tpu.storage.row_version import MAX_HT, RowVersion
from yugabyte_db_tpu.utils import metrics
from yugabyte_db_tpu.utils.flags import FLAGS
from yugabyte_db_tpu.utils.sync_point import SYNC_POINT


# -- (a) the picker -----------------------------------------------------------

@pytest.mark.parametrize("sizes, trigger, want", [
    # the deployment's runs, newest first: never the base with the small
    ([1875, 1875, 3375, 500_000], 4, None),
    ([1875, 1875, 1875, 3375, 500_000], 4, (0, 4)),
    ([1875, 1875, 1875, 1875, 15_100, 478_000], 4, (0, 4)),
    ([1875, 1875, 1875, 1875, 7_560, 478_000], 4, (0, 5)),
    ([1875, 1875, 1875, 7_560, 478_000], 4, None),
    # like sizes: all of them, a full compaction as before
    ([100, 100, 100, 100], 4, (0, 4)),
    ([61, 61, 61], 3, (0, 3)),
    ([900, 1000, 1100, 1000, 950, 1050], 4, (0, 6)),
    # fewer than the trigger: none
    ([100, 100, 100], 4, None),
    ([], 4, None),
    ([5], 1, None),
    # a big new run does not drag small old ones in, the stretch behind does
    ([1, 100, 1, 1, 1, 1], 4, (1, 5)),
    # the longest stretch wins, the newest on a tie
    ([10, 10, 10, 10, 1000, 50, 50, 50, 50, 50], 4, (4, 6)),
    ([10, 10, 10, 10, 1000, 3000, 7000, 7000, 7000, 7000], 4, (0, 4)),
    # a lower trigger lowers the minimum width with it
    ([10, 10, 500], 2, (0, 2)),
    # exactly at the ratio: 7,500 * 1.2 = 9,000 is taken, 9,001 is not
    ([1875, 1875, 1875, 1875, 9_000, 478_000], 4, (0, 5)),
    ([1875, 1875, 1875, 1875, 9_001, 478_000], 4, (0, 4)),
])
def test_picker(sizes, trigger, want):
    assert pick_compaction(sizes, trigger) == want


# -- (b) subset compaction against the oracle ---------------------------------

RUNS = 5
SUBSETS = [(i, n) for i in range(RUNS) for n in range(2, RUNS - i + 1)]


def _load(schema, engines, num_keys=120, writes=900, seed=11):
    """Seeded writes, overwrites, deletes and TTLs, flushed into RUNS
    runs. A version with a TTL carries no liveness marker here: history
    GC keeps one liveness marker a key, the newest unexpired at the
    cutoff, and where that one has a TTL a read after its expiry would
    fall back to an older marker that the GC dropped (so it is with a
    full compaction today; an uncompacted oracle shows it)."""
    rng = random.Random(seed)
    cid = {c.name: c.col_id for c in schema.columns}
    ht = 10
    for w in range(writes):
        i = rng.randrange(num_keys)
        key = schema.encode_primary_key(
            {"k": f"u{i:04d}"}, compute_hash_code(schema, {"k": f"u{i:04d}"}))
        ht += rng.randrange(1, 3)
        roll = rng.random()
        if roll < 0.08:
            rv = RowVersion(key, ht=ht, tombstone=True)
        elif roll < 0.16:
            rv = RowVersion(key, ht=ht,
                            columns={cid["a"]: rng.randrange(100),
                                     cid["b"]: rng.choice(["t", None])},
                            expire_ht=ht + rng.randrange(1, 400))
        else:
            cols = {}
            if rng.random() < 0.8:
                cols[cid["a"]] = rng.randrange(10**9)
            if rng.random() < 0.5:
                cols[cid["b"]] = rng.choice(["x", "yy", None])
            if rng.random() < 0.4:
                cols[cid["c"]] = rng.uniform(-5, 5)
            rv = RowVersion(key, ht=ht, liveness=rng.random() < 0.5,
                            columns=cols)
        for e in engines:
            e.apply([rv])
        if w and w % (writes // RUNS) == 0:
            for e in engines:
                e.flush()
    for e in engines:
        e.flush()
    return ht


def _loaded(tmp_path=None, seed=11):
    """A CPU oracle that never compacts and a TPU engine with RUNS runs."""
    schema, cpu, tpu = _mk_engines()
    if tmp_path is not None:
        tpu = make_engine("tpu", schema, {"rows_per_block": 64,
                                          "data_dir": str(tmp_path)})
    ht = _load(schema, (cpu, tpu), seed=seed)
    assert len(tpu.runs) == RUNS
    return schema, cpu, tpu, ht


def _assert_reads_equal(schema, cpu, tpu, read_hts):
    keys = sorted({k for k, _v in cpu.dump_entries()})
    for read_ht in read_hts:
        assert cpu.scan(ScanSpec(read_ht=read_ht)).rows \
            == tpu.scan(ScanSpec(read_ht=read_ht)).rows, read_ht
        for key in keys[::7]:
            spec = ScanSpec(lower=key, upper=key + b"\x00", read_ht=read_ht)
            assert cpu.scan(spec).rows == tpu.scan(spec).rows, (read_ht, key)


def _compactions(route=None):
    text = metrics.process_registry().prometheus_text()
    return sum(float(ln.rsplit(" ", 1)[1]) for ln in text.splitlines()
               if ln.startswith("yb_compactions{")
               and (route is None or f'route="{route}"' in ln))


@pytest.mark.parametrize("resident", [True, False],
                         ids=["device_mask", "host_mask"])
@pytest.mark.parametrize("first, count", SUBSETS)
def test_subset_compaction_matches_oracle(first, count, resident):
    schema, cpu, tpu, ht = _loaded()
    inputs = tpu.runs[first:first + count]
    # (a cutoff inside what the inputs hold, so that history goes)
    hts = sorted(v.ht for t in inputs
                 for _k, versions in t.crun.iter_entries() for v in versions)
    cutoff = hts[len(hts) * 7 // 10]
    rest = [t for t in tpu.runs if t not in inputs]
    if resident:
        for t in inputs:
            t.device()
    else:
        for t in inputs:
            t.invalidate_device()
    want_route = "device" if resident else "host"
    before = _compactions(want_route)
    # what the oldest run's absence must keep: each key's newest
    # tombstone at or under the cutoff among the inputs
    top_tombs = set()
    for t in inputs:
        for key, versions in t.crun.iter_entries():
            for v in versions:
                if v.tombstone and v.ht <= cutoff:
                    top_tombs.add((key, v.ht))
    newest = {}
    for key, h in top_tombs:
        newest[key] = max(newest.get(key, 0), h)
    assert tpu.compact(cutoff, runs=inputs)
    assert _compactions(want_route) == before + 1
    # the merged run stands where its inputs stood
    assert len(tpu.runs) == RUNS - count + 1
    assert [t for t in tpu.runs if t in rest] == rest
    merged = tpu.runs[first]
    assert merged not in rest
    _assert_reads_equal(schema, cpu, tpu,
                        [cutoff, (cutoff + ht) // 2, ht + 1, MAX_HT])
    kept = {(key, v.ht) for key, versions in merged.crun.iter_entries()
            for v in versions if v.tombstone and v.ht <= cutoff}
    if first == 0:
        assert not kept         # nothing older is left to shadow
    else:
        assert newest and kept == set(newest.items())
    # compacting everything afterwards gives what the oracle gives
    cpu.compact(cutoff)
    tpu.compact(cutoff)
    assert _entries_signature(cpu) == _entries_signature(tpu)


def test_subset_compaction_host_merge_route_keeps_tombstone():
    """Keys beyond the device prefix take the heap merge; the tombstone
    rule is the same there."""
    from yugabyte_db_tpu.models.schema import Schema

    schema = Schema([ColumnSchema("k", DataType.STRING, ColumnKind.HASH),
                     ColumnSchema("v", DataType.INT64)], table_id="lk")
    cpu, tpu = make_engine("cpu", schema), make_engine("tpu", schema)
    vid = {c.name: c.col_id for c in schema.columns}["v"]
    name = "long-" + "x" * 40
    key = schema.encode_primary_key(
        {"k": name}, compute_hash_code(schema, {"k": name}))
    steps = [RowVersion(key, ht=1, liveness=True, columns={vid: 1}),
             RowVersion(key, ht=5, tombstone=True),
             RowVersion(key, ht=7, liveness=True, columns={vid: 7}),
             RowVersion(key, ht=9, tombstone=True)]
    for rv in steps:
        for e in (cpu, tpu):
            e.apply([rv])
            e.flush()
    before = _compactions("host_merge")
    assert tpu.compact(20, runs=tpu.runs[1:])
    assert _compactions("host_merge") == before + 1
    assert [(v.ht, v.tombstone) for _k, vs in tpu.runs[1].crun.iter_entries()
            for v in vs] == [(9, True)]
    for read_ht in (20, 100):
        assert cpu.scan(ScanSpec(read_ht=read_ht)).rows \
            == tpu.scan(ScanSpec(read_ht=read_ht)).rows == []


def test_compaction_refuses_runs_that_left_the_list():
    _schema, _cpu, tpu, _ht = _loaded()
    taken = tpu.runs[1:3]
    assert tpu.compact(runs=taken)
    assert tpu.compact(runs=taken) is False
    assert len(tpu.runs) == RUNS - 1


# -- (c) a compaction held mid-way --------------------------------------------

def _one_key_row(schema, i, ht, value):
    cid = {c.name: c.col_id for c in schema.columns}
    key = schema.encode_primary_key(
        {"k": f"n{i:04d}"}, compute_hash_code(schema, {"k": f"n{i:04d}"}))
    return RowVersion(key, ht=ht, liveness=True, columns={cid["a"]: value})


def test_compaction_held_midway_blocks_nobody():
    schema, cpu, tpu, ht = _loaded()
    asked = []
    tpu.compaction_listener = asked.append
    tpu.options.update(memtable_flush_versions=8, compaction_trigger=4)
    # the flush that follows asks for the five runs of like size, and
    # not for the small one it has just made
    for i in range(8):
        ht += 1
        rv = _one_key_row(schema, i, ht, i)
        cpu.apply([rv])
        tpu.apply([rv])
    assert len(asked) == 1 and asked[0] == tpu.runs[:RUNS]
    inputs = asked[0]
    at_built, release = threading.Event(), threading.Event()

    def hold(_arg):
        at_built.set()
        assert release.wait(30)

    SYNC_POINT.set_callback("tpu_engine:compact:built", hold)
    SYNC_POINT.enable()
    done = []
    worker = threading.Thread(
        target=lambda: done.append(tpu.compact(runs=inputs, by="worker")))
    try:
        worker.start()
        assert at_built.wait(30)
        runs_before = list(tpu.runs)
        # apply returns, and a flush under the held compaction adds its run
        for i in range(8, 16):
            ht += 1
            rv = _one_key_row(schema, i, ht, i)
            cpu.apply([rv])
            t0 = time.monotonic()
            tpu.apply([rv])
            assert time.monotonic() - t0 < 5
        assert tpu.runs[:len(runs_before)] == runs_before
        assert len(tpu.runs) == len(runs_before) + 1
        # reads answer as before
        _assert_reads_equal(schema, cpu, tpu, [ht, MAX_HT])
        # a scan that began before the swap...
        spec = ScanSpec(read_ht=ht)
        in_flight = tpu.scan_batch_async([spec])
        release.set()
        worker.join(30)
        assert done == [True]
        # ...ends on its own runs
        assert in_flight.finish()[0].rows == cpu.scan(spec).rows
    finally:
        release.set()
        SYNC_POINT.disable_and_clear()
        worker.join(30)
    # after the swap: one run where the inputs stood, the newer two behind
    assert all(t not in tpu.runs for t in inputs)
    assert len(tpu.runs) == 3 and tpu.runs[1] is runs_before[RUNS]
    assert tpu.runs[0].crun.num_versions == sum(
        t.crun.num_versions for t in inputs)
    _assert_reads_equal(schema, cpu, tpu, [ht, MAX_HT])


def test_retire_waits_for_the_unpin():
    """A run that a compaction replaced stays resident and accounted
    while a reader's pin is out, and goes at the unpin."""
    _schema, _cpu, tpu, _ht = _loaded()
    t = tpu.runs[1]
    t.pin()
    held = tpu.device_tracker.consumption
    assert held > 0
    assert tpu.compact(runs=tpu.runs[1:3])
    assert hbm_cache().peek(t._res_key) is not None
    t.unpin()
    assert hbm_cache().peek(t._res_key) is None
    assert tpu.device_tracker.consumption < held


# -- (d) a crash at each step of the subset replace ---------------------------

class _Crash(Exception):
    pass


@pytest.mark.parametrize("step", ["written", "published", "removed"])
def test_reopen_after_crash_reads_each_version_once(tmp_path, step):
    schema, cpu, tpu, _ht = _loaded(tmp_path)
    want = _entries_signature(cpu)
    assert _entries_signature(tpu) == want
    point = {"written": "tpu_engine:compact:built",
             "published": "tpu_engine:compact:swapped"}.get(step)

    def crash(_arg):
        raise _Crash(step)

    if point:
        SYNC_POINT.set_callback(point, crash)
        SYNC_POINT.enable()
    try:
        if point:
            with pytest.raises(_Crash):
                tpu.compact(runs=tpu.runs[1:4])
        else:
            assert tpu.compact(runs=tpu.runs[1:4])
    finally:
        SYNC_POINT.disable_and_clear()
    import os

    on_disk = sorted(n for n in os.listdir(tmp_path) if n.startswith("run-"))
    assert len(on_disk) == {"written": RUNS + 1, "published": RUNS + 1,
                            "removed": RUNS - 2}[step]
    again = make_engine("tpu", schema, {"rows_per_block": 64,
                                        "data_dir": str(tmp_path)})
    # each version once: a version read twice would stand twice here
    assert _entries_signature(again) == want
    assert len(again.runs) == (RUNS if step == "written" else RUNS - 2)
    # what the manifest does not name was removed at open
    assert sorted(os.path.basename(p) for p in again.persist.files) \
        == sorted(n for n in os.listdir(tmp_path) if n.startswith("run-"))
    # the merged run stands in its inputs' place in age order
    if step != "written":
        sizes = [t.crun.num_versions for t in again.runs]
        assert sizes[1] == max(sizes)
    # and the reopened engine flushes and compacts on
    rv = _one_key_row(schema, 9999, 10**6, 1)
    again.apply([rv])
    again.flush()
    assert again.compact()
    third = make_engine("tpu", schema, {"rows_per_block": 64,
                                        "data_dir": str(tmp_path)})
    assert len(third.runs) == 1
    assert len(_entries_signature(third)) == len(want) + 1


def test_directory_without_a_manifest_opens_by_name(tmp_path):
    """A directory written before there was a manifest."""
    import os

    schema, cpu, tpu, _ht = _loaded(tmp_path)
    os.unlink(os.path.join(tmp_path, "MANIFEST.json"))
    again = make_engine("tpu", schema, {"rows_per_block": 64,
                                        "data_dir": str(tmp_path)})
    assert len(again.runs) == RUNS
    assert _entries_signature(again) == _entries_signature(cpu)


# -- (e), (f): tablet peers ---------------------------------------------------

COLUMNS = [ColumnSchema("k", DataType.STRING, ColumnKind.HASH),
           ColumnSchema("v", DataType.INT64)]


@pytest.fixture
def cluster(tmp_path):
    from yugabyte_db_tpu.integration import MiniCluster

    c = MiniCluster(str(tmp_path), num_masters=1, num_tservers=3).start()
    c.wait_tservers_registered()
    yield c
    c.shutdown()


def _peers(cluster, table_name):
    return [p for ts in cluster.tservers.values()
            for p in ts.tablet_manager.peers()
            if p.tablet.meta.table_name == table_name]


def _wait_for(pred, what, timeout=30.0):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return
        time.sleep(0.05)
    raise AssertionError(f"timed out waiting for {what}")


def _applied(peers):
    stats = [p.raft.stats() for p in peers]
    return all(s["applied_index"] == max(x["last_index"] for x in stats)
               for s in stats)


def test_inert_at_rest(cluster):
    """Load, one flush, scans: no worker, no compaction, no request."""
    from yugabyte_db_tpu.client import YBSession

    client = cluster.client()
    table = client.create_table("rest", COLUMNS, num_tablets=2,
                                replication_factor=3, engine="tpu")
    before = _compactions()
    s = YBSession(client)
    for i in range(300):
        s.insert(table, {"k": f"k{i}", "v": i})
    assert s.flush() == 300
    peers = _peers(cluster, "rest")
    assert len(peers) == 6
    _wait_for(lambda: _applied(peers), "applies")
    for p in peers:
        p.flush()
    assert len(s.scan(table, ScanSpec()).rows) == 300
    for p in peers:
        assert p._compactor is None and not p._compact_requests
        assert p.tablet.engine.stats()["num_runs"] == 1
    assert _compactions() == before


# run.py ends with os._exit: print, just before it, every series and
# thread that a compaction would have left in the process.
_REHEARSE_AND_LIST = """
import os, runpy, sys, threading
_exit = os._exit
def exit_listing(code):
    from yugabyte_db_tpu.utils import metrics
    text = metrics.process_registry().prometheus_text()
    print("COMPACTION_TRACES", sorted(
        {ln.split("{")[0].split(" ")[0] for ln in text.splitlines()
         if ln.startswith("yb_compaction") or "engine.compact" in ln}
        | {t.name for t in threading.enumerate()
           if t.name.startswith("compact-")}), flush=True)
    _exit(code)
os._exit = exit_listing
sys.argv[0] = os.path.join("benchmark", "run.py")
runpy.run_path(sys.argv[0], run_name="__main__")
"""


def test_tpch_rehearsal_records_no_compaction():
    """PR 26's lesson: a read-only cell, whose tablets are loaded and
    flushed once, leaves no compaction span, series or worker."""
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, "-c", _REHEARSE_AND_LIST,
         "--workload", "tpch_throughput_q1q6", "--seed", "2147483659",
         "--seconds", "6", "--trace", "0", "--rehearse-cpu"],
        cwd=root, env=dict(os.environ, JAX_PLATFORMS="cpu"),
        capture_output=True, text=True, timeout=300)
    assert proc.returncode == 0, proc.stderr[-800:]
    assert "COMPACTION_TRACES []" in proc.stdout, proc.stdout[-800:]


def test_replicas_compact_in_the_background_and_agree(cluster):
    from yugabyte_db_tpu.client import YBSession

    client = cluster.client()
    table = client.create_table("churn", COLUMNS, num_tablets=1,
                                replication_factor=3, engine="tpu")
    peers = _peers(cluster, "churn")
    assert len(peers) == 3
    old = FLAGS.get("memtable_flush_versions")
    FLAGS.set("memtable_flush_versions", 40)
    stalls = metrics.apply_stall_histogram().count
    try:
        s = YBSession(client)
        want = {}
        for i in range(420):
            k = f"k{i % 150}"
            if i % 11 == 10:
                s.delete(table, {"k": k})
                want.pop(k, None)
            else:
                s.insert(table, {"k": k, "v": i})
                want[k] = i
            if i % 7 == 6:
                s.flush()
        s.flush()
        _wait_for(lambda: _applied(peers), "applies")
    finally:
        FLAGS.set("memtable_flush_versions", old)
    # every replica compacted, on its worker and never where it applies
    _wait_for(lambda: all(p.tablet.engine.stats()["num_runs"] < 8
                          for p in peers), "compactions")
    for p in peers:
        assert p._compactor is not None
        assert p._compactor.name.startswith("compact-")
    text = metrics.process_registry().prometheus_text()
    by = [ln for ln in text.splitlines() if ln.startswith("yb_compactions{")]
    assert any('by="worker"' in ln for ln in by)
    # (the flushes did hold the applying thread, and that is counted)
    assert metrics.apply_stall_histogram().count > stalls
    # the replicas hold the same rows, whenever each compacted
    rows = [sorted(p.tablet.engine.scan(ScanSpec(read_ht=MAX_HT)).rows)
            for p in peers]
    assert rows[0] == rows[1] == rows[2] == sorted(want.items())
    assert sorted(s.scan(table, ScanSpec()).rows) == sorted(want.items())
    # a manual compaction is a full one, and the worker is shut out of it
    for p in peers:
        p.compact()
        assert p.tablet.engine.stats()["num_runs"] <= 2
    rows = [sorted(p.tablet.engine.scan(ScanSpec(read_ht=MAX_HT)).rows)
            for p in peers]
    assert rows[0] == rows[1] == rows[2] == sorted(want.items())


# -- spans and counters -------------------------------------------------------

def _series(name, **labels):
    total = 0.0
    for ln in metrics.process_registry().prometheus_text().splitlines():
        if ln.startswith(name + "{") or ln.startswith(name + " "):
            if all(f'{k}="{v}"' in ln for k, v in labels.items()):
                total += float(ln.rsplit(" ", 1)[1])
    return total


def test_flush_and_compaction_spans_and_dispatch_counters():
    from yugabyte_db_tpu.utils import trace

    schema, _cpu, tpu = _mk_engines()
    tpu.options.update(memtable_flush_versions=16, compaction_trigger=4)
    before = {e: (_series("yb_device_dispatches", entry=e),
                  _series("yb_device_program_read_bytes", entry=e))
              for e in ("replay_flush", "resident_gc_mask")}
    stalls = metrics.apply_stall_histogram().count
    with trace.trace_request("test.apply") as t:
        for i in range(64):
            tpu.apply([_one_key_row(schema, i, 100 + i, i)])
    spans = [dict(s[4] or {}, name=s[0]) for s in t.spans]
    flushes = [s for s in spans if s["name"] == "engine.flush"]
    assert len(flushes) == 4
    assert {(s["thread"], s["route"]) for s in flushes} == {("apply",
                                                             "device")}
    assert metrics.apply_stall_histogram().count == stalls + 4
    (comp,) = [s for s in spans if s["name"] == "engine.compact"]
    # (an engine with no peer compacts where the write was applied)
    assert (comp["by"], comp["kind"], comp["route"]) \
        == ("apply", "subset", "device")
    assert (comp["runs_in"], comp["versions_in"], comp["versions_out"]) \
        == (4, 64, 64)
    assert len(tpu.runs) == 1
    # a manual flush is not the apply thread's
    tpu.memtable.apply([_one_key_row(schema, 99, 999, 1)])
    with trace.trace_request("test.flush") as t:
        tpu.flush()
    assert [(s[0], s[4]["thread"]) for s in t.spans] \
        == [("engine.flush", "maintenance")]
    assert metrics.apply_stall_histogram().count == stalls + 4
    # both device programs count their dispatches and what they read
    for entry, calls in (("replay_flush", 5), ("resident_gc_mask", 1)):
        n0, b0 = before[entry]
        assert _series("yb_device_dispatches", entry=entry) == n0 + calls
        assert _series("yb_device_program_read_bytes", entry=entry) > b0


def test_write_rpc_has_replicate_and_wal_sync_spans(cluster):
    from yugabyte_db_tpu.client import YBSession

    client = cluster.client()
    table = client.create_table("spans", COLUMNS, num_tablets=1,
                                replication_factor=3, engine="tpu")
    syncs = _series("yb_span_us_count", span="wal.sync")
    s = YBSession(client)
    for i in range(5):
        s.insert(table, {"k": f"k{i}", "v": i})
        s.flush()
    names = set()
    for ts in cluster.tservers.values():
        for method, samples in ts.rpcz.dump()["methods"].items():
            if method.startswith("ts.write"):
                names |= {sp["name"] for smp in samples
                          for sp in smp["spans"]}
    assert "raft.replicate" in names
    assert _series("yb_span_us_count", span="wal.sync") > syncs
    assert _series("yb_span_us_count", span="raft.replicate") >= 5
