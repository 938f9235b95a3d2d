"""Grouped aggregates over several sources (storage.tpu_engine
``_plan_overlay_grouped``): TPC-H Q1 (GROUP BY) and Q6 (an expression
sum) over one or more runs AND a live memtable stay device programs — the
grouped program over the overlay's masked primary and again over the
dirty keys' mini-run, partials combined by group value — and answer as
the CPU oracle engine does, exactly: inserts, overwrites, row tombstones,
several delta runs, every read point, groups that exist only in the
delta or lose their last row, writes and flushes between two scans, a
dirty set too large for the overlay, and the whole way through the PG
wire.
"""

import random

import numpy as np
import pytest

from yugabyte_db_tpu.models.partition import compute_hash_code
from yugabyte_db_tpu.storage import RowVersion, make_engine
from yugabyte_db_tpu.storage.residency import hbm_cache
from yugabyte_db_tpu.storage.row_version import MAX_HT
import yugabyte_db_tpu.storage.tpu_engine  # noqa: F401
from yugabyte_db_tpu.utils import metrics
from yugabyte_db_tpu.yql.pgsql import tpch

SCHEMA = tpch.lineitem_schema("ovg")
CID = {c.name: c.col_id for c in SCHEMA.columns}
SPECS = {"q1": tpch.q1_spec, "q6": tpch.q6_spec}


def enc(order: int, line: int) -> bytes:
    kv = {"l_orderkey": order, "l_linenumber": line}
    return SCHEMA.encode_primary_key(kv, compute_hash_code(SCHEMA, kv))


def line(rnd, order, ln, ht, flag=None, status=None, shipdate=None):
    """A whole lineitem at ``ht``; Q6's bands hit about one row in nine."""
    return RowVersion(enc(order, ln), ht=ht, liveness=True, columns={
        CID["l_quantity"]: rnd.randrange(1, 51),
        CID["l_extendedprice"]: rnd.randrange(90_000, 10_000_000),
        CID["l_discount"]: rnd.randrange(0, 11),
        CID["l_tax"]: rnd.randrange(0, 9),
        CID["l_returnflag"]: flag or rnd.choice("ANR"),
        CID["l_linestatus"]: status or rnd.choice("FO"),
        CID["l_shipdate"]: shipdate or rnd.randrange(9000, 9600)})


class Pair:
    """The CPU oracle and the TPU engine, fed the same versions."""

    def __init__(self, seed=11, orders=150, rows_per_block=64):
        self.rnd = random.Random(seed)
        self.cpu = make_engine("cpu", SCHEMA)
        self.tpu = make_engine("tpu", SCHEMA,
                               {"rows_per_block": rows_per_block})
        self.ht = 100
        self.apply([line(self.rnd, o, ln, self.tick())
                    for o in range(1, orders + 1) for ln in range(1, 5)])
        self.flush()

    def tick(self) -> int:
        self.ht += 1
        return self.ht

    def apply(self, versions) -> None:
        for e in (self.cpu, self.tpu):
            e.apply(list(versions))

    def flush(self) -> None:
        for e in (self.cpu, self.tpu):
            e.flush()

    def insert(self, orders, **kw) -> None:
        self.apply([line(self.rnd, o, ln, self.tick(), **kw)
                    for o in orders for ln in range(1, 4)])

    def delete(self, orders, lines=range(1, 5)) -> None:
        self.apply([RowVersion(enc(o, ln), ht=self.tick(), tombstone=True)
                    for o in orders for ln in lines])

    def overwrite(self, orders) -> None:
        self.apply([RowVersion(enc(o, 1), ht=self.tick(), columns={
            CID["l_quantity"]: self.rnd.randrange(1, 51),
            CID["l_discount"]: self.rnd.randrange(0, 11)})
            for o in orders])

    def refresh(self, first_new=10_000) -> None:
        """Inserts, overwrites and row tombstones, as a refresh leaves."""
        self.insert(range(first_new, first_new + 12))
        self.overwrite(range(20, 30))
        self.delete(range(1, 13))

    def same(self, name, read_ht=MAX_HT, **bounds):
        spec = SPECS[name](read_ht)
        for k, v in bounds.items():
            setattr(spec, k, v)
        want = self.cpu.scan(spec)
        got = self.tpu.scan(spec)
        assert got.columns == want.columns
        assert got.rows == want.rows, (name, read_ht)
        return got

    def close(self) -> None:
        self.cpu.close()
        self.tpu.close()


@pytest.fixture
def pair():
    p = Pair()
    yield p
    p.close()


def scans(kind="grouped", outcome="device") -> int:
    return sum(n for (k, o, _reason), n in metrics.overlay_scans().items()
               if (k, o) == (kind, outcome))


def fallbacks() -> int:
    return sum(metrics.grouped_agg_fallbacks().values())


def dispatches(entry: str) -> int:
    text = metrics.process_registry().prometheus_text()
    return int(float(next(
        (ln.split()[-1] for ln in text.splitlines()
         if ln.startswith("yb_device_dispatches{")
         and f'entry="{entry}"' in ln), 0)))


# -- (a) one run + a memtable; (b) a base run, two delta runs, a memtable -----

@pytest.mark.parametrize("name", ["q1", "q6"])
@pytest.mark.parametrize("shape", ["run_and_memtable", "three_runs_and_memtable"])
def test_answers_as_the_oracle_on_the_device(pair, name, shape):
    if shape == "three_runs_and_memtable":
        pair.refresh(10_000)
        pair.flush()
        pair.insert(range(11_000, 11_010))
        pair.delete(range(40, 45))
        pair.flush()
        assert len(pair.tpu.runs) == 3
    pair.refresh(12_000)
    assert not pair.tpu.memtable.is_empty
    before = scans(), fallbacks(), dispatches("grouped_aggregate")
    pair.same(name)
    assert scans() == before[0] + 1
    assert fallbacks() == before[1]
    # two programs a scan: the masked primary's and the mini-run's
    assert dispatches("grouped_aggregate") == before[2] + 1
    state = pair.tpu._overlay_cache[3]
    assert state is not None and state.delta is not None
    assert state.delta.crun.max_group_versions > 1   # multi-version: MVCC
    assert pair.tpu._plan_scan(SPECS[name](MAX_HT))[0] == "overlay_deferred"


def test_the_mini_run_dispatch_is_counted_under_its_own_entry(pair):
    pair.refresh()
    pair.same("q1")
    before = dispatches("overlay_delta_aggregate")
    pair.same("q1")
    pair.same("q6")
    assert dispatches("overlay_delta_aggregate") == before + 2


# -- (c) read points before, between and after the writes --------------------

@pytest.mark.parametrize("name", ["q1", "q6"])
def test_every_read_point_sees_its_own_table(pair, name):
    marks = [pair.ht]
    pair.insert(range(10_000, 10_012))
    marks.append(pair.ht)
    pair.overwrite(range(20, 30))
    marks.append(pair.ht)
    pair.delete(range(1, 13))
    marks += [pair.ht - 20, pair.ht, pair.ht + 5, MAX_HT]
    before = scans()
    answers = [pair.same(name, read_ht=rp).rows for rp in marks]
    assert scans() == before + len(marks)
    # (the writes change the answer: the read points are told apart)
    assert answers[0] != answers[-1]


# -- (d) a key deleted and inserted again --------------------------------------

@pytest.mark.parametrize("name", ["q1", "q6"])
def test_a_key_deleted_and_inserted_again(pair, name):
    pair.delete(range(1, 6))
    gone = pair.ht
    pair.apply([line(pair.rnd, o, ln, pair.tick())
                for o in range(1, 6) for ln in range(1, 5)])
    for rp in (gone, pair.ht, MAX_HT):
        pair.same(name, read_ht=rp)
    pair.delete(range(1, 3))
    pair.same(name)


# -- the mini-run's resolve: bounded lookback, the segmented form's bits --------

@pytest.mark.parametrize("name", ["q1", "q6"])
def test_the_mini_runs_lookback_program_is_the_segmented_one_bit_for_bit(
        pair, name):
    """The mini-run of a refresh holds an inserted row as one version and
    a deleted or overwritten one as two: its programs (Q1's hashed, its
    string planes being plain; Q6's ungrouped) resolve by a lookback of
    2, and their packed vectors are the segmented resolve's to the bit
    at read points before, between and after the writes."""
    from tests.test_group_agg import _both_resolves

    marks = [pair.ht]
    pair.insert(range(10_000, 10_012))
    marks.append(pair.ht)
    pair.overwrite(range(20, 30))
    pair.delete(range(1, 13))
    marks += [pair.ht - 20, pair.ht, MAX_HT]
    pair.same(name)
    delta = pair.tpu._overlay_cache[3].delta
    assert delta.crun.max_group_versions == 2
    dev = delta.pin("high")
    try:
        vectors = []
        for rp in marks:
            sig, _params, (got, want) = _both_resolves(
                pair.tpu, delta, dev.arrays, SPECS[name](rp))
            assert (sig.lookback, sig.radix) == (2, ())
            assert bool(sig.group_cols) == (name == "q1")
            assert (got == want).all(), (name, rp)
            vectors.append(got)
    finally:
        delta.unpin()
    # (the read points are told apart: before the writes every dirty key
    # that existed shows its base row, after them the deleted are gone)
    assert (vectors[0] != vectors[-1]).any()


# -- (e) a group only in the delta; a group whose every row is deleted --------

def test_groups_that_come_and_go_with_the_delta():
    p = Pair(orders=60)
    try:
        # a group of the base run alone: every row of it deleted later
        p.apply([line(p.rnd, 500, ln, p.tick(), flag="Z", status="Z")
                 for ln in range(1, 5)])
        p.flush()
        p.tpu.compact()
        p.cpu.compact()
        assert len(p.tpu.runs) == 1
        rows = p.same("q1").rows
        assert ("Z", "Z") in {r[:2] for r in rows}
        p.delete([500])
        p.insert(range(10_000, 10_004), flag="X", status="Y")
        before = scans()
        rows = p.same("q1").rows
        assert scans() == before + 1
        groups = {r[:2] for r in rows}
        assert ("X", "Y") in groups and ("Z", "Z") not in groups
        p.same("q6")
    finally:
        p.close()


# -- (f) a write between two scans; a flush or compaction between two scans ---

def test_a_write_between_two_scans_advances_the_state(pair):
    pair.refresh()
    pair.same("q1")
    state1 = pair.tpu._overlay_cache[3]
    delta1 = state1.delta
    built = metrics.overlay_build_histogram("delta").count
    pair.insert(range(13_000, 13_003))      # new keys: the dirty set grows
    pair.same("q1")
    state2 = pair.tpu._overlay_cache[3]
    assert state2 is not state1 and state2.delta is not delta1
    assert len(state2.rows) == len(state1.rows) + 9
    assert metrics.overlay_build_histogram("delta").count == built + 1
    assert state1.dropped and not state2.dropped
    pair.overwrite(range(20, 22))           # tracked keys: new versions
    pair.same("q6")
    pair.same("q1")
    # an unchanged memtable is a cache hit: the same state, the same run
    state3 = pair.tpu._overlay_cache[3]
    pair.same("q1")
    assert pair.tpu._overlay_cache[3] is state3
    assert state3.delta is not None


@pytest.mark.parametrize("mini_run", ["not_built_yet", "built"])
def test_a_write_lands_between_the_state_and_its_mini_run(pair, mini_run):
    """A scan takes the overlay state; before it asks for the state's
    mini-run another scan, after a write, advances the cache and lets
    that state go. The first scan still answers at its read point, from
    a run that is registered and accounted while it is pinned (never the
    residency manager's unmanaged upload) and gone afterwards."""
    from yugabyte_db_tpu.storage.residency import device_nbytes
    from yugabyte_db_tpu.utils.sync_point import SYNC_POINT

    pinned = hbm_cache().pinned_bytes()
    pair.refresh()
    if mini_run == "built":
        pair.same("q1")
    read_ht = pair.ht
    uploads = metrics.device_upload_histogram().count
    states = []

    def a_write_and_a_scan(_arg):
        SYNC_POINT.set_callback("tpu_engine:overlay_grouped:state_taken",
                                None)
        states.append(pair.tpu._overlay_cache[3])
        pair.insert(range(14_000, 14_003))
        pair.same("q1")
        states.append(pair.tpu._overlay_cache[3])

    SYNC_POINT.set_callback("tpu_engine:overlay_grouped:state_taken",
                            a_write_and_a_scan)
    SYNC_POINT.enable()
    try:
        pair.same("q1", read_ht=read_ht)
    finally:
        SYNC_POINT.disable_and_clear()
    old, new = states
    assert old is not new and old.dropped and old.delta is None
    # one upload a mini-run: the new state's, and the one the first scan
    # built for itself; the residency manager served every access
    assert metrics.device_upload_histogram().count == uploads + 2
    tpu = pair.tpu
    mask = device_nbytes(new.masked.dev.arrays["valid"])
    # (what the cache itself holds, the primary and its mask, is all
    # that is left pinned)
    assert hbm_cache().pinned_bytes() \
        == pinned + tpu._overlay_pinned.dev.nbytes + mask
    assert tpu.device_tracker.consumption == sum(
        t.dev.nbytes for t in tpu.runs) + new.delta.dev.nbytes + mask
    pair.same("q1")
    pair.same("q6", read_ht=read_ht)


@pytest.mark.parametrize("how", ["flush", "compact"])
def test_a_flush_or_compaction_drops_the_state_and_its_bytes(pair, how):
    # (the cache is the process's: another test file's engine that was
    # never closed may hold a pin of its own in this worker)
    pinned = hbm_cache().pinned_bytes()
    pair.refresh()
    pair.same("q1")
    pair.same("q6")
    tracker = pair.tpu.device_tracker
    runs_before = sum(t.dev.nbytes for t in pair.tpu.runs)
    assert tracker.consumption > runs_before     # mask + mini-run
    pair.flush()
    if how == "compact":
        pair.cpu.compact()
        pair.tpu.compact()
    assert pair.tpu._overlay_cache is None
    assert pair.tpu._overlay_pinned is None
    # what is accounted is what the runs hold, and nothing is pinned
    resident = sum(t.dev.nbytes for t in pair.tpu.runs)
    assert tracker.consumption == resident
    assert hbm_cache().pinned_bytes() == pinned
    before = scans()
    pair.same("q1")
    pair.same("q6")
    # (two runs after the flush: the overlay again; one after compaction)
    assert scans() == before + (2 if how == "flush" else 0)


# -- (g) a dirty set past half the primary: the host serves, exactly ---------

@pytest.mark.parametrize("name", ["q1", "q6"])
def test_a_dirty_set_past_half_the_primary_is_host_served(name):
    p = Pair(orders=30)
    try:
        p.insert(range(10_000, 10_030))     # 90 dirty keys over 120 rows
        host, dev = scans(outcome="host"), scans()
        p.same(name)
        assert scans(outcome="host") == host + 1
        assert scans() == dev
        assert metrics.overlay_scans()[("grouped", "host", "dirty_set")] > 0
    finally:
        p.close()


# -- the masked valid plane keeps its leaf kind --------------------------------

def test_a_packed_valid_plane_stays_packed():
    """A run uploaded encoded has a bit-packed ``valid``; the masked
    primary's keeps that form (the dirty rows' bits cleared in the
    words), so the program over it has the single-source signature."""
    from yugabyte_db_tpu.ops import encodings

    p = Pair(orders=100, rows_per_block=64)
    try:
        trun = p.tpu.runs[0]
        trun.invalidate_device()            # (a device flush's run is plain)
        assert encodings.leaf_kind(trun.dev.arrays["valid"]) == "bits"
        rows_form = metrics.grouped_presence().get("rows", 0)
        p.same("q1")                        # single source: packed
        p.refresh()
        p.same("q1")
        p.same("q6")
        state = p.tpu._overlay_cache[3]
        masked = state.masked.dev.arrays["valid"]
        assert encodings.leaf_kind(masked) == "bits"
        cleared = np.asarray(encodings.decode_leaf(
            masked, trun.dev.B, trun.crun.R))[:trun.crun.B].reshape(-1)
        plain = trun.crun.valid.reshape(-1).copy()
        plain[state.idx] = False
        assert (cleared == plain).all() and state.idx.size > 0
        # the mini-run's program is by rows (not flat); the masked
        # primary's compiled nothing new in the rows form
        assert metrics.grouped_presence().get("rows", 0) <= rows_form + 1
    finally:
        p.close()


# -- (h) through the PG wire ---------------------------------------------------

def test_q1_and_q6_after_insert_and_delete_over_the_pg_wire(tmp_path):
    from yugabyte_db_tpu.drivers.minipg import PgConnection
    from yugabyte_db_tpu.integration.mini_cluster import MiniCluster
    from yugabyte_db_tpu.tools.admin_client import AdminClient

    cols = ("l_orderkey", "l_linenumber", "l_quantity", "l_extendedprice",
            "l_discount", "l_tax", "l_returnflag", "l_linestatus",
            "l_shipdate")
    ddl = ("CREATE TABLE {t} (l_orderkey BIGINT, l_linenumber INT, "
           "l_quantity INT, l_extendedprice BIGINT, l_discount TINYINT, "
           "l_tax TINYINT, l_returnflag TEXT, l_linestatus TEXT, "
           "l_shipdate INT, PRIMARY KEY ((l_orderkey), l_linenumber)) "
           "SPLIT INTO 2 TABLETS")

    def values(rows):
        return ",".join("(" + ",".join(repr(r[c]) for c in cols) + ")"
                        for r in rows)

    base = list(tpch.generate_lineitem(400, seed=9))
    extra = [dict(r, l_orderkey=r["l_orderkey"] + 5_000)
             for r in tpch.generate_lineitem(40, seed=10)]
    mc = MiniCluster(str(tmp_path), num_masters=1, num_tservers=1,
                     transport="socket").start()
    servers = []
    try:
        mc.wait_tservers_registered()
        answers = {}
        for engine in ("cpu", "tpu"):
            table = f"lineitem_{engine}"
            srv, addr = mc.start_pg_server(engine=engine, num_tablets=2,
                                           replication_factor=1,
                                           rpc_timeout_s=120)
            servers.append(srv)
            conn = PgConnection(*addr, timeout=120)
            conn.execute(ddl.format(t=table))
            conn.execute(f"INSERT INTO {table} ({','.join(cols)}) "
                         f"VALUES {values(base)}")
            assert AdminClient(mc.transport, mc.master_uuids).flush_table(
                table) == 2
            before = scans()
            conn.execute(f"INSERT INTO {table} ({','.join(cols)}) "
                         f"VALUES {values(extra)}")
            for order in (1, 2, 3, 50):
                tag = conn.execute(f"DELETE FROM {table} WHERE "
                                   f"l_orderkey = {order}").command_tag
                assert tag == "DELETE 4"
            assert conn.execute(f"DELETE FROM {table} WHERE l_orderkey = "
                                "777777").command_tag == "DELETE 0"
            answers[engine] = [
                conn.execute(tpch.q1_sql(table=table)).rows,
                conn.execute(tpch.q6_sql(table=table)).rows]
            conn.close()
            if engine == "tpu":
                # Q1 and Q6, two tablets each: run + memtable, the device
                assert scans() == before + 4
        assert answers["tpu"] == answers["cpu"]
        assert len(answers["tpu"][0]) >= 3
    finally:
        for srv in servers:
            srv.shutdown()
        mc.shutdown()
