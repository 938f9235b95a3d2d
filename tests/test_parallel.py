"""Sharded multi-tablet aggregate vs the CPU oracle.

The mesh-parallel combine (psum / lexicographic pmax over the ("t", "b")
mesh) must produce exactly what a single CPU engine holding the union of
all tablets' rows produces — the multi-tablet analog of the engine-diff
tests, and the test for BASELINE config 5 (the reference merges per-tablet
aggregate partials client-side: src/yb/yql/cql/ql/exec/eval_aggr.cc).

Runs on 8 virtual CPU devices (conftest) as a 4-tablet x 2-block-shard mesh.
"""

import random

import jax
import numpy as np
import pytest
from jax.sharding import Mesh

from yugabyte_db_tpu.models.datatypes import DataType
from yugabyte_db_tpu.models.partition import compute_hash_code
from yugabyte_db_tpu.models.schema import ColumnKind, ColumnSchema, Schema
from yugabyte_db_tpu.parallel import ShardedTablets, sharded_aggregate
from yugabyte_db_tpu.storage import (
    AggSpec, Predicate, RowVersion, ScanSpec, make_engine,
)
from yugabyte_db_tpu.storage.columnar import ColumnarRun
from yugabyte_db_tpu.storage.memtable import MemTable
from yugabyte_db_tpu.storage.row_version import MAX_HT

pytestmark = pytest.mark.mesh


def make_schema():
    return Schema([
        ColumnSchema("k", DataType.STRING, ColumnKind.HASH),
        ColumnSchema("r", DataType.INT64, ColumnKind.RANGE),
        ColumnSchema("a", DataType.INT64),
        ColumnSchema("c", DataType.DOUBLE),
        ColumnSchema("d", DataType.INT32),
    ], table_id="t")


def enc(schema, k, r):
    return schema.encode_primary_key(
        {"k": k, "r": r}, compute_hash_code(schema, {"k": k}))


def build_world(seed, num_tablets=4, num_keys=400, rows_per_block=16):
    """Random MVCC history distributed round-robin over tablets; returns
    (runs, oracle_engine, all_keys_sorted, max_ht)."""
    rng = random.Random(seed)
    schema = make_schema()
    oracle = make_engine("cpu", schema)
    mems = [MemTable() for _ in range(num_tablets)]
    cid = {c.name: c.col_id for c in schema.columns}
    ht = 100
    keys = []
    for i in range(num_keys):
        key = enc(schema, f"user{i:05d}", rng.randrange(10))
        keys.append(key)
        t = i % num_tablets
        for _ in range(rng.randrange(1, 4)):
            ht += rng.randrange(1, 5)
            roll = rng.random()
            if roll < 0.08:
                rv = RowVersion(key, ht=ht, tombstone=True)
            elif roll < 0.2:
                rv = RowVersion(key, ht=ht, columns={
                    cid["a"]: rng.randrange(-10**12, 10**12)})
            else:
                rv = RowVersion(key, ht=ht, liveness=True, columns={
                    cid["a"]: rng.randrange(-10**12, 10**12),
                    cid["c"]: rng.uniform(-1e6, 1e6),
                    cid["d"]: rng.randrange(-10**6, 10**6),
                })
            mems[t].apply([rv])
            oracle.apply([rv])
    runs = [ColumnarRun.build(make_schema(), m.drain_sorted(), rows_per_block)
            for m in mems]
    return runs, oracle, sorted(keys), ht


@pytest.fixture(scope="module")
def world():
    return build_world(seed=7)


@pytest.fixture(scope="module")
def mesh():
    devs = np.array(jax.devices()[:8]).reshape(4, 2)
    return Mesh(devs, ("t", "b"))


@pytest.fixture(scope="module")
def sharded(world, mesh):
    runs, _, _, _ = world
    return ShardedTablets(make_schema(), runs, mesh, window_blocks=2)


AGGS = [
    AggSpec("count", None), AggSpec("sum", "a"), AggSpec("min", "a"),
    AggSpec("max", "a"), AggSpec("sum", "d"), AggSpec("min", "d"),
    AggSpec("max", "c"), AggSpec("min", "c"), AggSpec("avg", "d"),
    AggSpec("count", "a"),
]


def check(st, oracle, spec):
    got = sharded_aggregate(st, spec)
    want = oracle.scan(spec)
    assert got.columns == want.columns
    for g, w in zip(got.rows[0], want.rows[0]):
        if w is None or g is None:
            assert g == w
        elif isinstance(w, float):
            assert g == pytest.approx(w, rel=1e-5, abs=1e-3)
        else:
            assert g == w


def test_full_range_aggregates(world, sharded):
    _, oracle, _, max_ht = world
    spec = ScanSpec(read_ht=max_ht + 1, aggregates=AGGS)
    check(sharded, oracle, spec)


def test_bounded_range(world, sharded):
    _, oracle, keys, max_ht = world
    lo, hi = keys[len(keys) // 5], keys[4 * len(keys) // 5]
    spec = ScanSpec(lower=lo, upper=hi, read_ht=max_ht + 1, aggregates=AGGS)
    check(sharded, oracle, spec)


def test_historical_read_points(world, sharded):
    _, oracle, keys, max_ht = world
    for read_ht in (150, 400, 800, max_ht // 2):
        spec = ScanSpec(read_ht=read_ht, aggregates=AGGS)
        check(sharded, oracle, spec)


def test_predicates(world, sharded):
    _, oracle, _, max_ht = world
    cases = [
        [Predicate("a", ">=", 0)],
        [Predicate("d", "<", 0), Predicate("a", "!=", 3)],
        [Predicate("c", ">", -5e5), Predicate("c", "<=", 5e5)],
        [Predicate("a", ">", -10**11), Predicate("d", ">=", -500000)],
    ]
    for preds in cases:
        spec = ScanSpec(read_ht=max_ht + 1, predicates=preds, aggregates=AGGS)
        check(sharded, oracle, spec)


def test_empty_range(world, sharded):
    _, oracle, keys, max_ht = world
    spec = ScanSpec(lower=keys[-1] + b"\xff", read_ht=max_ht + 1,
                    aggregates=[AggSpec("count", None), AggSpec("sum", "a"),
                                AggSpec("min", "d")])
    check(sharded, oracle, spec)


def test_exact_int64_sum_at_scale():
    """Big magnitudes: digit-vector psum must be bit-exact where f64 would
    lose precision."""
    runs, oracle, _, max_ht = build_world(seed=99, num_keys=300)
    devs = np.array(jax.devices()[:8]).reshape(4, 2)
    mesh = Mesh(devs, ("t", "b"))
    st = ShardedTablets(make_schema(), runs, mesh, window_blocks=2)
    spec = ScanSpec(read_ht=max_ht + 1, aggregates=[AggSpec("sum", "a")])
    got = sharded_aggregate(st, spec)
    want = oracle.scan(spec)
    assert got.rows[0][0] == want.rows[0][0]  # exact int equality


# -- sharded row/paging path -------------------------------------------------

def build_flat_world(seed, num_tablets=8, num_keys=800, rows_per_block=16):
    """Single-version rows (the flat-run shape the row path serves),
    spread over tablets; per-tablet CPU oracles for page parity."""
    rng = random.Random(seed)
    schema = make_schema()
    mems = [MemTable() for _ in range(num_tablets)]
    oracles = [make_engine("cpu", schema) for _ in range(num_tablets)]
    cid = {c.name: c.col_id for c in schema.columns}
    ht = 100
    for i in range(num_keys):
        key = enc(schema, f"user{i:05d}", rng.randrange(10))
        t = i % num_tablets
        ht += 1
        if rng.random() < 0.05:
            rv = RowVersion(key, ht=ht, tombstone=True)
        else:
            cols = {cid["a"]: rng.randrange(-10**12, 10**12),
                    cid["d"]: rng.randrange(-10**6, 10**6)}
            if rng.random() < 0.8:
                cols[cid["c"]] = rng.uniform(-1e6, 1e6)
            rv = RowVersion(key, ht=ht, liveness=True, columns=cols)
        mems[t].apply([rv])
        oracles[t].apply([rv])
    runs = []
    for m, o in zip(mems, oracles):
        o.flush()
        runs.append(ColumnarRun.build(make_schema(), m.drain_sorted(),
                                      rows_per_block))
    return schema, runs, oracles, ht


def test_sharded_row_pages_ycsbe_style():
    """8-way sharded YCSB-E shape on the CPU mesh: LIMIT pages with a
    predicate, chained by resume token per tablet order, match the
    per-tablet oracles' union exactly."""
    from yugabyte_db_tpu.parallel import sharded_row_page

    schema, runs, oracles, max_ht = build_flat_world(seed=3)
    devs = np.array(jax.devices()[:8]).reshape(4, 2)
    mesh = Mesh(devs, ("t", "b"))
    st = ShardedTablets(schema, runs, mesh, window_blocks=2)

    spec_kw = dict(read_ht=max_ht + 1,
                   predicates=[Predicate("d", ">=", 0)],
                   projection=["k", "r", "a", "d"])
    # Expected: per-tablet oracle scans concatenated in tablet order.
    want = []
    for o in oracles:
        want.extend(o.scan(ScanSpec(**spec_kw)).rows)

    got = []
    token = None
    pages = 0
    while True:
        res = sharded_row_page(st, ScanSpec(limit=100, **spec_kw),
                               resume=token)
        got.extend(res.rows)
        pages += 1
        if res.resume_key is None:
            break
        token = res.resume_key
        assert pages < 50
    # Pages walk tablets in order; within a tablet rows are key-ordered;
    # chaining by the (tablet, key) token visits every matching row
    # exactly once.
    assert got == want
    assert pages > 1


def test_sharded_row_pages_bounds_and_historical():
    from yugabyte_db_tpu.parallel import sharded_row_page

    schema, runs, oracles, max_ht = build_flat_world(seed=11,
                                                     num_tablets=4,
                                                     num_keys=300)
    devs = np.array(jax.devices()[:8]).reshape(4, 2)
    mesh = Mesh(devs, ("t", "b"))
    st = ShardedTablets(schema, runs, mesh, window_blocks=2)
    lo = enc(schema, "user00050", 0)
    hi = enc(schema, "user00250", 0)
    for rht in (max_ht + 1, max_ht // 2 + 60):
        kw = dict(lower=lo, upper=hi, read_ht=rht,
                  projection=["k", "a"])
        want = []
        for o in oracles:
            want.extend(o.scan(ScanSpec(**kw)).rows)
        got = sharded_row_page(st, ScanSpec(limit=4096, **kw))
        assert sorted(got.rows) == sorted(want), rht


def build_mvcc_tablets(seed, num_tablets=4, num_keys=240,
                       rows_per_block=16):
    """Multi-version histories with tombstones, TTL expiry and same-ht
    write_id ties, with PER-TABLET oracles (row scans compare in tablet
    order, unlike the union-oracle aggregate tests)."""
    rng = random.Random(seed)
    schema = make_schema()
    mems = [MemTable() for _ in range(num_tablets)]
    oracles = [make_engine("cpu", schema) for _ in range(num_tablets)]
    cid = {c.name: c.col_id for c in schema.columns}
    ht = 100
    for i in range(num_keys):
        key = enc(schema, f"user{i:05d}", rng.randrange(10))
        t = i % num_tablets
        for _ in range(rng.randrange(1, 4)):
            ht += rng.randrange(1, 5)
            roll = rng.random()
            if roll < 0.08:
                rv = RowVersion(key, ht=ht, tombstone=True)
            elif roll < 0.16:
                # TTL: some already expired at the read point, some not.
                rv = RowVersion(key, ht=ht, liveness=True,
                                expire_ht=ht + rng.randrange(1, 400),
                                columns={cid["a"]: rng.randrange(10**9)})
            elif roll < 0.24:
                # Same-ht write_id tie: the later write_id wins.
                rv = RowVersion(key, ht=ht, liveness=True, columns={
                    cid["a"]: rng.randrange(10**9)})
                mems[t].apply([rv])
                oracles[t].apply([rv])
                rv = RowVersion(key, ht=ht, write_id=1, columns={
                    cid["a"]: rng.randrange(10**9)})
            elif roll < 0.4:
                rv = RowVersion(key, ht=ht, columns={
                    cid["d"]: rng.randrange(-10**6, 10**6)})
            else:
                rv = RowVersion(key, ht=ht, liveness=True, columns={
                    cid["a"]: rng.randrange(-10**12, 10**12),
                    cid["c"]: rng.uniform(-1e6, 1e6),
                    cid["d"]: rng.randrange(-10**6, 10**6),
                })
            mems[t].apply([rv])
            oracles[t].apply([rv])
    runs = [ColumnarRun.build(make_schema(), m.drain_sorted(),
                              rows_per_block) for m in mems]
    assert any(r.max_group_versions > 1 for r in runs)
    return schema, runs, oracles, ht


def _page_all(st, spec_kw, limit):
    from yugabyte_db_tpu.parallel import sharded_row_page

    got, token, pages = [], None, 0
    while True:
        res = sharded_row_page(st, ScanSpec(limit=limit, **spec_kw),
                               resume=token)
        got.extend(res.rows)
        pages += 1
        assert pages < 80
        if res.resume_key is None:
            return got, pages
        token = res.resume_key


def test_sharded_row_pages_mvcc(mesh):
    """Row paging over MULTI-VERSION runs: on-device MVCC resolution
    (visibility, tombstone shadowing, TTL, write_id ties) must match the
    per-tablet CPU oracles at current and historical read points."""
    schema, runs, oracles, max_ht = build_mvcc_tablets(seed=17)
    st = ShardedTablets(schema, runs, mesh, window_blocks=2)
    assert any(r.max_group_versions > 1 for r in st.runs)
    for rht in (max_ht + 1, max_ht // 2 + 60):
        spec_kw = dict(read_ht=rht, projection=["k", "r", "a", "d"])
        want = []
        for o in oracles:
            want.extend(o.scan(ScanSpec(**spec_kw)).rows)
        got, pages = _page_all(st, spec_kw, limit=64)
        assert got == want, rht
        assert pages > 1


def test_sharded_row_pages_encoded_vs_plain(mesh):
    """Encoded stacks (compressed device planes) serve byte-identical
    pages to the uncompressed stack — including resume-token chains."""
    schema, runs, oracles, max_ht = build_mvcc_tablets(seed=29)
    st_enc = ShardedTablets(schema, runs, mesh, window_blocks=2,
                            encode=True)
    st_plain = ShardedTablets(schema, runs, mesh, window_blocks=2,
                              encode=False)
    assert st_enc.encoded and not st_plain.encoded
    spec_kw = dict(read_ht=max_ht + 1, projection=["k", "r", "a", "c"])
    got_e, _ = _page_all(st_enc, spec_kw, limit=96)
    got_p, _ = _page_all(st_plain, spec_kw, limit=96)
    assert got_e == got_p
    want = []
    for o in oracles:
        want.extend(o.scan(ScanSpec(**spec_kw)).rows)
    assert got_e == want


def test_update_tablet_in_place(mesh):
    """Single-tablet refresh: update_tablet rewrites one slot of the
    stacked arrays on device (no rebuild), after which aggregates and
    row pages serve the NEW run's data; per-device residency accounting
    is unchanged (same shapes)."""
    from yugabyte_db_tpu.parallel import sharded_row_page
    from yugabyte_db_tpu.storage.residency import hbm_cache

    schema, runs, oracles, max_ht = build_flat_world(seed=41,
                                                     num_tablets=4,
                                                     num_keys=200)
    st = ShardedTablets(schema, runs, mesh, window_blocks=2,
                        encode=False)
    before = {d: v["resident_bytes"]
              for d, v in hbm_cache().stats()["by_device"].items()}
    # New data for tablet 2: rewrite every row's d to a sentinel value.
    t = 2
    mem = MemTable()
    o2 = make_engine("cpu", schema)
    cid = {c.name: c.col_id for c in schema.columns}
    ht = max_ht
    old = oracles[t].scan(ScanSpec(read_ht=max_ht + 1,
                                   projection=["k", "r"]))
    rng = random.Random(1)
    for k, r in old.rows:
        ht += 1
        rv = RowVersion(enc(schema, k, r), ht=ht, liveness=True, columns={
            cid["a"]: rng.randrange(10**9), cid["d"]: 777})
        mem.apply([rv])
        o2.apply([rv])
    new_run = ColumnarRun.build(make_schema(), mem.drain_sorted(), 16)
    assert st.update_tablet(t, new_run)
    after = {d: v["resident_bytes"]
             for d, v in hbm_cache().stats()["by_device"].items()}
    assert after == before  # same shapes -> same per-device charge
    spec_kw = dict(read_ht=ht + 1, projection=["k", "r", "a", "d"])
    want = []
    for i, o in enumerate(oracles):
        want.extend((o2 if i == t else o).scan(ScanSpec(**spec_kw)).rows)
    got, _ = _page_all(st, spec_kw, limit=4096)
    assert got == want
    res = sharded_row_page(st, ScanSpec(
        read_ht=ht + 1, predicates=[Predicate("d", "=", 777)],
        projection=["k", "d"], limit=4096))
    assert len(res.rows) == len(old.rows)
    # Encoded stacks can't splice a plain run in place: callers rebuild.
    st_enc = ShardedTablets(schema, runs, mesh, window_blocks=2,
                            encode=True)
    if st_enc.encoded:
        assert not st_enc.update_tablet(t, new_run)


def test_stack_close_mid_serve(mesh):
    """close() releases the stack's residency pin immediately but keeps
    the arrays alive for in-flight pages — the flush/compaction
    supersede-while-serving case must neither leak pins nor break the
    page being served."""
    from yugabyte_db_tpu.storage.residency import hbm_cache
    from yugabyte_db_tpu.utils.memtracker import root_tracker

    import gc

    tracker = root_tracker().child("device").child("sharded")
    gc.collect()
    hbm_cache().stats()  # reap stacks dead from earlier tests first
    base = tracker.consumption
    schema, runs, oracles, max_ht = build_flat_world(seed=43,
                                                     num_tablets=4,
                                                     num_keys=200)
    st = ShardedTablets(schema, runs, mesh, window_blocks=2)
    assert tracker.consumption > base
    spec_kw = dict(read_ht=max_ht + 1, projection=["k", "a"])
    from yugabyte_db_tpu.parallel import sharded_row_page

    first = sharded_row_page(st, ScanSpec(limit=32, **spec_kw))
    assert first.resume_key is not None
    st.close()
    # Pin + MemTracker charge gone the moment the stack is superseded...
    assert tracker.consumption == base
    # ...and double-close stays a no-op.
    st.close()
    assert tracker.consumption == base
    # The in-flight page chain still serves, byte-identical.
    got = list(first.rows)
    token = first.resume_key
    while token is not None:
        res = sharded_row_page(st, ScanSpec(limit=32, **spec_kw),
                               resume=token)
        got.extend(res.rows)
        token = res.resume_key
    want = []
    for o in oracles:
        want.extend(o.scan(ScanSpec(**spec_kw)).rows)
    assert got == want


# -- the grouped mesh program: TPC-H Q1 and Q6 over a ("t", "b") mesh -----------
#
# ops.group_agg's window loop as the shard body
# (parallel.sharded.sharded_grouped_aggregate), against (i) the
# benchmark's plain reference (numpy; imports nothing of the program) and
# (ii) the CPU oracle engine, bit for bit.

def _mesh_of(n):
    shape = {1: (1, 1), 2: (1, 2), 4: (2, 2), 8: (4, 2)}[n]
    return Mesh(np.array(jax.devices()[:n]).reshape(shape), ("t", "b"))


def _pg_q1_spec(read_ht, cutoff):
    """Q1 as the PG executor pushes it down: the two averages are a sum
    and a count each (``g2a9p1f1``)."""
    from yugabyte_db_tpu.yql.pgsql import tpch

    base = tpch.q1_spec(read_ht, cutoff)
    a = base.aggregates
    return ScanSpec(
        read_ht=read_ht, predicates=base.predicates, group_by=base.group_by,
        aggregates=a[:4] + [
            AggSpec("sum", "l_quantity", label="aq_s"),
            AggSpec("count", "l_quantity", label="aq_c"),
            AggSpec("sum", "l_extendedprice", label="ap_s"),
            AggSpec("count", "l_extendedprice", label="ap_c"), a[4]])


def _q1_as_reference(res):
    return sorted([f, s, sq, sp, sd, sc, aq / aqn, ap / apn, n]
                  for f, s, sq, sp, sd, sc, aq, aqn, ap, apn, n in res.rows)


def _q6_spec(read_ht, lo, hi, dlo, dhi, qty):
    from yugabyte_db_tpu.yql.pgsql import tpch

    spec = tpch.q6_spec(read_ht)
    spec.predicates = [
        Predicate("l_shipdate", ">=", lo), Predicate("l_shipdate", "<", hi),
        Predicate("l_discount", ">=", dlo), Predicate("l_discount", "<=", dhi),
        Predicate("l_quantity", "<", qty)]
    return spec


class _Lineitem:
    """A seeded LINEITEM from the benchmark's reference generator, in
    ``T`` host-flushed TPU engines (hash-partitioned as tablets are), a
    CPU oracle engine holding all of it, and the reference itself."""

    def __init__(self, rows=4000, seed=2_147_483_777, tablets=2,
                 tablet_of=None, rows_per_block=64):
        from benchmark.references.tpch_lineitem import Reference
        from yugabyte_db_tpu.utils.flags import FLAGS
        from yugabyte_db_tpu.yql.pgsql import tpch

        self.schema = schema = tpch.lineitem_schema()
        self.ref = Reference({"scale": {"rows": rows},
                              "schema": {"table": "lineitem", "ddl": []},
                              "load": {"batch_ops": 1000}}, seed)
        self.cpu = make_engine("cpu", schema)
        cid = {c.name: c.col_id for c in schema.columns}
        keys = [c.name for c in schema.key_columns]
        # a host-built run keeps its presence planes as "bits" leaves
        old = FLAGS.get("tpu_device_flush")
        FLAGS.set("tpu_device_flush", False)
        try:
            self.tpus = [make_engine("tpu", schema,
                                     dict(rows_per_block=rows_per_block))
                         for _ in range(tablets)]
            ht = 1000
            for batch in self.ref.batches():
                for row in batch:
                    kv = {k: row[k] for k in keys}
                    hc = compute_hash_code(schema, kv)
                    ht += 1
                    rv = RowVersion(
                        schema.encode_primary_key(kv, hc), ht=ht,
                        liveness=True,
                        columns={cid[n]: row[n] for n in cid
                                 if n not in keys})
                    t = (tablet_of(row) if tablet_of
                         else hc * tablets >> 16)
                    self.tpus[t].apply([rv])
                    self.cpu.apply([rv])
            for e in self.tpus:
                e.flush()
        finally:
            FLAGS.set("tpu_device_flush", old)
        self.max_ht = ht
        self.runs = [e.runs[0].crun for e in self.tpus]

    def stack(self, mesh, encode=True):
        return ShardedTablets(self.schema, self.runs, mesh, window_blocks=2,
                              encode=encode)


@pytest.fixture(scope="module")
def lineitem():
    return _Lineitem()


Q6_PARAMS = dict(lo=9131, hi=9496, dlo=4, dhi=6, qty=24)


@pytest.mark.parametrize("encode", [True, False], ids=["encoded", "plain"])
@pytest.mark.parametrize("devices", [1, 2, 4, 8])
@pytest.mark.parametrize("stmt", ["q1", "q6"])
def test_grouped_mesh_equals_reference_and_oracle(lineitem, stmt, devices,
                                                  encode):
    from yugabyte_db_tpu.parallel import sharded_grouped_aggregate

    st = lineitem.stack(_mesh_of(devices), encode)
    assert st.encoded == encode
    rht = lineitem.max_ht + 1
    if stmt == "q1":
        spec = _pg_q1_spec(rht, 10471)
        got = sharded_grouped_aggregate(st, spec, lineitem.tpus[0])
        assert _q1_as_reference(got) == lineitem.ref.q1(10471)
    else:
        spec = _q6_spec(rht, **Q6_PARAMS)
        got = sharded_grouped_aggregate(st, spec, lineitem.tpus[0])
        assert [list(r) for r in got.rows] == lineitem.ref.q6(**Q6_PARAMS)
    want = lineitem.cpu.scan(spec)
    assert (got.columns, got.rows, got.rows_scanned) == (
        want.columns, want.rows, want.rows_scanned)


def test_grouped_mesh_traces_the_packed_presence_form_on_an_encoded_stack(
        lineitem):
    """Hazard of the re-encoded stack: Q1's program must see "bits"
    leaves (the packed presence form), and no plane may be an "rle" leaf
    (its decode is a gather, serialized on the TPU)."""
    from yugabyte_db_tpu.parallel import sharded, sharded_grouped_aggregate
    from yugabyte_db_tpu.utils import metrics

    st = lineitem.stack(_mesh_of(4), True)
    planes, cols = st.enc_struct
    kinds = [k for _n, k in planes] + [k for _c, e in cols for _n, k in e]
    assert "rle" not in kinds and "bits" in kinds
    sharded._compiled_dist_grouped.cache_clear()
    before = metrics.grouped_presence()
    sharded_grouped_aggregate(st, _pg_q1_spec(lineitem.max_ht + 1, 10471),
                              lineitem.tpus[0])
    after = metrics.grouped_presence()
    assert after["packed"] > before["packed"]
    assert after["rows"] == before["rows"]


def test_grouped_mesh_stays_hashed_at_num_buckets(lineitem, monkeypatch):
    """A stack holds no "dict" leaf (its encoder drops the code plane),
    so the mesh's signature is not addressed by dictionary codes: NB
    stays NUM_BUCKETS whatever the tablets' own runs hold, and a mesh
    dispatch of Q1 counts in ``yb_grouped_buckets{form="hashed"}``, one
    of Q6 (no group column, no buckets) in neither form."""
    from yugabyte_db_tpu.ops import group_agg
    from yugabyte_db_tpu.parallel import sharded, sharded_grouped_aggregate
    from yugabyte_db_tpu.utils import metrics

    st = lineitem.stack(_mesh_of(4), True)
    planes, cols = st.enc_struct
    assert "dict" not in [k for _n, k in planes] + [
        k for _c, e in cols for _n, k in e]
    sigs = []
    compiled = sharded._compiled_dist_grouped

    def recording(sig, *rest):
        sigs.append(sig)
        return compiled(sig, *rest)

    monkeypatch.setattr(sharded, "_compiled_dist_grouped", recording)
    rht = lineitem.max_ht + 1
    before = metrics.grouped_buckets()
    sharded_grouped_aggregate(st, _pg_q1_spec(rht, 10471), lineitem.tpus[0])
    assert metrics.grouped_buckets() == dict(before,
                                             hashed=before["hashed"] + 1)
    sharded_grouped_aggregate(st, _q6_spec(rht, **Q6_PARAMS),
                              lineitem.tpus[0])
    assert metrics.grouped_buckets() == dict(before,
                                             hashed=before["hashed"] + 1)
    q1, q6 = sigs
    assert q1.group_cols and not q6.group_cols
    assert q1.NB == q6.NB == group_agg.NUM_BUCKETS and q1.radix == ()
    assert group_agg.addressed(q1, st.arrays) == q1


def test_grouped_mesh_bounded_range_and_empty_tablet_range(lineitem):
    """Row bounds are rebased to each "b" shard; a range that misses a
    shard (or a whole tablet) walks no window there."""
    from yugabyte_db_tpu.parallel import sharded_grouped_aggregate

    st = lineitem.stack(_mesh_of(4))
    run = lineitem.runs[0]
    lo, hi = run.key_at(run.total_rows() // 3), \
        run.key_at(2 * run.total_rows() // 3)
    spec = _pg_q1_spec(lineitem.max_ht + 1, 10471)
    spec.lower, spec.upper = lo, hi
    got = sharded_grouped_aggregate(st, spec, lineitem.tpus[0])
    want = lineitem.cpu.scan(spec)
    assert (got.rows, got.rows_scanned) == (want.rows, want.rows_scanned)


@pytest.mark.parametrize("case", ["lacks_a_value", "other_order"])
def test_grouped_mesh_tablets_whose_dictionaries_differ(case):
    """Group values and ``rep`` rows belong to ONE run: a tablet that
    lacks an ``l_returnflag`` value, and one that met its values in
    another order, finish with their own runs and combine exactly."""
    from yugabyte_db_tpu.parallel import sharded_grouped_aggregate

    if case == "lacks_a_value":
        world = _Lineitem(rows=1500, tablet_of=lambda row: int(
            row["l_returnflag"] == "R"))
        flag = world.schema.column("l_returnflag").col_id
        for run, has_r in zip(world.runs, (False, True)):
            run.encoded_arrays()
            assert (b"R" in run.enc_dicts[flag]) == has_r
            assert (b"N" in run.enc_dicts[flag]) != has_r
    else:
        world = _Lineitem(rows=1500, tablet_of=lambda row: int(
            row["l_orderkey"] % 64 < 32))
    st = world.stack(_mesh_of(4))
    for spec in (_pg_q1_spec(world.max_ht + 1, 10471),
                 _q6_spec(world.max_ht + 1, **Q6_PARAMS)):
        got = sharded_grouped_aggregate(st, spec, world.tpus[0])
        want = world.cpu.scan(spec)
        assert (got.rows, got.rows_scanned) == (want.rows,
                                                want.rows_scanned)
    assert _q1_as_reference(sharded_grouped_aggregate(
        st, _pg_q1_spec(world.max_ht + 1, 10471), world.tpus[0])) \
        == world.ref.q1(10471)


def test_grouped_mesh_multi_version_run_and_ttl(lineitem):
    """Rows overwritten, deleted and expiring: the run is not flat, so
    the shard body resolves MVCC by rows; answers at two read points
    equal the oracle's."""
    from yugabyte_db_tpu.parallel import sharded_grouped_aggregate
    from yugabyte_db_tpu.utils.flags import FLAGS

    schema = lineitem.schema
    cid = {c.name: c.col_id for c in schema.columns}
    cpu = make_engine("cpu", schema)
    old = FLAGS.get("tpu_device_flush")
    FLAGS.set("tpu_device_flush", False)
    try:
        tpus = [make_engine("tpu", schema, dict(rows_per_block=64))
                for _ in range(2)]
        rng = random.Random(5)
        ht = 1000
        for i in range(1200):
            kv = {"l_orderkey": i // 4 + 1, "l_linenumber": i % 4 + 1}
            hc = compute_hash_code(schema, kv)
            key = schema.encode_primary_key(kv, hc)
            for v in range(rng.randrange(1, 4)):
                ht += 1
                if v and rng.random() < 0.15:
                    rv = RowVersion(key, ht=ht, tombstone=True)
                else:
                    rv = RowVersion(
                        key, ht=ht, liveness=True,
                        expire_ht=(ht + 900 if rng.random() < 0.2
                                   else MAX_HT),
                        columns={
                            cid["l_quantity"]: rng.randrange(1, 51),
                            cid["l_extendedprice"]: rng.randrange(
                                90_000, 10_000_000),
                            cid["l_discount"]: rng.randrange(0, 11),
                            cid["l_tax"]: rng.randrange(0, 9),
                            cid["l_returnflag"]: rng.choice("ANR"),
                            cid["l_linestatus"]: rng.choice("FO"),
                            cid["l_shipdate"]: rng.randrange(9000, 10600)})
                tpus[hc * 2 >> 16].apply([rv])
                cpu.apply([rv])
        for e in tpus:
            e.flush()
    finally:
        FLAGS.set("tpu_device_flush", old)
    runs = [e.runs[0].crun for e in tpus]
    assert any(r.max_group_versions > 1 for r in runs)
    st = ShardedTablets(schema, runs, _mesh_of(4), window_blocks=2)
    for rht in (ht + 1, ht - 700, ht + 5000):
        for spec in (_pg_q1_spec(rht, 10471),
                     _q6_spec(rht, 9131, 9900, 2, 8, 40)):
            got = sharded_grouped_aggregate(st, spec, tpus[0])
            assert got.rows == cpu.scan(spec).rows, rht
            # (``scanned`` counts what the device resolve sees: the
            # single-chip programs' own count, tablet by tablet)
            assert got.rows_scanned == sum(
                e.scan(spec).rows_scanned for e in tpus), rht


@pytest.fixture
def one_bucket(monkeypatch):
    """Every key hashes to bucket 0 in programs traced from here on."""
    import jax.numpy as jnp

    from yugabyte_db_tpu.ops import group_agg
    from yugabyte_db_tpu.parallel import sharded

    sharded._compiled_dist_grouped.cache_clear()
    monkeypatch.setattr(group_agg, "_bucket_hash",
                        lambda planes: jnp.zeros_like(planes[0]))
    yield
    sharded._compiled_dist_grouped.cache_clear()


def test_grouped_mesh_forced_collision_is_ineligible(lineitem, one_bucket):
    """Two groups in one bucket: the program counts them, the host
    throws its answer away (counted) and the caller's per-tablet path
    gives the same answer the oracle does."""
    from yugabyte_db_tpu.parallel import (GroupedIneligible,
                                          sharded_grouped_aggregate)
    from yugabyte_db_tpu.utils import metrics
    from yugabyte_db_tpu.yql.pgsql.operations import combine_grouped

    st = lineitem.stack(_mesh_of(4))
    spec = _pg_q1_spec(lineitem.max_ht + 1, 10471)
    before = metrics.grouped_agg_fallbacks()["collision"]
    with pytest.raises(GroupedIneligible):
        sharded_grouped_aggregate(st, spec, lineitem.tpus[0])
    assert metrics.grouped_agg_fallbacks()["collision"] == before + 1
    per_tablet = combine_grouped(spec, [e.scan(spec)
                                        for e in lineitem.tpus])
    assert per_tablet.rows == lineitem.cpu.scan(spec).rows


def test_grouped_mesh_keys_that_differ_across_b_shards_collide(one_bucket):
    """A bucket whose rows have one key in each "b" shard but ANOTHER key
    in the next shard: no shard sees a collision alone; the combine over
    "b" takes the key of the shard that holds ``rep`` and counts the
    other shard's rows."""
    from yugabyte_db_tpu.parallel import (GroupedIneligible,
                                          sharded_grouped_aggregate)
    from yugabyte_db_tpu.yql.pgsql import tpch

    schema = tpch.lineitem_schema()
    cid = {c.name: c.col_id for c in schema.columns}
    rows = []
    for i in range(512):
        kv = {"l_orderkey": i + 1, "l_linenumber": 1}
        rows.append(schema.encode_primary_key(
            kv, compute_hash_code(schema, kv)))
    rows.sort()
    mem, cpu = MemTable(), make_engine("cpu", schema)
    for pos, key in enumerate(rows):
        rv = RowVersion(key, ht=100 + pos, liveness=True, columns={
            cid["l_quantity"]: 1 + pos % 7, cid["l_extendedprice"]: 1000,
            cid["l_discount"]: 1, cid["l_tax"]: 1,
            # first half of the key space one group, second half another
            cid["l_returnflag"]: "A" if pos < 256 else "N",
            cid["l_linestatus"]: "F", cid["l_shipdate"]: 9000})
        mem.apply([rv])
        cpu.apply([rv])
    run = ColumnarRun.build(schema, mem.drain_sorted(), 64)
    mesh = _mesh_of(2)                      # (1, 2): 4 blocks a "b" shard
    st = ShardedTablets(schema, [run], mesh, window_blocks=2)
    assert st.Bl * st.R == 256
    tpu = make_engine("tpu", schema, dict(rows_per_block=64))
    spec = _pg_q1_spec(10_000, 10471)
    with pytest.raises(GroupedIneligible):
        sharded_grouped_aggregate(st, spec, tpu)


def test_grouped_mesh_groups_straddle_b_shards(lineitem):
    """Every group of Q1 has rows in both "b" shards of its tablet: the
    tablet's table is the shards' tables added (psum, digit carries),
    ``rep`` the least row of either."""
    from yugabyte_db_tpu.parallel import sharded_grouped_aggregate

    mesh = _mesh_of(2)
    st = lineitem.stack(mesh)
    assert mesh.shape["b"] == 2 and st.Bl * 2 == st.B
    spec = _pg_q1_spec(lineitem.max_ht + 1, 10600)
    got = sharded_grouped_aggregate(st, spec, lineitem.tpus[0])
    assert got.rows == lineitem.cpu.scan(spec).rows
    assert got.rows_scanned == sum(r.num_versions for r in lineitem.runs)


def test_grouped_mesh_refuses_what_group_agg_does_not_lower(lineitem):
    from yugabyte_db_tpu.parallel import (GroupedIneligible,
                                          sharded_grouped_aggregate)

    st = lineitem.stack(_mesh_of(4))
    rht = lineitem.max_ht + 1
    for spec in (
            ScanSpec(read_ht=rht, group_by=["l_returnflag"],
                     aggregates=[AggSpec("min", "l_quantity")]),
            ScanSpec(read_ht=rht, group_by=["l_returnflag"],
                     predicates=[Predicate("l_linestatus", "=", "F")],
                     aggregates=[AggSpec("count", None)])):
        with pytest.raises(GroupedIneligible):
            sharded_grouped_aggregate(st, spec, lineitem.tpus[0])
        # the per-tablet path answers it
        from yugabyte_db_tpu.yql.pgsql.operations import combine_grouped

        assert combine_grouped(spec, [e.scan(spec) for e in lineitem.tpus]
                               ).rows == lineitem.cpu.scan(spec).rows
