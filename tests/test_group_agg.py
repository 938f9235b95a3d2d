"""Device GROUP BY / expression aggregates vs the CPU oracle.

Pins ops.group_agg (bucket hashing, exact digit-vector product sums,
collision/negative fallbacks) to Aggregator semantics — the TPC-H Q1/Q6
machinery.
"""

import functools
import math
import random

import numpy as np
import pytest

from yugabyte_db_tpu.models.datatypes import DataType
from yugabyte_db_tpu.models.partition import compute_hash_code
from yugabyte_db_tpu.models.schema import ColumnKind, ColumnSchema, Schema
from yugabyte_db_tpu.storage import (AggSpec, Predicate, ScanSpec,
                                     make_engine)
from yugabyte_db_tpu.storage.expr import BinOp, Col, Const
from yugabyte_db_tpu.storage.row_version import RowVersion


def _load(num=3000, seed=7, with_nulls=True, negatives=False,
          versions=1, host_flush=False, null_groups=False):
    """``host_flush``: the run is built on the host and uploaded encoded
    ("bits" presence leaves, as the benchmark's tables are); a device
    flush leaves plain planes but for the string columns' dictionaries.
    ``null_groups``: a tenth of the versions write NULL into ``status``
    and a tenth leave ``flag`` unset."""
    schema = Schema([
        ColumnSchema("k", DataType.STRING, ColumnKind.HASH),
        ColumnSchema("flag", DataType.STRING),       # 1-char, Q1-like
        ColumnSchema("status", DataType.STRING),
        ColumnSchema("qty", DataType.INT64),
        ColumnSchema("price", DataType.INT64),       # cents
        ColumnSchema("disc", DataType.INT8),         # percent 0..10
        ColumnSchema("tax", DataType.INT8),          # percent 0..8
        ColumnSchema("d", DataType.INT32),
    ], table_id="li")
    rng = random.Random(seed)
    cid = {c.name: c.col_id for c in schema.columns}
    cpu = make_engine("cpu", schema, {"rows_per_block": 256})
    tpu = make_engine("tpu", schema, {"rows_per_block": 256})
    ht = 10
    for i in range(num):
        key = schema.encode_primary_key(
            {"k": f"r{i:06d}"}, compute_hash_code(schema, {"k": f"r{i:06d}"}))
        for _v in range(versions):
            ht += 1
            price = rng.randrange(100, 10_000_00)
            if negatives and rng.random() < 0.01:
                price = -price
            cols = {
                cid["flag"]: rng.choice(["A", "N", "R"]),
                cid["status"]: rng.choice(["F", "O"]),
                cid["qty"]: rng.randrange(1, 51),
                cid["price"]: price,
                cid["disc"]: rng.randrange(0, 11),
                cid["tax"]: rng.randrange(0, 9),
                cid["d"]: rng.randrange(0, 1000),
            }
            if with_nulls and rng.random() < 0.05:
                cols[cid["qty"]] = None
            if null_groups and rng.random() < 0.1:
                cols[cid["status"]] = None
            if null_groups and rng.random() < 0.1:
                del cols[cid["flag"]]
            rv = RowVersion(key, ht=ht, liveness=True, columns=cols)
            cpu.apply([rv])
            tpu.apply([rv])
    cpu.flush()
    from yugabyte_db_tpu.utils.flags import FLAGS

    device_flush = FLAGS.get("tpu_device_flush")
    FLAGS.set("tpu_device_flush", device_flush and not host_flush)
    try:
        tpu.flush()
    finally:
        FLAGS.set("tpu_device_flush", device_flush)
    return cpu, tpu, ht


Q1_AGGS = [
    AggSpec("count", None, label="n"),
    AggSpec("sum", "qty", label="sum_qty"),
    AggSpec("sum", "price", label="sum_price"),
    AggSpec("sum", None, label="sum_disc_price",
            expr=BinOp("*", Col("price"),
                       BinOp("-", Const(100), Col("disc")))),
    AggSpec("sum", None, label="sum_charge",
            expr=BinOp("*", BinOp("*", Col("price"),
                                  BinOp("-", Const(100), Col("disc"))),
                       BinOp("+", Const(100), Col("tax")))),
]


def test_grouped_q1_shape_matches_oracle():
    cpu, tpu, ht = _load()
    spec = ScanSpec(read_ht=ht + 1, aggregates=list(Q1_AGGS),
                    group_by=["flag", "status"],
                    predicates=[Predicate("d", "<", 900)])
    a = cpu.scan(spec)
    b = tpu.scan(spec)
    assert a.columns == b.columns
    assert a.rows == b.rows
    assert len(b.rows) == 6  # 3 flags x 2 statuses


def test_expression_sum_ungrouped_q6_shape():
    cpu, tpu, ht = _load()
    spec = ScanSpec(read_ht=ht + 1, aggregates=[
        AggSpec("sum", None, label="revenue",
                expr=BinOp("*", Col("price"), Col("disc"))),
    ], predicates=[Predicate("qty", "<", 25), Predicate("d", ">=", 100)])
    a = cpu.scan(spec)
    b = tpu.scan(spec)
    assert a.rows == b.rows


def test_grouped_with_nulls_in_group_column():
    cpu, tpu, ht = _load(num=500)
    # null out some statuses via overwrites
    schema = cpu.schema
    cid = {c.name: c.col_id for c in schema.columns}
    rows = []
    for i in range(0, 500, 7):
        key = schema.encode_primary_key(
            {"k": f"r{i:06d}"}, compute_hash_code(schema, {"k": f"r{i:06d}"}))
        rows.append(RowVersion(key, ht=ht + 1, columns={cid["status"]: None}))
    cpu.apply(rows)
    tpu.apply(rows)
    cpu.flush()
    tpu.flush()
    cpu.compact()
    tpu.compact()
    spec = ScanSpec(read_ht=ht + 2, group_by=["status"],
                    aggregates=[AggSpec("count", None),
                                AggSpec("sum", "qty")])
    a = cpu.scan(spec)
    b = tpu.scan(spec)
    assert a.rows == b.rows


def test_negative_base_falls_back_exactly():
    cpu, tpu, ht = _load(num=800, negatives=True)
    spec = ScanSpec(read_ht=ht + 1, group_by=["flag"], aggregates=[
        AggSpec("sum", "price"),
        AggSpec("sum", None,
                expr=BinOp("*", Col("price"),
                           BinOp("-", Const(100), Col("disc")))),
    ])
    a = cpu.scan(spec)
    b = tpu.scan(spec)
    assert a.rows == b.rows


def test_multiversion_grouped():
    cpu, tpu, ht = _load(num=300, versions=3)
    spec = ScanSpec(read_ht=ht + 1, group_by=["flag", "status"],
                    aggregates=[AggSpec("count", None),
                                AggSpec("sum", "price")])
    a = cpu.scan(spec)
    b = tpu.scan(spec)
    assert a.rows == b.rows
    # historical read (older versions visible)
    spec2 = ScanSpec(read_ht=ht - 300, group_by=["flag"],
                     aggregates=[AggSpec("sum", "qty")])
    assert cpu.scan(spec2).rows == tpu.scan(spec2).rows


# -- a run that is not flat: the lookback form is the segmented form ----------

@pytest.fixture(scope="module")
def three_versions():
    """``test_multiversion_grouped``'s table: three versions a key."""
    return _load(num=300, versions=3)


def _both_resolves(tpu, trun, arrays, spec, hashed=False):
    """``spec``'s packed vector over ``arrays`` from the program the
    engine plans for ``trun`` (the lookback form) and from the same
    signature with ``lookback=0`` (the segmented form): (the planned
    signature, its parameters, the two vectors)."""
    import dataclasses

    from yugabyte_db_tpu.ops import group_agg

    _kind, (sig, params) = tpu._grouped_prep(trun, spec, spec.predicates)
    if hashed:
        sig = dataclasses.replace(sig, NB=group_agg.NUM_BUCKETS, radix=())
    segmented = dataclasses.replace(sig, lookback=0)
    assert not sig.flat and sig.tag() == segmented.tag()
    assert (sig.resolve_form, segmented.resolve_form) == ("lookback",
                                                          "segmented")
    return sig, params, [
        np.asarray(group_agg.compiled_grouped(s)(arrays, params))
        for s in (sig, segmented)]


@pytest.mark.parametrize("shape", ["q1_direct", "q1_hashed", "q6_ungrouped"])
def test_the_lookback_forms_vector_is_the_segmented_forms_bit_for_bit(
        three_versions, shape):
    """A grouped program over a run of three versions a key resolves its
    window by bounded lookback (``_grouped_prep``: the next power of two
    over the run's ``max_group_versions``); ``count``, ``rep``, ``key``,
    ``collisions``, ``scanned``, ``negs`` and every digit sum are the
    segmented resolve's to the bit, at a read point after every version
    and at ones that see the older versions, and the answer is the CPU
    oracle's."""
    cpu, tpu, ht = three_versions
    trun = tpu.runs[0]
    assert trun.crun.max_group_versions == 3
    for read_ht in (ht + 1, ht - 300, 10 + 450):
        spec = ScanSpec(
            read_ht=read_ht, aggregates=list(Q1_AGGS),
            group_by=[] if shape == "q6_ungrouped" else ["flag", "status"],
            predicates=[Predicate("d", "<", 900)])
        sig, _params, (lookback, segmented) = _both_resolves(
            tpu, trun, trun.dev.arrays, spec, hashed=shape == "q1_hashed")
        assert sig.lookback == 4
        assert bool(sig.radix) == (shape == "q1_direct")
        assert (lookback == segmented).all(), (shape, read_ht)
        assert int(lookback.sum()) != 0
        assert cpu.scan(spec).rows == tpu.scan(spec).rows


def test_a_run_past_the_lookback_bound_keeps_the_segmented_form():
    """What the build recorded of the run decides: 33 versions of a key
    are past ``lookback_fold.MAX_LOOKBACK``, the signature keeps
    ``lookback == 0`` and its segment ops, and answers as the oracle."""
    from yugabyte_db_tpu.ops import lookback_fold

    cpu, tpu, ht = _load(num=12, versions=33)
    assert tpu.runs[0].crun.max_group_versions == 33 \
        > lookback_fold.MAX_LOOKBACK
    for read_ht in (ht + 1, ht - 40):
        spec = ScanSpec(read_ht=read_ht, aggregates=list(Q6_AGGS),
                        predicates=[Predicate("d", ">=", 100)])
        _kind, (sig, _params) = tpu._grouped_prep(tpu.runs[0], spec,
                                                  spec.predicates)
        assert (sig.flat, sig.lookback) == (False, 0)
        assert sig.resolve_form == "segmented"
        assert cpu.scan(spec).rows == tpu.scan(spec).rows


def test_int32_group_column_and_count_col():
    cpu, tpu, ht = _load(num=1000)
    spec = ScanSpec(read_ht=ht + 1, group_by=["disc"],
                    aggregates=[AggSpec("count", "qty"),
                                AggSpec("sum", "price")])
    a = cpu.scan(spec)
    b = tpu.scan(spec)
    assert a.rows == b.rows
    assert len(b.rows) == 11


# -- the dense form (PR 25): exactness bounds, collisions, no scatters ---------

def _flat_run(B, R, cols):
    """Synthetic flat planes of B x R rows, every row live, valid and
    visible at any read point; ``cols`` = {col_id: (cmp planes [B, R, P],
    NULL mask [B * R] or None)}."""
    i32 = np.iinfo(np.int32)
    return {
        "valid": np.ones((B, R), bool),
        "group_start": np.ones((B, R), bool),
        "tomb": np.zeros((B, R), bool),
        "live": np.ones((B, R), bool),
        "ht_hi": np.full((B, R), i32.min, np.int32),
        "ht_lo": np.full((B, R), i32.min, np.int32),
        "exp_hi": np.full((B, R), i32.max, np.int32),
        "exp_lo": np.full((B, R), i32.max, np.int32),
        "cols": {cid: {"set": np.ones((B, R), bool),
                       "isnull": (np.zeros((B, R), bool) if null is None
                                  else null.reshape(B, R)), "cmp": cmp}
                 for cid, (cmp, null) in cols.items()},
    }


def _one_window_program(K, R, grouped, NB=512):
    """One full window of synthetic flat planes, every row matching and
    (grouped) in one bucket: group column 1 = 7 everywhere, base column 2
    = 2^63 - 1, narrow column 3 = 127, so both factors 16256 + c3 sit at
    the static bound 2^14 - 1. Returns (outputs, N)."""
    from yugabyte_db_tpu.ops import group_agg, row_gather, scan
    from yugabyte_db_tpu.utils import planes as P

    N = K * R
    i32 = np.iinfo(np.int32)
    hi, lo = P.i64_to_ordered_planes(np.array([2**63 - 1], np.int64))

    def plane(v, p=1):
        return np.broadcast_to(np.asarray(v, np.int32), (K, R, p)).copy()

    run = _flat_run(K, R, {1: (plane(7), None),
                           2: (plane([int(hi[0]), int(lo[0])], 2), None),
                           3: (plane(127), None)})
    factor = ("+", ("k", 16256), ("c", 3))
    sig = group_agg.GroupAggSig(
        B=K, R=R, K=K, NB=NB,
        cols=(scan.ColSig(1, "i32"), scan.ColSig(2, "i64"),
              scan.ColSig(3, "i32")),
        preds=(), apply_preds=True, flat=True,
        group_cols=((1, 1),) if grouped else (),
        aggs=(group_agg.GAgg("sum_prod", 2, planes=2,
                             factors=(factor, factor), need_cols=(2, 3)),
              group_agg.GAgg("sum_prod", 2, planes=2, need_cols=(2,)),
              group_agg.GAgg("count", None)))
    ip, fp = row_gather.pack_params(
        0, 0, 0, N, (i32.max, i32.max, i32.min, i32.min), [], [])
    vec = group_agg.compiled_grouped(sig)(
        run, group_agg.pack_params(sig, ip, fp))
    return group_agg.unpack(sig, np.asarray(vec)), N


@pytest.mark.parametrize("grouped,K", [(True, 8), (True, 64),
                                       (False, 8), (False, 64)])
def test_full_window_in_one_bucket_at_the_static_bounds_is_exact(grouped, K):
    """The worst case of one window's reduction: K * R rows (16,384 and
    131,072) all in one bucket, every digit vector at its maximum,
    against Python's integers."""
    out, N = _one_window_program(K, 2048, grouped)
    live = [int(b) for b in out["count"].nonzero()[0]]
    assert len(live) == 1 and int(out["count"][live[0]]) == N
    b = live[0]
    assert int(out["negs"]) == 0 and int(out["collisions"]) == 0
    assert int(out["scanned"]) == N and int(out["rep"][b]) == 0

    def value(digits):
        return sum(int(d) << (16 * k) for k, d in enumerate(digits))

    assert value(out["a0"][b]) == N * (2**63 - 1) * 16383 * 16383
    assert value(out["a1"][b]) == N * (2**63 - 1)
    assert int(out["n0"][b]) == int(out["n1"][b]) == int(out["a2"][b]) == N
    if grouped:
        assert out["key"][b].tolist() == [7, 0]   # the value, not null


@pytest.mark.parametrize("grouped", [True, False],
                         ids=["grouped", "ungrouped"])
def test_a_window_past_the_exactness_bound_is_refused(grouped):
    """K * R * 127 < 2^30 (7-bit pieces in int8, int32 sums with room
    for the accumulator): asserted on the signature where the program is
    built. 2^24 rows a window are past it, 2^23 are not."""
    import dataclasses

    from yugabyte_db_tpu.ops import group_agg, scan

    sig = group_agg.GroupAggSig(
        B=8192, R=2048, K=8192, NB=512, cols=(scan.ColSig(1, "i32"),),
        preds=(), apply_preds=True, flat=True,
        group_cols=((1, 1),) if grouped else (),
        aggs=(group_agg.GAgg("count", None),))
    with pytest.raises(ValueError, match="rows_per_block=2048"):
        group_agg.compiled_grouped(sig)
    group_agg.compiled_grouped(dataclasses.replace(sig, K=4096))


def test_a_run_of_a_million_rows_is_one_window():
    from yugabyte_db_tpu.ops import group_agg

    assert group_agg.window_blocks(384, 2048) == 384    # the benchmark's
    assert group_agg.window_blocks(64, 256) == 64
    assert group_agg.window_blocks(1024, 2048) == 512   # two windows
    assert group_agg.window_blocks(7 * 64, 4096) == 224
    assert group_agg.window_blocks(3, 1 << 21) == 1


# -- the kernel's own edges (PR 32): synthetic planes, Python's integers --------

def _kernel_case(K, R, NB, group_of, row_lo, row_hi, lanes=None,
                 windows=1):
    """``windows`` flat windows of K * R rows through ``compiled_grouped``
    (or its vmap over ``lanes`` = [(row_lo, row_hi)...]): group column 1
    (int32) = ``group_of(rows)``, group column 4 (int64) = 1000 x column 1,
    base column 2 (int64) = 10^12 + row, narrow column 3 = row % 100, NULL
    every 7th row. Returns (sig, [unpacked outputs a lane], planes)."""
    import jax

    from yugabyte_db_tpu.ops import group_agg, row_gather, scan
    from yugabyte_db_tpu.utils import planes as P

    B = K * windows
    N = B * R
    i32 = np.iinfo(np.int32)
    rows = np.arange(N, dtype=np.int64)
    g1 = np.asarray(group_of(rows), np.int64)
    base = 10**12 + rows
    narrow = rows % 100

    def i64_planes(v):
        hi, lo = P.i64_to_ordered_planes(v.astype(np.int64))
        return np.stack([hi, lo], -1).reshape(B, R, 2).astype(np.int32)

    run = _flat_run(B, R, {
        1: (g1.astype(np.int32).reshape(B, R, 1), None),
        2: (i64_planes(base), None),
        3: (narrow.astype(np.int32).reshape(B, R, 1), rows % 7 == 0),
        4: (i64_planes(1000 * g1), None)})
    sig = group_agg.GroupAggSig(
        B=B, R=R, K=K, NB=NB,
        cols=(scan.ColSig(1, "i32"), scan.ColSig(2, "i64"),
              scan.ColSig(3, "i32"), scan.ColSig(4, "i64")),
        preds=(), apply_preds=True, flat=True,
        group_cols=((1, 1), (4, 2)),
        aggs=(group_agg.GAgg("sum_prod", 2, planes=2,
                             factors=(("+", ("k", 1), ("c", 3)),),
                             need_cols=(2, 3)),
              group_agg.GAgg("sum_prod", 3, planes=1, need_cols=(3,)),
              group_agg.GAgg("count", 3, need_cols=(3,))))

    def packed(lo, hi):
        ip, fp = row_gather.pack_params(
            lo // (K * R), (hi - 1) // (K * R), lo, hi,
            (i32.max, i32.max, i32.min, i32.min), [], [])
        return group_agg.pack_params(sig, ip, fp)

    fn = group_agg.compiled_grouped(sig)
    if lanes is None:
        vecs = [np.asarray(fn(run, packed(row_lo, row_hi)))]
    else:
        vecs = np.asarray(jax.jit(jax.vmap(fn, in_axes=(None, 0)))(
            run, np.stack([packed(lo, hi) for lo, hi in lanes])))
    return sig, [group_agg.unpack(sig, v) for v in vecs], \
        {"g1": g1, "base": base, "narrow": narrow, "null3": rows % 7 == 0}


def _assert_groups_exact(sig, out, planes, row_lo, row_hi):
    """Every live bucket of ``out`` against Python's integers over the
    rows of its key in [row_lo, row_hi)."""
    rows = np.arange(planes["g1"].size)
    inside = (rows >= row_lo) & (rows < row_hi)
    assert int(out["scanned"]) == int(inside.sum())
    assert int(out["negs"]) == 0 and int(out["collisions"]) == 0

    def value(digits):
        return sum(int(d) << (16 * k) for k, d in enumerate(digits))

    live = [int(b) for b in out["count"].nonzero()[0]]
    groups = sorted(set(planes["g1"][inside].tolist()))
    assert len(live) == len(groups)
    seen = set()
    for b in live:
        g1 = int(out["key"][b][0])
        assert out["key"][b][1] == 0 and out["key"][b][4] == 0  # not null
        from yugabyte_db_tpu.utils import planes as P
        assert int(P.ordered_planes_to_i64(
            out["key"][b][2:3], out["key"][b][3:4])[0]) == 1000 * g1
        seen.add(g1)
        mine = inside & (planes["g1"] == g1)
        full = mine & ~planes["null3"]
        assert int(out["count"][b]) == int(mine.sum())
        assert int(out["rep"][b]) == int(rows[mine].min())
        assert int(out["n0"][b]) == int(out["n1"][b]) == int(
            out["a2"][b]) == int(full.sum())
        assert value(out["a0"][b]) == sum(
            int(v) * (1 + int(f)) for v, f in
            zip(planes["base"][full], planes["narrow"][full]))
        assert value(out["a1"][b]) == int(planes["narrow"][full].sum())
    assert seen == set(groups)


KERNEL_EDGES = {
    # K, R, group of a row, row_lo, row_hi
    "ragged_tiles_cut_by_the_bounds":
        (20, 1024, lambda r: r % 3, 100, 20000),
    "a_bucket_first_seen_in_tile_2":
        (20, 1024, lambda r: np.where(r < 17000, r % 2, 2), 0, 20480),
    "no_row_in_the_first_tile":
        (20, 1024, lambda r: r % 4, 9000, 19999),
    "one_tile_smaller_than_a_register_row":
        (3, 64, lambda r: r % 5, 1, 190),
}


@pytest.mark.parametrize("case", list(KERNEL_EDGES))
def test_kernel_edges_are_exact(case):
    """The tile's edges: a window that is no multiple of the tile (the
    kernel pads it with rows that match nothing), bounds that cut inside
    a tile, a bucket whose first row, key and ``rep`` come from a later
    tile than the first, tiles with no matching row at all, and C = 49
    columns, no multiple of an int8 tile's 32."""
    from yugabyte_db_tpu.ops import group_agg

    K, R, group_of, lo, hi = KERNEL_EDGES[case]
    sig, (out,), planes = _kernel_case(K, R, 512, group_of, lo, hi)
    _KP5, C, CP, _NBP, _KW = group_agg._kernel_dims(sig)
    assert C == 50 and CP == 128
    T = group_agg._tile_rows(sig, K * R)
    if case != "one_tile_smaller_than_a_register_row":
        assert T == 8192 and (K * R) % T and K * R > 2 * T
    _assert_groups_exact(sig, out, planes, lo, hi)


def test_kernel_carries_counts_and_keys_from_window_to_window():
    """Two windows of three tiles each: the second window's kernel is
    handed the buckets' counts and keys of the first (a bucket that goes
    on keeps its key and its ``rep``, one first seen in window 2 gets
    both there), the sums add up in the accumulators outside."""
    lo, hi = 5, 2 * 20480 - 7
    sig, (out,), planes = _kernel_case(
        20, 1024, 512, lambda r: np.where(r < 30000, r % 2, 2 + r % 2),
        lo, hi, windows=2)
    assert sig.B == 2 * sig.K
    _assert_groups_exact(sig, out, planes, lo, hi)


def test_kernel_lanes_of_a_vmap_are_each_exact():
    """``batched_grouped``'s shape: the kernel under ``vmap`` (a grid
    axis a lane), each lane with bounds of its own."""
    lanes = [(0, 20480), (8193, 16383), (17000, 17001), (0, 1)]
    sig, outs, planes = _kernel_case(20, 1024, 512, lambda r: r % 3, 0, 0,
                                     lanes=lanes)
    for out, (lo, hi) in zip(outs, lanes):
        _assert_groups_exact(sig, out, planes, lo, hi)


def test_kernel_counts_a_collision_between_rows_of_different_tiles():
    """Two buckets, and two keys of one bucket whose rows lie in
    different tiles: the bucket keeps the key of the tile that came
    first, every matching row of the other key is counted."""
    import jax.numpy as jnp

    from yugabyte_db_tpu.ops import group_agg
    from yugabyte_db_tpu.utils import planes as P

    def bucket(g):
        hi, lo = P.i64_to_ordered_planes(np.array([1000 * g], np.int64))
        key = [jnp.array([g], jnp.int32), jnp.array([0], jnp.int32),
               jnp.asarray(hi, jnp.int32), jnp.asarray(lo, jnp.int32),
               jnp.array([0], jnp.int32)]
        return int(group_agg._bucket_hash(key)[0]) % 2

    a, b = next((a, b) for a in range(8) for b in range(a + 1, 9)
                if bucket(a) == bucket(b))
    sig, (out,), _planes = _kernel_case(
        20, 1024, 2, lambda r: np.where(r < 8192, a, b), 10, 20000)
    assert group_agg._tile_rows(sig, 20480) == 8192
    assert int(out["collisions"]) == 20000 - 8192
    assert int(out["count"][bucket(a)]) == 20000 - 10
    assert int(out["key"][bucket(a)][0]) == a
    assert int(out["rep"][bucket(a)]) == 10


def _fallbacks():
    from yugabyte_db_tpu.utils import metrics

    return metrics.grouped_agg_fallbacks()


def test_forced_collision_is_reported_counted_and_answered_exactly(
        monkeypatch):
    """Three groups in two buckets: the program counts the rows whose
    key is not their bucket's, the host row scan answers, and
    yb_grouped_agg_fallbacks{reason="collision"} grows by one."""
    from yugabyte_db_tpu.ops import group_agg

    monkeypatch.setattr(group_agg, "NUM_BUCKETS", 2)
    cpu, tpu, ht = _load(num=600)
    spec = ScanSpec(read_ht=ht + 1, group_by=["flag"],
                    aggregates=[AggSpec("count", None),
                                AggSpec("sum", "price")])
    _kind, (sig, params) = tpu._grouped_prep(tpu.runs[0], spec, [])
    assert sig.NB == 2
    out = group_agg.unpack(sig, np.asarray(group_agg.compiled_grouped(sig)(
        tpu.runs[0].dev.arrays, params)))
    assert int(out["collisions"]) > 0
    before = _fallbacks()
    a = cpu.scan(spec)
    b = tpu.scan(spec)
    assert a.rows == b.rows and len(b.rows) == 3
    after = _fallbacks()
    assert after["collision"] == before["collision"] + 1
    assert after["negs"] == before["negs"]
    assert after["decode"] == before["decode"]


def test_negative_base_fallback_is_counted_and_clean_runs_are_not():
    cpu, tpu, ht = _load(num=400, negatives=True)
    spec = ScanSpec(read_ht=ht + 1, group_by=["flag"],
                    aggregates=[AggSpec("sum", "price")])
    before = _fallbacks()
    assert cpu.scan(spec).rows == tpu.scan(spec).rows
    mid = _fallbacks()
    assert mid["negs"] == before["negs"] + 1
    spec = ScanSpec(read_ht=ht + 1, group_by=["flag"],
                    aggregates=[AggSpec("sum", "qty")])
    assert cpu.scan(spec).rows == tpu.scan(spec).rows
    assert _fallbacks() == mid
    from yugabyte_db_tpu.utils.metrics import process_registry

    text = process_registry().prometheus_text()
    for reason in ("negs", "collision", "decode"):
        assert f'yb_grouped_agg_fallbacks{{reason="{reason}"}}' in text


def _equations(jaxpr, inside_kernel=False):
    """(primitive name, equation, inside a pallas kernel?) of a jaxpr and
    of every jaxpr its equations hold."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name, eqn, inside_kernel
        for v in eqn.params.values():
            for sub in (v if isinstance(v, (tuple, list)) else (v,)):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    yield from _equations(
                        sub, inside_kernel
                        or eqn.primitive.name == "pallas_call")


def _mini_run_programs_hold_no_serialized_op():
    """The delta overlay's mini-run (the dirty keys' version lists after
    inserts, overwrites and row tombstones: plain planes, not flat)
    under the grouped (hashed: plain string planes) and the ungrouped
    program: the lookback resolve leaves no ``scatter`` (a segment op),
    ``gather``, ``cumsum`` or ``sort`` in the traced program but the
    hashed kernel's own look-up of a row's bucket key, a lane gather
    inside the kernel."""
    import jax

    from yugabyte_db_tpu.ops import group_agg

    cpu, tpu, ht = _load(num=300)
    schema = cpu.schema
    cid = {c.name: c.col_id for c in schema.columns}

    def key(i):
        return schema.encode_primary_key(
            {"k": f"r{i:06d}"}, compute_hash_code(schema, {"k": f"r{i:06d}"}))

    writes = [RowVersion(key(i), ht=ht + 1, tombstone=True)
              for i in range(0, 60, 3)]
    writes += [RowVersion(key(i), ht=ht + 2, columns={cid["qty"]: 7})
               for i in range(100, 130, 2)]
    writes += [RowVersion(key(i), ht=ht + 3, liveness=True, columns={
        cid["flag"]: "A", cid["status"]: "F", cid["qty"]: 3,
        cid["price"]: 500, cid["disc"]: 1, cid["tax"]: 1, cid["d"]: 5})
        for i in range(1000, 1010)]
    for e in (cpu, tpu):
        e.apply(list(writes))
    for group_by in ([], ["flag", "status"]):
        spec = ScanSpec(read_ht=ht + 4, group_by=group_by,
                        aggregates=list(Q1_AGGS),
                        predicates=[Predicate("d", "<", 900)])
        assert cpu.scan(spec).rows == tpu.scan(spec).rows
        delta = tpu._overlay_cache[3].delta
        assert delta.crun.max_group_versions == 2
        dev = delta.pin("high")
        try:
            sig, params, (lookback, segmented) = _both_resolves(
                tpu, delta, dev.arrays, spec)
            assert (lookback == segmented).all()
            eqns = list(_equations(jax.make_jaxpr(functools.partial(
                group_agg._packed, sig))(dev.arrays, params).jaxpr))
        finally:
            delta.unpin()
        assert (sig.lookback, sig.radix) == (2, ())
        names = [name for name, _e, _k in eqns]
        assert names.count("pallas_call") == (1 if group_by else 0)
        serialized = [(name, in_kernel) for name, _e, in_kernel in eqns
                      if any(op in name for op in
                             ("scatter", "gather", "cumsum", "sort"))]
        # (one look-up a key plane a segment of 128 buckets)
        assert set(serialized) <= {("gather", True)}, serialized
        assert bool(serialized) == bool(group_by)


@pytest.mark.parametrize("group_by", [[], ["flag", "status"], None],
                         ids=["ungrouped", "grouped", "mini_run"])
def test_no_scatter_in_the_lowered_program(group_by):
    """The guard that keeps a serialized TPU scatter from coming back:
    neither the ungrouped (Q6) nor the grouped flat (Q1) signature traces
    to one, inside the window loop or outside it. The grouped one is ONE
    kernel a window that holds the int8 product; what XLA is left with
    stacks the kernel's dozen row vectors and never the C columns of
    pieces (PR 25's ``[C, N]`` operand, 1.08 ms a call on the v5e).
    ``mini_run``: nor do the programs over a run that is not flat, the
    overlay's (60 segment ops and gathers of 72 us each before PR 44)."""
    import jax

    from yugabyte_db_tpu.ops import group_agg

    from yugabyte_db_tpu.ops import encodings

    if group_by is None:
        return _mini_run_programs_hold_no_serialized_op()
    _cpu, tpu, ht = _load(num=300, host_flush=True)
    spec = ScanSpec(read_ht=ht + 1, group_by=group_by,
                    aggregates=list(Q1_AGGS),
                    predicates=[Predicate("d", "<", 900)])
    _kind, (sig, params) = tpu._grouped_prep(tpu.runs[0], spec,
                                             spec.predicates)
    assert sig.flat and bool(sig.group_cols) == bool(group_by)
    arrays = tpu.runs[0].dev.arrays
    assert encodings.leaf_kind(arrays["valid"]) == "bits"
    eqns = list(_equations(jax.make_jaxpr(functools.partial(
        group_agg._packed, sig))(arrays, params).jaxpr))
    names = [name for name, _e, _k in eqns]
    assert "while" in names
    assert not [n for n in names if "scatter" in n]
    outside = [(name, e) for name, e, in_kernel in eqns if not in_kernel]
    assert [n for n, _e in outside].count("dot_general") == 0
    assert names.count("pallas_call") == (1 if group_by else 0)
    if not group_by:
        assert "dot_general" not in names
        # (the text XLA is given holds none either)
        assert "scatter" not in group_agg.compiled_grouped(sig).lower(
            arrays, params).as_text()
        return
    assert sum(1 for name, _e, in_kernel in eqns
               if in_kernel and name == "dot_general") == 1
    _KP5, C, _CP, _NBP, _KW = group_agg._kernel_dims(sig)
    N = sig.K * sig.R
    # the mask word and the planes: the rowid is made in the kernel
    rows = len(group_agg._kernel_rows(sig)[1]) + 1
    assert rows < 16 < C
    widest = max(math.prod(e.outvars[0].aval.shape)
                 for name, e in outside if name == "concatenate")
    assert widest == rows * N      # the kernel's operand, not [C, N]
    # The presence planes stay packed (PR 34): ONE [K, R // 32, 32]
    # value is laid out by rows, the mask word, where the rows form
    # lays out valid, tomb, live and every column's set and isnull
    # (31 of 0.020 ms each in Q1's program on the v5e).
    assert _relayouts(eqns, sig) == 1
    # (one plain plane, as the overlay's masked run has, and the others
    # are unpacked one by one: group_start, tomb, live, set and isnull)
    by_rows = list(_equations(jax.make_jaxpr(functools.partial(
        group_agg._packed, sig))(
            _plain_presence(arrays, sig, only=("valid",)), params).jaxpr))
    assert [n for n, _e, _k in by_rows].count("pallas_call") == 1
    assert _relayouts(by_rows, sig) == 3 + 2 * len(sig.cols) >= 17


def _relayouts(eqns, sig):
    """Reshapes of a ``[K, R // 32, 32]`` value (a window of packed
    words, a bit a row) to rows, outside the kernel."""
    return sum(1 for name, e, in_kernel in eqns
               if name == "reshape" and not in_kernel
               and e.invars[0].aval.shape == (sig.K, sig.R // 32, 32))


def _plain_presence(arrays, sig, only=None):
    """A run's device arrays with the "bits" leaves of the presence
    planes (or of ``only``) decoded to plain bool planes."""
    from yugabyte_db_tpu.ops import encodings

    def plain(name, leaf):
        if only is not None and name not in only:
            return leaf
        assert encodings.leaf_kind(leaf) == "bits", name
        return np.asarray(encodings.decode_leaf(leaf, sig.B, sig.R))

    out = dict(arrays)
    for name in ("valid", "tomb", "live"):
        out[name] = plain(name, arrays[name])
    out["cols"] = {
        cid: dict(col, set=plain("set", col["set"]),
                  isnull=plain("isnull", col["isnull"]))
        for cid, col in arrays["cols"].items()}
    return out


# -- the presence bits stay packed (PR 34) --------------------------------------

def _presence_run(K, R, seed, extra_cols=0):
    """Synthetic flat planes with everything a flat resolve has to mask:
    rows that are not valid (and two invalid pad blocks at the end), row
    tombstones, rows without a liveness marker, unset columns, NULLs,
    commit times 100..199 and expiries of which some lie at or under
    150. Columns: 1 int32 (the group, 0..4), 2 int64 (the base), 3 int32
    (narrow, the predicate's), 5.. int32 that no aggregate names. Returns
    (plain numpy tree, the planes as flat arrays)."""
    from yugabyte_db_tpu.utils import planes as P

    rng = np.random.default_rng(seed)
    N = K * R

    def coin(p):
        return rng.random(N) < p

    valid = coin(0.97)
    valid[(K - 2) * R:] = False
    ht = rng.integers(100, 200, N)
    exp = np.where(coin(0.1), rng.integers(100, 200, N), 2**62)
    flat = {"valid": valid, "tomb": coin(0.05), "live": coin(0.7),
            "ht": ht, "exp": exp, "set": {}, "isnull": {}, "val": {}}
    cols = {}

    def add(cid, values, planes):
        flat["set"][cid], flat["isnull"][cid] = coin(0.8), coin(0.15)
        flat["val"][cid] = values
        if planes == 2:
            hi, lo = P.i64_to_ordered_planes(values.astype(np.int64))
            cmp = np.stack([hi, lo], -1)
        else:
            cmp = values[:, None]
        cols[cid] = {"set": flat["set"][cid].reshape(K, R),
                     "isnull": flat["isnull"][cid].reshape(K, R),
                     "cmp": cmp.reshape(K, R, planes).astype(np.int32)}

    add(1, rng.integers(0, 5, N), 1)
    add(2, rng.integers(10**11, 10**12, N), 2)
    add(3, rng.integers(0, 100, N), 1)
    for cid in range(5, 5 + extra_cols):
        add(cid, rng.integers(0, 9, N), 1)

    def i64(v):
        hi, lo = P.i64_to_ordered_planes(np.asarray(v, np.int64))
        return (hi.reshape(K, R).astype(np.int32),
                lo.reshape(K, R).astype(np.int32))

    ht_hi, ht_lo = i64(ht)
    exp_hi, exp_lo = i64(exp)
    tree = {"valid": valid.reshape(K, R),
            "group_start": np.ones((K, R), bool),
            "tomb": flat["tomb"].reshape(K, R),
            "live": flat["live"].reshape(K, R),
            "ht_hi": ht_hi, "ht_lo": ht_lo, "exp_hi": exp_hi,
            "exp_lo": exp_lo, "cols": cols}
    return tree, flat


def _bits_presence(tree, but=()):
    """The tree with its presence planes as "bits" leaves, as a run is
    uploaded (those named in ``but`` stay plain bool planes)."""
    from yugabyte_db_tpu.ops import encodings

    def bits(name, plane):
        return plane if name in but else encodings.encode_bool_plane(plane)

    out = dict(tree)
    for name in ("valid", "tomb", "live"):
        out[name] = bits(name, tree[name])
    out["cols"] = {cid: dict(col, set=bits("set", col["set"]),
                             isnull=bits("isnull", col["isnull"]))
                   for cid, col in tree["cols"].items()}
    return out


def _presence_sig(K, R, extra_cols=0, counts=()):
    from yugabyte_db_tpu.ops import group_agg, scan

    cols = [scan.ColSig(1, "i32"), scan.ColSig(2, "i64"),
            scan.ColSig(3, "i32")] + [scan.ColSig(c, "i32")
                                      for c in range(5, 5 + extra_cols)]
    return group_agg.GroupAggSig(
        B=K, R=R, K=K, NB=512, cols=tuple(cols),
        preds=(scan.PredSig(3, "i32", "<"),), apply_preds=True, flat=True,
        group_cols=((1, 1),),
        aggs=(group_agg.GAgg("sum_prod", 2, planes=2,
                             factors=(("+", ("k", 1), ("c", 3)),),
                             need_cols=(2, 3)),
              group_agg.GAgg("count", 2, need_cols=(2,)),
              group_agg.GAgg("count", None))
        + tuple(group_agg.GAgg("count", c, need_cols=(c,))
                for c in counts))


def _presence_params(sig, lo, hi, read_ht, read_exp, lit):
    from yugabyte_db_tpu.ops import group_agg, row_gather
    from yugabyte_db_tpu.utils import planes as P

    def point(v):
        hi_, lo_ = P.i64_to_ordered_planes(np.array([v], np.int64))
        return int(hi_[0]), int(lo_[0])

    ip, fp = row_gather.pack_params(
        0, 0, lo, hi, point(read_ht) + point(read_exp), [lit], [])
    return group_agg.pack_params(sig, ip, fp)


def _presence_oracle(flat, lo, hi, read_ht, read_exp, lit):
    """(scanned, matching rows, {column: its not-null mask over the
    matching rows}) by numpy, from the flat resolve's own definition: a
    row exists by its liveness marker or by a column that is set and not
    NULL, if it is valid, visible, no tombstone and not expired."""
    rows = np.arange(flat["valid"].size)
    ok = flat["valid"] & (flat["ht"] <= read_ht) & ~flat["tomb"] \
        & ~(flat["exp"] <= read_exp)
    notnull = {c: ok & flat["set"][c] & ~flat["isnull"][c]
               for c in flat["set"]}
    exists = ok & flat["live"]
    for c in notnull:
        exists = exists | notnull[c]
    pre = exists & (rows >= lo) & (rows < hi)
    m = pre & notnull[3] & (flat["val"][3] < lit)
    return int(pre.sum()), int(m.sum()), {c: m & nn
                                          for c, nn in notnull.items()}


PRESENCE_CASES = {
    # row_lo, row_hi, read point, expiry read point, the predicate's literal
    "whole_run_every_version_visible": (0, 20480, 10**6, 0, 100),
    "bounds_cut_inside_a_word_and_inside_a_tile":
        (37, 8192 + 45, 10**6, 0, 100),
    "a_read_point_that_hides_versions": (0, 20480, 150, 0, 100),
    "ttl_expired_versions": (0, 20480, 10**6, 150, 100),
    "all_of_it_and_a_predicate": (1000 + 31, 20480 - 33, 170, 140, 60),
    "one_row": (8191, 8192, 10**6, 0, 100),
}


@pytest.mark.parametrize("case", list(PRESENCE_CASES))
def test_packed_presence_is_the_rows_form_bit_for_bit(case):
    """The kernel's mask words made on the packed words of "bits" leaves
    (``_packed_window``) against the same words made of planes laid out
    by rows (``_rows_window``), and the two programs' outputs, bit for
    bit: unset columns, NULLs, row tombstones, rows without liveness,
    TTL-expired versions, rows that are not valid and invalid pad
    blocks, bounds that cut inside a 32-row word and inside a tile, a
    read point that hides versions."""
    from yugabyte_db_tpu.ops import group_agg
    from yugabyte_db_tpu.ops.row_gather import _unpack_literals

    K, R = 20, 1024
    lo, hi, read_ht, read_exp, lit = PRESENCE_CASES[case]
    tree, flat = _presence_run(K, R, seed=34, extra_cols=2)
    sig = _presence_sig(K, R, extra_cols=2)
    packed = _bits_presence(tree)
    params = _presence_params(sig, lo, hi, read_ht, read_exp, lit)
    n = group_agg.int_params(sig)
    window = (0, lo, hi, tuple(params[4:8]),
              _unpack_literals(sig, params[:n], params[n:].view(np.float32)))
    words, _plane = group_agg._packed_window(sig, packed, *window)
    r, gvalid, m, _plane = group_agg._rows_window(sig, tree, *window)
    want = group_agg._rows_words(sig, r, gvalid, m)
    assert len(words) == len(want) == 1
    assert np.asarray(words[0]).tobytes() == np.asarray(want[0]).tobytes()
    # (the masks are not trivial: each bit is set somewhere, clear elsewhere)
    notnull_cols, scanned_bit, _w = group_agg._mask_bits(sig)
    scanned, matching, _notnull = _presence_oracle(flat, lo, hi, read_ht,
                                                   read_exp, lit)
    word = np.asarray(words[0])
    assert int((word & 1).sum()) == matching
    assert int(((word >> scanned_bit) & 1).sum()) == scanned
    assert notnull_cols == (1, 2, 3) and scanned_bit == 4

    fn = group_agg.compiled_grouped(sig)
    got = group_agg.unpack(sig, np.asarray(fn(packed, params)))
    _assert_same_bits(got, group_agg.unpack(sig, np.asarray(fn(tree,
                                                               params))))
    assert int(got["scanned"]) == scanned
    assert int(got["count"].sum()) == matching == int(got["a2"].sum())
    assert int(got["collisions"]) == 0 and int(got["negs"]) == 0
    if case != "one_row":
        assert 0 < matching < scanned < hi - lo


def test_more_than_32_masks_take_a_second_word_packed_and_by_rows():
    """33 not-null masks, the match and ``scanned``: two mask words a
    row, the second one's bits cleared by the per-row parts as the
    first's."""
    from yugabyte_db_tpu.ops import group_agg

    K, R = 4, 256
    tree, flat = _presence_run(K, R, seed=5, extra_cols=31)
    sig = _presence_sig(K, R, extra_cols=31, counts=tuple(range(5, 36)))
    notnull_cols, scanned_bit, words = group_agg._mask_bits(sig)
    assert len(notnull_cols) == 34 and scanned_bit == 35 and words == 2
    lo, hi = 70, K * R - 300
    params = _presence_params(sig, lo, hi, 160, 130, 80)
    fn = group_agg.compiled_grouped(sig)
    got = group_agg.unpack(sig, np.asarray(fn(_bits_presence(tree),
                                              params)))
    _assert_same_bits(got, group_agg.unpack(sig, np.asarray(fn(tree,
                                                               params))))
    scanned, matching, notnull = _presence_oracle(flat, lo, hi, 160, 130, 80)
    assert int(got["scanned"]) == scanned > matching > 0
    assert int(got["count"].sum()) == matching
    # (count(c35): the last not-null mask of the second word)
    assert int(got["a33"].sum()) == int(notnull[35].sum()) > 0


def _presence_counts():
    from yugabyte_db_tpu.utils import metrics

    return metrics.grouped_presence()


@pytest.mark.parametrize("plain", ["every_presence_plane", "valid_alone"])
def test_a_plain_bool_leaf_takes_the_rows_form_and_agrees(plain):
    """What the program sees decides: one plain bool plane among the
    presence planes (a run uploaded unencoded; the delta overlay's
    masked ``valid``, storage/tpu_engine.py ``_MaskedRun``) and the
    window is resolved by rows, to the same outputs;
    ``yb_grouped_presence{form}`` says which form a program got."""
    from yugabyte_db_tpu.ops import group_agg

    # (a shape of its own a case: the counter counts programs traced)
    K, R = 6 + (plain == "valid_alone"), 512
    tree, _flat = _presence_run(K, R, seed=9)
    sig = _presence_sig(K, R)
    params = _presence_params(sig, 5, K * R - 5, 180, 120, 70)
    fn = group_agg.compiled_grouped(sig)
    before = _presence_counts()
    want = group_agg.unpack(sig, np.asarray(fn(_bits_presence(tree),
                                               params)))
    mid = _presence_counts()
    assert mid == {"packed": before["packed"] + 1, "rows": before["rows"]}
    run = tree if plain == "every_presence_plane" else _bits_presence(
        tree, but=("valid",))
    _assert_same_bits(group_agg.unpack(sig, np.asarray(fn(run, params))),
                      want)
    assert _presence_counts() == {"packed": mid["packed"],
                                  "rows": mid["rows"] + 1}
    from yugabyte_db_tpu.utils.metrics import process_registry

    text = process_registry().prometheus_text()
    for form in ("packed", "rows"):
        assert f'yb_grouped_presence{{form="{form}"}}' in text


def test_the_overlays_masked_run_keeps_the_packed_form():
    """The delta overlay's masked primary (``_MaskedRun``): its ``valid``
    stays a "bits" leaf, the dirty rows' bits cleared in the packed
    words, so a grouped program over it is the run's own (same pytree,
    no new trace, the packed form). It answers as the rows form does
    over a plain bool ``valid`` with the same rows cleared (a device
    flush's run: the form the overlay's mask took before PR 43)."""
    import jax.numpy as jnp

    from yugabyte_db_tpu.ops import encodings, group_agg

    _cpu, tpu, ht = _load(num=700, host_flush=True)
    spec = ScanSpec(read_ht=ht + 1, group_by=["flag", "status"],
                    aggregates=list(Q1_AGGS),
                    predicates=[Predicate("d", "<", 900)])
    _kind, (sig, params) = tpu._grouped_prep(tpu.runs[0], spec,
                                             spec.predicates)
    arrays = tpu.runs[0].dev.arrays
    idx = np.arange(3, 700, 11, dtype=np.int32)
    masked = tpu._masked_primary(tpu.runs[0], idx).dev.arrays
    assert encodings.leaf_kind(masked["valid"]) == "bits"
    assert encodings.leaf_kind(masked["tomb"]) == "bits"
    fn = group_agg.compiled_grouped(sig)
    before = _presence_counts()
    whole = group_agg.unpack(sig, np.asarray(fn(arrays, params)))
    got = group_agg.unpack(sig, np.asarray(fn(masked, params)))
    # one trace serves both: the masked run has the run's own signature
    assert _presence_counts() == {"packed": before["packed"] + 1,
                                  "rows": before["rows"]}
    valid = np.array(encodings.decode_leaf(arrays["valid"], sig.B, sig.R))
    assert valid.reshape(-1)[idx].all()
    valid.reshape(-1)[idx] = False
    assert (np.asarray(encodings.decode_leaf(
        masked["valid"], sig.B, sig.R)) == valid).all()
    cleared = dict(arrays, valid=jnp.asarray(valid))
    _assert_same_bits(got, group_agg.unpack(sig, np.asarray(fn(cleared,
                                                               params))))
    assert _presence_counts() == {"packed": before["packed"] + 1,
                                  "rows": before["rows"] + 1}
    assert int(got["scanned"]) == int(whole["scanned"]) - idx.size


def test_the_ungrouped_lowering_does_not_reach_the_packed_form(monkeypatch):
    """Q6's program is its parent's: a signature with no group column
    resolves its window by rows whatever the leaves are (XLA fuses the
    unpacks into its reductions; a reordering there cost Q6 0.172 ->
    0.251 ms on the v5e, PR 32), and counts no presence form."""
    import jax

    from yugabyte_db_tpu.ops import encodings, group_agg

    def unreachable(*_a, **_k):
        raise AssertionError("the packed form, from an ungrouped program")

    _cpu, tpu, ht = _load(num=300, host_flush=True)
    arrays = tpu.runs[0].dev.arrays
    preds = [Predicate("qty", "<", 25), Predicate("d", ">=", 100)]
    lowered = {}
    for group_by in ([], ["flag"]):
        spec = ScanSpec(read_ht=ht + 1, group_by=group_by,
                        aggregates=list(Q6_AGGS), predicates=preds)
        _kind, (sig, params) = tpu._grouped_prep(tpu.runs[0], spec, preds)
        assert sig.flat and encodings.leaf_kind(arrays["valid"]) == "bits"
        lowered[bool(group_by)] = (sig, params)
    monkeypatch.setattr(group_agg, "_packed_window", unreachable)
    monkeypatch.setattr(group_agg, "resolve_flat_packed", unreachable)
    monkeypatch.setattr(encodings, "rows_of_words", unreachable)
    before = _presence_counts()
    sig, params = lowered[False]
    text = jax.jit(functools.partial(group_agg._packed, sig)).lower(
        arrays, params).as_text()
    assert "scatter" not in text and _presence_counts() == before
    # (the same walk does reach it from a grouped signature)
    sig, params = lowered[True]
    with pytest.raises(AssertionError, match="the packed form"):
        jax.make_jaxpr(functools.partial(group_agg._packed, sig))(arrays,
                                                                  params)


# LINEITEM's value columns as the benchmark's DDL numbers them.
BENCHMARK_KINDS = {12: "i32", 13: "i32", 14: "i32", 15: "i64", 16: "i32",
                   17: "i32", 18: "str", 19: "str", 20: "i32", 21: "i32",
                   22: "i32", 23: "str", 24: "str", 25: "str", 26: "str",
                   27: "str"}


def benchmark_signatures(K, R, flat=True, lookback=0):
    """(Q1's, Q6's) signatures as the PG executor pushes the benchmark's
    two statements down (Q1's ``avg``s as a sum and a count each), over
    a run of ``K`` blocks of ``R`` rows in one window, hashed."""
    from yugabyte_db_tpu.ops import group_agg, scan

    G = group_agg.GAgg
    disc, tax = ("-", ("k", 100), ("c", 16)), ("+", ("k", 100), ("c", 17))
    shape = dict(
        B=K, R=R, K=K, NB=group_agg.NUM_BUCKETS,
        cols=tuple(scan.ColSig(c, k) for c, k in BENCHMARK_KINDS.items()),
        apply_preds=True, flat=flat, lookback=lookback)
    q1 = group_agg.GroupAggSig(
        preds=(scan.PredSig(20, "i32", "<="),),
        group_cols=((18, 2), (19, 2)),
        aggs=(G("sum_prod", 14, 1, (), (14,)), G("sum_prod", 15, 2, (), (15,)),
              G("sum_prod", 15, 2, (disc,), (15, 16)),
              G("sum_prod", 15, 2, (disc, tax), (15, 16, 17)),
              G("sum_prod", 14, 1, (), (14,)), G("count", 14, 1, (), (14,)),
              G("sum_prod", 15, 2, (), (15,)), G("count", 15, 1, (), (15,)),
              G("count", None, 1, (), ())), **shape)
    q6 = group_agg.GroupAggSig(
        preds=(scan.PredSig(20, "i32", ">="), scan.PredSig(20, "i32", "<"),
               scan.PredSig(16, "i32", ">="), scan.PredSig(16, "i32", "<="),
               scan.PredSig(14, "i32", "<")),
        group_cols=(),
        aggs=(G("sum_prod", 15, 2, (("c", 16),), (15, 16)),), **shape)
    return q1, q6


@pytest.fixture(scope="module")
def one_described_v5e():
    """A sharding on one chip of a described, not attached, v5e: the
    TPU's compiler compiles for it here (made inside a fixture: no
    import of this file loads the TPU's library)."""
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no compiler here, or its lock
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("form", ["hashed", "direct"])
def test_q1_compiles_for_the_v5e_with_one_relayout_of_its_masks(
        one_described_v5e, monkeypatch, form):
    """TPC-H Q1's signature at the benchmark's shape (384 blocks of 2,048
    rows, 16 columns, "bits" presence leaves, delta16 and plain value
    planes; ``direct``: the two group columns as the "dict" leaves of 4
    slots a served run holds, so 16 buckets under the same name),
    compiled by the TPU's own compiler: Mosaic takes the kernel,
    and in the optimized module no bool plane is laid out by rows, the
    packed masks are laid out ONCE (one copy of an int32 ``[384, 64,
    32]``), and nothing beside the kernel has the mask word's producer
    fused into it a second time (a ``scanned`` reduction of XLA's did: a
    broadcast and a reshape to rows a mask)."""
    import re

    import jax
    import jax.numpy as jnp

    from yugabyte_db_tpu.ops import group_agg, scan

    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    K, R = 384, 2048
    kinds = BENCHMARK_KINDS
    sig, _q6 = benchmark_signatures(K, R)
    assert sig.tag() == "g2a9p1f1_d1ea91"     # the benchmark's Q1

    def S(shape, dtype=jnp.int32):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=one_described_v5e)

    bits = {"bits": {"bw": S((K, R // 32))}}
    run = {"valid": bits, "group_start": bits, "tomb": bits, "live": bits,
           "ht_hi": {"const": {"cval": S((1, 1))}},
           "ht_lo": {"delta16": {"dbase": S((K, 1)),
                                 "doff": S((K, R), jnp.uint16)}},
           "exp_hi": {"const": {"cval": S((1, 1))}},
           "exp_lo": {"const": {"cval": S((1, 1))}},
           "cols": {c: {"set": bits, "isnull": bits,
                        "cmp": {"delta16": {"dbase": S((K, 1, 1)),
                                            "doff": S((K, R, 1), jnp.uint16)}}
                        if k == "i32" else S((K, R, 2))}
                    for c, k in kinds.items()}}
    if form == "direct":
        for c, _planes in sig.group_cols:
            run["cols"][c]["cmp"] = {"dict": {
                "codes": S((K, R), jnp.uint16), "dhi": S((4,)),
                "dlo": S((4,))}}
        sig = group_agg.addressed(sig, run)
        assert (sig.NB, sig.radix) == (16, (4, 4))
        assert sig.tag() == "g2a9p1f1_d1ea91"     # the name does not move
    else:
        assert group_agg.addressed(sig, run) == sig
    before = _presence_counts()
    text = jax.jit(functools.partial(group_agg._packed, sig)).lower(
        run, S((group_agg.int_params(sig),))).compile().as_text()
    assert _presence_counts()["packed"] == before["packed"] + 1
    assert text.count("tpu_custom_call") == 1
    assert "pred[384,64,32]" not in text and "pred[786432]" not in text
    top = [ln for ln in text.splitlines() if re.match(r"  (ROOT )?%\S+ = ", ln)]
    relayouts = [ln for ln in top if re.match(
        r"  %\S+ = s32\[384,64,32\]\S* copy\(", ln)]
    assert len(relayouts) == 1, relayouts
    assert not [ln for ln in top if re.match(
        r"  %\S+ = s32\[786432\]\S* reshape\(%broadcast", ln)]
    # (the dictionaries' prefix planes are looked up nowhere)
    assert " gather(" not in text


# -- the buckets by dictionary code (PR 38) --------------------------------------

def _leaf_of_cap(cap):
    """A group column's ``cmp`` leaf as a dictionary of ``cap`` slots
    (0: a plain plane), by shapes alone: what ``addressed`` looks at."""
    if not cap:
        return np.zeros((2, 32, 1), np.int32)
    return {"dict": {"codes": np.zeros((2, 32), np.uint16),
                     "dhi": np.zeros(cap, np.int32),
                     "dlo": np.zeros(cap, np.int32)}}


ADDRESSING = {
    # the group columns' dictionary caps (0: no dictionary) -> (NB, radix)
    "q1s_two_dictionaries": ((4, 4), (16, (4, 4))),
    "one_dictionary": ((8,), (8, (8,))),
    "a_dictionary_and_an_integer_column": ((4, 0), (512, ())),
    "an_integer_column_alone": ((0,), (512, ())),
    "a_product_of_512_is_direct": ((32, 16), (512, (32, 16))),
    "a_product_of_1024_is_hashed": ((32, 32), (512, ())),
    "three_dictionaries": ((2, 4, 8), (64, (2, 4, 8))),
    "no_group_column": ((), (512, ())),
}


@pytest.mark.parametrize("case", list(ADDRESSING))
def test_the_leaves_of_the_group_columns_decide_the_bucket_form(case):
    """``addressed``: direct iff EVERY group column's ``cmp`` leaf is a
    "dict" leaf and the product of the caps is within NUM_BUCKETS; the
    table then has that product's buckets. Nothing of it is in the name,
    and a hashed signature over the same leaves comes back the same."""
    import dataclasses

    from yugabyte_db_tpu.ops import group_agg, scan

    caps, (NB, radix) = ADDRESSING[case]
    run = {"cols": {10 + i: {"cmp": _leaf_of_cap(cap)}
                    for i, cap in enumerate(caps)}}
    sig = group_agg.GroupAggSig(
        B=2, R=32, K=2, NB=group_agg.NUM_BUCKETS,
        cols=tuple(scan.ColSig(10 + i, "str" if cap else "i32")
                   for i, cap in enumerate(caps)),
        preds=(), apply_preds=True, flat=True,
        group_cols=tuple((10 + i, 2 if cap else 1)
                         for i, cap in enumerate(caps)),
        aggs=(group_agg.GAgg("count", None),))
    got = group_agg.addressed(sig, run)
    assert (got.NB, got.radix) == (NB, radix)
    assert got.tag() == sig.tag()
    assert group_agg.addressed(got, run) == got
    assert dataclasses.replace(got, NB=sig.NB, radix=()) == sig
    if radix:
        # a bucket's codes are its mixed-radix digits, first column first
        assert group_agg.bucket_codes(got, NB - 1) == [c - 1 for c in radix]
        assert group_agg.bucket_codes(got, radix[-1]) == (
            [0] * (len(radix) - 2) + [1, 0] if len(radix) > 1 else [0])
        # leaves of another cap are another program: refused where traced
        other = {"cols": {c: {"cmp": _leaf_of_cap(2 * cap)} for c, cap in
                          zip(run["cols"], radix)}}
        with pytest.raises(ValueError, match="addresses its buckets"):
            group_agg.grouped_aggregate(got, other, None, None)


def _golden_case():
    """Three blocks of 64 flat rows, no random number in them: group
    column 1 a "dict" leaf (A, N, R and the absent slot, NULL every 11th
    row), group column 4 an int32 (row % 2), base column 2 = 10^12 + row,
    narrow column 3 = row % 100 (NULL every 7th row), rows [1, 190)."""
    from yugabyte_db_tpu.ops import encodings, group_agg, row_gather, scan
    from yugabyte_db_tpu.utils import planes as P

    K, R = 3, 64
    rows = np.arange(K * R, dtype=np.int64)
    i32 = np.iinfo(np.int32)
    hi, lo = P.varlen_prefix_planes([b"A", b"N", b"R"])
    dhi, dlo = np.zeros(4, np.int32), np.zeros(4, np.int32)
    dhi[:3], dlo[:3] = hi, lo
    null1 = rows % 11 == 0
    bh, bl = P.i64_to_ordered_planes(10**12 + rows)
    run = _flat_run(K, R, {
        1: (encodings.dict_leaf(np.where(null1, 3, rows % 3).reshape(K, R),
                                dhi, dlo), null1),
        2: (np.stack([bh, bl], -1).reshape(K, R, 2).astype(np.int32), None),
        3: ((rows % 100).astype(np.int32).reshape(K, R, 1), rows % 7 == 0),
        4: ((rows % 2).astype(np.int32).reshape(K, R, 1), None)})
    sig = group_agg.GroupAggSig(
        B=K, R=R, K=K, NB=512,
        cols=(scan.ColSig(1, "str"), scan.ColSig(2, "i64"),
              scan.ColSig(3, "i32"), scan.ColSig(4, "i32")),
        preds=(), apply_preds=True, flat=True, group_cols=((1, 2), (4, 1)),
        aggs=(group_agg.GAgg("sum_prod", 2, planes=2,
                             factors=(("+", ("k", 1), ("c", 3)),),
                             need_cols=(2, 3)),
              group_agg.GAgg("count", 3, need_cols=(3,)),
              group_agg.GAgg("count", None)))
    ip, fp = row_gather.pack_params(
        0, 0, 1, 190, (i32.max, i32.max, i32.min, i32.min), [], [])
    return sig, run, group_agg.pack_params(sig, ip, fp)


def test_the_hashed_forms_vector_is_its_parents_bit_for_bit():
    """The packed result of the hashed form over a run with a "dict" leaf
    and an integer group column, against the sha256 of the vector PR 38's
    parent (5062ecb) gives for the same planes: the direct form was put
    beside the hashed one, not into it."""
    import hashlib

    from yugabyte_db_tpu.ops import group_agg

    sig, run, params = _golden_case()
    assert group_agg.addressed(sig, run) == sig        # hashed it stays
    vec = np.asarray(group_agg.compiled_grouped(sig)(run, params))
    assert vec.size == 9219 and np.count_nonzero(vec) == 595
    assert hashlib.sha256(vec.tobytes()).hexdigest() == (
        "83f11a01052f8f34a1cb8aa982fd28cae6387d42031f4d4fa9203a6d252277c3")
    out = group_agg.unpack(sig, vec)
    assert int(out["collisions"]) == 0 and int(out["scanned"]) == 189
    assert len(out["count"].nonzero()[0]) == 8      # (A, N, R, NULL) x 2


# -- the jit boundary (PR 28): one vector in, one vector out --------------------

Q6_AGGS = [AggSpec("sum", None, label="revenue",
                   expr=BinOp("*", Col("price"), Col("disc")))]
PACKED_SHAPES = {
    # aggregates, predicates of lane i (literals differ lane by lane)
    "q1": (Q1_AGGS, lambda i: [Predicate("d", "<", 900 - 40 * i)]),
    "q6": (Q6_AGGS, lambda i: [Predicate("qty", "<", 25 + i),
                               Predicate("d", ">=", 100 + 30 * i)]),
}


@functools.lru_cache(maxsize=None)
def _packed_case(shape, grouped, flat):
    """(engine run arrays, sig, eight lanes' packed params, each lane's
    dict from the program as it was before the packing: a jit of
    ``grouped_aggregate`` itself, two parameter vectors in, a dict out)."""
    import jax

    from yugabyte_db_tpu.ops import group_agg

    _cpu, tpu, ht = _load(num=300, versions=1 if flat else 3)
    aggs, preds = PACKED_SHAPES[shape]
    sigs, lanes = set(), []
    for i in range(8):
        spec = ScanSpec(read_ht=ht + 1 - i, aggregates=list(aggs),
                        group_by=["flag", "status"] if grouped else [],
                        predicates=preds(i))
        _kind, (sig, params) = tpu._grouped_prep(tpu.runs[0], spec,
                                                 spec.predicates)
        sigs.add(sig)
        lanes.append(params)
    (sig,) = sigs
    assert sig.flat == flat and bool(sig.group_cols) == grouped
    arrays = tpu.runs[0].dev.arrays
    n = group_agg.int_params(sig)
    plain = jax.jit(functools.partial(group_agg.grouped_aggregate, sig))
    want = [jax.device_get(plain(arrays, p[:n], p[n:].view(np.float32)))
            for p in lanes]
    return arrays, sig, lanes, want


def _assert_same_bits(got: dict, want: dict):
    assert set(got) == set(want)
    for name, w in want.items():
        g = got[name]
        assert g.dtype == w.dtype == np.int32 and g.shape == w.shape, name
        assert g.tobytes() == np.asarray(w).tobytes(), name


@pytest.mark.parametrize("lanes", [0, 1, 3, 8],
                         ids=["single", "vmap1", "vmap3", "vmap8"])
@pytest.mark.parametrize("flat", [True, False],
                         ids=["flat", "multi_version"])
@pytest.mark.parametrize("grouped", [True, False],
                         ids=["NA_eq_NB", "NA_ne_NB"])
@pytest.mark.parametrize("shape", ["q1", "q6"])
def test_unpacked_vector_is_the_dict_bit_for_bit(shape, grouped, flat,
                                                 lanes):
    """``unpack(sig, program(arrays, params))`` against the dict the same
    traced function gives unpacked: Q1's and Q6's aggregates, with group
    columns (NA == NB) and without (NA != NB: bucket 0, padded), flat and
    multi-version runs, alone and as lanes of the vmapped program (padded
    to a power of two, as the engine pads)."""
    from yugabyte_db_tpu.ops import group_agg
    from yugabyte_db_tpu.storage.tpu_engine import TpuStorageEngine

    arrays, sig, params, want = _packed_case(shape, grouped, flat)
    if not lanes:
        vec = np.asarray(group_agg.compiled_grouped(sig)(arrays, params[0]))
        assert vec.dtype == np.int32 and vec.ndim == 1
        _assert_same_bits(group_agg.unpack(sig, vec), want[0])
        return
    m = 1 << (lanes - 1).bit_length()
    stacked = np.zeros((m, params[0].size), np.int32)
    stacked[:lanes] = params[:lanes]
    res = np.asarray(TpuStorageEngine._batched_grouped_fn(sig)(arrays,
                                                               stacked))
    assert res.shape == (m, sum(
        int(np.prod(s)) for _o, s in group_agg.out_layout(sig).values()))
    for i in range(lanes):
        _assert_same_bits(group_agg.unpack(sig, res[i]), want[i])
    # (a lane that differs from lane 0 somewhere: the lanes are not one)
    if lanes > 1:
        assert res[0].tobytes() != res[lanes - 1].tobytes()


def test_out_layout_is_a_pure_function_of_the_signature():
    import dataclasses

    from yugabyte_db_tpu.ops import group_agg

    _arrays, sig, params, want = _packed_case("q1", True, True)
    layout = group_agg.out_layout(sig)
    assert layout == group_agg.out_layout(dataclasses.replace(sig))
    # what the program's size or the parameters are is not in it
    assert layout == group_agg.out_layout(
        dataclasses.replace(sig, B=sig.B * 4, R=128, K=2, preds=()))
    # back to back, in grouped_aggregate's documented order, every
    # output there and nothing else
    assert sig.radix == (4, 4) and sig.NB == 16      # the direct form
    assert list(layout)[:4] == ["count", "collisions", "scanned", "negs"]
    hashed = dataclasses.replace(sig, NB=group_agg.NUM_BUCKETS, radix=())
    assert list(group_agg.out_layout(hashed))[:6] == [
        "count", "rep", "key", "collisions", "scanned", "negs"]
    assert set(group_agg.out_layout(hashed)) == set(layout) | {"rep", "key"}
    assert set(layout) == set(want[0])
    off = 0
    for name, (o, shape) in layout.items():
        assert o == off and shape == want[0][name].shape, name
        off += int(np.prod(shape))
    # the parameter vector: row_gather's nine, the literals' ints, then
    # the float literals' bits
    assert group_agg.int_params(sig) == 9 + 1 and params[0].size == 11
    assert group_agg.out_layout(dataclasses.replace(hashed, group_cols=()))[
        "key"] == (2 * hashed.NB, (hashed.NB, 1))
    with pytest.raises(ValueError, match="int parameters"):
        group_agg.pack_params(sig, np.zeros(3, np.int32),
                              np.zeros(1, np.float32))
