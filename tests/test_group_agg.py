"""Device GROUP BY / expression aggregates vs the CPU oracle.

Pins ops.group_agg (bucket hashing, exact digit-vector product sums,
collision/negative fallbacks) to Aggregator semantics — the TPC-H Q1/Q6
machinery.
"""

import functools
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
          versions=1):
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
            rv = RowVersion(key, ht=ht, liveness=True, columns=cols)
            cpu.apply([rv])
            tpu.apply([rv])
    cpu.flush()
    tpu.flush()
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

    def col(cmp):
        return {"set": np.ones((K, R), bool),
                "isnull": np.zeros((K, R), bool), "cmp": cmp}

    run = {
        "valid": np.ones((K, R), bool),
        "group_start": np.ones((K, R), bool),
        "tomb": np.zeros((K, R), bool),
        "live": np.ones((K, R), bool),
        "ht_hi": np.full((K, R), i32.min, np.int32),
        "ht_lo": np.full((K, R), i32.min, np.int32),
        "exp_hi": np.full((K, R), i32.max, np.int32),
        "exp_lo": np.full((K, R), i32.max, np.int32),
        "cols": {1: col(plane(7)),
                 2: col(plane([int(hi[0]), int(lo[0])], 2)),
                 3: col(plane(127))},
    }
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


@pytest.mark.parametrize("group_by", [[], ["flag", "status"]],
                         ids=["ungrouped", "grouped"])
def test_no_scatter_in_the_lowered_program(group_by):
    """The guard that keeps a serialized TPU scatter from coming back:
    neither the ungrouped (Q6) nor the grouped flat (Q1) signature lowers
    to one, inside the window loop or outside it; the grouped one holds
    the two int8 products instead."""
    from yugabyte_db_tpu.ops import group_agg

    _cpu, tpu, ht = _load(num=300)
    spec = ScanSpec(read_ht=ht + 1, group_by=group_by,
                    aggregates=list(Q1_AGGS),
                    predicates=[Predicate("d", "<", 900)])
    _kind, (sig, params) = tpu._grouped_prep(tpu.runs[0], spec,
                                             spec.predicates)
    assert sig.flat and bool(sig.group_cols) == bool(group_by)
    text = group_agg.compiled_grouped(sig).lower(
        tpu.runs[0].dev.arrays, params).as_text()
    assert "while" in text
    assert "scatter" not in text
    assert text.count("dot_general") == (2 if group_by else 0)


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
    assert list(layout)[:6] == ["count", "rep", "key", "collisions",
                                "scanned", "negs"]
    assert set(layout) == set(want[0])
    off = 0
    for name, (o, shape) in layout.items():
        assert o == off and shape == want[0][name].shape, name
        off += int(np.prod(shape))
    # the parameter vector: row_gather's nine, the literals' ints, then
    # the float literals' bits
    assert group_agg.int_params(sig) == 9 + 1 and params[0].size == 11
    assert group_agg.out_layout(dataclasses.replace(sig, group_cols=()))[
        "key"] == (2 * sig.NB, (sig.NB, 1))
    with pytest.raises(ValueError, match="int parameters"):
        group_agg.pack_params(sig, np.zeros(3, np.int32),
                              np.zeros(1, np.float32))
