"""Pallas flat-aggregate kernel vs the XLA/CPU oracle.

Runs in pallas interpret mode (CPU backend). The same program compiles
through Mosaic on the chip: ``chip_smoke.py`` does that once, at B=64k,
R=2048, and compares it with the CPU oracle. Nothing selects the kernel in
service yet — ``--tpu_engine_use_pallas`` has no reader (ROADMAP C3).
"""

import random

import numpy as np
import pytest

from yugabyte_db_tpu.models.datatypes import DataType
from yugabyte_db_tpu.models.partition import compute_hash_code
from yugabyte_db_tpu.models.schema import ColumnKind, ColumnSchema, Schema
from yugabyte_db_tpu.ops import pallas_agg
from yugabyte_db_tpu.ops.device_run import DeviceRun
from yugabyte_db_tpu.ops.scan import AggSig, PredSig
from yugabyte_db_tpu.storage import AggSpec, Predicate, ScanSpec, make_engine
from yugabyte_db_tpu.storage.row_version import RowVersion


def _schema():
    return Schema([
        ColumnSchema("k", DataType.STRING, ColumnKind.HASH),
        ColumnSchema("a", DataType.INT64),
        ColumnSchema("d", DataType.INT32),
    ], table_id="pal")


def _build(num_keys=700, seed=5, rows_per_block=128):
    schema = _schema()
    cid = {c.name: c.col_id for c in schema.columns}
    rng = random.Random(seed)
    rows = []
    ht = 10
    for i in range(num_keys):
        key = schema.encode_primary_key(
            {"k": f"u{i:05d}"}, compute_hash_code(schema, {"k": f"u{i:05d}"}))
        ht += rng.randrange(1, 3)
        if rng.random() < 0.06:
            rows.append(RowVersion(key, ht=ht, tombstone=True))
            continue
        cols = {}
        if rng.random() < 0.9:
            cols[cid["a"]] = rng.randrange(-10**14, 10**14)
        if rng.random() < 0.85:
            cols[cid["d"]] = rng.randrange(-10**6, 10**6)
        elif rng.random() < 0.5:
            cols[cid["d"]] = None
        rows.append(RowVersion(key, ht=ht, liveness=True, columns=cols))
    eng = make_engine("cpu", schema)
    eng.apply(rows)
    eng.flush()
    # a flat columnar run + device planes for the kernel
    from yugabyte_db_tpu.storage.columnar import ColumnarRun
    from yugabyte_db_tpu.storage.memtable import MemTable

    mem = MemTable()
    mem.apply(rows)
    crun = ColumnarRun.build(schema, mem.drain_sorted(), rows_per_block)
    assert crun.max_group_versions == 1  # flat
    dev = DeviceRun(crun, pallas_agg.BLOCKS_PER_STEP)
    return schema, cid, eng, crun, dev, ht


@pytest.mark.parametrize("pred_lo", [None, -400_000])
def test_pallas_matches_oracle(pred_lo):
    schema, cid, eng, crun, dev, max_ht = _build()
    read_ht = max_ht + 1

    preds = [] if pred_lo is None else [Predicate("d", ">=", pred_lo)]
    spec = ScanSpec(read_ht=read_ht, predicates=list(preds), aggregates=[
        AggSpec("count", None), AggSpec("count", "d"),
        AggSpec("sum", "a"), AggSpec("sum", "d"),
        AggSpec("min", "a"), AggSpec("max", "a"),
        AggSpec("min", "d"), AggSpec("max", "d")])
    want = eng.scan(spec).rows[0]

    aggs = (AggSig("count", None, None), AggSig("count", cid["d"], "i32"),
            AggSig("sum", cid["a"], "i64"), AggSig("sum", cid["d"], "i32"),
            AggSig("min", cid["a"], "i64"), AggSig("max", cid["a"], "i64"),
            AggSig("min", cid["d"], "i32"), AggSig("max", cid["d"], "i32"))
    psigs = tuple(PredSig(cid["d"], "i32", ">=") for _ in preds)
    assert pallas_agg.eligible(True, aggs, psigs)
    col_order = ((cid["a"], True), (cid["d"], False))

    from yugabyte_db_tpu.utils import planes as P

    r_hi, r_lo = P.scalar_ht_planes(read_ht)
    e_hi, e_lo = P.scalar_ht_planes(read_ht - 1)
    iparams = [0, crun.total_rows(), r_hi, r_lo, e_hi, e_lo]
    for p in preds:
        iparams.append(int(p.value))
    fn = pallas_agg.compiled_flat_aggregate(
        dev.B, crun.R, aggs, psigs, col_order, interpret=True)
    tensors = pallas_agg.gather_tensors(dev.arrays, col_order)
    partials = np.asarray(fn(tensors, np.array(iparams, np.int32)))
    count, scanned, vals = pallas_agg.combine_partials(partials, aggs)
    assert tuple(vals) == tuple(want)


def test_pallas_row_bounds():
    schema, cid, eng, crun, dev, max_ht = _build(num_keys=300)
    read_ht = max_ht + 1
    # bound the scan to the middle of the run and compare to the engine
    lo_key = crun.key_at(crun.total_rows() // 4)
    hi_key = crun.key_at(crun.total_rows() // 2)
    spec = ScanSpec(lower=lo_key, upper=hi_key, read_ht=read_ht,
                    aggregates=[AggSpec("count", None),
                                AggSpec("sum", "d")])
    want = eng.scan(spec).rows[0]

    aggs = (AggSig("count", None, None), AggSig("sum", cid["d"], "i32"))
    col_order = ((cid["a"], True), (cid["d"], False))
    from yugabyte_db_tpu.utils import planes as P

    r_hi, r_lo = P.scalar_ht_planes(read_ht)
    e_hi, e_lo = P.scalar_ht_planes(read_ht - 1)
    iparams = np.array([crun.lower_row(lo_key), crun.upper_row(hi_key),
                        r_hi, r_lo, e_hi, e_lo], np.int32)
    fn = pallas_agg.compiled_flat_aggregate(
        dev.B, crun.R, aggs, (), col_order, interpret=True)
    tensors = pallas_agg.gather_tensors(dev.arrays, col_order)
    partials = np.asarray(fn(tensors, iparams))
    _c, _s, vals = pallas_agg.combine_partials(partials, aggs)
    assert tuple(vals) == tuple(want)
