"""The grouped program's buckets by dictionary code (the direct form of
ops.group_agg, PR 38) against the hashed form and the CPU oracle.

A file of its own beside tests/test_group_agg.py: every case compiles
the kernel twice in interpret mode, and tier-1 spreads files, not cases,
over its workers.
"""

import dataclasses
import functools

import numpy as np
import pytest

from tests.test_group_agg import Q1_AGGS, _load
from yugabyte_db_tpu.storage import AggSpec, Predicate, ScanSpec

COUNT_ONLY = [AggSpec("count", None, label="n")]


@functools.lru_cache(maxsize=None)
def _engines(versions, host_flush, null_groups):
    return _load(num=700, seed=38, versions=versions, host_flush=host_flush,
                 null_groups=null_groups)


def _hashed(sig):
    from yugabyte_db_tpu.ops import group_agg

    return dataclasses.replace(sig, NB=group_agg.NUM_BUCKETS, radix=())


def _key_of_codes(arrays, sig, codes):
    """The key planes the hashed form keeps of the group whose dictionary
    codes are ``codes``: each column's prefix planes and its null flag."""
    key = []
    for (cid, _planes), cap, code in zip(sig.group_cols, sig.radix, codes):
        d = arrays["cols"][cid]["cmp"]["dict"]
        null = code == cap - 1
        key += [0 if null else int(np.asarray(d["dhi"])[code]),
                0 if null else int(np.asarray(d["dlo"])[code]), int(null)]
    return key


@pytest.mark.parametrize("nulls", [False, True],
                         ids=["no_null", "null_and_unset_groups"])
@pytest.mark.parametrize("windows", [1, 3],
                         ids=["one_window", "a_window_a_block"])
@pytest.mark.parametrize("host_flush", [True, False],
                         ids=["bits_presence", "plain_presence"])
@pytest.mark.parametrize("versions", [1, 3], ids=["flat", "multi_version"])
@pytest.mark.parametrize("aggs", ["q1", "count_only"])
def test_direct_form_is_the_hashed_form_and_the_oracle(aggs, versions,
                                                       host_flush, windows,
                                                       nulls):
    """Bucket by bucket, bit for bit: the direct form's sums of the group
    with codes (c0, c1) are the hashed form's of the bucket that keeps
    that group's key planes, over flat runs (the packed prologue where
    the presence planes are "bits" leaves, by rows where they are plain)
    and multi-version ones (by rows), one window and one a block, NULL and
    unset group values in the dictionaries' absent slots; and both
    forms' rows through ``_finish_grouped`` are the CPU engine's."""
    from yugabyte_db_tpu.ops import encodings, group_agg

    cpu, tpu, ht = _engines(versions, host_flush, nulls)
    spec = ScanSpec(read_ht=ht + 1, group_by=["flag", "status"],
                    aggregates=list(Q1_AGGS if aggs == "q1" else COUNT_ONLY),
                    predicates=[Predicate("d", "<", 900)])
    trun = tpu.runs[0]
    _kind, (sig, params) = tpu._grouped_prep(trun, spec, spec.predicates)
    arrays = trun.dev.arrays
    assert sig.radix == (4, 4) and sig.NB == 16
    assert sig.flat == (versions == 1)
    assert (encodings.leaf_kind(arrays["valid"]) == "bits") == host_flush
    if windows > 1:
        # the same rows, a window a block
        assert sig.K == sig.B
        sig = dataclasses.replace(sig, K=1)
        params = params.copy()
        params[1] = (int(params[3]) - 1) // sig.R
        assert params[0] == 0 and params[1] + 1 >= windows
    hashed = _hashed(sig)
    assert sig.tag() == hashed.tag()
    vec_d = np.asarray(group_agg.compiled_grouped(sig)(arrays, params))
    vec_h = np.asarray(group_agg.compiled_grouped(hashed)(arrays, params))
    d, h = group_agg.unpack(sig, vec_d), group_agg.unpack(hashed, vec_h)
    assert "rep" not in d and "key" not in d and vec_d.size < vec_h.size / 30
    for name in ("scanned", "negs", "collisions"):
        assert int(d[name]) == int(h[name]), name
    assert int(d["collisions"]) == 0 and int(d["negs"]) == 0
    live_d = d["count"].nonzero()[0]
    live_h = h["count"].nonzero()[0]
    assert len(live_d) == len(live_h) >= 6
    seen_null = False
    for b in live_d:
        codes = group_agg.bucket_codes(sig, int(b))
        seen_null |= 3 in codes
        key = _key_of_codes(arrays, sig, codes)
        (hb,) = [hb for hb in live_h if h["key"][hb].tolist() == key]
        for name in d:
            if d[name].ndim:
                assert d[name][b].tobytes() == h[name][hb].tobytes(), name
    assert seen_null == nulls

    def no_fallback():
        raise AssertionError("the program's answer was thrown away")

    want = cpu.scan(spec)
    for s, vec in ((sig, vec_d), (hashed, vec_h)):
        got = tpu._finish_grouped(trun.crun, spec, s, vec, no_fallback)
        assert got.columns == want.columns and got.rows == want.rows
    assert any(r[0] is None or r[1] is None for r in want.rows) == nulls


def test_the_masked_primary_of_the_overlay_goes_direct_by_rows():
    """The delta overlay's masked primary: the primary's leaves with a
    plain ``valid``. Its group columns are still "dict" leaves, so a
    grouped program over it is addressed directly (rows prologue)."""
    from yugabyte_db_tpu.ops import group_agg

    _cpu, tpu, ht = _engines(1, True, False)
    spec = ScanSpec(read_ht=ht + 1, group_by=["flag", "status"],
                    aggregates=list(COUNT_ONLY))
    _kind, (sig, params) = tpu._grouped_prep(tpu.runs[0], spec, [])
    idx = np.arange(5, 700, 13, dtype=np.int32)
    masked = tpu._masked_primary(tpu.runs[0], idx).dev.arrays
    assert group_agg.addressed(_hashed(sig), masked) == sig
    fn = group_agg.compiled_grouped(sig)
    whole = group_agg.unpack(sig, np.asarray(fn(tpu.runs[0].dev.arrays,
                                                params)))
    got = group_agg.unpack(sig, np.asarray(fn(masked, params)))
    assert int(got["count"].sum()) == int(whole["count"].sum()) - idx.size
