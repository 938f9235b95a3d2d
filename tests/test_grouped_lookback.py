"""ops.lookback_fold.resolve_window (merge-on-read by bounded lookback:
static shifts and selects) against ops.scan.resolve_window's segmented
branch (cumsum, segment ops, gathers) on seeded random multi-version
windows of plain planes. Pure jnp, no engine, no kernel.

The two give a key group's state at different places: the segmented form
at the group's NUMBER in the window, with its columns as indices into
the window's planes; the lookback form at the group's first ROW, with
its columns as merged values. At each group they must agree on
``pre_pred``, every ``col_notnull`` and, wherever a column has an alive
setter at all, every merged plane. The windows hold what a run can:
key groups of 1..W versions, a tombstone at exactly a write's ``ht``
(either order: the ``<=`` tie), a key deleted and inserted again, NULLs,
unset columns, TTL expiry, padding rows at a block's end, groups ending
at a block's last row, blocks of padding alone behind the run, and are
read before, between and after their versions.
"""

import functools
import random

import jax
import numpy as np
import pytest

from yugabyte_db_tpu.ops import lookback_fold, scan
from yugabyte_db_tpu.ops.group_agg import GroupAggSig

from tests.test_group_agg import _equations

I32 = np.iinfo(np.int32)
HTS = 12          # commit times are 1..HTS on the low plane
COLS = (scan.ColSig(1, "i32"), scan.ColSig(2, "i64"), scan.ColSig(3, "f32"))


def _version(rnd, ht, tomb=False):
    """One row: (ht, tomb, live, exp, {col: (set, isnull, value)})."""
    cols = {cs.col_id: (rnd.random() < 0.7 and not tomb,
                        rnd.random() < 0.2, rnd.randrange(-50, 50))
            for cs in COLS}
    # TTL: most versions never expire, some at a time inside the reads'
    exp = rnd.randrange(1, HTS + 3) if rnd.random() < 0.15 else None
    return (ht, tomb, not tomb and rnd.random() < 0.6, exp, cols)


def _crafted(rnd, W):
    """The groups a random draw may miss, newest first."""
    groups = [[_version(rnd, 5)]]
    if W >= 2:
        groups += [
            [_version(rnd, 6), _version(rnd, 6, tomb=True)],   # tie, write first
            [_version(rnd, 6, tomb=True), _version(rnd, 6)],   # tie, tomb first
            [_version(rnd, 7, tomb=True), _version(rnd, 3)],   # deleted
        ]
    if W >= 3:
        groups.append([_version(rnd, 9), _version(rnd, 6, tomb=True),
                       _version(rnd, 2)])          # deleted, inserted again
    return groups


def _random_group(rnd, W):
    n = rnd.randrange(1, W + 1)
    hts = sorted((rnd.randrange(1, HTS + 1) for _ in range(n)), reverse=True)
    return [_version(rnd, ht, tomb=rnd.random() < 0.25) for ht in hts]


def _window(W, seed, B, R, pad_blocks, exact_fit):
    """Planes ``[B, R]`` of a run of ``B - pad_blocks`` blocks of key
    groups (none spans a block; ``exact_fit`` blocks end at a group's
    last row, the others with padding rows) and ``pad_blocks`` of
    padding alone."""
    rnd = random.Random(seed)
    blocks = []
    todo = _crafted(rnd, W) + [[_version(rnd, ht) for ht in range(W, 0, -1)]]
    for b in range(B - pad_blocks):
        room = R if b in exact_fit else R - rnd.randrange(1, 4)
        groups = []
        while True:
            group = todo.pop(0) if todo else _random_group(rnd, W)
            if len(group) > room:
                todo.insert(0, group)
                break
            groups.append(group)
            room -= len(group)
        while b in exact_fit and room:
            groups.append([_version(rnd, ht)
                           for ht in range(min(W, room), 0, -1)])
            room -= len(groups[-1])
        blocks.append([(i == 0, v) for g in groups for i, v in enumerate(g)])
    assert len(todo) <= 1, "the crafted groups did not fit"
    blocks += [[] for _ in range(pad_blocks)]

    def plane(fn, dtype, fill, tail=()):
        out = np.full((B, R) + tail, fill, dtype)
        for b, rows in enumerate(blocks):
            for r, (start, v) in enumerate(rows):
                out[b, r] = fn(start, v)
        return out

    run = {
        "valid": plane(lambda s, v: True, bool, False),
        "group_start": plane(lambda s, v: s, bool, False),
        "tomb": plane(lambda s, v: v[1], bool, False),
        "live": plane(lambda s, v: v[2], bool, False),
        "ht_hi": plane(lambda s, v: 0, np.int32, 0),
        "ht_lo": plane(lambda s, v: v[0], np.int32, 0),
        "exp_hi": plane(lambda s, v: 0 if v[3] else I32.max, np.int32,
                        I32.max),
        "exp_lo": plane(lambda s, v: v[3] or I32.max, np.int32, I32.max),
        "cols": {},
    }
    for cs in COLS:
        cid = cs.col_id
        col = {
            "set": plane(lambda s, v: v[4][cid][0], bool, False),
            "isnull": plane(lambda s, v: v[4][cid][1], bool, False),
            # the value and, where the kind has a second plane, the row's
            # ht: a plane that tells versions of one key apart
            "cmp": plane(lambda s, v: [v[4][cid][2], v[0]][:1 + cs.two_plane],
                         np.int32, 0, (1 + cs.two_plane,)),
        }
        if cs.kind == "f32":
            col["arith"] = plane(lambda s, v: v[4][cid][2] / 4, np.float32, 0)
        run["cols"][cid] = col
    return run


def _sig(K, R, W):
    return GroupAggSig(B=K, R=R, K=K, NB=512, cols=COLS, preds=(),
                       apply_preds=False, flat=False, group_cols=(),
                       aggs=(), lookback=W)


@functools.lru_cache(maxsize=None)
def _programs(K, R, W):
    sig = _sig(K, R, W)
    seg = jax.jit(lambda run, b0, lo, hi, read: scan.resolve_window(
        sig, run, b0, lo, hi, *read, ()))
    lb = jax.jit(lambda run, b0, lo, hi, read: lookback_fold.resolve_window(
        sig, run, b0, lo, hi, *read))
    return seg, lb


def _assert_same_groups(seg, lb, where):
    seg, lb = jax.device_get((seg, lb))
    ng = int(seg["num_groups"])
    rep = np.flatnonzero(lb["group_start"])
    assert rep.size == ng > 0, where
    assert (seg["start_idx"][:ng] == rep).all(), where
    assert (seg["pre_pred"][:ng] == lb["pre_pred"][rep]).all(), where
    for cs in COLS:
        cid = cs.col_id
        assert (seg["col_notnull"][cid][:ng]
                == lb["col_notnull"][cid][rep]).all(), (where, cid)
        has = seg["col_has"][cid][:ng]
        at = seg["col_idx"][cid][:ng][has]
        for name in ("cmp_w", "arith_w"):
            if cid in seg[name]:
                assert (seg[name][cid][at] == lb[name][cid][rep[has]]).all(), \
                    (where, cid, name)
    return ng, int(lb["pre_pred"][rep].sum())


LAYOUTS = {
    # blocks, rows a block, blocks of padding alone, blocks that end at a
    # group's last row
    "padded_tails": dict(B=4, R=32, pad_blocks=1, exact_fit=()),
    "groups_end_at_block_ends": dict(B=4, R=32, pad_blocks=0,
                                     exact_fit=(0, 1, 3)),
}


@pytest.mark.parametrize("layout", list(LAYOUTS))
@pytest.mark.parametrize("W", [1, 2, 3, 4, 8])
def test_lookback_resolve_is_the_segmented_resolve_at_each_group(W, layout):
    shape = LAYOUTS[layout]
    B, R = shape["B"], shape["R"]
    run = _window(W, seed=100 * W + len(layout), **shape)
    seg, lb = _programs(B, R, W)
    seen = set()
    # before every version, between them (each tie's ht too), after all;
    # the TTL's clock at, before and after the versions' expiries
    for read_ht in (0, 2, 3, 5, 6, 7, 9, HTS, I32.max):
        for exp_now in (I32.min, 4, HTS + 3):
            read = (np.int32(0), np.int32(read_ht),
                    np.int32(0 if exp_now != I32.min else I32.min),
                    np.int32(exp_now))
            for lo, hi in ((0, B * R), (R // 2, 2 * R + 5)):
                got = _assert_same_groups(
                    seg(run, 0, lo, hi, read), lb(run, 0, lo, hi, read),
                    (W, layout, read_ht, exp_now, lo, hi))
                if (lo, hi) == (0, B * R):
                    seen.add(got[1])
    # the read points are told apart: nothing before the first version,
    # most groups after the last
    assert min(seen) == 0 and max(seen) > 0.5 * got[0]


@pytest.mark.parametrize("W", [2, 4])
def test_a_window_that_is_not_the_runs_first(W):
    """Two windows of two blocks: the second resolves the run's last two
    blocks, its bounds window-local."""
    B, K, R = 4, 2, 32
    run = _window(W, seed=7 + W, B=B, R=R, pad_blocks=0, exact_fit=(2,))
    seg, lb = _programs(K, R, W)
    read = (np.int32(0), np.int32(HTS), np.int32(0), np.int32(4))
    for b0 in (0, 2):
        for lo, hi in ((0, K * R), (5, K * R - 9)):
            _assert_same_groups(seg(run, b0, lo, hi, read),
                                lb(run, b0, lo, hi, read), (W, b0, lo, hi))
    whole = _programs(B, R, W)[1](run, 0, 0, B * R, read)
    tail = lb(run, 2, 0, K * R, read)
    assert (np.asarray(whole["pre_pred"])[K * R:]
            == np.asarray(tail["pre_pred"])).all()


def _primitives(jaxpr) -> set:
    """The primitives of a jaxpr and of every jaxpr its equations hold."""
    return {name for name, _eqn, _in_kernel in _equations(jaxpr)}


def test_the_lookback_resolve_traces_to_shifts_and_selects():
    """No cumsum, no segment op (a scatter), no gather, no sort: pads,
    slices and elementwise ops over ``[K, R]``."""
    K, R, W = 4, 32, 4
    run = _window(W, seed=3, B=K, R=R, pad_blocks=1, exact_fit=())
    read = (np.int32(0), np.int32(5), np.int32(0), np.int32(4))
    jaxpr = jax.make_jaxpr(lambda run, read: lookback_fold.resolve_window(
        _sig(K, R, W), run, 0, 0, K * R, *read))(run, read)
    names = _primitives(jaxpr.jaxpr)
    assert "pad" in names and "select_n" in names
    assert not [n for n in names if "scatter" in n or "gather" in n
                or "cumsum" in n or "sort" in n or "while" in n], names
    seg = jax.make_jaxpr(lambda run, read: scan.resolve_window(
        _sig(K, R, W), run, 0, 0, K * R, *read, ()))(run, read)
    assert {"cumsum", "gather"} <= _primitives(seg.jaxpr)


@pytest.mark.parametrize("versions,want", [
    (0, 0), (1, 0), (2, 2), (3, 4), (4, 4), (5, 8), (17, 32), (32, 32),
    (33, 0), (1000, 0)])
def test_the_bound_a_run_is_compiled_for(versions, want):
    """Flat runs and runs past MAX_LOOKBACK take none; the others the
    next power of two: five values."""
    assert lookback_fold.bound(versions) == want
