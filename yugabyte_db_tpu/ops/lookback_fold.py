"""Loop-free full-run aggregate over a MULTI-VERSION run: bounded
lookback instead of segmented scans.

ops.seg_fold answers every per-group MVCC question with
lax.associative_scan — log-depth, but each of the ~11 combine levels
re-materializes the full payload (ht planes + every column's planes),
so the resolve runs an order of magnitude below the flat path's memory
roofline (~16 GB/s vs ~490 GB/s measured at 17M rows).

This module exploits one more layout invariant: the columnar build
records the run's LARGEST key-group version count (max_group_versions).
When that bound W is small — the common case; version counts reflect
update traffic since the last compaction — every per-group question is
answerable by looking at most W-1 rows to either side:

- rows of a group are contiguous, newest-first, never spanning a block,
  so a shift along the row axis with zero fill never leaks across keys;
- "newest visible tombstone shadows ht <= its ht" becomes: any EARLIER
  visible tombstone in-group shadows this row (ht-desc order makes its
  ht >= ours), plus any LATER one at exactly our ht (same-batch
  DELETE+write ties);
- "latest alive setter per column" becomes a first-match select over
  the W forward offsets, evaluated at each group's first row (the
  representative), exactly seg_fold's suffix-first.

Everything is elementwise + W-1 static shifts, which XLA fuses like the
flat path. seg_fold remains the fallback for runs whose W exceeds the
unroll bound (heavy-update groups), and the oracle in tests.

Reference analog: the same merge-on-read (DocRowwiseIterator,
src/yb/docdb/doc_rowwise_iterator.cc:545) at memory-roofline shape.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp


from yugabyte_db_tpu.ops import encodings
from yugabyte_db_tpu.ops import flat_fold
from yugabyte_db_tpu.ops import scan as dscan
from yugabyte_db_tpu.ops.scan import I32_MIN, le2
from yugabyte_db_tpu.utils import jitting
from yugabyte_db_tpu.utils.jitting import compile_contract

# Largest per-group version count the unrolled lookback compiles for.
# Beyond it the engine falls back to seg_fold's associative scans.
MAX_LOOKBACK = 32


def supports(sig: dscan.ScanSig) -> bool:
    if sig.flat or sig.lookback < 1 or sig.lookback > MAX_LOOKBACK:
        return False
    if sig.R > flat_fold.MAX_R or sig.B > flat_fold.MAX_B:
        return False
    if any(ps.kind not in ("i32", "i64", "f64", "code")
           for ps in sig.preds):
        return False
    for ag in sig.aggs:
        if ag.fn not in ("count", "sum", "min", "max"):
            return False
    return True


def _shift_r(x, k):
    """x[r-k] with zero/False fill (along the row axis)."""
    if k == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[1] = (k, 0)
    return jnp.pad(x, pad)[:, : x.shape[1]]


def _shift_l(x, k):
    """x[r+k] with zero/False fill (along the row axis)."""
    if k == 0:
        return x
    pad = [(0, 0)] * x.ndim
    pad[1] = (0, k)
    return jnp.pad(x, pad)[:, k:]


def bound(max_group_versions: int) -> int:
    """The lookback a run's programs are compiled for, from the run's
    largest key group: 0 where the run is flat (nothing to look back
    at) or past MAX_LOOKBACK (the segmented resolve), else the next
    power of two, so that drifting version counts share at most five
    compiled variants of a signature."""
    if max_group_versions <= 1 or max_group_versions > MAX_LOOKBACK:
        return 0
    return 1 << (max_group_versions - 1).bit_length()


def resolve(W: int, cols, run, read_hi, read_lo, rexp_hi, rexp_lo):
    """The merge-on-read of ``run`` at a read point by looking at most
    ``W - 1`` rows to either side (traced; the one copy of the
    shadowing rule outside ops.scan.resolve_window's segment ops).

    ``run`` holds plain planes ``[B, R(, P)]`` whose key groups hold at
    most ``W`` rows and never span a block: a decoded run
    (``compiled_lookback_aggregate``) or a window of one
    (``resolve_window``). ``cols`` are the ColSigs to merge. Everything
    is elementwise over ``[B, R]`` but for static shifts along the row
    axis: no cumsum, no segment op, no gather. Returns, per ROW and
    meaningful at each group's first row (its representative,
    ``run["group_start"]``):
      live_any     bool — an alive, unexpired version holds the
                   liveness marker
      col_notnull  {col_id: bool} — the column's latest alive setter
                   exists, is not NULL and has not expired
      col_val      {col_id: {"null", "exp", "cmp"[, "arith"]}} — that
                   setter's payload as VALUES (garbage where there is
                   none: gated by ``col_notnull``)."""
    valid = run["valid"]
    gs = run["group_start"]
    ht_hi, ht_lo = run["ht_hi"], run["ht_lo"]
    visible = valid & le2(ht_hi, ht_lo, read_hi, read_lo)
    expired = le2(run["exp_hi"], run["exp_lo"], rexp_hi, rexp_lo)
    tomb = run["tomb"]

    # same_prev[k]: row r-k is in r's group (k = 1..W-1); built
    # incrementally from "no group start in (r-k, r]".
    not_gs = ~gs
    same_prev = [None] * W
    for k in range(1, W):
        same_prev[k] = (not_gs if k == 1
                        else same_prev[k - 1] & _shift_r(not_gs, k - 1))
    # same_next[k]: row r+k is in r's group.
    same_next = [None] * W
    for k in range(1, W):
        same_next[k] = _shift_l(same_prev[k], k)

    # 1. Tombstone shadowing. Earlier in-group visible tombstones
    # always shadow (their ht is >= ours in ht-desc layout); later
    # ones shadow only at exactly our ht (same-batch ties).
    vt = visible & tomb
    shadowed = jnp.zeros_like(vt)
    for k in range(1, W):
        shadowed = shadowed | (same_prev[k] & _shift_r(vt, k))
        later_vt = same_next[k] & _shift_l(vt, k)
        eq_ht = (ht_hi == _shift_l(ht_hi, k)) & \
            (ht_lo == _shift_l(ht_lo, k))
        shadowed = shadowed | (later_vt & eq_ht)
    alive = visible & ~tomb & ~shadowed

    # 2. Group-level liveness at the representative (first row).
    def group_or(x):
        out = x
        for k in range(1, W):
            out = out | (same_next[k] & _shift_l(x, k))
        return out

    live_any = group_or(alive & run["live"] & ~expired)

    # 3. Per-column latest alive setter: first forward match over
    # the W offsets, payload selected newest-match-wins (iterate
    # offsets far-to-near so the nearest match lands last).
    col_notnull = {}
    col_val = {}

    def sel_where(m, a, b):
        mm = m
        while mm.ndim < a.ndim:
            mm = mm[..., None]
        return jnp.where(mm, a, b)

    for cs in cols:
        c = run["cols"][cs.col_id]
        cand = alive & c["set"]
        payload = {"null": c["isnull"], "exp": expired,
                   "cmp": c["cmp"]}
        if "arith" in c:
            payload["arith"] = c["arith"]
        # Nearest-forward-match wins: fold offsets far -> near, then
        # let the row itself (offset 0) override. Garbage where no
        # offset matches -- gated by ``has``.
        has = cand
        sel = dict(payload)
        for k in range(W - 1, 0, -1):
            cand_k = same_next[k] & _shift_l(cand, k)
            has = has | cand_k
            sel = {name: sel_where(cand_k,
                                   _shift_l(payload[name], k),
                                   sel[name])
                   for name in payload}
        if W > 1:
            sel = {name: sel_where(cand, payload[name], sel[name])
                   for name in payload}
        col_notnull[cs.col_id] = has & ~sel["null"] & ~sel["exp"]
        col_val[cs.col_id] = sel
    return live_any, col_notnull, col_val


def resolve_window(sig, run, b0, row_lo, row_hi,
                   read_hi, read_lo, rexp_hi, rexp_lo):
    """One K-block window of ``run`` resolved by ``resolve`` (traced):
    what ops.scan.resolve_window's segmented branch gives per key group,
    here per ROW of the window and meaningful where ``group_start`` is
    set — a key group's entry is its first row, whose window-local
    position is the segmented form's ``start_idx`` — and the merged
    columns as planes of VALUES, not as indices into the window's.

    ``sig`` needs K, R, cols and ``lookback`` (>= the run's largest key
    group); ``row_lo``/``row_hi`` are window-local. The window's planes
    (encodings.wplane) are taken ``[K, R, ...]``, so that a shift never
    crosses a block. Returns
      group_start  bool [N] — the row is a key group's representative
      pre_pred     bool [N] — the group exists and is in range (before
                   predicates); as ``resolve_window``'s, any valid row
                   of an existing group being implied
      ridx, start_idx  i32 [N] — a row's place in the window: its
                   group's first row where it is a representative
      col_notnull  {col_id: bool [N]}
      cmp_w, arith_w  {col_id: [N, ...]} the merged planes."""
    K, R = sig.K, sig.R
    N = K * R

    def wp(leaf):
        p = encodings.wplane(leaf, b0, K, R)
        return p.reshape((K, R) + p.shape[1:])

    win = {name: wp(run[name]) for name in (
        "valid", "group_start", "tomb", "live", "ht_hi", "ht_lo",
        "exp_hi", "exp_lo")}
    win["cols"] = {
        cs.col_id: {name: wp(leaf)
                    for name, leaf in run["cols"][cs.col_id].items()}
        for cs in sig.cols}
    live_any, col_notnull, col_val = resolve(
        sig.lookback, sig.cols, win, read_hi, read_lo, rexp_hi, rexp_lo)

    def rows(x):
        return x.reshape((N,) + x.shape[2:])

    exists = live_any
    for cs in sig.cols:
        exists = exists | col_notnull[cs.col_id]
    ridx = jnp.arange(N, dtype=jnp.int32)
    return {
        "group_start": rows(win["group_start"]),
        "pre_pred": rows(exists) & (ridx >= row_lo) & (ridx < row_hi),
        "ridx": ridx,
        "start_idx": ridx,
        "col_notnull": {cid: rows(nn) for cid, nn in col_notnull.items()},
        "cmp_w": {cid: rows(v["cmp"]) for cid, v in col_val.items()},
        "arith_w": {cid: rows(v["arith"]) for cid, v in col_val.items()
                    if "arith" in v},
    }


@functools.lru_cache(maxsize=128)
@compile_contract("lookback_aggregate", max_compiles=128)
def compiled_lookback_aggregate(sig: dscan.ScanSig):
    """jit(run, row_lo, row_hi, read_hi, read_lo, rexp_hi, rexp_lo,
    pred_lits) -> (ivec, fvec) in agg_fold's packed format; exact
    equivalence with seg_fold on any run whose group sizes are within
    sig.lookback."""
    assert supports(sig)

    def fn(run, row_lo, row_hi, read_hi, read_lo, rexp_hi, rexp_lo,
           pred_lits):
        run = encodings.decode_run(run)
        live_any, col_notnull, col_val = resolve(
            sig.lookback, sig.cols, run, read_hi, read_lo, rexp_hi, rexp_lo)
        return flat_fold.finish_groups(
            sig, run["group_start"], live_any, col_notnull, col_val,
            row_lo, row_hi, pred_lits)

    return jitting.jit(fn, "lookback_aggregate", sig.tag())
