"""The device scan kernel: MVCC merge-on-read + filter + aggregate pushdown.

One jitted program (per static signature) scans a window of K blocks from a
ColumnarRun: it resolves MVCC visibility (commit-ht vs read point, row
tombstone shadowing, TTL expiry), merges each key group to its
latest-visible per-column state, applies key-range row bounds and pushed
predicates, and either reports matching groups (row scans) or reduces
aggregate partials per block (aggregate pushdown).

Semantics are exactly storage.merge.merge_versions, vectorized with
segmented reductions keyed on contiguous key-group ids. The randomized
engine-diff tests pin this kernel to the CPU oracle.

Design notes (TPU-first):
- all 64-bit comparisons are two-int32-plane lexicographic compares
  (utils.planes); no int64 on device;
- groups never span blocks (columnar build invariant), so any window of
  whole blocks is segment-complete;
- range bounds arrive as *row index* bounds, pre-resolved on host by exact
  bisection over full key bytes — the device never resolves key-prefix ties;
- integer SUM is exact: values decompose into 16-bit limbs summed per block
  in int32, recombined on host in arbitrary precision (the float path sums
  f32 per block, f64 across blocks);
- varlen (string) predicates evaluate on 8-byte order-preserving prefixes
  as a SUPERSET mask (plane-equal = maybe-match); the engine host-verifies
  candidates, and routes aggregates through the row path in that case.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from yugabyte_db_tpu.ops import encodings
from yugabyte_db_tpu.utils import jitting
from yugabyte_db_tpu.utils.jitting import compile_contract

I32_MIN = np.int32(np.iinfo(np.int32).min)
I32_MAX = np.int32(np.iinfo(np.int32).max)

# -- 2-plane lexicographic compares (signed int32 planes) -------------------

def le2(a_hi, a_lo, b_hi, b_lo):
    return (a_hi < b_hi) | ((a_hi == b_hi) & (a_lo <= b_lo))


def lt2(a_hi, a_lo, b_hi, b_lo):
    return (a_hi < b_hi) | ((a_hi == b_hi) & (a_lo < b_lo))


def eq2(a_hi, a_lo, b_hi, b_lo):
    return (a_hi == b_hi) & (a_lo == b_lo)


# -- static signature -------------------------------------------------------

@dataclass(frozen=True)
class ColSig:
    col_id: int
    kind: str        # 'i32' | 'i64' | 'f32' | 'f64' | 'str'

    @property
    def two_plane(self) -> bool:
        return self.kind in ("i64", "f64", "str")


@dataclass(frozen=True)
class PredSig:
    col_id: int
    kind: str
    op: str          # '=', '!=', '<', '<=', '>', '>=' ('IN' expands to '='s)


@dataclass(frozen=True)
class AggSig:
    fn: str          # 'count' | 'sum' | 'min' | 'max'
    col_id: int | None
    kind: str | None


@dataclass(frozen=True)
class ScanSig:
    """Everything that shapes the compiled program."""

    B: int           # blocks in run
    R: int           # rows per block
    K: int           # blocks per window
    cols: tuple      # tuple[ColSig] — columns the program touches
    preds: tuple     # tuple[PredSig]
    aggs: tuple      # tuple[AggSig] — empty for row scans
    apply_preds: bool  # False: candidates only (multi-source scans)
    flat: bool = False  # every key group has exactly 1 version: the MVCC
                        # merge degenerates to elementwise masks (no
                        # segment ops / gathers) — the post-compaction
                        # fast path
    lookback: int = 0   # a bound on the run's versions per key group
                        # (lookback_fold.bound: the next power of two; 0
                        # = flat, or past MAX_LOOKBACK): small bounds
                        # unlock the shifted-mask resolve
                        # (lookback_fold.resolve, which the grouped
                        # program's GroupAggSig.lookback selects too)
                        # instead of segmented scans

    def tag(self) -> str:
        """What the query decides of the program, for its name
        (utils.jitting.tag): not B, R, K, nor the table's columns."""
        return jitting.tag(aggs=self.aggs, preds=self.preds,
                           flat=self.flat, lookback=self.lookback)


# -- the program ------------------------------------------------------------

def _window(arr, b0, K):
    """Slice K blocks starting at b0 and flatten the block axis."""
    sizes = (K,) + arr.shape[1:]
    starts = (b0,) + (0,) * (arr.ndim - 1)
    w = jax.lax.dynamic_slice(arr, starts, sizes)
    return w.reshape((sizes[0] * sizes[1],) + sizes[2:])


def _seg_max(vals, gid, n):
    return jax.ops.segment_max(vals, gid, num_segments=n,
                               indices_are_sorted=True)


def _seg_min(vals, gid, n):
    return jax.ops.segment_min(vals, gid, num_segments=n,
                               indices_are_sorted=True)


def _seg_sum(vals, gid, n):
    return jax.ops.segment_sum(vals, gid, num_segments=n,
                               indices_are_sorted=True)


def _u32(x):
    return x.astype(jnp.uint32)


def _limbs16(lo_u32, hi_u32):
    """Four 16-bit limbs of a biased u64 (hi*2^32 + lo), as int32."""
    return (
        (lo_u32 & jnp.uint32(0xFFFF)).astype(jnp.int32),
        (lo_u32 >> jnp.uint32(16)).astype(jnp.int32),
        (hi_u32 & jnp.uint32(0xFFFF)).astype(jnp.int32),
        (hi_u32 >> jnp.uint32(16)).astype(jnp.int32),
    )


def resolve_window(sig, run, b0, row_lo, row_hi,
                   read_hi, read_lo, rexp_hi, rexp_lo, pred_literals):
    """Resolve one K-block window to per-group MVCC state (traced).

    ``sig`` needs K, R, cols, preds, apply_preds (ScanSig or GatherSig).
    ``row_lo``/``row_hi`` are *window-local* row-index bounds. Returns a
    dict of per-group arrays (indexed by group id, length N, entries at
    gid >= num_groups are garbage):
      result        bool  — exists & in-range & predicates
      pre_pred      bool  — exists & in-range (before predicates)
      start_idx     i32   — first row of the group (window-local)
      col_idx/col_has/col_notnull  per touched column
      cmp_w/arith_w windowed column planes (per-row, window-local)
    """
    K, R = sig.K, sig.R
    N = K * R

    def wp(leaf):
        # Encoded leaves (ops.encodings) decode inline per window; plain
        # ndarrays take the dynamic-slice path _window always used.
        return encodings.wplane(leaf, b0, K, R)

    valid = wp(run["valid"])
    group_start = wp(run["group_start"])
    tomb = wp(run["tomb"])
    live = wp(run["live"])
    ht_hi = wp(run["ht_hi"])
    ht_lo = wp(run["ht_lo"])
    exp_hi = wp(run["exp_hi"])
    exp_lo = wp(run["exp_lo"])

    ridx = jnp.arange(N, dtype=jnp.int32)

    # 1. MVCC visibility at the read point.
    visible = valid & le2(ht_hi, ht_lo, read_hi, read_lo)
    expired = le2(exp_hi, exp_lo, rexp_hi, rexp_lo)

    if sig.flat:
        return _resolve_flat(sig, run, b0, row_lo, row_hi, pred_literals,
                             N, ridx, valid, tomb, live, visible, expired)

    gid = jnp.cumsum(group_start.astype(jnp.int32)) - 1
    num_groups = gid[-1] + 1

    # 2. Row-tombstone shadowing: newest visible tombstone per group.
    t_hi = _seg_max(jnp.where(visible & tomb, ht_hi, I32_MIN), gid, N)
    t_hi_r = t_hi[gid]
    t_lo = _seg_max(jnp.where(visible & tomb & (ht_hi == t_hi_r), ht_lo, I32_MIN),
                    gid, N)
    t_lo_r = t_lo[gid]
    has_tomb = t_hi_r != I32_MIN
    # <= (not <): a value at exactly the tombstone's ht is shadowed too,
    # matching merge.py (same-batch DELETE+write share one ht).
    shadowed = has_tomb & le2(ht_hi, ht_lo, t_hi_r, t_lo_r)
    alive = visible & ~tomb & ~shadowed

    # 3. Liveness (INSERT marker) per group.
    live_exists = _seg_max((alive & live & ~expired).astype(jnp.int32), gid, N) > 0

    # 4. Per-column latest visible version (first alive setter in ht-desc order).
    start_idx = _seg_min(ridx, gid, N)  # first row of each group
    col_idx = {}
    col_has = {}
    col_notnull = {}
    isnull_w = {}
    set_w = {}
    cmp_w = {}
    arith_w = {}
    for cs in sig.cols:
        c = run["cols"][cs.col_id]
        set_c = wp(c["set"])
        null_c = wp(c["isnull"])
        cand = alive & set_c
        first = _seg_min(jnp.where(cand, ridx, I32_MAX), gid, N)
        has = first != I32_MAX
        idx = jnp.clip(first, 0, N - 1)
        col_idx[cs.col_id] = idx
        col_has[cs.col_id] = has
        col_notnull[cs.col_id] = has & ~null_c[idx] & ~expired[idx]
        isnull_w[cs.col_id] = null_c
        set_w[cs.col_id] = set_c
        cmp_w[cs.col_id] = wp(c["cmp"])
        if "arith" in c:
            arith_w[cs.col_id] = wp(c["arith"])

    # 5. Row existence (liveness or any non-null column value).
    exists = live_exists
    for cs in sig.cols:
        exists = exists | col_notnull[cs.col_id]

    # 6. Key-range bounds as exact global row-index bounds (host-resolved).
    in_range = (start_idx >= row_lo) & (start_idx < row_hi)
    valid_group = _seg_max(valid.astype(jnp.int32), gid, N) > 0

    result = exists & in_range & valid_group

    # 7. Predicates on merged per-group values.
    pre_pred = result
    if sig.apply_preds:
        for i, ps in enumerate(sig.preds):
            lit = pred_literals[i]
            idx = col_idx[ps.col_id]
            notnull = col_notnull[ps.col_id]
            result = result & notnull & _eval_pred(
                ps, cmp_w.get(ps.col_id), arith_w.get(ps.col_id), idx, lit)

    return {
        "result": result,
        "pre_pred": pre_pred,
        "start_idx": start_idx,
        "num_groups": num_groups,
        "ridx": ridx,
        "col_idx": col_idx,
        "col_has": col_has,
        "col_notnull": col_notnull,
        "cmp_w": cmp_w,
        "arith_w": arith_w,
    }


def _resolve_flat(sig, run, b0, row_lo, row_hi, pred_literals,
                  N, ridx, valid, tomb, live, visible, expired):
    """Single-version-per-key resolve: every row is its own group, so
    tombstone shadowing, per-column latest-version selection, and the
    group-start machinery are all elementwise (no segment ops, no
    gathers). Produces the same output contract as the general path with
    num_groups == N and col_idx == ridx."""
    alive = visible & ~tomb
    live_exists = alive & live & ~expired
    col_idx = {}
    col_has = {}
    col_notnull = {}
    cmp_w = {}
    arith_w = {}

    def wp(leaf):
        return encodings.wplane(leaf, b0, sig.K, sig.R)

    for cs in sig.cols:
        c = run["cols"][cs.col_id]
        set_c = wp(c["set"])
        null_c = wp(c["isnull"])
        has = alive & set_c
        col_idx[cs.col_id] = ridx
        col_has[cs.col_id] = has
        col_notnull[cs.col_id] = has & ~null_c & ~expired
        cmp_w[cs.col_id] = wp(c["cmp"])
        if "arith" in c:
            arith_w[cs.col_id] = wp(c["arith"])

    exists = live_exists
    for cs in sig.cols:
        exists = exists | col_notnull[cs.col_id]

    in_range = (ridx >= row_lo) & (ridx < row_hi)
    result = exists & in_range & valid
    pre_pred = result
    if sig.apply_preds:
        for i, ps in enumerate(sig.preds):
            lit = pred_literals[i]
            result = result & col_notnull[ps.col_id] & _eval_pred(
                ps, cmp_w.get(ps.col_id), arith_w.get(ps.col_id), ridx, lit)

    return {
        "result": result,
        "pre_pred": pre_pred,
        "start_idx": ridx,
        "num_groups": jnp.int32(N),
        "ridx": ridx,
        "col_idx": col_idx,
        "col_has": col_has,
        "col_notnull": col_notnull,
        "cmp_w": cmp_w,
        "arith_w": arith_w,
    }


def presence_is_packed(sig, run) -> bool:
    """Every presence plane a flat resolve reads of ``run`` — valid,
    tomb, live, each touched column's set and isnull — is a "bits" leaf
    (pytree structure: known where the program is traced). A plain bool
    plane among them (a run uploaded unencoded, the delta overlay's
    masked ``valid``) makes it False."""
    leaves = [run["valid"], run["tomb"], run["live"]]
    for cs in sig.cols:
        c = run["cols"][cs.col_id]
        leaves += [c["set"], c["isnull"]]
    return all(encodings.leaf_kind(leaf) == "bits" for leaf in leaves)


def resolve_flat_packed(sig, run, b0, row_lo, row_hi,
                        read_hi, read_lo, rexp_hi, rexp_lo):
    """``_resolve_flat`` with the presence algebra left on the packed
    words (``presence_is_packed`` runs only), for a consumer XLA cannot
    fuse an unpack into: ops.group_agg's kernel, which takes ONE mask
    word a row. Every ``&``, ``|``, ``~`` of the flat resolve's bit
    planes is done on int32 [K, R // 32] (32 rows an element, no
    relayout); what is per row and no bit plane stays per row. Returns
      exists_w      i32 [K, R // 32]   valid & ~tomb & (live | any column
                                        set and not NULL)
      notnull_w     {col_id: i32 [K, R // 32]}  valid & ~tomb & set & ~isnull
      row_ok        bool [N]  visible at the read point and not expired
      in_range      bool [N]  inside the scan's row bounds
      cmp_w, arith_w          as ``_resolve_flat`` gives them
    with, bit for bit, ``_resolve_flat``'s
      pre_pred        == rows(exists_w) & row_ok & in_range
      col_notnull[c]  == rows(notnull_w[c]) & row_ok
    (every term of ``exists`` holds ``alive & ~expired``, whose per-row
    part is ``row_ok`` and whose packed part ``valid & ~tomb``)."""
    K, R = sig.K, sig.R
    N = K * R

    def ww(leaf):
        return encodings.wwords(leaf, b0, K)

    def wp(leaf):
        return encodings.wplane(leaf, b0, K, R)

    alive_w = ww(run["valid"]) & ~ww(run["tomb"])
    exists_w = alive_w & ww(run["live"])
    notnull_w = {}
    cmp_w = {}
    arith_w = {}
    for cs in sig.cols:
        c = run["cols"][cs.col_id]
        notnull_w[cs.col_id] = alive_w & ww(c["set"]) & ~ww(c["isnull"])
        exists_w = exists_w | notnull_w[cs.col_id]
        cmp_w[cs.col_id] = wp(c["cmp"])
        if "arith" in c:
            arith_w[cs.col_id] = wp(c["arith"])

    ridx = jnp.arange(N, dtype=jnp.int32)
    visible = le2(wp(run["ht_hi"]), wp(run["ht_lo"]), read_hi, read_lo)
    expired = le2(wp(run["exp_hi"]), wp(run["exp_lo"]), rexp_hi, rexp_lo)
    return {
        "exists_w": exists_w,
        "notnull_w": notnull_w,
        "row_ok": visible & ~expired,
        "in_range": (ridx >= row_lo) & (ridx < row_hi),
        "cmp_w": cmp_w,
        "arith_w": arith_w,
    }


def scan_window(sig: ScanSig, run, b0, row_lo, row_hi,
                read_hi, read_lo, rexp_hi, rexp_lo, pred_literals):
    """The traced scan program. ``run`` is the device-array pytree
    (ops.device_run.DeviceRun.arrays); scalars are traced.

    Returns a dict:
      row scans:  result[N] bool (per group id), start_idx[N] i32,
                  num_groups i32
      aggregates: additionally 'agg<i>_*' partials per AggSig.
    """
    K, R = sig.K, sig.R
    N = K * R
    r = resolve_window(sig, run, b0, row_lo, row_hi,
                       read_hi, read_lo, rexp_hi, rexp_lo, pred_literals)
    result, start_idx = r["result"], r["start_idx"]
    out = {"result": result, "start_idx": start_idx,
           "num_groups": r["num_groups"]}

    # 8. Aggregate partials.
    block_of_group = start_idx // R  # in [0, K)
    for i, ag in enumerate(sig.aggs):
        out.update(_eval_agg(f"agg{i}", ag, result, r["col_idx"], r["col_has"],
                             r["col_notnull"], r["cmp_w"], r["arith_w"],
                             block_of_group, K, N))
    return out


def _eval_pred(ps: PredSig, cmp, arith, idx, lit):
    """Predicate mask over merged values. For 'str' AND 'f32', a SUPERSET
    mask (ties count as maybe-match; the host verifies): f32 rounding is
    monotone but not injective, so equal-after-rounding comparisons are
    ambiguous just like equal string prefixes."""
    if ps.kind == "f32":
        v = arith[idx]
        x = lit
        eq = v == x
        return {"=": eq, "!=": jnp.ones_like(eq),
                "<": v <= x, "<=": v <= x,
                ">": v >= x, ">=": v >= x}[ps.op]
    if ps.kind == "i32":
        v = cmp[idx, 0]
        x = lit
        return {"=": v == x, "!=": v != x, "<": v < x, "<=": v <= x,
                ">": v > x, ">=": v >= x}[ps.op]
    if ps.kind == "code":
        # Promoted string predicate on a dictionary-encoded column: the
        # sorted dict makes code order == value order, so the host
        # translated the literal to an int32 code bound and the compare
        # is EXACT (no superset verify) on the decoded code plane.
        v = cmp[idx, 2]
        x = lit
        return {"=": v == x, "!=": v != x, "<": v < x, "<=": v <= x,
                ">": v > x, ">=": v >= x}[ps.op]
    hi, lo = cmp[idx, 0], cmp[idx, 1]
    lhi, llo = lit[0], lit[1]
    eq = eq2(hi, lo, lhi, llo)
    lt = lt2(hi, lo, lhi, llo)
    if ps.kind in ("i64", "f64"):
        return {"=": eq, "!=": ~eq, "<": lt, "<=": lt | eq,
                ">": ~(lt | eq), ">=": ~lt}[ps.op]
    # strings: plane-equality is ambiguous -> superset semantics
    return {
        "=": eq,                # equal strings always plane-equal
        "!=": jnp.ones_like(eq),  # plane-diff => ne true; plane-eq => maybe
        "<": lt | eq,
        "<=": lt | eq,
        ">": ~lt,               # gt or plane-eq(maybe)
        ">=": ~lt,
    }[ps.op]


def _eval_agg(name, ag: AggSig, result, col_idx, col_has, col_notnull,
              cmp_w, arith_w, block_of_group, K, N):
    out = {}
    if ag.fn == "count":
        mask = result if ag.col_id is None else (result & col_notnull[ag.col_id])
        out[f"{name}_count"] = jnp.sum(mask.astype(jnp.int32))
        return out
    mask = result & col_notnull[ag.col_id]
    idx = col_idx[ag.col_id]
    if ag.fn == "sum":
        if ag.kind in ("f32", "f64"):
            v = jnp.where(mask, arith_w[ag.col_id][idx], jnp.float32(0))
            out[f"{name}_fsum"] = _seg_sum(v, block_of_group, K)
            out[f"{name}_n"] = jnp.sum(mask.astype(jnp.int32))
        elif ag.kind == "i32":
            u = _u32(cmp_w[ag.col_id][idx, 0]) ^ jnp.uint32(0x80000000)
            l0 = jnp.where(mask, (u & jnp.uint32(0xFFFF)).astype(jnp.int32), 0)
            l1 = jnp.where(mask, (u >> jnp.uint32(16)).astype(jnp.int32), 0)
            zeros = jnp.zeros_like(l0)
            limbs = jnp.stack([l0, l1, zeros, zeros], axis=-1)
            out[f"{name}_limbs"] = _seg_sum(limbs, block_of_group, K)
            out[f"{name}_n"] = jnp.sum(mask.astype(jnp.int32))
        else:  # i64
            hi_u = _u32(cmp_w[ag.col_id][idx, 0]) ^ jnp.uint32(0x80000000)
            lo_u = _u32(cmp_w[ag.col_id][idx, 1]) ^ jnp.uint32(0x80000000)
            l0, l1, l2, l3 = _limbs16(lo_u, hi_u)
            limbs = jnp.stack([jnp.where(mask, l, 0) for l in (l0, l1, l2, l3)],
                              axis=-1)
            out[f"{name}_limbs"] = _seg_sum(limbs, block_of_group, K)
            out[f"{name}_n"] = jnp.sum(mask.astype(jnp.int32))
        return out
    # min / max on ordered planes (exact); f32 on the arith plane.
    # (No sign-negation trick: -I32_MIN overflows int32.)
    is_max = ag.fn == "max"
    red = jnp.max if is_max else jnp.min
    if ag.kind == "f32":
        v = arith_w[ag.col_id][idx]
        fill = -jnp.inf if is_max else jnp.inf
        out[f"{name}_fext"] = red(jnp.where(mask, v, fill))
        out[f"{name}_n"] = jnp.sum(mask.astype(jnp.int32))
        return out
    ifill = I32_MIN if is_max else I32_MAX
    if ag.kind == "i32":
        v = cmp_w[ag.col_id][idx, 0]
        out[f"{name}_ext"] = red(jnp.where(mask, v, ifill))
        out[f"{name}_n"] = jnp.sum(mask.astype(jnp.int32))
        return out
    hi, lo = cmp_w[ag.col_id][idx, 0], cmp_w[ag.col_id][idx, 1]
    mhi = red(jnp.where(mask, hi, ifill))
    tie = mask & (hi == mhi)
    mlo = red(jnp.where(tie, lo, ifill))
    out[f"{name}_ext_hi"] = mhi
    out[f"{name}_ext_lo"] = mlo
    out[f"{name}_n"] = jnp.sum(mask.astype(jnp.int32))
    return out


@functools.lru_cache(maxsize=256)
@compile_contract("scan_window", max_compiles=256)
def compiled_scan(sig: ScanSig):
    """One compiled XLA program per static scan signature."""
    fn = functools.partial(scan_window, sig)
    return jitting.jit(fn, "scan_window", sig.tag())
