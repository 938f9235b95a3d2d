"""Device GROUP BY aggregates: bucket-hashed segmented reduction.

The TPC-H Q1 shape — few, low-cardinality groups over millions of rows —
runs as ONE device dispatch: a fori_loop over block windows resolves MVCC
visibility + predicates (ops.scan.resolve_window), hashes each row's
group-key planes into a fixed bucket table, and segment-sums exact
integer digit vectors per bucket. The host decodes buckets back to group
values through a representative row.

Exactness machinery:
- group keys hash over the columns' cmp planes (+ a null plane). A
  bucket also accumulates the min and max of every key plane; the host
  verifies min == max per live bucket — a hash collision (different
  groups, one bucket) fails that check and the scan falls back to the
  host path (retry-with-salt left for later; collisions are vanishingly
  rare with NB >= 16x groups). Varlen group columns are exact only when
  their values fit the 8-byte device prefix — the engine checks the
  run's recorded max length before choosing this path.
- integer sums (including product expressions like
  sum(price * (100 - disc) * (100 + tax)) over scaled-integer money
  columns) evaluate per row in base-2^16 digit vectors: the wide column
  splits into digits, each small factor (statically bounded < 2^14,
  non-negative) multiplies the digit vector with an elementwise carry
  chain, digits segment-sum per bucket, and a per-window carry
  normalization keeps everything inside int32 — bit-exact at any scale
  (the same discipline as ops.agg_fold's limb sums).

Reference analog: the grouped aggregate evaluation the reference runs
row-at-a-time inside the scan (PgsqlReadOperation::EvalAggregate,
src/yb/docdb/pgsql_operation.cc:473) — vectorized per window here.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax import lax

from yugabyte_db_tpu.ops.scan import I32_MAX, I32_MIN, resolve_window
from yugabyte_db_tpu.utils import jitting
from yugabyte_db_tpu.utils.jitting import compile_contract

NUM_BUCKETS = 512
DIGITS = 8            # base-2^16 digits per integer accumulator (2^128 cap)

# factor-expression opcodes (static tuples, traced evaluation)
#   ("k", const) | ("c", col_id) | ("+"|"-"|"*", left, right)


@dataclass(frozen=True)
class GAgg:
    kind: str            # 'count' | 'sum_int' | 'sum_prod'
    col_id: int | None   # sum_int: the column; sum_prod: the wide base
    planes: int = 1      # base column plane count (1=i32, 2=i64)
    factors: tuple = ()  # sum_prod: tuple of factor expression tuples
    need_cols: tuple = ()  # col_ids whose notnull gates the row


@dataclass(frozen=True)
class GroupAggSig:
    B: int
    R: int
    K: int
    NB: int
    cols: tuple          # tuple[ColSig] — everything resolve touches
    preds: tuple
    apply_preds: bool
    flat: bool
    group_cols: tuple    # tuple[(col_id, planes)]
    aggs: tuple          # tuple[GAgg]

    def tag(self) -> str:
        """What the query decides of the program, for its name
        (utils.jitting.tag): TPC-H Q1 is ``g2a8p1f1_...``, Q6
        ``g0a1p4f1_...``, whatever the run's size."""
        return jitting.tag(groups=self.group_cols, aggs=self.aggs,
                           preds=self.preds, flat=self.flat)


def _eval_factor(expr, cmp_w, idx, flat):
    """Trace a small-factor expression to a per-row int32 vector."""
    op = expr[0]
    if op == "k":
        return jnp.int32(expr[1])
    if op == "c":
        col = cmp_w[expr[1]]
        v = col[:, 0] if flat else col[idx[expr[1]], 0]
        return v
    left = _eval_factor(expr[1], cmp_w, idx, flat)
    right = _eval_factor(expr[2], cmp_w, idx, flat)
    if op == "+":
        return left + right
    if op == "-":
        return left - right
    return left * right


def _digits_mul(digits: list, f):
    """Multiply a base-2^16 digit vector by a small non-negative factor,
    renormalizing with an elementwise carry chain."""
    out = []
    carry = jnp.int32(0)
    for d in digits:
        t = d * f + carry
        out.append(t & jnp.int32(0xFFFF))
        carry = t >> jnp.int32(16)
    out.append(carry)  # f < 2^14 and digits < 2^16: one extra digit
    return out[:DIGITS]


def _base_digits(sig_planes, cmp, idx, flat):
    """Wide base column -> (digit list, value-negative flag per row)."""
    if sig_planes == 1:
        v = cmp[:, 0] if flat else cmp[idx, 0]
        neg = v < 0
        d0 = v & jnp.int32(0xFFFF)
        d1 = (v >> jnp.int32(16)) & jnp.int32(0x7FFF)
        return [d0, d1], neg
    hi = cmp[:, 0] if flat else cmp[idx, 0]
    lo = cmp[:, 1] if flat else cmp[idx, 1]
    # ordered planes: u64 = v ^ 2^63 with both words bias-flipped
    hi_u = (hi.view(jnp.uint32) ^ jnp.uint32(0x80000000)).view(jnp.int32)
    lo_u = (lo.view(jnp.uint32) ^ jnp.uint32(0x80000000)).view(jnp.int32)
    # v >= 0  <=>  top bit of u64 set  <=>  hi_u (as i32) < 0
    neg = hi_u >= 0
    v_hi = hi_u & jnp.int32(0x7FFFFFFF)  # strip the sign-bias bit
    d0 = lo_u & jnp.int32(0xFFFF)
    d1 = (lo_u >> jnp.int32(16)) & jnp.int32(0xFFFF)
    d2 = v_hi & jnp.int32(0xFFFF)
    d3 = (v_hi >> jnp.int32(16)) & jnp.int32(0x7FFF)
    return [d0, d1, d2, d3], neg


def _carry_norm(acc):
    """Carry-normalize a [NB, DIGITS] accumulator after one window."""
    for _ in range(2):
        lo = acc & jnp.int32(0xFFFF)
        hi = acc >> jnp.int32(16)
        acc = lo + jnp.concatenate(
            [jnp.zeros_like(hi[:, :1]), hi[:, :-1]], axis=1)
    return acc


def grouped_aggregate(sig: GroupAggSig, run, iparams, fparams):
    """Traced program: one dispatch over [w_first, w_last] windows.

    iparams layout: [w_first, w_last, row_lo, row_hi, r_hi, r_lo,
                     e_hi, e_lo, scan_from, *int predicate literals]
    (the row_gather params layout — reuses pack_params).

    Returns a dict of arrays keyed per output (fetched in one transfer):
      count[NB] i32, rep[NB] i32 (min matching global row, I32_MAX if
      none), keymin/keymax[NB, KP] i32 (collision check), scanned i32,
      negs i32 (any negative base seen — host falls back), and per agg
      a<i>[NB, DIGITS] i32 digit sums (count aggs: a<i>[NB] i32).
    """
    from yugabyte_db_tpu.ops.row_gather import _unpack_literals

    K, R, NB = sig.K, sig.R, sig.NB
    N = K * R
    w_first, w_last = iparams[0], iparams[1]
    row_lo, row_hi = iparams[2], iparams[3]
    read = (iparams[4], iparams[5], iparams[6], iparams[7])
    pred_literals = _unpack_literals(sig, iparams, fparams)

    KP = max(1, sum(p + 1 for _c, p in sig.group_cols))  # planes+null/col

    NBP = NB + 1  # one trash segment for non-matching rows

    def init_acc():
        acc = {
            "count": jnp.zeros((NBP,), jnp.int32),
            "rep": jnp.full((NBP,), I32_MAX, jnp.int32),
            "keymin": jnp.full((NBP, KP), I32_MAX, jnp.int32),
            "keymax": jnp.full((NBP, KP), I32_MIN, jnp.int32),
            "scanned": jnp.int32(0),
            "negs": jnp.int32(0),
        }
        for i, ag in enumerate(sig.aggs):
            if ag.kind == "count":
                acc[f"a{i}"] = jnp.zeros((NBP,), jnp.int32)
            else:
                acc[f"a{i}"] = jnp.zeros((NBP, DIGITS), jnp.int32)
                # non-null input count: SQL sum over zero inputs is NULL,
                # which a zero digit vector alone cannot distinguish.
                acc[f"n{i}"] = jnp.zeros((NBP,), jnp.int32)
        return acc

    def seg(vals, bucket, red="sum"):
        fn = {"sum": jax.ops.segment_sum, "min": jax.ops.segment_min,
              "max": jax.ops.segment_max}[red]
        return fn(vals, bucket, num_segments=NBP)

    def body(w, acc):
        b0 = w * K
        base = b0 * R
        r = resolve_window(sig, run, b0, row_lo - base, row_hi - base,
                           *read, pred_literals)
        gvalid = r["ridx"] < r["num_groups"]
        m = r["result"] & gvalid
        cmp_w = r["cmp_w"]
        col_idx = r["col_idx"]
        col_notnull = r["col_notnull"]

        # group key planes (+ null flags) and FNV-ish bucket hash
        planes = []
        h = jnp.full((N,), 0x01000193, jnp.int32)
        for cid, np_ in sig.group_cols:
            idx = col_idx[cid]
            nn = col_notnull[cid]
            for pi in range(np_):
                p = (cmp_w[cid][:, pi] if sig.flat
                     else cmp_w[cid][idx, pi])
                p = jnp.where(nn, p, jnp.int32(0))
                planes.append(p)
                h = (h ^ p) * jnp.int32(-2128831035)
            nulls = (~nn).astype(jnp.int32)
            planes.append(nulls)
            h = (h ^ nulls) * jnp.int32(-2128831035)
        # Avalanche: mod-2^32 multiplies only push bits UP, so values
        # differing in high bits alone (e.g. short string prefixes) would
        # share the low-bit bucket; fold the high bits back down
        # (murmur3 fmix shape).
        h = h ^ ((h >> jnp.int32(16)) & jnp.int32(0xFFFF))
        h = h * jnp.int32(-2048144789)
        h = h ^ ((h >> jnp.int32(13)) & jnp.int32(0x7FFFF))
        bucket = jnp.where(m, (h & jnp.int32(0x7FFFFFFF)) % NB, NB)

        acc = dict(acc)
        acc["count"] = acc["count"] + seg(m.astype(jnp.int32), bucket)
        acc["rep"] = jnp.minimum(
            acc["rep"], seg(jnp.where(m, base + r["start_idx"], I32_MAX),
                            bucket, red="min"))
        if planes:
            key = jnp.stack(planes, axis=1)  # [N, KP]
            acc["keymin"] = jnp.minimum(
                acc["keymin"], seg(jnp.where(m[:, None], key, I32_MAX),
                                   bucket, red="min"))
            acc["keymax"] = jnp.maximum(
                acc["keymax"], seg(jnp.where(m[:, None], key, I32_MIN),
                                   bucket, red="max"))
        acc["scanned"] = acc["scanned"] + jnp.sum(
            (r["pre_pred"] & gvalid).astype(jnp.int32))

        for i, ag in enumerate(sig.aggs):
            if ag.kind == "count":
                mask = m
                if ag.col_id is not None:
                    mask = mask & col_notnull[ag.col_id]
                acc[f"a{i}"] = acc[f"a{i}"] + seg(mask.astype(jnp.int32),
                                                  bucket)
                continue
            mask = m
            for cid in ag.need_cols:
                mask = mask & col_notnull[cid]
            acc[f"n{i}"] = acc[f"n{i}"] + seg(mask.astype(jnp.int32),
                                              bucket)
            digits, neg = _base_digits(
                ag.planes, cmp_w[ag.col_id],
                None if sig.flat else col_idx[ag.col_id], sig.flat)
            acc["negs"] = acc["negs"] + jnp.sum(
                (mask & neg).astype(jnp.int32))
            for fx in ag.factors:
                f = _eval_factor(fx, cmp_w,
                                 None if sig.flat else col_idx, sig.flat)
                # Factors are statically bounded |f| < 2^14 but may still
                # be negative at runtime (dtype ranges are conservative);
                # a negative factor invalidates the digit math — counted
                # here, and the host falls back when any were seen.
                acc["negs"] = acc["negs"] + jnp.sum(
                    (mask & (f < 0)).astype(jnp.int32))
                digits = _digits_mul(digits, f)
            dg = jnp.stack(
                digits + [jnp.zeros_like(digits[0])] *
                (DIGITS - len(digits)), axis=1)
            dg = jnp.where(mask[:, None], dg, 0)
            acc[f"a{i}"] = _carry_norm(acc[f"a{i}"] + seg(dg, bucket))
        return acc

    return lax.fori_loop(w_first, w_last + 1, body, init_acc())


@functools.lru_cache(maxsize=64)
@compile_contract("grouped_aggregate", max_compiles=64)
def compiled_grouped(sig: GroupAggSig):
    return jitting.jit(functools.partial(grouped_aggregate, sig),
                       "grouped_aggregate", sig.tag())
