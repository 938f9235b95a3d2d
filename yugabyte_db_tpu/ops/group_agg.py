"""Device GROUP BY aggregates: bucket-hashed dense reduction.

The TPC-H Q1 shape — few, low-cardinality groups over millions of rows —
runs as ONE device dispatch: a fori_loop over windows (one window for a
run of up to a million rows, ``window_blocks``) resolves MVCC visibility
(ops.scan.resolve_window), applies the predicates, hashes each row's
group-key planes into a fixed bucket table, and reduces the window into
the buckets without a scatter (XLA serializes a TPU scatter: 9 ns a row;
the twelve of Q1's old program were 71 of its 82 ms):

- sums and counts: the bucket one-hot ``[NB, N]`` (bucket == iota) times
  ONE stacked matrix ``[C, N]`` of everything a bucket sums — the 0/1
  masks of ``count`` / ``n<i>`` / count aggregates, every sum's masked
  base-2^16 digits cut into 7-bit pieces, the key planes' pieces — as
  one ``dot_general`` on the MXU: int8 operands (0..127 and 0/1 are
  exact), int32 accumulation, exact while K * R * 127 < 2^30
  (``check_window_bound``, asserted where the program is built). The
  piece sums recombine into the ``[NB, DIGITS]`` int32 accumulators and
  a per-window carry normalization keeps them inside int32 at any scale
  (the same discipline as ops.agg_fold's limb sums);
- ``rep`` (a bucket's first matching row, through which the host decodes
  string groups): a masked minimum over the same one-hot on the VPU;
- collisions: a bucket keeps ONE key, taken when its first rows arrive
  (their key pieces sum to count x piece when they agree). Every
  matching row's bucket key comes back through a second small one-hot
  product (one non-zero term a row) and rows whose own key differs are
  counted in ``collisions``; the host falls back to its row scan when
  that is non-zero (retry-with-salt left for later; collisions are
  vanishingly rare with NB >= 16x groups). Varlen group columns are
  exact only when their values fit the 8-byte device prefix — the engine
  checks the run's recorded max length before choosing this path;
- no group column (Q6, every ungrouped expression sum): no hash, no
  one-hot, no buckets — ``jnp.sum`` / ``jnp.min`` of the same columns
  over the rows, into bucket 0 of the same outputs. The signature
  decides, nothing else.

Integer sums (including product expressions like
sum(price * (100 - disc) * (100 + tax)) over scaled-integer money
columns) evaluate per row in base-2^16 digit vectors: the wide column
splits into digits and each small factor (statically bounded < 2^14,
non-negative) multiplies the digit vector with an elementwise carry
chain. A negative base or factor invalidates the digits: such rows are
counted in ``negs`` and the host falls back. Every fallback is counted
(``yb_grouped_agg_fallbacks{reason}``, storage/tpu_engine.py).

Reference analog: the grouped aggregate evaluation the reference runs
row-at-a-time inside the scan (PgsqlReadOperation::EvalAggregate,
src/yb/docdb/pgsql_operation.cc:473) — vectorized per window here.
"""

from __future__ import annotations

import dataclasses
import functools
import math
from dataclasses import dataclass

import jax.numpy as jnp
import numpy as np
from jax import lax

from yugabyte_db_tpu.ops.scan import I32_MAX, _eval_pred, resolve_window
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
    """Carry-normalize a [NA, DIGITS] accumulator after one window."""
    for _ in range(2):
        lo = acc & jnp.int32(0xFFFF)
        hi = acc >> jnp.int32(16)
        acc = lo + jnp.concatenate(
            [jnp.zeros_like(hi[:, :1]), hi[:, :-1]], axis=1)
    return acc


# What a window sums is cut into 7-bit pieces: 0..127 and the 0/1 of a
# mask or of the bucket one-hot are exact in int8, the MXU accumulates
# int8 products in int32, and a bucket's sum of K * R pieces stays under
# 2^30 (check_window_bound), with room for what _add_digit_sums adds.

def _digit_pieces(d):
    """A base-2^16 digit -> its three 7-bit pieces (the last 2 bits)."""
    return [d & jnp.int32(0x7F), (d >> jnp.int32(7)) & jnp.int32(0x7F),
            d >> jnp.int32(14)]


def _plane_pieces(p):
    """An int32 key plane -> its five 7-bit pieces (the last 4 bits)."""
    return [(p >> jnp.int32(7 * j)) & jnp.int32(0x7F) for j in range(4)] \
        + [(p >> jnp.int32(28)) & jnp.int32(0xF)]


def _plane_of_pieces(q):
    """[..., 5] pieces -> the int32 plane (the top piece wraps into the
    sign, as it came)."""
    return functools.reduce(
        jnp.bitwise_or, [q[..., j] << jnp.int32(7 * j) for j in range(5)])


def _add_digit_sums(acc, s):
    """acc[NA, DIGITS] += the window's sums of L digits, given as the
    sums s[NA, 3L] of their pieces (_digit_pieces' order). What stays
    under 2^16 of each piece's weight goes to digit k, the rest carries
    to digit k+1; the carry out of the last digit drops (the 2^128 cap)."""
    s = s.reshape(s.shape[0], -1, 3)
    s0, s1, s2 = s[..., 0], s[..., 1], s[..., 2]
    low = s0 + ((s1 & jnp.int32(0x1FF)) << jnp.int32(7)) \
        + ((s2 & jnp.int32(3)) << jnp.int32(14))
    carry = (s1 >> jnp.int32(9)) + (s2 >> jnp.int32(2))
    L = low.shape[1]
    carry = carry[:, :min(L, DIGITS - 1)]
    add = jnp.pad(low, ((0, 0), (0, DIGITS - L))) + jnp.pad(
        carry, ((0, 0), (1, DIGITS - 1 - carry.shape[1])))
    return _carry_norm(acc + add)


# Rows a run's window may hold. A window is a loop iteration, and an
# iteration is some hundreds of device ops whatever its size, so the
# fewer the better: 46 windows of 16,384 rows made Q1's and Q6's programs
# 14.8K and 5.6K ops a call, and a profiler's buffer held 4.8 s of them.
# The cap bounds the program's temporaries (a few hundred bytes a row).
MAX_WINDOW_ROWS = 1 << 20


@functools.lru_cache(maxsize=256)
def window_blocks(B: int, R: int) -> int:
    """Blocks a window of a ``B``-block run takes: the largest divisor of
    ``B`` within MAX_WINDOW_ROWS (one window for a run of up to a million
    rows), so that the last window never reaches past the run."""
    return max(k for k in range(1, B + 1)
               if B % k == 0 and (k == 1 or k * R <= MAX_WINDOW_ROWS))


def check_window_bound(sig: GroupAggSig) -> None:
    """Exactness of one window's reduction, from the signature alone: a
    bucket sums at most K * R pieces of at most 127 in int32, and the
    sum must leave room for the accumulator it is added to."""
    if sig.K * sig.R * 127 >= 1 << 30:
        raise ValueError(
            f"rows_per_block={sig.R} x window_blocks={sig.K}: a bucket's "
            f"sum of 7-bit pieces ({sig.K * sig.R} x 127) must stay under "
            "2^30 to be exact in int32; shrink one")


def _bucket_hash(planes, n):
    """FNV-ish hash of the key planes, folded to a non-negative int32."""
    h = jnp.full((n,), 0x01000193, jnp.int32)
    for p in planes:
        h = (h ^ p) * jnp.int32(-2128831035)
    # Avalanche: mod-2^32 multiplies only push bits UP, so values
    # differing in high bits alone (e.g. short string prefixes) would
    # share the low-bit bucket; fold the high bits back down
    # (murmur3 fmix shape).
    h = h ^ ((h >> jnp.int32(16)) & jnp.int32(0xFFFF))
    h = h * jnp.int32(-2048144789)
    h = h ^ ((h >> jnp.int32(13)) & jnp.int32(0x7FFFF))
    return h & jnp.int32(0x7FFFFFFF)


def _int8_dot(a, b, axis_a, axis_b):
    """int8 x int8 -> int32 product, contracting one axis of each."""
    return lax.dot_general(a.astype(jnp.int8), b.astype(jnp.int8),
                           (((axis_a,), (axis_b,)), ((), ())),
                           preferred_element_type=jnp.int32)


def _key_planes(sig: GroupAggSig) -> int:
    """Width of ``key``: each group column's planes and its null flag."""
    return max(1, sum(p + 1 for _c, p in sig.group_cols))


def grouped_aggregate(sig: GroupAggSig, run, iparams, fparams):
    """Traced program: one dispatch over [w_first, w_last] windows.

    iparams layout: [w_first, w_last, row_lo, row_hi, r_hi, r_lo,
                     e_hi, e_lo, scan_from, *int predicate literals]
    (the row_gather params layout — reuses pack_params).

    Returns a dict of arrays keyed per output (``out_layout`` has the
    shapes; ``compiled_grouped`` packs them into one vector):
      count[NB] i32, rep[NB] i32 (min matching global row, I32_MAX if
      none), key[NB, KP] i32 (the key planes of the bucket's rows),
      collisions i32 (matching rows whose key differs from their
      bucket's — host falls back), scanned i32, negs i32 (rows with a
      negative base or factor — host falls back), and per agg
      a<i>[NB, DIGITS] i32 digit sums with n<i>[NB] i32 non-null inputs
      (count aggs: a<i>[NB] i32). With no group column everything is in
      bucket 0.
    """
    from yugabyte_db_tpu.ops.row_gather import _unpack_literals

    K, R, NB = sig.K, sig.R, sig.NB
    N = K * R
    w_first, w_last = iparams[0], iparams[1]
    row_lo, row_hi = iparams[2], iparams[3]
    read = (iparams[4], iparams[5], iparams[6], iparams[7])
    pred_literals = _unpack_literals(sig, iparams, fparams)

    unfiltered = dataclasses.replace(sig, apply_preds=False)
    KP = _key_planes(sig)
    NA = NB if sig.group_cols else 1   # buckets the loop accumulates

    def init_acc():
        acc = {
            "count": jnp.zeros((NA,), jnp.int32),
            "rep": jnp.full((NA,), I32_MAX, jnp.int32),
            "key": jnp.zeros((NA, KP), jnp.int32),
            "collisions": jnp.int32(0),
            "scanned": jnp.int32(0),
            "negs": jnp.int32(0),
        }
        for i, ag in enumerate(sig.aggs):
            if ag.kind == "count":
                acc[f"a{i}"] = jnp.zeros((NA,), jnp.int32)
            else:
                acc[f"a{i}"] = jnp.zeros((NA, DIGITS), jnp.int32)
                # non-null input count: SQL sum over zero inputs is NULL,
                # which a zero digit vector alone cannot distinguish.
                acc[f"n{i}"] = jnp.zeros((NA,), jnp.int32)
        return acc

    def body(w, acc):
        b0 = w * K
        base = b0 * R
        r = resolve_window(unfiltered, run, b0, row_lo - base,
                           row_hi - base, *read, pred_literals)
        gvalid = r["ridx"] < r["num_groups"]
        cmp_w = r["cmp_w"]
        col_idx = r["col_idx"]
        col_notnull = r["col_notnull"]
        # The predicates, on the window's planes themselves where every
        # row is its own group: resolve_window would index them through
        # col_idx, and a gather by arange is still a gather on the TPU
        # (117 us a predicate column a window of 16,384 rows on the v5e).
        m = r["pre_pred"] & gvalid
        if sig.apply_preds:
            for ps, lit in zip(sig.preds, pred_literals):
                m = m & col_notnull[ps.col_id] & _eval_pred(
                    ps, cmp_w.get(ps.col_id), r["arith_w"].get(ps.col_id),
                    slice(None) if sig.flat else col_idx[ps.col_id], lit)
        rowid = base + r["start_idx"]

        # group key planes (+ null flags)
        planes = []
        for cid, np_ in sig.group_cols:
            idx = col_idx[cid]
            nn = col_notnull[cid]
            for pi in range(np_):
                p = (cmp_w[cid][:, pi] if sig.flat
                     else cmp_w[cid][idx, pi])
                planes.append(jnp.where(nn, p, jnp.int32(0)))
            planes.append((~nn).astype(jnp.int32))

        # Everything a bucket sums, as columns of 0..127: the 0/1 masks,
        # the masked digit vectors' pieces, the key planes' pieces.
        cols = [m.astype(jnp.int32)]
        mask_at = {"count": 0}      # output -> its column
        digits_at = {}              # output -> its pieces' columns
        bad = jnp.zeros((N,), jnp.bool_)
        for i, ag in enumerate(sig.aggs):
            if ag.kind == "count":
                mask_at[f"a{i}"] = len(cols)
                cols.append((m if ag.col_id is None
                             else m & col_notnull[ag.col_id]
                             ).astype(jnp.int32))
                continue
            mask = m
            for cid in ag.need_cols:
                mask = mask & col_notnull[cid]
            mask_at[f"n{i}"] = len(cols)
            cols.append(mask.astype(jnp.int32))
            digits, neg = _base_digits(
                ag.planes, cmp_w[ag.col_id],
                None if sig.flat else col_idx[ag.col_id], sig.flat)
            bad = bad | (mask & neg)
            for fx in ag.factors:
                f = _eval_factor(fx, cmp_w,
                                 None if sig.flat else col_idx, sig.flat)
                # Factors are statically bounded |f| < 2^14 but may still
                # be negative at runtime (dtype ranges are conservative);
                # a negative factor invalidates the digit math — counted
                # here, and the host falls back when any were seen.
                bad = bad | (mask & (f < 0))
                digits = _digits_mul(digits, f)
            digits_at[f"a{i}"] = slice(len(cols),
                                       len(cols) + 3 * len(digits))
            for d in digits:
                cols += _digit_pieces(jnp.where(mask, d, 0))
        k0 = len(cols)
        for p in planes:
            cols += _plane_pieces(p)

        new = {
            "scanned": acc["scanned"] + jnp.sum(
                (r["pre_pred"] & gvalid).astype(jnp.int32)),
            "negs": acc["negs"] + jnp.sum(bad.astype(jnp.int32)),
            "key": acc["key"], "collisions": acc["collisions"],
        }
        if sig.group_cols:
            # ONE product of the bucket one-hot with the stacked columns
            # for every per-bucket sum of the window; the bucket's first
            # row as a masked minimum over the same one-hot.
            bucket = jnp.where(m, _bucket_hash(planes, N) % NB, NB)
            onehot = lax.broadcasted_iota(
                jnp.int32, (NB, N), 0) == bucket[None, :]
            mat = jnp.stack(cols)                               # [C, N]
            sums = _int8_dot(onehot, mat, 1, 1)                  # [NB, C]
            rep = jnp.min(jnp.where(onehot, rowid[None, :], I32_MAX),
                          axis=1)
            # The key of a bucket seen for the first time: its rows' key
            # pieces sum to count x piece when they agree (and to
            # anything when they do not: then some row differs from
            # whatever is kept, and is counted).
            cnt = jnp.maximum(sums[:, 0], 1)[:, None]
            first = _plane_of_pieces(
                (sums[:, k0:] // cnt).reshape(NB, KP, 5))
            key = jnp.where((acc["count"] > 0)[:, None], acc["key"], first)
            # Each row's bucket key, back through the one-hot (one
            # non-zero term a row), against the row's own key pieces.
            want = _int8_dot(
                jnp.stack(_plane_pieces(key), axis=-1).reshape(NB, -1),
                onehot, 0, 0)                                   # [5KP, N]
            differs = jnp.any(want != mat[k0:], axis=0)
            new["key"] = key
            new["collisions"] = acc["collisions"] + jnp.sum(
                (m & differs).astype(jnp.int32))
        else:
            # No group column, no buckets: plain reductions over the rows.
            sums = jnp.stack([jnp.sum(c) for c in cols])[None]   # [1, C]
            rep = jnp.min(jnp.where(m, rowid, I32_MAX))[None]

        new["rep"] = jnp.minimum(acc["rep"], rep)
        for name, col in mask_at.items():
            new[name] = acc[name] + sums[:, col]
        for name, pieces in digits_at.items():
            new[name] = _add_digit_sums(acc[name], sums[:, pieces])
        return new

    acc = lax.fori_loop(w_first, w_last + 1, body, init_acc())
    if NA == NB:
        return acc
    # bucket 0 of the outputs every signature has
    return {name: v if v.ndim == 0 else jnp.pad(
        v, ((0, NB - NA),) + ((0, 0),) * (v.ndim - 1),
        constant_values=I32_MAX if name == "rep" else 0)
        for name, v in acc.items()}


# -- the jit boundary: one array in, one array out -----------------------------
# A call into the runtime costs the host a fixed time for every array it
# moves (the upload of a numpy argument, a copy_to_host_async, a buffer
# in device_get), whatever the array's size: the program takes its
# parameters as ONE int32 vector and gives its outputs as ONE.

def int_params(sig: GroupAggSig) -> int:
    """Length of ``iparams`` (row_gather's layout): the packed parameter
    vector holds the bits of ``fparams`` behind it."""
    from yugabyte_db_tpu.ops.row_gather import PARAM_FIXED

    return PARAM_FIXED + sum(
        0 if ps.kind == "f32" else 1 if ps.kind in ("i32", "code") else 2
        for ps in sig.preds)


def pack_params(sig: GroupAggSig, iparams, fparams) -> np.ndarray:
    """Host side: i32[P] and f32[F] -> the program's one i32[P + F]."""
    n = int_params(sig)
    if iparams.size != n:
        raise ValueError(f"{iparams.size} int parameters for a signature "
                         f"of {n}")
    return np.concatenate([iparams, fparams.view(np.int32)])


def out_layout(sig: GroupAggSig) -> dict:
    """{output: (offset, shape)} of the packed result vector, from the
    signature alone, in ``grouped_aggregate``'s documented order."""
    NB = sig.NB
    shapes = {"count": (NB,), "rep": (NB,), "key": (NB, _key_planes(sig)),
              "collisions": (), "scanned": (), "negs": ()}
    for i, ag in enumerate(sig.aggs):
        if ag.kind == "count":
            shapes[f"a{i}"] = (NB,)
        else:
            shapes[f"a{i}"] = (NB, DIGITS)
            shapes[f"n{i}"] = (NB,)
    layout, off = {}, 0
    for name, shape in shapes.items():
        layout[name] = (off, shape)
        off += math.prod(shape)
    return layout


def unpack(sig: GroupAggSig, vec) -> dict:
    """A fetched result vector (numpy int32) -> ``grouped_aggregate``'s
    dict, as views of it."""
    return {name: vec[off:off + math.prod(shape)].reshape(shape)
            for name, (off, shape) in out_layout(sig).items()}


def _packed(sig: GroupAggSig, run, params):
    n = int_params(sig)
    out = grouped_aggregate(
        sig, run, params[:n],
        lax.bitcast_convert_type(params[n:], jnp.float32))
    layout = out_layout(sig)
    have = {name: (v.shape, v.dtype) for name, v in out.items()}
    want = {name: (shape, jnp.int32) for name, (_o, shape) in layout.items()}
    if have != want:
        raise AssertionError(f"out_layout is {want}, the program gives "
                             f"{have}")
    return jnp.concatenate([out[name].reshape(-1) for name in layout])


@functools.lru_cache(maxsize=64)
@compile_contract("grouped_aggregate", max_compiles=64)
def compiled_grouped(sig: GroupAggSig):
    """The program of a signature: ``(run arrays, params i32[P + F]) ->
    i32[L]``; ``pack_params`` makes the one, ``unpack`` reads the
    other."""
    check_window_bound(sig)
    return jitting.jit(functools.partial(_packed, sig),
                       "grouped_aggregate", sig.tag())
