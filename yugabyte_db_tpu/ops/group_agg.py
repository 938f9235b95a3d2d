"""Device GROUP BY aggregates: a dense reduction into a bucket table.

The TPC-H Q1 shape — few, low-cardinality groups over millions of rows —
runs as ONE device dispatch: a fori_loop over windows (one window for a
run of up to a million rows, ``window_blocks``) resolves MVCC visibility
(ops.scan.resolve_window or, for a run of few versions a key,
lookback_fold.resolve_window; both decode the run's encoded planes),
applies the predicates, and reduces the window into a fixed bucket table
without a scatter (XLA serializes a TPU scatter: 9 ns a row; the twelve
of Q1's old program were 71 of its 82 ms). The signature decides how,
nothing else:

- with group columns, ONE Pallas kernel a window, gridded over row tiles
  (``_window_kernel``; ``_tile_rows`` rows a step). XLA hands it ten
  int32 vectors a row (the match, the not-null masks and ``scanned`` as
  bits of ONE word, ``_mask_bits``; the planes of the base, factor and
  group columns); what is made per row — the rowid, digits, carries,
  7-bit pieces, key pieces, the bucket, the bucket one-hot — lives in
  VMEM for the length of a tile and never exists in HBM (as XLA ops it
  was an ``[109, N]`` int8 operand, 860 launches and 70 MB of
  temporaries a call of Q1).
  The buckets are addressed in one of two forms, by what the program
  sees of the run it reads (``addressed``: the group columns' leaf kinds
  and shapes; no flag, nothing of a name; the form is not in ``tag()``):
  - *direct*, where EVERY group column's ``cmp`` leaf is a "dict" leaf
    and the product of the dictionaries' caps is within NUM_BUCKETS
    (TPC-H Q1 over a served run: 3 and 2 values, caps 4 and 4): the
    bucket is the mixed-radix number of the columns' codes
    (``_code_bucket``; NULL and unset rows take a dictionary's last,
    absent slot) and the table has the product's buckets, 16 for Q1,
    padded to 128 lanes. Bucket <-> key is a bijection, so the kernel
    keeps no key, no ``rep``, looks no key up and counts no collision
    (``collisions`` is 0 by construction); the group columns' prefix
    planes and their ``jnp.take`` of the dictionary leave the program,
    and the host reads a bucket's values off the run's dictionaries
    (``bucket_codes``);
  - *hashed*, everything else (an integer group column, plain string
    planes, a product of caps over NUM_BUCKETS, a mesh stack: it holds
    no "dict" leaf): ``_bucket_hash`` of the key planes into
    NUM_BUCKETS, a bucket keeps ONE key and every row is checked against
    it, as below. ``yb_grouped_buckets{form}`` counts the dispatches of
    each form.
  The mask word is made in one of two forms, by what the program sees
  where it is traced (the signature and the run's pytree; no flag):
  - *packed* (``_packed_window``), for a flat run whose presence planes
    — valid, tomb, live, every column's set and isnull — are "bits"
    leaves (a run uploaded encoded: the served tables): ``alive & set &
    ~isnull``, ``exists = live | any(not null)`` and the predicates'
    not-null are ``&``, ``|``, ``~`` on the packed words ``[K, R //
    32]`` (ops.scan.resolve_flat_packed), 32 rows an element and no
    relayout; ONE producer lays the word's masks out by rows
    (encodings.rows_of_words) and the per-row parts — the read point
    and the TTL against ``ht`` / ``exp``, the scan's bounds, the
    predicates' compares — clear their bits there. A custom call is a
    consumer XLA fuses no unpack into: by rows, each of the 31 bool
    planes of Q1 was materialised, 0.65 of the program's 2.0 ms on the
    v5e;
  - *by rows* (``_rows_window``), for everything else: a run that is
    not flat (the delta overlay's mini-run), a plain bool plane among
    the presence planes (a device flush's run). Bit for bit the
    same words: the oracle of tests/test_group_agg.py.
  ``yb_grouped_presence{form}`` counts the programs traced in each form.
  A window of a run that is not flat (by rows always, grouped or not)
  merges each key's versions at the read point in one of two forms, by
  what the build recorded of the run (``sig.lookback``, set in
  ``_grouped_prep`` from ``max_group_versions``; no flag; not in
  ``tag()``):
  - *lookback*, where the run's largest key group is within
    lookback_fold.MAX_LOOKBACK (the overlay's mini-run: a deleted row is
    a tombstone over its base row, 2): ``lookback - 1`` static shifts
    along the row axis and elementwise selects
    (lookback_fold.resolve_window, the resolve ops.lookback_fold's
    ungrouped fold runs too). A key group's entry is its first ROW,
    which holds the group's merged planes as values: predicates and
    planes read them as a flat window's, a row is its own rowid, and
    the kernel is handed what a flat window hands it;
  - *segmented*, past the bound: ops.scan.resolve_window's cumsum,
    segment ops and gathers, each serialized on the TPU (72 us apiece
    over 8,192 rows on the v5e, some 60 a call). A key group's entry is
    its NUMBER in the window, its planes are gathered through
    ``col_idx`` and the kernel takes the groups' rowids as one more row.
  Bit for bit the same vector (tests/test_group_agg.py,
  tests/test_grouped_lookback.py). ``yb_grouped_resolve{form}`` counts
  the dispatches of each, and of flat programs.
  In the kernel:
  - sums and counts: the bucket one-hot ``[NB, T]`` (bucket == iota)
    times ONE matrix ``[C, T]`` of everything a bucket sums — the 0/1
    masks of ``count`` / ``n<i>`` / count aggregates, every sum's masked
    base-2^16 digits cut into 7-bit pieces, the key planes' pieces — on
    the MXU: int8 operands (0..127 and 0/1 are exact), int32
    accumulation over the grid, exact while K * R * 127 < 2^30
    (``check_window_bound``, asserted where the program is built). The
    piece sums recombine into the ``[NB, DIGITS]`` int32 accumulators
    and a per-window carry normalization keeps them inside int32 at any
    scale (the same discipline as ops.agg_fold's limb sums);
  - hashed form only: ``rep`` (a bucket's first matching row, through
    which the host decodes string groups) and the bucket's key, taken
    in the tile that first holds a row of the bucket — rows come in
    rowid order — ``rep`` as a masked minimum over the one-hot, the key
    from the tile's sums (its rows' key pieces sum to count x piece
    when they agree);
  - hashed form only: collisions. A bucket keeps ONE key. Every
    matching row looks its bucket's key planes up (a lane gather of a
    128-bucket table) and rows whose own key differs are counted in
    ``collisions``; the host
    falls back to its row scan when that is non-zero
    (retry-with-salt left for later; collisions are vanishingly rare
    with NB >= 16x groups). Varlen group columns are exact only when
    their values fit the 8-byte device prefix — the engine checks the
    run's recorded max length before choosing this path;
  on a backend that is no TPU the same ``pallas_call`` is interpreted, so
  the tests and the CPU rehearsals execute the kernel's own body;
- no group column (Q6, every ungrouped expression sum): no hash, no
  one-hot, no buckets, no kernel — ``jnp.sum`` / ``jnp.min`` of the same
  columns (``_columns``) over the window's rows, into bucket 0 of the
  same outputs. Its window is always resolved by rows: XLA fuses the
  unpacks of the bit planes into the reductions (Q6's whole program is
  0.172 ms on the v5e), and its lowering is kept as it is to the line.

Integer sums (including product expressions like
sum(price * (100 - disc) * (100 + tax)) over scaled-integer money
columns) evaluate per row in base-2^16 digit vectors: the wide column
splits into digits and each small factor (statically bounded < 2^14,
non-negative) multiplies the digit vector with an elementwise carry
chain. A negative base or factor invalidates the digits: such rows are
counted in ``negs`` and the host falls back. Every fallback is counted
(``yb_grouped_agg_fallbacks{reason}``, storage/tpu_engine.py). The same
functions build the columns of a window's ``[N]`` vectors (ungrouped)
and of a tile's ``[S, 128]`` values in the kernel.

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

from yugabyte_db_tpu.ops import encodings, lookback_fold
from yugabyte_db_tpu.ops.scan import (I32_MAX, _eval_pred, presence_is_packed,
                                      resolve_flat_packed, resolve_window)
from yugabyte_db_tpu.utils import jitting, metrics
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
    # The direct form: each group column's dictionary cap (NB is their
    # product). () is the hashed form.
    radix: tuple = ()
    # A bound on the versions of the run's largest key group
    # (lookback_fold.bound of ``max_group_versions``: a power of two up
    # to MAX_LOOKBACK) where the run is not flat: its windows resolve
    # MVCC by that many static shifts. 0: by segment ops (or flat).
    lookback: int = 0

    def tag(self) -> str:
        """What the query decides of the program, for its name
        (utils.jitting.tag): TPC-H Q1 is ``g2a8p1f1_...``, Q6
        ``g0a1p4f1_...``, whatever the run's size, whichever way its
        buckets are addressed and whichever way a window that is not
        flat resolves its versions."""
        return jitting.tag(groups=self.group_cols, aggs=self.aggs,
                           preds=self.preds, flat=self.flat)

    @property
    def resolve_form(self) -> str:
        """How a window merges its versions (``yb_grouped_resolve``)."""
        return ("flat" if self.flat else "lookback" if self.lookback
                else "segmented")

    @property
    def own_rows(self) -> bool:
        """A window's entries are its ROWS, each at its own rowid and
        with its own (merged) planes: the flat and the lookback forms.
        The segmented form's are key groups, numbered along the window,
        that point at rows."""
        return self.resolve_form != "segmented"


def _eval_factor(expr, plane):
    """Trace a small-factor expression to a per-row int32 vector;
    ``plane(col_id, i)`` gives a column's i-th plane of the rows."""
    op = expr[0]
    if op == "k":
        return jnp.int32(expr[1])
    if op == "c":
        return plane(expr[1], 0)
    left = _eval_factor(expr[1], plane)
    right = _eval_factor(expr[2], plane)
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


def _base_digits(col_id, sig_planes, plane):
    """Wide base column -> (digit list, value-negative flag per row)."""
    if sig_planes == 1:
        v = plane(col_id, 0)
        neg = v < 0
        d0 = v & jnp.int32(0xFFFF)
        d1 = (v >> jnp.int32(16)) & jnp.int32(0x7FFF)
        return [d0, d1], neg
    hi = plane(col_id, 0)
    lo = plane(col_id, 1)
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


def _bucket_hash(planes):
    """FNV-ish hash of the key planes, folded to a non-negative int32."""
    h = jnp.full(planes[0].shape, 0x01000193, jnp.int32)
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


def addressed(sig: GroupAggSig, run) -> GroupAggSig:
    """``sig`` with its buckets addressed the way ``run``'s leaves allow
    (pytree structure and shapes; no flag, no name): *direct* where
    every group column's ``cmp`` leaf is a "dict" leaf and the product
    of the dictionaries' caps is within NUM_BUCKETS — the bucket is the
    mixed-radix number of the columns' codes and the table has that
    product's buckets — else *hashed* into NUM_BUCKETS. A signature with
    no group column has no buckets and comes back as it is."""
    if not sig.group_cols:
        return sig
    leaves = [run["cols"][cid]["cmp"] for cid, _planes in sig.group_cols]
    if all(encodings.leaf_kind(leaf) == "dict" for leaf in leaves):
        caps = tuple(int(leaf["dict"]["dhi"].shape[0]) for leaf in leaves)
        if math.prod(caps) <= NUM_BUCKETS:
            return dataclasses.replace(sig, NB=math.prod(caps), radix=caps)
    return dataclasses.replace(sig, NB=NUM_BUCKETS, radix=())


def count_dispatch_forms(sig: GroupAggSig) -> None:
    """One dispatch of ``sig``'s program in ``yb_grouped_resolve{form}``
    and, with group columns, in ``yb_grouped_buckets{form}`` (a
    signature without has no buckets: neither form)."""
    metrics.count_grouped_resolve(sig.resolve_form)
    if sig.group_cols:
        metrics.count_grouped_buckets("direct" if sig.radix else "hashed")


def bucket_codes(sig: GroupAggSig, bucket: int) -> list:
    """Host side, the direct form: a bucket's dictionary code of each
    group column (``_code_bucket``'s digits, the first column the most
    significant). Code ``cap - 1`` is the absent slot: the NULL group."""
    codes = []
    for cap in reversed(sig.radix):
        bucket, code = divmod(bucket, cap)
        codes.append(code)
    return codes[::-1]


def _code_bucket(sig: GroupAggSig, notnull, plane):
    """The rows' bucket in the direct form: distinct keys have distinct
    buckets by construction, NULL included. A dictionary's codes are its
    sorted FULL values' ranks, and its last slot is the absent rows'
    (encodings.dict_leaf); a row whose merged value is unset or NULL
    takes that slot whatever code the version it was read off holds."""
    bucket = None
    for (cid, _planes), cap in zip(sig.group_cols, sig.radix):
        code = jnp.where(notnull[cid], plane(cid, 2), jnp.int32(cap - 1))
        bucket = code if bucket is None else bucket * jnp.int32(cap) + code
    return bucket


def _int8_dot(a, b, axis_a, axis_b):
    """int8 x int8 -> int32 product, contracting one axis of each."""
    return lax.dot_general(a.astype(jnp.int8), b.astype(jnp.int8),
                           (((axis_a,), (axis_b,)), ((), ())),
                           preferred_element_type=jnp.int32)


def _key_planes(sig: GroupAggSig) -> int:
    """Width of ``key``: each group column's planes and its null flag."""
    return max(1, sum(p + 1 for _c, p in sig.group_cols))


def _columns(sig: GroupAggSig, m, notnull, plane):
    """Everything a bucket sums of its rows but their key, as columns of
    0..127: the 0/1 masks and the masked digit vectors' pieces
    (``_column_layout`` says which column is whose). ``m`` is the rows'
    match mask, ``notnull[col_id]`` a column's not-null mask and
    ``plane(col_id, i)`` its i-th plane: a window's ``[N]`` vectors in
    the ungrouped program, a tile's ``[S, 128]`` in the kernel. Returns
    (cols, bad): ``bad`` marks the rows whose digits are invalid."""
    cols = [m.astype(jnp.int32)]
    bad = jnp.zeros(m.shape, jnp.bool_)
    for ag in sig.aggs:
        if ag.kind == "count":
            cols.append((m if ag.col_id is None
                         else m & notnull[ag.col_id]).astype(jnp.int32))
            continue
        mask = m
        for cid in ag.need_cols:
            mask = mask & notnull[cid]
        cols.append(mask.astype(jnp.int32))
        digits, neg = _base_digits(ag.col_id, ag.planes, plane)
        bad = bad | (mask & neg)
        for fx in ag.factors:
            f = _eval_factor(fx, plane)
            # Factors are statically bounded |f| < 2^14 but may still
            # be negative at runtime (dtype ranges are conservative);
            # a negative factor invalidates the digit math — counted
            # here, and the host falls back when any were seen.
            bad = bad | (mask & (f < 0))
            digits = _digits_mul(digits, f)
        for d in digits:
            cols += _digit_pieces(jnp.where(mask, d, 0))
    return cols, bad


def _column_layout(sig: GroupAggSig):
    """``_columns``' list from the signature alone: ({output: its mask
    column}, {output: its digits' pieces' columns}, the column count)."""
    mask_at = {"count": 0}
    digits_at = {}
    n = 1
    for i, ag in enumerate(sig.aggs):
        if ag.kind == "count":
            mask_at[f"a{i}"] = n
            n += 1
            continue
        mask_at[f"n{i}"] = n
        digits = min(DIGITS, (2 if ag.planes == 1 else 4) + len(ag.factors))
        digits_at[f"a{i}"] = slice(n + 1, n + 1 + 3 * digits)
        n += 1 + 3 * digits
    return mask_at, digits_at, n


def _group_planes(sig: GroupAggSig, notnull, plane):
    """The rows' group key: each group column's planes (0 where it is
    null) and its null flag."""
    planes = []
    for cid, np_ in sig.group_cols:
        nn = notnull[cid]
        for pi in range(np_):
            planes.append(jnp.where(nn, plane(cid, pi), jnp.int32(0)))
        planes.append((~nn).astype(jnp.int32))
    return planes


# -- the grouped window: one kernel over row tiles ----------------------------
# What is made per row (digits, carries, pieces, masks, bucket, one-hot)
# lives in VMEM for the length of a tile and goes straight into the MXU;
# per-bucket sums, ``rep``, the key and two counts leave the kernel.

def _factor_cols(expr):
    if expr[0] == "k":
        return []
    if expr[0] == "c":
        return [expr[1]]
    return _factor_cols(expr[1]) + _factor_cols(expr[2])


def _kernel_rows(sig: GroupAggSig):
    """What the XLA prologue hands the kernel of every row, from the
    signature: (the columns whose not-null masks follow the match mask
    as bits of the mask words, the (col_id, plane) vectors). Of a group
    column the direct form takes the code (a "dict" leaf's third plane,
    encodings.wplane) and not the prefix planes: their ``jnp.take`` of
    the dictionary leaves the program."""
    notnull, planes = {}, {}
    for cid, np_ in sig.group_cols:
        notnull[cid] = None
        planes.update({(cid, i): None
                       for i in ((2,) if sig.radix else range(np_))})
    for ag in sig.aggs:
        if ag.kind == "count":
            if ag.col_id is not None:
                notnull[ag.col_id] = None
            continue
        notnull.update({cid: None for cid in ag.need_cols})
        planes.update({(ag.col_id, i): None for i in range(ag.planes)})
        planes.update({(cid, 0): None
                       for fx in ag.factors for cid in _factor_cols(fx)})
    return tuple(notnull), tuple(planes)


def _mask_bits(sig: GroupAggSig):
    """The masks a row's mask words hold, mask j in bit j % 32 of word
    j // 32: the match mask, the not-null masks of ``_kernel_rows``'
    columns, and last the row's ``scanned`` mask (exists and in range,
    before the predicates: the kernel counts it off the word it reads
    anyway; a reduction of XLA's beside the kernel has the mask word's
    whole producer fused into it a second time, one relayout a mask).
    Returns (the not-null columns, the bit of ``scanned``,
    the number of words)."""
    notnull_cols = _kernel_rows(sig)[0]
    scanned_bit = 1 + len(notnull_cols)
    return notnull_cols, scanned_bit, scanned_bit // 32 + 1


def _words_of_rows(bits):
    """``[N]`` bool masks -> the rows' mask words, ``_mask_bits``'
    layout: 32 shifts and ORs a word over ``[N]``, each mask laid out
    by rows before it comes here."""
    return [functools.reduce(jnp.bitwise_or, [
        b.astype(jnp.int32) << jnp.int32(k)
        for k, b in enumerate(bits[w:w + 32])])
        for w in range(0, len(bits), 32)]


def _round_up(n: int, m: int) -> int:
    return -(-n // m) * m


def _kernel_dims(sig: GroupAggSig):
    """(KP5, C, CP, NBP, KW): the key's pieces lead the kernel's columns
    (none in the direct form: a bucket IS its key), ``_columns``' follow;
    the MXU's operands and the key tables want the columns, the buckets
    and the key's pieces padded to whole lanes (CP, NBP, KW)."""
    KP5 = 0 if sig.radix else 5 * _key_planes(sig)
    C = KP5 + _column_layout(sig)[2]
    return (KP5, C, _round_up(C, 128), _round_up(sig.NB, 128),
            _round_up(KP5, 128))


def _tile_rows(sig: GroupAggSig, n: int) -> int:
    """Rows a grid step takes: a power of two from 1,024 (a row vector is
    then whole (8, 128) registers) to 8,192 that keeps a tile's columns
    (int32 ``[CP, T]``) within 4 MiB of VMEM, and no more than covers
    the window."""
    CP = _kernel_dims(sig)[2]
    t = 1024
    while t < min(n, 8192) and 2 * t * 4 * CP <= 4 << 20:
        t *= 2
    return t


# Lane-rows (of 128 rows) one product of the kernel contracts: the MXU
# accumulates over K = 2,048 by itself, and the tile's sums are read and
# written once a product (Q1's program on the v5e: 1.60 ms a call at 2,
# 1.44 at 4, 1.32 at 8, 1.29 at 16, 1.27 at 32).
_PRODUCT_LANE_ROWS = 16


def _fold8(v):
    """[S, 128] -> [8, 128] partial sums (whole registers added)."""
    return v.reshape(-1, 8, 128).sum(axis=0)


def _window_kernel(sig: GroupAggSig, S: int, *refs):
    """One tile of S x 128 rows (grid axis 0, ``arbitrary``: the outputs
    stay in VMEM and accumulate over the window). ``refs``, hashed form:
    x, base, cnt0, key0 | sums, keyp, rep, stat | p, b, tile, seen, ktab;
    direct form (bucket <-> key is a bijection: no key to keep, no
    ``rep`` to decode it through, no collision to look for): x | sums,
    stat | p, b.

    x_ref[V, S, 128]: the mask words (``_mask_bits``), the planes of
    ``_kernel_rows`` and, of a window whose entries are key groups
    (``sig.own_rows`` False), the rowid of each group's first row;
    base_ref[1, 128]: the rowid of the window's first row (a row's own
    is that, its tile's offset and its place in the tile: made here,
    not handed in); cnt0_ref[NBP, 1] /
    key0_ref[NBP, KW]: the buckets' counts and key pieces of the windows
    before. Outputs: sums[NBP, CP]
    (the window's; column order: key pieces, then ``_columns``'),
    keyp[NBP, KW] (every seen bucket's key pieces), rep[NBP, 1] (first
    matching row of the buckets first seen in this window), stat[3, 8,
    128] (partial counts: negs, collisions, scanned). Scratch: p[S * CP, 128] the
    tile's columns (row s * CP + c: column c of the rows of lane-row s),
    b[S, 128] the rows' buckets, tile[NBP, CP] the tile's sums, seen[NBP,
    1] the buckets' rows so far, ktab[KP, NBP] the kept keys' planes with
    the buckets along the lanes, as the rows look their bucket's up."""
    from jax.experimental import pallas as pl

    direct = sig.radix != ()
    if direct:
        x_ref, sums_ref, stat_ref, p_ref, b_ref = refs
    else:
        (x_ref, base_ref, cnt0_ref, key0_ref, sums_ref, keyp_ref, rep_ref,
         stat_ref, p_ref, b_ref, tile_ref, seen_ref, ktab_ref) = refs
    KP, NB = _key_planes(sig), sig.NB
    KP5, _C, CP, NBP, KW = _kernel_dims(sig)
    notnull_cols, scanned_bit, words = _mask_bits(sig)    # x_ref[:words]
    planes = _kernel_rows(sig)[1]
    G = min(_PRODUCT_LANE_ROWS, S)
    i = pl.program_id(0)

    def keep_keys(keyp):
        """Key pieces [NBP, KW] -> keyp_ref, and their planes -> ktab."""
        keyp_ref[...] = keyp
        pieces = keyp.T                                      # [KW, NBP]
        for k in range(KP):
            ktab_ref[k:k + 1, :] = functools.reduce(jnp.bitwise_or, [
                pieces[5 * k + j:5 * k + j + 1, :] << jnp.int32(7 * j)
                for j in range(5)])

    @pl.when(i == 0)
    def _():
        sums_ref[...] = jnp.zeros_like(sums_ref)
        stat_ref[...] = jnp.zeros_like(stat_ref)
        if not direct:
            rep_ref[...] = jnp.full_like(rep_ref, I32_MAX)
        p_ref[...] = jnp.zeros_like(p_ref)      # (the padding columns)
        if not direct:
            seen_ref[...] = cnt0_ref[...]
            keep_keys(key0_ref[...])

    def bit(k):
        return ((x_ref[k // 32] >> jnp.int32(k % 32)) & jnp.int32(1)) != 0

    m = bit(0)
    stat_ref[2] += _fold8(bit(scanned_bit).astype(jnp.int32))

    # A tile no row of which matches (past the run's rows, outside the
    # scan's bounds) sums nothing.
    @pl.when(jnp.sum(m.astype(jnp.int32)) > 0)
    def _():
        notnull = {cid: bit(1 + j) for j, cid in enumerate(notnull_cols)}

        def plane(cid, pi):
            return x_ref[words + planes.index((cid, pi))]

        cols, bad = _columns(sig, m, notnull, plane)
        stat_ref[0] += _fold8(bad.astype(jnp.int32))
        if direct:
            key = []
            bucket = _code_bucket(sig, notnull, plane)
        else:
            key = _group_planes(sig, notnull, plane)
            bucket = _bucket_hash(key) % NB
        bucket = jnp.where(m, bucket, NBP)                   # NBP: none
        b_ref[...] = bucket
        for c, col in enumerate(
                [q for p in key for q in _plane_pieces(p)] + cols):
            p_ref[pl.ds(c, S, stride=CP), :] = col

        # ONE product of the bucket one-hot with the tile's columns for
        # every per-bucket sum, G lane-rows of the tile at a time:
        # onehot[NBP, G * 128] x columns[CP, G * 128]^T, int8 operands.
        # The direct form has nothing to read off a tile's own sums and
        # adds the products to the window's.
        buckets = lax.broadcasted_iota(jnp.int32, (NBP, 128), 0)
        acc_ref = sums_ref if direct else tile_ref
        if not direct:
            tile_ref[...] = jnp.zeros_like(tile_ref)

        def product(g, carry):
            onehot, columns = [], []
            for s in (g * G + u for u in range(G)):
                onehot.append((buckets == b_ref[pl.ds(s, 1), :]).astype(
                    jnp.int32).astype(jnp.int8))
                columns.append(p_ref[pl.ds(pl.multiple_of(s * CP, CP), CP),
                                     :].astype(jnp.int8))
            acc_ref[...] += _int8_dot(jnp.concatenate(onehot, axis=1),
                                      jnp.concatenate(columns, axis=1), 1, 1)
            return carry

        lax.fori_loop(0, S // G, product, 0)
        if direct:
            return
        tile = tile_ref[...]
        cnt = tile[:, KP5:KP5 + 1]
        first_seen = (seen_ref[...] == 0) & (cnt > 0)

        # The key and the first row of a bucket seen for the first time:
        # its rows' key pieces sum to count x piece when they agree (and
        # to anything when they do not: then some row differs from
        # whatever is kept, and is counted). Rows come in rowid order, so
        # a bucket's first row is in the first tile that has any.
        @pl.when(jnp.sum(first_seen.astype(jnp.int32)) > 0)
        def _():
            a, n = tile[:, :KW], jnp.maximum(cnt, 1)
            # a // n, exact: a < 2^24 (a tile's rows x 127) is exact in
            # float32, the quotient is off by one at most
            q = (a.astype(jnp.float32) / n.astype(jnp.float32)).astype(
                jnp.int32)
            q = q + ((q + 1) * n <= a).astype(jnp.int32) \
                - (q * n > a).astype(jnp.int32)
            keep_keys(jnp.where(first_seen, q, keyp_ref[...]))

            lane = lax.broadcasted_iota(jnp.int32, (1, 128), 1)

            def first_row(s, rep):
                if sig.own_rows:
                    rowid = base_ref[...] + ((i * S + s) * 128 + lane)
                else:
                    rowid = x_ref[words + len(planes), pl.ds(s, 1), :]
                return jnp.minimum(rep, jnp.where(
                    buckets == b_ref[pl.ds(s, 1), :], rowid, I32_MAX))

            rep = lax.fori_loop(0, S, first_row,
                                jnp.full((NBP, 128), I32_MAX, jnp.int32))
            rep_ref[...] = jnp.where(
                first_seen, jnp.min(rep, axis=1, keepdims=True), rep_ref[...])

        seen_ref[...] += cnt
        sums_ref[...] += tile

        # Each matching row against its bucket's key: the key's planes,
        # looked up by the row's bucket along the lanes of ``ktab`` (128
        # buckets a lookup).
        differs = jnp.zeros(m.shape, jnp.bool_)
        for k in range(KP):
            want = jnp.zeros(m.shape, jnp.int32)
            for seg in range(NBP // 128):
                table = jnp.broadcast_to(
                    ktab_ref[k:k + 1, seg * 128:(seg + 1) * 128], m.shape)
                want = jnp.where(
                    (bucket >> jnp.int32(7)) == seg,
                    jnp.take_along_axis(table, bucket & jnp.int32(127),
                                        axis=1), want)
            differs = differs | (want != key[k])
        stat_ref[1] += _fold8((m & differs).astype(jnp.int32))


def _grouped_window(sig: GroupAggSig, words, plane, base, start_idx,
                    count, key):
    """The grouped reduction of one window, as one ``pallas_call`` over
    row tiles (interpreted where the backend is no TPU): the rows' mask
    ``words`` (``_mask_bits``; ``[N]`` int32 each), ``plane`` as
    ``_columns`` takes it (``[N]`` vectors), ``base`` the rowid of the
    window's first row, ``start_idx[N]`` the first row of each key group
    in the window (read of the segmented form only: in the others a
    group's entry is its row), and the accumulator's ``count[NB]`` and
    ``key[NB, KP]`` of the windows before. Returns (sums[NB, C] in
    ``_columns``' order, rep[NB], key[NB, KP], collisions, negs,
    scanned); the direct form reads neither ``base`` nor the last three
    arguments and gives None for ``rep`` and ``key``."""
    import jax
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    NB, KP = sig.NB, _key_planes(sig)
    KP5, C, CP, NBP, KW = _kernel_dims(sig)
    direct = sig.radix != ()
    rows = list(words) + [plane(*cp) for cp in _kernel_rows(sig)[1]]
    if not sig.own_rows and not direct:
        rows.append(base + start_idx)
    n = rows[0].shape[0]
    T = _tile_rows(sig, n)
    S = T // 128
    x = jnp.stack(rows)
    if n % T:
        x = jnp.pad(x, ((0, 0), (0, -n % T)))   # (mask word 0: no match)
    x = x.reshape(len(rows), -1, 128)

    def whole(*shape):
        return pl.BlockSpec(shape, lambda i: (0,) * len(shape))

    def call(operands, in_shapes, out_shapes, scratch_shapes):
        """``_window_kernel`` over the row tiles: ``x`` by tiles, every
        other operand and every output whole."""
        vmem = 4 * (T * (CP + 2 * len(rows)) + NBP * (3 * CP + 4 * KW))
        return pl.pallas_call(
            functools.partial(_window_kernel, sig, S),
            grid=(x.shape[1] // S,),
            in_specs=[pl.BlockSpec((len(rows), S, 128),
                                   lambda i: (0, i, 0))]
            + [whole(*shape) for shape in in_shapes],
            out_specs=[whole(*shape) for shape in out_shapes],
            out_shape=[jax.ShapeDtypeStruct(shape, jnp.int32)
                       for shape in out_shapes],
            scratch_shapes=[pltpu.VMEM(shape, jnp.int32)
                            for shape in scratch_shapes],
            compiler_params=pltpu.CompilerParams(
                dimension_semantics=("arbitrary",),
                vmem_limit_bytes=2 * vmem + (8 << 20)),
            interpret=jax.default_backend() != "tpu",
            name="grouped_window",
        )(x, *operands)

    if direct:
        sums, stat = call((), [], [(NBP, CP), (3, 8, 128)],
                          [(S * CP, 128), (S, 128)])
        return (sums[:NB, :C], None, None, jnp.int32(0),
                jnp.sum(stat[0]), jnp.sum(stat[2]))
    key0 = jnp.pad(jnp.stack(_plane_pieces(key), axis=-1).reshape(NB, KP5),
                   ((0, NBP - NB), (0, KW - KP5)))
    cnt0 = jnp.pad(count, (0, NBP - NB))[:, None]
    base = jnp.full((1, 128), base, jnp.int32)
    sums, keyp, rep, stat = call(
        (base, cnt0, key0), [(1, 128), (NBP, 1), (NBP, KW)],
        [(NBP, CP), (NBP, KW), (NBP, 1), (3, 8, 128)],
        [(S * CP, 128), (S, 128), (NBP, CP), (NBP, 1), (KP, NBP)])
    return (sums[:NB, KP5:C], rep[:NB, 0],
            _plane_of_pieces(keyp[:NB, :KP5].reshape(NB, KP, 5)),
            jnp.sum(stat[1]), jnp.sum(stat[0]), jnp.sum(stat[2]))


def _rows_window(sig: GroupAggSig, run, b0, row_lo, row_hi, read,
                 pred_literals):
    """A window by rows: every plane the resolve reads laid out by rows
    (XLA fuses the unpacks into the ungrouped program's reductions; for
    the kernel it materialises each). The signature says which resolve:
    flat; *lookback* (a run that is not flat whose largest key group is
    within ``sig.lookback``: lookback_fold.resolve_window, shifts and
    selects, a group's entry its first row holding the merged planes);
    *segmented* (ops.scan.resolve_window's segment ops, a group's entry
    its number in the window, its planes gathered through ``col_idx``).
    Bit for bit the same sums either way. Returns (the resolve's dict
    with ``start_idx``, the mask of the entries that are key groups,
    the match mask, plane)."""
    if sig.lookback:
        r = lookback_fold.resolve_window(sig, run, b0, row_lo, row_hi, *read)
        gvalid = r["group_start"]
    else:
        r = resolve_window(dataclasses.replace(sig, apply_preds=False), run,
                           b0, row_lo, row_hi, *read, pred_literals)
        gvalid = r["ridx"] < r["num_groups"]
    cmp_w = r["cmp_w"]
    col_notnull = r["col_notnull"]
    # The predicates and the planes, on the window's (merged) planes
    # themselves where an entry is its row: the segmented form indexes
    # them through col_idx, and a gather by arange is still a gather on
    # the TPU (117 us a predicate column a window of 16,384 rows on the
    # v5e; 72 us each over the overlay's 8,192).
    m = r["pre_pred"] & gvalid
    if sig.apply_preds:
        for ps, lit in zip(sig.preds, pred_literals):
            m = m & col_notnull[ps.col_id] & _eval_pred(
                ps, cmp_w.get(ps.col_id), r["arith_w"].get(ps.col_id),
                slice(None) if sig.own_rows else r["col_idx"][ps.col_id],
                lit)

    def plane(cid, pi):
        return (cmp_w[cid][:, pi] if sig.own_rows
                else cmp_w[cid][r["col_idx"][cid], pi])

    return r, gvalid, m, plane


def _rows_words(sig: GroupAggSig, r, gvalid, m):
    """``_rows_window``'s masks as the kernel's mask words."""
    return _words_of_rows(
        [m] + [r["col_notnull"][cid] for cid in _mask_bits(sig)[0]]
        + [r["pre_pred"] & gvalid])


def _packed_window(sig: GroupAggSig, run, b0, row_lo, row_hi, read,
                   pred_literals):
    """The mask words and planes of a flat window whose presence planes
    are "bits" leaves, bit for bit the rows form's (``_words_of_rows``
    over ``resolve_window``'s masks), without one of those planes laid
    out by rows: the masks are combined on the packed words
    (ops.scan.resolve_flat_packed), ONE producer lays a word's 32 masks
    out by rows (encodings.rows_of_words), and what is per row and no
    bit plane — the read point and the TTL against ``ht`` / ``exp``, the
    scan's bounds, the predicates' compares — clears its bits of the
    word there. Returns (words, plane)."""
    notnull_cols, scanned_bit, _words = _mask_bits(sig)
    p = resolve_flat_packed(sig, run, b0, row_lo, row_hi, *read)
    cmp_w, arith_w = p["cmp_w"], p["arith_w"]
    match_w = p["exists_w"]
    hit = p["in_range"]
    if sig.apply_preds:
        for ps, lit in zip(sig.preds, pred_literals):
            match_w = match_w & p["notnull_w"][ps.col_id]
            hit = hit & _eval_pred(ps, cmp_w.get(ps.col_id),
                                   arith_w.get(ps.col_id), slice(None), lit)
    masks = [match_w] + [p["notnull_w"][cid] for cid in notnull_cols] \
        + [p["exists_w"]]
    words = []
    for w in range(0, len(masks), 32):
        # a row outside the bounds keeps its not-null bits (they hold
        # no bound in the rows form either), one that is not visible or
        # is expired keeps none
        drop = jnp.int32(0)
        if w == 0:
            drop = jnp.where(hit, drop, jnp.int32(1))
        if w <= scanned_bit < w + 32:
            drop = drop | jnp.where(
                p["in_range"], jnp.int32(0),
                jnp.int32(1) << jnp.int32(scanned_bit - w))
        words.append(jnp.where(
            p["row_ok"], encodings.rows_of_words(masks[w:w + 32]) & ~drop,
            jnp.int32(0)))

    def plane(cid, pi):
        return cmp_w[cid][:, pi]

    return words, plane


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
      bucket 0. The direct form (``sig.radix``, ``addressed``) has no
      ``rep`` and no ``key`` — bucket b IS the key ``bucket_codes(sig,
      b)`` — and its ``collisions`` is 0 by construction.
    """
    from yugabyte_db_tpu.ops.row_gather import _unpack_literals

    if sig.radix and (seen := addressed(sig, run).radix) != sig.radix:
        raise ValueError(
            f"a signature that addresses its buckets by the dictionaries "
            f"{sig.radix} over a run whose group columns' leaves give "
            f"{seen}")
    K, R, NB = sig.K, sig.R, sig.NB
    w_first, w_last = iparams[0], iparams[1]
    row_lo, row_hi = iparams[2], iparams[3]
    read = (iparams[4], iparams[5], iparams[6], iparams[7])
    pred_literals = _unpack_literals(sig, iparams, fparams)

    KP = _key_planes(sig)
    NA = NB if sig.group_cols else 1   # buckets the loop accumulates
    mask_at, digits_at, _C = _column_layout(sig)

    def init_acc():
        acc = {
            "count": jnp.zeros((NA,), jnp.int32),
            "rep": jnp.full((NA,), I32_MAX, jnp.int32),
            "key": jnp.zeros((NA, KP), jnp.int32),
            "collisions": jnp.int32(0),
            "scanned": jnp.int32(0),
            "negs": jnp.int32(0),
        }
        if sig.radix:       # the direct form: a bucket is its key
            del acc["rep"], acc["key"]
        for i, ag in enumerate(sig.aggs):
            if ag.kind == "count":
                acc[f"a{i}"] = jnp.zeros((NA,), jnp.int32)
            else:
                acc[f"a{i}"] = jnp.zeros((NA, DIGITS), jnp.int32)
                # non-null input count: SQL sum over zero inputs is NULL,
                # which a zero digit vector alone cannot distinguish.
                acc[f"n{i}"] = jnp.zeros((NA,), jnp.int32)
        return acc

    # A flat grouped window hands its kernel the presence masks; where
    # the run keeps them as "bits" leaves they are combined packed. What
    # the program sees decides, nothing else; the ungrouped program's
    # reductions fuse the unpacks and keep the rows form.
    packed = (len(sig.group_cols) > 0 and sig.flat
              and presence_is_packed(sig, run))
    if sig.group_cols:
        metrics.count_grouped_presence("packed" if packed else "rows")

    def body(w, acc):
        b0 = w * K
        base = b0 * R
        window = (sig, run, b0, row_lo - base, row_hi - base, read,
                  pred_literals)
        if packed:
            words, plane = _packed_window(*window)
            return accumulate(acc, base, None, words, plane)
        r, gvalid, m, plane = _rows_window(*window)
        if sig.group_cols:
            return accumulate(acc, base, r["start_idx"],
                              _rows_words(sig, r, gvalid, m), plane)
        # No group column, no buckets: plain reductions over the rows.
        rowid = base + r["start_idx"]
        cols, bad = _columns(sig, m, r["col_notnull"], plane)
        new = {
            "scanned": acc["scanned"] + jnp.sum(
                (r["pre_pred"] & gvalid).astype(jnp.int32)),
            "negs": acc["negs"] + jnp.sum(bad.astype(jnp.int32)),
            "key": acc["key"], "collisions": acc["collisions"],
        }
        sums = jnp.stack([jnp.sum(c) for c in cols])[None]   # [1, C]
        rep = jnp.min(jnp.where(m, rowid, I32_MAX))[None]
        return add_sums(acc, new, sums, rep)

    def accumulate(acc, base, start_idx, words, plane):
        """A grouped window, from its rows' mask words on."""
        sums, rep, key, collisions, negs, scanned = _grouped_window(
            sig, words, plane, base, start_idx, acc["count"], acc.get("key"))
        new = {"scanned": acc["scanned"] + scanned,
               "negs": acc["negs"] + negs,
               "collisions": acc["collisions"] + collisions}
        if key is not None:
            new["key"] = key
        return add_sums(acc, new, sums, rep)

    def add_sums(acc, new, sums, rep):
        if rep is not None:
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
    if sig.radix:
        del shapes["rep"], shapes["key"]
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
