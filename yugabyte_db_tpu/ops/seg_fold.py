"""Loop-free full-run aggregate over a MULTI-VERSION run: segmented
scans instead of a serialized window walk.

ops.flat_fold made flat runs bandwidth-bound; this module does the same
for segmented MVCC state. The key layout invariant — a key's versions
are contiguous, newest-first, and never span a block (storage.columnar)
— turns every per-group MVCC question into a segmented scan along the
row axis of the [B, R] planes, which XLA lowers to log-depth fused
passes over the whole run:

- newest visible tombstone per group: prefix + suffix segmented
  first-found scans over (visible & tomb) carrying the ht planes;
- per-column latest alive setter: ONE suffix segmented first-found scan
  per column carrying the value planes — evaluated at each group's
  first row (the group representative), the suffix IS the whole group;
- group aggregates: representative rows then ride the exact flat limb
  machinery (flat_fold) with mask = group_start & exists & predicates.

Equal-hybrid-time DELETE+write pairs shadow correctly regardless of
intra-tie layout order because the tombstone reduction combines both
scan directions (prefix ∪ suffix covers the whole group).

Reference analog: the same merge-on-read the windowed fold implements
(DocRowwiseIterator semantics) at memory-roofline shape.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax

from yugabyte_db_tpu.ops import encodings
from yugabyte_db_tpu.ops import flat_fold
from yugabyte_db_tpu.ops import scan as dscan
from yugabyte_db_tpu.ops.scan import I32_MIN, le2
from yugabyte_db_tpu.utils import jitting
from yugabyte_db_tpu.utils.jitting import compile_contract


def supports(sig: dscan.ScanSig) -> bool:
    if sig.R > flat_fold.MAX_R or sig.B > flat_fold.MAX_B:
        return False
    if any(ps.kind not in ("i32", "i64", "f64", "code")
           for ps in sig.preds):
        return False
    for ag in sig.aggs:
        if ag.fn not in ("count", "sum", "min", "max"):
            return False
    return True


def _seg_first(found, payload, group_start, last_found: bool):
    """Segmented first-found scan along axis=1.

    found: [B, R] bool; payload: pytree of [B, R] arrays. Returns
    (found', payload') where each position holds the first found
    element of its segment-prefix (last_found=False) or the LAST found
    of the prefix (last_found=True — used via flipping for suffix
    scans). Segments restart where group_start is True."""
    def op(a, b):
        a_found, a_g, a_p = a
        b_found, b_g, b_p = b
        # b is the element/aggregate closer to the scan end. If b
        # restarts the segment, a's contribution is discarded.
        if last_found:
            take_b = b_g | b_found
        else:
            take_b = b_g | ~a_found

        def sel(x, y):
            m = take_b
            while m.ndim < x.ndim:  # plane leaves carry a trailing axis
                m = m[..., None]
            return jnp.where(m, y, x)

        out_found = jnp.where(b_g, b_found, a_found | b_found)
        return out_found, a_g | b_g, jax.tree.map(sel, a_p, b_p)

    f, _g, p = lax.associative_scan(
        op, (found, group_start, payload), axis=1)
    return f, p


def _suffix_first(found, payload, group_start):
    """At each row: the first-in-forward-order found element among the
    rows of ITS group at-or-after it. At a group's first row this is the
    group's overall first found — the 'latest version' selector."""
    # Reversed coordinates: suffix -> prefix, and the forward-first
    # becomes the LAST found of the reversed prefix. Segment restarts in
    # reversed order happen at original group ENDS (the row before the
    # next group_start).
    flip = lambda x: jnp.flip(x, axis=1)
    group_end = jnp.concatenate(
        [group_start[:, 1:], jnp.ones_like(group_start[:, :1])], axis=1)
    f, p = _seg_first(flip(found), jax.tree.map(flip, payload),
                      flip(group_end), last_found=True)
    return flip(f), jax.tree.map(flip, p)


@functools.lru_cache(maxsize=128)
@compile_contract("seg_aggregate", max_compiles=128)
def compiled_seg_aggregate(sig: dscan.ScanSig):
    """jit(run, row_lo, row_hi, read_hi, read_lo, rexp_hi, rexp_lo,
    pred_lits) -> (ivec, fvec) in agg_fold's packed format; exact
    equivalence with the windowed fold on any multi-version run."""
    assert supports(sig)

    def fn(run, row_lo, row_hi, read_hi, read_lo, rexp_hi, rexp_lo,
           pred_lits):
        run = encodings.decode_run(run)
        valid = run["valid"]
        gs = run["group_start"]
        ht_hi, ht_lo = run["ht_hi"], run["ht_lo"]
        visible = valid & le2(ht_hi, ht_lo, read_hi, read_lo)
        expired = le2(run["exp_hi"], run["exp_lo"], rexp_hi, rexp_lo)
        tomb = run["tomb"]

        # 1. Newest visible tombstone per group (ht-desc layout: the
        # first visible tombstone in forward order has the max ht).
        # Prefix pass covers older rows, suffix pass covers newer/tied
        # rows; lex-max of both = the group's tombstone everywhere.
        vt = visible & tomb
        tf, tf_p = _seg_first(vt, (ht_hi, ht_lo), gs, last_found=False)
        tb, tb_p = _suffix_first(vt, (ht_hi, ht_lo), gs)
        tf_hi = jnp.where(tf, tf_p[0], I32_MIN)
        tf_lo = jnp.where(tf, tf_p[1], I32_MIN)
        tb_hi = jnp.where(tb, tb_p[0], I32_MIN)
        tb_lo = jnp.where(tb, tb_p[1], I32_MIN)
        use_b = (tb_hi > tf_hi) | ((tb_hi == tf_hi) & (tb_lo > tf_lo))
        t_hi = jnp.where(use_b, tb_hi, tf_hi)
        t_lo = jnp.where(use_b, tb_lo, tf_lo)
        has_tomb = tf | tb
        shadowed = has_tomb & le2(ht_hi, ht_lo, t_hi, t_lo)
        alive = visible & ~tomb & ~shadowed

        # 2. Group-level liveness + per-column latest values at the
        # group representative (= group_start rows; their suffix is the
        # whole group).
        live_any, _ = _suffix_first(
            alive & run["live"] & ~expired,
            (jnp.zeros_like(ht_hi),), gs)
        col_notnull = {}
        col_val = {}
        for cs in sig.cols:
            c = run["cols"][cs.col_id]
            cand = alive & c["set"]
            payload = {"null": c["isnull"], "exp": expired,
                       "cmp": c["cmp"]}
            if "arith" in c:
                payload["arith"] = c["arith"]
            has, latest = _suffix_first(cand, payload, gs)
            col_notnull[cs.col_id] = has & ~latest["null"] & ~latest["exp"]
            col_val[cs.col_id] = latest

        return flat_fold.finish_groups(sig, gs, live_any, col_notnull,
                                       col_val, row_lo, row_hi, pred_lits)

    return jitting.jit(fn, "seg_aggregate", sig.tag())
