"""Loop-free full-run aggregate over a FLAT run: one fused XLA program.

The windowed fold (agg_fold.compiled_full_aggregate) walks a run with a
fori_loop of small dynamic-slice windows — correct for segmented MVCC
state threading, but the serialized tiny iterations leave the MXU/VPU
idle (measured ~1 GB/s of HBM traffic at 17M rows). A flat run (one
version per key — the common post-compaction shape) needs no cross-row
state at all, so the whole resolve + predicate + aggregate evaluates as
ONE elementwise/reduction program over the full [B, R] planes and XLA
tiles it at memory speed (measured ~130 GB/s / >5G rows/s on the same
shape — ~180x the windowed fold).

Exact integer sums without int64: every 32-bit plane splits into two
16-bit limbs; per-BLOCK limb sums stay below 2^31 for R <= 2^15-1, and
a second decompose+sum over the block axis stays exact for B <= 2^14 —
the program returns a handful of scalars, packed into agg_fold's
(ivec, fvec) format so the engine's unpack/finalize path is shared.

Reference analog: the same per-tablet aggregate pushdown
(PgsqlReadOperation::EvalAggregate, src/yb/docdb/pgsql_operation.cc:473)
— this is its bandwidth-roofline form.
"""

from __future__ import annotations

import functools

import jax.numpy as jnp
import numpy as np
from jax import lax

from yugabyte_db_tpu.ops import agg_fold
from yugabyte_db_tpu.ops import encodings
from yugabyte_db_tpu.ops import scan as dscan
from yugabyte_db_tpu.ops.scan import le2
from yugabyte_db_tpu.utils import jitting
from yugabyte_db_tpu.utils.jitting import compile_contract

# np scalars, not jnp: module import must not touch the backend.
I32_MIN = np.int32(-(1 << 31))
I32_MAX = np.int32((1 << 31) - 1)
_BIAS = np.int32(-(1 << 31))  # bit pattern 0x80000000

MAX_R = (1 << 15) - 1   # block limb sums stay < 2^31
MAX_B = 1 << 14         # second-stage limb sums stay < 2^31


def supports(sig: dscan.ScanSig) -> bool:
    """Eligibility: flat run within the exact-limb shape bounds, exact
    predicate kinds only (the callers' device-exact set)."""
    if not sig.flat or sig.R > MAX_R or sig.B > MAX_B:
        return False
    if any(ps.kind not in ("i32", "i64", "f64", "code")
           for ps in sig.preds):
        return False
    for ag in sig.aggs:
        if ag.fn not in ("count", "sum", "min", "max"):
            return False
    return True


def _limb_scalars(masked_u16, pos, digits):
    """Exactly sum a [B, R] int32 array of values in [0, 0xFFFF] and add
    the total into the base-2^16 digit accumulation at digit ``pos``.
    Two-stage: per-block int32 sums, then decompose and sum over blocks.
    """
    s1 = jnp.sum(masked_u16, axis=1, dtype=jnp.int32)          # [B] < 2^31
    lo = jnp.sum(s1 & jnp.int32(0xFFFF), dtype=jnp.int32)      # < B*2^16
    hi = jnp.sum(lax.shift_right_logical(s1, 16), dtype=jnp.int32)
    digits[pos] = digits[pos] + lo
    digits[pos + 1] = digits[pos + 1] + hi
    return digits


def _masked_plane_limbs(plane, m_i32, digits, base_pos):
    """Add a biased-u32 plane's masked exact sum into the digits."""
    u = plane ^ _BIAS  # biased: unsigned order == signed plane order
    lo16 = (u & jnp.int32(0xFFFF)) * m_i32
    hi16 = lax.shift_right_logical(u, 16) * m_i32
    digits = _limb_scalars(lo16, base_pos, digits)
    digits = _limb_scalars(hi16, base_pos + 1, digits)
    return digits


def _eval_pred_flat(ps: dscan.PredSig, cmp, arith, lit):
    """Elementwise exact predicate over full planes (i32/i64/f64)."""
    if ps.kind == "i32":
        v = cmp[..., 0]
        return {"=": v == lit, "!=": v != lit, "<": v < lit,
                "<=": v <= lit, ">": v > lit, ">=": v >= lit}[ps.op]
    if ps.kind == "code":
        # Promoted string predicate: exact compare on the decoded
        # dictionary-code plane (see ops.scan._eval_pred).
        v = cmp[..., 2]
        return {"=": v == lit, "!=": v != lit, "<": v < lit,
                "<=": v <= lit, ">": v > lit, ">=": v >= lit}[ps.op]
    hi, lo = cmp[..., 0], cmp[..., 1]
    lhi, llo = lit[0], lit[1]
    eq = (hi == lhi) & (lo == llo)
    lt = (hi < lhi) | ((hi == lhi) & (lo < llo))
    return {"=": eq, "!=": ~eq, "<": lt, "<=": lt | eq,
            ">": ~(lt | eq), ">=": ~lt}[ps.op]


def finish_groups(sig: dscan.ScanSig, gs, live_any, col_notnull, col_val,
                  row_lo, row_hi, pred_lits):
    """Shared group-representative accumulation tail of the multi-version
    folds (seg_fold / lookback_fold): exists fold, range/predicate result
    mask at each group's first row, and exact count/sum/min/max packing.
    ``col_val`` holds each column's latest-visible payload {null, exp,
    cmp[, arith]} evaluated at the representative row."""
    from jax import lax

    exists = live_any
    for cs in sig.cols:
        exists = exists | col_notnull[cs.col_id]

    B, R = gs.shape
    gidx = (lax.broadcasted_iota(jnp.int32, (B, R), 0) * R
            + lax.broadcasted_iota(jnp.int32, (B, R), 1))
    result = gs & exists & (gidx >= row_lo) & (gidx < row_hi)
    for i, ps in enumerate(sig.preds):
        latest = col_val[ps.col_id]
        result = result & col_notnull[ps.col_id] & \
            _eval_pred_flat(ps, latest["cmp"], latest.get("arith"),
                            pred_lits[i])

    scanned = jnp.sum(result, dtype=jnp.int32)
    acc = []
    for ag in sig.aggs:
        if ag.fn == "count":
            m = (result if ag.col_id is None
                 else result & col_notnull[ag.col_id])
            acc.append({"count": jnp.sum(m, dtype=jnp.int32)})
            continue
        latest = col_val[ag.col_id]
        m = result & col_notnull[ag.col_id]
        n = jnp.sum(m, dtype=jnp.int32)
        if ag.fn == "sum":
            if ag.kind in ("f32", "f64"):
                s1 = jnp.sum(jnp.where(m, latest["arith"], 0.0), axis=1)
                acc.append({"fsum": jnp.sum(s1),
                            "fcomp": jnp.float32(0), "n": n})
            else:
                m_i32 = m.astype(jnp.int32)
                digits = [jnp.int32(0)] * agg_fold.DIGITS
                if ag.kind == "i32":
                    digits = _masked_plane_limbs(
                        latest["cmp"][..., 0], m_i32, digits, 0)
                else:
                    digits = _masked_plane_limbs(
                        latest["cmp"][..., 1], m_i32, digits, 0)
                    digits = _masked_plane_limbs(
                        latest["cmp"][..., 0], m_i32, digits, 2)
                acc.append({"digits": jnp.stack(digits), "n": n})
        else:
            is_max = ag.fn == "max"
            red = jnp.max if is_max else jnp.min
            if ag.kind == "f32":
                fill = jnp.float32(-jnp.inf if is_max else jnp.inf)
                acc.append({"fext": red(
                    jnp.where(m, latest["arith"], fill)), "n": n})
            elif ag.kind == "i32":
                fill = I32_MIN if is_max else I32_MAX
                acc.append({"ext": red(jnp.where(
                    m, latest["cmp"][..., 0], fill)), "n": n})
            else:
                fill = I32_MIN if is_max else I32_MAX
                hi = latest["cmp"][..., 0]
                lo = latest["cmp"][..., 1]
                ext_hi = red(jnp.where(m, hi, fill))
                ext_lo = red(jnp.where(m & (hi == ext_hi), lo, fill))
                acc.append({"ext_hi": ext_hi, "ext_lo": ext_lo, "n": n})
    return agg_fold.pack(sig.aggs, acc, scanned)


@functools.lru_cache(maxsize=128)
@compile_contract("flat_aggregate", max_compiles=128)
def compiled_flat_aggregate(sig: dscan.ScanSig):
    """jit(run, row_lo, row_hi, read_hi, read_lo, rexp_hi, rexp_lo,
    pred_lits) -> (ivec, fvec) in agg_fold's packed format."""
    assert supports(sig)
    import jax

    def fn(run, row_lo, row_hi, read_hi, read_lo, rexp_hi, rexp_lo,
           pred_lits):
        # Encoded leaves decode here as transients fused into the one
        # elementwise program — HBM holds only the compressed planes.
        run = encodings.decode_run(run)
        valid = run["valid"]
        visible = valid & le2(run["ht_hi"], run["ht_lo"], read_hi, read_lo)
        expired = le2(run["exp_hi"], run["exp_lo"], rexp_hi, rexp_lo)
        alive = visible & ~run["tomb"]
        not_expired = ~expired
        exists = alive & run["live"] & not_expired
        notnull = {}
        for cs in sig.cols:
            c = run["cols"][cs.col_id]
            nn = alive & c["set"] & ~c["isnull"] & not_expired
            notnull[cs.col_id] = nn
            exists = exists | nn
        B, R = valid.shape
        gidx = (lax.broadcasted_iota(jnp.int32, (B, R), 0) * R
                + lax.broadcasted_iota(jnp.int32, (B, R), 1))
        pre_pred = exists & (gidx >= row_lo) & (gidx < row_hi)
        result = pre_pred
        for i, ps in enumerate(sig.preds):
            c = run["cols"][ps.col_id]
            result = result & notnull[ps.col_id] & _eval_pred_flat(
                ps, c["cmp"], c.get("arith"), pred_lits[i])

        # Match the windowed fold's statistic: result rows scanned
        # (agg_fold.fold_window counts parts["result"]).
        scanned = jnp.sum(result, dtype=jnp.int32)
        acc = []
        for ag in sig.aggs:
            if ag.fn == "count":
                m = (result if ag.col_id is None
                     else result & notnull[ag.col_id])
                acc.append({"count": jnp.sum(m, dtype=jnp.int32)})
                continue
            c = run["cols"][ag.col_id]
            m = result & notnull[ag.col_id]
            n = jnp.sum(m, dtype=jnp.int32)
            if ag.fn == "sum":
                if ag.kind in ("f32", "f64"):
                    # Two-stage f32 sum of the arithmetic plane (block
                    # partials then block-axis sum); fcomp carries 0 —
                    # accuracy matches the windowed Kahan path to the
                    # tested tolerances.
                    s1 = jnp.sum(jnp.where(m, c["arith"], 0.0), axis=1)
                    acc.append({"fsum": jnp.sum(s1),
                                "fcomp": jnp.float32(0), "n": n})
                else:
                    m_i32 = m.astype(jnp.int32)
                    digits = [jnp.int32(0)] * agg_fold.DIGITS
                    if ag.kind == "i32":
                        digits = _masked_plane_limbs(
                            c["cmp"][..., 0], m_i32, digits, 0)
                    else:  # i64: lo plane at digit 0, hi plane at 2
                        digits = _masked_plane_limbs(
                            c["cmp"][..., 1], m_i32, digits, 0)
                        digits = _masked_plane_limbs(
                            c["cmp"][..., 0], m_i32, digits, 2)
                    acc.append({"digits": jnp.stack(digits), "n": n})
            else:
                is_max = ag.fn == "max"
                if ag.kind == "f32":
                    fill = jnp.float32(-jnp.inf if is_max else jnp.inf)
                    red = jnp.max if is_max else jnp.min
                    acc.append({"fext": red(jnp.where(m, c["arith"], fill)),
                                "n": n})
                elif ag.kind == "i32":
                    fill = I32_MIN if is_max else I32_MAX
                    red = jnp.max if is_max else jnp.min
                    acc.append({"ext": red(
                        jnp.where(m, c["cmp"][..., 0], fill)), "n": n})
                else:
                    fill = I32_MIN if is_max else I32_MAX
                    red = jnp.max if is_max else jnp.min
                    hi = c["cmp"][..., 0]
                    lo = c["cmp"][..., 1]
                    ext_hi = red(jnp.where(m, hi, fill))
                    ext_lo = red(jnp.where(m & (hi == ext_hi), lo, fill))
                    acc.append({"ext_hi": ext_hi, "ext_lo": ext_lo,
                                "n": n})
        return agg_fold.pack(sig.aggs, acc, scanned)

    return jitting.jit(fn, "flat_aggregate", sig.tag())
