"""Aggregate partial folding: exact accumulation across scan windows.

Shared by the single-chip full-run aggregate (one dispatch per scan — the
device fori_loops every window and returns two packed vectors, because the
host link pays ~per-transfer latency, not bandwidth) and the mesh-sharded
path (parallel.sharded, which folds per device then combines over ICI).

Integer sums are bit-exact at any scale: per-block 16-bit-limb partials
(ops.scan._eval_agg) fold into a base-2^16 digit vector with one
carry-propagation step per window, so no int32 ever overflows
(limb partial <= 65535*R*K <= ~1.1e9 for K<=8, digits stay < ~2^17).
Min/max fold lexicographically on two int32 planes; float sums fold in f32.

Reference analog of what this replaces: the per-row Python/C++ aggregate
accumulation inside the scan loop (QLReadOperation::EvalAggregate,
src/yb/docdb/cql_operation.cc:1212; PgsqlReadOperation::EvalAggregate,
src/yb/docdb/pgsql_operation.cc:473).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

from yugabyte_db_tpu.ops import scan as dscan
from yugabyte_db_tpu.ops.scan import I32_MAX, I32_MIN
from yugabyte_db_tpu.utils import planes as PL
from yugabyte_db_tpu.utils import jitting
from yugabyte_db_tpu.utils.jitting import compile_contract

DIGITS = 8  # base-2^16 digit vector length for exact integer sums

# Window size for on-device full-run loops: keeps the per-window limb sum
# (<= 65535 * R * K) inside int32.
FULL_WINDOW_BLOCKS = 8

# Headroom for the accumulated carry digits (< ~2^17 after carry_step) on
# top of one window's limb sum.
_LIMB_BUDGET = (1 << 31) - (1 << 18)


def check_limb_bound(R: int, K: int) -> None:
    """Integer-sum safety: one window's 16-bit-limb partial plus carry
    headroom must fit int32."""
    if 65535 * R * K > _LIMB_BUDGET:
        raise ValueError(
            f"rows_per_block={R} x window_blocks={K} overflows the int32 "
            f"limb accumulator (65535*R*K > {_LIMB_BUDGET}); shrink one")


def safe_window_blocks(R: int, max_k: int) -> int:
    """Largest power-of-two window <= max_k that satisfies check_limb_bound."""
    k = max_k
    while k > 1 and 65535 * R * k > _LIMB_BUDGET:
        k //= 2
    check_limb_bound(R, k)
    return k


def carry_step(digits):
    """One base-2^16 carry propagation over a non-negative int32 digit vector."""
    lo = digits & jnp.int32(0xFFFF)
    hi = digits >> jnp.int32(16)
    return lo + jnp.concatenate([jnp.zeros((1,), jnp.int32), hi[:-1]])


def agg_init(sig_aggs):
    acc = []
    for ag in sig_aggs:
        if ag.fn == "count":
            acc.append({"count": jnp.int32(0)})
        elif ag.fn == "sum":
            if ag.kind in ("f32", "f64"):
                # Kahan-compensated f32 pair: cross-window accumulation must
                # not drift (TPU has no fast f64; the compensation term
                # recovers the per-add rounding, summed back in f64 on host).
                acc.append({"fsum": jnp.float32(0), "fcomp": jnp.float32(0),
                            "n": jnp.int32(0)})
            else:
                acc.append({"digits": jnp.zeros((DIGITS,), jnp.int32),
                            "n": jnp.int32(0)})
        else:  # min/max
            is_max = ag.fn == "max"
            fill = I32_MIN if is_max else I32_MAX
            if ag.kind == "f32":
                acc.append({"fext": jnp.float32(-np.inf if is_max else np.inf),
                            "n": jnp.int32(0)})
            elif ag.kind == "i32":
                acc.append({"ext": jnp.int32(fill), "n": jnp.int32(0)})
            else:
                acc.append({"ext_hi": jnp.int32(fill),
                            "ext_lo": jnp.int32(fill), "n": jnp.int32(0)})
    return acc


def agg_fold(sig_aggs, acc, parts):
    """Fold one window's scan_window partials into the accumulators."""
    out = []
    for i, ag in enumerate(sig_aggs):
        a = acc[i]
        p = {k.split("_", 1)[1]: v for k, v in parts.items()
             if k.startswith(f"agg{i}_")}
        if ag.fn == "count":
            out.append({"count": a["count"] + p["count"]})
        elif ag.fn == "sum":
            if ag.kind in ("f32", "f64"):
                # Kahan add of this window's block-partial sum.
                y = jnp.sum(p["fsum"]) - a["fcomp"]
                t = a["fsum"] + y
                out.append({"fsum": t, "fcomp": (t - a["fsum"]) - y,
                            "n": a["n"] + p["n"]})
            else:
                win = jnp.sum(p["limbs"], axis=0)  # [4] per-window limb sums
                widened = jnp.concatenate(
                    [win, jnp.zeros((DIGITS - win.shape[0],), jnp.int32)])
                out.append({"digits": carry_step(a["digits"] + widened),
                            "n": a["n"] + p["n"]})
        else:
            is_max = ag.fn == "max"
            red = jnp.maximum if is_max else jnp.minimum
            if ag.kind == "f32":
                out.append({"fext": red(a["fext"], p["fext"]),
                            "n": a["n"] + p["n"]})
            elif ag.kind == "i32":
                out.append({"ext": red(a["ext"], p["ext"]),
                            "n": a["n"] + p["n"]})
            else:
                phi, plo = p["ext_hi"], p["ext_lo"]
                if is_max:
                    take = (phi > a["ext_hi"]) | (
                        (phi == a["ext_hi"]) & (plo > a["ext_lo"]))
                else:
                    take = (phi < a["ext_hi"]) | (
                        (phi == a["ext_hi"]) & (plo < a["ext_lo"]))
                out.append({
                    "ext_hi": jnp.where(take, phi, a["ext_hi"]),
                    "ext_lo": jnp.where(take, plo, a["ext_lo"]),
                    "n": a["n"] + p["n"]})
    return out


# -- packing: accumulators <-> two flat vectors (minimize D2H transfers) -----

def pack(sig_aggs, acc, scanned):
    """(int32 vector, float32 vector) carrying every accumulator + scanned."""
    ints, floats = [scanned], []
    for ag, a in zip(sig_aggs, acc):
        if ag.fn == "count":
            ints.append(a["count"])
        elif ag.fn == "sum":
            if ag.kind in ("f32", "f64"):
                floats.extend([a["fsum"], a["fcomp"]])
                ints.append(a["n"])
            else:
                ints.extend([a["digits"][j] for j in range(DIGITS)])
                ints.append(a["n"])
        elif ag.kind == "f32":
            floats.append(a["fext"])
            ints.append(a["n"])
        elif ag.kind == "i32":
            ints.extend([a["ext"], a["n"]])
        else:
            ints.extend([a["ext_hi"], a["ext_lo"], a["n"]])
    ivec = jnp.stack(ints)
    fvec = (jnp.stack(floats) if floats
            else jnp.zeros((0,), jnp.float32))
    return ivec, fvec


def unpack(sig_aggs, ivec, fvec):
    """Inverse of pack on host numpy arrays -> (acc dicts of python
    numbers, scanned)."""
    ints = [int(x) for x in np.asarray(ivec)]
    floats = [float(x) for x in np.asarray(fvec)]
    ii, fi = 1, 0
    scanned = ints[0]
    acc = []
    for ag in sig_aggs:
        if ag.fn == "count":
            acc.append({"count": ints[ii]}); ii += 1
        elif ag.fn == "sum":
            if ag.kind in ("f32", "f64"):
                acc.append({"fsum": floats[fi], "fcomp": floats[fi + 1],
                            "n": ints[ii]})
                fi += 2; ii += 1
            else:
                acc.append({"digits": ints[ii:ii + DIGITS],
                            "n": ints[ii + DIGITS]})
                ii += DIGITS + 1
        elif ag.kind == "f32":
            acc.append({"fext": floats[fi], "n": ints[ii]}); fi += 1; ii += 1
        elif ag.kind == "i32":
            acc.append({"ext": ints[ii], "n": ints[ii + 1]}); ii += 2
        else:
            acc.append({"ext_hi": ints[ii], "ext_lo": ints[ii + 1],
                        "n": ints[ii + 2]})
            ii += 3
    return acc, scanned


def merge_accs(ag: dscan.AggSig, a: dict, b: dict) -> dict:
    """Combine two unpacked accumulators over DISJOINT row sets (the
    overlay-scan composition: primary-run partial + dirty-key overlay
    partial). Exact for count/sum (digit adds) and order-correct for
    min/max (lexicographic on ordered planes)."""
    if ag.fn == "count":
        return {"count": a["count"] + b["count"]}
    n = a["n"] + b["n"]
    if ag.fn == "sum":
        if ag.kind in ("f32", "f64"):
            return {"fsum": a["fsum"] + b["fsum"],
                    "fcomp": a["fcomp"] + b["fcomp"], "n": n}
        return {"digits": [int(x) + int(y)
                           for x, y in zip(a["digits"], b["digits"])],
                "n": n}
    if a["n"] == 0:
        return dict(b, n=n)
    if b["n"] == 0:
        return dict(a, n=n)
    pick = max if ag.fn == "max" else min
    if ag.kind == "f32":
        return {"fext": pick(a["fext"], b["fext"]), "n": n}
    if ag.kind == "i32":
        return {"ext": pick(a["ext"], b["ext"]), "n": n}
    best = pick((a["ext_hi"], a["ext_lo"]), (b["ext_hi"], b["ext_lo"]))
    return {"ext_hi": best[0], "ext_lo": best[1], "n": n}


def finalize(ag: dscan.AggSig, a: dict, fn_name: str):
    """Accumulator -> python value (fn_name is the user fn: avg uses a sum
    accumulator)."""
    if fn_name == "count":
        return int(a["count"])
    n = int(a["n"])
    if fn_name in ("sum", "avg"):
        if n == 0:
            return None
        if ag.kind in ("f32", "f64"):
            s = float(a["fsum"]) - float(a["fcomp"])
        else:
            digits = a["digits"]
            total = sum(int(digits[j]) << (16 * j) for j in range(DIGITS))
            bias = (1 << 63) if ag.kind == "i64" else (1 << 31)
            s = total - n * bias
        return s / n if fn_name == "avg" else s
    if n == 0:
        return None
    if ag.kind == "f32":
        return float(a["fext"])
    if ag.kind == "i32":
        return int(a["ext"])
    hi = np.array([int(a["ext_hi"])], dtype=np.int32)
    lo = np.array([int(a["ext_lo"])], dtype=np.int32)
    if ag.kind == "i64":
        return int(PL.ordered_planes_to_i64(hi, lo)[0])
    return float(PL.ordered_planes_to_f64(hi, lo)[0])


# -- shared window-fold body (single-chip + sharded paths) -------------------

def fold_window(sig: dscan.ScanSig, run, w, carry, row_lo, row_hi,
                read_planes, pred_lits, block_off=0):
    """fori_loop body: scan window w of `run` (local block offset
    block_off for mesh shards) and fold its partials into the carry."""
    acc, scanned = carry
    b0 = w * sig.K
    base = (block_off + b0) * sig.R
    parts = dscan.scan_window(
        sig, run, b0,
        jnp.clip(row_lo - base, -(1 << 30), 1 << 30),
        jnp.clip(row_hi - base, -(1 << 30), 1 << 30),
        *read_planes, pred_lits)
    scanned = scanned + jnp.sum(parts["result"].astype(jnp.int32))
    return agg_fold(sig.aggs, acc, parts), scanned


def window_bounds(row_lo: int, row_hi: int, R: int, K: int, W: int):
    """[w_first, w_last) window indices overlapping row range (host ints)."""
    if row_hi <= row_lo:
        return 0, 0
    w_first = max(0, min(W, (row_lo // R) // K))
    w_last = max(0, min(W, ((row_hi - 1) // R) // K + 1))
    return w_first, w_last


# -- AggSpec lowering (shared by tpu_engine and parallel.sharded) ------------

def lower_aggs(spec_aggs, name_to_id, kinds):
    """ScanSpec aggregates -> (device AggSigs, [(user_fn, index)] lowering).
    avg lowers to a sum accumulator; finalize() divides by n."""
    dev_aggs, lowering = [], []
    for a in spec_aggs:
        cid = name_to_id.get(a.column) if a.column else None
        kind = kinds[cid] if cid is not None else None
        fn = "sum" if a.fn == "avg" else a.fn
        lowering.append((a.fn, len(dev_aggs)))
        dev_aggs.append(dscan.AggSig(fn, cid, kind))
    return tuple(dev_aggs), lowering


def pred_literal_host(kind: str, value):
    """Predicate literal -> host (numpy) device representation. Kept on
    host so batched planners can stack many specs' literals into one
    transfer instead of queueing a tiny H2D copy per predicate."""
    if kind == "i32":
        return np.int32(int(value))
    if kind == "code":
        # Promoted string predicate: the engine already translated the
        # value to an int32 dictionary-code bound.
        return np.int32(int(value))
    if kind == "f32":
        return np.float32(value)
    if kind == "i64":
        hi, lo = PL.i64_to_ordered_planes(np.array([int(value)], dtype=np.int64))
        return np.array([hi[0], lo[0]], dtype=np.int32)
    if kind == "f64":
        hi, lo = PL.f64_to_ordered_planes(np.array([value], dtype=np.float64))
        return np.array([hi[0], lo[0]], dtype=np.int32)
    raw = (value.encode("utf-8", "surrogateescape")
           if isinstance(value, str) else bytes(value))
    hi, lo = PL.varlen_prefix_planes([raw])
    return np.array([hi[0], lo[0]], dtype=np.int32)


def pred_literal(kind: str, value):
    """Predicate literal -> device representation for its column kind."""
    return jnp.asarray(pred_literal_host(kind, value))


# -- the single-dispatch full-run aggregate program --------------------------

@functools.lru_cache(maxsize=128)
@compile_contract("full_aggregate", max_compiles=128)
def compiled_full_aggregate(sig: dscan.ScanSig):
    """One jitted program: fori_loop the [w_first, w_last) windows of the
    run, fold partials, return (ivec, fvec). One dispatch + two transfers
    per scan; window bounds are traced so bounded scans skip blocks."""
    check_limb_bound(sig.R, sig.K)

    def fn(run, row_lo, row_hi, w_first, w_last, read_hi, read_lo,
           rexp_hi, rexp_lo, pred_lits):
        init = (agg_init(sig.aggs), jnp.int32(0))
        body = functools.partial(
            fold_window, sig, run, row_lo=row_lo, row_hi=row_hi,
            read_planes=(read_hi, read_lo, rexp_hi, rexp_lo),
            pred_lits=pred_lits)
        acc, scanned = jax.lax.fori_loop(
            w_first, w_last, lambda w, c: body(w, c), init)
        return pack(sig.aggs, acc, scanned)

    return jitting.jit(fn, "full_aggregate", sig.tag())
