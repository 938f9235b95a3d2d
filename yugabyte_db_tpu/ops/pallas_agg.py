"""Pallas TPU kernel: flat-run MVCC aggregate fold.

The hottest all-device loop — visibility resolution + predicate mask +
exact integer aggregation over a whole run — written as a Pallas grid
kernel (VMEM-tiled blocks over the plane arrays, scalar-prefetched read
point/bounds/literals, one int32 partial row per grid step). It computes
EXACTLY what ops.scan's flat path + ops.agg_fold compute for eligible
signatures: COUNT(*) / COUNT(col), exact SUM over int32/int64 columns
(16-bit limb partials), and MIN/MAX over int32/int64 ordered planes,
under device-exact i32/i64 predicates, on single-version-per-key runs.
The XLA path remains the default and the oracle. Nothing routes scans
here yet: the ``tpu_engine_use_pallas`` flag has no reader (ROADMAP C3).
Tests pin both paths to identical results in interpret mode on the CPU;
``chip_smoke.py`` compiles the kernel through Mosaic on the chip.

Layout notes (pallas_guide.md): blocks are (8 tablet-blocks x R rows) so
the sublane dimension meets the (8, 128) int32 tile minimum and R (a
multiple of 128) fills lanes; the output is one (1, 128) partial row per
grid step — host-side numpy folds the tiny [G, 128] matrix.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl

from yugabyte_db_tpu.ops.scan import I32_MAX, I32_MIN, AggSig, PredSig
from yugabyte_db_tpu.utils import jitting
from yugabyte_db_tpu.utils.jitting import compile_contract

BLOCKS_PER_STEP = 8
OUT_LANES = 128

# per-aggregate slots in the partial row (after [count, scanned]):
#   count(col): 1 (masked count)
#   sum:        5 (4 limbs + n)
#   min/max:    3 (hi, lo, n)
_SLOTS = {"count": 1, "sum": 5, "min": 3, "max": 3}


def eligible(sig_flat: bool, aggs, preds) -> bool:
    """Kernel applicability: flat run, i32/i64 aggregates, i32/i64
    equality/range predicates."""
    if not sig_flat or not aggs:
        return False
    for ag in aggs:
        if ag.fn == "count":
            continue
        if ag.fn not in ("sum", "min", "max") or ag.kind not in ("i32",
                                                                 "i64"):
            return False
    return all(p.kind in ("i32", "i64") and p.op != "IN" for p in preds)


def _le2(a_hi, a_lo, b_hi, b_lo):
    return (a_hi < b_hi) | ((a_hi == b_hi) & (a_lo <= b_lo))


def _pred_mask(ps: PredSig, hi, lo, lit_hi, lit_lo):
    if ps.kind == "i32":
        v, x = hi, lit_hi
        return {"=": v == x, "!=": v != x, "<": v < x, "<=": v <= x,
                ">": v > x, ">=": v >= x}[ps.op]
    eq = (hi == lit_hi) & (lo == lit_lo)
    lt = (hi < lit_hi) | ((hi == lit_hi) & (lo < lit_lo))
    return {"=": eq, "!=": ~eq, "<": lt, "<=": lt | eq,
            ">": ~(lt | eq), ">=": ~lt}[ps.op]


def _scalar(x):
    return jnp.reshape(x.astype(jnp.int32), (1, 1))


def _kernel(aggs, preds, col_order, R, iparams_ref, *refs):
    """One grid step: resolve an (8 x R)-row slab, emit one partial row.

    refs layout: ht_hi, ht_lo, exp_hi, exp_lo, valid, tomb, live, then
    per column in col_order: set_, isnull, plane0[, plane1], and finally
    the output ref.
    """
    out_ref = refs[-1]
    ht_hi, ht_lo, exp_hi, exp_lo, valid8, tomb8, live8 = refs[:7]
    cols = {}
    i = 7
    for cid, two_plane in col_order:
        set_c = refs[i][:] != 0
        null_c = refs[i + 1][:] != 0
        p0 = refs[i + 2][:]
        p1 = refs[i + 3][:] if two_plane else None
        i += 3 + (1 if two_plane else 0)
        cols[cid] = (set_c, null_c, p0, p1)

    row_lo, row_hi = iparams_ref[0], iparams_ref[1]
    read_hi, read_lo = iparams_ref[2], iparams_ref[3]
    rexp_hi, rexp_lo = iparams_ref[4], iparams_ref[5]

    valid = valid8[:] != 0
    visible = valid & _le2(ht_hi[:], ht_lo[:], read_hi, read_lo)
    expired = _le2(exp_hi[:], exp_lo[:], rexp_hi, rexp_lo)
    alive = visible & (tomb8[:] == 0)
    live_exists = alive & (live8[:] != 0) & ~expired

    notnull = {}
    exists = live_exists
    for cid, (set_c, null_c, _p0, _p1) in cols.items():
        nn = alive & set_c & ~null_c & ~expired
        notnull[cid] = nn
        exists = exists | nn

    g = pl.program_id(0)
    sub = jax.lax.broadcasted_iota(jnp.int32, (BLOCKS_PER_STEP, R), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (BLOCKS_PER_STEP, R), 1)
    rowidx = (g * BLOCKS_PER_STEP + sub) * R + lane
    in_range = (rowidx >= row_lo) & (rowidx < row_hi)

    pre = exists & in_range & valid
    mask = pre
    li = 6
    for ps in preds:
        _s, _n, p0, p1 = cols[ps.col_id]
        lit_hi = iparams_ref[li]
        lit_lo = iparams_ref[li + 1] if ps.kind != "i32" else lit_hi
        li += 1 if ps.kind == "i32" else 2
        mask = mask & notnull[ps.col_id] & _pred_mask(ps, p0, p1, lit_hi,
                                                     lit_lo)

    parts = [_scalar(jnp.sum(mask.astype(jnp.int32))),
             _scalar(jnp.sum(pre.astype(jnp.int32)))]
    for ag in aggs:
        if ag.fn == "count":
            m = mask if ag.col_id is None else (mask & notnull[ag.col_id])
            parts.append(_scalar(jnp.sum(m.astype(jnp.int32))))
            continue
        m = mask & notnull[ag.col_id]
        _s, _n, p0, p1 = cols[ag.col_id]
        n = _scalar(jnp.sum(m.astype(jnp.int32)))
        if ag.fn == "sum":
            if ag.kind == "i32":
                u = p0.astype(jnp.uint32) ^ jnp.uint32(0x80000000)
                limbs = [(u & jnp.uint32(0xFFFF)).astype(jnp.int32),
                         (u >> jnp.uint32(16)).astype(jnp.int32),
                         jnp.zeros_like(p0), jnp.zeros_like(p0)]
            else:
                hi_u = p0.astype(jnp.uint32) ^ jnp.uint32(0x80000000)
                lo_u = p1.astype(jnp.uint32) ^ jnp.uint32(0x80000000)
                limbs = [(lo_u & jnp.uint32(0xFFFF)).astype(jnp.int32),
                         (lo_u >> jnp.uint32(16)).astype(jnp.int32),
                         (hi_u & jnp.uint32(0xFFFF)).astype(jnp.int32),
                         (hi_u >> jnp.uint32(16)).astype(jnp.int32)]
            for limb in limbs:
                parts.append(_scalar(jnp.sum(jnp.where(m, limb, 0))))
            parts.append(n)
        else:
            is_max = ag.fn == "max"
            red = jnp.max if is_max else jnp.min
            fill = I32_MIN if is_max else I32_MAX
            hi_src = p0
            mhi = red(jnp.where(m, hi_src, fill))
            if ag.kind == "i32":
                parts.append(_scalar(mhi))
                parts.append(_scalar(jnp.int32(0)))
            else:
                tie = m & (hi_src == mhi)
                mlo = red(jnp.where(tie, p1, fill))
                parts.append(_scalar(mhi))
                parts.append(_scalar(mlo))
            parts.append(n)
    row = jnp.concatenate(parts, axis=1)
    pad = OUT_LANES - row.shape[1]
    padded = jnp.concatenate(
        [row, jnp.zeros((1, pad), jnp.int32)], axis=1)
    # TPU block shapes need sublane-divisible dims: the output block is
    # (1, 8, 128) with the partial row broadcast across the 8 sublanes
    # (the host reads sublane 0)
    out_ref[:] = jnp.broadcast_to(padded, (8, OUT_LANES))[None]


@functools.lru_cache(maxsize=64)
@compile_contract("pallas_flat_aggregate", max_compiles=64)
def compiled_flat_aggregate(B: int, R: int, aggs: tuple, preds: tuple,
                            col_order: tuple, interpret: bool = False):
    """Build the pallas program for one static signature.

    col_order: tuple[(col_id, two_plane)] — the columns shipped, in ref
    order. Returns fn(plane_arrays_list, iparams) -> [G, 128] int32.
    """
    if B % BLOCKS_PER_STEP != 0:
        raise ValueError(f"B={B} not a multiple of {BLOCKS_PER_STEP}")
    grid = (B // BLOCKS_PER_STEP,)
    n_tensor = 7 + sum(3 + (1 if tp else 0) for _cid, tp in col_order)
    # with scalar prefetch, index maps receive (grid idx, scalar ref)
    block = pl.BlockSpec((BLOCKS_PER_STEP, R),
                         lambda g, _sref: (g, 0))
    kernel = functools.partial(_kernel, aggs, preds, col_order, R)

    from jax.experimental.pallas import tpu as pltpu

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,
        grid=grid,
        in_specs=[block] * n_tensor,
        out_specs=pl.BlockSpec((1, 8, OUT_LANES),
                               lambda g, _sref: (g, 0, 0)),
    )
    call = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((grid[0], 8, OUT_LANES),
                                       jnp.int32),
        interpret=interpret,
    )

    def fn(tensors, iparams):
        return call(iparams, *tensors)

    return jitting.jit(fn, "pallas_flat_aggregate",
                       jitting.tag(aggs=aggs, preds=preds, cols=col_order))


def gather_tensors(dev_arrays, col_order):
    """The plane arrays in kernel ref order. Bool planes ship as int32:
    v5e mosaic restricts sub-32-bit compares and int8 tiles need 32
    sublanes (the block here has 8). Compressed runs
    (--tpu_plane_encoding) materialize decoded planes here: the pallas
    refs are raw tiled arrays, so the decoded tensors live as a cached
    side-car on the run's residency entry instead of decoding in-kernel."""
    from yugabyte_db_tpu.ops import encodings

    if encodings.tree_encoded(dev_arrays):
        dev_arrays = jax.jit(encodings.decode_run)(dev_arrays)

    def b2i(a):
        return a.astype(jnp.int32)

    out = [dev_arrays["ht_hi"], dev_arrays["ht_lo"],
           dev_arrays["exp_hi"], dev_arrays["exp_lo"],
           b2i(dev_arrays["valid"]), b2i(dev_arrays["tomb"]),
           b2i(dev_arrays["live"])]
    for cid, two_plane in col_order:
        c = dev_arrays["cols"][cid]
        out.append(b2i(c["set"]))
        out.append(b2i(c["isnull"]))
        out.append(c["cmp"][:, :, 0])
        if two_plane:
            out.append(c["cmp"][:, :, 1])
    return out


def combine_partials(partials: np.ndarray, aggs) -> tuple:
    """[G, 8, 128] int32 partial rows (sublane 0 carries the data) ->
    (count, scanned, per-agg value)."""
    partials = partials[:, 0, :]
    count = int(partials[:, 0].sum())
    scanned = int(partials[:, 1].sum())
    vals = []
    off = 2
    for ag in aggs:
        if ag.fn == "count":
            vals.append(int(partials[:, off].sum()))
            off += 1
            continue
        if ag.fn == "sum":
            limbs = partials[:, off:off + 4].astype(object).sum(axis=0)
            n = int(partials[:, off + 4].sum())
            off += 5
            u = sum(int(d) << (16 * k) for k, d in enumerate(limbs))
            if ag.kind == "i32":
                vals.append(u - n * (1 << 31) if n else None)
            else:
                vals.append(u - n * (1 << 63) if n else None)
            continue
        his = partials[:, off]
        los = partials[:, off + 1]
        ns = partials[:, off + 2]
        off += 3
        live = ns > 0
        if not live.any():
            vals.append(None)
            continue
        pairs = list(zip(his[live].tolist(), los[live].tolist()))
        best = max(pairs) if ag.fn == "max" else min(pairs)
        if ag.kind == "i32":
            vals.append(best[0])
        else:
            from yugabyte_db_tpu.utils import planes as P

            vals.append(int(P.ordered_planes_to_i64(
                np.array([best[0]], np.int32),
                np.array([best[1]], np.int32))[0]))
    return count, scanned, vals
