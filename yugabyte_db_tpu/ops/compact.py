"""Device compaction filter: vectorized history GC over a merged order.

Reference analog: DocDBCompactionFilter inside CompactionJob::Run — the
per-version retention decision (drop overwritten / TTL-expired /
history-GC'd versions) made while merging K sorted runs
(src/yb/rocksdb/db/compaction_job.cc:622,
src/yb/docdb/docdb_compaction_filter.cc).

Division of labor (measured): XLA's variadic sort compiles catastrophically
slowly for 10-key lexsorts, while numpy's np.lexsort is vectorized C — so
the engine computes the merge ORDER host-side (exact whenever keys fit the
32-byte prefix planes) and this kernel computes the RETENTION MASK over
the sorted union in one dispatch: visibility at the cutoff, tombstone
shadowing, per-column/liveness contributors, and equal-hybrid-time span
propagation — mirroring CpuStorageEngine._gc_versions exactly.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from yugabyte_db_tpu.ops import encodings
from yugabyte_db_tpu.ops.scan import I32_MAX, le2
from yugabyte_db_tpu.utils import jitting
from yugabyte_db_tpu.utils.jitting import compile_contract


def _seg_min(vals, gid, n):
    return jax.ops.segment_min(vals, gid, num_segments=n,
                               indices_are_sorted=True)


def _seg_max(vals, gid, n):
    return jax.ops.segment_max(vals, gid, num_segments=n,
                               indices_are_sorted=True)


def gc_mask(num_cols: int, N: int, s, cutoff_planes, keep_tombstones=False):
    """Retention mask over the SORTED union (key asc, ht desc).

    ``s`` = {new_group, tomb, live: [N] bool; ht_hi, ht_lo, exp_hi,
    exp_lo: [N] i32; set_: [num_cols, N] bool}. Returns keep[N] bool.
    ``keep_tombstones`` (a scalar, traced): the union is not all there
    is of its keys, so each group's newest row tombstone at or under
    the cutoff is kept (CpuStorageEngine._gc_versions).
    """
    ht_hi, ht_lo = s["ht_hi"], s["ht_lo"]
    gid = jnp.cumsum(s["new_group"].astype(jnp.int32)) - 1
    ridx = jnp.arange(N, dtype=jnp.int32)
    c_hi, c_lo, ce_hi, ce_lo = cutoff_planes

    # Visibility + tombstone shadowing AT THE CUTOFF.
    visible = le2(ht_hi, ht_lo, c_hi, c_lo)
    sentinel = jnp.int32(-2**31)
    t_hi = _seg_max(jnp.where(visible & s["tomb"], ht_hi, sentinel), gid, N)
    t_hi_r = t_hi[gid]
    t_lo = _seg_max(jnp.where(visible & s["tomb"] & (ht_hi == t_hi_r),
                              ht_lo, sentinel), gid, N)
    t_lo_r = t_lo[gid]
    has_tomb = t_hi_r != sentinel
    shadowed = has_tomb & le2(ht_hi, ht_lo, t_hi_r, t_lo_r)
    alive = visible & ~s["tomb"] & ~shadowed

    # Contributors at the cutoff: first alive setter per column (expiry
    # does NOT matter for contribution — an expired value still shadows),
    # plus the first alive NON-expired liveness.
    is_contrib = jnp.zeros((N,), jnp.bool_)
    for c in range(num_cols):
        set_c = s["set_"][c]
        first = _seg_min(jnp.where(alive & set_c, ridx, I32_MAX), gid, N)
        is_contrib = is_contrib | (first[gid] == ridx)
    expired = le2(s["exp_hi"], s["exp_lo"], ce_hi, ce_lo)
    lfirst = _seg_min(jnp.where(alive & s["live"] & ~expired, ridx,
                                I32_MAX), gid, N)
    is_contrib = is_contrib | (lfirst[gid] == ridx)

    # The CPU GC keys its contributing set by hybrid time: versions
    # sharing a contributor's ht are kept together. Equal-ht rows of a
    # group are adjacent in the sorted order — propagate over spans.
    prev_hi = jnp.concatenate([ht_hi[:1], ht_hi[:-1]])
    prev_lo = jnp.concatenate([ht_lo[:1], ht_lo[:-1]])
    new_span = s["new_group"] | (ht_hi != prev_hi) | (ht_lo != prev_lo)
    sid = jnp.cumsum(new_span.astype(jnp.int32)) - 1
    span_contrib = jax.ops.segment_max(is_contrib.astype(jnp.int32), sid,
                                       num_segments=N,
                                       indices_are_sorted=True)
    kept_contrib = span_contrib[sid] > 0

    newer = ~visible  # ht > cutoff: always retained
    top_tomb = (visible & s["tomb"] & (ht_hi == t_hi_r)
                & (ht_lo == t_lo_r))
    return newer | (kept_contrib & ~le2(ht_hi, ht_lo, t_hi_r, t_lo_r)) \
        | (top_tomb & keep_tombstones)


@functools.lru_cache(maxsize=32)
@compile_contract("gc_mask", max_compiles=32)
def compiled_gc_mask(num_cols: int, N: int):
    return jitting.jit(functools.partial(gc_mask, num_cols, N), "gc_mask",
                       jitting.tag(cols=num_cols))


# -- host-vectorized twin ----------------------------------------------------

def gc_mask_host(num_cols: int, s, cutoff_planes,
                 keep_tombstones: bool = False) -> "np.ndarray":
    """Numpy twin of gc_mask (reduceat segment reductions) for unions
    small enough that a device round trip costs more than the mask:
    every dispatch pays a synchronous fetch cycle plus a ~4B/row index
    upload, while these ~15 vectorized passes scale linearly with the
    union (storage.tpu_engine.HOST_GC_MASK_MAX is the crossover). The
    device kernel is the route above it; both paths must return
    identical masks (pinned by the compaction oracle tests, which force
    each route)."""
    import numpy as np

    ht_hi, ht_lo = s["ht_hi"], s["ht_lo"]
    N = ht_hi.shape[0]
    c_hi, c_lo, ce_hi, ce_lo = (int(x) for x in cutoff_planes)

    def le2s(a_hi, a_lo, b_hi, b_lo):
        return (a_hi < b_hi) | ((a_hi == b_hi) & (a_lo <= b_lo))

    gs_idx = np.flatnonzero(s["new_group"])
    sizes = np.diff(np.append(gs_idx, N))

    def seg_max(vals):
        return np.repeat(np.maximum.reduceat(vals, gs_idx), sizes)

    def seg_min(vals):
        return np.repeat(np.minimum.reduceat(vals, gs_idx), sizes)

    visible = le2s(ht_hi, ht_lo, c_hi, c_lo)
    sentinel = np.int32(-2**31)
    vt = visible & s["tomb"]
    t_hi_r = seg_max(np.where(vt, ht_hi, sentinel))
    t_lo_r = seg_max(np.where(vt & (ht_hi == t_hi_r), ht_lo, sentinel))
    has_tomb = t_hi_r != sentinel
    shadowed = has_tomb & le2s(ht_hi, ht_lo, t_hi_r, t_lo_r)
    alive = visible & ~s["tomb"] & ~shadowed

    ridx = np.arange(N, dtype=np.int64)
    imax = np.int64(np.iinfo(np.int64).max)
    is_contrib = np.zeros(N, dtype=bool)
    for c in range(num_cols):
        first = seg_min(np.where(alive & s["set_"][c], ridx, imax))
        is_contrib |= first == ridx
    expired = le2s(s["exp_hi"], s["exp_lo"], ce_hi, ce_lo)
    lfirst = seg_min(np.where(alive & s["live"] & ~expired, ridx, imax))
    is_contrib |= lfirst == ridx

    new_span = s["new_group"] | np.concatenate(
        [[True], (ht_hi[1:] != ht_hi[:-1]) | (ht_lo[1:] != ht_lo[:-1])])
    span_idx = np.flatnonzero(new_span)
    span_sizes = np.diff(np.append(span_idx, N))
    kept_contrib = np.repeat(
        np.maximum.reduceat(is_contrib.astype(np.int8), span_idx),
        span_sizes) > 0

    newer = ~visible
    keep = newer | (kept_contrib & ~le2s(ht_hi, ht_lo, t_hi_r, t_lo_r))
    if keep_tombstones:
        keep |= vt & (ht_hi == t_hi_r) & (ht_lo == t_lo_r)
    return keep


# -- resident-plane variant --------------------------------------------------

_PAD_ZLO = -(1 << 31)  # low plane of value 0 (bias-flipped)


@compile_contract("resident_gc_mask", max_compiles=64)
@jax.jit
def resident_gc_mask(runs_planes, idx, new_group, cutoff_planes,
                     keep_tombstones=False):
    """gc_mask over the merge order WITHOUT shipping the union's planes:
    the runs' planes are already HBM-resident (ops.device_run), so the
    host uploads only the sorted row-index vector (idx[i] = flat index
    into the concatenation of the runs' flattened planes; -1 = padding,
    synthesized as hybrid-time-0 non-contributors) plus the new_group
    bits. Cuts per-compaction host->device traffic ~10x.

    runs_planes: tuple of {ht_hi, ht_lo, exp_hi, exp_lo, tomb, live:
    [B, R] device arrays; sets: tuple of per-column set planes}.
    """
    pads = idx < 0
    safe = jnp.maximum(idx, 0)

    def dec(r, leaf):
        # Encoded resident planes (--tpu_plane_encoding) decode inline;
        # tomb always carries block dims (bits or plain), giving the
        # run's (B, R) for block-dimension-free leaves (const).
        B, R = encodings.leaf_dims(r["tomb"])
        return encodings.decode_leaf(leaf, B, R).reshape(-1)

    def take(name, fill):
        cat = jnp.concatenate([dec(r, r[name]) for r in runs_planes])
        return jnp.where(pads, jnp.asarray(fill, cat.dtype), cat[safe])

    s = {
        "new_group": new_group,
        "ht_hi": take("ht_hi", 0),
        "ht_lo": take("ht_lo", _PAD_ZLO),
        "exp_hi": take("exp_hi", 0),
        "exp_lo": take("exp_lo", _PAD_ZLO),
        "tomb": take("tomb", False),
        "live": take("live", False),
    }
    num_cols = len(runs_planes[0]["sets"])
    sets = []
    for c in range(num_cols):
        cat = jnp.concatenate([dec(r, r["sets"][c])
                               for r in runs_planes])
        sets.append(jnp.where(pads, False, cat[safe]))
    s["set_"] = (jnp.stack(sets) if sets
                 else jnp.zeros((0, idx.shape[0]), jnp.bool_))
    return gc_mask(num_cols, idx.shape[0], s, cutoff_planes,
                   keep_tombstones)
