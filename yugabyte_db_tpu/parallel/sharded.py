"""Sharded multi-tablet aggregate: shard_map over a ("t", "b") mesh.

Layout: every tablet's ColumnarRun planes are stacked to [T, B, R, ...] and
placed with NamedSharding(P("t", "b")) — tablets split over the "t" mesh
axis (data parallel; the reference's unit of sharding, one tablet per
scanning thread at best), blocks of each tablet split over "b" (sequence
parallel; no reference analog — a tablet scan there is strictly
single-threaded). Each device fori_loops scan windows over its local
(tablet, block-range) shard reusing ops.scan.scan_window, folds exact
per-block aggregate partials into carry-safe accumulators, and the final
combine rides ICI collectives:

- count / n / fsum: ``psum`` over both axes;
- integer sums: base-2^16 digit vectors (int32) with a carry-propagation
  step per window so digits never overflow int32, ``psum``-ed then
  recombined host-side in arbitrary precision — bit-exact at any scale;
- min/max: two-int32-plane lexicographic maxima via a two-step collective
  (pmax on the high plane, then pmax on the tie-masked low plane).

Group/window invariant: key groups never span blocks (storage.columnar
build invariant), so any contiguous block range — in particular a device's
"b"-shard — is segment-complete and partials add up exactly.

Reference analog of the combine being replaced: the client-side merge of
per-tablet partial aggregates (src/yb/yql/cql/ql/exec/eval_aggr.cc,
src/yb/docdb/pgsql_operation.cc:473).
"""

from __future__ import annotations

import contextlib
import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from yugabyte_db_tpu.models.schema import Schema
from yugabyte_db_tpu.ops import encodings
from yugabyte_db_tpu.ops import group_agg
from yugabyte_db_tpu.ops import row_gather as RG
from yugabyte_db_tpu.ops import scan as dscan
from yugabyte_db_tpu.parallel import meshcompat
from yugabyte_db_tpu.utils import jitting, metrics
from yugabyte_db_tpu.utils.jitting import compile_contract
from yugabyte_db_tpu.ops.agg_fold import (agg_init, check_limb_bound,
                                          finalize, fold_window, lower_aggs,
                                          pred_literal)
from yugabyte_db_tpu.ops.scan import I32_MAX, I32_MIN
from yugabyte_db_tpu.storage.columnar import ColumnarRun
from yugabyte_db_tpu.storage.residency import device_nbytes, hbm_cache
from yugabyte_db_tpu.storage.scan_spec import (ScanResult, ScanSpec,
                                                combine_grouped)
from yugabyte_db_tpu.utils import planes as PL
from yugabyte_db_tpu.utils.memtracker import root_tracker


# -- host-side assembly ------------------------------------------------------

def shard_dev_bytes(tree) -> dict:
    """Per-device byte map of a sharded array pytree: each leaf's
    addressable shards charged to the chip holding them — the
    ``dev_bytes`` the residency cache partitions its budget by.
    Replicated leaves charge every device (each holds a copy)."""
    from yugabyte_db_tpu.ops.device_run import device_label

    out: dict[str, int] = {}
    stack = [tree]
    while stack:
        node = stack.pop()
        if isinstance(node, dict):
            stack.extend(node.values())
        elif isinstance(node, (list, tuple)):
            stack.extend(node)
        elif node is not None:
            for sh in node.addressable_shards:
                lbl = device_label(sh.device)
                out[lbl] = (out.get(lbl, 0)
                            + int(sh.data.size) * sh.data.dtype.itemsize)
    return out


# -- encoding-aware tree structure -------------------------------------------
#
# Stacked planes may carry compressed leaves (ops.encodings): a leaf is
# either a plain [T, B, ...] ndarray or a single-key dict naming the
# encoding. shard_map in_specs, per-tablet slicing and device placement
# all dispatch on that structure, captured once per stack as a hashable
# ``enc_struct`` so the compiled-program caches key on it.

_ENC_SPEC_PARTS = {
    "bits": ("bw",),
    "delta16": ("dbase", "doff"),
    "dict": ("codes",),
}


def _tree_struct(tree):
    """Hashable encoding structure of a stacked plane tree: leaf name ->
    encoding kind (None = plain), per top-level plane and per column."""
    planes = tuple(sorted((n, encodings.leaf_kind(l))
                          for n, l in tree.items() if n != "cols"))
    cols = tuple(sorted(
        (cid, tuple(sorted((n, encodings.leaf_kind(p))
                           for n, p in col.items())))
        for cid, col in tree["cols"].items()))
    return planes, cols


def _leaf_spec(kind, spec_tb):
    """shard_map PartitionSpec subtree for one leaf: components carrying
    the (tablet, block) axes shard P("t", "b"); components without a
    block axis (const cval, dict dhi/dlo) replicate."""
    if kind is None:
        return spec_tb
    if kind == "const":
        return {"const": {"cval": P()}}
    parts = {n: spec_tb for n in _ENC_SPEC_PARTS[kind]}
    if kind == "dict":
        parts["dhi"] = P()
        parts["dlo"] = P()
    return {kind: parts}


def _specs_from_struct(struct, spec_tb):
    planes, cols = struct
    out = {n: _leaf_spec(k, spec_tb) for n, k in planes}
    out["cols"] = {cid: {n: _leaf_spec(k, spec_tb) for n, k in entry}
                   for cid, entry in cols}
    return out


def _tree_shardings(struct, mesh):
    specs = _specs_from_struct(struct, P("t", "b"))
    return jax.tree.map(lambda s: NamedSharding(mesh, s), specs,
                        is_leaf=lambda x: isinstance(x, P))


def _tablet_slice(tree, t):
    """Slice one tablet out of a device-local [Tl, Bl, ...] shard tree,
    keeping encoded-leaf structure: replicated components (const cval,
    dict dhi/dlo) carry no tablet axis and pass through unchanged."""
    def one(leaf):
        k = encodings.leaf_kind(leaf)
        if k is None:
            return leaf[t]
        if k == "const":
            return leaf
        no_t = {"dict": ("dhi", "dlo")}.get(k, ())
        return {k: {n: (a if n in no_t else a[t])
                    for n, a in leaf[k].items()}}

    out = {n: one(l) for n, l in tree.items() if n != "cols"}
    out["cols"] = {cid: {n: one(p) for n, p in col.items()}
                   for cid, col in tree["cols"].items()}
    return out


def _encode_stack(stacked):
    """Re-encode stacked [T, B, ...] planes with the host encoders
    (ops.encodings) over the flattened [T*B, ...] block axis, then fold
    the leading axis of every block-dimensioned component back to
    [T, B, ...]. Padding (invalid blocks / pad tablets) is already baked
    into the plain planes, so decode is byte-identical by construction.
    The stack-level encoder never emits dict leaves (those come from
    per-run device flush output) and never "rle" leaves: an rle plane
    decodes by a ``jnp.take``, and a gather is serialized on the TPU
    (5.6 ms for a plane of 786K rows), in every program that reads the
    plane; a table loaded in few large batches has its commit times in
    exactly such long runs. Pathological planes stay plain."""
    T, B = stacked["valid"].shape[:2]

    def int_plane(plane):
        c = encodings.encode_const(plane)
        return c if c is not None else encodings._pick_smaller(
            plane, [encodings.encode_delta16(plane)])

    def float_plane(plane):
        c = encodings.encode_const(plane)
        return plane if c is None else c

    def enc(plane, how):
        leaf = how(plane.reshape((T * B,) + plane.shape[2:]))
        k = encodings.leaf_kind(leaf)
        if k is None:
            return plane
        if k == "const":
            return leaf
        return {k: {n: a.reshape((T, B) + a.shape[1:])
                    for n, a in leaf[k].items()}}

    out = {n: enc(stacked[n], encodings.encode_bool_plane)
           for n in ("valid", "group_start", "tomb", "live")}
    for n in ("ht_hi", "ht_lo", "exp_hi", "exp_lo"):
        out[n] = enc(stacked[n], int_plane)
    out["cols"] = {}
    for cid, col in stacked["cols"].items():
        e = {"set": enc(col["set"], encodings.encode_bool_plane),
             "isnull": enc(col["isnull"], encodings.encode_bool_plane),
             "cmp": enc(col["cmp"], int_plane)}
        if "arith" in col:
            e["arith"] = enc(col["arith"], float_plane)
        out["cols"][cid] = e
    return out


class ShardedTablets:
    """Stacked, mesh-sharded device residency for T tablets' single runs.

    Each tablet contributes one ColumnarRun (compact first); runs are padded
    to a common block count divisible by mesh_b * window and stacked to
    [T, B, R, ...]. Dummy all-invalid tablets pad T to a multiple of mesh_t.
    """

    def __init__(self, schema: Schema, runs: list[ColumnarRun], mesh: Mesh,
                 window_blocks: int = 8, encode: bool | None = None):
        if not runs:
            raise ValueError("need at least one run")
        R = runs[0].R
        if any(r.R != R for r in runs):
            raise ValueError("all runs must share rows_per_block")
        self.schema = schema
        self.mesh = mesh
        self.K = window_blocks
        self.R = R
        mesh_t = mesh.shape["t"]
        mesh_b = mesh.shape["b"]
        self.T = len(runs)
        self.runs = runs
        pad_t = (-self.T) % mesh_t
        chunk = mesh_b * window_blocks
        Bmax = max(r.B for r in runs)
        self.B = Bmax + ((-Bmax) % chunk)
        self.Bl = self.B // mesh_b
        if self.Bl % window_blocks:
            raise AssertionError("local block count not a window multiple")

        stacked = self._stack(runs, pad_t)
        if encode is None:
            from yugabyte_db_tpu.utils.flags import FLAGS
            encode = FLAGS.get("tpu_plane_encoding") != "off"
        if encode:
            stacked = _encode_stack(stacked)
        self.enc_struct = _tree_struct(stacked)
        self.encoded = encodings.tree_encoded(stacked)
        # grouped signature -> bytes one chip's shard holds of its planes
        # (stack_read_bytes; update_tablet keeps the tree's structure)
        self._read_bytes: dict = {}
        # Mesh placement must shard, not cache: plane-group residency for
        # sharded arrays is accounted (and pinned) via add_external below.
        self.arrays = jax.tree.map(
            lambda a, s: jax.device_put(a, s),  # yb-lint: disable=ijax/unmanaged-device-put
            stacked, _tree_shardings(self.enc_struct, mesh))
        self.padded_T = self.T + pad_t
        # The stacked mesh arrays live outside the demand-upload path but
        # inside the same HBM budget: account them as a pinned external
        # entry so /memz, /metrics and eviction pressure see them.  The
        # charge is a per-device map — one shard's bytes on the chip
        # that actually holds it — so each chip's budget bucket sees its
        # true share, not T devices each blamed for the whole stack.
        self._res_key = hbm_cache().add_external(
            self, device_nbytes(self.arrays),
            root_tracker().child("device").child("sharded"), "sharded_mesh",
            dev_bytes=shard_dev_bytes(self.arrays))

    def close(self) -> None:
        """Release the mesh arrays' residency accounting. The arrays
        stay usable for scans already holding this stack (they free when
        the last reference dies) — a flush/compaction can supersede a
        stack mid-serve without crashing the in-flight page."""
        if self._res_key is not None:
            hbm_cache().invalidate(self._res_key)
            self._res_key = None

    def update_tablet(self, t: int, run: ColumnarRun,
                      device_arrays=None) -> bool:
        """Replace tablet ``t``'s slot of the stacked mesh arrays in
        place (one jitted dynamic_update_slice over the tree) — the
        incremental path when a flush/compaction swaps a single tablet's
        run. ``device_arrays``, when given, is a DeviceRun.arrays tree
        already ON device (ops.flush output): its planes reshard over
        the mesh directly, no host round trip. Returns False when the
        stack must be rebuilt instead (encoded stack, block overflow,
        row-shape or column mismatch); residency accounting is unchanged
        either way because every shape is."""
        if self.encoded or t >= self.T or run.R != self.R:
            return False
        if max(run.B, 1) > self.B:
            return False
        src = None
        if device_arrays is not None:
            src = self._device_src(device_arrays)
        if src is None:
            src = self._stack([run], 0)
        if _tree_struct(src) != self.enc_struct:
            return False
        spec_b = P(None, "b")
        src = jax.tree.map(
            lambda a: jax.device_put(a, NamedSharding(self.mesh, spec_b)),  # yb-lint: disable=ijax/unmanaged-device-put
            src)
        cols_desc = tuple(sorted(
            (cid, "arith" in col)
            for cid, col in self.arrays["cols"].items()))
        fn = _compiled_stack_update(self.padded_T, self.B, self.R,
                                    cols_desc)
        out = fn(self.arrays, src, jnp.int32(t))
        # Pin the result back to the stack's sharding (GSPMD is free to
        # choose otherwise for the update program's output).
        self.arrays = jax.tree.map(
            lambda a, s: jax.device_put(a, s),  # yb-lint: disable=ijax/unmanaged-device-put
            out, _tree_shardings(self.enc_struct, self.mesh))
        self.runs = list(self.runs)
        self.runs[t] = run
        return True

    def _device_src(self, arrays):
        """[1, self.B, ...] plain source tree built from device-resident
        run planes: encoded leaves decode ON DEVICE (ops.encodings jnp
        decode — dict cmp drops its third code plane), the block axis
        pads to the stack's B with the stack's padding values. Returns
        None when the planes don't fit the stack's shape."""
        B = int(arrays["valid"].shape[0])
        if B > self.B or arrays["valid"].shape[1] != self.R:
            return None

        def prep(leaf, ones=False):
            k = encodings.leaf_kind(leaf)
            if k is not None:
                leaf = encodings.decode_leaf(leaf, B, self.R)
                if k == "dict":
                    leaf = leaf[..., :2]
            leaf = jnp.asarray(leaf)
            pad = self.B - leaf.shape[0]
            if pad:
                fill = (jnp.ones if ones else jnp.zeros)(
                    (pad,) + leaf.shape[1:], leaf.dtype)
                leaf = jnp.concatenate([leaf, fill], axis=0)
            return leaf[None]

        out = {n: prep(arrays[n], ones=(n == "group_start"))
               for n in ("valid", "group_start", "tomb", "live",
                         "ht_hi", "ht_lo", "exp_hi", "exp_lo")}
        out["cols"] = {cid: {n: prep(p) for n, p in col.items()}
                       for cid, col in arrays["cols"].items()}
        return out

    def _stack(self, runs, pad_t):
        B, R = self.B, self.R
        T = len(runs) + pad_t

        def alloc(shape, dtype, fill=0):
            return np.full((T, B) + shape, fill, dtype=dtype)

        def like(plane):
            # Pad rows are invalid, so their times are never read: they
            # take the first row's, and a plane that is one value in
            # every run (no TTL anywhere) stays a "const" leaf.
            return alloc((R,), np.int32,
                         plane.flat[0] if plane.size else 0)

        out = {
            "valid": alloc((R,), bool, False),
            # pad rows are their own groups so they never join a real one
            "group_start": alloc((R,), bool, True),
            "tomb": alloc((R,), bool, False),
            "live": alloc((R,), bool, False),
            "ht_hi": like(runs[0].ht_hi),
            "ht_lo": like(runs[0].ht_lo),
            "exp_hi": like(runs[0].exp_hi),
            "exp_lo": like(runs[0].exp_lo),
            "cols": {},
        }
        for c in self.schema.value_columns:
            nplanes = runs[0].cols[c.col_id].cmp_planes.shape[-1]
            entry = {
                "set": alloc((R,), bool, False),
                "isnull": alloc((R,), bool, False),
                "cmp": alloc((R, nplanes), np.int32),
            }
            if runs[0].cols[c.col_id].arith is not None:
                entry["arith"] = alloc((R,), np.float32)
            out["cols"][c.col_id] = entry
        for t, run in enumerate(runs):
            b = run.B
            out["valid"][t, :b] = run.valid
            out["group_start"][t, :b] = run.group_start
            out["tomb"][t, :b] = run.tomb
            out["live"][t, :b] = run.live
            out["ht_hi"][t, :b] = run.ht_hi
            out["ht_lo"][t, :b] = run.ht_lo
            out["exp_hi"][t, :b] = run.exp_hi
            out["exp_lo"][t, :b] = run.exp_lo
            for cid, col in run.cols.items():
                e = out["cols"][cid]
                e["set"][t, :b] = col.set_
                e["isnull"][t, :b] = col.isnull
                e["cmp"][t, :b] = col.cmp_planes
                if col.arith is not None:
                    e["arith"][t, :b] = col.arith
        return out

    # -- per-tablet exact row bounds (host bisection over full key bytes) ---
    def row_bounds(self, lower: bytes, upper: bytes):
        lo = np.zeros(self.padded_T, dtype=np.int32)
        hi = np.zeros(self.padded_T, dtype=np.int32)
        for t, run in enumerate(self.runs):
            lo[t] = run.lower_row(lower)
            hi[t] = run.upper_row(upper)
        return lo, hi


# -- the device program ------------------------------------------------------

def _lex_collective_ext(hi, lo, is_max, axes):
    """Lexicographic (hi, lo) extreme across mesh axes: pmax the high plane,
    then pmax the low plane masked to high-plane ties."""
    red = jax.lax.pmax if is_max else jax.lax.pmin
    fill = I32_MIN if is_max else I32_MAX
    mhi = red(hi, axes)
    mlo = red(jnp.where(hi == mhi, lo, fill), axes)
    return mhi, mlo


def _combine_across_mesh(sig_aggs, acc, scanned, axes=("t", "b")):
    out = []
    for ag, a in zip(sig_aggs, acc):
        if ag.fn == "count":
            out.append({"count": jax.lax.psum(a["count"], axes)})
        elif ag.fn == "sum":
            if ag.kind in ("f32", "f64"):
                out.append({"fsum": jax.lax.psum(a["fsum"], axes),
                            "fcomp": jax.lax.psum(a["fcomp"], axes),
                            "n": jax.lax.psum(a["n"], axes)})
            else:
                out.append({"digits": jax.lax.psum(a["digits"], axes),
                            "n": jax.lax.psum(a["n"], axes)})
        else:
            is_max = ag.fn == "max"
            n = jax.lax.psum(a["n"], axes)
            if ag.kind == "f32":
                red = jax.lax.pmax if is_max else jax.lax.pmin
                out.append({"fext": red(a["fext"], axes), "n": n})
            elif ag.kind == "i32":
                red = jax.lax.pmax if is_max else jax.lax.pmin
                out.append({"ext": red(a["ext"], axes), "n": n})
            else:
                mhi, mlo = _lex_collective_ext(a["ext_hi"], a["ext_lo"],
                                               is_max, axes)
                out.append({"ext_hi": mhi, "ext_lo": mlo, "n": n})
    return out, jax.lax.psum(scanned, axes)


def _shard_body(sig: dscan.ScanSig, Tl: int, Bl: int, R: int,
                run, row_lo, row_hi, read_hi, read_lo, rexp_hi, rexp_lo,
                pred_lits):
    """Runs on one device over its [Tl, Bl, R] shard. Returns replicated
    combined aggregate partials + scanned-row count."""
    K = sig.K
    W = Bl // K
    block_off = jax.lax.axis_index("b") * Bl
    # Loop carries become device-varying inside the loop body; mark the
    # replicated initial values as varying so the carry types match.
    varying = lambda x: meshcompat.varying(x, ("t", "b"))
    acc = jax.tree.map(varying, agg_init(sig.aggs))
    scanned = varying(jnp.int32(0))
    for t in range(Tl):
        local = _tablet_slice(run, t)
        lo_t, hi_t = row_lo[t], row_hi[t]
        body = functools.partial(
            fold_window, sig, local, row_lo=lo_t, row_hi=hi_t,
            read_planes=(read_hi, read_lo, rexp_hi, rexp_lo),
            pred_lits=pred_lits, block_off=block_off)
        # Local window bounds: only windows of this shard overlapping the
        # tablet's row range (floor division is floor for negatives too).
        w_first = jnp.clip((lo_t // R - block_off) // K, 0, W)
        w_last = jnp.clip(((hi_t - 1) // R - block_off) // K + 1, 0, W)
        acc, scanned = jax.lax.fori_loop(
            w_first, w_last, lambda w, c: body(w, c), (acc, scanned))
    return _combine_across_mesh(sig.aggs, acc, scanned)


@functools.lru_cache(maxsize=64)
@compile_contract("dist_agg", max_compiles=64)
def _compiled_dist_agg(sig: dscan.ScanSig, mesh: Mesh, enc_struct,
                       Tl: int, Bl: int):
    """One jitted shard_map program per (scan signature, mesh, stack
    encoding structure). Mesh is hashable and the cache entry keeps it
    alive only until eviction."""
    spec_tb = P("t", "b")
    in_specs = (
        _specs_from_struct(enc_struct, spec_tb),  # stacked run pytree
        P("t"), P("t"),            # row bounds
        P(), P(), P(), P(),        # read/expiry planes
        P(),                       # predicate literals (replicated)
    )
    body = functools.partial(_shard_body, sig, Tl, Bl, sig.R)
    smapped = meshcompat.shard_map(body, mesh, in_specs,
                                   (_acc_specs(sig), P()))
    return jitting.jit(smapped, "dist_agg", sig.tag())


# -- the grouped program ------------------------------------------------------
#
# GROUP BY and expression aggregates (TPC-H Q1, Q6): the per-shard body
# is ops.group_agg's window loop as the single-chip engine runs it, over
# this shard's block range of each local tablet. Dictionaries, ``rep``
# rows and string group values belong to ONE run, so bucket tables of
# different tablets are never added on the device: across "b" (one
# tablet's block ranges) the shards' partial tables are combined by
# collectives, across "t" every tablet keeps its own packed vector
# (``out_specs`` on "t") and the host finishes each with that tablet's
# ColumnarRun, then combines as it combines per-tablet replies.

def _combine_blocks(acc: dict, row_base):
    """One tablet's partial bucket tables of the "b" shards -> the
    tablet's table, replicated over "b". Sums by ``psum`` (digit
    vectors carry-normalized again behind it: a shard's digits are under
    2^17, so no int32 digit overflows below 2^13 shards); ``rep`` (local
    to the shard's rows: ``row_base`` makes it the run's) by ``pmin``;
    a bucket's key from the shard that holds its ``rep``, and a shard
    whose rows of that bucket have another key counts them as
    collisions, which send the host to the per-tablet path."""
    out = {}
    for name, v in acc.items():
        if name in ("rep", "key"):
            continue
        v = jax.lax.psum(v, "b")
        out[name] = group_agg._carry_norm(v) if v.ndim == 2 else v
    has = acc["count"] > 0
    rep = jnp.where(acc["rep"] == I32_MAX, I32_MAX, acc["rep"] + row_base)
    out["rep"] = jax.lax.pmin(rep, "b")
    mine = has & (rep == out["rep"])
    out["key"] = jax.lax.psum(
        jnp.where(mine[:, None], acc["key"], jnp.int32(0)), "b")
    differs = has & jnp.any(acc["key"] != out["key"], axis=1)
    out["collisions"] = out["collisions"] + jax.lax.psum(
        jnp.sum(jnp.where(differs, acc["count"], jnp.int32(0))), "b")
    return out


def _grouped_body(sig: group_agg.GroupAggSig, Tl: int, Bl: int, run,
                  params):
    """Per device: ops.group_agg's program over each local tablet's
    [Bl, R] shard. ``params[t]`` is the tablet's packed parameter vector
    (group_agg.pack_params) with the RUN's row bounds; each shard
    rebases them to its own block range and walks only the windows that
    overlap (none where the range misses the shard)."""
    n = group_agg.int_params(sig)
    shard_rows = Bl * sig.R
    KR = sig.K * sig.R
    Wl = Bl // sig.K
    row_base = jax.lax.axis_index("b") * shard_rows
    layout = group_agg.out_layout(sig)
    outs = []
    for t in range(Tl):
        p = params[t]
        lo = jnp.clip(p[2] - row_base, 0, shard_rows)
        hi = jnp.clip(p[3] - row_base, 0, shard_rows)
        w_first = jnp.clip(lo // KR, 0, Wl - 1)
        w_last = jnp.where(hi > lo, jnp.clip((hi - 1) // KR, 0, Wl - 1),
                           w_first - 1)
        ip = jnp.concatenate([jnp.stack([w_first, w_last, lo, hi]), p[4:n]])
        acc = group_agg.grouped_aggregate(
            sig, _tablet_slice(run, t), ip,
            jax.lax.bitcast_convert_type(p[n:], jnp.float32))
        acc = _combine_blocks(acc, row_base)
        outs.append(jnp.concatenate(
            [acc[name].reshape(-1) for name in layout]))
    return jnp.stack(outs)


@functools.lru_cache(maxsize=64)
@compile_contract("dist_grouped_aggregate", max_compiles=64)
def _compiled_dist_grouped(sig: group_agg.GroupAggSig, mesh: Mesh,
                           enc_struct, Tl: int, Bl: int):
    """One jitted shard_map program per (grouped signature, mesh, stack
    encoding structure): ``(stack arrays, params i32[T, P + F]) ->
    i32[T, L]``, a packed vector a tablet (group_agg.out_layout)."""
    group_agg.check_window_bound(sig)
    body = functools.partial(_grouped_body, sig, Tl, Bl)
    smapped = meshcompat.shard_map(
        body, mesh, (_specs_from_struct(enc_struct, P("t", "b")), P("t")),
        P("t"), check_vma=False)  # (the kernel: see meshcompat)
    return jitting.jit(smapped, "dist_grouped_aggregate", sig.tag())


@functools.lru_cache(maxsize=32)
@compile_contract("stack_update", max_compiles=32)
def _compiled_stack_update(padded_T: int, B: int, R: int, cols_desc):
    """One in-place tablet-slot update program per stack shape: every
    leaf gets its [1, B, ...] source written at block row ``t`` with a
    traced dynamic_update_slice (no per-tablet recompiles)."""
    def upd(dst, src, t):
        return jax.tree.map(
            lambda d, s: jax.lax.dynamic_update_slice(
                d, s.astype(d.dtype), (t,) + (0,) * (d.ndim - 1)),
            dst, src)

    return jitting.jit(upd, "stack_update",
                       jitting.tag(cols=cols_desc))


def _acc_specs(sig):
    return [jax.tree.map(lambda _: P(), a)
            for a in agg_init(sig.aggs)]


# -- public API --------------------------------------------------------------

def sharded_aggregate(st: ShardedTablets, spec: ScanSpec) -> ScanResult:
    """Evaluate spec's aggregates over all tablets on the mesh.

    Constraints (callers fall back to the per-tablet host path otherwise):
    aggregate-only spec, no GROUP BY, device-exact predicates only
    (non-key i32/i64/f64 columns), numeric aggregate columns.
    """
    if not spec.is_aggregate or spec.group_by:
        raise ValueError("sharded_aggregate handles plain aggregate specs")
    schema = st.schema
    name_to_id = {c.name: c.col_id for c in schema.value_columns}
    kinds = {c.col_id: _kind(c) for c in schema.value_columns}
    key_names = {c.name for c in schema.key_columns}

    pred_sigs, pred_lits = [], []
    for p in spec.predicates:
        if p.column in key_names or p.op == "IN":
            raise ValueError(f"predicate on {p.column} not device-exact")
        cid = name_to_id[p.column]
        if kinds[cid] in ("str", "f32"):
            raise ValueError(f"predicate kind {kinds[cid]} not device-exact")
        pred_sigs.append(dscan.PredSig(cid, kinds[cid], p.op))
        pred_lits.append(pred_literal(kinds[cid], p.value))

    for a in spec.aggregates:
        if a.expr is not None:
            # lower_aggs drops the expression tree silently; without
            # this guard a sum(a*b) spec would fold the wrong thing.
            raise ValueError("expression aggregates need the host path")
        if a.column and a.column not in name_to_id:
            raise ValueError(f"aggregate on key column {a.column}")
        if a.column and kinds[name_to_id[a.column]] == "str" and a.fn != "count":
            raise ValueError("string min/max needs the host path")
    dev_aggs, lowering = lower_aggs(spec.aggregates, name_to_id, kinds)

    check_limb_bound(st.R, st.K)
    col_sigs = tuple(dscan.ColSig(c.col_id, kinds[c.col_id])
                     for c in schema.value_columns)
    sig = dscan.ScanSig(B=st.B, R=st.R, K=st.K, cols=col_sigs,
                        preds=tuple(pred_sigs), aggs=dev_aggs,
                        apply_preds=True)

    lo, hi = st.row_bounds(spec.lower, spec.upper)
    from yugabyte_db_tpu.storage.row_version import MAX_HT
    r_hi, r_lo = PL.scalar_ht_planes(min(spec.read_ht, MAX_HT))
    e_hi, e_lo = PL.scalar_ht_planes(min(spec.read_ht, MAX_HT - 1))

    Tl = st.padded_T // st.mesh.shape["t"]
    fn = _compiled_dist_agg(sig, st.mesh, st.enc_struct, Tl, st.Bl)
    acc, scanned = fn(st.arrays, jnp.asarray(lo), jnp.asarray(hi),
                      jnp.int32(r_hi), jnp.int32(r_lo),
                      jnp.int32(e_hi), jnp.int32(e_lo), tuple(pred_lits))
    # Both outputs in one explicit fetch — finalize() reads every limb
    # of acc, so an implicit per-limb transfer would pay the link
    # round-trip len(acc) times.
    acc, scanned = jax.device_get((acc, scanned))

    out_row, names = [], []
    for a, (fn_name, di) in zip(spec.aggregates, lowering):
        names.append(f"{a.fn}({a.column or '*'})")
        out_row.append(finalize(dev_aggs[di], acc[di], fn_name))
    return ScanResult(names, [tuple(out_row)], None, int(scanned))


_FIRST_CALL = threading.Lock()


class GroupedIneligible(ValueError):
    """The grouped mesh program cannot answer this spec over this stack
    exactly (nothing ops.group_agg lowers; a bucket collision, a
    negative factor, an undecodable group): the per-tablet path does."""


def stack_read_bytes(st: ShardedTablets, sig) -> int:
    """Bytes ONE chip's shard of the stack holds of the planes ``sig``
    names (ops.device_run.program_read_bytes over the stacked tree, by
    the mesh's size: shards are padded to one size, so this is what the
    fullest chip reads a dispatch). What a roofline sets against one
    chip's module time; the whole stack would read mesh-size times it."""
    from yugabyte_db_tpu.storage.tpu_engine import _sig_read_bytes

    if sig not in st._read_bytes:
        st._read_bytes[sig] = _sig_read_bytes(st.arrays, sig) // st.mesh.size
    return st._read_bytes[sig]


def _lower_grouped(st: ShardedTablets, spec: ScanSpec, engine):
    """The first part of a grouped mesh request's issue: the spec
    lowered to ONE ops.group_agg signature and a parameter vector a
    tablet, as the ``[T, P + F]`` array the program takes."""
    exact, superset, host_only = engine._split_predicates(spec)
    if superset or host_only:
        raise GroupedIneligible("predicates the device cannot decide")
    sig0 = vecs = None
    K = group_agg.window_blocks(st.Bl, st.R)
    flat = all(r.max_group_versions <= 1 for r in st.runs)
    for t, run in enumerate(st.runs):
        low = engine._grouped_lower(run, spec, exact)
        if low is None:
            raise GroupedIneligible("not a signature group_agg lowers")
        make_sig, int_lits, f32_lits = low
        sig = make_sig(st.Bl, K, flat)
        if sig0 is not None and sig != sig0:
            raise GroupedIneligible("tablets lower to different "
                                    "signatures")
        ip, fp = RG.pack_params(
            0, 0, run.lower_row(spec.lower), run.upper_row(spec.upper),
            engine._read_plane_ints(spec), int_lits, f32_lits)
        vec = group_agg.pack_params(sig, ip, fp)
        if sig0 is None:
            # (pad tablets keep zero bounds: their shards walk no
            # window and give empty tables)
            sig0, vecs = sig, np.zeros((st.padded_T, vec.size), np.int32)
        vecs[t] = vec
    return sig0, vecs


def sharded_grouped_aggregate(st: ShardedTablets, spec: ScanSpec, engine,
                              phase=None) -> ScanResult:
    """GROUP BY / expression aggregates over all tablets on the mesh, as
    ONE device program (``jit_dist_grouped_aggregate_<tag>``) and ONE
    fetch. ``engine`` is any TPU engine of the table's schema: it lowers
    the spec to ops.group_agg's signature and finishes a tablet's packed
    vector with that tablet's run, exactly as it does its own. Raises
    :class:`GroupedIneligible` where the per-tablet path has to serve.
    ``phase(name, part=None)``, where given, is a context manager around
    the three phases (issue, wait_fetch, finish) and, inside issue,
    around its two parts (lower; dispatch: the ONE jit call with its
    parameter upload)."""
    phase = phase or (lambda _name, _part=None: contextlib.nullcontext())
    with phase("issue"):
        with phase("issue", "lower"):
            sig0, vecs = _lower_grouped(st, spec, engine)
        with phase("issue", "dispatch"):
            Tl = st.padded_T // st.mesh.shape["t"]
            fn = _compiled_dist_grouped(sig0, st.mesh, st.enc_struct, Tl,
                                        st.Bl)
            if fn._cache_size():
                out = fn(st.arrays, vecs)
            else:
                # The first requests after a flush come from every
                # tserver of the process at once: one traces and
                # compiles, the others find its program.
                with _FIRST_CALL:
                    out = fn(st.arrays, vecs)
            metrics.count_device_dispatch(
                "dist_grouped_aggregate", stack_read_bytes(st, sig0), h2d=1,
                d2h=1)
            # (hashed: a stack holds no "dict" leaf to address by)
            group_agg.count_dispatch_forms(sig0)
    with phase("wait_fetch"):
        out = jax.device_get(out)

    def ineligible():
        raise GroupedIneligible("the program's answer cannot be used")

    with phase("finish"):
        results = [engine._finish_grouped(run, spec, sig0, out[t],
                                          ineligible)
                   for t, run in enumerate(st.runs)]
        return combine_grouped(spec, results)


def _kind(c):
    from yugabyte_db_tpu.ops.device_run import dtype_kind
    return dtype_kind(c.dtype)


# -- sharded row/paging path -------------------------------------------------
#
# The cluster ROW read path on the mesh: each device runs the packed
# row-gather program (ops.row_gather — the same MVCC resolve + top_k
# compaction the single-chip engine serves pages with) over its
# (tablet, block-range) shard, emitting the first M matches IN KEY ORDER
# plus a per-device match count combined with psum over ICI; the host
# assembles LIMIT pages in tablet order (a device's "b"-shard covers a
# contiguous disjoint row range, so concatenating shard outputs in "b"
# order is already key order) and decodes ONLY the page's rows from the
# fetched value planes. This is the device-sharded analog of the
# per-tablet parallel read fan-out (reference: src/yb/client/batcher.h:80)
# — the reference scans one tablet per thread; here tablets AND block
# ranges split over the mesh, and multi-version (MVCC) groups, encoded
# planes, tombstones and TTL all resolve on device.

_PAGE_BUCKETS = (128, 512, 2048)


def _page_body(sig: RG.GatherSig, Tl: int, Bl: int, R: int,
               run, iparams, fparams):
    """Per-device: the packed gather over each local tablet's [Bl, R]
    shard. ``iparams`` rows carry GLOBAL row bounds in the w_first/
    w_last/row_lo/row_hi/scan_from slots; each shard rebases them to its
    own block range (clipping to empty when the tablet's range misses
    the shard) so the while_loop walks only overlapping windows — the
    per-device trip counts diverge, so every loop carry is typed
    device-varying (meshcompat.varying)."""
    base = jax.lax.axis_index("b") * (Bl * R)
    KR = sig.K * R
    Wl = Bl // sig.K
    outs = []
    varying = functools.partial(meshcompat.varying, axes=("t", "b"))
    counts = varying(jnp.int32(0))
    for t in range(Tl):
        local = _tablet_slice(run, t)
        ip = iparams[t]
        lo = jnp.clip(ip[2] - base, 0, Bl * R)
        hi = jnp.clip(ip[3] - base, 0, Bl * R)
        sf = jnp.clip(ip[8] - base, 0, Bl * R)
        w_first = jnp.clip(lo // KR, 0, Wl - 1)
        w_last = jnp.where(hi > lo,
                           jnp.clip((hi - 1) // KR, 0, Wl - 1),
                           w_first - 1)
        head = jnp.stack([w_first, w_last, lo, hi, ip[4], ip[5], ip[6],
                          ip[7], sf])
        ipl = jnp.concatenate([head, ip[RG.PARAM_FIXED:]])
        buf = RG.gather_rows(sig, local, ipl, fparams, carry=varying)
        counts = counts + buf[sig.M, 0]
        outs.append(buf[None, None])
    # The per-device match-count combine rides ICI; the buffers ride the
    # ("t", "b")-sharded output (the host fetches only the page's rows).
    total = jax.lax.psum(counts, ("t", "b"))
    return jnp.concatenate(outs, axis=0), total


@functools.lru_cache(maxsize=64)
@compile_contract("dist_page", max_compiles=64)
def _compiled_dist_page(sig: RG.GatherSig, mesh: Mesh, enc_struct,
                        Tl: int, Bl: int):
    spec_tb = P("t", "b")
    body = functools.partial(_page_body, sig, Tl, Bl, sig.R)
    smapped = meshcompat.shard_map(
        body, mesh,
        (_specs_from_struct(enc_struct, spec_tb), P("t"), P()),
        (P("t", "b"), P()))
    return jitting.jit(smapped, "dist_page", sig.tag())


def sharded_row_page(st: ShardedTablets, spec: ScanSpec,
                     resume: bytes | None = None) -> ScanResult:
    """LIMIT page over all tablets on the mesh: ONE device dispatch runs
    the packed MVCC row gather on every (tablet, block-range) shard; the
    host takes the first `limit` in (tablet, key) order and decodes them
    from the fetched value planes (result-proportional host work —
    varlen/f32 payloads fetch by setter index from the host mirror, the
    engine gather path's split). Serves multi-version AND encoded
    stacks. Constraints (callers fall back to the per-tablet host path):
    exact (i32/i64/f64 value-column) predicates, no aggregates.

    Cross-tablet paging: the returned resume_key encodes
    (tablet index, last key) — pass it back as ``resume`` to continue
    (the QLPagingStatePB next_partition_key + next_row_key shape)."""
    if spec.is_aggregate:
        raise ValueError("sharded_row_page serves row scans")
    schema = st.schema
    name_to_id = {c.name: c.col_id for c in schema.value_columns}
    kinds = {c.col_id: _kind(c) for c in schema.value_columns}
    key_names = {c.name for c in schema.key_columns}
    pred_sigs, int_lits = [], []
    for p in spec.predicates:
        if p.column in key_names or p.op == "IN":
            raise ValueError(f"predicate on {p.column} not device-exact")
        cid = name_to_id[p.column]
        kind = kinds[cid]
        if kind not in ("i32", "i64", "f64"):
            raise ValueError(f"predicate kind {kind} not device-exact")
        if kind == "i32":
            int_lits.append(int(p.value))
        elif kind == "i64":
            phi, plo = PL.i64_to_ordered_planes(
                np.array([int(p.value)], dtype=np.int64))
            int_lits += [int(phi[0]), int(plo[0])]
        else:
            phi, plo = PL.f64_to_ordered_planes(
                np.array([p.value], dtype=np.float64))
            int_lits += [int(phi[0]), int(plo[0])]
        pred_sigs.append(dscan.PredSig(cid, kind, p.op))

    limit = spec.limit if spec.limit is not None else _PAGE_BUCKETS[-1]
    M = next((m for m in _PAGE_BUCKETS if m >= limit),
             -(-limit // 128) * 128)
    projection = spec.projection or [c.name for c in schema.columns]
    key_pos = {c.name: i for i, c in enumerate(schema.key_columns)}
    out_cols = tuple(
        RG.OutCol(name_to_id[nm],
                  2 if kinds[name_to_id[nm]] in ("i64", "f64", "str")
                  else 1,
                  kinds[name_to_id[nm]] in ("str", "f32"))
        for nm in projection if nm not in key_pos)
    col_sigs = tuple(dscan.ColSig(c.col_id, kinds[c.col_id])
                     for c in schema.value_columns)
    flat = all(r.max_group_versions <= 1 for r in st.runs)
    sig = RG.GatherSig(B=st.Bl, R=st.R, K=st.K, M=M, cols=col_sigs,
                       preds=tuple(pred_sigs), apply_preds=True,
                       out_cols=out_cols, flat=flat, packed=True)

    start_t = 0
    start_key = spec.lower
    from yugabyte_db_tpu.utils import codec as _codec

    if resume is not None:
        start_t, last_key = _codec.decode(resume)
        start_key = max(spec.lower, last_key + b"\x00")
    lo, hi = st.row_bounds(spec.lower, spec.upper)
    if resume is not None:
        for t in range(min(start_t, len(st.runs))):
            lo[t] = hi[t]  # earlier tablets: already consumed
        if start_t < len(st.runs):
            lo[start_t] = max(lo[start_t],
                              st.runs[start_t].lower_row(start_key))
    from yugabyte_db_tpu.storage.row_version import MAX_HT

    r_hi, r_lo = PL.scalar_ht_planes(min(spec.read_ht, MAX_HT))
    e_hi, e_lo = PL.scalar_ht_planes(min(spec.read_ht, MAX_HT - 1))
    ip = np.zeros((st.padded_T, RG.PARAM_FIXED + len(int_lits)),
                  dtype=np.int32)
    for t in range(st.padded_T):
        ip[t], _f = RG.pack_params(0, 0, int(lo[t]), int(hi[t]),
                                   (r_hi, r_lo, e_hi, e_lo), int_lits,
                                   [])
    fparams = np.zeros((1,), dtype=np.float32)
    Tl = st.padded_T // st.mesh.shape["t"]
    fn = _compiled_dist_page(sig, st.mesh, st.enc_struct, Tl, st.Bl)
    bufs, total = fn(st.arrays, jnp.asarray(ip), jnp.asarray(fparams))
    # One explicit batched fetch for both outputs (one link round-trip,
    # not one per array): bufs [padded_T, mesh_b, M+1, W] packed pages,
    # total the psum-combined match count.
    bufs, total = jax.device_get((bufs, total))

    W, col_offs = RG.out_layout(sig)
    rows: list[tuple] = []
    budget = limit
    mesh_b = st.mesh.shape["b"]
    shard_rows = st.Bl * st.R
    KR = st.K * st.R
    Wl = st.Bl // st.K
    resume_out = None
    for t, run in enumerate(st.runs):
        truncated = False
        sel: list[tuple] = []  # (global row, buf row, shard base)
        for b in range(mesh_b):
            buf = bufs[t, b]
            c = int(buf[M, 0])
            w_end = int(buf[M, 2])
            base = b * shard_rows
            lo_loc = min(max(int(lo[t]) - base, 0), shard_rows)
            hi_loc = min(max(int(hi[t]) - base, 0), shard_rows)
            w_last = (hi_loc - 1) // KR if hi_loc > lo_loc else -1
            # Early exit (count hit M before w_last) leaves windows
            # unscanned: matches may remain beyond the buffer.
            if c > M or (c >= M and w_end <= min(w_last, Wl - 1)):
                truncated = True
            for m in range(min(c, M)):
                sel.append((base + int(buf[m, 0]), buf[m], base))
        more_in_tablet = truncated or len(sel) > budget
        sel = sel[:budget]
        for g, br, sbase in sel:
            rows.append(_decode_buf_row(run, schema, br, col_offs,
                                        sbase, projection, key_pos,
                                        kinds))
        budget -= len(sel)
        page_full = budget <= 0
        if sel and (more_in_tablet
                    or (page_full and t + 1 < len(st.runs))):
            resume_out = _codec.encode([t, run.key_at(sel[-1][0])])
            break
        if page_full:
            break
    return ScanResult(list(projection), rows, resume_out, int(total))


def _decode_buf_row(run, schema, buf_row, col_offs, shard_base,
                    projection, key_pos, kinds):
    """One packed gather output row -> result tuple (the engine's
    fetched-plane decode split: fixed-width values from the device
    planes, varlen/f32 payloads by setter index from the host mirror,
    key columns from the group-start key)."""
    from yugabyte_db_tpu.models.datatypes import DataType

    key_vals = None
    out = []
    for nm in projection:
        if nm in key_pos:
            if key_vals is None:
                key_vals = run.key_vals_at(shard_base + int(buf_row[0]))
            out.append(key_vals[key_pos[nm]])
            continue
        col = schema.column(nm)
        cmp_off, null_off, idx_off = col_offs[col.col_id]
        if buf_row[null_off]:
            out.append(None)
            continue
        kind = kinds[col.col_id]
        if kind in ("str", "f32"):
            g = shard_base + int(buf_row[idx_off])
            b, r = divmod(g, run.R)
            out.append(run.row_versions[b][r].columns[col.col_id])
        elif kind == "i32":
            v = int(buf_row[cmp_off])
            out.append(bool(v) if col.dtype == DataType.BOOL else v)
        elif kind == "i64":
            out.append(int(PL.ordered_planes_to_i64(
                buf_row[cmp_off:cmp_off + 1],
                buf_row[cmp_off + 1:cmp_off + 2])[0]))
        else:
            out.append(float(PL.ordered_planes_to_f64(
                buf_row[cmp_off:cmp_off + 1],
                buf_row[cmp_off + 1:cmp_off + 2])[0]))
    return tuple(out)
