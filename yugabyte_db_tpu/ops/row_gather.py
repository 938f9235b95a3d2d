"""Packed device-side row materialization: the YCSB-E hot path.

A single jitted dispatch scans every block window in a key range with a
``lax.while_loop``, resolves MVCC visibility + predicates per key group
(ops.scan.resolve_window), and scatter-compacts the matched rows — group
start row index plus each projected column's latest-visible value planes —
into ONE fixed-capacity int32 output matrix. The host then bulk-decodes
the packed planes with vectorized numpy (utils.planes inverses); per-row
Python work is proportional to the *result* size, never the scanned size.

Interface design is driven by measured link behavior (the host↔device
link pays ~1 RTT per blocking call, ~ms per transferred array, and
pipelines async dispatches):
- every dynamic scalar (window range, row bounds, read point, predicate
  literals) rides in ONE int32 params vector (+ one float32 vector when
  f32 literals exist) — one upload per dispatch, not eight;
- the entire result (packed rows + count/scanned/w_end scalars) is ONE
  int32 [M+1, W] matrix — one download per dispatch;
- ``compiled_gather_batch`` vmaps the program over G independent scans
  (one tablet serving many concurrent pages — the YCSB-E server shape),
  so a whole batch costs one dispatch + one download.

Reference analog: the DocRowwiseIterator::HasNext/DoNextRow hot loop
(src/yb/docdb/doc_rowwise_iterator.cc:545) — here vectorized across a
whole key range in one device program, with LIMIT/paging expressed as the
output buffer capacity (truncation is a clean in-key-order prefix, so a
page resumes exactly where the buffer filled).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

from yugabyte_db_tpu.ops.scan import resolve_window
from yugabyte_db_tpu.utils import jitting
from yugabyte_db_tpu.utils.jitting import compile_contract

# Fixed slots at the head of the int32 params vector; predicate literal
# planes follow from PARAM_FIXED onward (layout per GatherSig.preds).
# scan_from: rows below it are excluded from the rows_scanned statistic
# (but not from results) — continuation rounds re-scan part of an already
# counted window and must not double-count it.
PARAM_FIXED = 9  # w_first, w_last, row_lo, row_hi, r_hi, r_lo, e_hi, e_lo,
                 # scan_from


@dataclass(frozen=True)
class OutCol:
    col_id: int
    planes: int      # cmp-plane count (1 or 2)
    want_idx: bool   # fetch-by-index column: emit the setter's global row


@dataclass(frozen=True)
class GatherSig:
    """Static shape of the compiled gather program."""

    B: int            # blocks in run (padded)
    R: int            # rows per block
    K: int            # blocks per window
    M: int            # output capacity (rows)
    cols: tuple       # tuple[ColSig] — every column the resolve touches
    preds: tuple      # tuple[PredSig]
    apply_preds: bool
    out_cols: tuple   # tuple[OutCol]
    flat: bool = False  # single-version-per-key run (see ScanSig.flat)
    packed: bool = True  # True: device-compacted pages (top_k of the first
                         # M matches, while_loop over windows); False: one
                         # whole window emitted in place (start=-1 marks
                         # non-matches; the host compacts with numpy)

    def tag(self) -> str:
        """What the query decides of the program, for its name
        (utils.jitting.tag)."""
        return jitting.tag(out=self.out_cols, preds=self.preds,
                           flat=self.flat, packed=self.packed)


def out_layout(sig: GatherSig):
    """Column layout of the packed [M+1, W] output matrix.

    Row m < M: [start | per out col: cmp planes.., null, (idx)].
    Row M:     [count, scanned, w_end, 0...].
    Returns (W, {col_id: (cmp_off, null_off, idx_off|None)}).
    """
    off = 1
    cols = {}
    for oc in sig.out_cols:
        idx_off = off + oc.planes + 1 if oc.want_idx else None
        cols[oc.col_id] = (off, off + oc.planes, idx_off)
        off += oc.planes + 1 + (1 if oc.want_idx else 0)
    return max(off, 3), cols


def pack_params(w_first, w_last, row_lo, row_hi, read_planes, int_lits,
                f32_lits, scan_from=None):
    """Host-side mirror of the in-kernel params layout -> (i32[P], f32[F])."""
    iparams = np.array(
        [w_first, w_last, row_lo, row_hi, *read_planes,
         row_lo if scan_from is None else scan_from, *int_lits],
        dtype=np.int32)
    fparams = np.array(f32_lits if f32_lits else [0.0], dtype=np.float32)
    return iparams, fparams


def _unpack_literals(sig: GatherSig, iparams, fparams):
    off, foff = PARAM_FIXED, 0
    lits = []
    for ps in sig.preds:
        if ps.kind == "f32":
            lits.append(fparams[foff])
            foff += 1
        elif ps.kind in ("i32", "code"):
            lits.append(iparams[off])
            off += 1
        else:
            lits.append((iparams[off], iparams[off + 1]))
            off += 2
    return tuple(lits)


def _window_parts(sig, r, base, m):
    """Per-position output columns [N, W]: start (or -1 for non-match) +
    each out col's value planes / null / setter index."""
    W, _ = out_layout(sig)
    parts = [jnp.where(m, base + r["start_idx"], -1)[:, None]]
    for oc in sig.out_cols:
        cid = oc.col_id
        idx = r["col_idx"][cid]
        notnull = r["col_notnull"][cid]
        # Slice to the layout's plane count: dictionary-encoded string
        # columns decode a third (code) plane the output never carries.
        cmp = r["cmp_w"][cid][:, :oc.planes]
        parts.append(cmp if sig.flat else cmp[idx])
        parts.append((~notnull).astype(jnp.int32)[:, None])
        if oc.want_idx:
            parts.append(jnp.where(notnull, base + idx, -1)[:, None])
    vals = jnp.concatenate(parts, axis=1)
    if vals.shape[1] < W:
        vals = jnp.pad(vals, ((0, 0), (0, W - vals.shape[1])))
    return vals


def gather_rows(sig: GatherSig, run, iparams, fparams, carry=None):
    """Traced program over one scan's params. Returns i32 [M+1, W].

    ``carry`` maps each replicated initial loop-carry value to the type
    the loop body returns; a caller tracing this under shard_map passes
    its mark-as-device-varying function (the body's outputs vary with
    the shard's planes), the vmapped single-device caller passes None."""
    K, R, M = sig.K, sig.R, sig.M
    N = K * R
    W, col_offs = out_layout(sig)
    w_first, w_last = iparams[0], iparams[1]
    row_lo, row_hi = iparams[2], iparams[3]
    read_hi, read_lo, rexp_hi, rexp_lo = (iparams[4], iparams[5],
                                          iparams[6], iparams[7])
    scan_from = iparams[8]
    pred_literals = _unpack_literals(sig, iparams, fparams)

    def resolve(w):
        b0 = w * K
        base = b0 * R
        r = resolve_window(sig, run, b0, row_lo - base, row_hi - base,
                           read_hi, read_lo, rexp_hi, rexp_lo, pred_literals)
        gvalid = r["ridx"] < r["num_groups"]
        m = r["result"] & gvalid
        pre = r["pre_pred"] & gvalid & (r["start_idx"] >= scan_from - base)
        return r, base, m, pre

    if not sig.packed:
        # One whole window emitted in place; the host compacts (numpy
        # boolean indexing) — no device scatter/sort at all.
        r, base, m, pre = resolve(w_first)
        vals = _window_parts(sig, r, base, m)
        tail = jnp.zeros((W,), jnp.int32)
        tail = tail.at[0].set(jnp.sum(m.astype(jnp.int32)))
        tail = tail.at[1].set(jnp.sum(pre.astype(jnp.int32)))
        tail = tail.at[2].set(w_first + 1)
        return jnp.concatenate([vals, tail[None, :]], axis=0)

    buf = jnp.zeros((M + 1, W), jnp.int32)

    def cond(carry):
        w, count, scanned, buf = carry
        return (w <= w_last) & (count < M)

    def body(carry):
        w, count, scanned, buf = carry
        r, base, m, pre = resolve(w)
        # Compact to the first M matches in key order: top_k over negated
        # match positions (non-matches sort last), then a small [M] gather
        # + contiguous scatter — far cheaper than scattering all N rows.
        sel = jnp.where(m, r["ridx"], jnp.int32(N))
        k = min(M, N)
        neg_vals, top_idx = lax.top_k(-sel, k)
        valid = (-neg_vals) < N
        vals = _window_parts(sig, r, base, m)[top_idx]
        pos = jnp.where(valid, count + jnp.arange(k, dtype=jnp.int32), M + 1)
        buf = buf.at[pos].set(vals, mode="drop")
        count = count + jnp.sum(m.astype(jnp.int32))
        scanned = scanned + jnp.sum(pre.astype(jnp.int32))
        return (w + jnp.int32(1), count, scanned, buf)

    init = (w_first, jnp.int32(0), jnp.int32(0), buf)
    if carry is not None:
        init = jax.tree.map(carry, init)
    w_end, count, scanned, buf = lax.while_loop(cond, body, init)
    tail = jnp.zeros((W,), jnp.int32).at[0].set(count).at[1].set(
        scanned).at[2].set(w_end)
    return buf.at[M].set(tail)


@functools.lru_cache(maxsize=128)
@compile_contract("gather_batch", max_compiles=128)
def compiled_gather_batch(sig: GatherSig, G: int):
    """G scans per dispatch: (run, i32[G,P], f32[G,F]) -> i32[G, M+1, W]."""
    fn = functools.partial(gather_rows, sig)
    return jitting.jit(jax.vmap(fn, in_axes=(None, 0, 0)), "gather_batch",
                       sig.tag())
