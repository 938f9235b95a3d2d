"""Compressed plane encodings for device-resident columnar runs.

Reference analog: the block-based SSTable keeps blocks compressed in the
block cache and only materialises restart-interval rows on read
(src/yb/rocksdb/table/block_builder.cc prefix compression;
src/yb/rocksdb/table/block_based_table_reader.cc). Here the unit is the
column *plane* instead of the row block: each [B, R] (or [B, R, P])
host plane may upload in one of five compressed leaf forms, and the
scan/fold kernels decode windows of them inline — HBM holds only the
compressed bytes, decoded values exist as register/vmem transients
inside the fused XLA program.

Leaf forms (a leaf is either a bare ndarray — "plain" — or a
single-key dict naming the encoding):

  {"bits":    {"bw": i32 [B, R//32]}}          bool plane, 1 bit/row
  {"const":   {"cval": [1, 1, ...]}}           whole-plane constant
  {"delta16": {"dbase": i32 [B, 1, ...],
               "doff": u16 [B, R, ...]}}       per-block base + u16 offset
  {"rle":     {"rid": i16 [B, R],
               "rvals": [B, Rc, ...]}}         per-block run id -> value
  {"dict":    {"codes": u16 [B, R],
               "dhi": i32 [D], "dlo": i32 [D]}} sorted per-run dictionary

Encoding invariants the kernels rely on:

- "valid" and "group_start" are only ever bits or plain — never const —
  so DeviceRun block padding can force valid=False / group_start=True
  word patterns on pad blocks exactly as the plain format does.
- A dict is the SORTED unique full (not prefix) values of the column's
  set, non-null rows; its last slot (index D-1) is reserved for
  absent rows (unset or NULL) and decodes to prefix planes (0, 0) —
  byte-identical to the zero-initialised planes those rows hold in the
  plain format. Sortedness makes the code order the value order, so
  range predicates translate to code-range compares ("code" preds).
- A dict cmp leaf decodes to THREE planes [.., 3]: the two prefix
  planes (byte-identical to the plain path) plus the int32 code plane
  that promoted "code" predicates compare against.
- rle uses one run id per block row shared by every plane of the leaf
  (a run breaks where ANY plane changes), so multi-plane values decode
  with a single gather index.

Selection (encode_int_plane / encode_bool_plane / encode_float_plane)
is a cheap stats pass: const when one distinct value, else the smaller
of delta16 (every block's span <= 65535) and rle (max runs/block <=
R//8), else plain. Pathological planes transparently stay plain — the
fallback matrix lives in docs/columnar-encoding.md.
"""

from __future__ import annotations

import functools

import numpy as np

import jax.numpy as jnp
from jax import lax

ENC_KINDS = ("bits", "const", "delta16", "rle", "dict")
_ENC_SET = frozenset(ENC_KINDS)

# Dictionary capacity: codes are uint16 and one slot is reserved for the
# absent (unset/NULL) rows, so at most 2^16 - 1 distinct values.
DICT_MAX_VALUES = (1 << 16) - 1
# rle is eligible when the worst block has at most R // RLE_MAX_RUN_DIV
# runs (denser planes gain too little over delta16/plain).
RLE_MAX_RUN_DIV = 8


def pow2_bucket(n: int) -> int:
    """Round a count up to the next power of two (>= 1) so encoded
    widths land in a small set of static shapes (bounded retraces)."""
    return 1 << max(0, int(n - 1).bit_length())


def leaf_kind(x):
    """Encoding kind of a plane leaf, or None for a plain ndarray.

    Encoded leaves are single-key dicts keyed by the kind; every other
    dict in a run tree (column entries, the cols map) has multiple keys
    or non-kind keys, so this never misfires on tree structure.
    """
    if isinstance(x, dict) and len(x) == 1:
        k = next(iter(x))
        if k in _ENC_SET:
            return k
    return None


def leaf_dims(leaf):
    """(B, R) of a leaf, or None when the leaf carries no block dim
    (const)."""
    k = leaf_kind(leaf)
    if k is None:
        return leaf.shape[0], leaf.shape[1]
    e = leaf[k]
    if k == "bits":
        return e["bw"].shape[0], e["bw"].shape[1] * 32
    if k == "delta16":
        return e["doff"].shape[0], e["doff"].shape[1]
    if k == "rle":
        return e["rid"].shape[0], e["rid"].shape[1]
    if k == "dict":
        return e["codes"].shape[0], e["codes"].shape[1]
    return None


def tree_encoded(run) -> bool:
    """True when any leaf of a run-plane tree is encoded."""
    for name, leaf in run.items():
        if name == "cols":
            for col in leaf.values():
                for p in col.values():
                    if leaf_kind(p) is not None:
                        return True
        elif leaf_kind(leaf) is not None:
            return True
    return False


def tree_dims(run):
    """(B, R) of a run-plane tree; "valid" always carries block dims."""
    d = leaf_dims(run["valid"])
    if d is None:  # pragma: no cover - valid is never const
        raise ValueError("run tree has no block-dimensioned valid plane")
    return d


# ---------------------------------------------------------------------------
# host-side encoders (numpy; run once per ColumnarRun at upload time)
# ---------------------------------------------------------------------------


def _as_cmp_words(p):
    """Bitwise view for value comparisons: floats compare as their bit
    patterns (NaN == NaN, -0.0 != 0.0) so decode is byte-identical."""
    if p.dtype.kind == "f":
        return p.view(np.int32 if p.dtype.itemsize == 4 else np.int64)
    return p


def encode_bits(plane):
    """[B, R] bool -> bits leaf (R must be a multiple of 32)."""
    B, R = plane.shape
    if R % 32 or plane.size == 0:
        return None
    w = plane.reshape(B, R // 32, 32).astype(np.uint32)
    bw = (w << np.arange(32, dtype=np.uint32)).sum(
        axis=-1, dtype=np.uint32)
    return {"bits": {"bw": bw.view(np.int32)}}


def encode_const(plane):
    """Whole-plane constant -> const leaf (cval keeps the dtype)."""
    if plane.size == 0:
        return None
    w = _as_cmp_words(plane)
    if not (w == w.reshape(-1, *w.shape[2:])[:1]).all():
        return None
    return {"const": {"cval": np.ascontiguousarray(plane[:1, :1])}}


def encode_delta16(plane):
    """Per-block int32 base + uint16 offsets; eligible when every
    block's span fits 16 bits (span computed in int64 — int32 max-min
    overflows)."""
    if plane.size == 0 or plane.dtype.kind not in "iu":
        return None
    p64 = plane.astype(np.int64)
    base = p64.min(axis=1, keepdims=True)
    span = (p64.max(axis=1, keepdims=True) - base).max(initial=0)
    if span > 0xFFFF:
        return None
    return {"delta16": {"dbase": base.astype(np.int32),
                        "doff": (p64 - base).astype(np.uint16)}}


def encode_rle(plane):
    """Per-block run-length leaf: rid[b, r] indexes rvals[b]; a run
    breaks where ANY plane of the leaf changes."""
    if plane.size == 0:
        return None
    B, R = plane.shape[0], plane.shape[1]
    w = _as_cmp_words(plane).reshape(B, R, -1)
    brk = np.ones((B, R), np.bool_)
    brk[:, 1:] = (w[:, 1:] != w[:, :-1]).any(axis=-1)
    rid = brk.cumsum(axis=1, dtype=np.int64) - 1
    nruns = int(rid[:, -1].max()) + 1
    if nruns > max(1, R // RLE_MAX_RUN_DIV):
        return None
    Rc = pow2_bucket(nruns)
    v3 = plane.reshape(B, R, -1)
    rvals = np.zeros((B, Rc, v3.shape[2]), plane.dtype)
    bi, ri = np.nonzero(brk)
    rvals[bi, rid[bi, ri]] = v3[bi, ri]
    if plane.ndim == 2:
        rvals = rvals[:, :, 0]
    return {"rle": {"rid": rid.astype(np.int16),
                    "rvals": np.ascontiguousarray(rvals)}}


def dict_leaf(codes, dhi, dlo):
    """Assemble a dict leaf. ``codes`` [B, R] row codes (absent rows
    already set to len(dhi) - 1); ``dhi``/``dlo`` the prefix planes of
    the sorted dictionary, absent slot zeroed, padded to a pow2 width."""
    return {"dict": {"codes": codes.astype(np.uint16),
                     "dhi": dhi.astype(np.int32),
                     "dlo": dlo.astype(np.int32)}}


def leaf_nbytes(leaf) -> int:
    """Encoded byte size of a leaf as uploaded (unpadded)."""
    k = leaf_kind(leaf)
    if k is None:
        return leaf.nbytes
    return sum(a.nbytes for a in leaf[k].values())


def _pick_smaller(plane, candidates):
    cands = [c for c in candidates if c is not None]
    if not cands:
        return plane
    best = min(cands, key=leaf_nbytes)
    return best if leaf_nbytes(best) < plane.nbytes else plane


def encode_bool_plane(plane):
    """bool planes bit-pack (never const: valid/group_start padding
    semantics depend on per-block words)."""
    e = encode_bits(np.ascontiguousarray(plane))
    return plane if e is None else e


def encode_int_plane(plane):
    """int32 [B, R(, P)] -> const | smaller of delta16/rle | plain."""
    c = encode_const(plane)
    if c is not None:
        return c
    return _pick_smaller(plane, [encode_delta16(plane),
                                 encode_rle(plane)])


def encode_float_plane(plane):
    """f32 arith planes: const | rle | plain (no delta on floats)."""
    c = encode_const(plane)
    if c is not None:
        return c
    return _pick_smaller(plane, [encode_rle(plane)])


# ---------------------------------------------------------------------------
# accounting (budget gates, metrics)
# ---------------------------------------------------------------------------


def leaf_padded_nbytes(leaf, B: int, pad_b: int) -> int:
    """Device byte size of a leaf once its block axis pads to pad_b.

    Block-dimensioned arrays scale by pad_b / B; const cval and dict
    dhi/dlo have no block axis and upload once.
    """
    k = leaf_kind(leaf)
    if k is None:
        per_block = int(np.prod(leaf.shape[1:], dtype=np.int64))
        return per_block * leaf.dtype.itemsize * pad_b
    total = 0
    no_block = {"const": ("cval",), "dict": ("dhi", "dlo")}.get(k, ())
    for name, a in leaf[k].items():
        if name in no_block:
            total += a.nbytes
        else:
            per_block = int(np.prod(a.shape[1:], dtype=np.int64))
            total += per_block * a.dtype.itemsize * pad_b
    return total


def tree_padded_nbytes(tree, B: int, pad_b: int) -> int:
    total = 0
    for name, leaf in tree.items():
        if name == "cols":
            for col in leaf.values():
                for p in col.values():
                    total += leaf_padded_nbytes(p, B, pad_b)
        else:
            total += leaf_padded_nbytes(leaf, B, pad_b)
    return total


def _leaf_logical_nbytes(leaf, B: int, R: int) -> int:
    """Plain-format bytes the leaf replaces (dict: the two int32 prefix
    planes; bits: one bool byte per row)."""
    k = leaf_kind(leaf)
    if k is None:
        return leaf.nbytes
    if k == "bits":
        return B * R
    if k == "dict":
        return B * R * 8
    if k == "const":
        cv = leaf[k]["cval"]
        return B * R * int(np.prod(cv.shape[2:], dtype=np.int64)) * \
            cv.dtype.itemsize
    if k == "delta16":
        d = leaf[k]["doff"]
        return B * R * int(np.prod(d.shape[2:], dtype=np.int64)) * 4
    rv = leaf[k]["rvals"]
    return B * R * int(np.prod(rv.shape[2:], dtype=np.int64)) * \
        rv.dtype.itemsize


def tree_stats(tree) -> dict:
    """Per-encoding byte accounting for metrics/memz: {"by_encoding":
    {kind: encoded_bytes}, "encoded_bytes", "logical_bytes"}."""
    B, R = tree_dims(tree)
    by = {}
    logical = 0

    def one(leaf):
        nonlocal logical
        k = leaf_kind(leaf) or "plain"
        by[k] = by.get(k, 0) + leaf_nbytes(leaf)
        logical += _leaf_logical_nbytes(leaf, B, R)

    for name, leaf in tree.items():
        if name == "cols":
            for col in leaf.values():
                for p in col.values():
                    one(p)
        else:
            one(leaf)
    return {"by_encoding": by, "encoded_bytes": sum(by.values()),
            "logical_bytes": logical}


# ---------------------------------------------------------------------------
# device-side block padding (DeviceRun upload)
# ---------------------------------------------------------------------------


def pad_leaf(leaf, pad_b: int, ones: bool = False):
    """Pad a leaf's block axis to pad_b blocks with the plain format's
    padding values: False/0 everywhere, except ``ones`` (group_start)
    pads all-True words so pad rows are each their own group."""
    k = leaf_kind(leaf)
    if k is None:
        B = leaf.shape[0]
        if pad_b <= B:
            return leaf
        fill = np.ones if ones else np.zeros
        pad = fill((pad_b - B,) + leaf.shape[1:], leaf.dtype)
        return np.concatenate([leaf, pad], axis=0)
    e = dict(leaf[k])
    if k == "bits":
        B = e["bw"].shape[0]
        if pad_b > B:
            fill = np.full((pad_b - B,) + e["bw"].shape[1:], -1,
                           np.int32) if ones else \
                np.zeros((pad_b - B,) + e["bw"].shape[1:], np.int32)
            e["bw"] = np.concatenate([e["bw"], fill], axis=0)
    elif k == "delta16":
        B = e["doff"].shape[0]
        if pad_b > B:
            for n in ("dbase", "doff"):
                pad = np.zeros((pad_b - B,) + e[n].shape[1:], e[n].dtype)
                e[n] = np.concatenate([e[n], pad], axis=0)
    elif k == "rle":
        B = e["rid"].shape[0]
        if pad_b > B:
            for n in ("rid", "rvals"):
                pad = np.zeros((pad_b - B,) + e[n].shape[1:], e[n].dtype)
                e[n] = np.concatenate([e[n], pad], axis=0)
    elif k == "dict":
        B = e["codes"].shape[0]
        if pad_b > B:
            # pad rows decode the absent slot: prefix planes (0, 0),
            # matching the plain format's zeroed pad rows.
            absent = e["dhi"].shape[0] - 1
            pad = np.full((pad_b - B,) + e["codes"].shape[1:], absent,
                          np.uint16)
            e["codes"] = np.concatenate([e["codes"], pad], axis=0)
    return {k: e}


# ---------------------------------------------------------------------------
# device-side decode (traced inside the scan/fold programs)
# ---------------------------------------------------------------------------


def _slice_b(arr, b0, K):
    return lax.dynamic_slice_in_dim(arr, b0, K, axis=0)


def wwords(leaf, b0, K: int):
    """A K-block window of a "bits" leaf as it is stored: int32 words
    [K, R // 32], bit b of word w the plane's row 32 * w + b of the
    block. ``&``, ``|`` and ``~`` of bool planes are the same on their
    words, 32 rows an element and no relayout: a consumer that XLA
    cannot fuse the unpack into (ops.group_agg's kernel) combines the
    words and lays ONE result out by rows (``rows_of_words``)."""
    return _slice_b(leaf["bits"]["bw"], b0, K)


def rows_of_words(masks):
    """Packed masks -> one int32 a row, in ``wplane``'s flat [K * R]
    order: bit j of a row's value is the row's bit of ``masks[j]`` (at
    most 32 of them, each int32 [K, R // 32] as ``wwords`` gives them).
    ONE producer over [K, R // 32, 32] and one relayout to rows,
    however many masks there are."""
    shifts = jnp.arange(32, dtype=jnp.int32)
    return functools.reduce(jnp.bitwise_or, [
        ((m[:, :, None] >> shifts) & jnp.int32(1)) << jnp.int32(j)
        for j, m in enumerate(masks)]).reshape(-1)


def wplane(leaf, b0, K: int, R: int):
    """Decode a K-block window of a leaf to the flat [K*R, ...] layout
    ops.scan's plain-plane windowing produces. Dispatch is on pytree
    STRUCTURE, so each branch is resolved at trace time."""
    k = leaf_kind(leaf)
    if k is None:
        return _slice_b(leaf, b0, K).reshape((K * R,) + leaf.shape[2:])
    e = leaf[k]
    if k == "bits":
        w = wwords(leaf, b0, K)
        bits = (w[:, :, None] >> jnp.arange(32, dtype=jnp.int32)) \
            & jnp.int32(1)
        return bits.astype(jnp.bool_).reshape(K * R)
    if k == "const":
        cv = e["cval"]
        tail = cv.shape[2:]
        return jnp.broadcast_to(jnp.reshape(cv, (1,) + tail),
                                (K * R,) + tail)
    if k == "delta16":
        base = _slice_b(e["dbase"], b0, K)
        off = _slice_b(e["doff"], b0, K).astype(jnp.int32)
        return (base + off).reshape((K * R,) + e["doff"].shape[2:])
    if k == "rle":
        Rc = e["rvals"].shape[1]
        rid = _slice_b(e["rid"], b0, K).reshape(K * R).astype(jnp.int32)
        rv = _slice_b(e["rvals"], b0, K)
        flat = rv.reshape((K * Rc,) + rv.shape[2:])
        idx = rid + Rc * (jnp.arange(K * R, dtype=jnp.int32)
                          // jnp.int32(R))
        return jnp.take(flat, idx, axis=0)
    # dict: prefix planes + the code plane for promoted predicates
    codes = _slice_b(e["codes"], b0, K).reshape(K * R).astype(jnp.int32)
    return jnp.stack([jnp.take(e["dhi"], codes),
                      jnp.take(e["dlo"], codes), codes], axis=-1)


def decode_leaf(leaf, B: int, R: int):
    """Full-plane decode back to the [B, R, ...] layout."""
    if leaf_kind(leaf) is None:
        return leaf
    flat = wplane(leaf, 0, B, R)
    return flat.reshape((B, R) + flat.shape[1:])


def decode_run(run):
    """Decode every leaf of a run-plane tree (flat fold entry points
    that read whole planes; the windowed kernels use wplane instead)."""
    B, R = tree_dims(run)
    out = {}
    for name, leaf in run.items():
        if name == "cols":
            out[name] = {
                cid: {n: decode_leaf(p, B, R) for n, p in col.items()}
                for cid, col in leaf.items()}
        else:
            out[name] = decode_leaf(leaf, B, R)
    return out
