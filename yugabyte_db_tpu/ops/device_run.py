"""DeviceRun: a ColumnarRun's planes uploaded to device memory (HBM).

Reference analog: the SSTable blocks an LRU block cache holds in RAM
(src/yb/rocksdb/util/cache.cc).  A DeviceRun is the cached unit, not a
permanent resident: the TPU engine demand-uploads runs through the
residency manager (storage/residency.py) under ``--tpu_hbm_budget_bytes``
and re-uploads from the authoritative host ColumnarRun after eviction.
While resident, scans window over the planes with dynamic slices, so a
scan is pure compute with no host↔device data motion besides its scalars
and its (small) result.
"""

from __future__ import annotations

from typing import TYPE_CHECKING

import numpy as np
import jax
import jax.numpy as jnp

from yugabyte_db_tpu.models.datatypes import DataType
from yugabyte_db_tpu.ops import encodings

if TYPE_CHECKING:  # type-only: ops never depends on storage at runtime
    from yugabyte_db_tpu.storage.columnar import ColumnarRun


def device_label(d) -> str:
    """Canonical budget-bucket name for a jax Device — the string the
    residency cache keys its per-device budget map and {device=...}
    metric labels by (storage/residency.py)."""
    return "%s:%d" % (d.platform, d.id)


def dtype_kind(dt: DataType) -> str:
    if not dt.is_fixed_width:
        return "str"  # varlen/opaque: host payload + 8-byte prefix planes
    if dt == DataType.DOUBLE:
        return "f64"
    if dt == DataType.FLOAT:
        return "f32"
    if dt.np_dtype.itemsize == 8:
        return "i64"
    return "i32"


def padded_blocks(B: int, window_blocks: int) -> int:
    """The padded block count a DeviceRun uses for a run of ``B`` blocks
    — host-side math shared with residency sizing and warmup, so cache
    keys and byte hints agree with the actual upload."""
    b = max(B, 1)
    return b + (-b) % window_blocks


def plane_nbytes(run: ColumnarRun, window_blocks: int) -> int:
    """Predicted HBM footprint of DeviceRun(run, window_blocks), computed
    from host plane shapes without uploading — the eviction hint that
    lets the residency cache make room *before* a demand upload."""
    pb = padded_blocks(run.B, window_blocks)
    # Compressed runs (--tpu_plane_encoding) upload their encoded tree;
    # the budget must account those bytes, not the logical plane bytes.
    tree = getattr(run, "encoded_arrays", lambda: None)()
    if tree is not None:
        return encodings.tree_padded_nbytes(tree, run.B, pb)

    def padded(arr) -> int:
        per_block = 1
        for d in arr.shape[1:]:
            per_block *= int(d)
        return pb * per_block * arr.dtype.itemsize

    total = sum(padded(a) for a in (
        run.valid, run.group_start, run.tomb, run.live,
        run.ht_hi, run.ht_lo, run.exp_hi, run.exp_lo))
    for col in run.cols.values():
        total += padded(col.set_) + padded(col.isnull)
        total += padded(col.cmp_planes)
        if col.arith is not None:
            total += padded(col.arith)
    return total


def _tree_nbytes(tree) -> int:
    total = 0
    stack = [tree]
    while stack:
        node = stack.pop()
        if isinstance(node, dict):
            stack.extend(node.values())
        else:
            total += int(node.size) * node.dtype.itemsize
    return total


def program_read_bytes(arrays: dict, named: tuple, presence: tuple,
                       flat: bool, arith: tuple = ()) -> int:
    """Resident bytes of the planes one aggregate program reads from a
    run's device ``arrays``, as its signature names them: the MVCC
    planes every resolve touches (``group_start`` too unless the run is
    flat), the ``set`` and ``isnull`` planes of every column in
    ``presence`` (a row exists if any of its columns is non-null, so the
    resolve reads them all: ``sig.cols``), and the ``cmp`` planes of the
    ``named`` columns alone (group, aggregate and predicate columns;
    ``arith`` planes for float predicates). Not the other columns' value
    planes: XLA drops what the program never uses. Compressed leaves
    count at their resident (encoded) size."""
    total = sum(_tree_nbytes(arrays[n]) for n in (
        "valid", "tomb", "live", "ht_hi", "ht_lo", "exp_hi", "exp_lo"))
    if not flat:
        total += _tree_nbytes(arrays["group_start"])
    cols = arrays["cols"]
    for cid in presence:
        total += _tree_nbytes(cols[cid]["set"]) \
            + _tree_nbytes(cols[cid]["isnull"])
    for cid in named:
        total += _tree_nbytes(cols[cid]["cmp"])
    for cid in arith:
        if "arith" in cols[cid]:
            total += _tree_nbytes(cols[cid]["arith"])
    return total


class DeviceRun:
    """Uploads a ColumnarRun, padding the block axis to a multiple of the
    window size so window tiling never clamps (clamped dynamic slices would
    re-read earlier blocks and double-count aggregates)."""

    def __init__(self, run: ColumnarRun, window_blocks: int, device=None):
        self.run = run
        self.K = window_blocks
        B = max(run.B, 1)
        pad = padded_blocks(run.B, window_blocks) - B
        self.B = B + pad
        self.device = device or jax.devices()[0]

        # Compressed upload: the run's cached encoded tree (if the
        # encoding flag is on) uploads leaf-by-leaf with the same block
        # padding semantics; kernels decode windows of it inline.
        tree = getattr(run, "encoded_arrays", lambda: None)()
        self.encoded = tree is not None
        if tree is not None:

            def up_leaf(leaf, ones=False):
                padded = encodings.pad_leaf(leaf, self.B, ones=ones)
                k = encodings.leaf_kind(padded)
                if k is None:
                    return jax.device_put(padded, self.device)
                return {k: {n: jax.device_put(a, self.device)
                            for n, a in padded[k].items()}}

            self.arrays = {"cols": {}}
            for name in ("valid", "group_start", "tomb", "live",
                         "ht_hi", "ht_lo", "exp_hi", "exp_lo"):
                self.arrays[name] = up_leaf(
                    tree[name], ones=(name == "group_start"))
            for cid, col in tree["cols"].items():
                self.arrays["cols"][cid] = {
                    n: up_leaf(p) for n, p in col.items()}
            return

        def pad_b(arr):
            if pad == 0:
                return arr
            shape = (pad,) + arr.shape[1:]
            return np.concatenate([arr, np.zeros(shape, dtype=arr.dtype)], axis=0)

        def up(arr):
            return jax.device_put(pad_b(arr), self.device)

        # Padding blocks: valid=False, group_start=True (each pad row its own
        # group), everything else zero.
        gs = pad_b(run.group_start)
        if pad:
            gs[B:] = True
        self.arrays = {
            "valid": up(run.valid),
            "group_start": jax.device_put(gs, self.device),
            "tomb": up(run.tomb),
            "live": up(run.live),
            "ht_hi": up(run.ht_hi),
            "ht_lo": up(run.ht_lo),
            "exp_hi": up(run.exp_hi),
            "exp_lo": up(run.exp_lo),
            "cols": {},
        }
        for cid, col in run.cols.items():
            entry = {
                "set": up(col.set_),
                "isnull": up(col.isnull),
                "cmp": up(col.cmp_planes),
            }
            if col.arith is not None:
                entry["arith"] = up(col.arith)
            self.arrays["cols"][cid] = entry

    @classmethod
    def from_arrays(cls, run: ColumnarRun, window_blocks: int, arrays,
                    device=None) -> "DeviceRun":
        """Wrap device planes produced ON DEVICE (ops.flush) instead of
        uploading host planes — the arrays must already carry this
        class's padding encoding, with the block axis padded to the
        window multiple. Lets a flush seed the residency cache without
        a host->device round trip."""
        self = cls.__new__(cls)
        self.run = run
        self.K = window_blocks
        self.B = int(arrays["valid"].shape[0])
        self.device = device or jax.devices()[0]
        self.arrays = arrays
        # The device flush emits dict leaves for string columns when the
        # encoding flag is on; everything else it scatters stays plain
        # until the run is evicted and demand re-uploads compressed.
        self.encoded = encodings.tree_encoded(arrays)
        return self

    @property
    def num_windows(self) -> int:
        return self.B // self.K

    @property
    def nbytes(self) -> int:
        """Device-resident bytes of this run's planes — the HBM
        footprint the engine accounts under the root->device MemTracker
        subtree (/memz)."""
        return _tree_nbytes(self.arrays)
