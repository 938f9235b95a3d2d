"""Columnar sorted runs: the TPU-native SSTable.

This is the storage-format heart of the framework (SURVEY.md §7): where the
reference stores row-wise prefix-delta-compressed byte blocks
(src/yb/rocksdb/table/block_builder.cc:29-46), a ColumnarRun stores
fixed-shape SoA planes sized for HBM tiling:

- rows are MVCC versions sorted (encoded key asc, commit ht desc), grouped
  by key; a key's versions never span a block boundary (so device kernels
  can treat each block window as segment-complete);
- keys are represented device-side by a fixed-width big-endian word prefix
  as int32 "planes" (signed compare == byte order, utils.planes); full key
  bytes stay host-side for ties/materialization;
- every 64-bit ordered quantity (hybrid times, int64/double values) is two
  int32 planes; varlen values keep an 8-byte order-preserving prefix on
  device and their payload host-side;
- per-block metadata (min/max key, max commit ht) plays the role of the
  reference's index blocks + UserFrontiers (src/yb/rocksdb/metadata.h:103)
  and drives host-side block pruning.

The numpy arrays here are the host mirror; ops.device_run uploads them.
"""

from __future__ import annotations

from dataclasses import dataclass, field

import numpy as np

from yugabyte_db_tpu.models.datatypes import DataType
from yugabyte_db_tpu.models.schema import Schema
from yugabyte_db_tpu.storage.row_version import MAX_HT, RowVersion
from yugabyte_db_tpu.utils import planes as P

DEFAULT_ROWS_PER_BLOCK = 2048
KEY_WORDS = 8  # 32-byte key prefix on device


def _varlen_raw(v) -> bytes:
    """Bytes for a varlen value's device prefix planes. Strings/bytes are
    their contents (order-preserving compares); opaque containers
    (collections, jsonb) serialize deterministically — their prefix is
    only an equality heuristic, predicates on them stay host-side."""
    if isinstance(v, str):
        return v.encode("utf-8", "surrogateescape")
    if isinstance(v, (bytes, bytearray)):
        return bytes(v)
    return repr(v).encode("utf-8", "surrogateescape")


@dataclass
class ColumnData:
    """Host planes for one value column across all blocks: [B, R, ...]."""

    dtype: DataType
    set_: np.ndarray          # bool: version sets this column
    isnull: np.ndarray        # bool: set and value is NULL
    cmp_planes: np.ndarray    # [B, R, P] int32: order planes (compare/minmax)
    arith: np.ndarray | None  # [B, R] float32 arithmetic plane (numeric only)
    varlen: list | None       # per-block list of python payloads (varlen only)


@dataclass
class BlockMeta:
    min_key: bytes
    max_key: bytes
    num_valid: int


class ColumnarRun:
    """One immutable sorted run in blocked columnar layout."""

    def __init__(self, schema: Schema, rows_per_block: int = DEFAULT_ROWS_PER_BLOCK):
        self.schema = schema
        self.R = rows_per_block
        self.B = 0
        self.num_versions = 0
        self.blocks: list[BlockMeta] = []
        # Filled by build():
        self.key_planes: np.ndarray | None = None   # [B, R, KEY_WORDS] i32
        self.ht_hi = self.ht_lo = None              # [B, R] i32
        self.exp_hi = self.exp_lo = None            # [B, R] i32
        self.tomb = self.live = self.valid = self.group_start = None  # [B, R] bool
        self.cols: dict[int, ColumnData] = {}       # col_id -> ColumnData
        # Host-side exact data for ties/materialization/compaction —
        # [B, R] OBJECT ndarrays (bytes / RowVersion / key-value lists)
        # so compaction slices whole blocks as views:
        self.row_keys: np.ndarray | None = None     # [B, R] object (b"" pad)
        self.row_versions: np.ndarray | None = None  # [B, R] object
        self.min_key = b""
        self.max_key = b""
        self.max_ht = 0
        # Largest key-group version count. 1 means the run is "flat": every
        # row is its own group, so device kernels can skip the segmented
        # MVCC merge machinery entirely (the common post-compaction shape).
        self.max_group_versions = 0
        # Longest varlen value per column (bytes): values <= 8 are fully
        # captured by the device prefix planes, making prefix equality
        # EXACT — the device GROUP BY eligibility check for strings.
        self.varlen_max_len: dict[int, int] = {}
        # Longest encoded key (bytes): keys <= 32 are fully captured by
        # the KEY_WORDS prefix planes, so plane equality/order is EXACT —
        # the device compaction eligibility check.
        self.max_key_len = 0
        # Lazily-built per-key-column object arrays (global row index ->
        # decoded key value) for C-speed fancy-indexed materialization of
        # key columns on the batched scan path; decoded block-by-block
        # under a lock (concurrent scans share one tablet's run).
        import threading

        self._kv_cols: list[np.ndarray] | None = None
        self._kv_blocks_done: set[int] = set()
        # Lazily-encoded compressed device plane tree (ops.encodings):
        # (cache_key, tree) — recomputed when the encoding flag flips or
        # alter_schema grows the column set. ``enc_dicts`` holds each
        # dictionary-encoded column's sorted value list so the engine
        # can translate string predicates to code-range compares.
        self._enc_cache: tuple | None = None
        # True: upload the plain planes whatever the flag says (the
        # delta overlay's mini-run: an encoded tree's structure follows
        # the content, and every rebuild would meet another program).
        self.plain_planes = False
        self.enc_dicts: dict[int, list[bytes]] = {}
        self.enc_stats: dict | None = None
        self.kv_ready = False  # True once every block's keys are decoded
        # Hashed-prefix bloom (storage.bloom): None = not built yet,
        # True = inapplicable (range-partitioned keys present).
        self._hash_bloom = None
        self._kv_lock = threading.Lock()

    # -- construction ------------------------------------------------------
    @staticmethod
    def build(schema: Schema, entries: list[tuple[bytes, list[RowVersion]]],
              rows_per_block: int = DEFAULT_ROWS_PER_BLOCK,
              plain_planes: bool = False) -> "ColumnarRun":
        """entries: (key asc, versions ht-desc) — MemTable.drain_sorted() or a
        compaction merge. Packs key groups into blocks without splitting.
        ``plain_planes``: the run uploads plain whatever the flag says."""
        run = ColumnarRun(schema, rows_per_block)
        run.plain_planes = plain_planes
        R = run.R
        for key, versions in entries:
            n = len(versions)
            if n > run.max_group_versions:
                run.max_group_versions = n
            if len(key) > run.max_key_len:
                run.max_key_len = len(key)
        # Greedy block packing, key groups kept whole (shared with the
        # device-compaction gather path).
        ranges = ColumnarRun.pack_group_ranges(
            [len(v) for _, v in entries], R)
        blocks = [entries[g0:g0 + gn] for g0, gn, _rows in ranges]
        B = max(1, len(blocks))
        run.B = B
        run._alloc(B)
        for b, group_list in enumerate(blocks):
            run._fill_block(b, group_list)
        run.min_key = blocks[0][0][0] if blocks else b""
        run.max_key = blocks[-1][-1][0] if blocks else b""
        run.num_versions = sum(len(v) for _, v in entries)
        return run

    # Value-column kinds the native flush understands (drain_run).
    _NATIVE_KIND = {
        DataType.INT8: 0, DataType.INT16: 0, DataType.INT32: 0,
        DataType.INT64: 0, DataType.TIMESTAMP: 0, DataType.COUNTER: 0,
        DataType.BOOL: 0,
        DataType.DOUBLE: 1, DataType.FLOAT: 2,
        DataType.STRING: 3, DataType.BINARY: 3, DataType.LIST: 3,
        DataType.SET: 3, DataType.MAP: 3, DataType.JSONB: 3,
        DataType.DECIMAL: 3, DataType.VARINT: 3, DataType.UUID: 3,
        DataType.TIMEUUID: 3, DataType.INET: 3, DataType.DATE: 3,
        DataType.TIME: 3, DataType.TUPLE: 3, DataType.FROZEN: 3,
    }

    @staticmethod
    def build_from_memtable(schema: Schema, mt,
                            rows_per_block: int) -> "ColumnarRun | None":
        """The native flush path: one C pass over the sorted memtable
        (yb_wp.Memtable.drain_run) emits flat packed buffers — block
        packing, key prefixes, per-column values, RowVersion payloads —
        and this assembles the [B, R] planes with vectorized numpy only
        (no per-row Python anywhere). Returns None when the memtable
        shape needs the generic path (spilled big-int rows, value kinds
        the C pass doesn't cover) — callers fall back to
        drain_sorted() + build(). Reference analog: the rocksdb flush
        building the SSTable straight off the memtable iterator
        (src/yb/rocksdb/db/flush_job.cc)."""
        native_mt = getattr(mt, "_mt", None)
        if native_mt is None or getattr(mt, "_spill", None):
            return None
        desc = []
        for c in schema.value_columns:
            kind = ColumnarRun._NATIVE_KIND.get(c.dtype)
            if kind is None:
                return None
            desc.append((c.col_id, kind))
        try:
            data = native_mt.drain_run(rows_per_block, KEY_WORDS, desc)
        except (TypeError, ValueError):
            return None  # value shape outside the C pass: generic path
        n = data["n"]
        run = ColumnarRun(schema, rows_per_block)
        R = rows_per_block
        ranges = np.frombuffer(data["ranges"], np.int32).reshape(-1, 3)
        B = max(1, ranges.shape[0])
        run.B = B
        run._alloc(B)
        run.max_key_len = data["max_key_len"]
        run.max_group_versions = max(run.max_group_versions,
                                     data["max_group"])
        run.num_versions = n
        sizes = np.frombuffer(data["group_sizes"], np.int32)
        keys_list = data["keys"]
        if n == 0:
            return run
        # Destination slot of packed row i: blocks keep whole key
        # groups; rows pack densely from each block's start.
        rows_per = ranges[:, 2]
        block_of = np.repeat(np.arange(ranges.shape[0], dtype=np.int64),
                             rows_per)
        offs = np.cumsum(rows_per) - rows_per
        dst = block_of * R + (np.arange(n, dtype=np.int64)
                              - np.repeat(offs, rows_per))

        def scatter(dest, vals):
            dest.reshape((dest.shape[0] * R,) + dest.shape[2:])[dst] = \
                vals

        ht = np.frombuffer(data["ht"], np.uint64)
        hi, lo = P.u64_to_planes(ht)
        scatter(run.ht_hi, hi)
        scatter(run.ht_lo, lo)
        run.max_ht = int(ht.max())
        ehi, elo = P.u64_to_planes(
            np.frombuffer(data["exp"], np.uint64) & np.uint64(MAX_HT))
        scatter(run.exp_hi, ehi)
        scatter(run.exp_lo, elo)
        scatter(run.tomb, np.frombuffer(data["tomb"], np.uint8)
                .astype(bool))
        scatter(run.live, np.frombuffer(data["live"], np.uint8)
                .astype(bool))
        run.valid.reshape(-1)[dst] = True
        gfirst = np.cumsum(sizes) - sizes
        gs = np.zeros(n, dtype=bool)
        gs[gfirst] = True
        scatter(run.group_start, gs)
        kw = np.frombuffer(data["keywords"], ">u4").reshape(
            n, KEY_WORDS).astype(np.uint32)
        scatter(run.key_planes, P.u32_to_plane(kw))
        keys_arr = np.empty(len(keys_list), dtype=object)
        keys_arr[:] = keys_list
        scatter(run.row_keys, np.repeat(keys_arr, sizes))
        vers_arr = np.empty(n, dtype=object)
        vers_arr[:] = data["versions"]
        scatter(run.row_versions, vers_arr)

        for cid, entry in data["cols"].items():
            col = run.cols.get(cid)
            if col is None:
                continue
            rows = np.frombuffer(entry["rows"], np.int32)
            if rows.size == 0:
                continue
            gdst = dst[rows]
            col.set_.reshape(-1)[gdst] = True
            nulls = np.frombuffer(entry["nulls"], np.int32)
            if nulls.size:
                col.isnull.reshape(-1)[dst[nulls]] = True
            nn = rows if not nulls.size else np.setdiff1d(
                rows, nulls, assume_unique=True)
            ndst = dst[nn] if nulls.size else gdst
            kind = entry["kind"]
            cmp_flat = col.cmp_planes.reshape(
                -1, col.cmp_planes.shape[-1])
            if kind == 0:
                arr = np.frombuffer(entry["ivals"], np.int64)
                if cmp_flat.shape[-1] == 2:
                    chi, clo = P.i64_to_ordered_planes(arr)
                    cmp_flat[ndst, 0] = chi
                    cmp_flat[ndst, 1] = clo
                else:
                    cmp_flat[ndst, 0] = arr.astype(np.int32)
                if col.arith is not None:
                    col.arith.reshape(-1)[ndst] = arr.astype(np.float32)
            elif kind in (1, 2):
                arr = np.frombuffer(entry["dvals"], np.float64)
                if kind == 2:
                    f32 = arr.astype(np.float32)
                    cmp_flat[ndst, 0] = f32.view(np.int32)
                    col.arith.reshape(-1)[ndst] = f32
                else:
                    chi, clo = P.f64_to_ordered_planes(arr)
                    cmp_flat[ndst, 0] = chi
                    cmp_flat[ndst, 1] = clo
                    col.arith.reshape(-1)[ndst] = arr.astype(np.float32)
            else:  # varlen: prefixes from C; containers re-prefixed here
                pre = np.frombuffer(entry["prefix"], np.uint64).copy()
                pyvals = entry["pyvals"]
                maxlen = entry["maxlen"]
                for fix_row in entry["pyfix"]:
                    pos = int(np.searchsorted(nn, fix_row))
                    raw = _varlen_raw(pyvals[pos])
                    pre[pos] = int.from_bytes(
                        raw[:8].ljust(8, b"\x00"), "big")
                    maxlen = max(maxlen, len(raw))
                phi = P.u32_to_plane(
                    (pre >> np.uint64(32)).astype(np.uint32))
                plo = P.u32_to_plane(
                    (pre & np.uint64(0xFFFFFFFF)).astype(np.uint32))
                cmp_flat[ndst, 0] = phi
                cmp_flat[ndst, 1] = plo
                if maxlen > run.varlen_max_len.get(cid, 0):
                    run.varlen_max_len[cid] = maxlen
                bpos = (ndst // R).astype(np.int64)
                rpos = (ndst % R).astype(np.int64)
                vl = col.varlen
                for i in range(len(pyvals)):
                    vl[bpos[i]][rpos[i]] = pyvals[i]

        for b in range(ranges.shape[0]):
            g0, gn, nrows = (int(ranges[b, 0]), int(ranges[b, 1]),
                             int(ranges[b, 2]))
            run.blocks[b] = BlockMeta(keys_list[g0],
                                      keys_list[g0 + gn - 1], nrows)
        run.min_key = keys_list[0]
        run.max_key = keys_list[-1]
        return run

    @staticmethod
    def pack_group_ranges(sizes: list[int], R: int):
        """Greedy packing of whole key groups into R-row blocks:
        [(first_group_index, group_count, row_count)] per block. The ONE
        packing implementation — build() and device compaction share it,
        so their block layouts always agree."""
        ranges = []
        g0, gn, fill = 0, 0, 0
        for gi, n in enumerate(sizes):
            if n > R:
                raise ValueError(
                    f"key has {n} versions > rows_per_block={R}; "
                    "GC history (compact with a cutoff) to shrink it")
            if fill + n > R and fill > 0:
                ranges.append((g0, gn, fill))
                g0, gn, fill = gi, 0, 0
            gn += 1
            fill += n
        if fill > 0 or not ranges:
            if gn > 0:
                ranges.append((g0, gn, fill))
        return ranges

    def _alloc(self, B: int) -> None:
        R = self.R
        self.key_planes = np.zeros((B, R, KEY_WORDS), dtype=np.int32)
        self.ht_hi = np.zeros((B, R), dtype=np.int32)
        self.ht_lo = np.zeros((B, R), dtype=np.int32)
        maxhi, maxlo = P.scalar_ht_planes(MAX_HT)
        self.exp_hi = np.full((B, R), maxhi, dtype=np.int32)
        self.exp_lo = np.full((B, R), maxlo, dtype=np.int32)
        self.tomb = np.zeros((B, R), dtype=bool)
        self.live = np.zeros((B, R), dtype=bool)
        self.valid = np.zeros((B, R), dtype=bool)
        # Padding rows are each their own group so they never join a real one.
        self.group_start = np.ones((B, R), dtype=bool)
        for c in self.schema.value_columns:
            P_cmp = 2 if c.dtype.device_planes == 2 else 1
            self.cols[c.col_id] = ColumnData(
                dtype=c.dtype,
                set_=np.zeros((B, R), dtype=bool),
                isnull=np.zeros((B, R), dtype=bool),
                cmp_planes=np.zeros((B, R, P_cmp), dtype=np.int32),
                arith=(np.zeros((B, R), dtype=np.float32)
                       if c.dtype.is_numeric else None),
                varlen=([[None] * R for _ in range(B)]
                        if not c.dtype.is_fixed_width else None),
            )
        # Object NDARRAYS (not lists): compaction slices whole blocks of
        # row payloads as views instead of per-row pointer copies.
        self.row_keys = np.empty((B, R), dtype=object)
        self.row_keys[:] = b""
        self.row_versions = np.empty((B, R), dtype=object)
        self.row_key_vals = np.empty((B, R), dtype=object)
        self.blocks = [BlockMeta(b"", b"", 0) for _ in range(B)]

    def _fill_block(self, b: int, group_list) -> None:
        """Encode one block's rows. One cheap Python pass collects parallel
        per-plane lists; every plane then encodes with a single vectorized
        numpy call (the per-row scalar encode was the write-path
        bottleneck: ~15 tiny numpy ops per version)."""
        keys_flat: list[bytes] = []
        vers_flat: list[RowVersion] = []
        gs: list[bool] = []
        hts: list[int] = []
        tombs: list[bool] = []
        lives: list[bool] = []
        exp_idx: list[int] = []
        exp_hts: list[int] = []
        col_rows: dict[int, list[int]] = {cid: [] for cid in self.cols}
        col_vals: dict[int, list] = {cid: [] for cid in self.cols}
        r = 0
        for key, versions in group_list:
            first = True
            for v in versions:
                gs.append(first)
                first = False
                keys_flat.append(key)
                vers_flat.append(v)
                hts.append(v.ht)
                tombs.append(v.tombstone)
                lives.append(v.liveness)
                if v.has_ttl:
                    exp_idx.append(r)
                    exp_hts.append(v.expire_ht)
                for cid, val in v.columns.items():
                    if cid in col_rows:  # dropped columns: id retired
                        col_rows[cid].append(r)
                        col_vals[cid].append(val)
                r += 1
        n = r
        self.blocks[b] = BlockMeta(
            group_list[0][0] if group_list else b"",
            group_list[-1][0] if group_list else b"",
            n,
        )
        if n == 0:
            return
        self.valid[b, :n] = True
        self.group_start[b, :n] = gs
        self.tomb[b, :n] = tombs
        self.live[b, :n] = lives
        self.row_keys[b][:n] = keys_flat
        self.row_versions[b][:n] = vers_flat
        ht_arr = np.array(hts, dtype=np.int64)
        hi, lo = P.ht_to_planes(ht_arr)
        self.ht_hi[b, :n] = hi
        self.ht_lo[b, :n] = lo
        self.max_ht = max(self.max_ht, int(ht_arr.max()))
        if exp_idx:
            ehi, elo = P.ht_to_planes(np.array(exp_hts, dtype=np.int64))
            self.exp_hi[b, exp_idx] = ehi
            self.exp_lo[b, exp_idx] = elo
        kp = P.key_prefix_planes(keys_flat, KEY_WORDS)
        self.key_planes[b, :n] = kp
        for cid in self.cols:
            if col_rows[cid]:
                self._fill_column(b, cid, col_rows[cid], col_vals[cid])

    def _fill_column(self, b: int, cid: int, rows: list[int],
                     vals: list) -> None:
        """Vectorized encode of one column's set values within a block."""
        col = self.cols[cid]
        col.set_[b, rows] = True
        nn_rows = rows
        nn_vals = vals
        if any(v is None for v in vals):
            null_rows = [r for r, v in zip(rows, vals) if v is None]
            col.isnull[b, null_rows] = True
            nn_rows = [r for r, v in zip(rows, vals) if v is not None]
            nn_vals = [v for v in vals if v is not None]
            if not nn_rows:
                return
        dt = col.dtype
        if dt.is_integer or dt == DataType.BOOL:
            if dt == DataType.BOOL:
                arr = np.array([int(bool(v)) for v in nn_vals],
                               dtype=np.int64)
            else:
                arr = np.array(nn_vals, dtype=np.int64)
            if col.cmp_planes.shape[-1] == 2:
                hi, lo = P.i64_to_ordered_planes(arr)
                col.cmp_planes[b, nn_rows, 0] = hi
                col.cmp_planes[b, nn_rows, 1] = lo
            else:
                col.cmp_planes[b, nn_rows, 0] = arr
            if col.arith is not None:  # BOOL: orderable but not numeric
                col.arith[b, nn_rows] = arr.astype(np.float32)
        elif dt == DataType.FLOAT:
            arr = np.array(nn_vals, dtype=np.float32)
            col.cmp_planes[b, nn_rows, 0] = arr.view(np.int32)
            col.arith[b, nn_rows] = arr
        elif dt == DataType.DOUBLE:
            arr = np.array(nn_vals, dtype=np.float64)
            hi, lo = P.f64_to_ordered_planes(arr)
            col.cmp_planes[b, nn_rows, 0] = hi
            col.cmp_planes[b, nn_rows, 1] = lo
            col.arith[b, nn_rows] = arr.astype(np.float32)
        else:  # STRING / BINARY / opaque (collections, jsonb)
            raws = [_varlen_raw(v) for v in nn_vals]
            hi, lo = P.varlen_prefix_planes(raws)
            col.cmp_planes[b, nn_rows, 0] = hi
            col.cmp_planes[b, nn_rows, 1] = lo
            vl = col.varlen[b]
            for r, v in zip(nn_rows, nn_vals):
                vl[r] = v
            longest = max(map(len, raws))
            if longest > self.varlen_max_len.get(cid, 0):
                self.varlen_max_len[cid] = longest

    # -- compressed device planes (ops.encodings) ---------------------------
    def encoded_arrays(self):
        """The compressed device plane tree for this run, or None when
        --tpu_plane_encoding=off (or the run is empty): upload the plain
        planes instead. Encoded once per run and cached — demand
        re-uploads after eviction reuse the same compressed tree."""
        from yugabyte_db_tpu.utils.flags import FLAGS

        if self.plain_planes:
            return None
        key = (FLAGS.get("tpu_plane_encoding"), len(self.cols))
        if self._enc_cache is not None and self._enc_cache[0] == key:
            return self._enc_cache[1]
        tree = None
        if key[0] != "off" and self.num_versions:
            tree = self._encode_planes()
        self._enc_cache = (key, tree)
        return tree

    def _encode_planes(self):
        """One cheap stats pass per plane picks its encoding; every
        fallback is per plane (a pathological column stays plain while
        its neighbours compress)."""
        from yugabyte_db_tpu.ops import encodings as enc

        tree = {
            "valid": enc.encode_bool_plane(self.valid),
            "group_start": enc.encode_bool_plane(self.group_start),
            "tomb": enc.encode_bool_plane(self.tomb),
            "live": enc.encode_bool_plane(self.live),
            "ht_hi": enc.encode_int_plane(self.ht_hi),
            "ht_lo": enc.encode_int_plane(self.ht_lo),
            "exp_hi": enc.encode_int_plane(self.exp_hi),
            "exp_lo": enc.encode_int_plane(self.exp_lo),
            "cols": {},
        }
        self.enc_dicts = {}
        for cid, col in self.cols.items():
            entry = {"set": enc.encode_bool_plane(col.set_),
                     "isnull": enc.encode_bool_plane(col.isnull)}
            cmp_leaf = None
            if col.dtype in (DataType.STRING, DataType.BINARY):
                cmp_leaf = self._encode_dict_col(cid, col)
            if cmp_leaf is None:
                cmp_leaf = enc.encode_int_plane(col.cmp_planes)
            entry["cmp"] = cmp_leaf
            if col.arith is not None and col.dtype in (
                    DataType.FLOAT, DataType.DOUBLE):
                # Float arith planes are the value itself and must
                # upload; every other numeric kind aggregates exactly
                # from the cmp planes on device, so its arith plane is
                # redundant there and is simply omitted from the tree.
                entry["arith"] = enc.encode_float_plane(col.arith)
            tree["cols"][cid] = entry
        self.enc_stats = enc.tree_stats(tree)
        return tree

    def _encode_dict_col(self, cid: int, col: ColumnData):
        """Per-run sorted dictionary for a string/binary column, or None
        (dict overflow / no set rows) — the caller falls back to the
        prefix-plane int encodings. The dictionary is the sorted unique
        FULL values, so codes order exactly as values do and the last
        (absent) slot decodes the zero prefix planes unset/NULL rows
        hold in the plain format."""
        from yugabyte_db_tpu.ops import encodings as enc

        if col.varlen is None:
            return None
        nn = col.set_ & ~col.isnull
        bi, ri = np.nonzero(nn)
        if bi.size == 0:
            return None
        raws = [_varlen_raw(col.varlen[b][r])
                for b, r in zip(bi.tolist(), ri.tolist())]
        uniq = sorted(set(raws))
        if len(uniq) > enc.DICT_MAX_VALUES:
            return None
        cap = enc.pow2_bucket(len(uniq) + 1)
        hi, lo = P.varlen_prefix_planes(uniq)
        dhi = np.zeros(cap, np.int32)
        dlo = np.zeros(cap, np.int32)
        dhi[:len(uniq)] = hi
        dlo[:len(uniq)] = lo
        code_of = {v: i for i, v in enumerate(uniq)}
        codes = np.full((self.B, self.R), cap - 1, np.int64)
        codes[bi, ri] = [code_of[v] for v in raws]
        self.enc_dicts[cid] = uniq
        return enc.dict_leaf(codes, dhi, dlo)

    # -- host-side access (compaction input, materialization) -------------
    def iter_entries(self):
        """Yield (key, versions ht-desc) in key order — compaction input."""
        for b in range(self.B):
            meta = self.blocks[b]
            r = 0
            while r < meta.num_valid:
                key = self.row_keys[b][r]
                versions = []
                while r < meta.num_valid and self.row_keys[b][r] == key:
                    versions.append(self.row_versions[b][r])
                    r += 1
                yield key, versions

    def group_versions(self, b: int, r: int) -> tuple[bytes, list[RowVersion]]:
        """The key group starting at (block b, row r) — r must be group_start."""
        key = self.row_keys[b][r]
        versions = []
        meta = self.blocks[b]
        while r < meta.num_valid and self.row_keys[b][r] == key:
            versions.append(self.row_versions[b][r])
            r += 1
        return key, versions

    # -- exact host-side key location (bounds, point lookups) --------------
    def lower_row(self, key: bytes) -> int:
        """Global row index (b*R + r) of the first valid row with
        row_key >= key. Exact on full key bytes — this is what turns scan
        bounds into device row-index bounds with no prefix-tie ambiguity."""
        import bisect as _bisect

        if self.B == 0 or not self.blocks[0].num_valid:
            return 0
        maxes = getattr(self, "_block_maxes", None)
        if maxes is None:
            # Runs are immutable once built; cache the per-block max-key
            # list (page scans bisect this on every request).
            maxes = self._block_maxes = [m.max_key for m in self.blocks
                                         if m.num_valid]
        b = _bisect.bisect_left(maxes, key)
        if b >= len(maxes):
            return self.total_rows()
        meta = self.blocks[b]
        r = _bisect.bisect_left(self.row_keys[b], key, 0, meta.num_valid)
        return b * self.R + r

    def upper_row(self, upper: bytes) -> int:
        """Global row index bound for exclusive upper (b'' = unbounded)."""
        if not upper:
            return self.total_rows()
        return self.lower_row(upper)

    def total_rows(self) -> int:
        return self.B * self.R

    def find_versions(self, key: bytes) -> list[RowVersion]:
        """All versions of key in this run (ht-desc), or []."""
        import bisect as _bisect

        row = self.lower_row(key)
        if row >= self.total_rows():
            return []
        b, r = divmod(row, self.R)
        if b >= self.B or r >= self.blocks[b].num_valid or \
                self.row_keys[b][r] != key:
            return []
        out = []
        meta = self.blocks[b]
        while r < meta.num_valid and self.row_keys[b][r] == key:
            out.append(self.row_versions[b][r])
            r += 1
        return out

    def key_at(self, global_row: int) -> bytes:
        b, r = divmod(global_row, self.R)
        return self.row_keys[b][r]

    def key_vals_at(self, global_row: int) -> list:
        """Decoded key-column values (hashed + range) of the row's key,
        memoized per row so repeated scans never re-decode."""
        from yugabyte_db_tpu.models.encoding import decode_doc_key

        b, r = divmod(global_row, self.R)
        kv = self.row_key_vals[b][r]
        if kv is None:
            _, hashed, ranges = decode_doc_key(self.row_keys[b][r])
            kv = self.row_key_vals[b][r] = hashed + ranges
        return kv

    def key_col_arrays(self, blocks=None) -> list[np.ndarray]:
        """One object ndarray per key column, indexed by global row index
        (b*R + r), holding the decoded key value. Decoded lazily PER
        BLOCK (``blocks``: iterable of block indices a scan touched;
        None = all) so a small page never pays an O(run) decode pass;
        batched scans then materialize key columns with one numpy
        fancy-index instead of per-row Python."""
        from yugabyte_db_tpu.models.encoding import decode_doc_key

        nk = len(self.schema.key_columns)
        if self.kv_ready:  # lock-free fast path once fully decoded
            return self._kv_cols
        with self._kv_lock:
            if self._kv_cols is None:
                self._kv_cols = [np.empty(self.B * self.R, dtype=object)
                                 for _ in range(nk)]
            cols = self._kv_cols
            todo = range(self.B) if blocks is None else blocks
            for b in todo:
                if b in self._kv_blocks_done or b >= self.B:
                    continue
                n = self.blocks[b].num_valid
                rk = self.row_keys[b]
                kvs = self.row_key_vals[b]
                base = b * self.R
                for r in range(n):
                    kv = kvs[r]
                    if kv is None:
                        _, hashed, ranges = decode_doc_key(rk[r])
                        kv = kvs[r] = hashed + ranges
                    for p in range(nk):
                        cols[p][base + r] = kv[p]
                # marked done only after the block is fully decoded, so a
                # concurrent reader can never see half-filled rows
                self._kv_blocks_done.add(b)
            if len(self._kv_blocks_done) == self.B:
                self.kv_ready = True
        return cols

    # -- run pruning (hashed-prefix bloom) ----------------------------------
    @property
    def bloom_ready(self) -> bool:
        """True once the lazy hash bloom exists (callers use this to
        avoid paying the build for workloads where a binary search on
        one or two runs is already cheap)."""
        return self._hash_bloom is not None

    def may_contain_hashed(self, prefix: bytes) -> bool:
        """Can this run contain any key with the given hashed-components
        prefix? False lets point gets / single-key scans skip the run
        entirely (reference: DocDbAwareFilterPolicy,
        src/yb/docdb/doc_key.h:551-575). Never a false negative."""
        bl = self._hash_bloom
        if bl is None:
            bl = self._build_hash_bloom()
        if bl is True:
            return True
        return bl.may_contain(prefix)

    def _build_hash_bloom(self):
        from yugabyte_db_tpu.models.encoding import hashed_prefix
        from yugabyte_db_tpu.storage.bloom import BloomFilter

        with self._kv_lock:
            if self._hash_bloom is not None:
                return self._hash_bloom
            bl = BloomFilter(self.num_versions or 1)
            prefixes: list[bytes] = []
            last = None
            for b in range(self.B):
                n = self.blocks[b].num_valid
                rk = self.row_keys[b]
                for r in range(n):
                    key = rk[r]
                    hp = hashed_prefix(key)
                    if not hp:
                        self._hash_bloom = True  # filter inapplicable
                        return True
                    if hp != last:
                        prefixes.append(hp)
                        last = hp
            bl.add_many(prefixes)
            self._hash_bloom = bl
            return bl

    # -- block pruning -----------------------------------------------------
    def block_range(self, lower: bytes, upper: bytes) -> tuple[int, int]:
        """[b0, b1) of blocks that may contain keys in [lower, upper)."""
        if self.B == 0 or not self.blocks[0].num_valid:
            return 0, 0
        b0 = 0
        while b0 < self.B and self.blocks[b0].num_valid and \
                self.blocks[b0].max_key < lower:
            b0 += 1
        b1 = self.B
        if upper:
            while b1 > b0 and (not self.blocks[b1 - 1].num_valid or
                               self.blocks[b1 - 1].min_key >= upper):
                b1 -= 1
        while b1 > b0 and not self.blocks[b1 - 1].num_valid:
            b1 -= 1
        return b0, b1
