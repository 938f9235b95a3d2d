"""The storage-engine seam: the pluggable boundary the query layer scans through.

Reference analog: common::YQLStorageIf (src/yb/common/ql_storage_interface.h:31)
— the only interface the query execution layer uses to read a tablet, with
the engine selected where the tablet injects its storage
(src/yb/tablet/tablet.h:648). Here the seam also carries writes (the
reference applies writes through rocksdb::DB::Write below the same tablet).

Engines:
- ``cpu``: exact Python/numpy engine — the correctness oracle and the
  baseline the TPU engine is benchmarked against.
- ``tpu``: columnar HBM-resident data plane driven by JAX/Pallas kernels
  (the ``tablet_storage_engine=tpu`` option of the north star).
"""

from __future__ import annotations

import abc

from yugabyte_db_tpu.models.schema import Schema
from yugabyte_db_tpu.storage.row_version import RowVersion
from yugabyte_db_tpu.storage.scan_spec import ScanResult, ScanSpec


class StorageEngine(abc.ABC):
    """Per-tablet storage: an LSM of MVCC row versions behind a scan API."""

    def __init__(self, schema: Schema, options: dict | None = None):
        from yugabyte_db_tpu.utils.memtracker import root_tracker

        self.schema = schema
        self.options = dict(options or {})
        # Hierarchical memory accounting: root -> memstore -> this engine
        # (reference: the MemTracker tree + the shared memstore budget,
        # mem_tracker.h / docdb_rocksdb_util.cc:437 memory_monitor).
        self.mem_tracker = root_tracker().child("memstore").child(
            self.options.get("tracker_name", f"engine-{id(self):x}"))
        self._tracked_bytes = 0
        # Engines with a device dispatch path install a CircuitBreaker
        # (storage/breaker.py) here; None = pure-host engine, nothing to
        # quarantine. /healthz and yb_engine_degraded read the breaker
        # registry, not this attribute.
        self.breaker = None

    def _track_memstore(self) -> None:
        """Sync this engine's tracker with its memtable size. Crossing
        the GLOBAL memstore budget flushes this engine only when it is
        (one of) the LARGEST memstore consumers — flushing whichever
        writer merely noticed would storm tiny flushes while the real
        offender stays resident (the reference's memory monitor also
        picks the largest memstore). An over-budget engine that never
        writes again keeps its memory until its own next apply/flush."""
        from yugabyte_db_tpu.utils.flags import FLAGS

        mem = getattr(self, "memtable", None)
        current = 0 if mem is None else mem.approx_bytes
        delta = current - self._tracked_bytes
        if delta:
            self.mem_tracker.consume(delta)
            self._tracked_bytes = current
        parent = self.mem_tracker.parent
        if current and parent is not None and \
                parent.consumption > FLAGS.get("global_memstore_limit_bytes"):
            with parent._lock:
                largest = max((c.consumption
                               for c in parent._children.values()),
                              default=0)
            if current >= largest:
                self.flush()
                self._track_memstore()  # memtable swapped: release to 0

    # -- writes ------------------------------------------------------------
    @abc.abstractmethod
    def apply(self, rows: list[RowVersion]) -> None:
        """Apply committed row versions (the Raft-apply stage calls this)."""

    def apply_block(self, block: bytes) -> None:
        """Apply an encoded row block (storage.rowblock layout) — the
        native write path's zero-materialization ingest. The default
        decodes and delegates; engines with a block-aware memtable
        override it."""
        from yugabyte_db_tpu.storage import rowblock

        self.apply(rowblock.rows_from_block(block))

    # -- reads -------------------------------------------------------------
    @abc.abstractmethod
    def scan(self, spec: ScanSpec) -> ScanResult:
        """MVCC scan/aggregate at spec.read_ht over [lower, upper)."""

    def scan_batch(self, specs: list[ScanSpec],
                   deadline=None) -> list[ScanResult]:
        """Execute many scans. Engines with an accelerator data plane
        override this to pipeline device dispatches (one host↔device
        round-trip for the whole batch) — the analog of the reference
        serving hundreds of concurrent YCSB clients per tserver.
        ``deadline`` (utils.retry.Deadline) is the RPC edge's propagated
        budget: the batch aborts with Code.TIMED_OUT instead of serving
        results nobody is waiting for."""
        out = []
        for s in specs:
            if deadline is not None:
                deadline.check("scan_batch")
            out.append(self.scan(s))
        return out

    def scan_batch_wire(self, specs: list[ScanSpec], fmt: str = "cql",
                        deadline=None):
        """Execute many scans and return each result as serialized
        protocol bytes (host_page.WirePage): fmt "cql" = CQL binary
        cells, "pg" = PG text DataRow messages. This base implementation
        scans then serializes in Python (models.wirefmt — the format
        definition); the TPU engine overrides the LIMIT-page path with
        the native wire page server, which emits the same bytes straight
        from plane buffers. Reference contract: rows serialize once into
        rows_data (src/yb/common/ql_rowblock.h:66) and the YQL frontends
        forward bytes."""
        from yugabyte_db_tpu.storage.host_page import wire_from_result

        return [wire_from_result(self, r, fmt)
                for r in self.scan_batch(specs, deadline=deadline)]

    def point_serve(self, keys: list[bytes], read_ht: int, col_id: int):
        """Batch point-value lookup for the native request-batch serving
        path: one value column of each full-doc-key row, straight from
        the native memtable. Returns ``None`` when this engine cannot
        answer the batch definitively (sorted runs on disk, non-native
        memtable, spilled rows) — the caller falls back to the general
        read path. Otherwise a list aligned with ``keys`` whose entries
        are payload ``bytes``, ``None`` (absent row / NULL column), or
        ``False`` (value not natively servable: fall back per key)."""
        if getattr(self, "runs", None):
            return None
        lookup = getattr(getattr(self, "memtable", None),
                         "point_lookup", None)
        if lookup is None:
            return None
        return lookup(keys, read_ht, col_id)

    # -- lifecycle ---------------------------------------------------------
    @abc.abstractmethod
    def flush(self) -> None:
        """Persist the memtable as a new sorted run."""

    @abc.abstractmethod
    def compact(self, history_cutoff_ht: int = 0) -> None:
        """Merge all sorted runs into one, GCing history older than cutoff."""

    @abc.abstractmethod
    def stats(self) -> dict:
        """Observability counters (runs, rows, bytes, versions)."""

    def run_files(self):
        """Context manager: the paths of the run files as they stand,
        none of which goes away inside the block (a snapshot links
        them)."""
        import contextlib

        return contextlib.nullcontext(list(self.persist.files))

    def restore_entries(self, entries) -> None:
        """Replace ALL engine content (memtable + runs + persisted files)
        with the given (key, versions) entries — the snapshot-restore
        primitive. Subclasses rebuild their run representations."""
        raise NotImplementedError

    def alter_schema(self, new_schema: Schema) -> None:
        """Adopt an evolved schema (ALTER TABLE). Key columns never
        change; value columns may be added (NULL for existing rows),
        dropped (values become invisible; ids are never reused), or
        renamed (ids are stable, so data is untouched)."""
        self.schema = new_schema

    def compaction_trigger(self) -> int:
        from yugabyte_db_tpu.utils.flags import FLAGS

        return self.options.get("compaction_trigger",
                                FLAGS.get("compaction_trigger"))

    def maybe_compact(self, history_cutoff_ht: int = 0) -> bool:
        """Universal-compaction trigger: compact when run count reaches the
        threshold (reference: universal style with num_levels=1,
        docdb_rocksdb_util.cc:476-482)."""
        if self.stats().get("num_runs", 0) >= self.compaction_trigger():
            self.compact(history_cutoff_ht)
            return True
        return False

    def close(self) -> None:
        self.mem_tracker.detach()


# Universal (size-tiered) compaction's picker, with upstream's defaults
# (rocksdb universal_compaction_size_ratio / _min_merge_width, as
# docdb_rocksdb_util.cc leaves them).
COMPACTION_SIZE_RATIO_PCT = 20
COMPACTION_MIN_MERGE_WIDTH = 4


def pick_compaction(sizes: list[int], trigger: int) -> tuple[int, int] | None:
    """Which age-adjacent runs to merge: ``sizes`` are the runs' sizes
    (versions) from the NEWEST to the oldest; returns ``(first, count)``
    into that list, or None. Nothing under ``trigger`` runs. From each
    start, a stretch grows while the next (older) run is no larger than
    the sum of the stretch so far plus ``COMPACTION_SIZE_RATIO_PCT`` percent;
    the longest stretch of at least the minimum width wins, the newest
    on a tie. So a small run is never merged with one hundreds of times
    its size, and runs of like size are merged all at once (reference:
    UniversalCompactionPicker::PickCompactionUniversalReadAmp,
    src/yb/rocksdb/db/compaction_picker.cc)."""
    n = len(sizes)
    if n < max(2, trigger):
        return None
    width = max(2, min(COMPACTION_MIN_MERGE_WIDTH, trigger))
    best = None
    for first in range(n - width + 1):
        total, count = sizes[first], 1
        while first + count < n and \
                sizes[first + count] * 100 <= \
                total * (100 + COMPACTION_SIZE_RATIO_PCT):
            total += sizes[first + count]
            count += 1
        if count >= width and (best is None or count > best[1]):
            best = (first, count)
    return best


_ENGINES: dict[str, type] = {}


def register_engine(name: str, cls: type) -> None:
    _ENGINES[name] = cls


def make_engine(name: str, schema: Schema, options: dict | None = None) -> StorageEngine:
    """Factory behind the ``tablet_storage_engine`` option."""
    if name == "tpu" and name not in _ENGINES:
        # Lazy: importing the TPU engine pulls in jax; CPU-only paths
        # (tools, tests of the host layers) shouldn't pay for it.
        import yugabyte_db_tpu.storage.tpu_engine  # noqa: F401
    if name not in _ENGINES:
        raise ValueError(f"unknown storage engine {name!r}; have {sorted(_ENGINES)}")
    return _ENGINES[name](schema, options)
