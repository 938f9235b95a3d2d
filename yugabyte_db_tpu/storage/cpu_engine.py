"""CPU storage engine: the exact oracle and the CPU baseline.

Reference analog: the behavior of DocDB-on-RocksDB reads
(DocRowwiseIterator + IntentAwareIterator + GetSubDocument,
src/yb/docdb/doc_rowwise_iterator.cc) expressed directly: per-key version
lists in sorted runs, merged at read time by storage.merge. Also plays the
role of the in-memory model-checking oracle the reference uses in
randomized DocDB tests (InMemDocDbState, src/yb/docdb/in_mem_docdb.cc) —
the TPU engine must produce identical results on every scan.
"""

from __future__ import annotations

import bisect
import heapq

from yugabyte_db_tpu.models.encoding import decode_doc_key
from yugabyte_db_tpu.models.schema import Schema
from yugabyte_db_tpu.storage.engine import StorageEngine, register_engine
from yugabyte_db_tpu.storage.memtable import (MemTable, NativeMemTable,
                                              make_memtable)
from yugabyte_db_tpu.storage.merge import MergedRow, merge_versions
from yugabyte_db_tpu.storage.row_version import RowVersion
from yugabyte_db_tpu.storage.scan_spec import AggSpec, ScanResult, ScanSpec


class CpuRun:
    """One immutable sorted run: keys ascending, per-key versions ht-desc.

    Reference analog: one SSTable (block_based_table_reader) — here a plain
    sorted list because the CPU engine optimizes for being obviously correct.
    """

    def __init__(self, entries: list[tuple[bytes, list[RowVersion]]]):
        self.keys = [k for k, _ in entries]
        self.versions = [v for _, v in entries]
        self.num_versions = sum(len(v) for v in self.versions)
        self.min_key = self.keys[0] if self.keys else b""
        self.max_key = self.keys[-1] if self.keys else b""

    def scan_keys(self, lower: bytes, upper: bytes):
        i = bisect.bisect_left(self.keys, lower)
        while i < len(self.keys):
            k = self.keys[i]
            if upper and k >= upper:
                return
            yield k
            i += 1

    def get(self, key: bytes) -> list[RowVersion]:
        i = bisect.bisect_left(self.keys, key)
        if i < len(self.keys) and self.keys[i] == key:
            return self.versions[i]
        return []


class RowMaterializer:
    """Shared helper: merged row + decoded key -> output tuple / predicate eval.

    Key columns live in the encoded DocKey (not in the version columns), so
    materialization decodes them positionally (models.encoding layout).
    """

    def __init__(self, schema: Schema):
        self.schema = schema
        self._key_cols = {c.name: i for i, c in enumerate(schema.key_columns)}
        self._val_ids = {c.name: c.col_id for c in schema.value_columns}

    def key_values(self, key: bytes) -> list:
        _, hashed, ranges = decode_doc_key(key)
        return hashed + ranges

    def value(self, name: str, key_vals: list, merged: MergedRow):
        if name in self._key_cols:
            return key_vals[self._key_cols[name]]
        return merged.get(self._val_ids[name])

    def matches(self, spec: ScanSpec, key_vals: list, merged: MergedRow) -> bool:
        return all(
            p.matches(self.value(p.column, key_vals, merged))
            for p in spec.predicates
        )


class Aggregator:
    """Pushdown aggregation: count/sum/min/max/avg with optional GROUP BY.

    Reference analog: QLReadOperation::EvalAggregate /
    PgsqlReadOperation::EvalAggregate (per-tablet partials computed inside
    the scan, src/yb/docdb/pgsql_operation.cc:473).
    """

    def __init__(self, aggs: list[AggSpec], group_by: list[str]):
        self.aggs = aggs
        self.group_by = group_by
        self.groups: dict[tuple, list] = {}

    def _new_acc(self) -> list:
        return [None] * len(self.aggs)

    def add(self, get_value) -> None:
        from yugabyte_db_tpu.storage.expr import eval_expr

        gkey = tuple(get_value(c) for c in self.group_by)
        acc = self.groups.get(gkey)
        if acc is None:
            acc = self.groups[gkey] = self._new_acc()
        for i, a in enumerate(self.aggs):
            if a.fn == "count":
                if a.column is None and a.expr is None:
                    acc[i] = (acc[i] or 0) + 1
                else:
                    v = (eval_expr(a.expr, get_value)
                         if a.expr is not None else get_value(a.column))
                    if v is not None:
                        acc[i] = (acc[i] or 0) + 1
                continue
            v = (eval_expr(a.expr, get_value) if a.expr is not None
                 else get_value(a.column))
            if v is None:
                continue
            if a.fn == "sum":
                acc[i] = v if acc[i] is None else acc[i] + v
            elif a.fn == "min":
                acc[i] = v if acc[i] is None else min(acc[i], v)
            elif a.fn == "max":
                acc[i] = v if acc[i] is None else max(acc[i], v)
            elif a.fn == "avg":
                s, n = acc[i] or (0, 0)
                acc[i] = (s + v, n + 1)

    def results(self) -> list[tuple]:
        if not self.groups and not self.group_by:
            self.groups[()] = self._new_acc()
        rows = []
        for gkey in sorted(self.groups, key=lambda g: tuple(map(_sortable, g))):
            acc = self.groups[gkey]
            out = list(gkey)
            for i, a in enumerate(self.aggs):
                v = acc[i]
                if a.fn == "count":
                    v = v or 0
                elif a.fn == "avg" and v is not None:
                    v = v[0] / v[1]
                out.append(v)
            rows.append(tuple(out))
        return rows

    def column_names(self) -> list[str]:
        names = list(self.group_by)
        for a in self.aggs:
            names.append(a.output_name)
        return names


def _sortable(v):
    # Group keys may mix None with values; sort None first.
    return (v is None, v)


class CpuStorageEngine(StorageEngine):
    def __init__(self, schema: Schema, options: dict | None = None):
        super().__init__(schema, options)
        from yugabyte_db_tpu.storage.run_io import RunPersistence

        self.memtable = make_memtable()
        self.runs: list[CpuRun] = []
        self.mat = RowMaterializer(schema)
        self.flushed_frontier_ht = 0  # max ht persisted into runs
        self.persist = RunPersistence(self.options.get("data_dir"))
        for entries in self.persist.load_all():
            run = CpuRun(entries)
            self.runs.append(run)
            for versions in run.versions:
                for v in versions:
                    self.flushed_frontier_ht = max(self.flushed_frontier_ht, v.ht)

    # -- writes ------------------------------------------------------------
    def alter_schema(self, new_schema) -> None:
        super().alter_schema(new_schema)
        self.mat = RowMaterializer(new_schema)

    def apply(self, rows: list[RowVersion]) -> None:
        self.memtable.apply(rows)
        self._after_apply()

    def apply_block(self, block: bytes) -> None:
        self.memtable.apply_block(block)
        self._after_apply()

    def _after_apply(self) -> None:
        from yugabyte_db_tpu.utils.flags import FLAGS

        limit = self.options.get("memtable_flush_versions",
                                 FLAGS.get("memtable_flush_versions"))
        if self.memtable.num_versions >= limit:
            self.flush()
            self.maybe_compact()
        self._track_memstore()

    # -- lifecycle ---------------------------------------------------------
    def flush(self) -> None:
        if self.memtable.is_empty:
            return
        if self.memtable.max_ht is not None:
            self.flushed_frontier_ht = max(self.flushed_frontier_ht,
                                           self.memtable.max_ht)
        entries = self.memtable.drain_sorted()
        self.persist.save_new(entries)
        self.runs.append(CpuRun(entries))
        self.memtable = make_memtable()
        self._track_memstore()

    def restore_entries(self, entries) -> None:
        self.memtable = make_memtable()
        self.persist.replace_all(entries)
        self.runs = [CpuRun(entries)] if entries else []
        for _key, versions in entries:
            for v in versions:
                self.flushed_frontier_ht = max(self.flushed_frontier_ht,
                                               v.ht)

    def compact(self, history_cutoff_ht: int = 0) -> None:
        if len(self.runs) <= 1 and history_cutoff_ht == 0:
            return
        merged: list[tuple[bytes, list[RowVersion]]] = []
        for key, versions in self._merge_runs_by_key():
            kept = self._gc_versions(key, versions, history_cutoff_ht)
            if kept:
                merged.append((key, kept))
        self.persist.replace_all(merged)
        self.runs = [CpuRun(merged)] if merged else []

    def _merge_runs_by_key(self):
        """Yield (key, versions ht-desc) over all runs, key-merged.

        Reference analog: the MergingIterator k-way merge inside
        CompactionJob::Run (src/yb/rocksdb/db/compaction_job.cc:622).
        """
        def run_iter(run):
            return ((k, run) for k in run.scan_keys(b"", b""))

        iters = [run_iter(run) for run in self.runs]
        current_key = None
        bucket: list[RowVersion] = []
        for key, run in heapq.merge(*iters, key=lambda p: p[0]):
            if key != current_key:
                if current_key is not None:
                    yield current_key, sorted(bucket, key=lambda r: (-r.ht, -r.write_id))
                current_key, bucket = key, []
            bucket.extend(run.get(key))
        if current_key is not None:
            yield current_key, sorted(bucket, key=lambda r: (-r.ht, -r.write_id))

    @staticmethod
    def _gc_versions(key: bytes, versions: list[RowVersion], cutoff: int,
                     keep_tombstones: bool = False) -> list[RowVersion]:
        """History GC: keep versions needed by any read at read_ht >= cutoff.
        ``keep_tombstones``: the versions are not all there are of the
        key (a compaction of some runs, the oldest not among them), so
        the newest row tombstone at or under the cutoff stays to shadow
        what an older run may hold.

        Reference analog: DocDBCompactionFilter retention
        (src/yb/docdb/docdb_compaction_filter.cc) driven by
        TabletRetentionPolicy's history cutoff.
        """
        if cutoff <= 0:
            return versions
        state = merge_versions(key, versions, cutoff)
        contributing = set(state.value_hts.values())
        if state.live_ht:
            contributing.add(state.live_ht)
        kept = [
            v for v in versions
            if v.ht > cutoff or (v.ht in contributing and v.ht > state.tomb_ht)
            or (keep_tombstones and v.tombstone and v.ht == state.tomb_ht)
        ]
        return kept  # tombstones <= cutoff drop: nothing older remains to shadow

    def dump_entries(self):
        """All flushed (key, versions ht-desc) pairs, key-merged across
        runs — the storage payload of a remote-bootstrap session."""
        return list(self._merge_runs_by_key())

    def stats(self) -> dict:
        return {
            "num_runs": len(self.runs),
            "memtable_versions": self.memtable.num_versions,
            "run_versions": sum(r.num_versions for r in self.runs),
            "flushed_frontier_ht": self.flushed_frontier_ht,
        }

    # -- reads -------------------------------------------------------------
    def _sources(self):
        return [self.memtable] + list(self.runs)

    def _merged_rows(self, spec: ScanSpec):
        """Yield (key, MergedRow) in key order over [lower, upper)."""
        sources = self._sources()
        key_iters = [src.scan_keys(spec.lower, spec.upper) for src in sources]
        merged_keys = heapq.merge(*key_iters)
        last = None
        for key in merged_keys:
            if key == last:
                continue
            last = key
            versions: list[RowVersion] = []
            for src in sources:
                if isinstance(src, (MemTable, NativeMemTable)):
                    versions.extend(src.versions(key))
                else:
                    versions.extend(src.get(key))
            yield key, merge_versions(key, versions, spec.read_ht)

    def scan_batch(self, specs: list[ScanSpec],
                   deadline=None) -> list[ScanResult]:
        """Point gets skip the k-way source merge: one map/bisect lookup
        per source (the DocRowwiseIterator point-get shape); everything
        else takes the generic scan. Results are identical to scan() —
        pinned by tests/test_point_fastpath.py. ``deadline`` is the RPC
        edge's propagated budget (utils.retry.Deadline): checked between
        specs so an expired batch aborts with Code.TIMED_OUT."""
        from yugabyte_db_tpu.storage.scan_spec import point_key_of

        out = []
        for s in specs:
            if deadline is not None:
                deadline.check("cpu_engine.scan_batch")
            pk = point_key_of(s, self.schema)
            out.append(self.scan(s) if pk is None
                       else self._point_scan(s, pk))
        return out

    def _point_scan(self, spec: ScanSpec, key: bytes) -> ScanResult:
        versions: list[RowVersion] = list(self.memtable.versions(key))
        for run in self.runs:
            versions.extend(run.get(key))
        projection = spec.projection or [c.name for c in
                                         self.schema.columns]
        rows: list[tuple] = []
        resume = None
        scanned = 0
        if versions:
            scanned = 1
            merged = merge_versions(key, versions, spec.read_ht)
            if merged.exists:
                key_vals = self.mat.key_values(key)
                if self.mat.matches(spec, key_vals, merged):
                    rows.append(tuple(
                        self.mat.value(name, key_vals, merged)
                        for name in projection))
                    if spec.limit is not None and \
                            len(rows) >= spec.limit:
                        resume = key + b"\x00"
        return ScanResult(projection, rows, resume, scanned)

    def scan(self, spec: ScanSpec) -> ScanResult:
        if spec.is_aggregate:
            return self._scan_aggregate(spec)
        projection = spec.projection or [c.name for c in self.schema.columns]
        rows: list[tuple] = []
        scanned = 0
        resume = None
        for key, merged in self._merged_rows(spec):
            scanned += 1
            if not merged.exists:
                continue
            key_vals = self.mat.key_values(key)
            if not self.mat.matches(spec, key_vals, merged):
                continue
            rows.append(tuple(
                self.mat.value(name, key_vals, merged) for name in projection))
            if spec.limit is not None and len(rows) >= spec.limit:
                resume = key + b"\x00"  # smallest key strictly greater
                break
        return ScanResult(projection, rows, resume, scanned)

    def _scan_aggregate(self, spec: ScanSpec) -> ScanResult:
        agg = Aggregator(spec.aggregates, spec.group_by or [])
        scanned = 0
        for key, merged in self._merged_rows(spec):
            scanned += 1
            if not merged.exists:
                continue
            key_vals = self.mat.key_values(key)
            if not self.mat.matches(spec, key_vals, merged):
                continue
            agg.add(lambda name: self.mat.value(name, key_vals, merged))
        return ScanResult(agg.column_names(), agg.results(), None, scanned)


register_engine("cpu", CpuStorageEngine)
