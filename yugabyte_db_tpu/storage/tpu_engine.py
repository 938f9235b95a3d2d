"""TPU storage engine: the ``tablet_storage_engine=tpu`` data plane.

The north-star component (BASELINE.json): scans, MVCC merge-on-read,
predicate filtering and aggregate pushdown execute as device programs over
columnar runs demand-paged into HBM through the residency manager
(storage.residency, bounded by --tpu_hbm_budget_bytes; the host
ColumnarRun stays authoritative), while writes, the memtable, and exact
tie/varlen handling stay host-side. Query results are required to be
identical to CpuStorageEngine (the oracle) — the engine-diff tests
enforce it.

Read-path policy (correctness first, device fast path where it's sound):

- single-source scans (one run covers the range, memtable empty there):
  device evaluates visibility + range + numeric predicates exactly; varlen
  (string) predicates produce a candidate SUPERSET that the host verifies
  during materialization.
- multi-source scans (several overlapping runs and/or a live memtable):
  each run reports candidate keys from the device without predicate
  filtering (a column's latest value may live in another source, so
  per-source predicate evaluation is unsound — see ops/scan.py); the host
  merges versions across sources per candidate key (storage.merge) and
  applies predicates. Memtable keys in range are always candidates.
- aggregates push down to the device (per-block partials, exact integer
  limb sums) only when the scan is single-source and every predicate is
  device-exact; otherwise they fall back to the row path + host Aggregator.

Reference analog of the seam/merge behavior: DocRowwiseIterator over an
IntentAwareIterator merging regular/provisional sources
(src/yb/docdb/doc_rowwise_iterator.cc, intent_aware_iterator.h:81).
"""

from __future__ import annotations

import bisect
import contextlib
import functools
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np

from yugabyte_db_tpu.models.datatypes import DataType
from yugabyte_db_tpu.models.schema import Schema
from yugabyte_db_tpu.ops import agg_fold
from yugabyte_db_tpu.ops import encodings
from yugabyte_db_tpu.ops import scan as dscan
from yugabyte_db_tpu.ops.device_run import (DeviceRun, device_label,
                                            dtype_kind, padded_blocks,
                                            plane_nbytes,
                                            program_read_bytes)
from yugabyte_db_tpu.storage.residency import device_nbytes, hbm_cache
from yugabyte_db_tpu.storage.breaker import CircuitBreaker
from yugabyte_db_tpu.storage.columnar import ColumnarRun
from yugabyte_db_tpu.storage import host_page
from yugabyte_db_tpu.storage.cpu_engine import Aggregator, RowMaterializer
from yugabyte_db_tpu.storage.engine import StorageEngine, register_engine
from yugabyte_db_tpu.storage.memtable import MemTable, make_memtable
from yugabyte_db_tpu.storage.merge import merge_versions
from yugabyte_db_tpu.storage.row_version import MAX_HT, RowVersion
from yugabyte_db_tpu.storage.scan_spec import ScanResult, ScanSpec
from yugabyte_db_tpu.utils import planes as P
from yugabyte_db_tpu.utils.fault_injection import FaultInjected, maybe_fault
from yugabyte_db_tpu.utils import jitting, metrics, trace
from yugabyte_db_tpu.utils.jitting import compile_contract
from yugabyte_db_tpu.utils.metrics import (count_flush_path,
                                           count_host_verify_rows,
                                           count_swallowed)

# Failures the circuit breaker attributes to the DEVICE path: injected
# dispatch faults and runtime errors out of the device framework
# (compile/dispatch/transfer). Deliberately narrow — Status-carrying
# errors (e.g. a propagated deadline) and programming errors
# (Type/Key/Index) are NOT device faults and propagate unchanged.
DEVICE_FAULT_TYPES = (FaultInjected, RuntimeError)

WINDOW_BLOCKS = 8          # blocks per device dispatch on the row path
PAD_BLOCKS = 64            # run block-axis padding (multiple of every window)
# Compaction unions at/below this size take the host-vectorized
# retention mask (ops.compact.gc_mask_host): the link's fixed
# per-dispatch fence + index upload costs more than ~15 numpy passes.
HOST_GC_MASK_MAX = 2_000_000


def _place_run():
    """The device a run's own planes live on: the node's first chip,
    where the single-chip programs run. A node with several chips
    spreads a tablet by serving it through a mesh stack
    (tserver/mesh_scan.py: the stack's shards are then the tablet's
    device copy and this one is released), not by placing whole runs
    on other chips."""
    return jax.local_devices()[0]


class TpuRun:
    """A columnar run plus its managed device residency.

    ``.dev`` demand-uploads the run's DeviceRun through the process-wide
    residency cache (storage.residency) and may be evicted once the
    access returns when --tpu_hbm_budget_bytes is under pressure; the
    host ColumnarRun stays authoritative and re-uploads on the next
    access. Hold a :meth:`pin` across multi-dispatch windows so the
    accounting can't drop planes a dispatch still references."""

    def __init__(self, crun: ColumnarRun, device_tracker=None,
                 device=None, path: str | None = None,
                 pad_blocks: int = PAD_BLOCKS, label: str = "run"):
        self.crun = crun
        self.path = path        # its file under RunPersistence, if any
        # Block-axis padding of the upload: PAD_BLOCKS for a tablet's
        # runs (a multiple of every window); the overlay's mini-run pads
        # to its own power of two, so its programs run over thousands of
        # rows and not PAD_BLOCKS x R.
        self.pad_blocks = pad_blocks
        self.host_index = None  # storage.host_page.HostPageIndex, lazy
        self._dev_nbytes_hint: int | None = None
        # The owning device: every demand (re-)upload for this run
        # targets it, so eviction/readmission cycles never migrate a
        # run's bytes into another chip's budget bucket.
        self.jax_device = device if device is not None else _place_run()
        self._res_key = hbm_cache().register(
            self, device_tracker, label,
            device=device_label(self.jax_device))

    def _build_dev(self):
        # (host time: pad and device_put every plane; the transfers
        # themselves may still be in flight when this returns, and what
        # is left of them shows in the first fetch's wait)
        with trace.span("engine.upload", metrics.device_upload_histogram(),
                        seconds=True) as sp:
            d = DeviceRun(self.crun, self.pad_blocks,
                          device=self.jax_device)
            sp.labels["bytes"] = nbytes = d.nbytes
        metrics.count_device_upload_bytes(nbytes)
        return d, nbytes

    def _nbytes_hint(self) -> int:
        if self._dev_nbytes_hint is None:
            self._dev_nbytes_hint = plane_nbytes(self.crun,
                                                 self.pad_blocks)
        return self._dev_nbytes_hint

    @property
    def dev(self) -> DeviceRun:
        return self.device()

    def device(self, priority: str | None = None) -> DeviceRun:
        return hbm_cache().acquire(self._res_key, self._build_dev,
                                   nbytes_hint=self._nbytes_hint(),
                                   priority=priority)

    def pin(self, priority: str | None = None) -> DeviceRun:
        """Acquire + pin the device planes until :meth:`unpin` — the
        issue→finish dispatch windows' eviction guard."""
        return hbm_cache().pin(self._res_key, self._build_dev,
                               nbytes_hint=self._nbytes_hint(),
                               priority=priority)

    def unpin(self) -> None:
        hbm_cache().unpin(self._res_key)

    def peek_device(self) -> DeviceRun | None:
        """The resident DeviceRun if its planes are on device right now
        (e.g. just seeded by the device flush), else None — no demand
        upload, no LRU touch.  Lets the mesh stack update feed from
        already-resident planes without paying for a miss."""
        return hbm_cache().peek(self._res_key)

    def invalidate_device(self) -> None:
        """Drop any resident planes for a run that stays live (host
        planes rebuilt in place, e.g. ALTER adding columns).  The
        residency registration survives, so the next access demand
        re-uploads through the cache — budgeted and tracker-accounted,
        not the unmanaged unregistered-owner fallback."""
        hbm_cache().release(self._res_key)

    def retire(self) -> None:
        """Run leaving the run set for good (compaction, restore,
        close): drop resident planes and the registration itself, once
        no reader that took the run before holds a pin on it."""
        hbm_cache().retire(self._res_key)

    def seed_device(self, dev: DeviceRun) -> None:
        """Admit an already-built DeviceRun (the device flush output) as
        this run's resident payload — budgeted and tracker-accounted
        like any demand upload; a no-op hit if something already
        uploaded. Eviction works normally afterwards: the host planes
        stay authoritative and the next access re-uploads."""
        hbm_cache().acquire(self._res_key, lambda: (dev, dev.nbytes),
                            nbytes_hint=self._nbytes_hint())


def _set_phase(sp, phase: str, route: str) -> None:
    """Point a span at ``yb_engine_phase_us{phase, route}``."""
    sp.labels["route"] = route
    sp.histogram = metrics.engine_phase_histogram(phase, route)


def _record_phase(phase: str, route: str, wall_ns: int, ns: int) -> None:
    trace.record_span("engine." + phase, wall_ns, ns // 1000,
                      metrics.engine_phase_histogram(phase, route),
                      route=route)


def _issue_part(part: str) -> trace.span:
    """One of the three parts of ``engine.issue`` on the device path
    (plan, dispatch, copy_out): ``yb_engine_issue_part_us{part}``."""
    return trace.span("engine.issue." + part,
                      metrics.engine_issue_part_histogram(part))


def _count_dispatch(entry: str, dev, sig, args, outs) -> None:
    """One aggregate program of ``entry`` dispatched over ``dev``'s
    planes: ``yb_device_dispatches`` and, for the roofline, the resident
    bytes of the planes ``sig`` names (ops.device_run.program_read_bytes:
    its group, aggregate and predicate columns' value planes, every
    column's presence planes, the MVCC planes; not the rest of the
    run). A vmapped batch counts its planes once. The sum is kept on
    ``dev`` (one upload of one run) by signature. ``args`` (what the
    call passed beside ``dev.arrays``) and ``outs`` are the arrays the
    dispatch has the runtime move, parameters up and outputs down:
    their leaves are counted in ``yb_device_transfers{dir, entry}``."""
    cache = dev.__dict__.setdefault("_read_bytes", {})
    nbytes = cache.get(sig)
    if nbytes is None:
        nbytes = cache[sig] = _sig_read_bytes(dev.arrays, sig)
    metrics.count_device_dispatch(entry, nbytes,
                                  h2d=len(jax.tree.leaves(args)),
                                  d2h=len(jax.tree.leaves(outs)))


def _count_grouped_dispatch(entry: str, dev, sig, args, outs) -> None:
    """``_count_dispatch`` of an ops.group_agg program, which way its
    windows resolve their versions (``yb_grouped_resolve{form}``) and
    which way its buckets are addressed (``yb_grouped_buckets{form}``)."""
    from yugabyte_db_tpu.ops import group_agg

    _count_dispatch(entry, dev, sig, args, outs)
    group_agg.count_dispatch_forms(sig)


def _sig_read_bytes(arrays: dict, sig) -> int:
    named: list = [cid for cid, _planes in getattr(sig, "group_cols", ())]
    arith: list = []
    for a in sig.aggs:
        if a.col_id is not None:
            named.append(a.col_id)
        named.extend(getattr(a, "need_cols", ()))
        if getattr(a, "kind", None) in ("f32", "f64"):
            arith.append(a.col_id)
    for ps in sig.preds:
        (arith if ps.kind == "f32" else named).append(ps.col_id)
    return program_read_bytes(
        arrays, tuple(dict.fromkeys(named)),
        tuple(cs.col_id for cs in sig.cols), sig.flat,
        tuple(dict.fromkeys(arith)))


def _batch_route(plans: list) -> str:
    """A batch's label: ``_plan_scan``'s tag when its plans share one
    (host, page, issued, agg_deferred, grouped_deferred,
    overlay_deferred, gather)."""
    tags = {plan[0] for plan in plans}
    return tags.pop() if len(tags) == 1 else "mixed" if tags else "empty"


class _MaskedRun:
    """A TpuRun view with substituted device arrays (the delta overlay's
    valid-masked primary). Shares the source's ColumnarRun."""

    class _Dev:
        def __init__(self, B, arrays):
            self.B = B
            self.arrays = arrays

    def __init__(self, source: "TpuRun", arrays: dict):
        self.crun = source.crun
        self.source = source
        self.dev = _MaskedRun._Dev(source.dev.B, arrays)


class _CodePred:
    """A string predicate promoted to a device-EXACT int32 compare
    against a dictionary-encoded column's code plane
    (--tpu_plane_encoding): the per-run dictionary is sorted, so the
    engine bisects the literal into a code bound and the kernel compares
    codes — no host verify round, unlike the prefix-plane superset path.
    ``value`` is the already-translated int32 code bound; ``op`` is the
    (possibly rewritten) code compare to apply."""

    __slots__ = ("column", "op", "value")

    def __init__(self, column: str, op: str, value: int):
        self.column = column
        self.op = op
        self.value = value


class _OverlayState:
    """Cached delta-overlay state (TpuStorageEngine._overlay): the
    masked primary, the key-sorted dirty rows with a parallel key list
    and by-key map (what the incremental copy-on-write update bisects
    into), the cleared primary row indices, the memtable version count
    the state includes, the per-read-point host-partial cache, and
    ``delta``: the dirty keys' version lists as ONE small multi-version
    run on the device (a TpuRun, built by the first grouped aggregate
    that needs it: TpuStorageEngine._overlay_delta_run), retired and
    forgotten when the engine's cache lets the state go (``dropped``: a
    scan that still holds the state then builds a run of its own)."""

    __slots__ = ("masked", "rows", "keys", "by_key", "idx", "mem_count",
                 "partial", "delta", "dropped")

    def __init__(self, masked, rows, keys, by_key, idx, mem_count):
        self.masked = masked
        self.rows = rows
        self.keys = keys
        self.by_key = by_key
        self.idx = idx
        self.mem_count = mem_count
        self.partial: dict = {}
        self.delta: TpuRun | None = None
        self.dropped = False


# _overlay_apply_delta verdict: the delta can't be applied (no memtable
# log) and the caller must rebuild from scratch.
_OVERLAY_REBUILD = object()


class TpuStorageEngine(StorageEngine):
    def __init__(self, schema: Schema, options: dict | None = None):
        super().__init__(schema, options)
        self.memtable = make_memtable()
        self.runs: list[TpuRun] = []
        self.mat = RowMaterializer(schema)
        self.flushed_frontier_ht = 0
        self.rows_per_block = self.options.get("rows_per_block", 2048)
        self._kinds = {c.col_id: dtype_kind(c.dtype)
                       for c in schema.value_columns}
        self._dtypes = {c.col_id: c.dtype for c in schema.value_columns}
        self._name_to_id = {c.name: c.col_id for c in schema.value_columns}
        self._key_col_names = {c.name for c in schema.key_columns}
        # Structural gather-plan cache; invalidated whenever the run set
        # changes (flush/compact). Holds strong refs to its TpuRuns, so
        # id(trun) keys can't be reused while cached.
        self._plan_cache: dict = {}
        # Delta-overlay cache for multi-source scans: (source runs,
        # memtable ref, memtable version count, state | None). Validity
        # is judged by identity + the monotone version counter, and the
        # tuple holds strong refs so nothing it names can be collected
        # and identity-reused underneath it.
        self._overlay_cache = None
        self._read_plane_cache: dict = {}
        self._wire_dtype_cache: dict = {}
        from yugabyte_db_tpu.storage.run_io import RunPersistence

        # Device-plane accounting: the runs' resident plane bytes, a
        # sibling subtree of memstore so /memz shows both residencies.
        # Charged and released per cache entry by the residency manager.
        from yugabyte_db_tpu.utils.memtracker import root_tracker

        self.device_tracker = root_tracker().child("device").child(
            self.mem_tracker.name)
        # Overlay pin bookkeeping: the cached delta-overlay state keeps
        # its primary run pinned (its masked arrays alias the primary's
        # planes) and its masked valid plane accounted as an external
        # residency entry until the cache is dropped.
        self._overlay_pinned: TpuRun | None = None
        self._overlay_ext_key: int | None = None
        # Serializes the lazy build and the pin of a state's mini-run
        # against the cache letting that state go (one builds and pins,
        # the other retires).
        self._overlay_delta_lock = threading.Lock()
        # Fault domain: the breaker quarantines the device dispatch path
        # after repeated device faults; while open (and for one probe's
        # worth of half-open) every scan re-serves byte-identically from
        # the authoritative host structures (_serve_host_batch).
        from yugabyte_db_tpu.utils.flags import FLAGS

        self.breaker = CircuitBreaker(
            f"tpu_engine:{self.mem_tracker.name}",
            failure_threshold=int(self.options.get(
                "breaker_failure_threshold",
                FLAGS.get("tpu_breaker_failure_threshold"))),
            cooldown_s=float(self.options.get(
                "breaker_cooldown_s",
                FLAGS.get("tpu_breaker_cooldown_s"))))
        self.persist = RunPersistence(self.options.get("data_dir"))
        for path, entries in zip(self.persist.files,
                                 self.persist.load_all()):
            crun = ColumnarRun.build(self.schema, entries, self.rows_per_block)
            self.runs.append(TpuRun(crun, self.device_tracker, path=path))
            self.flushed_frontier_ht = max(self.flushed_frontier_ht, crun.max_ht)
        # The run list changes hands under this lock: a flush adds its
        # run, a compaction puts one run in the place of those it
        # merged, both on their own threads. Readers take no lock: the
        # list is replaced, never changed in place.
        self._runs_lock = threading.Lock()
        # Odd while the runs are rebuilt where they stand (ALTER): a
        # compaction neither starts then nor keeps what it merged across
        # a change of it.
        self._run_epoch = 0
        # Who compacts what :meth:`pick_compaction` picked after a
        # flush: the tablet peer's background worker sets this (a
        # callable that takes the picked runs and only wakes it); an
        # engine on its own compacts where it stands.
        self.compaction_listener = None
        # Plane-encoding observability: yb_plane_bytes{encoding} /
        # yb_plane_encoded_ratio sample plane_stats() at scrape time
        # (weakly held — a dropped engine falls out of the series).
        from yugabyte_db_tpu.utils.metrics import register_plane_stats

        register_plane_stats(self)

    # -- writes ------------------------------------------------------------
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
            self.flush(caller="apply")
            picked = self.pick_compaction()
            if picked is not None:
                if self.compaction_listener is not None:
                    self.compaction_listener(picked)
                else:
                    self.compact(runs=picked, by="apply")
        self._track_memstore()

    # -- plane-encoding introspection --------------------------------------
    def plane_stats(self) -> dict:
        """Per-tablet plane-encoding byte accounting for the
        yb_plane_bytes{encoding} gauges and /memz: stored bytes per
        encoding kind vs the logical (plain-format) bytes they replace,
        over this engine's current run set. A run reports its encoded
        stats only once something has actually built its encoded tree
        (first device access under --tpu_plane_encoding=auto); until
        then — and always with the flag off — it counts as plain, so
        the ratio reflects bytes as stored, not a hypothetical."""
        by: dict[str, int] = {}
        logical = 0
        for t in list(self.runs):
            st = t.crun.enc_stats
            if st is not None:
                for k, v in st["by_encoding"].items():
                    by[k] = by.get(k, 0) + int(v)
                logical += int(st["logical_bytes"])
            else:
                nb = self._plain_run_nbytes(t.crun)
                by["plain"] = by.get("plain", 0) + nb
                logical += nb
        return {"tablet": self.mem_tracker.name, "by_encoding": by,
                "encoded_bytes": sum(by.values()),
                "logical_bytes": logical}

    @staticmethod
    def _plain_run_nbytes(crun: ColumnarRun) -> int:
        total = sum(a.nbytes for a in (
            crun.valid, crun.group_start, crun.tomb, crun.live,
            crun.ht_hi, crun.ht_lo, crun.exp_hi, crun.exp_lo))
        for col in crun.cols.values():
            total += col.set_.nbytes + col.isnull.nbytes
            total += col.cmp_planes.nbytes
            if col.arith is not None:
                total += col.arith.nbytes
        return total

    # -- lifecycle ---------------------------------------------------------
    def alter_schema(self, new_schema: Schema) -> None:
        """Adopt an evolved schema. Existing columnar runs were built
        against the old schema, so each gets zero planes for any ADDED
        column (all rows unset -> NULL) and a fresh device upload;
        dropped columns keep their (now unreachable) planes. The memtable
        flushes first so no old-schema rows build runs after the switch."""
        self.flush()
        with self._runs_lock:
            self._run_epoch += 1
        try:
            self._alter_runs(new_schema)
        finally:
            with self._runs_lock:
                self._run_epoch += 1

    def _alter_runs(self, new_schema: Schema) -> None:
        super().alter_schema(new_schema)
        self.mat = RowMaterializer(new_schema)
        self._kinds = {c.col_id: dtype_kind(c.dtype)
                       for c in new_schema.value_columns}
        self._dtypes = {c.col_id: c.dtype for c in new_schema.value_columns}
        self._name_to_id = {c.name: c.col_id
                            for c in new_schema.value_columns}
        self._key_col_names = {c.name for c in new_schema.key_columns}
        with self._runs_lock:
            self._run_set_changed()
        from yugabyte_db_tpu.storage.columnar import ColumnData

        for trun in self.runs:
            crun = trun.crun
            changed = False
            for c in new_schema.value_columns:
                if c.col_id in crun.cols:
                    continue
                B, R = crun.key_planes.shape[0], crun.R
                planes = 2 if c.dtype.device_planes == 2 else 1
                crun.cols[c.col_id] = ColumnData(
                    dtype=c.dtype,
                    set_=np.zeros((B, R), dtype=bool),
                    isnull=np.zeros((B, R), dtype=bool),
                    cmp_planes=np.zeros((B, R, planes), dtype=np.int32),
                    arith=(np.zeros((B, R), dtype=np.float32)
                           if c.dtype.is_numeric else None),
                    varlen=([[None] * R for _ in range(B)]
                            if not c.dtype.is_fixed_width else None),
                )
                changed = True
            crun.schema = new_schema
            trun.host_index = None  # column planes changed shape/set
            if changed:
                # Host planes grew: drop any resident upload (the next
                # access re-uploads the evolved planes) and recompute
                # the residency byte hint.
                trun.invalidate_device()
                trun._dev_nbytes_hint = None
        with self._runs_lock:
            self._run_set_changed()

    def flush(self, caller: str = "maintenance") -> None:
        """``caller`` is who is held meanwhile: ``apply`` for the thread
        that applies committed Raft entries (the memtable reached its
        limit under it; the time goes to ``yb_apply_stall_us``), else
        ``maintenance``."""
        from yugabyte_db_tpu.utils.sync_point import sync_point

        sync_point("tpu_engine:flush:start")
        if self.memtable.is_empty:
            return
        with trace.span("engine.flush",
                        metrics.apply_stall_histogram()
                        if caller == "apply" else None,
                        thread=caller) as sp:
            sp.labels["route"] = self._flush()
        sync_point("tpu_engine:flush:done")

    def _flush(self) -> str:
        if self.memtable.max_ht is not None:
            self.flushed_frontier_ht = max(self.flushed_frontier_ht,
                                           self.memtable.max_ht)
        # Device flush first: replay the memtable op log into sorted run
        # planes in one device scatter, leaving the run HBM-resident
        # with no separate upload (--tpu_device_flush). Host build when
        # ineligible or over the residency budget: the native one-C-pass
        # path, generic drain+build behind it.
        seeded = self._device_flush()
        entries = None
        if seeded is not None:
            crun, trun = seeded
        else:
            count_flush_path("host")
            crun = ColumnarRun.build_from_memtable(
                self.schema, self.memtable, self.rows_per_block)
            if crun is None:
                entries = self.memtable.drain_sorted()
                crun = ColumnarRun.build(self.schema, entries,
                                         self.rows_per_block)
            trun = TpuRun(crun, self.device_tracker)
        if self.persist.enabled:
            trun.path = self.persist.write_run(
                entries if entries is not None
                else list(crun.iter_entries()))
        with self._runs_lock:
            if trun.path:
                # Justified hold (here and in _compact, restore_entries):
                # the manifest is the run list's durable twin and changes
                # with it; what it costs is one small file's fsync, the
                # run's own file is written before.
                # yb-lint: disable=iholds/lock-across-blocking
                self.persist.install([], trun.path)
            self.runs = [*self.runs, trun]
            self.memtable = make_memtable()
            self._run_set_changed()
        self._track_memstore()
        if len(self.runs) > 1:
            self._warm_overlay_scatter()
        return "host" if seeded is None else "device"

    def _device_flush(self):
        """The device flush path: stage the memtable's apply-order op
        log through the columnar encoders, compute the flush sort
        (key asc, ht desc, write_id desc — drain_sorted()'s order) and
        block packing host-side with one stable argsort over memcmp
        keys, then materialize the sorted padded run planes in a single
        device scatter (ops.flush.replay_flush). The outputs seed the
        residency cache directly AND round-trip back as the host planes,
        so device and host content are byte-identical by construction.

        Returns (crun, trun) on success, None when ineligible — flag
        off, no op log (capped), keys beyond the exact 32-byte prefix,
        run over the HBM residency budget, breaker open, or a device
        fault mid-flush (recorded on the breaker) — sending the caller
        to the host build."""
        from yugabyte_db_tpu.ops import flush as dflush
        from yugabyte_db_tpu.utils.flags import FLAGS

        try:
            if not FLAGS.get("tpu_device_flush"):
                return None
        except KeyError:
            return None
        rows = self.memtable.versions_since(0)
        if not rows:
            return None
        n = len(rows)
        keys = [r.key for r in rows]
        max_key_len = max(map(len, keys))
        if max_key_len > 32:
            # Sorted-order group boundaries come from prefix-plane
            # equality — exact only when every key fits the 32-byte
            # device prefix (the same eligibility device compaction
            # enforces).
            return None
        R = self.rows_per_block
        # Stage apply-order planes through the columnar encoders: one
        # block whose row capacity is the bucketed op count (pad rows
        # are never gathered, and bucketing keeps the device program
        # count bounded).
        m = 1 << max(10, (n - 1).bit_length())
        try:
            staged = ColumnarRun(self.schema, rows_per_block=m)
            staged.B = 1
            staged._alloc(1)
            staged._fill_block(0, [(r.key, [r]) for r in rows])
        except (OverflowError, ValueError, TypeError):
            return None  # value shape the encoders reject: host path
        wid = np.fromiter((r.write_id for r in rows), np.int64, n)
        sk = self._flush_sortkey(staged.key_planes[0, :n],
                                 staged.ht_hi[0, :n],
                                 staged.ht_lo[0, :n], wid)
        perm = np.argsort(sk, kind="stable").astype(np.int32)
        kw_s = staged.key_planes[0][perm]
        new_group = np.empty(n, dtype=bool)
        new_group[0] = True
        new_group[1:] = (kw_s[1:] != kw_s[:-1]).any(axis=1)
        gstarts = np.flatnonzero(new_group)
        sizes = np.diff(np.append(gstarts, n))
        try:
            ranges = ColumnarRun.pack_group_ranges(sizes.tolist(), R)
        except ValueError:
            return None  # an over-block key group: the host build's call
        B = len(ranges)
        Bp = padded_blocks(B, PAD_BLOCKS)
        budget = hbm_cache().budget()
        if budget and dflush.flush_plane_nbytes(Bp, R,
                                                self.schema) > budget:
            return None  # run exceeds the residency budget: host build
        if not self.breaker.allow():
            return None
        try:
            return self._device_flush_dispatch(
                rows, keys, staged, perm, kw_s, new_group, gstarts,
                sizes, ranges, Bp, max_key_len)
        except DEVICE_FAULT_TYPES as e:
            self.breaker.record_failure(e)
            return None
        except BaseException as e:
            # Any other raise still retires the half-open probe admitted by
            # allow() above — leaking it wedges the breaker's probe slot so
            # it could never close again. The error itself propagates.
            self.breaker.record_failure(e)
            raise

    def _device_flush_dispatch(self, rows, keys, staged, perm, kw_s,
                               new_group, gstarts, sizes, ranges, Bp,
                               max_key_len):
        from yugabyte_db_tpu.ops import flush as dflush
        from yugabyte_db_tpu.storage.columnar import BlockMeta

        self._device_fault_point()
        n = len(rows)
        m = staged.R
        R = self.rows_per_block
        B = len(ranges)
        rows_per = np.array([nr for _g0, _gn, nr in ranges], np.int64)
        block_of = np.repeat(np.arange(B, dtype=np.int64), rows_per)
        offs = np.cumsum(rows_per) - rows_per
        dst = (block_of * R
               + (np.arange(n, dtype=np.int64)
                  - np.repeat(offs, rows_per))).astype(np.int32)
        pad = m - n
        # Pad rows: gather staged row 0, scatter out of range (dropped).
        perm_p = (np.concatenate([perm, np.zeros(pad, np.int32)])
                  if pad else perm)
        dst_p = (np.concatenate([dst, np.full(pad, Bp * R, np.int32)])
                 if pad else dst)
        gs_p = (np.concatenate([new_group, np.zeros(pad, bool)])
                if pad else new_group)
        staged_tree = {
            "ht_hi": staged.ht_hi[0], "ht_lo": staged.ht_lo[0],
            "exp_hi": staged.exp_hi[0], "exp_lo": staged.exp_lo[0],
            "tomb": staged.tomb[0], "live": staged.live[0],
            "cols": {},
        }
        dict_cols = self._flush_dict_cols(staged, n)
        for cid, col in staged.cols.items():
            entry = {"set": col.set_[0], "isnull": col.isnull[0]}
            if cid in dict_cols:
                codes, dhi, dlo, _uniq = dict_cols[cid]
                entry["codes"] = codes
                entry["dhi"] = dhi
                entry["dlo"] = dlo
            else:
                entry["cmp"] = col.cmp_planes[0]
            if col.arith is not None:
                entry["arith"] = col.arith[0]
            staged_tree["cols"][cid] = entry
        is_real = np.zeros(Bp, dtype=bool)
        is_real[:B] = True
        ehi, elo = P.scalar_ht_planes(MAX_HT)
        args = (staged_tree, perm_p, dst_p, gs_p, is_real)
        out = dflush.replay_flush(*args, ehi, elo, R=R)
        # (the planes the program reads: the staged op log, the sort
        # permutation, the slots, the group and block bits)
        metrics.count_device_dispatch(
            "replay_flush", device_nbytes(args),
            h2d=len(jax.tree.leaves(args)) + 2,
            d2h=len(jax.tree.leaves(out)))
        # The device planes round-trip back as the run's HOST planes
        # (one copy per plane; np.array so they're owned and writable —
        # never a read-only view of a device buffer).
        host = jax.tree_util.tree_map(np.array, out)

        run = ColumnarRun(self.schema, R)
        run.B = B
        run._alloc(B)
        run.valid = host["valid"][:B]
        run.group_start = host["group_start"][:B]
        run.tomb = host["tomb"][:B]
        run.live = host["live"][:B]
        run.ht_hi = host["ht_hi"][:B]
        run.ht_lo = host["ht_lo"][:B]
        run.exp_hi = host["exp_hi"][:B]
        run.exp_lo = host["exp_lo"][:B]
        for cid, col in run.cols.items():
            h = host["cols"][cid]
            col.set_ = h["set"][:B]
            col.isnull = h["isnull"][:B]
            hc = h["cmp"]
            if isinstance(hc, dict):
                # Dict-encoded on device; the authoritative host planes
                # decode the codes through the dictionary (numpy gather
                # — byte-identical to what the device kernels decode).
                e = hc["dict"]
                codes = e["codes"][:B].astype(np.int64)
                col.cmp_planes = np.ascontiguousarray(np.stack(
                    [e["dhi"][codes], e["dlo"][codes]],
                    axis=-1).astype(np.int32))
            else:
                col.cmp_planes = hc[:B]
            if col.arith is not None:
                col.arith = h["arith"][:B]

        # Keys and row payloads stay host-side (no key planes on
        # device): the same flat scatter, in numpy.
        def scatter(dest, vals):
            dest.reshape((B * R,) + dest.shape[2:])[dst] = vals

        scatter(run.key_planes, kw_s)
        keys_arr = np.empty(n, dtype=object)
        keys_arr[:] = keys
        keys_s = keys_arr[perm]
        scatter(run.row_keys, keys_s)
        vers_arr = np.empty(n, dtype=object)
        vers_arr[:] = rows
        scatter(run.row_versions, vers_arr[perm])
        bpos = dst // R
        rpos = dst % R
        for cid, col in run.cols.items():
            if col.varlen is None:
                continue
            src = staged.cols[cid].varlen[0]
            for j in range(n):
                v = src[perm[j]]
                if v is not None:
                    col.varlen[bpos[j]][rpos[j]] = v

        group_keys = keys_s[gstarts]
        for b, (g0, gn, nrows) in enumerate(ranges):
            run.blocks[b] = BlockMeta(group_keys[g0],
                                      group_keys[g0 + gn - 1], nrows)
        run.min_key = group_keys[0]
        run.max_key = run.blocks[B - 1].max_key
        run.num_versions = n
        run.max_ht = staged.max_ht
        run.max_group_versions = int(sizes.max())
        run.max_key_len = max_key_len
        run.varlen_max_len = dict(staged.varlen_max_len)

        trun = TpuRun(run, self.device_tracker)
        trun.seed_device(DeviceRun.from_arrays(run, PAD_BLOCKS, out))
        self.breaker.record_success()
        count_flush_path("device")
        return run, trun

    def _flush_dict_cols(self, staged, n: int):
        """Per-column flush dictionaries (--tpu_plane_encoding): sorted
        unique set non-null raw values of the staged op-log rows ->
        {cid: (codes[m] u16, dhi, dlo, uniq)}. Built the same way
        ColumnarRun._encode_dict_col builds them from run planes, so a
        demand re-upload after eviction produces the SAME dictionary
        (same codes) as the flush-seeded device form."""
        from yugabyte_db_tpu.storage.columnar import _varlen_raw
        from yugabyte_db_tpu.utils.flags import FLAGS

        try:
            if FLAGS.get("tpu_plane_encoding") == "off":
                return {}
        except KeyError:
            return {}
        out = {}
        m = staged.R
        for cid, col in staged.cols.items():
            if col.varlen is None:
                continue
            nn = col.set_[0, :n] & ~col.isnull[0, :n]
            idxs = np.nonzero(nn)[0]
            if idxs.size == 0:
                continue
            src = col.varlen[0]
            raws = [_varlen_raw(src[i]) for i in idxs.tolist()]
            uniq = sorted(set(raws))
            if len(uniq) > encodings.DICT_MAX_VALUES:
                continue  # overflow: prefix planes, like the host encoder
            cap = encodings.pow2_bucket(len(uniq) + 1)
            code_of = {v: i for i, v in enumerate(uniq)}
            codes = np.full(m, cap - 1, np.int64)
            codes[idxs] = [code_of[v] for v in raws]
            hi, lo = P.varlen_prefix_planes(uniq)
            dhi = np.zeros(cap, np.int32)
            dlo = np.zeros(cap, np.int32)
            dhi[:len(uniq)] = hi
            dlo[:len(uniq)] = lo
            out[cid] = (codes.astype(np.uint16), dhi, dlo, uniq)
        return out

    @staticmethod
    def _flush_sortkey(kw_part, ht_hi_part, ht_lo_part, wid):
        """_sortkey_bytes plus a trailing inverted write_id: the FLUSH
        order (key asc, ht desc, write_id desc) — exactly
        drain_sorted()'s version order — as ONE memcmp key per row."""
        n, W = kw_part.shape
        buf = np.empty((n, W + 4), dtype=np.uint32)
        buf[:, :W] = (kw_part.view(np.uint32)
                      ^ np.uint32(0x80000000)).byteswap()
        buf[:, W] = (~(ht_hi_part.view(np.uint32)
                       ^ np.uint32(0x80000000))).byteswap()
        buf[:, W + 1] = (~(ht_lo_part.view(np.uint32)
                           ^ np.uint32(0x80000000))).byteswap()
        w = wid.view(np.uint64)
        buf[:, W + 2] = (~(w >> np.uint64(32))
                         .astype(np.uint32)).byteswap()
        buf[:, W + 3] = (~(w & np.uint64(0xFFFFFFFF))
                         .astype(np.uint32)).byteswap()
        return np.ascontiguousarray(buf).view(
            f"S{4 * (W + 4)}").reshape(n)

    _scatter_warmed: set = set()
    _scatter_warm_lock = __import__("threading").Lock()

    def _warm_overlay_scatter(self) -> None:
        """Compile the overlay's valid-plane scatter programs off the
        critical path: a second run means the next scan likely builds a
        delta overlay, and its first dispatch would otherwise pay the
        XLA compile inside the measured scan. One background compile per
        (plane shape, index bucket), process-wide. The shape is computed
        host-side (padded_blocks x R) so warmup neither forces the
        primary's planes resident nor depends on cache state — the keys
        stay identical to what _overlay dispatches, full or incremental."""
        primary = max(self.runs, key=lambda t: t.crun.total_rows())
        shape = (padded_blocks(primary.crun.B, PAD_BLOCKS),
                 primary.crun.R)
        size = shape[0] * shape[1]
        todo = [b for b in self._MASK_BUCKETS if b <= 65536
                and (shape, b) not in TpuStorageEngine._scatter_warmed]
        if not todo:
            return

        def warm():
            try:
                valid = jnp.zeros(shape, dtype=bool)
            except Exception as e:  # noqa: BLE001 — warmup best-effort
                count_swallowed("tpu_engine.scatter_warmup", e)
                return
            for b in todo:
                key = (shape, b)
                with TpuStorageEngine._scatter_warm_lock:
                    if key in TpuStorageEngine._scatter_warmed:
                        continue
                    TpuStorageEngine._scatter_warmed.add(key)
                try:
                    idx = jnp.full((b,), size, dtype=jnp.int32)
                    TpuStorageEngine.scatter_invalid(valid, idx)
                except Exception as e:  # noqa: BLE001 — warmup best-effort
                    count_swallowed("tpu_engine.scatter_warmup", e)

        import threading

        threading.Thread(target=warm, daemon=True).start()

    def pick_compaction(self) -> "list[TpuRun] | None":
        """The runs a compaction should merge now, oldest first, by
        size (storage.engine.pick_compaction), or None."""
        from yugabyte_db_tpu.storage.engine import pick_compaction

        runs = self.runs
        pick = pick_compaction(
            [t.crun.num_versions for t in reversed(runs)],
            self.compaction_trigger())
        if pick is None:
            return None
        first, count = pick
        return runs[len(runs) - first - count:len(runs) - first]

    def maybe_compact(self, history_cutoff_ht: int = 0,
                      by: str = "caller") -> bool:
        picked = self.pick_compaction()
        if picked is None:
            return False
        return self.compact(history_cutoff_ht, runs=picked, by=by)

    def compact(self, history_cutoff_ht: int = 0,
                runs: "list[TpuRun] | None" = None,
                by: str = "caller") -> bool:
        """Merge ``runs`` (age-adjacent, oldest first; every run when
        None: a manual compaction is a full one) into one run that takes
        their place, GCing history at the cutoff. The k-way merge ORDER
        is computed on the host and the GC decisions as one retention
        mask (ops.compact), on the device where the planes already are
        or the union is big, whenever every key fits the exact 32-byte
        device prefix; the host then materializes the merged run with a
        single linear pass. Falls back to the host heap merge otherwise
        (BASELINE config 4; reference hot loop: CompactionJob::Run,
        src/yb/rocksdb/db/compaction_job.cc:622).

        Where the tablet's oldest run does not take part, an older run
        may hold what a tombstone shadows: row tombstones at or under
        the cutoff are then kept (what they shadow among ``runs`` still
        goes, as does every overwritten version).

        The merged run is built from the runs as they are, with no lock
        held: readers and flushes go on. The swap of the run list, the
        manifest's, the caches and the retiring of the inputs happen
        under the lock a flush adds its run under. False when ``runs``
        no longer stand side by side in the run list (they were not
        taken from it now) and the result was thrown away. ``by`` says
        whose thread is held meanwhile (``yb_compactions{by}``):
        ``apply`` for an engine on its own that compacts where the
        write was applied, ``worker`` for the tablet peer's background
        thread, else ``caller``."""
        full = runs is None
        inputs = list(self.runs) if full else list(runs)
        if not inputs or (full and len(inputs) <= 1
                          and history_cutoff_ht == 0):
            return True
        if not full:
            return self._compact(inputs, history_cutoff_ht, "subset", by)
        # Bulk object churn (hundreds of thousands of row objects
        # moving between containers) makes the cyclic GC fire on
        # allocation and rescan the whole heap repeatedly — measured
        # 27x slowdown on plain object-array fills. Nothing here
        # creates cycles; a full compaction (an operator's, in the
        # foreground) pauses collection for its duration. A subset
        # compaction moves a few thousand versions from a background
        # thread and leaves the process's collector alone.
        import gc

        gc_was = gc.isenabled()
        gc.disable()
        try:
            return self._compact(inputs, history_cutoff_ht, "full", by)
        finally:
            if gc_was:
                gc.enable()

    def _position(self, inputs: "list[TpuRun]") -> int:
        """Where ``inputs`` stand side by side in the run list, or -1."""
        cur = self.runs
        at = next((i for i, t in enumerate(cur) if t is inputs[0]), -1)
        if at < 0 or len(cur) < at + len(inputs) \
                or any(a is not b for a, b in zip(cur[at:], inputs)):
            return -1
        return at

    def _compact(self, inputs: "list[TpuRun]", history_cutoff_ht: int,
                 kind: str, by: str) -> bool:
        from yugabyte_db_tpu.utils.sync_point import sync_point

        wall, t0 = time.time_ns(), time.perf_counter_ns()
        epoch = self._run_epoch
        at = self._position(inputs)
        if at < 0 or epoch & 1:
            return False
        try:
            # (at 0 the oldest run takes part: nothing older is left
            # for a tombstone to shadow)
            merged, crun, route = self._merge_runs(
                inputs, history_cutoff_ht, keep_tombstones=at > 0)
        except Exception:
            if self._run_epoch != epoch:
                return False    # (ALTER rebuilt the planes it read)
            raise
        # Crash-safe in this order: write the new run, publish the
        # manifest that names it in its inputs' place, remove their files.
        path = (self.persist.write_run(merged)
                if merged and self.persist.enabled else None)
        new = (TpuRun(crun, self.device_tracker, path=path)
               if crun is not None else None)
        sync_point("tpu_engine:compact:built")
        with self._runs_lock:
            cur = self.runs
            at = self._position(inputs) if self._run_epoch == epoch else -1
            if at >= 0:
                if self.persist.enabled:
                    # yb-lint: disable=iholds/lock-across-blocking
                    self.persist.install([t.path for t in inputs], path)
                self.runs = (cur[:at] + ([new] if new is not None else [])
                             + cur[at + len(inputs):])
                self._run_set_changed()
        if at < 0:
            if new is not None:
                new.retire()
            self.persist.remove([path] if path else [])
            return False
        sync_point("tpu_engine:compact:swapped")
        for t in inputs:
            t.retire()
        self.persist.remove([t.path for t in inputs if t.path])
        metrics.count_compaction(route, kind, by)
        trace.record_span(
            "engine.compact", wall, (time.perf_counter_ns() - t0) // 1000,
            metrics.compaction_histogram(route, kind), route=route,
            kind=kind, by=by, runs_in=len(inputs),
            versions_in=sum(t.crun.num_versions for t in inputs),
            versions_out=0 if crun is None else crun.num_versions)
        return True

    def _merge_runs(self, inputs: "list[TpuRun]", history_cutoff_ht: int,
                    keep_tombstones: bool):
        """-> (entries for the run's file, merged ColumnarRun or None,
        route), from the runs as they are; takes no lock."""
        result = None
        if all(t.crun.max_key_len <= 32 for t in inputs) \
                and sum(t.crun.num_versions for t in inputs) > 0:
            result = self._device_compact_entries(inputs, history_cutoff_ht,
                                                  keep_tombstones)
        if result is not None:
            make_entries, crun, route = result
            # The (key, versions) entry list exists only for durability;
            # materialize it lazily — an in-memory engine (data_dir=None)
            # skips the 1-tuple-per-group Python walk entirely.
            return (make_entries() if self.persist.enabled else [],
                    crun, route)
        from yugabyte_db_tpu.storage.cpu_engine import CpuStorageEngine
        from yugabyte_db_tpu.storage.merge import merge_entry_streams

        merged = []
        for key, versions in merge_entry_streams(
                [t.crun.iter_entries() for t in inputs]):
            kept = CpuStorageEngine._gc_versions(
                key, versions, history_cutoff_ht, keep_tombstones)
            if kept:
                merged.append((key, kept))
        crun = (ColumnarRun.build(self.schema, merged, self.rows_per_block)
                if merged else None)
        return merged, crun, "host_merge"

    def _run_set_changed(self) -> None:
        """What is cached by run goes with the run set (flush, compact,
        restore under ``_runs_lock``, so that two of them never drop
        the overlay's pin twice)."""
        self._plan_cache.clear()
        self._drop_overlay_cache()

    def _drop_overlay_cache(self) -> None:
        """Forget the cached delta-overlay state, releasing its pin on
        the primary run and its masked-valid residency accounting. Must
        run whenever the run set changes (flush/compact/restore/alter) —
        validity checks alone would leak the pin."""
        self._retire_overlay_delta(None)
        self._overlay_cache = None
        if self._overlay_pinned is not None:
            self._overlay_pinned.unpin()
            self._overlay_pinned = None
        if self._overlay_ext_key is not None:
            hbm_cache().invalidate(self._overlay_ext_key)
            self._overlay_ext_key = None

    def close(self) -> None:
        self._run_set_changed()
        for t in self.runs:
            t.retire()
        self.device_tracker.detach()
        super().close()

    @staticmethod
    def _device_gc_fits_budget(runs) -> bool:
        """Compaction's resident mask needs every run pinned at once;
        under a budget smaller than the union's plane bytes that would
        force pinned overflow, so the caller falls back to the
        host-vectorized mask instead."""
        b = hbm_cache().budget()
        if not b:
            return True
        return sum(t._nbytes_hint() for t in runs) <= b

    def _device_compact_entries(self, runs, cutoff: int,
                                keep_tombstones: bool = False):
        """Merge+GC of ``runs`` -> (entries, merged ColumnarRun, route),
        or None when the union is empty; ``route`` says where the
        retention mask was computed (``device`` or ``host``). The merged
        run is assembled by GATHERING the surviving rows' existing
        planes (numpy) instead of re-encoding every version through
        ColumnarRun.build — the whole pipeline is vectorized except one
        linear grouping pass."""
        from yugabyte_db_tpu.ops import compact as dcompact

        crs = [t.crun for t in runs]
        parts_kw, parts = [], {k: [] for k in
                               ("ht_hi", "ht_lo", "exp_hi", "exp_lo",
                                "tomb", "live")}
        col_ids = [c.col_id for c in self.schema.value_columns]
        set_parts = {cid: [] for cid in col_ids}
        null_parts = {cid: [] for cid in col_ids}
        cmp_parts = {cid: [] for cid in col_ids}
        arith_parts = {cid: [] for cid in col_ids}
        varlen_all = {cid: [] for cid in col_ids}
        run_row_counts = []
        for cr in crs:
            nrun = 0
            for b in range(cr.B):
                nv = cr.blocks[b].num_valid
                if nv == 0:
                    continue
                nrun += nv
                parts_kw.append(cr.key_planes[b, :nv])
                parts["ht_hi"].append(cr.ht_hi[b, :nv])
                parts["ht_lo"].append(cr.ht_lo[b, :nv])
                parts["exp_hi"].append(cr.exp_hi[b, :nv])
                parts["exp_lo"].append(cr.exp_lo[b, :nv])
                parts["tomb"].append(cr.tomb[b, :nv])
                parts["live"].append(cr.live[b, :nv])
                for cid in col_ids:
                    col = cr.cols[cid]
                    set_parts[cid].append(col.set_[b, :nv])
                    null_parts[cid].append(col.isnull[b, :nv])
                    cmp_parts[cid].append(col.cmp_planes[b, :nv])
                    if col.arith is not None:
                        arith_parts[cid].append(col.arith[b, :nv])
                    if col.varlen is not None:
                        varlen_all[cid].extend(col.varlen[b][:nv])
            run_row_counts.append(nrun)
        if not parts_kw:
            return None
        N = sum(run_row_counts)
        # Pad to a size bucket so the compiled program is reused; pad rows
        # carry max key planes (sort last) and the plane encoding of
        # hybrid time 0 (visible, never a contributor), and are dropped by
        # the perm < N filter regardless.
        Np = 1 << max(10, (N - 1).bit_length())
        pad = Np - N
        ZLO = -(1 << 31)  # low plane of value 0 (bias-flipped)

        def cat(lst, fill):
            arr = np.concatenate(lst)
            if pad:
                shape = (pad,) + arr.shape[1:]
                arr = np.concatenate(
                    [arr, np.full(shape, fill, dtype=arr.dtype)])
            return arr

        kw = cat(parts_kw, np.iinfo(np.int32).max)
        ht_hi = cat(parts["ht_hi"], 0)
        ht_lo = cat(parts["ht_lo"], ZLO)

        # Merge ORDER host-side, as a k-way merge of the PRESORTED runs
        # (each run is (key asc, ht desc) by construction) over memcmp
        # sort keys — vectorized C, ~6x cheaper than np.lexsort of the
        # union, which XLA's variadic sort can't replace either (its
        # 10-key lexsort compiles catastrophically slowly, measured).
        # The retention decisions run on device (ops.compact docstring).
        run_items = []
        off = 0
        for t, nrows in zip(runs, run_row_counts):
            if nrows == 0:
                continue
            sk = self._sortkey_bytes(kw[off:off + nrows],
                                     ht_hi[off:off + nrows],
                                     ht_lo[off:off + nrows])
            run_items.append((np.arange(off, off + nrows,
                                        dtype=np.int64), sk))
            off += nrows
        perm = self._merge_sorted(run_items)
        if pad:
            perm = np.concatenate(
                [perm, np.arange(N, Np, dtype=np.int64)])
        skw = kw[perm]
        s_ht_hi = ht_hi[perm]
        s_ht_lo = ht_lo[perm]
        new_group = np.empty(Np, dtype=bool)
        new_group[0] = True
        new_group[1:] = (skw[1:] != skw[:-1]).any(axis=1)

        exp_hi = cat(parts["exp_hi"], 0)
        exp_lo = cat(parts["exp_lo"], ZLO)
        tomb = cat(parts["tomb"], False)
        live = cat(parts["live"], False)
        cat_set = {cid: cat(set_parts[cid], False) for cid in col_ids}

        c_hi, c_lo = P.scalar_ht_planes(max(cutoff, 0))
        keep_dev = None
        gc_pins: list[TpuRun] = []
        try:
            # The mask is computed where the planes are: on the device
            # when the union is big enough to be worth uploading, or
            # when every run is resident already (a device flush leaves
            # its run in HBM), so that nothing crosses the link but the
            # index vector.
            if (N > HOST_GC_MASK_MAX
                    or all(t.peek_device() is not None for t in runs)) \
                    and self._device_gc_fits_budget(runs):
                # Device retention mask over RESIDENT planes: upload only
                # the sorted flat-index vector (union position -> row in
                # the concatenation of the runs' flattened device planes)
                # and the group bits — the planes never re-cross the link.
                # Every run is pinned for the dispatch window so eviction
                # can't drop planes the mask program still references.
                route = "device"
                for t in runs:
                    t.pin("low")
                    gc_pins.append(t)
                R = self.rows_per_block
                offsets = np.cumsum(
                    [0] + [t.dev.B * R for t in runs])[:-1]
                src_parts = []
                for t, off in zip(runs, offsets):
                    cr = t.crun
                    for b in range(cr.B):
                        nv = cr.blocks[b].num_valid
                        if nv:
                            src_parts.append(np.arange(
                                off + b * R, off + b * R + nv,
                                dtype=np.int32))
                if pad:
                    src_parts.append(np.full(pad, -1, np.int32))
                src = np.concatenate(src_parts)
                idx = src[perm]
                runs_planes = tuple(
                    {"ht_hi": t.dev.arrays["ht_hi"],
                     "ht_lo": t.dev.arrays["ht_lo"],
                     "exp_hi": t.dev.arrays["exp_hi"],
                     "exp_lo": t.dev.arrays["exp_lo"],
                     "tomb": t.dev.arrays["tomb"],
                     "live": t.dev.arrays["live"],
                     "sets": tuple(t.dev.arrays["cols"][cid]["set"]
                                   for cid in col_ids)}
                    for t in runs)
                # (numpy scalars: a jnp one is a device program each)
                cutoff_planes = (np.int32(c_hi), np.int32(c_lo),
                                 np.int32(c_hi), np.int32(c_lo))
                args = (idx, new_group, cutoff_planes,
                        np.bool_(keep_tombstones))
                keep_dev = dcompact.resident_gc_mask(runs_planes, *args)
                keep_dev.copy_to_host_async()
                # (the planes the program reads: the runs' MVCC and set
                # planes as they lie in HBM, the index and group vectors)
                metrics.count_device_dispatch(
                    "resident_gc_mask",
                    device_nbytes((runs_planes, args[:2])),
                    h2d=len(jax.tree.leaves(args)), d2h=1)
            else:
                # Small unions that are not resident (or budgets too
                # tight to pin the whole union): the host-vectorized
                # twin beats uploading the planes.
                route = "host"
                keep = dcompact.gc_mask_host(
                    len(col_ids),
                    {"new_group": new_group, "ht_hi": s_ht_hi,
                     "ht_lo": s_ht_lo, "exp_hi": exp_hi[perm],
                     "exp_lo": exp_lo[perm], "tomb": tomb[perm],
                     "live": live[perm],
                     "set_": [cat_set[cid][perm] for cid in col_ids]},
                    (c_hi, c_lo, c_hi, c_lo), keep_tombstones)

            # While any device mask computes/streams back, do the host
            # work that doesn't need it: collect the row-level Python
            # payloads (block VIEWS of the runs' object ndarrays, one
            # pointer-copying concatenate per payload).
            valid_blocks = [(cr, b, cr.blocks[b].num_valid)
                            for cr in crs for b in range(cr.B)
                            if cr.blocks[b].num_valid]
            all_keys = np.concatenate(
                [cr.row_keys[b, :nv] for cr, b, nv in valid_blocks])
            all_vers = np.concatenate(
                [cr.row_versions[b, :nv] for cr, b, nv in valid_blocks])
            all_kvs = np.concatenate(
                [cr.row_key_vals[b, :nv] for cr, b, nv in valid_blocks])
            if keep_dev is not None:
                keep = jax.device_get(keep_dev)
        finally:
            for t in gc_pins:
                t.unpin()

        kept_pos = np.nonzero(keep[:].astype(bool) & (perm < N))[0]
        kept_src = perm[kept_pos]
        if kept_src.size == 0:
            return (lambda: []), None, route
        # Group boundaries among KEPT rows (still key-sorted).
        gid_sorted = np.cumsum(new_group.astype(np.int64)) - 1
        kept_gids = gid_sorted[kept_pos]
        kept_new_group = np.empty(kept_src.size, dtype=bool)
        kept_new_group[0] = True
        kept_new_group[1:] = kept_gids[1:] != kept_gids[:-1]

        # Survivor (key, versions) groups via one fancy index + per-group
        # slices (C-speed object-array copies; the per-row append loop
        # was the second compaction hot spot). Deferred: only the
        # durability path needs the entry-list form.
        kept_keys = all_keys[kept_src]
        kept_vers = all_vers[kept_src]

        def make_entries() -> list[tuple[bytes, list]]:
            group_starts = np.nonzero(kept_new_group)[0].tolist()
            group_ends = group_starts[1:] + [kept_src.size]
            return [(kept_keys[g0], kept_vers[g0:g1].tolist())
                    for g0, g1 in zip(group_starts, group_ends)]

        planes = {
            "ht_hi": ht_hi, "ht_lo": ht_lo, "exp_hi": exp_hi,
            "exp_lo": exp_lo, "tomb": tomb, "live": live,
            "set": cat_set,
        }
        crun = self._gather_run(kept_src, kept_new_group, all_keys,
                                all_vers, all_kvs, kw, planes, col_ids,
                                null_parts, cmp_parts, arith_parts,
                                varlen_all)
        return make_entries, crun, route

    def _gather_run(self, kept_src, kept_new_group, all_keys, all_vers,
                    all_kvs, kw, planes, col_ids, null_parts, cmp_parts,
                    arith_parts, varlen_all):
        """Assemble the merged ColumnarRun by numpy-gathering surviving
        rows' planes (no per-version re-encoding)."""
        R = self.rows_per_block
        nk = kept_src.size
        bounds = np.nonzero(kept_new_group)[0].tolist() + [nk]
        sizes = [bounds[gi + 1] - bounds[gi]
                 for gi in range(len(bounds) - 1)]
        max_group = max(sizes) if sizes else 0
        # (kept start row, row count) per block via the SHARED packing.
        ranges = [(bounds[g0], rows)
                  for g0, _gn, rows in ColumnarRun.pack_group_ranges(
                      sizes, R)]

        run = ColumnarRun(self.schema, R)
        B = len(ranges)
        run.B = B
        run._alloc(B)
        from yugabyte_db_tpu.storage.columnar import BlockMeta

        cat_null = {cid: np.concatenate(null_parts[cid])
                    for cid in col_ids}
        cat_cmp = {cid: np.concatenate(cmp_parts[cid]) for cid in col_ids}
        cat_set = planes["set"]
        cat_arith = {cid: (np.concatenate(arith_parts[cid])
                           if arith_parts[cid] else None)
                     for cid in col_ids}
        ht_hi_u = planes["ht_hi"]
        ht_lo_u = planes["ht_lo"]
        exp_hi_u = planes["exp_hi"]
        exp_lo_u = planes["exp_lo"]
        tomb_u = planes["tomb"]
        live_u = planes["live"]

        # One flat scatter per plane: kept row j lands at (block_of[j],
        # pos[j]) — the per-block slice loop was the remaining gather
        # hot spot.
        starts = np.array([s0 for s0, _n in ranges], dtype=np.int64)
        ns = np.array([n for _s0, n in ranges], dtype=np.int64)
        block_of = np.repeat(np.arange(B, dtype=np.int64), ns)
        dst = block_of * R + (np.arange(nk, dtype=np.int64)
                              - np.repeat(starts, ns))

        def scatter(dest, vals):
            dest.reshape((B * R,) + dest.shape[2:])[dst] = vals

        scatter(run.key_planes, kw[kept_src])
        scatter(run.ht_hi, ht_hi_u[kept_src])
        scatter(run.ht_lo, ht_lo_u[kept_src])
        scatter(run.exp_hi, exp_hi_u[kept_src])
        scatter(run.exp_lo, exp_lo_u[kept_src])
        scatter(run.tomb, tomb_u[kept_src])
        scatter(run.live, live_u[kept_src])
        run.valid.reshape(-1)[dst] = True
        scatter(run.group_start, kept_new_group)
        for cid in col_ids:
            col = run.cols[cid]
            scatter(col.set_, cat_set[cid][kept_src])
            scatter(col.isnull, cat_null[cid][kept_src])
            scatter(col.cmp_planes, cat_cmp[cid][kept_src])
            if col.arith is not None and cat_arith[cid] is not None:
                scatter(col.arith, cat_arith[cid][kept_src])
        scatter(run.row_keys, all_keys[kept_src])
        scatter(run.row_versions, all_vers[kept_src])
        scatter(run.row_key_vals, all_kvs[kept_src])
        has_varlen = any(run.cols[cid].varlen is not None
                         for cid in col_ids)
        for b, (s0, n) in enumerate(ranges):
            if has_varlen:
                sel_list = kept_src[s0:s0 + n].tolist()
                for cid in col_ids:
                    col = run.cols[cid]
                    if col.varlen is not None:
                        vl = varlen_all[cid]
                        col.varlen[b][:n] = [vl[i] for i in sel_list]
            run.blocks[b] = BlockMeta(run.row_keys[b][0],
                                      run.row_keys[b][n - 1], n)
        run.min_key = run.row_keys[0][0]
        run.max_key = run.blocks[B - 1].max_key
        run.num_versions = nk
        run.max_ht = int(P.planes_to_u64(ht_hi_u[kept_src],
                                         ht_lo_u[kept_src]).max())
        run.max_group_versions = max_group
        # Exact (not inherited) maxima over SURVIVING rows, so GC'd long
        # values/keys don't disable device-exact paths forever.
        kept_keys_flat = all_keys[kept_src]
        run.max_key_len = max(run.max_key_len, int(np.fromiter(
            map(len, kept_keys_flat), np.int64,
            kept_keys_flat.size).max()))
        for b in range(run.B):
            n = run.blocks[b].num_valid
            for cid in col_ids:
                vl = run.cols[cid].varlen
                if vl is None:
                    continue
                # ASCII-dominant workloads: len(str) == encoded length; only
                # re-measure the (rare) non-ASCII cells byte-exactly.
                from yugabyte_db_tpu.storage.columnar import _varlen_raw
                lens = [len(v) if (isinstance(v, str) and v.isascii())
                        else len(v) if isinstance(v, (bytes, bytearray))
                        else len(_varlen_raw(v))
                        for v in vl[b][:n] if v is not None]
                if lens:
                    run.varlen_max_len[cid] = max(
                        run.varlen_max_len.get(cid, 0), max(lens))
        return run

    @contextlib.contextmanager
    def run_files(self):
        with self._runs_lock:   # (a compaction unlinks after its swap)
            yield list(self.persist.files)

    def restore_entries(self, entries) -> None:
        crun = (ColumnarRun.build(self.schema, entries, self.rows_per_block)
                if entries else None)
        path = (self.persist.write_run(entries)
                if entries and self.persist.enabled else None)
        if crun is not None:
            self.flushed_frontier_ht = max(self.flushed_frontier_ht,
                                           crun.max_ht)
        with self._runs_lock:
            old_runs = self.runs
            old_files = list(self.persist.files)
            if self.persist.enabled:
                # yb-lint: disable=iholds/lock-across-blocking
                self.persist.install(old_files, path)
            self.memtable = make_memtable()
            self.runs = ([TpuRun(crun, self.device_tracker, path=path)]
                         if crun is not None else [])
            self._run_set_changed()
        self.persist.remove(old_files)
        for t in old_runs:
            t.retire()

    def dump_entries(self):
        """All flushed (key, versions ht-desc) pairs, key-merged across
        runs — the storage payload of a remote-bootstrap session."""
        from yugabyte_db_tpu.storage.merge import merge_entry_streams

        return list(merge_entry_streams(
            [t.crun.iter_entries() for t in self.runs]))

    def stats(self) -> dict:
        return {
            "num_runs": len(self.runs),
            "memtable_versions": self.memtable.num_versions,
            "run_versions": sum(t.crun.num_versions for t in self.runs),
            "flushed_frontier_ht": self.flushed_frontier_ht,
            # True residency: what the cache currently holds for this
            # engine (demand uploads minus evictions), not the run total.
            "device_bytes": self.device_tracker.consumption,
        }

    # -- scan plumbing ------------------------------------------------------
    @staticmethod
    def _prune_prefix(spec: ScanSpec) -> bytes | None:
        """The hashed-components prefix shared by EVERY key in the scan
        range, or None. Present for point gets and single-primary-key
        range scans — the shapes the per-run bloom prunes."""
        if not spec.lower or not spec.upper:
            return None
        from yugabyte_db_tpu.models.encoding import (hashed_prefix,
                                                     prefix_successor)

        hp = hashed_prefix(spec.lower)
        if not hp:
            return None
        ps = prefix_successor(hp)
        if ps and spec.upper > ps:
            return None  # range crosses out of the hash section
        return hp

    def _overlapping_runs(self, spec: ScanSpec) -> list[TpuRun]:
        out = []
        hp = self._prune_prefix(spec)
        for t in self.runs:
            if t.crun.num_versions == 0:
                continue
            if spec.upper and t.crun.min_key >= spec.upper:
                continue
            if t.crun.max_key < spec.lower:
                continue
            if hp is not None and not t.crun.may_contain_hashed(hp):
                continue
            out.append(t)
        return out

    def _memtable_in_range(self, spec: ScanSpec) -> bool:
        if self.memtable.is_empty:
            return False
        return self.memtable.has_keys(spec.lower, spec.upper)

    def _split_predicates(self, spec: ScanSpec):
        """(device-exact preds, device-superset preds, host-only preds).

        'str' prefixes and 'f32' rounded values give superset masks only
        (ties are maybe-matches the host verifies); key-column and IN
        predicates are host-only."""
        exact, superset, host_only = [], [], []
        for p in spec.predicates:
            if p.column in self._key_col_names or p.op == "IN":
                host_only.append(p)
                continue
            cid = self._name_to_id[p.column]
            dt = self._dtypes[cid]
            if not dt.is_fixed_width and dt not in (DataType.STRING,
                                                    DataType.BINARY):
                # opaque payloads (collections, jsonb): the device prefix
                # is repr-ordered, not value-ordered — host only
                host_only.append(p)
                continue
            kind = self._kinds[cid]
            if kind in ("str", "f32"):
                superset.append(p)
            else:
                exact.append(p)
        return exact, superset, host_only

    def _promote_code_preds(self, trun: TpuRun, preds):
        """Translate superset string predicates into device-EXACT
        dictionary-code predicates (_CodePred) against ``trun``'s
        per-run sorted dictionaries, or None when any predicate can't
        promote (encoding off, column not dictionary-encoded on this
        run — overflow fallback — or a non-range operator).

        The dictionary is the sorted unique set non-null values, so
        order-preserving code translation is a bisect:
        '<' v  -> code <  bisect_left,  '<=' v -> code <  bisect_right,
        '>' v  -> code >= bisect_right, '>=' v -> code >= bisect_left;
        '='/'!=' use the exact code, or -1 (matches/misses nothing set:
        every eval site ANDs with the column's notnull mask). Promotion
        requires the RESIDENT device form to be the encoded tree — a
        device-flush-seeded run stays plain in HBM until evicted."""
        dicts = getattr(trun.crun, "enc_dicts", None)
        if not dicts or trun.crun.encoded_arrays() is None:
            return None
        out = []
        for p in preds:
            cid = self._name_to_id[p.column]
            d = dicts.get(cid)
            if d is None or p.op not in ("=", "!=", "<", "<=", ">", ">="):
                return None
            raw = (p.value.encode("utf-8", "surrogateescape")
                   if isinstance(p.value, str) else bytes(p.value))
            if p.op in ("=", "!="):
                i = bisect.bisect_left(d, raw)
                code = i if i < len(d) and d[i] == raw else -1
                out.append(_CodePred(p.column, p.op, code))
            elif p.op == "<":
                out.append(_CodePred(p.column, "<",
                                     bisect.bisect_left(d, raw)))
            elif p.op == "<=":
                out.append(_CodePred(p.column, "<",
                                     bisect.bisect_right(d, raw)))
            elif p.op == ">":
                out.append(_CodePred(p.column, ">=",
                                     bisect.bisect_right(d, raw)))
            else:  # >=
                out.append(_CodePred(p.column, ">=",
                                     bisect.bisect_left(d, raw)))
        if not trun.dev.encoded:
            return None  # resident planes are the plain (seeded) form
        return out

    def _aggs_device_eligible(self, spec: ScanSpec) -> bool:
        """Device aggregates need every aggregate column to be a numeric
        VALUE column (key columns live in the encoded key, not in planes;
        string min/max needs full bytes the device doesn't have)."""
        for a in spec.aggregates:
            if a.column is None:
                continue
            cid = self._name_to_id.get(a.column)
            if cid is None:
                return False  # key column (or unknown): host path
            if self._kinds[cid] == "str" and a.fn != "count":
                return False
        return True

    def _pred_kind(self, p) -> str:
        """Device plane kind a predicate compares against; promoted
        dictionary-code predicates compare the int32 code plane."""
        if isinstance(p, _CodePred):
            return "code"
        return self._kinds[self._name_to_id[p.column]]

    def _pred_sig_and_literals(self, preds, literal_fn=None):
        lit = _literal if literal_fn is None else literal_fn
        sigs, lits = [], []
        for p in preds:
            cid = self._name_to_id[p.column]
            kind = self._pred_kind(p)
            sigs.append(dscan.PredSig(cid, kind, p.op))
            lits.append(lit(kind, p.value))
        return tuple(sigs), tuple(lits)

    def _pred_sigs_only(self, preds):
        """PredSigs without materializing device literals (the gather path
        ships literals inside the params vector; creating jnp scalars here
        would queue one tiny host->device transfer per predicate ahead of
        the batched dispatch)."""
        return tuple(
            dscan.PredSig(self._name_to_id[p.column],
                          self._pred_kind(p), p.op)
            for p in preds)

    def _col_sigs(self):
        return tuple(dscan.ColSig(c.col_id, self._kinds[c.col_id])
                     for c in self.schema.value_columns)

    def _read_planes(self, spec: ScanSpec):
        return tuple(jnp.int32(v) for v in self._read_plane_ints(spec))

    @staticmethod
    def _scan_priority(spec: ScanSpec) -> str:
        """Residency-pool priority of a scan: unbounded full-table
        traffic is admitted low-pri (scan-resistant), bounded ranges and
        point shapes protect their runs in the high-pri pool."""
        return "low" if (not spec.lower and not spec.upper) else "high"

    def _device_candidates(self, trun: TpuRun, spec: ScanSpec,
                           pred_sigs, pred_lits, apply_preds: bool):
        """Run the device row-scan over the block windows covering the range;
        yield candidate keys (host-materialized, in key order)."""
        self._device_fault_point()
        crun = trun.crun
        row_lo = crun.lower_row(spec.lower)
        row_hi = crun.upper_row(spec.upper)
        if row_lo >= row_hi:
            return
        R = crun.R
        K = WINDOW_BLOCKS
        b_first = (row_lo // R) // K * K
        b_last = ((row_hi - 1) // R) // K * K
        sig = dscan.ScanSig(B=trun.dev.B, R=R, K=K, cols=self._col_sigs(),
                            preds=pred_sigs, aggs=(), apply_preds=apply_preds,
                            flat=crun.max_group_versions <= 1)
        fn = dscan.compiled_scan(sig)
        r_hi_, r_lo_, e_hi_, e_lo_ = self._read_planes(spec)
        for b0 in range(b_first, b_last + 1, K):
            base = b0 * R
            res = fn(trun.dev.arrays, jnp.int32(b0),
                     jnp.int32(np.clip(row_lo - base, -(1 << 30), 1 << 30)),
                     jnp.int32(np.clip(row_hi - base, -(1 << 30), 1 << 30)),
                     r_hi_, r_lo_, e_hi_, e_lo_, pred_lits)
            # One explicit fetch for all three outputs instead of a
            # blocking transfer per array.
            res = jax.device_get(res)
            mask = res["result"]
            ng = int(res["num_groups"])
            start = res["start_idx"]
            for g in np.nonzero(mask[:ng])[0]:
                yield crun.key_at(base + int(start[g]))

    # -- reads -------------------------------------------------------------
    # The host↔device link pays a full round-trip per *blocking* call,
    # ~ms per transferred array, and pipelines async dispatches (measured:
    # 10 async dispatches complete in ~1 RTT). Every scan therefore splits
    # into a plan step that DESCRIBES device work and a finish step that
    # decodes fetched results; scan_batch() groups all page scans with the
    # same static signature into one vmapped dispatch, issues everything
    # async, and fetches every output in one device_get.
    def scan(self, spec: ScanSpec) -> ScanResult:
        return self.scan_batch([spec])[0]

    # G buckets for the vmapped page-scan dispatch (one compile per bucket).
    _G_BUCKETS = (1, 2, 4, 8, 16, 32, 64, 128)

    def scan_batch(self, specs: list[ScanSpec],
                   deadline=None) -> list[ScanResult]:
        return self.scan_batch_async(specs, deadline=deadline).finish()

    def _device_fault_point(self) -> None:
        """MAYBE_FAULT marker for the device dispatch path (flag
        ``fault.tpu_dispatch``): fires as the kind of failure the
        breaker quarantines."""
        if maybe_fault("fault.tpu_dispatch"):
            raise FaultInjected("injected device dispatch fault")

    def scan_batch_async(self, specs: list[ScanSpec], deadline=None):
        """Plan every scan, issue all round-1 device work, and start the
        outputs streaming host-ward (copy_to_host_async) WITHOUT waiting.
        The caller finishes the batch later with .finish().

        This is the server shape: one synchronous fetch cycle has a
        fixed cost regardless of size, but dispatches and async copies
        pipeline — so overlapping batches (issue N+1 before finishing N)
        amortizes that cost across whole batches.

        Fault containment: while the breaker quarantines the device path
        (or a device fault strikes during planning/dispatch) the batch
        is served from the authoritative host structures instead —
        byte-identical results, no device traffic. ``deadline``
        (utils.retry.Deadline) is the propagated RPC budget; an expired
        deadline aborts with Code.TIMED_OUT before any work is issued
        (and between finish()-time rounds), unwinding residency pins."""
        if deadline is not None:
            deadline.check("tpu_engine.scan_batch")
        # Phase "issue" of the batch: planning of every spec, pins, the
        # deferred batch dispatches, copy_to_host_async. Once a batch,
        # under the route its plans took ("failed" if it raises).
        with trace.span("engine.issue") as sp:
            route = "failed"
            try:
                batch = self._issue_batch(specs, deadline)
                route = batch.route
                sp.labels["sources"] = batch.sources
            finally:
                _set_phase(sp, "issue", route)
        return batch

    def _issue_batch(self, specs: list[ScanSpec], deadline):
        if not self.breaker.allow():
            return _HostServeBatch(self, specs, deadline)
        try:
            return self._scan_batch_async_device(specs, deadline)
        except DEVICE_FAULT_TYPES as e:
            self.breaker.record_failure(e)
            return _HostServeBatch(self, specs, deadline)
        except BaseException as e:
            # A non-device raise (planning bug, expired deadline between
            # rounds) must still retire the probe allow() admitted, or the
            # breaker's half-open slot stays consumed forever.
            self.breaker.record_failure(e)
            raise

    def _scan_batch_async_device(self, specs: list[ScanSpec],
                                 deadline=None) -> "_AsyncBatch":
        self._device_fault_point()
        # Until the _AsyncBatch below takes ownership (its finish path
        # unpins), any failure while pinning or planning must unwind the
        # pins already taken, or those entries stay unevictable for the
        # process lifetime.
        pins = []
        try:
            with _issue_part("plan"):
                agg_sink: list = []
                grouped_sink: list = []
                batch = {"pins": pins}
                plans = [self._plan_scan(s, agg_sink=agg_sink,
                                         grouped_sink=grouped_sink,
                                         batch=batch)
                         for s in specs]

                results: list = [None] * len(plans)
                issued_outs = []
                host_plans = []
                page_items: list[tuple[int, tuple]] = []
                gathers: list[tuple[int, "_GatherScan"]] = []
                pre_work = []
                deferred: list = []
                gdeferred: list = []
                odeferred: list = []
                for pi, plan in enumerate(plans):
                    if plan[0] == "host":
                        host_plans.append((pi, plan[1]))
                    elif plan[0] == "page":
                        page_items.append((pi, plan[1]))
                    elif plan[0] == "issued":
                        issued_outs.append((pi, plan[1], plan[2]))
                        if len(plan) > 3:  # host work to overlap the fetch
                            pre_work.append(plan[3])
                    elif plan[0] == "agg_deferred":
                        deferred.append(pi)
                    elif plan[0] == "grouped_deferred":
                        gdeferred.append(pi)
                    elif plan[0] == "overlay_deferred":
                        odeferred.append((pi, plan[1]))
                    else:
                        gathers.append((pi, plan[1]))
                # Residency pins for the issue→finish window: every run a
                # device plan references stays resident until finish()
                # releases it, so eviction can't drop planes an in-flight
                # dispatch still holds. Unbounded full scans pin at low
                # priority — they stream through the cache's low-pri pool
                # instead of flushing the protected working set (the
                # overlay's masked primary is pinned separately by the
                # engine's overlay cache).
                want_pins: dict[int, tuple[TpuRun, str]] = {}

                def want_pin(trun, priority):
                    if isinstance(trun, _MaskedRun):
                        return
                    prev = want_pins.get(id(trun))
                    if prev is None or priority == "high":
                        want_pins[id(trun)] = (trun, priority)

                for _pi, st in gathers:
                    want_pin(st.trun,
                             "low" if st.mode == "chunks" else "high")
                for trun, spec, _exact in agg_sink:
                    want_pin(trun, self._scan_priority(spec))
                for item in grouped_sink:
                    want_pin(item[0], self._scan_priority(item[1]))
                for trun, priority in want_pins.values():
                    trun.pin(priority)
                    pins.append(trun)
            with _issue_part("dispatch"):
                if deferred:
                    # Single-source device aggregates dispatch together:
                    # one vmapped program per (run, signature) group.
                    items = [(pi, trun, spec, exact)
                             for pi, (trun, spec, exact)
                             in zip(deferred, agg_sink)]
                    issued_outs.extend(
                        self._plan_device_aggregate_batch(items))
                if gdeferred:
                    items = [(pi, trun, spec, exact, payload)
                             for pi, (trun, spec, exact, payload)
                             in zip(gdeferred, grouped_sink)]
                    issued_outs.extend(self._plan_grouped_batch(items))
                # Multi-source grouped aggregates: two programs a spec,
                # over the overlay's masked primary and its mini-run.
                issued_outs.extend(
                    (pi, *dispatch()) for pi, dispatch in odeferred)
                # Page items defer wholesale to finish() (device work
                # first); host_page.serve_pages runs them through the
                # native page server.
                pages = page_items

                states = dict(gathers)
                pending = {pi: st.pending for pi, st in gathers
                           if st.pending}
                dispatches = (self._issue_round(states, pending)
                              if pending else [])
            with _issue_part("copy_out"):
                # (the lanes of a vmapped dispatch share one array)
                leaves = jax.tree.leaves([[d for _c, d in dispatches],
                                          [o for _pi, o, _f
                                           in issued_outs]])
                for leaf in {id(leaf): leaf for leaf in leaves}.values():
                    leaf.copy_to_host_async()
            return _AsyncBatch(self, results, host_plans, issued_outs,
                               gathers, states, pending, dispatches,
                               pages, pre_work, pins, specs=specs,
                               deadline=deadline,
                               route=_batch_route(plans),
                               sources=batch.get("sources", 1))
        except BaseException:
            for trun in pins:
                trun.unpin()
            raise

    def scan_batch_wire(self, specs: list[ScanSpec], fmt: str = "cql",
                        deadline=None):
        """Wire-serialized pages with the native fast path: LIMIT pages
        on a single flat run with host-exact predicates serialize to
        protocol bytes entirely in C (host_page.serve_pages_wire /
        native serve_page_wire_batch) — no Python value objects on the
        hot path. Point gets (exact-key ranges) keep a dedicated
        bloom-pruned per-key path that stays fast with a live memtable
        and overlapping runs. Everything else (multi-source range
        scans, aggregates, superset predicates) takes the
        scan + Python-serialize fallback, which produces identical
        bytes (models.wirefmt)."""
        fmt_id = host_page.WIRE_CQL if fmt == "cql" else host_page.WIRE_PG
        out = [None] * len(specs)
        mem = self.memtable
        fast_ok = (len(self.runs) == 1 and mem.is_empty
                   and self.runs[0].crun.num_versions > 0
                   and self.runs[0].crun.max_group_versions <= 1)
        slow_idx: list[int] = []
        slow_specs: list[ScanSpec] = []
        if fast_ok:
            trun = self.runs[0]
            items, item_idx = [], []
            for i, spec in enumerate(specs):
                if (spec.limit is not None
                        and spec.limit <= host_page.MAX_PAGE_LIMIT
                        and not spec.is_aggregate and not spec.group_by):
                    pred_items = host_page.encode_pred_items(
                        self, spec.predicates)
                    if pred_items is not None:
                        items.append((trun, spec, pred_items))
                        item_idx.append(i)
                        continue
                slow_idx.append(i)
                slow_specs.append(spec)
            if items:
                served = host_page.serve_pages_wire(self, items, fmt_id)
                for i, pg in zip(item_idx, served):
                    if pg is None:
                        slow_idx.append(i)
                        slow_specs.append(specs[i])
                    else:
                        out[i] = pg
        else:
            # Live memtable: most point reads still miss it (the YCSB
            # mixed steady state — updates touch a small dirty set), so
            # keys ABSENT from the memtable serve from the flat run via
            # the native page server exactly like the fast path; only
            # memtable hits pay the Python merge. The presence probe is
            # the native memtable's has_keys (C, O(log n)).
            run_ok = (len(self.runs) == 1
                      and self.runs[0].crun.num_versions > 0
                      and self.runs[0].crun.max_group_versions <= 1)
            trun = self.runs[0] if run_ok else None
            items, item_idx = [], []
            for i, spec in enumerate(specs):
                pk = self._point_key(spec)
                if pk is None:
                    slow_idx.append(i)
                    slow_specs.append(spec)
                    continue
                if (trun is not None and spec.limit is not None
                        and spec.limit <= host_page.MAX_PAGE_LIMIT
                        and not mem.has_keys(spec.lower, spec.upper)):
                    pred_items = host_page.encode_pred_items(
                        self, spec.predicates)
                    if pred_items is not None:
                        items.append((trun, spec, pred_items))
                        item_idx.append(i)
                        continue
                out[i] = self._point_get_wire(spec, fmt_id, mem, pk)
            if items:
                served = host_page.serve_pages_wire(self, items, fmt_id)
                for i, pg in zip(item_idx, served):
                    if pg is None:
                        out[i] = self._point_get_wire(
                            specs[i], fmt_id, mem,
                            self._point_key(specs[i]))
                    else:
                        out[i] = pg
        if slow_specs:
            for i, pg in zip(slow_idx,
                             super().scan_batch_wire(slow_specs, fmt,
                                                     deadline=deadline)):
                out[i] = pg
        return out

    def _point_key(self, spec: ScanSpec) -> bytes | None:
        from yugabyte_db_tpu.storage.scan_spec import point_key_of

        return point_key_of(spec, self.schema)

    def _point_versions(self, key: bytes, mem) -> list[RowVersion]:
        """Bloom-pruned per-key version lookup across runs + memtable —
        O(log run), no scan machinery (the reference's
        DocRowwiseIterator point-get over the IntentAwareIterator,
        src/yb/docdb/doc_rowwise_iterator.cc)."""
        from yugabyte_db_tpu.models.encoding import hashed_prefix

        versions: list[RowVersion] = []
        hp = hashed_prefix(key)
        # The bloom earns its (lazy, full-run) build only when it can
        # skip several runs per get; with 1-2 runs the per-run binary
        # search is already O(log n), so only probe a bloom that exists.
        many_runs = len(self.runs) > 2
        for t in self.runs:
            crun = t.crun
            if crun.num_versions == 0 or crun.max_key < key \
                    or crun.min_key > key:
                continue
            if hp and (many_runs or crun.bloom_ready) \
                    and not crun.may_contain_hashed(hp):
                continue
            versions.extend(crun.find_versions(key))
        versions.extend(mem.versions(key))
        return versions

    def _point_get_row(self, spec: ScanSpec, mem, key: bytes):
        """-> (projection, rows, resume, scanned) for one exact-key
        read (merge + predicates + materialization, shared by the wire
        and row point paths)."""
        versions = self._point_versions(key, mem)
        projection = spec.projection or [c.name for c in
                                         self.schema.columns]
        rows: list[tuple] = []
        if versions:
            merged = merge_versions(key, versions, spec.read_ht)
            if merged.exists:
                key_vals = self.mat.key_values(key)
                if self.mat.matches(spec, key_vals, merged):
                    rows.append(tuple(
                        self.mat.value(nm, key_vals, merged)
                        for nm in projection))
        resume = (key + b"\x00" if spec.limit is not None
                  and len(rows) >= spec.limit else None)
        return projection, rows, resume, 1 if versions else 0

    def _point_get_wire(self, spec: ScanSpec, fmt_id, mem, key: bytes):
        """Exact-key read serialized by the Python twin (one row)."""
        from yugabyte_db_tpu.models import wirefmt

        projection, rows, resume, scanned = self._point_get_row(
            spec, mem, key)
        dts = self._wire_dtypes(tuple(projection))
        data = wirefmt.serialize_rows(
            "cql" if fmt_id == host_page.WIRE_CQL else "pg", dts, rows)
        return host_page.WirePage(list(projection), data, len(rows),
                                  resume, scanned)

    def _wire_dtypes(self, projection: tuple):
        dts = self._wire_dtype_cache.get(projection)
        if dts is None:
            by_name = {c.name: c.dtype for c in self.schema.columns}
            dts = [by_name[nm] for nm in projection]
            if len(self._wire_dtype_cache) >= 64:
                self._wire_dtype_cache.pop(
                    next(iter(self._wire_dtype_cache)))
            self._wire_dtype_cache[projection] = dts
        return dts

    def _issue_round(self, states, pending):
        """Group every active gather's pending param-rows by (signature,
        run) into vmapped dispatches; returns [(chunk, out_array)]."""
        self._device_fault_point()
        from yugabyte_db_tpu.ops import row_gather

        by_sig: dict = {}
        for pi, rows in pending.items():
            st = states[pi]
            for ri, (ip, fp) in enumerate(rows):
                by_sig.setdefault((st.sig, id(st.trun)),
                                  (st.trun, []))[1].append(
                    (pi, ri, ip, fp))
        dispatches = []
        for (sig, _tid), (trun, members) in by_sig.items():
            for c0 in range(0, len(members), self._G_BUCKETS[-1]):
                chunk = members[c0:c0 + self._G_BUCKETS[-1]]
                G = next(g for g in self._G_BUCKETS if g >= len(chunk))
                ip = np.zeros((G, len(chunk[0][2])), dtype=np.int32)
                fp = np.zeros((G, len(chunk[0][3])), dtype=np.float32)
                ip[:, 1] = -1  # padding: w_last < w_first -> no work
                for j, (_pi, _ri, ipj, fpj) in enumerate(chunk):
                    ip[j] = ipj
                    fp[j] = fpj
                fn = row_gather.compiled_gather_batch(sig, G)
                dispatches.append((chunk, fn(trun.dev.arrays, ip, fp)))
        return dispatches

    def _feed_round(self, states, pending, dispatches, disp_bufs):
        """Feed fetched buffers back to their gathers; returns the next
        round's pending param-rows ({} when every gather completed).

        Lanes that are provably complete after round 1 (paged LIMIT scans
        with no host verification: the while_loop either filled M >= limit
        matches or exhausted the range) are decoded in one vectorized pass
        per plan structure instead of page-by-page — per-page Python cost
        is what caps server throughput once fetches are pipelined."""
        groups: dict = {}
        handled: set[int] = set()
        for (chunk, _out), bufs in zip(dispatches, disp_bufs):
            tails = None
            for j, (pi, ri, _ip, _fp) in enumerate(chunk):
                st = states[pi]
                ctx = st.ctx
                if (ri != 0 or len(pending[pi]) != 1 or st.rows or
                        st.mode != "paged" or ctx["aggregate"] or
                        ctx["verify_preds"] or ctx["limit"] is None or
                        ctx.get("struct_key") is None):
                    continue
                if tails is None:  # one vectorized read per chunk
                    tails = bufs[:, ctx["M"], :2].tolist()
                groups.setdefault(ctx["struct_key"], []).append(
                    (pi, st, bufs[j], tails[j]))
        for members in groups.values():
            self._batch_emit(members)
            handled.update(pi for pi, _st, _b, _t in members)

        plan_bufs: dict[int, dict[int, np.ndarray]] = {}
        for (chunk, _out), bufs in zip(dispatches, disp_bufs):
            for j, (pi, ri, _ip, _fp) in enumerate(chunk):
                if pi in handled:
                    continue
                plan_bufs.setdefault(pi, {})[ri] = bufs[j]
        next_pending = {}
        for pi, rows in pending.items():
            st = states[pi]
            if pi in handled:
                st.pending = []
                continue
            bufs = [plan_bufs[pi][ri] for ri in range(len(rows))]
            more = st.consume(bufs)
            if more:
                next_pending[pi] = more
        return next_pending

    def _batch_emit(self, members):
        """Vectorized decode of many completed LIMIT pages that share one
        plan structure: one concatenate + one decode per column for the
        whole group, then per-page list slices."""
        from yugabyte_db_tpu.ops import row_gather

        st0 = members[0][1]
        ctx = st0.ctx
        M, limit, crun = ctx["M"], ctx["limit"], ctx["crun"]
        projection = ctx["projection"]
        key_col_pos = ctx["key_col_pos"]
        _w, col_offs = row_gather.out_layout(ctx["sig"])
        parts, metas = [], []
        for _pi, st, buf, (count, scanned) in members:
            n_take = min(count, M, limit)
            st.scanned += scanned
            if n_take:
                parts.append(buf[:n_take])
            metas.append((st, n_take))
        if parts:
            flat = np.concatenate(parts) if len(parts) > 1 else parts[0]
            starts = flat[:, 0]
            kv_cols = (crun.key_col_arrays(
                           np.unique(starts // crun.R).tolist())
                       if any(nm in key_col_pos for nm in projection)
                       else None)
            cols_out = []
            for nm in projection:
                if nm in key_col_pos:
                    cols_out.append(
                        kv_cols[key_col_pos[nm]][starts].tolist())
                else:
                    cols_out.append(self._decode_col(
                        self._name_to_id[nm], flat, flat.shape[0], crun,
                        col_offs))
            rows_all = list(zip(*cols_out))
        else:
            rows_all = []
            starts = None
        off = 0
        for st, n_take in metas:
            st.rows = rows_all[off:off + n_take]
            if n_take >= limit:
                st.resume = crun.key_at(
                    int(starts[off + n_take - 1])) + b"\x00"
            off += n_take

    def _plan_scan(self, spec: ScanSpec, agg_sink: list | None = None,
                   grouped_sink: list | None = None,
                   batch: dict | None = None):
        """-> ("host", finish()) | ("issued", outs, finish(fetched))
           | ("gather", _GatherScan) | ("agg_deferred",) /
           ("grouped_deferred",) for single-source device (grouped)
           aggregates, which land in the sinks — the caller dispatches
           those together (one vmapped program per signature group;
           _plan_device_aggregate_batch / _plan_grouped_batch)
           | ("overlay_deferred", dispatch) for a grouped aggregate over
           several sources (_plan_overlay_grouped).
           ``batch``: the scan batch's ``pins`` (runs a plan pinned for
           the issue -> finish window; without a batch the plan unpins
           them itself) and ``sources`` (the most sources a spec of the
           batch read, where more than one)."""
        if agg_sink is None:
            agg_sink = []
        if grouped_sink is None:
            grouped_sink = []
        # Snapshot the memtable BEFORE the run list: flush() appends the
        # new run and THEN swaps in an empty memtable, so (old mem, runs
        # read after) can at worst see a flushed row in both sources
        # (harmless — merge dedups by hybrid time) but never in neither.
        # The snapshot also covers _AsyncBatch.finish()-time execution of
        # host-path closures: flush() never mutates the old MemTable.
        mem = self.memtable
        from yugabyte_db_tpu.utils.sync_point import sync_point

        sync_point("tpu_engine:plan:mem_snapshotted")
        runs = self._overlapping_runs(spec)
        mem_live = (not mem.is_empty) and \
            mem.has_keys(spec.lower, spec.upper)
        exact, superset, host_only = self._split_predicates(spec)
        pred_split = (exact, superset, host_only)
        single_source = len(runs) == 1 and not mem_live

        if spec.is_aggregate:
            has_expr = any(a.expr is not None for a in spec.aggregates)
            if single_source and runs and superset and not host_only:
                # Dictionary-encoded string predicates promote to exact
                # code-range compares: the aggregate stays a pure device
                # fold instead of degrading to the gather+verify path.
                promoted = self._promote_code_preds(runs[0], superset)
                if promoted is not None:
                    exact = exact + promoted
                    superset = []
                    pred_split = (exact, superset, host_only)
            if single_source and runs and not superset and not host_only \
                    and (spec.group_by or has_expr):
                prep = self._grouped_prep(runs[0], spec, exact)
                if prep is not None:
                    kind, payload = prep
                    if kind == "empty":
                        return payload
                    grouped_sink.append((runs[0], spec, exact, payload))
                    return ("grouped_deferred",)
            eligible = (not superset and not host_only
                        and not spec.group_by and not has_expr
                        and self._aggs_device_eligible(spec))
            if eligible and single_source and runs:
                agg_sink.append((runs[0], spec, exact))
                return ("agg_deferred",)
            if not single_source and (runs or mem_live):
                # Multi-source (overlapping runs / live memtable): the
                # cached delta overlay keeps this a pure device scan —
                # primary run with dirty keys masked out of its valid
                # plane + the dirty keys' full merged version sets, as
                # a device mini-run for a grouped program and a cached
                # host fold for a flat one (disjoint partials, combined
                # on host). Counted once, whoever serves it.
                if batch is not None:
                    batch["sources"] = max(batch.get("sources", 1),
                                           len(runs) + bool(mem_live))
                if spec.group_by or has_expr:
                    if superset or host_only:
                        metrics.count_overlay_scan("grouped", "host", "spec")
                    else:
                        plan = self._plan_overlay_grouped(
                            mem, spec, exact, batch,
                            lambda: self._row_scan(
                                spec, runs, mem_live, pred_split,
                                aggregate=True, mem=mem))
                        if plan is not None:
                            return plan
                elif not eligible:
                    metrics.count_overlay_scan("flat", "host", "spec")
                else:
                    ov = self._overlay(mem)
                    if ov is not None:
                        metrics.count_overlay_scan("flat", "device")
                        return self._plan_overlay_aggregate(ov, spec, exact)
                    metrics.count_overlay_scan("flat", "host", "dirty_set")
            if single_source and runs:
                return ("gather", self._plan_gather(
                    runs[0], spec, pred_split, aggregate=True))
            return ("host", lambda: self._row_scan(
                spec, runs, mem_live, pred_split, aggregate=True, mem=mem))
        page_eligible = (single_source and runs
                         and spec.limit is not None
                         and spec.limit <= host_page.MAX_PAGE_LIMIT
                         and runs[0].crun.max_group_versions <= 1
                         and not superset and not host_only)
        page_pred_items = (host_page.encode_pred_items(self, exact)
                           if page_eligible else None)
        pk = self._point_key(spec)
        if pk is not None:
            # Exact-key read: the bloom-pruned per-key lookup beats both
            # the generic source-merge (~10x) and a device dispatch (the
            # link RTT). The native page server keeps flat-run LIMIT
            # point reads (it emits them in C).
            if page_pred_items is None:
                def point():
                    projection, rows, resume, scanned = \
                        self._point_get_row(spec, mem, pk)
                    return ScanResult(list(projection), rows, resume,
                                      scanned)

                return ("host", point)
        if single_source and runs:
            # Result-bound LIMIT pages on a flat run with host-exact
            # predicates: serve from the host mirror (block-cache analog,
            # storage.host_page) — no device round trip for ~100 rows.
            if page_eligible:
                pred_items = page_pred_items
                if pred_items is not None:
                    # Deferred: scan_batch_async batch-plans all pages
                    # (one vectorized searchsorted per shared structure).
                    return ("page", (runs[0], spec, pred_items))
            return ("gather", self._plan_gather(
                runs[0], spec, pred_split, aggregate=False))
        return ("host", lambda: self._row_scan(
            spec, runs, mem_live, pred_split, aggregate=False, mem=mem))

    def _serve_host_batch(self, specs: list[ScanSpec],
                          deadline=None) -> list[ScanResult]:
        """Serve a whole batch WITHOUT touching the device: candidate
        keys come from the authoritative host ColumnarRuns instead of
        device scans, and the shared merge/materialize loop applies the
        full predicate set host-side — so results are byte-identical to
        the device path (and to the CPU oracle). This is the degraded
        mode behind the circuit breaker."""
        out = []
        for spec in specs:
            if deadline is not None:
                deadline.check("tpu_engine.host_serve")
            out.append(self._host_scan(spec))
        return out

    def _host_scan(self, spec: ScanSpec) -> ScanResult:
        mem = self.memtable
        runs = self._overlapping_runs(spec)
        mem_live = (not mem.is_empty) and \
            mem.has_keys(spec.lower, spec.upper)
        pred_split = self._split_predicates(spec)
        if not spec.is_aggregate:
            pk = self._point_key(spec)
            if pk is not None:
                projection, rows, resume, scanned = \
                    self._point_get_row(spec, mem, pk)
                return ScanResult(list(projection), rows, resume, scanned)
        return self._row_scan(spec, runs, mem_live, pred_split,
                              aggregate=spec.is_aggregate, mem=mem,
                              device_ok=False)

    def _host_candidates(self, trun: TpuRun, spec: ScanSpec):
        """Candidate keys for one run straight from the host ColumnarRun
        (every valid key in range, duplicates adjacent — the merge loop
        dedups and applies predicates). The device-free twin of
        _device_candidates for breaker-degraded serving. Pad rows past
        each block's valid prefix hold b"" keys and MUST be skipped:
        they would both break heapq.merge's sorted-stream contract and
        defeat the merge loop's adjacency dedup."""
        crun = trun.crun
        row_lo = crun.lower_row(spec.lower)
        row_hi = crun.upper_row(spec.upper)
        R = crun.R
        for row in range(row_lo, row_hi):
            b, r = divmod(row, R)
            if r >= crun.blocks[b].num_valid:
                continue
            yield crun.row_keys[b][r]

    def _row_scan(self, spec: ScanSpec, runs, mem_live, pred_split,
                  aggregate: bool, mem: MemTable | None = None,
                  device_ok: bool = True):
        exact, superset, host_only = pred_split
        mem = self.memtable if mem is None else mem
        single_source = len(runs) == 1 and not mem_live
        apply_preds = single_source and device_ok
        pred_sigs, pred_lits = (
            self._pred_sig_and_literals(exact + superset) if apply_preds
            else ((), ()))

        key_streams = [
            self._device_candidates(t, spec, pred_sigs, pred_lits,
                                    apply_preds)
            if device_ok else self._host_candidates(t, spec)
            for t in runs
        ]
        if mem_live or not mem.is_empty:
            key_streams.append(mem.scan_keys(spec.lower, spec.upper))

        import heapq

        candidates = heapq.merge(*key_streams)
        projection = spec.projection or [c.name for c in self.schema.columns]
        agg = Aggregator(spec.aggregates or [], spec.group_by or []) \
            if aggregate else None
        rows: list[tuple] = []
        scanned = 0
        resume = None
        last = None
        for key in candidates:
            if key == last:
                continue
            last = key
            scanned += 1
            versions: list[RowVersion] = []
            for t in runs:
                versions.extend(t.crun.find_versions(key))
            versions.extend(mem.versions(key))
            merged = merge_versions(key, versions, spec.read_ht)
            if not merged.exists:
                continue
            key_vals = self.mat.key_values(key)
            if not self.mat.matches(spec, key_vals, merged):
                continue
            if aggregate:
                agg.add(lambda name: self.mat.value(name, key_vals, merged))
                continue
            rows.append(tuple(
                self.mat.value(name, key_vals, merged) for name in projection))
            if spec.limit is not None and len(rows) >= spec.limit:
                resume = key + b"\x00"
                break
        if aggregate:
            return ScanResult(agg.column_names(), agg.results(), None, scanned)
        return ScanResult(projection, rows, resume, scanned)

    # -- device row-materialization path -------------------------------------
    def _gather_out_cols(self, names):
        from yugabyte_db_tpu.ops import row_gather

        seen = {}
        for name in names:
            cid = self._name_to_id.get(name)
            if cid is None or cid in seen:
                continue  # key column (decoded from the key) or duplicate
            kind = self._kinds[cid]
            planes = 2 if kind in ("i64", "f64", "str") else 1
            # FLOAT round-trips through f32 planes lossily vs the stored
            # python value; STRING/BINARY payloads live host-side — both
            # fetch the original value via the setter row index instead.
            seen[cid] = row_gather.OutCol(cid, planes, kind in ("str", "f32"))
        return tuple(seen.values())

    def _decode_col(self, cid, buf, n, crun, col_offs):
        """Packed buffer columns -> python value list (None for NULL)."""
        kind = self._kinds[cid]
        cmp_off, null_off, idx_off = col_offs[cid]
        null = buf[:n, null_off] != 0
        if kind in ("str", "f32"):
            idxs = buf[:n, idx_off]
            R = crun.R
            out = []
            for i in range(n):
                gi = int(idxs[i])
                if null[i] or gi < 0:
                    out.append(None)
                else:
                    b, r = divmod(gi, R)
                    out.append(crun.row_versions[b][r].columns[cid])
            return out
        if kind == "i32":
            raw = buf[:n, cmp_off].tolist()
        elif kind == "i64":
            raw = P.ordered_planes_to_i64(
                buf[:n, cmp_off], buf[:n, cmp_off + 1]).tolist()
        else:  # f64
            raw = P.ordered_planes_to_f64(
                buf[:n, cmp_off], buf[:n, cmp_off + 1]).tolist()
        dt = self._dtypes[cid]
        if dt == DataType.BOOL:
            return [None if null[i] else bool(raw[i]) for i in range(n)]
        if not null.any():
            return raw
        for i in np.nonzero(null)[0].tolist():
            raw[i] = None
        return raw

    def _pred_host_literals(self, preds):
        """Predicate literals -> (int32 plane list, f32 list), host values."""
        int_lits, f32_lits = [], []
        for p in preds:
            kind = self._pred_kind(p)
            if kind == "code":
                int_lits.append(int(p.value))
            elif kind == "f32":
                f32_lits.append(float(p.value))
            elif kind == "i32":
                int_lits.append(int(p.value))
            elif kind == "i64":
                hi, lo = P.i64_to_ordered_planes(
                    np.array([int(p.value)], dtype=np.int64))
                int_lits += [int(hi[0]), int(lo[0])]
            elif kind == "f64":
                hi, lo = P.f64_to_ordered_planes(
                    np.array([p.value], dtype=np.float64))
                int_lits += [int(hi[0]), int(lo[0])]
            else:
                raw = (p.value.encode("utf-8", "surrogateescape")
                       if isinstance(p.value, str)
                       else bytes(p.value))
                hi, lo = P.varlen_prefix_planes([raw])
                int_lits += [int(hi[0]), int(lo[0])]
        return int_lits, f32_lits

    def _plan_gather(self, trun: TpuRun, spec: ScanSpec, pred_split,
                     aggregate: bool):
        """Single-source scan fully resolved on device: gather dispatches
        pack matched rows' value planes into one int32 matrix; the host
        bulk-decodes. Superset (str/f32) and host-only (key-column, IN)
        predicates are verified on the decoded values — still
        result-proportional work.

        Dispatch shape: a LIMIT page is ONE param-row whose while_loop
        early-exits once the buffer fills; an unbounded scan is one
        param-row per window with the buffer sized to the window (no
        overflow possible). scan_batch() coalesces same-signature rows
        into vmapped dispatches, so whole batches cost one round-trip."""
        from yugabyte_db_tpu.ops import row_gather

        exact, superset, host_only = pred_split
        crun = trun.crun
        # Structural plan cache: a server runs thousands of pages with
        # the same shape (projection/predicates/limit) per batch; the
        # per-spec parts (row bounds, read point, params) are cheap, the
        # structure (out cols, sigs, literal encodings) is not.
        cache_key = None
        if not aggregate:
            try:
                cache_key = (id(trun), spec.limit,
                             tuple(spec.projection or ()),
                             tuple((p.column, p.op, p.value)
                                   for p in spec.predicates))
                cached = self._plan_cache.get(cache_key)
            except TypeError:
                cache_key = cached = None  # unhashable literal: no cache
            if cached is not None:
                ctx = dict(cached)
                return self._finish_plan_gather(trun, spec, ctx)
        projection = spec.projection or [c.name for c in self.schema.columns]
        verify_preds = superset + host_only
        if aggregate:
            from yugabyte_db_tpu.storage.expr import columns_of

            agg = Aggregator(spec.aggregates or [], spec.group_by or [])
            out_names = ([a.column for a in (spec.aggregates or [])
                          if a.column is not None]
                         + [c for a in (spec.aggregates or [])
                            if a.expr is not None
                            for c in columns_of(a.expr)]
                         + list(spec.group_by or []))
        else:
            agg = None
            out_names = list(projection)
        out_names += [p.column for p in verify_preds]
        out_cols = self._gather_out_cols(out_names)
        decode_ids = {self._name_to_id[n] for n in out_names
                      if n in self._name_to_id}
        device_preds = exact + superset
        pred_sigs = self._pred_sigs_only(device_preds)
        int_lits, f32_lits = self._pred_host_literals(device_preds)
        limit = None if aggregate else spec.limit
        K = WINDOW_BLOCKS
        R = crun.R

        ctx = {
            "crun": crun, "trun": trun, "agg": agg,
            "aggregate": aggregate, "projection": projection,
            "verify_preds": verify_preds, "decode_ids": decode_ids,
            "limit": limit, "out_cols": out_cols, "pred_sigs": pred_sigs,
            "int_lits": int_lits, "f32_lits": f32_lits,
            "key_col_pos": {c.name: i
                            for i, c in enumerate(self.schema.key_columns)},
        }
        if limit is None and not device_preds and not verify_preds:
            # Unbounded, unpredicated: one param-row per window, emitted
            # in place (every row is a result row; the host compacts).
            ctx["mode"] = "chunks"
            ctx["M"] = M = K * R
            ctx["sig"] = self._gather_sig(ctx, M, packed=False, K=K)
        else:
            # One definitive round, LIMIT page or selective scan: the
            # while_loop walks windows to the range end, early-exiting
            # once the buffer holds M matches. A LIMIT page (M > limit)
            # never needs a second dispatch — every round is one more
            # synchronous fetch cycle.
            ctx["mode"] = "paged"
            # The output buffer M is the page's device->host transfer
            # cost, so use the smallest bucket that guarantees one-round
            # completion (M >= limit).
            M = 4096
            if limit is not None and not verify_preds:
                M = next((m for m in (104, 256, 1024, 4096) if m >= limit),
                         -(-limit // 8) * 8)
            ctx["M"] = M
            ctx["sig"] = self._gather_sig(ctx, M, K=K)
        if cache_key is not None:
            if len(self._plan_cache) >= 1024:  # distinct literals bound it
                self._plan_cache.pop(next(iter(self._plan_cache)))
            ctx["struct_key"] = cache_key
            self._plan_cache[cache_key] = dict(ctx)
        return self._finish_plan_gather(trun, spec, ctx)

    def _finish_plan_gather(self, trun: TpuRun, spec: ScanSpec, ctx):
        """Per-spec completion of a (possibly cached) gather plan:
        row bounds, read point, param rows."""
        from yugabyte_db_tpu.ops import row_gather

        crun = trun.crun
        read_planes = self._read_plane_ints(spec)
        ctx["read_planes"] = read_planes
        row_lo = crun.lower_row(spec.lower)
        row_hi = crun.upper_row(spec.upper)
        if row_lo >= row_hi:
            return _GatherScan(self, ctx, "paged", [], 0, 0)
        K = ctx["sig"].K
        R = crun.R
        w_first = row_lo // (K * R)
        w_last = (row_hi - 1) // (K * R)
        if ctx["mode"] == "chunks":
            param_rows = [
                row_gather.pack_params(w, w, row_lo, row_hi, read_planes,
                                       ctx["int_lits"], ctx["f32_lits"])
                for w in range(w_first, w_last + 1)
            ]
            return _GatherScan(self, ctx, "chunks", param_rows,
                               w_last, row_hi)
        ip, fp = row_gather.pack_params(
            w_first, w_last, row_lo, row_hi, read_planes,
            ctx["int_lits"], ctx["f32_lits"])
        return _GatherScan(self, ctx, "paged", [(ip, fp)],
                           w_last, row_hi)

    def _read_plane_ints(self, spec: ScanSpec):
        # Tiny keyed cache: servers issue thousands of pages at the same
        # read point and the plane math costs ~µs/page at wire rates.
        cached = self._read_plane_cache.get(spec.read_ht)
        if cached is not None:
            return cached
        r_hi, r_lo = P.scalar_ht_planes(min(spec.read_ht, MAX_HT))
        e_hi, e_lo = P.scalar_ht_planes(min(spec.read_ht, MAX_HT - 1))
        planes = (r_hi, r_lo, e_hi, e_lo)
        if len(self._read_plane_cache) >= 64:
            self._read_plane_cache.pop(next(iter(self._read_plane_cache)))
        self._read_plane_cache[spec.read_ht] = planes
        return planes

    def _gather_sig(self, ctx, M, packed=True, K=WINDOW_BLOCKS):
        from yugabyte_db_tpu.ops import row_gather

        return row_gather.GatherSig(
            B=ctx["trun"].dev.B, R=ctx["crun"].R, K=K, M=M,
            cols=self._col_sigs(), preds=ctx["pred_sigs"], apply_preds=True,
            out_cols=ctx["out_cols"],
            flat=ctx["crun"].max_group_versions <= 1, packed=packed)

    def _emit_fetched(self, ctx, buf, rows):
        """Decode one fetched packed buffer into ctx's sinks.

        Returns (count, emitted_n, hit_limit, last_start). ``last_start``
        is the global row index of the last *consumed* packed row (for
        resume / continuation bounds)."""
        from yugabyte_db_tpu.ops import row_gather

        crun = ctx["crun"]
        M = ctx["M"]
        limit = ctx["limit"]
        verify_preds = ctx["verify_preds"]
        aggregate = ctx["aggregate"]
        agg = ctx["agg"]
        projection = ctx["projection"]
        key_col_pos = ctx["key_col_pos"]
        count = int(buf[M, 0])
        if not ctx["sig"].packed:
            # In-place window: compact matched rows with numpy.
            body = buf[:M]
            buf = body[body[:, 0] >= 0]
            n = buf.shape[0]
        else:
            n = min(count, M)
            buf = buf[:n]
        if n == 0:
            return 0, 0, False, None
        _w, col_offs = row_gather.out_layout(ctx["sig"])
        starts = buf[:n, 0]

        hit_limit = False
        if not verify_preds and not aggregate:
            # Columnar fast path: decode only the rows the page will
            # emit; key columns come from the run's per-column object
            # arrays via one fancy-index (no per-row Python decode).
            n_take = n if limit is None else min(n, limit - len(rows))
            sel = starts[:n_take]
            kv_cols = (crun.key_col_arrays(
                           np.unique(sel // crun.R).tolist())
                       if any(nm in key_col_pos for nm in projection)
                       else None)
            cols_out = []
            for nm in projection:
                if nm in key_col_pos:
                    cols_out.append(kv_cols[key_col_pos[nm]][sel].tolist())
                else:
                    cols_out.append(self._decode_col(
                        self._name_to_id[nm], buf, n_take, crun, col_offs))
            rows.extend(zip(*cols_out))
            hit_limit = limit is not None and len(rows) >= limit
            return count, n, hit_limit, int(starts[n_take - 1])

        colvals = {cid: self._decode_col(cid, buf, n, crun, col_offs)
                   for cid in ctx["decode_ids"]}

        def getter(name, i, _s=starts, _cv=colvals, _kp=key_col_pos):
            if name in _kp:
                return crun.key_vals_at(int(_s[i]))[_kp[name]]
            return _cv[self._name_to_id[name]][i]
        if verify_preds and n:
            # Every fetched row crosses back for host re-verification
            # when the device mask is a superset (string predicates) —
            # yb_scan_host_verify_rows makes that cliff measurable.
            count_host_verify_rows(int(n))
        taken_i = -1
        for i in range(n):
            if verify_preds and not all(
                    p.matches(getter(p.column, i)) for p in verify_preds):
                taken_i = i
                continue
            if aggregate:
                agg.add(lambda nm, _i=i: getter(nm, _i))
                taken_i = i
                continue
            rows.append(tuple(getter(nm, i) for nm in projection))
            taken_i = i
            if limit is not None and len(rows) >= limit:
                hit_limit = True
                break
        last = int(starts[taken_i]) if taken_i >= 0 else None
        return count, n, hit_limit, last

    def _gather_result(self, ctx, rows, scanned, resume):
        if ctx["aggregate"]:
            return ScanResult(ctx["agg"].column_names(), ctx["agg"].results(),
                              None, scanned)
        return ScanResult(ctx["projection"], rows, resume, scanned)

    # (gather round execution lives in _GatherScan below)

    # -- device grouped/expression aggregates --------------------------------
    def _dtype_of(self, name: str):
        cid = self._name_to_id.get(name)
        if cid is None:
            raise ValueError(f"{name} is not a value column")
        return self._dtypes[cid]

    def _encode_factor(self, node):
        """storage.expr tree -> the kernel's static factor tuples."""
        from yugabyte_db_tpu.storage import expr as X

        if isinstance(node, X.Col):
            return ("c", self._name_to_id[node.name])
        if isinstance(node, X.Const):
            return ("k", int(node.value))
        return (node.op, self._encode_factor(node.left),
                self._encode_factor(node.right))

    def _grouped_lower(self, crun, spec: ScanSpec, exact_preds):
        """A GROUP BY / expression-aggregate spec over ``crun`` as
        ops.group_agg takes it: ``(make_sig, int_lits, f32_lits)``, with
        ``make_sig(B, K, flat, lookback=0)`` the signature of a program
        over ``B`` blocks in windows of ``K`` (the run's own for the
        engine's program, a mesh shard's for parallel.sharded's), or None where
        the spec is not device-lowerable."""
        from yugabyte_db_tpu.ops import group_agg
        from yugabyte_db_tpu.storage import expr as X

        group_cols = []
        for name in (spec.group_by or []):
            cid = self._name_to_id.get(name)
            if cid is None:
                return None  # key column: host path
            kind = self._kinds[cid]
            if kind == "str":
                if crun.varlen_max_len.get(cid, 0) > 8:
                    return None  # prefix equality not exact
                planes = 2
            elif kind in ("i64", "f64"):
                planes = 2
            elif kind == "f32":
                return None  # raw-bit equality conflates -0.0/0.0
            else:
                planes = 1
            group_cols.append((cid, planes))

        gaggs = []
        for a in spec.aggregates:
            if a.fn == "count" and a.expr is None:
                cid = self._name_to_id.get(a.column) if a.column else None
                if a.column and cid is None:
                    return None
                gaggs.append(group_agg.GAgg(
                    "count", cid,
                    need_cols=(cid,) if cid is not None else ()))
            elif a.fn == "sum":
                if a.expr is None:
                    cid = self._name_to_id.get(a.column)
                    if cid is None or self._kinds[cid] not in ("i32", "i64"):
                        return None
                    gaggs.append(group_agg.GAgg(
                        "sum_prod", cid,
                        planes=1 if self._kinds[cid] == "i32" else 2,
                        factors=(), need_cols=(cid,)))
                else:
                    lowered = X.lower_product(a.expr, self._dtype_of)
                    if lowered is None:
                        return None
                    base, factors = lowered
                    # (negative factor VALUES are caught at runtime by the
                    # kernel's negs counter -> host fallback)
                    base_cid = self._name_to_id[base]
                    need = [base_cid]
                    for f in factors:
                        for cname in X.columns_of(f):
                            need.append(self._name_to_id[cname])
                    gaggs.append(group_agg.GAgg(
                        "sum_prod", base_cid,
                        planes=1 if self._kinds[base_cid] == "i32" else 2,
                        factors=tuple(self._encode_factor(f)
                                      for f in factors),
                        need_cols=tuple(dict.fromkeys(need))))
            else:
                return None  # min/max/avg: lowered by callers or host

        pred_sigs = self._pred_sigs_only(exact_preds)
        int_lits, f32_lits = self._pred_host_literals(exact_preds)

        def make_sig(B: int, K: int, flat: bool, lookback: int = 0):
            return group_agg.GroupAggSig(
                B=B, R=crun.R, K=K, NB=group_agg.NUM_BUCKETS,
                cols=self._col_sigs(), preds=pred_sigs, apply_preds=True,
                flat=flat, group_cols=tuple(group_cols), aggs=tuple(gaggs),
                lookback=lookback)

        return make_sig, int_lits, f32_lits

    def _grouped_prep(self, trun: TpuRun, spec: ScanSpec, exact_preds):
        """Device GROUP BY / expression aggregates (ops.group_agg) — the
        TPC-H Q1/Q6 path. Host-side planning only: returns None when the
        spec isn't device-lowerable (caller falls back), ("empty", plan)
        for empty ranges, or ("params", (sig, params)) ready for a
        single or vmapped-batch dispatch (``params``: the program's one
        int32 vector, group_agg.pack_params)."""
        from yugabyte_db_tpu.ops import group_agg, lookback_fold, row_gather

        crun = trun.crun
        lowered = self._grouped_lower(crun, spec, exact_preds)
        if lowered is None:
            return None
        make_sig, int_lits, f32_lits = lowered
        row_lo = crun.lower_row(spec.lower)
        row_hi = crun.upper_row(spec.upper)
        R = crun.R
        dev = trun.dev
        K = group_agg.window_blocks(dev.B, R)
        # A run that is not flat resolves its versions by bounded
        # lookback where its largest key group allows (the overlay's
        # mini-run: a tombstone over its base row, 2), by segment ops
        # past the bound: what the build recorded of the run decides.
        sig = make_sig(dev.B, K, crun.max_group_versions <= 1,
                       lookback_fold.bound(crun.max_group_versions))
        # The buckets by dictionary code where the resident leaves of
        # the group columns are dictionaries (a run uploaded encoded, a
        # device flush's) whose values the host holds to read a bucket
        # back through.
        if crun.encoded_arrays() is not None and all(
                cid in crun.enc_dicts for cid, _planes in sig.group_cols):
            sig = group_agg.addressed(sig, dev.arrays)

        if row_lo >= row_hi:
            agg = Aggregator(spec.aggregates, spec.group_by or [])
            empty = ScanResult(agg.column_names(), agg.results(), None, 0)
            return ("empty", ("issued", [], lambda _f: empty))
        w_first = row_lo // (K * R)
        w_last = (row_hi - 1) // (K * R)
        ip, fp = row_gather.pack_params(
            w_first, w_last, row_lo, row_hi, self._read_plane_ints(spec),
            int_lits, f32_lits)
        return ("params", (sig, group_agg.pack_params(sig, ip, fp)))

    def _grouped_finish(self, trun: TpuRun, spec: ScanSpec, exact_preds,
                        sig):
        def fallback():
            return self._row_scan(spec, [trun], False,
                                  (exact_preds, [], []), aggregate=True)

        return lambda vec: self._finish_grouped(trun.crun, spec, sig, vec,
                                                fallback)

    def _dispatch_grouped(self, trun: TpuRun, spec: ScanSpec,
                          exact_preds, prep):
        from yugabyte_db_tpu.ops import group_agg

        sig, params = prep
        fn = group_agg.compiled_grouped(sig)
        dev = trun.dev
        out = fn(dev.arrays, params)
        _count_grouped_dispatch("grouped_aggregate", dev, sig, params, out)
        return ("issued", out,
                self._grouped_finish(trun, spec, exact_preds, sig))

    @staticmethod
    @functools.lru_cache(maxsize=64)
    @compile_contract("batched_grouped", max_compiles=256)
    def _batched_grouped_fn(sig):
        """jit(vmap) of the grouped-aggregate program: N same-signature
        GROUP BY scans (distinct bounds/read points/literals packed in
        the param vectors) in one dispatch."""
        from yugabyte_db_tpu.ops import group_agg

        base = group_agg.compiled_grouped(sig)
        return jitting.jit(jax.vmap(base, in_axes=(None, 0)),
                           "batched_grouped", sig.tag())

    def _plan_grouped_batch(self, items):
        """Batched grouped aggregates (the concurrent TPC-H Q1 shape):
        group prepped specs by (run, signature), stack their packed
        param vectors (padded to the next power of two), one vmapped
        dispatch per group (``[m, P] -> [m, L]``); per-lane finishes
        take their row of the fetched result. items = [(pi, trun, spec,
        exact, (sig, params))]; returns [(pi, outs, finish)]."""
        groups: dict = {}
        out = []
        for pi, trun, spec, exact, (sig, params) in items:
            groups.setdefault((id(trun), sig), []).append(
                (pi, trun, spec, exact, params))
        for (_trun_id, sig), grp in groups.items():
            if len(grp) == 1:
                pi, trun, spec, exact, params = grp[0]
                _tag, outs, fin = self._dispatch_grouped(
                    trun, spec, exact, (sig, params))
                out.append((pi, outs, fin))
                continue
            trun = grp[0][1]
            n = len(grp)
            m = 1 << (n - 1).bit_length()
            params_b = np.zeros((m, grp[0][-1].size), np.int32)
            for i, (*_item, params) in enumerate(grp):
                params_b[i] = params
            fn = self._batched_grouped_fn(sig)
            dev = trun.dev
            res = fn(dev.arrays, params_b)
            _count_grouped_dispatch("batched_grouped", dev, sig, params_b,
                                    res)
            for i, (pi, trun_i, spec, exact, _params) in enumerate(grp):
                fin1 = self._grouped_finish(trun_i, spec, exact, sig)
                out.append((pi, res,
                            lambda f, i=i, fin1=fin1: fin1(f[i])))
        return out


    def _finish_grouped(self, crun, spec, sig, vec, fallback):
        """One program's fetched vector to the scan's result."""
        return self._finish_grouped_programs(spec, [(crun, sig, vec)],
                                             fallback)

    def _finish_grouped_programs(self, spec, programs, fallback):
        """The fetched vectors of the programs that answer ONE scan over
        disjoint key sets, ``[(crun, sig, vec)]``, to its result; a
        partial the host cannot take (``_grouped_partial``) throws the
        programs' answers away and serves the scan again as a host row
        scan: never silently."""
        partials = []
        for crun, sig, vec in programs:
            part = self._grouped_partial(crun, spec, sig, vec)
            if isinstance(part, str):
                metrics.count_grouped_agg_fallback(part)
                return fallback()
            partials.append(part)
        return self._grouped_result(spec, partials)

    def _grouped_partial(self, crun, spec, sig, vec):
        """Decode one grouped program's fetched vector over ``crun``:
        -> (scanned, {group values: [(value, inputs seen) an aggregate]})
        with sums as the digit vectors' integers and a count as (n, 1),
        or the reason the answer cannot be used (``negs``, ``collision``,
        ``decode``). Groups are keyed by VALUE: every run has its own
        dictionary and hash table, so bucket numbers of two runs do not
        correspond."""
        from yugabyte_db_tpu.ops import group_agg

        res = group_agg.unpack(sig, vec)
        NB = sig.NB
        count = np.asarray(res["count"])[:NB]
        live = np.nonzero(count > 0)[0]
        if int(res["negs"]) > 0:
            return "negs"  # negative base values: digits invalid
        if int(res["collisions"]) > 0:
            return "collision"  # two groups, one bucket
        groups = {}
        if not sig.radix:
            keys = np.asarray(res["key"])[:NB]
            reps = np.asarray(res["rep"])[:NB]
        for b in live:
            gvals = (self._decode_codes(crun, sig, int(b)) if sig.radix
                     else self._decode_group(crun, spec, sig, keys[b],
                                             int(reps[b])))
            if gvals is None:
                return "decode"
            aggs = []
            for i, ga in enumerate(sig.aggs):
                if ga.kind == "count":
                    aggs.append((int(np.asarray(res[f"a{i}"])[b]), 1))
                else:
                    digits = np.asarray(res[f"a{i}"])[b]
                    v = sum(int(d) << (16 * k)
                            for k, d in enumerate(digits))
                    aggs.append((v, int(np.asarray(res[f"n{i}"])[b])))
            groups[tuple(gvals)] = aggs
        return int(res["scanned"]), groups

    def _grouped_result(self, spec, partials):
        """Combine the partials of disjoint key sets (one a program),
        order and name: counts and sums add by group value, a group may
        come from one partial alone, and a sum over zero non-null inputs
        in all of them is NULL."""
        scanned = sum(p[0] for p in partials)
        merged: dict = {}
        for _scanned, groups in partials:
            for g, aggs in groups.items():
                have = merged.get(g)
                merged[g] = aggs if have is None else [
                    (v + w, n + m) for (v, n), (w, m) in zip(have, aggs)]
        group_names = list(spec.group_by or [])
        if not merged and not group_names:
            agg = Aggregator(spec.aggregates, [])
            return ScanResult(agg.column_names(), agg.results(), None,
                              scanned)
        rows = [g + tuple(v if n else None for v, n in aggs)
                for g, aggs in merged.items()]
        rows.sort(key=lambda r: tuple(
            (v is None, v) for v in r[:len(group_names)]))
        names = group_names + [a.output_name for a in spec.aggregates]
        return ScanResult(names, rows, None, scanned)

    def _decode_codes(self, crun, sig, bucket: int):
        """The direct form: a bucket's group values, read off the run's
        dictionaries (``crun.enc_dicts``: the sorted FULL values, so
        exact past the 8-byte prefix too) by the bucket's codes; a
        dictionary's last slot is the NULL group."""
        from yugabyte_db_tpu.ops import group_agg

        out = []
        for (cid, _planes), cap, code in zip(
                sig.group_cols, sig.radix,
                group_agg.bucket_codes(sig, bucket)):
            if code == cap - 1:
                out.append(None)
                continue
            raw = crun.enc_dicts[cid][code]
            out.append(raw.decode("utf-8", "surrogateescape")
                       if self._dtypes[cid] == DataType.STRING else raw)
        return out

    def _decode_group(self, crun, spec, sig, key_planes, rep):
        """Bucket key planes (no collision counted) -> python group values.
        Strings decode from the representative row's merged state."""
        from yugabyte_db_tpu.storage.merge import merge_versions

        out = []
        off = 0
        for (cid, planes), name in zip(sig.group_cols,
                                       spec.group_by or []):
            vals = key_planes[off:off + planes]
            null = key_planes[off + planes]
            off += planes + 1
            if null:
                out.append(None)
                continue
            kind = self._kinds[cid]
            dt = self._dtypes[cid]
            if kind == "i32":
                v = int(vals[0])
                out.append(bool(v) if dt == DataType.BOOL else v)
            elif kind == "i64":
                v = int(P.ordered_planes_to_i64(
                    np.array([vals[0]], np.int32),
                    np.array([vals[1]], np.int32))[0])
                out.append(v)
            elif kind == "f64":
                out.append(float(P.ordered_planes_to_f64(
                    np.array([vals[0]], np.int32),
                    np.array([vals[1]], np.int32))[0]))
            else:  # str: exact via the representative row's merged value
                if rep >= crun.total_rows():
                    return None
                b_, r_ = divmod(rep, crun.R)
                key, versions = crun.group_versions(b_, r_)
                merged = merge_versions(key, versions, spec.read_ht)
                out.append(merged.get(cid))
        return out

    @staticmethod
    def _sortkey_bytes(kw_part, ht_hi_part, ht_lo_part):
        """[n, W] i32 key planes + ht planes -> fixed-width big-endian
        byte strings whose memcmp order is (key asc, ht desc) — the
        merge order, as ONE comparison per row."""
        n, W = kw_part.shape
        buf = np.empty((n, W + 2), dtype=np.uint32)
        buf[:, :W] = (kw_part.view(np.uint32)
                      ^ np.uint32(0x80000000)).byteswap()
        buf[:, W] = (~(ht_hi_part.view(np.uint32)
                       ^ np.uint32(0x80000000))).byteswap()
        buf[:, W + 1] = (~(ht_lo_part.view(np.uint32)
                           ^ np.uint32(0x80000000))).byteswap()
        return np.ascontiguousarray(buf).view(
            f"S{4 * (W + 2)}").reshape(n)

    @staticmethod
    def _merge_sorted(items):
        """Stable k-way merge of presorted (indices, sortkeys) pairs via
        a pairwise searchsorted tournament — O(N log K) comparisons, all
        vectorized, replacing a full np.lexsort of the union (measured
        ~6x cheaper at 500K rows; each run is already sorted)."""
        while len(items) > 1:
            nxt = []
            for i in range(0, len(items) - 1, 2):
                a_idx, a_keys = items[i]
                b_idx, b_keys = items[i + 1]
                # Stability: ties keep earlier-run rows first.
                a_dst = np.arange(a_keys.size, dtype=np.int64) + \
                    np.searchsorted(b_keys, a_keys, side="left")
                b_dst = np.arange(b_keys.size, dtype=np.int64) + \
                    np.searchsorted(a_keys, b_keys, side="right")
                out_n = a_keys.size + b_keys.size
                out_keys = np.empty(out_n, dtype=a_keys.dtype)
                out_idx = np.empty(out_n, dtype=np.int64)
                out_keys[a_dst] = a_keys
                out_keys[b_dst] = b_keys
                out_idx[a_dst] = a_idx
                out_idx[b_dst] = b_idx
                nxt.append((out_idx, out_keys))
            if len(items) % 2:
                nxt.append(items[-1])
            items = nxt
        return items[0][0]

    # -- delta overlay (masked primary + host-folded dirty set) -------------
    # Dirty-index buckets: the scatter that clears dirty rows from the
    # primary's valid plane pads its index vector to one of these sizes
    # so at most a handful of programs ever compile.
    _MASK_BUCKETS = (256, 1024, 4096, 16384, 65536, 262144)

    @staticmethod
    @compile_contract("scatter_invalid", max_compiles=64)
    @jax.jit
    def scatter_invalid(valid, idx):
        flat = valid.reshape(-1)
        return flat.at[idx].set(False, mode="drop").reshape(valid.shape)

    @staticmethod
    @compile_contract("scatter_invalid_bits", max_compiles=64)
    @jax.jit
    def scatter_invalid_bits(bw, widx, keep):
        """Bit-packed valid plane (--tpu_plane_encoding): clear the
        dirty rows' bits in the packed words themselves, ``bw[widx] &=
        keep`` (``widx``: the flat numbers of the words that hold a
        dirty row, each once, padded out of range; ``keep``: the word's
        mask with those rows' bits 0). The masked plane stays a "bits"
        leaf, so a program over the masked primary has the signature of
        the run's own (ops.group_agg's packed form, the very programs a
        single-source scan compiled)."""
        flat = bw.reshape(-1)
        cur = flat.at[widx].get(mode="fill", fill_value=0)
        return flat.at[widx].set(cur & keep, mode="drop").reshape(bw.shape)

    def _overlay(self, mem):
        """The cached delta-overlay state for the current engine content:
        (masked_primary, dirty rows, per-read-point partial cache).

        Multi-source reads (overlapping runs and/or a live memtable)
        previously merged EVERY key on host — correct, but ~100x slower
        than a device scan. The overlay keeps the DEVICE scanning only
        the primary run, with dirty keys' rows cleared from its valid
        plane, and folds the (small) dirty set on host:

        - dirty keys = every key present in any non-primary source, with
          their FULL version sets merged across all sources (primary
          included) and their key values pre-decoded;
        - masked primary = the primary run's device arrays with dirty
          rows scatter-cleared from ``valid`` — the scatter ships a
          bucketed index vector (KBs), never a full mask plane;
        - scans = one already-compiled flat dispatch over the masked
          primary + a cached host fold of the dirty rows (exact MVCC
          merge + predicates at the spec's read point); a grouped
          aggregate folds the dirty rows on the device instead, as a
          second dispatch over the state's mini-run
          (_plan_overlay_grouped, _overlay_delta_run).

        Nothing here builds a device run or compiles a multi-version
        kernel (the mini-run is built by the first grouped aggregate
        that needs it), so the first post-write flat scan pays only the
        dirty-set collection.
        Rebuilds amortize two ways: (run-set identity, memtable version
        counter) keying makes the steady-state scan a pure cache hit,
        and when only the version counter moved the state is advanced
        INCREMENTALLY (_overlay_apply_delta) by the memtable's
        versions_since() log instead of re-collecting every dirty key.
        Reference contract: IntentAwareIterator's multi-source merge
        (src/yb/docdb/intent_aware_iterator.h:81) and the
        immutable-memtable flush handoff (rocksdb/db/flush_job.cc:
        reads never stall on flush). Returns None (host fallback) when
        the dirty set approaches the primary's size — at that shape a
        compaction is the real answer."""
        runs = list(self.runs)
        if not runs:
            return None
        cache = self._overlay_cache
        if cache is not None:
            c_runs, c_mem, c_ver, state = cache
            if c_runs == runs and c_mem is mem:
                if c_ver == mem.num_versions:
                    return state
                if state is not None and mem.num_versions > c_ver:
                    with self._overlay_build_span("delta") as sp:
                        inc = self._overlay_apply_delta(state, mem, c_ver)
                        if inc is not None and inc is not _OVERLAY_REBUILD:
                            self._note_overlay_built(sp, inc)
                    if inc is not _OVERLAY_REBUILD:
                        ver = (inc.mem_count if inc is not None
                               else mem.num_versions)
                        self._cache_overlay(runs, mem, inc, ver)
                        return inc
        with self._overlay_build_span("full") as sp:
            state = self._overlay_build(runs, mem)
            if state is not None:
                self._note_overlay_built(sp, state)
        return state

    @staticmethod
    def _overlay_build_span(how: str):
        """Span ``engine.overlay.build`` -> ``yb_overlay_build_us{how}``."""
        return trace.span("engine.overlay.build",
                          metrics.overlay_build_histogram(how), how=how)

    @staticmethod
    def _note_overlay_built(sp, state: _OverlayState) -> None:
        versions = sum(len(e[1]) for e in state.rows)
        sp.labels.update(dirty_keys=len(state.rows),
                         primary_rows_masked=int(state.idx.size),
                         delta_versions=versions)
        metrics.set_overlay_size(len(state.rows), versions)

    def _overlay_build(self, runs, mem):
        """The full build: every dirty key collected and merged over all
        sources, the primary masked (``_overlay``)."""
        primary = max(runs, key=lambda t: t.crun.total_rows())
        deltas = [t for t in runs if t is not primary]

        # Snapshot the counter BEFORE collecting: rows racing in during
        # collection are re-applied by the next delta (idempotent — the
        # incremental path dedups versions by (ht, write_id)).
        ver0 = mem.num_versions
        dirty: dict[bytes, list] = {}
        for t in deltas:
            for key, versions in t.crun.iter_entries():
                dirty.setdefault(key, []).extend(versions)
        for key in mem.scan_keys(b"", b""):
            dirty.setdefault(key, []).extend(mem.versions(key))
        state = None
        if dirty and len(dirty) * 2 <= max(primary.crun.total_rows(), 64):
            primary.pin("high")
            try:
                rows_out = []
                idx_parts = []
                crun = primary.crun
                R = crun.R
                total = crun.total_rows()
                for key in sorted(dirty):
                    versions = list(dirty[key])
                    # Locate the key's primary versions with ONE bisect
                    # and read forward (find_versions would bisect again).
                    start = crun.lower_row(key)
                    n = 0
                    if start < total:
                        b, r = divmod(start, R)
                        meta = crun.blocks[b]
                        rk = crun.row_keys[b]
                        rv = crun.row_versions[b]
                        while r + n < meta.num_valid and rk[r + n] == key:
                            versions.append(rv[r + n])
                            n += 1
                    if n:
                        idx_parts.append(
                            np.arange(start, start + n, dtype=np.int32))
                    if len(versions) > 1:
                        versions.sort(key=lambda x: (x.ht, x.write_id),
                                      reverse=True)
                    # Key values decode lazily at first host fold.
                    rows_out.append([key, versions, None])
                idx = (np.concatenate(idx_parts) if idx_parts
                       else np.zeros(0, np.int32))
                masked_primary = self._masked_primary(primary, idx)
                state = _OverlayState(
                    masked_primary, rows_out,
                    [e[0] for e in rows_out],
                    {e[0]: e for e in rows_out}, idx, ver0)
                self._cache_overlay(runs, mem, state, ver0)
            finally:
                primary.unpin()
        else:
            self._cache_overlay(runs, mem, None, mem.num_versions)
        return state

    def _masked_primary(self, primary: TpuRun, idx) -> _MaskedRun:
        """The primary's device arrays with ``idx`` rows cleared from the
        valid plane, which keeps the leaf kind it has (a plain bool
        plane, or packed words); the index vector pads to a
        _MASK_BUCKETS size so at most a handful of scatter programs ever
        compile."""
        vleaf = primary.dev.arrays["valid"]
        if encodings.leaf_kind(vleaf) == "bits":
            bw = vleaf["bits"]["bw"]
            # A row's bit: word row // 32 of the flat plane (a block's R
            # is a multiple of 32), bit row % 32.
            words, at = np.unique(idx >> 5, return_inverse=True)
            keep = np.full(words.size, -1, np.int32)
            np.bitwise_and.at(
                keep, at,
                ~(np.int32(1) << (idx & 31).astype(np.int32)))
            bucket = next((b for b in self._MASK_BUCKETS
                           if b >= words.size), words.size)
            # Pad with an out-of-range word; mode="drop" discards it.
            pwords = np.full(bucket, bw.size, dtype=np.int32)
            pwords[:words.size] = words
            pkeep = np.full(bucket, -1, dtype=np.int32)
            pkeep[:words.size] = keep
            masked_valid = {"bits": {"bw": (
                TpuStorageEngine.scatter_invalid_bits(
                    bw, jnp.asarray(pwords), jnp.asarray(pkeep)))}}
        else:
            bucket = next((b for b in self._MASK_BUCKETS
                           if b >= idx.size), idx.size)
            # Pad with an out-of-range index; mode="drop" discards it.
            pidx = np.full(bucket, vleaf.size, dtype=np.int32)
            pidx[:idx.size] = idx
            masked_valid = TpuStorageEngine.scatter_invalid(
                vleaf, jnp.asarray(pidx))
        masked_arrays = dict(primary.dev.arrays, valid=masked_valid)
        return _MaskedRun(primary, masked_arrays)

    def _cache_overlay(self, runs, mem, state, ver) -> None:
        """Publish an overlay cache entry, moving the primary-run pin
        and the masked-valid residency accounting with it."""
        self._retire_overlay_delta(state)
        new_primary = state.masked.source if state is not None else None
        old = self._overlay_pinned
        if old is not new_primary:
            if new_primary is not None:
                new_primary.pin("high")
            if old is not None:
                old.unpin()
            self._overlay_pinned = new_primary
            if self._overlay_ext_key is not None:
                hbm_cache().invalidate(self._overlay_ext_key)
                self._overlay_ext_key = None
            if state is not None:
                self._overlay_ext_key = hbm_cache().add_external(
                    None,
                    device_nbytes(state.masked.dev.arrays["valid"]),
                    self.device_tracker, "overlay_mask",
                    device=device_label(state.masked.source.jax_device))
        self._overlay_cache = (runs, mem, ver, state)

    def _overlay_apply_delta(self, state: _OverlayState, mem,
                             since: int):
        """Advance the cached overlay by the memtable versions applied
        after index ``since`` (copy-on-write: shared row entries are
        replaced, never mutated, so in-flight readers of the old state
        stay consistent). Returns the new state, None when the dirty
        set outgrew the overlay shape (host fallback, as in the full
        build), or _OVERLAY_REBUILD when the memtable has no delta log.

        Steady-state cost is O(delta): one bisect per touched key plus
        one re-scatter only when new primary rows need clearing — this
        is what keeps a wave of writes from costing a full overlay
        rebuild."""
        delta = getattr(mem, "versions_since", lambda _n: None)(since)
        if delta is None:
            return _OVERLAY_REBUILD
        if not delta:
            return state
        changed: dict[bytes, list] = {}
        for r in delta:
            changed.setdefault(r.key, []).append(r)
        primary = state.masked.source
        crun = primary.crun
        n_new = sum(1 for k in changed if k not in state.by_key)
        if (len(state.rows) + n_new) * 2 > max(crun.total_rows(), 64):
            return None
        rows = list(state.rows)
        by_key = dict(state.by_key)
        idx_parts = [state.idx]
        added: list = []
        R = crun.R
        total = crun.total_rows()
        for key in sorted(changed):
            add = changed[key]
            old_entry = by_key.get(key)
            if old_entry is not None:
                # Re-applied versions (a build racing a write) dedup by
                # the version identity the merge sorts on.
                seen = {(v.ht, v.write_id) for v in old_entry[1]}
                versions = old_entry[1] + [
                    v for v in add if (v.ht, v.write_id) not in seen]
                if len(versions) > 1:
                    versions.sort(key=lambda x: (x.ht, x.write_id),
                                  reverse=True)
                entry = [key, versions, old_entry[2]]
                rows[bisect.bisect_left(state.keys, key)] = entry
                by_key[key] = entry
                continue
            versions = list(add)
            start = crun.lower_row(key)
            n = 0
            if start < total:
                b, r = divmod(start, R)
                meta = crun.blocks[b]
                rk = crun.row_keys[b]
                rv = crun.row_versions[b]
                while r + n < meta.num_valid and rk[r + n] == key:
                    versions.append(rv[r + n])
                    n += 1
            if n:
                idx_parts.append(
                    np.arange(start, start + n, dtype=np.int32))
            if len(versions) > 1:
                versions.sort(key=lambda x: (x.ht, x.write_id),
                              reverse=True)
            entry = [key, versions, None]
            by_key[key] = entry
            added.append(entry)  # sorted: changed iterates in key order
        if added:
            # One linear merge of the two sorted lists (inserting one at
            # a time would memmove the tail per new key).
            merged_rows = []
            i = j = 0
            while i < len(rows) and j < len(added):
                if rows[i][0] <= added[j][0]:
                    merged_rows.append(rows[i])
                    i += 1
                else:
                    merged_rows.append(added[j])
                    j += 1
            merged_rows.extend(rows[i:])
            merged_rows.extend(added[j:])
            rows = merged_rows
        keys = [e[0] for e in rows] if added else state.keys
        if len(idx_parts) > 1:
            idx = np.concatenate(idx_parts)
            masked = self._masked_primary(primary, idx)
        else:
            idx = state.idx
            masked = state.masked
        return _OverlayState(masked, rows, keys, by_key, idx,
                             since + len(delta))

    def _retire_overlay_delta(self, keep) -> None:
        """The cache is about to let its state go (for ``keep``, or for
        nothing): retire that state's mini-run, its device bytes back
        with the tracker once no scan in flight pins it."""
        cache = self._overlay_cache
        old = cache[3] if cache is not None else None
        if old is None or old is keep:
            return
        with self._overlay_delta_lock:
            old.dropped = True
            if old.delta is not None:
                old.delta.retire()
                old.delta = None

    def _overlay_delta_run(self, state: _OverlayState):
        """The dirty keys' full version lists as ONE small multi-version
        run on the device, PINNED: -> (the TpuRun, its DeviceRun); the
        caller unpins. Built once a state, by the first grouped
        aggregate that needs it (a flat aggregate folds the dirty rows
        on the host and never asks), uploaded through the residency
        manager as any run (label ``overlay_delta``), with plain planes
        (``ColumnarRun.plain_planes``) and its block axis padded to its
        own power of two, so the programs over it have one signature a
        size class. Per-row Python belongs here, to the BUILD; a scan
        folds the run with a device program. The pin is taken under the
        lock that ``_retire_overlay_delta`` retires under, so a state the
        cache lets go at any moment leaves this scan a registered,
        accounted run until its unpin."""
        with self._overlay_delta_lock:
            delta = state.delta
            if delta is None:
                with self._overlay_build_span("mini_run") as sp:
                    crun = ColumnarRun.build(
                        self.schema, [(e[0], e[1]) for e in state.rows],
                        self.rows_per_block, plain_planes=True)
                    pad = 1 << (crun.B - 1).bit_length()
                    delta = TpuRun(crun, self.device_tracker,
                                   pad_blocks=pad, label="overlay_delta")
                    sp.labels.update(dirty_keys=len(state.rows),
                                     delta_versions=crun.num_versions,
                                     blocks=pad)
                if not state.dropped:
                    state.delta = delta
            dev = delta.pin("high")
            if state.dropped:
                # A reader of a state the cache already let go: the run
                # serves this scan alone and goes with its pin.
                delta.retire()
        return delta, dev

    def _plan_overlay_grouped(self, mem, spec: ScanSpec, exact_preds,
                              batch, host_scan):
        """A GROUP BY / expression aggregate over several sources as two
        dispatches of the grouped program and ONE fetch
        (-> ("overlay_deferred", dispatch), which the batch calls in its
        dispatch part: -> (outs, finish(fetched))): over the masked
        primary (as flat as the run is: with its packed valid plane kept
        packed, the program a single-source scan compiled) and over the
        overlay's mini-run (the MVCC resolve at the spec's read point
        inside the program, so a read point before a delete still sees
        the row). The two cover disjoint key sets; their partials are
        combined by group value (``_grouped_result``). None where the
        overlay does not apply (the dirty set passed half the primary)
        or the spec cannot be lowered: the caller's host row scan, which
        is also what a partial the host cannot take falls back to
        (``yb_grouped_agg_fallbacks``)."""
        from yugabyte_db_tpu.ops import group_agg
        from yugabyte_db_tpu.utils.sync_point import sync_point

        ov = self._overlay(mem)
        sync_point("tpu_engine:overlay_grouped:state_taken")
        if ov is None:
            metrics.count_overlay_scan("grouped", "host", "dirty_set")
            return None
        prep = self._grouped_prep(ov.masked, spec, exact_preds)
        if prep is None:
            metrics.count_overlay_scan("grouped", "host", "spec")
            return None
        delta, delta_dev = self._overlay_delta_run(ov)
        if batch is not None:
            batch["pins"].append(delta)
        try:
            prep_d = self._grouped_prep(delta, spec, exact_preds)
        finally:
            if batch is None:
                delta.unpin()
        if prep_d is None:
            metrics.count_overlay_scan("grouped", "host", "spec")
            return None

        def dispatch():
            outs, decode = [], []
            for dev, crun, entry, (kind, payload) in (
                    (ov.masked.dev, ov.masked.crun, "grouped_aggregate",
                     prep),
                    (delta_dev, delta.crun, "overlay_delta_aggregate",
                     prep_d)):
                if kind == "empty":
                    continue
                sig, params = payload
                out = group_agg.compiled_grouped(sig)(dev.arrays, params)
                _count_grouped_dispatch(entry, dev, sig, params, out)
                outs.append(out)
                decode.append((crun, sig))
            metrics.count_overlay_scan("grouped", "device")
            return outs, lambda fetched: self._finish_grouped_programs(
                spec, [(crun, sig, vec)
                       for (crun, sig), vec in zip(decode, fetched)],
                host_scan)

        return ("overlay_deferred", dispatch)

    def _overlay_host_partial(self, ov, spec: ScanSpec):
        """Exact host fold of the dirty rows at spec's read point:
        -> (scanned, [per-agg (n, value)]) where value is the finalized
        partial (sum / min / max; count rides n). Cached per (read
        point, predicates, aggregates) on the overlay state — the
        steady-state scan shape reuses it for free."""
        rows_out = ov.rows
        cache = ov.partial
        try:
            key = (self._read_plane_ints(spec), spec.lower, spec.upper,
                   tuple((p.column, p.op, p.value)
                         for p in spec.predicates),
                   tuple((a.fn, a.column) for a in spec.aggregates))
        except TypeError:
            key = None
        if key is not None:
            hit = cache.get(key)
            if hit is not None:
                return hit
        scanned = 0
        parts = [[0, None] for _ in spec.aggregates]
        # Key columns decode only when something references one (the
        # usual aggregate shape touches value columns only).
        needs_keys = any(
            p.column in self._key_col_names for p in spec.predicates
        ) or any(a.column in self._key_col_names
                 for a in spec.aggregates if a.column)
        for entry in rows_out:
            rkey, versions, key_vals = entry
            if rkey < spec.lower or (spec.upper and rkey >= spec.upper):
                continue
            merged = merge_versions(rkey, versions, spec.read_ht)
            if not merged.exists:
                continue
            scanned += 1
            if needs_keys and key_vals is None:
                key_vals = entry[2] = self.mat.key_values(rkey)
            if not self.mat.matches(spec, key_vals, merged):
                continue
            for pi, a in enumerate(spec.aggregates):
                if a.column is None:
                    parts[pi][0] += 1
                    continue
                v = self.mat.value(a.column, key_vals, merged)
                if v is None:
                    continue
                p = parts[pi]
                p[0] += 1
                if a.fn in ("sum", "avg"):
                    p[1] = v if p[1] is None else p[1] + v
                elif a.fn == "min":
                    p[1] = v if p[1] is None else min(p[1], v)
                elif a.fn == "max":
                    p[1] = v if p[1] is None else max(p[1], v)
        result = (scanned, [tuple(p) for p in parts])
        if key is not None:
            if len(cache) >= 8:
                cache.pop(next(iter(cache)))
            cache[key] = result
        return result

    def _plan_overlay_aggregate(self, ov, spec: ScanSpec, exact_preds):
        """One device aggregate over the masked primary (flat,
        already-compiled program) + the cached host fold of the dirty
        rows, combined exactly at the finalized level (disjoint key
        sets)."""
        masked_primary = ov.masked
        dev_aggs, lowering = agg_fold.lower_aggs(
            spec.aggregates, self._name_to_id, self._kinds)
        o1, f1 = self._plan_device_aggregate(masked_primary, spec,
                                             exact_preds, raw=True)

        def pre_fetch():
            # Runs while the device outputs stream host-ward: the host
            # fold overlaps the link fetch instead of following it.
            self._overlay_host_partial(ov, spec)

        def finish(fetched):
            acc, s1 = f1(fetched)
            h_scanned, h_parts = self._overlay_host_partial(ov, spec)
            out_row, names = [], []
            for pi, (a, (fn_name, di)) in enumerate(
                    zip(spec.aggregates, lowering)):
                names.append(f"{a.fn}({a.column or '*'})")
                ag = dev_aggs[di]
                h_n, h_v = h_parts[pi]
                if a.fn == "count":
                    dv = agg_fold.finalize(ag, acc[di], "count")
                    out_row.append(int(dv) + h_n)
                    continue
                dev_n = int(acc[di].get("n", 0))
                if a.fn in ("sum", "avg"):
                    ds = agg_fold.finalize(ag, acc[di], "sum")
                    total = None
                    if ds is not None or h_v is not None:
                        total = (ds or 0) + (h_v or 0)
                    if a.fn == "sum":
                        out_row.append(total)
                    else:
                        n = dev_n + h_n
                        out_row.append(total / n if n else None)
                    continue
                dv = agg_fold.finalize(ag, acc[di], a.fn)
                vals = [v for v in (dv, h_v) if v is not None]
                if not vals:
                    out_row.append(None)
                elif a.fn == "min":
                    out_row.append(min(vals))
                else:
                    out_row.append(max(vals))
            return ScanResult(names, [tuple(out_row)], None,
                              s1 + h_scanned)

        return ("issued", o1, finish, pre_fetch)

    # -- device aggregate path ---------------------------------------------
    def _device_agg_prep(self, trun: TpuRun, spec: ScanSpec, exact_preds):
        """Host-side planning shared by the single-spec and batched
        device-aggregate paths: compile signature, fold route, scan
        bounds, read planes (host ints), and HOST predicate literals
        (so batched dispatch stacks them into one transfer)."""
        from yugabyte_db_tpu.ops import flat_fold, lookback_fold, seg_fold

        crun = trun.crun
        row_lo = crun.lower_row(spec.lower)
        row_hi = crun.upper_row(spec.upper)
        sigs, lits = self._pred_sig_and_literals(
            exact_preds, literal_fn=agg_fold.pred_literal_host)
        dev_aggs, lowering = agg_fold.lower_aggs(
            spec.aggregates, self._name_to_id, self._kinds)
        R = crun.R
        K = agg_fold.safe_window_blocks(R, agg_fold.FULL_WINDOW_BLOCKS)
        flat = crun.max_group_versions <= 1
        # lookback rides in the compile signature: set it ONLY when the
        # lookback route can serve this run (otherwise every distinct
        # version count would recompile the byte-identical fallbacks),
        # rounded up to a power of two (lookback_fold.bound).
        sig = dscan.ScanSig(
            B=trun.dev.B, R=R, K=K, cols=self._col_sigs(),
            preds=tuple(sigs), aggs=dev_aggs, apply_preds=True, flat=flat,
            lookback=lookback_fold.bound(crun.max_group_versions))
        if flat_fold.supports(sig):
            route = "flat"
        elif lookback_fold.supports(sig):
            route = "lookback"
        elif seg_fold.supports(sig):
            route = "seg"
        else:
            route = "full"
        planes = self._read_plane_ints(spec)
        return (sig, route, row_lo, row_hi, planes, tuple(lits),
                dev_aggs, lowering)

    @staticmethod
    def _agg_route_fn(route: str, sig):
        from yugabyte_db_tpu.ops import flat_fold, lookback_fold, seg_fold

        if route == "flat":
            # Flat run: one fused full-array program (bandwidth-roofline;
            # ops.flat_fold) instead of the serialized window fold.
            return flat_fold.compiled_flat_aggregate(sig)
        if route == "lookback":
            # Bounded version counts: shifted-mask resolve at the flat
            # path's memory roofline (ops.lookback_fold).
            return lookback_fold.compiled_lookback_aggregate(sig)
        if route == "seg":
            # Multi-version run: fused segmented-scan resolve
            # (ops.seg_fold) — same results as the windowed fold.
            return seg_fold.compiled_seg_aggregate(sig)
        return agg_fold.compiled_full_aggregate(sig)

    @staticmethod
    def _agg_finish(spec: ScanSpec, dev_aggs, lowering, raw: bool):
        def finish(f):
            iv, fv = f
            acc, scanned = agg_fold.unpack(dev_aggs, iv, fv)
            if raw:
                return acc, scanned
            out_row, names = [], []
            for a, (fn_name, di) in zip(spec.aggregates, lowering):
                names.append(f"{a.fn}({a.column or '*'})")
                out_row.append(agg_fold.finalize(dev_aggs[di], acc[di],
                                                 fn_name))
            return ScanResult(names, [tuple(out_row)], None, scanned)

        return finish

    def _plan_device_aggregate(self, trun: TpuRun, spec: ScanSpec,
                               exact_preds, raw: bool = False):
        """Single-dispatch full-run aggregate: the device fori_loops every
        window and returns two packed vectors (ops.agg_fold) — one dispatch
        plus two small transfers per scan, because the host link pays
        per-transfer latency (see ops/agg_fold.py docstring)."""
        prep = self._device_agg_prep(trun, spec, exact_preds)
        return self._dispatch_prepped(trun, spec, prep, raw=raw)

    @staticmethod
    @functools.lru_cache(maxsize=64)
    @compile_contract("batched_agg", max_compiles=256)
    def _batched_agg_fn(route: str, sig):
        """jit(vmap) of the per-spec aggregate program: N same-signature
        scans (distinct bounds / read points / predicate literals) in
        ONE dispatch. The run planes broadcast; everything else maps.
        Distinct batch sizes retrace inside the jit cache."""
        base = TpuStorageEngine._agg_route_fn(route, sig)
        return jitting.jit(
            jax.vmap(base, in_axes=(None, 0, 0, 0, 0, 0, 0, 0)),
            "batched_agg", f"{route}_{sig.tag()}")

    def _plan_device_aggregate_batch(self, items):
        """Batched device aggregates: group deferred specs by
        (run, signature, literal shapes) and dispatch each group as one
        vmapped program — the per-dispatch host cost and the per-scan
        transfer latency amortize across the whole group (the tserver
        shape: many concurrent aggregate queries differing only in
        bounds/literals). Returns [(pi, outs, finish)] entries for the
        async batch. Reference capability analog: doc-op batching in
        src/yb/docdb/doc_operation.cc (one RocksDB pass serving many
        ops) — here one DEVICE pass serving many scans."""
        preps = []
        for pi, trun, spec, exact in items:
            preps.append((pi, trun, spec,
                          self._device_agg_prep(trun, spec, exact)))
        groups: dict = {}
        for p in preps:
            (pi, trun, spec,
             (sig, route, row_lo, row_hi, planes, lits, da, lo)) = p
            lit_shapes = tuple(l.shape for l in lits)
            if route == "full":
                # The windowed fold's traced fori bounds don't vmap
                # cheaply; keep it per-spec.
                key = ("solo", pi)
            else:
                key = (id(trun), sig, route, lit_shapes)
            groups.setdefault(key, []).append(p)
        out = []
        for key, grp in groups.items():
            if key[0] == "solo" or len(grp) == 1:
                for pi, trun, spec, prep in grp:
                    outs, fin = self._dispatch_prepped(trun, spec, prep)
                    out.append((pi, outs, fin))
                continue
            _pi0, trun, _s0, (sig, route, *_rest) = grp[0]
            n = len(grp)
            # Pad lanes to the next power of two so drifting batch sizes
            # share at most log2(max) compiled variants (the same trick
            # the lookback signature uses). Pad lanes scan nothing
            # (row_lo == row_hi == 0) and their outputs are ignored.
            m = 1 << (n - 1).bit_length()
            row_lo_b = np.zeros(m, np.int32)
            row_hi_b = np.zeros(m, np.int32)
            planes_b = np.zeros((4, m), np.int32)
            lits0 = grp[0][3][5]
            lits_b = [np.zeros((m,) + l.shape, l.dtype) for l in lits0]
            for i, (_pi, _t, _s, (_sig, _r, rlo, rhi, pl, lits,
                                  _da, _lo)) in enumerate(grp):
                row_lo_b[i] = rlo
                row_hi_b[i] = rhi
                planes_b[:, i] = pl
                for k, l in enumerate(lits):
                    lits_b[k][i] = l
            fn = self._batched_agg_fn(route, sig)
            dev = trun.dev
            args = (row_lo_b, row_hi_b, *planes_b, tuple(lits_b))
            ivec, fvec = fn(dev.arrays, *args)
            _count_dispatch("batched_agg", dev, sig, args, (ivec, fvec))
            for i, (pi, _t, spec, (_sig, _r, _rlo, _rhi, _pl, _lits,
                                   dev_aggs, lowering)) in enumerate(grp):
                fin1 = self._agg_finish(spec, dev_aggs, lowering,
                                        raw=False)
                out.append((pi, [ivec, fvec],
                            lambda f, i=i, fin1=fin1:
                            fin1((f[0][i], f[1][i]))))
        return out

    def _dispatch_prepped(self, trun: TpuRun, spec: ScanSpec, prep,
                          raw: bool = False):
        """Dispatch one prepped aggregate (the per-spec path and the
        solo leg of the batched planner) without re-running host
        planning."""
        (sig, route, row_lo, row_hi, planes, lits,
         dev_aggs, lowering) = prep
        r_hi_, r_lo_, e_hi_, e_lo_ = (jnp.int32(v) for v in planes)
        pred_lits = tuple(jnp.asarray(l) for l in lits)
        fn = self._agg_route_fn(route, sig)
        dev = trun.dev
        windows = ()
        if route == "full":
            W = dev.B // sig.K
            windows = tuple(jnp.int32(w) for w in agg_fold.window_bounds(
                row_lo, row_hi, sig.R, sig.K, W))
        args = (jnp.int32(row_lo), jnp.int32(row_hi), *windows,
                r_hi_, r_lo_, e_hi_, e_lo_, pred_lits)
        ivec, fvec = fn(dev.arrays, *args)
        _count_dispatch(route + "_aggregate", dev, sig, args, (ivec, fvec))
        return [ivec, fvec], self._agg_finish(spec, dev_aggs, lowering,
                                              raw=raw)


class _AsyncBatch:
    """An in-flight scan_batch: round-1 device work is issued and its
    outputs are streaming host-ward; .finish() consumes them (one fetch
    cycle worst case, free when the copies already landed), runs any host
    fallback scans, and drives the (rare) continuation rounds."""

    def __init__(self, eng, results, host_plans, issued_outs, gathers,
                 states, pending, dispatches, pages=(), pre_work=(),
                 pins=(), specs=(), deadline=None, route="mixed",
                 sources=1):
        self.route = route        # _batch_route of the plans
        self.sources = sources    # the most sources one of its specs read
        self._fetch_ns = 0        # phase "wait_fetch", summed over rounds
        self.eng = eng
        self.results = results
        self.host_plans = host_plans
        self.issued_outs = issued_outs
        self.gathers = gathers
        self.states = states
        self.pending = pending
        self.dispatches = dispatches
        self.pages = list(pages)
        self.pre_work = list(pre_work)
        self.pins = list(pins)
        self.specs = list(specs)
        self.deadline = deadline
        self._done = False

    def _release_pins(self) -> None:
        pins, self.pins = self.pins, []
        for trun in pins:
            trun.unpin()

    def __del__(self):
        # An abandoned batch (never finished) must still release its
        # residency pins, or the cache leaks protected bytes.
        try:
            self._release_pins()
        except Exception as e:  # noqa: BLE001 — interpreter teardown
            count_swallowed("tpu_engine.async_batch_del", e)

    def finish(self) -> list[ScanResult]:
        if self._done:
            return self.results
        wall, t0 = time.time_ns(), time.perf_counter_ns()
        try:
            return self._finish_or_reserve()
        finally:
            # Once a batch: "wait_fetch" is the device_get calls (device
            # queue + run + transfer, as the host feels it), "finish"
            # the rest (pre_work, host plans, pages, decode, rounds).
            total = time.perf_counter_ns() - t0
            _record_phase("wait_fetch", self.route, wall, self._fetch_ns)
            _record_phase("finish", self.route, wall,
                          total - self._fetch_ns)

    def _finish_or_reserve(self) -> list[ScanResult]:
        try:
            out = self._finish()
        except DEVICE_FAULT_TYPES as e:
            # Mid-flight device fault: release the pins, report to the
            # breaker, and re-serve the WHOLE batch from the host — the
            # specs' pinned read points make the re-serve byte-identical
            # (MVCC: later writes are invisible at spec.read_ht).
            self._release_pins()
            self.eng.breaker.record_failure(e)
            self.route = "breaker_host"
            self.results = self.eng._serve_host_batch(self.specs,
                                                      self.deadline)
            self._done = True
            return self.results
        except BaseException:
            self._release_pins()
            raise
        self._release_pins()
        self.eng.breaker.record_success()
        return out

    def _fetch(self, tree):
        t0 = time.perf_counter_ns()
        out = jax.device_get(tree)
        self._fetch_ns += time.perf_counter_ns() - t0
        return out

    def _check_deadline(self) -> None:
        if self.deadline is not None:
            self.deadline.check("tpu_engine.scan_batch.finish")

    def _finish(self) -> list[ScanResult]:
        eng = self.eng
        results = self.results
        self._check_deadline()
        # Host work that overlaps the in-flight fetch (e.g. the delta
        # overlay's dirty-row fold), then host-path scans.
        for pre in self.pre_work:
            pre()
        for pi, fin in self.host_plans:
            results[pi] = fin()
        # Host page-cache scans through the native page server (numpy
        # plan/decode fallback inside serve_pages).
        if self.pages:
            served = host_page.serve_pages(
                eng, [it for _pi, it in self.pages])
            for (pi, _it), res in zip(self.pages, served):
                results[pi] = res
        # One fetch for everything issued in round 1 (device_get reuses
        # buffers the async copies already landed).
        disp_bufs, issued_np = self._fetch(
            [[d for _c, d in self.dispatches],
             [o for _pi, o, _f in self.issued_outs]])
        for (pi, _outs, fin), f in zip(self.issued_outs, issued_np):
            results[pi] = fin(f)
        pending = eng._feed_round(self.states, self.pending,
                                  self.dispatches, disp_bufs)
        # Continuation rounds (overflow/verification shortfalls): plain
        # synchronous cycles. Each round re-checks the propagated
        # deadline: a budget that expired mid-scan aborts here and
        # finish() unwinds the residency pins on the way out.
        while pending:
            self._check_deadline()
            dispatches = eng._issue_round(self.states, pending)
            disp_bufs = self._fetch([d for _c, d in dispatches])
            pending = eng._feed_round(self.states, pending, dispatches,
                                      disp_bufs)
        for pi, st in self.gathers:
            results[pi] = st.result()
        self._done = True
        return self.results


class _HostServeBatch:
    """The degraded-mode stand-in for _AsyncBatch: produced while the
    circuit breaker quarantines the device path (or after a fault struck
    during planning). Nothing was issued to the device and no residency
    pins are held; finish() serves the whole batch from the host."""

    route = "breaker_host"
    sources = 0     # (nothing was planned: the host serves every spec)

    def __init__(self, eng, specs, deadline=None):
        self.eng = eng
        self.specs = list(specs)
        self.deadline = deadline
        self.results: list | None = None
        self._done = False

    def finish(self) -> list[ScanResult]:
        if self._done:
            return self.results
        wall, t0 = time.time_ns(), time.perf_counter_ns()
        try:
            self.results = self.eng._serve_host_batch(self.specs,
                                                      self.deadline)
        finally:
            # (no device work: the fetch phase is there, and empty)
            _record_phase("wait_fetch", self.route, wall, 0)
            _record_phase("finish", self.route, wall,
                          time.perf_counter_ns() - t0)
        self._done = True
        return self.results


class _GatherScan:
    """State of one in-flight device scan across scan_batch rounds.

    ``pending`` holds the param-rows to dispatch this round; ``consume``
    decodes the fetched buffers and returns the next round's param-rows
    ([] when the scan is complete). Continuations advance by global row
    index only — no host key lookups on the continuation path."""

    def __init__(self, eng: TpuStorageEngine, ctx, mode: str, pending,
                 w_last: int, row_hi: int):
        self.eng = eng
        self.ctx = ctx
        self.mode = mode          # "paged" | "chunks"
        self.pending = pending
        self.sig = ctx["sig"]
        self.trun = ctx["trun"]
        self.w_last = w_last
        self.row_hi = row_hi
        self.rows: list[tuple] = []
        self.scanned = 0
        self.resume: bytes | None = None

    def consume(self, bufs) -> list:
        eng, ctx = self.eng, self.ctx
        M = ctx["M"]
        if self.mode == "chunks":
            for buf in bufs:
                self.scanned += int(buf[M, 1])
                eng._emit_fetched(ctx, buf, self.rows)
            return []

        from yugabyte_db_tpu.ops import row_gather

        buf = bufs[0]
        (prev_ip, _prev_fp) = self.pending[0]
        w_cap = int(prev_ip[1])
        count = int(buf[M, 0])
        self.scanned += int(buf[M, 1])
        w_end = int(buf[M, 2])
        n = min(count, M)
        last_start = int(buf[n - 1, 0]) if n else None
        _c, _n, hit_limit, last = eng._emit_fetched(ctx, buf, self.rows)
        if hit_limit:
            self.resume = ctx["crun"].key_at(last) + b"\x00"
            self.pending = []
            return []
        # Complete iff no match was dropped (count > M: overflow) AND the
        # loop consumed every window up to the range end.
        if count <= M and w_end > w_cap and w_cap >= self.w_last:
            self.pending = []
            return []
        K, R = self.sig.K, self.sig.R
        if count > M:
            row_lo2 = last_start + 1
        else:
            row_lo2 = max(int(prev_ip[2]), w_end * K * R)
        if row_lo2 >= self.row_hi:
            self.pending = []
            return []
        w_first2 = row_lo2 // (K * R)
        # Windows up to w_end were already counted toward rows_scanned;
        # a mid-window resume must not re-count them.
        scan_from = max(row_lo2, w_end * K * R)
        ip, fp = row_gather.pack_params(
            w_first2, self.w_last, row_lo2, self.row_hi, ctx["read_planes"],
            ctx["int_lits"], ctx["f32_lits"], scan_from=scan_from)
        self.pending = [(ip, fp)]
        return self.pending

    def result(self) -> ScanResult:
        return self.eng._gather_result(self.ctx, self.rows, self.scanned,
                                       self.resume)


_literal = agg_fold.pred_literal


register_engine("tpu", TpuStorageEngine)
