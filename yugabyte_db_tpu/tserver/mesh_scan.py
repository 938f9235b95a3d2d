"""MeshScanService: multi-tablet scans over the device mesh.

The cluster read path for a tserver leading several tablets of a table:
instead of one ts.scan per tablet with the CLIENT merging on host (the
reference's shape — per-tablet EvalAggregate partials recombined by the
CQL executor / PG FDW, src/yb/docdb/pgsql_operation.cc:473, and the
batcher's thread-per-tablet row fan-out, src/yb/client/batcher.h:80),
the tserver serves them with ONE device program: tablets sharded over
the mesh "t" axis, each tablet's blocks over "b".

- Aggregates: GROUP BY and expression aggregates (TPC-H Q1, Q6) run
  ops.group_agg's window loop on every (tablet, block-range) shard
  (parallel.sharded.sharded_grouped_aggregate): a tablet's partial
  bucket tables are combined over "b" by collectives, tablets stay
  apart (dictionaries and ``rep`` rows are per run) and the host
  finishes and combines them as it does per-tablet replies. Plain
  min/max/float aggregates that group_agg does not lower keep the
  fold program, combined with psum / two-plane lexicographic pmax over
  ICI (parallel.sharded.sharded_aggregate).
- Row scans: the packed MVCC row gather runs on every (tablet,
  block-range) shard, per-device match counts psum over ICI, and the
  host decodes only the LIMIT page's rows
  (parallel.sharded.sharded_row_page). Cross-tablet paging rides the
  (tablet index, last key) resume token, opaque to the client.

The client-side merge remains only as the cross-tserver / ineligible-
spec fallback.

Where a tablet's planes live: a tablet served through a stack has its
device copy in the stack's shards, a share on every chip of the mesh;
the per-run copy the engine's own programs would upload to the node's
first chip is released when a stack is built over the run, and comes
back by demand upload if a per-tablet scan needs it.

Mesh policy: built once from the visible devices — "t" gets the larger
factor (tablet parallelism is the dominant axis), "b" gets 2 when the
device count is even. A single-chip node degenerates to a 1x1 mesh and
still executes the same program (collectives become identities), so the
code path is identical from laptop to pod slice.

Stack lifecycle: stacked device residency is cached per run set. A
flush/compaction replaces ONE tablet's ColumnarRun; when the stack is
un-encoded the cache updates that tablet's slot in place with a jitted
dynamic_update_slice — fed straight from the run's resident device
planes (the PR-15 device-flush output) when they are on device, no host
round trip. Otherwise the superseded stack's residency is released
immediately (close() — in-flight scans holding the old arrays finish
unharmed; the bytes leave the budget when the last reference dies).
"""

from __future__ import annotations

import threading

from yugabyte_db_tpu.storage.scan_spec import ScanResult, ScanSpec
from yugabyte_db_tpu.utils import metrics, trace
from yugabyte_db_tpu.utils.fault_injection import maybe_fault


def _phase(name: str, part: str | None = None) -> trace.span:
    """A phase of a mesh request, beside the per-tablet batches':
    ``yb_engine_phase_us{phase, route="mesh"}``; a ``part`` of its
    issue (``lower``, ``dispatch``) is span ``engine.issue.<part>`` and
    goes to ``yb_mesh_issue_part_us{part}``."""
    if part is None:
        return trace.span("engine." + name,
                          metrics.engine_phase_histogram(name, "mesh"),
                          route="mesh")
    return trace.span(f"engine.{name}.{part}",
                      metrics.mesh_issue_part_histogram(part), route="mesh")


class MeshScanService:
    """Per-tserver service executing multi-tablet scans on the device
    mesh. Stateless between calls except for a small cache of stacked
    device residency, invalidated incrementally as flush/compaction
    replace ColumnarRun objects."""

    def __init__(self, max_cached_stacks: int = 2):
        self._lock = threading.Lock()
        self._mesh = None
        self._stacks: dict[tuple, object] = {}
        self._max_cached = max_cached_stacks
        # This server's own counts (a process may hold several); the
        # series are yb_mesh_scans{kind, outcome} and
        # yb_mesh_stack_builds{how} on the process registry.
        self.served = 0       # aggregates answered on the mesh
        self.served_rows = 0  # row pages answered on the mesh
        self.updated = 0      # stacks refreshed in place (update_tablet)
        self.fallbacks = 0    # ineligible requests bounced to per-tablet
        self.chip_losses = 0  # mesh dispatches lost to a dropped chip

    def _ineligible(self, kind: str) -> None:
        self.fallbacks += 1
        metrics.count_mesh_scan(kind, "ineligible")

    def _get_mesh(self):
        if self._mesh is None:
            import jax
            from jax.sharding import Mesh
            import numpy as np

            devices = jax.devices()
            n = len(devices)
            mesh_b = 2 if n % 2 == 0 else 1
            mesh_t = n // mesh_b
            self._mesh = Mesh(
                np.array(devices[:mesh_t * mesh_b]).reshape(mesh_t, mesh_b),
                ("t", "b"))
        return self._mesh

    def eligible_peer(self, peer, spec: ScanSpec) -> bool:
        """Engine-state eligibility: TPU engine, exactly one run, no
        memtable data in the scanned range (single-source — the mesh
        program has no host-merge stage)."""
        engine = peer.tablet.engine
        runs = getattr(engine, "runs", None)
        if runs is None or len(runs) != 1:
            return False
        if not hasattr(runs[0], "crun"):
            return False  # cpu engine
        if engine._memtable_in_range(spec) or runs[0].crun.num_versions == 0:
            return False
        return True

    def _get_stack(self, peers: list, runs: list):
        """The cached ShardedTablets for this exact run set, refreshed
        incrementally when exactly one tablet's run changed since a
        cached stack (the flush/compaction case): the changed slot is
        rewritten in place on device, seeded from the run's resident
        flush planes when they exist. Full rebuilds release the
        superseded stack's residency immediately. None = unbuildable
        (caller falls back)."""
        key = tuple(id(r) for r in runs)
        mesh = self._get_mesh()
        with self._lock:
            st = self._stacks.get(key)
            if st is not None:
                return st
            for okey in list(self._stacks):
                if len(okey) != len(key):
                    continue
                diff = [i for i, (a, b) in enumerate(zip(okey, key))
                        if a != b]
                if len(diff) != 1:
                    continue
                t = diff[0]
                ost = self._stacks[okey]
                trun = peers[t].tablet.engine.runs[0]
                dev = getattr(trun, "peek_device", lambda: None)()
                if ost.update_tablet(t, runs[t],
                                     device_arrays=(dev.arrays
                                                    if dev is not None
                                                    else None)):
                    del self._stacks[okey]
                    self._stacks[key] = ost
                    self.updated += 1
                    metrics.count_mesh_stack_build("update")
                    self._release_run_copies(peers)
                    return ost
                break
            from yugabyte_db_tpu.parallel import ShardedTablets

            schema = peers[0].tablet.meta.schema
            with trace.span("mesh.stack_build", tablets=len(runs)) as sp:
                try:
                    st = ShardedTablets(schema, runs, mesh)
                except ValueError:
                    return None
                sp.labels["encoded"] = st.encoded
            metrics.count_mesh_stack_build("build")
            while len(self._stacks) >= self._max_cached:
                old = self._stacks.pop(next(iter(self._stacks)))
                old.close()  # release residency; in-flight scans finish
            self._stacks[key] = st
            self._release_run_copies(peers)
            return st

    @staticmethod
    def _release_run_copies(peers: list) -> None:
        """The stack's shards are these tablets' device copy from here
        on: a per-run copy on the node's first chip (an earlier
        per-tablet scan's upload, a device flush's output that has just
        fed the stack) would hold the tablet a second time there."""
        for p in peers:
            trun = p.tablet.engine.runs[0]
            if trun.peek_device() is not None:
                trun.invalidate_device()

    def drop_stacks(self) -> int:
        """Release every cached stack's residency (chip loss / device
        hot-unplug: placements on the lost chip are unusable, so the
        whole per-device footprint unwinds — in-flight scans holding
        the old arrays finish unharmed). Subsequent eligible scans
        rebuild on the surviving mesh. Returns the number dropped."""
        with self._lock:
            stacks = list(self._stacks.values())
            self._stacks.clear()
        for st in stacks:
            st.close()
        return len(stacks)

    def _lost_chip(self, kind: str) -> bool:
        """The ``fault.mesh_dispatch`` point, evaluated right before a
        device dispatch: a fired fault models a mesh chip dropping out
        mid-scan. The service releases all stacked residency and bounces
        the request to the per-tablet host path (byte-identical serve);
        it does NOT retry on the device — the caller's fallback is the
        availability story, exactly like the engine breaker's."""
        if not maybe_fault("fault.mesh_dispatch"):
            return False
        self.chip_losses += 1
        self.fallbacks += 1
        metrics.count_mesh_scan(kind, "chip_loss")
        self.drop_stacks()
        return True

    def _eligible_runs(self, peers: list, spec: ScanSpec):
        if not all(self.eligible_peer(p, spec) for p in peers):
            return None
        return [p.tablet.engine.runs[0].crun for p in peers]

    def aggregate(self, peers: list, spec: ScanSpec) -> ScanResult | None:
        """Run spec's aggregates over all peers' tablets on the mesh.
        Returns None when ineligible (caller falls back to per-tablet
        scans + host combine): an engine state the mesh has no stage
        for, a spec neither program lowers, or a grouped answer the host
        cannot use (a bucket collision, a negative factor)."""
        from yugabyte_db_tpu.parallel import (sharded_aggregate,
                                              sharded_grouped_aggregate)

        if not spec.is_aggregate:
            self._ineligible("agg")
            return None
        runs = self._eligible_runs(peers, spec)
        st = self._get_stack(peers, runs) if runs else None
        if st is None:
            self._ineligible("agg")
            return None
        if self._lost_chip("agg"):
            return None
        grouped = spec.group_by or any(a.expr is not None
                                       for a in spec.aggregates)
        try:
            if grouped:
                # (as the engine's own planner: GROUP BY and expression
                # aggregates are ops.group_agg's, the rest the folds')
                res = sharded_grouped_aggregate(
                    st, spec, peers[0].tablet.engine, phase=_phase)
            else:
                res = sharded_aggregate(st, spec)
        except ValueError:  # (GroupedIneligible is one)
            self._ineligible("agg")
            return None  # spec not device-exact: fallback
        self.served += 1
        metrics.count_mesh_scan("agg", "served")
        return res

    def rows(self, peers: list, spec: ScanSpec,
             resume: bytes | None = None) -> ScanResult | None:
        """Serve one LIMIT row page over all peers' tablets on the mesh
        (parallel.sharded.sharded_row_page). ``resume`` is the previous
        page's resume token (opaque (tablet index, last key)); tablet
        indices resolve against THIS peer list, so callers must pass the
        same tablet order every page. Returns None when ineligible."""
        from yugabyte_db_tpu.parallel import sharded_row_page

        if spec.is_aggregate or spec.group_by:
            self._ineligible("rows")
            return None
        runs = self._eligible_runs(peers, spec)
        st = self._get_stack(peers, runs) if runs else None
        if st is None:
            self._ineligible("rows")
            return None
        if self._lost_chip("rows"):
            return None
        try:
            res = sharded_row_page(st, spec, resume=resume)
        except ValueError:
            self._ineligible("rows")
            return None  # spec not device-exact: fallback
        self.served_rows += 1
        metrics.count_mesh_scan("rows", "served")
        return res
