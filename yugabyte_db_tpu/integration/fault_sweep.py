"""Randomized fault-sweep harness: seeded faults against a live
mini-cluster workload, invariants checked after every round.

Reference analog: the randomized kill-testing loop of
src/yb/integration-tests (ExternalMiniClusterITest crash-point sweeps)
crossed with the fault-injection flags of util/fault_injection.h — a
seeded RNG drives both the workload and the fault schedule, so any
failing sweep replays byte-for-byte from its seed.

Each round fires one fault from the catalog mid-workload:

==================  =======================================================
``wal_sync``        ``fault.wal_sync_failed`` armed once: the next WAL
                    group-commit raises; the write's outcome is ambiguous
                    (appended-but-unsynced entries may still replicate).
``respond_dropped`` ``fault.ts_write_respond_failed`` armed once: the
                    write APPLIES but the response reports failure; the
                    client retry must dedup (exactly-once).
``leader_crash``    The tserver hosting the most leaders is stopped and
                    restarted (bootstrap replay); in-flight ops fail over.
``device_dispatch`` ``fault.tpu_dispatch`` armed once: the next device
                    dispatch faults; the circuit breaker must re-serve
                    from the host byte-identically and later recover.
``hbm_eviction``    ``hbm_cache().evict_unpinned()`` hammered from a side
                    thread while scans run (mid-scan eviction pressure).
``commit_ack_crash`` ``fault.raft_apply_stall`` held while one write is
                    acked at COMMIT time (pipelined apply still queued),
                    then the leader crashes before applying; after
                    restart the acked write must survive WAL replay and
                    every peer's apply lag must drain back to 0.
``chip_loss``       A mesh chip drops out mid paged row scan
                    (``fault.mesh_dispatch`` armed between two pages of
                    a mesh-served LIMIT scan): the MeshScanService
                    releases every stacked placement, the request
                    bounces to the per-tablet host path, and the full
                    host re-serve must be byte-identical to the mesh
                    serve taken before the loss. Per-device pins unwind
                    to zero (the ``device/sharded`` MemTracker subtree
                    reads 0 after the fault).
==================  =======================================================

Invariants after every round (each returns a list of error strings):

1. **No acked write lost** — every acknowledged write is visible at its
   exact value; writes whose ack was lost to a fault may hold either the
   old or the attempted value (never anything else).
2. **Engine diff** — for every TPU-engine leader, the device scan path
   and the host (CPU) serve path return byte-identical rows; the
   breaker must be recovered (``yb_engine_degraded == 0``) first.
3. **No leaked residency pins** — ``hbm_cache().pinned_bytes() == 0``
   once no scan is in flight. With the resource witness live
   (``--resource-witness-out`` / ``--pin_witness``) a violation names
   the acquire site and thread of every outstanding pin.
4. **MemTracker baseline** — after evicting every unpinned entry the
   device subtree's consumption returns to the post-setup baseline
   (a leaked pin or unaccounted upload shows up here).

The harness also asserts its injection ledger against the
``yb_faults_fired{name=...}`` process metric — the fault points
themselves count fires, so a fault that silently failed to arm (or
fired twice) is caught rather than trusted.
"""

from __future__ import annotations

import random
import threading
import time

from yugabyte_db_tpu.client.session import YBSession
from yugabyte_db_tpu.integration.mini_cluster import MiniCluster
from yugabyte_db_tpu.models.datatypes import DataType
from yugabyte_db_tpu.models.schema import ColumnKind, ColumnSchema
from yugabyte_db_tpu.storage.breaker import degraded
from yugabyte_db_tpu.storage.residency import hbm_cache
from yugabyte_db_tpu.storage.scan_spec import ScanSpec
from yugabyte_db_tpu.utils.fault_injection import (arm_fault_once,
                                                   clear_faults)
from yugabyte_db_tpu.utils.flags import FLAGS
from yugabyte_db_tpu.utils.memtracker import root_tracker
from yugabyte_db_tpu.utils.metrics import faults_fired

FAULT_CATALOG = ("wal_sync", "respond_dropped", "leader_crash",
                 "device_dispatch", "hbm_eviction", "commit_ack_crash",
                 "chip_loss")

# Catalog entries backed by a maybe_fault() point (armed one-shot and
# asserted against the yb_faults_fired metric).
ARMED_FLAG = {
    "wal_sync": "fault.wal_sync_failed",
    "respond_dropped": "fault.ts_write_respond_failed",
    "device_dispatch": "fault.tpu_dispatch",
}

# Catalog entries whose handler arms AND reaches the fault point itself
# (the round's trailing op/scan cannot be relied on to hit it); still
# asserted against yb_faults_fired like the ARMED_FLAG entries.
HANDLER_FLAG = {
    "chip_loss": "fault.mesh_dispatch",
}

# "the row is absent" in the oracle / acceptable-value sets.
ABSENT = object()


class FaultSweep:
    """One seeded sweep: a MiniCluster with a TPU-engine table, a
    keyed write/scan workload, one fault per round, invariants after
    each. ``run()`` returns a summary dict or raises AssertionError
    with every violated invariant (prefixed by the seed, so the report
    alone is enough to replay)."""

    def __init__(self, data_root: str, seed: int, rounds: int = 5,
                 ops_per_round: int = 16,
                 faults: tuple = FAULT_CATALOG,
                 schedule: tuple | None = None,
                 num_tservers: int = 3, num_tablets: int = 2,
                 keyspace: int = 48, witness_out: str | None = None,
                 compile_witness_out: str | None = None,
                 resource_witness_out: str | None = None):
        self.data_root = data_root
        self.seed = seed
        self.rounds = len(schedule) if schedule is not None else rounds
        self.ops_per_round = ops_per_round
        self.faults = tuple(faults)
        # Explicit per-round fault names (deterministic coverage: one
        # round per catalog entry); None = rng-chosen from ``faults``.
        self.schedule = tuple(schedule) if schedule is not None else None
        self.num_tservers = num_tservers
        self.num_tablets = num_tablets
        self.keys = [f"k{i:04d}" for i in range(keyspace)]
        self.rng = random.Random(seed)
        # key -> last acked value (ABSENT = acked delete / never written)
        self.oracle: dict[str, object] = {}
        # key -> set of acceptable values while the last write's ack was
        # lost to a fault (old value or attempted value, until a later
        # acked write re-fixes it)
        self.ambiguous: dict[str, set] = {}
        self._next_value = 0
        self.fired_ledger: dict[str, int] = {}
        self.errors: list[str] = []
        self.mc: MiniCluster | None = None
        self.client = None
        self.table = None
        # Dump lock-witness observations here after the sweep (also
        # honors the --lock_witness flag without a path, for ad-hoc
        # runs; the dump is meant for yb-lint --witness-check).
        self.witness_out = witness_out
        # Same contract for the compile witness (utils/jitting.py):
        # per-entry XLA compile counts, honoring --compile_witness.
        self.compile_witness_out = compile_witness_out
        # And for the resource witness (utils/resources.py): pin
        # acquire/release attribution + holds-across-blocking, honoring
        # --pin_witness. With the witness live, the no-leaked-pins
        # invariant names the exact acquire site of every leak.
        self.resource_witness_out = resource_witness_out

    # -- lifecycle -----------------------------------------------------------

    def setup(self) -> None:
        FLAGS.set("fault.seed", self.seed, force=True)
        self._fired_base = {n: faults_fired(f)
                            for n, f in {**ARMED_FLAG,
                                         **HANDLER_FLAG}.items()}
        self.mc = MiniCluster(
            self.data_root, num_tservers=self.num_tservers,
            # A fast breaker so degrade -> half-open probe -> recover
            # fits inside one round.
            engine_options={"breaker_cooldown_s": 0.05,
                            "breaker_failure_threshold": 1}).start()
        self.mc.wait_tservers_registered()
        self.client = self.mc.client()
        self.client.create_table("sweep", [
            ColumnSchema("k", DataType.STRING, ColumnKind.HASH),
            ColumnSchema("v", DataType.INT64)],
            num_tablets=self.num_tablets, engine="tpu")
        self.table = self.client.open_table("sweep")
        # Pre-fill + flush so the device path has runs to scan.
        s = YBSession(self.client)
        for k in self.keys[: len(self.keys) // 2]:
            v = self._bump_value()
            s.insert(self.table, {"k": k, "v": v})
            self.oracle[k] = v
        s.flush()
        self._flush_tablets()
        self._scan_cluster()  # warm the device path
        self._quiesce_device()
        self._device_baseline = root_tracker().child("device").consumption

    def teardown(self) -> None:
        clear_faults()
        FLAGS.set("fault.seed", 0, force=True)
        if self.mc is not None:
            self.mc.shutdown()
            self.mc = None

    def run(self) -> dict:
        from yugabyte_db_tpu.utils import jitting, locking, resources

        # Enable BEFORE setup so every lock the cluster creates is
        # ownership-tracked from birth.
        wit = self.witness_out is not None or bool(
            FLAGS.get("lock_witness"))
        if wit:
            locking.enable_lock_witness()
        # Likewise before the setup scans: warmup compiles are part of
        # each entry's budget.
        cwit = self.compile_witness_out is not None or bool(
            FLAGS.get("compile_witness"))
        if cwit:
            jitting.enable_compile_witness()
        # And before setup for the resource witness: the pre-fill pins
        # and every guard lock the cluster constructs must be owned.
        rwit = self.resource_witness_out is not None or bool(
            FLAGS.get("pin_witness"))
        if rwit:
            resources.enable_resource_witness()
        self.setup()
        try:
            for rnd in range(self.rounds):
                fault = (self.schedule[rnd] if self.schedule is not None
                         else self.faults[self.rng.randrange(
                             len(self.faults))])
                self._run_round(rnd, fault)
                self.errors.extend(
                    f"round {rnd} ({fault}, seed {self.seed}): {e}"
                    for e in self.check_invariants())
            self.errors.extend(
                f"final (seed {self.seed}): {e}"
                for e in self._check_fired_ledger())
            if self.errors:
                raise AssertionError(
                    "fault sweep invariants violated:\n  "
                    + "\n  ".join(self.errors))
            return {"seed": self.seed, "rounds": self.rounds,
                    "faults_fired": dict(self.fired_ledger),
                    "keys": len(self.oracle)}
        finally:
            self.teardown()
            if wit:
                if self.witness_out is not None:
                    locking.dump_lock_witness(self.witness_out)
                locking.disable_lock_witness()
            if cwit:
                if self.compile_witness_out is not None:
                    jitting.dump_compile_witness(self.compile_witness_out)
                jitting.disable_compile_witness()
            if rwit:
                if self.resource_witness_out is not None:
                    resources.dump_resource_witness(
                        self.resource_witness_out)
                resources.disable_resource_witness()

    # -- one round -----------------------------------------------------------

    def _run_round(self, rnd: int, fault: str) -> None:
        fire_at = self.rng.randrange(self.ops_per_round)
        evictor = None
        for i in range(self.ops_per_round):
            if i == fire_at:
                evictor = self._fire(fault)
            self._one_op()
            if i % 5 == 4:
                self._scan_cluster()
        # Ensure every armed fault point is actually reached this round:
        # a write (WAL sync + response path) and a scan (device dispatch)
        # both run after the arm point.
        self._one_op(kind="insert")
        self._scan_cluster()
        if evictor is not None:
            evictor.join(timeout=5.0)

    def _fire(self, fault: str) -> threading.Thread | None:
        flag = ARMED_FLAG.get(fault)
        if flag is not None:
            arm_fault_once(flag)
            self.fired_ledger[fault] = self.fired_ledger.get(fault, 0) + 1
            return None
        if fault == "leader_crash":
            self._crash_and_restart_leader()
            return None
        if fault == "commit_ack_crash":
            self._commit_ack_crash()
            return None
        if fault == "chip_loss":
            self._chip_loss()
            return None
        if fault == "hbm_eviction":
            # Eviction pressure racing the scans the round keeps issuing.
            def pound():
                try:
                    for _ in range(20):
                        hbm_cache().evict_unpinned()
                        time.sleep(0.002)
                except Exception as e:  # noqa: BLE001 — surfaced as a failure
                    self.errors.append(f"evictor thread died: {e!r}")

            t = threading.Thread(target=pound, name="sweep-evictor",
                                 daemon=True)
            t.start()
            return t
        raise ValueError(f"unknown fault {fault!r}")

    def _commit_ack_crash(self) -> None:
        """The pipelined-apply durability round: hold
        ``fault.raft_apply_stall`` so commit-time acks go out while
        every apply stays queued, take one acked write inside that
        window, then crash the leader BEFORE anything applies. The
        acked write must come back from WAL replay (checked by
        check_acked_writes via the round's scans), and once the stall
        clears every peer's apply lag (the yb_apply_lag_ops gauge
        source: commit_index - applied_index) must drain to 0."""
        stall_base = faults_fired("fault.raft_apply_stall")
        FLAGS.set("fault.raft_apply_stall", 1.0, force=True)
        try:
            # Acked at commit; apply is stalled cluster-wide, so the
            # ack/apply window is provably open when the leader dies.
            self._one_op(kind="insert")
            counts = {
                uuid: sum(1 for p in ts.tablet_manager.peers()
                          if p.is_leader())
                for uuid, ts in self.mc.tservers.items()}
            victim = max(counts, key=counts.get)
            self.mc.stop_tserver(victim)
        finally:
            FLAGS.set("fault.raft_apply_stall", 0.0, force=True)
        if faults_fired("fault.raft_apply_stall") <= stall_base:
            self.errors.append(
                "commit_ack_crash: fault.raft_apply_stall never fired "
                "(apply was not stalled during the ack window)")
        self.mc.restart_tserver(victim)
        self.mc.wait_tservers_registered()
        # A current-term entry drags the stalled old-term entries to
        # commit on the new leader, then every queue must drain.
        self._one_op(kind="insert")
        self._await_apply_drain()

    def _await_apply_drain(self, timeout_s: float = 10.0) -> None:
        deadline = time.monotonic() + timeout_s
        lag = {}
        while time.monotonic() < deadline:
            lag = {}
            for uuid, ts in self.mc.tservers.items():
                for peer in ts.tablet_manager.peers():
                    rs = peer.raft.stats()
                    d = rs["commit_index"] - rs["applied_index"]
                    if d > 0:
                        lag[f"{uuid}/{peer.tablet_id}"] = d
            if not lag:
                return
            time.sleep(0.05)
        self.errors.append(
            f"commit_ack_crash: apply lag never drained to 0: {lag}")

    def _crash_and_restart_leader(self) -> None:
        counts = {
            uuid: sum(1 for p in ts.tablet_manager.peers()
                      if p.is_leader())
            for uuid, ts in self.mc.tservers.items()}
        victim = max(counts, key=counts.get)
        self.mc.stop_tserver(victim)
        try:
            self._one_op()          # ops fail over to the new leader
        finally:
            self.mc.restart_tserver(victim)
        self.mc.wait_tservers_registered()

    def _chip_loss(self) -> None:
        """The multi-chip availability round: a mesh chip drops out
        between two pages of a mesh-served LIMIT row scan
        (``fault.mesh_dispatch`` fires at the next dispatch). The
        MeshScanService must release every stacked placement — the
        ``device/sharded`` MemTracker subtree reads 0 and the stack
        cache empties — and the full host re-serve must be
        byte-identical to the mesh serve taken before the loss.

        Mesh eligibility needs a single run and an empty memtable, so
        the round flushes + compacts first; that legitimately moves
        device residency, so the MemTracker baseline is re-anchored
        BEFORE the stack is built — the end-of-round invariant then
        measures the chip loss itself, not the flush."""
        self._flush_tablets()
        for ts in self.mc.tservers.values():
            for peer in ts.tablet_manager.peers():
                peer.compact()
        self._quiesce_device()
        self._device_baseline = root_tracker().child("device").consumption

        def tpu_leaders(ts):
            return [p for p in ts.tablet_manager.peers()
                    if p.is_leader()
                    and hasattr(p.tablet.engine, "_serve_host_batch")]

        ts = max(self.mc.tservers.values(),
                 key=lambda t: len(tpu_leaders(t)))
        peers = tpu_leaders(ts)
        if not peers:
            self.errors.append("chip_loss: no TPU leader peers to scan")
            return
        read_ht = min(p.read_time().value for p in peers)
        full = ScanSpec(read_ht=read_ht, projection=["k", "v"])
        paged = ScanSpec(read_ht=read_ht, projection=["k", "v"], limit=8)
        mesh_full = ts.mesh_scan.rows(peers, full)
        first = ts.mesh_scan.rows(peers, paged)
        if mesh_full is None or first is None:
            self.errors.append(
                "chip_loss: mesh path ineligible after flush+compact")
            return
        arm_fault_once("fault.mesh_dispatch")
        self.fired_ledger["chip_loss"] = \
            self.fired_ledger.get("chip_loss", 0) + 1
        lost = ts.mesh_scan.rows(peers, paged, resume=first.resume_key)
        if lost is not None:
            self.errors.append(
                "chip_loss: dispatch served despite the lost chip")
        sharded = root_tracker().child("device").child(
            "sharded").consumption
        if sharded != 0:
            self.errors.append(
                f"chip_loss: {sharded} stacked bytes survived the "
                "lost chip")
        if ts.mesh_scan._stacks:
            self.errors.append("chip_loss: stack cache not emptied")
        host_rows = []
        for p in peers:
            host_rows.extend(
                p.tablet.engine._serve_host_batch([full])[0].rows)
        if mesh_full.rows != host_rows:
            self.errors.append(
                f"chip_loss: host re-serve diverged ({len(host_rows)} "
                f"rows vs mesh {len(mesh_full.rows)})")

    def _one_op(self, kind: str | None = None) -> None:
        k = self.keys[self.rng.randrange(len(self.keys))]
        if kind is None:
            kind = "delete" if self.rng.random() < 0.15 else "insert"
        value = ABSENT if kind == "delete" else self._bump_value()
        s = YBSession(self.client)
        if kind == "delete":
            s.delete(self.table, {"k": k})
        else:
            s.insert(self.table, {"k": k, "v": value})
        try:
            s.flush()
        except Exception:  # noqa: BLE001 — ack lost; outcome ambiguous
            self.ambiguous[k] = {self._current(k), value}
            return
        self.oracle[k] = value
        self.ambiguous.pop(k, None)

    def _current(self, k: str):
        amb = self.ambiguous.get(k)
        if amb:
            # Still unresolved from an earlier lost ack: any previously
            # acceptable value remains acceptable.
            return next(iter(amb))
        return self.oracle.get(k, ABSENT)

    def _bump_value(self) -> int:
        self._next_value += 1
        return self._next_value

    # -- cluster access ------------------------------------------------------

    def _scan_cluster(self) -> dict:
        res = YBSession(self.client).scan(
            self.table, ScanSpec(projection=["k", "v"]))
        return dict(res.rows)

    def _tpu_leader_engines(self):
        for ts in self.mc.tservers.values():
            for peer in ts.tablet_manager.peers():
                if peer.is_leader() and \
                        hasattr(peer.tablet.engine, "_serve_host_batch"):
                    yield peer

    def _flush_tablets(self) -> None:
        for ts in self.mc.tservers.values():
            for peer in ts.tablet_manager.peers():
                peer.flush()

    # -- invariants ----------------------------------------------------------

    def check_invariants(self) -> list[str]:
        errs = []
        errs.extend(self.check_acked_writes())
        errs.extend(self.check_engine_diff())
        errs.extend(self.check_residency_pins())
        errs.extend(self.check_memtracker_baseline())
        return errs

    def check_acked_writes(self) -> list[str]:
        got = self._scan_cluster()
        errs = []
        for k in self.keys:
            actual = got.get(k, ABSENT)
            acceptable = self.ambiguous.get(k)
            if acceptable is None:
                acceptable = {self.oracle.get(k, ABSENT)}
            if actual not in acceptable:
                want = sorted("ABSENT" if v is ABSENT else str(v)
                              for v in acceptable)
                errs.append(
                    f"acked write lost: {k} = "
                    f"{'ABSENT' if actual is ABSENT else actual}, "
                    f"acceptable {want}")
        for k in got:
            if k not in self.keys:
                errs.append(f"phantom row {k!r}")
        return errs

    def check_engine_diff(self) -> list[str]:
        errs = []
        for peer in list(self._tpu_leader_engines()):
            eng = peer.tablet.engine
            spec = ScanSpec(read_ht=peer.read_time().value,
                            projection=["k", "v"])
            self._await_breaker_recovery(eng, spec)
            device = eng.scan_batch([spec])[0]
            host = eng._serve_host_batch([spec])[0]
            if (device.rows, device.resume_key) != (host.rows,
                                                    host.resume_key):
                errs.append(
                    f"engine diff on {peer.tablet_id}: device "
                    f"{len(device.rows)} rows vs host {len(host.rows)}")
        if degraded():
            errs.append(
                "breaker still degraded after recovery probes: "
                f"{[b.name for b in degraded()]}")
        return errs

    def _await_breaker_recovery(self, eng, spec,
                                timeout_s: float = 5.0) -> None:
        """Probe the breaker back to closed: after the cooldown, one
        successful half-open dispatch restores the device path."""
        deadline = time.monotonic() + timeout_s
        while eng.breaker.is_degraded and time.monotonic() < deadline:
            eng.scan_batch([spec])
            time.sleep(0.02)

    def _quiesce_device(self) -> None:
        """Release every legitimate pin holder: the cached delta
        overlays (which pin their primary run while cached), the mesh
        services' stacked placements (rebuilt on the next eligible
        scan), and all unpinned residency. Whatever stays pinned
        afterward is a leak."""
        for ts in self.mc.tservers.values():
            for peer in ts.tablet_manager.peers():
                eng = peer.tablet.engine
                if hasattr(eng, "_drop_overlay_cache"):
                    eng._drop_overlay_cache()
            if hasattr(ts, "mesh_scan"):
                ts.mesh_scan.drop_stacks()
        hbm_cache().evict_unpinned()

    def check_residency_pins(self) -> list[str]:
        self._quiesce_device()
        pinned = hbm_cache().pinned_bytes()
        external = self._external_bytes()
        if pinned > external:
            msg = (f"leaked residency pins: {pinned} pinned bytes "
                   f"({external} external)")
            # With the resource witness live, name the culprits: the
            # acquire site and thread of every pin still outstanding.
            from yugabyte_db_tpu.utils import resources
            if resources.resource_witness_enabled():
                leaks = resources.witness().outstanding()
                if leaks:
                    msg += "".join(
                        f"; {r['key']} acquired at {r['site']} "
                        f"on {r['thread']}" for r in leaks)
            return [msg]
        return []

    def _external_bytes(self) -> int:
        cache = hbm_cache()
        with cache._lock:
            return sum(e.nbytes
                       for pool in cache._pools.values()
                       for e in pool.values() if e.external)

    def check_memtracker_baseline(self) -> list[str]:
        self._quiesce_device()
        dev = root_tracker().child("device").consumption
        if dev != self._device_baseline:
            return [f"device MemTracker not back to baseline: {dev} "
                    f"(baseline {self._device_baseline})"]
        return []

    def _check_fired_ledger(self) -> list[str]:
        errs = []
        for name, count in self.fired_ledger.items():
            flag = ARMED_FLAG.get(name) or HANDLER_FLAG[name]
            fired = faults_fired(flag) - self._fired_base[name]
            if fired != count:
                errs.append(
                    f"yb_faults_fired{{name={flag}}} = {fired}, "
                    f"harness armed {count}")
        return errs


def run_sweep(data_root: str, seed: int, rounds: int = 5,
              ops_per_round: int = 16,
              faults: tuple = FAULT_CATALOG, **kwargs) -> dict:
    """Run one seeded sweep; returns its summary dict (see FaultSweep)."""
    return FaultSweep(data_root, seed, rounds=rounds,
                      ops_per_round=ops_per_round, faults=faults,
                      **kwargs).run()


if __name__ == "__main__":  # replay a failing seed: python -m ... <seed>
    # With --witness-out PATH the replay records lock-witness
    # observations, with --compile-witness-out PATH per-jit-entry
    # compile counts, and with --resource-witness-out PATH pin/hold
    # attribution — all three dumps feed yb-lint --witness-check.
    import sys
    import tempfile

    from yugabyte_db_tpu.utils.jitting import enable_compile_cache

    enable_compile_cache()
    argv = list(sys.argv[1:])
    wout = cwout = rwout = None
    if "--witness-out" in argv:
        i = argv.index("--witness-out")
        wout = argv[i + 1]
        del argv[i:i + 2]
    if "--compile-witness-out" in argv:
        i = argv.index("--compile-witness-out")
        cwout = argv[i + 1]
        del argv[i:i + 2]
    if "--resource-witness-out" in argv:
        i = argv.index("--resource-witness-out")
        rwout = argv[i + 1]
        del argv[i:i + 2]
    with tempfile.TemporaryDirectory() as root:
        print(run_sweep(root, int(argv[0]) if argv else 1234,
                        witness_out=wout, compile_witness_out=cwout,
                        resource_witness_out=rwout))
