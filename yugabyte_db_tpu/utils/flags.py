"""Flags: a typed runtime-settable configuration registry with tags.

Reference analog: the gflags + flag-tags system (src/yb/util/flag_tags.h
— stable/evolving/advanced/unsafe/runtime) and the SetFlag RPC of
GenericService (src/yb/server/generic_service.cc). Flags tagged
``runtime`` may change on a live process; ``unsafe`` flags require
explicit unlocking, mirroring --unlock_unsafe_flags.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field

VALID_TAGS = {"stable", "evolving", "advanced", "runtime", "unsafe",
              "hidden"}


@dataclass
class FlagInfo:
    name: str
    default: object
    help: str
    tags: frozenset = frozenset()
    value: object = None


class FlagRegistry:
    def __init__(self):
        self._lock = threading.Lock()
        self._flags: dict[str, FlagInfo] = {}
        self.unsafe_unlocked = False

    def define(self, name: str, default, help_: str = "",
               tags=()) -> None:
        tags = frozenset(tags)
        bad = tags - VALID_TAGS
        if bad:
            raise ValueError(f"unknown flag tags {sorted(bad)}")
        with self._lock:
            if name in self._flags:
                return  # idempotent re-import
            self._flags[name] = FlagInfo(name, default, help_, tags,
                                         default)

    def get(self, name: str):
        with self._lock:
            return self._flags[name].value

    def set(self, name: str, value, force: bool = False) -> None:
        with self._lock:
            f = self._flags[name]
            if "unsafe" in f.tags and not (self.unsafe_unlocked or force):
                raise PermissionError(
                    f"flag {name} is tagged unsafe; unlock unsafe flags "
                    "first")
            if not isinstance(value, type(f.default)) and \
                    f.default is not None:
                value = type(f.default)(value)
            f.value = value

    def all(self) -> list[FlagInfo]:
        with self._lock:
            return [FlagInfo(f.name, f.default, f.help, f.tags, f.value)
                    for f in self._flags.values()]


FLAGS = FlagRegistry()

# Core flags (grown as subsystems adopt them).
FLAGS.define("memtable_flush_versions", 1 << 60,
             "versions buffered before an automatic flush",
             ("stable", "runtime"))
FLAGS.define("compaction_trigger", 4,
             "sorted-run count triggering universal compaction",
             ("stable", "runtime"))
FLAGS.define("txn_expiry_s", 10.0,
             "seconds without heartbeat before a txn is auto-aborted",
             ("evolving", "runtime"))
FLAGS.define("max_clock_skew_us", 500_000,
             "bound on tolerated inter-node clock skew",
             ("stable",))
FLAGS.define("follower_unavailable_considered_failed_sec", 5.0,
             "tserver liveness timeout", ("stable",))
FLAGS.define("tpu_hbm_budget_bytes", 0,
             "PER-DEVICE capacity budget for device-resident (HBM) "
             "columnar run planes; 0 = unbounded. When set, run planes "
             "are demand-uploaded through the storage.residency cache "
             "and evicted LRU per device with a scan-resistant two-pool "
             "policy (reference: rocksdb/util/cache.cc high-pri/low-pri "
             "split). Each mesh chip gets its own bucket of this size",
             ("evolving", "runtime"))
FLAGS.define("global_memstore_limit_bytes", 1 << 40,
             "process-wide memtable budget; crossing it flushes the "
             "engine that noticed (reference: the shared memory_monitor "
             "across rocksdb instances)", ("stable", "runtime"))
FLAGS.define("use_cassandra_authentication", False,
             "require CQL authentication + per-statement role "
             "permission checks (reference: the flag of the same name "
             "gating auth in the CQL proxy)", ("stable", "runtime"))
FLAGS.define("ysql_require_auth", False,
             "require cleartext-password authentication on the PG wire "
             "(reference: pg_hba password auth via initdb defaults)",
             ("stable", "runtime"))
FLAGS.define("fault.ts_write_respond_failed", 0.0,
             "probability a successful tablet write responds failure "
             "anyway (client-retry / exactly-once testing; reference: "
             "FLAGS_respond_write_failed_probability)",
             ("unsafe", "runtime", "hidden"))
FLAGS.define("fault.wal_sync_failed", 0.0,
             "probability a WAL group-commit sync raises IOError",
             ("unsafe", "runtime", "hidden"))
FLAGS.define("tpu_breaker_failure_threshold", 3,
             "consecutive device-dispatch faults before the TPU engine's "
             "circuit breaker opens and scans re-serve from the host path",
             ("advanced", "runtime"))
FLAGS.define("tpu_breaker_cooldown_s", 1.0,
             "seconds an open TPU-engine breaker waits before admitting "
             "one half-open probe dispatch",
             ("advanced", "runtime"))
FLAGS.define("fault.tpu_dispatch", 0.0,
             "probability a device (TPU) dispatch raises — exercises the "
             "storage/breaker.py circuit breaker and the host re-serve "
             "path",
             ("unsafe", "runtime", "hidden"))
FLAGS.define("lock_witness", False,
             "record (field, lock-held) observations for every "
             "@guarded_by-declared field write (utils/locking.py); dump "
             "is cross-checked against yb-lint's static guarded facts "
             "via python -m yugabyte_db_tpu.analysis --witness-check",
             ("advanced", "runtime", "hidden"))
FLAGS.define("compile_witness", False,
             "count actual XLA trace/compile events per "
             "@compile_contract-declared jit entry (utils/jitting.py); "
             "dump is cross-checked against yb-lint's static compile "
             "contracts via python -m yugabyte_db_tpu.analysis "
             "--witness-check",
             ("advanced", "runtime", "hidden"))
FLAGS.define("pin_witness", False,
             "attribute every residency pin acquire/release to an owner "
             "site and thread, record per-lock hold durations into "
             "yb_lock_hold_seconds{cls}, and flag locks held across "
             "blocking seams (utils/resources.py); dump is cross-checked "
             "against yb-lint's static resource facts via python -m "
             "yugabyte_db_tpu.analysis --witness-check",
             ("advanced", "runtime", "hidden"))
FLAGS.define("fault.seed", 0,
             "non-zero: seed the fault-injection RNG so probabilistic "
             "faults replay deterministically (the sweep harness sets "
             "this; 0 = unseeded)",
             ("unsafe", "runtime", "hidden"))
FLAGS.define("raft_group_commit_window_us", 200,
             "microseconds the leader-side commit pipeline waits after "
             "the first append before issuing one WAL sync + one "
             "AppendEntries round per peer for every entry admitted in "
             "the window; 0 disables coalescing (every append signals "
             "peers immediately, the pre-group-commit behaviour)",
             ("evolving", "runtime"))
FLAGS.define("raft_max_inflight_ops", 4096,
             "backpressure bound on the leader's append->apply window: "
             "append_leader blocks while last_index - applied_index "
             "reaches this many entries (bounded apply-queue depth for "
             "the ack-at-commit pipeline)",
             ("evolving", "runtime"))
FLAGS.define("tpu_device_flush", True,
             "build flush runs on-device: replay the memtable op log "
             "into staged columnar planes and apply the sort "
             "permutation via a jitted gather (ops/flush.py), "
             "pre-seeding the run's resident device planes; falls back "
             "to the host path when the run exceeds the HBM residency "
             "budget or the device dispatch faults",
             ("evolving", "runtime"))
FLAGS.define("tpu_plane_encoding", "auto",
             "compressed device plane encodings for columnar runs: "
             "'auto' picks per-column encodings (dictionary for varlen, "
             "RLE/delta16/const for ints, bit-packed bools) at build "
             "time via a cheap stats pass and the kernels read the "
             "compressed planes directly; 'off' uploads uncompressed "
             "planes (the pre-encoding format). Pathological columns "
             "(dictionary overflow, low run-length) transparently fall "
             "back to uncompressed per plane",
             ("evolving", "runtime"))
FLAGS.define("fault.raft_apply_stall", 0.0,
             "non-zero: the Raft apply stage stalls (committed entries "
             "stay unapplied) — used by the commit_ack_crash fault-sweep "
             "round to widen the commit-ack/apply window deterministically",
             ("unsafe", "runtime", "hidden"))
FLAGS.define("tablet_split_size_bytes", 0,
             "size threshold for master-driven tablet splitting: a "
             "tablet whose reported on-disk size (WAL + flushed runs) "
             "crosses this many bytes is split at its median resident "
             "key; 0 disables size-based splitting (reference: "
             "FLAGS_tablet_split_size_threshold_bytes of "
             "catalog_manager's tablet-split heuristics)",
             ("evolving", "runtime"))
FLAGS.define("tablet_split_ops_per_sec", 0.0,
             "op-rate threshold for master-driven tablet splitting: a "
             "tablet whose heartbeat-reported op rate sustains above "
             "this many ops/s is split at its median resident key; 0 "
             "disables load-based splitting (reference: the automatic "
             "tablet-splitting thresholds of the reference's "
             "TabletSplitManager)",
             ("evolving", "runtime"))
FLAGS.define("enable_leader_balancing", False,
             "run the master's leader load-balancer pass: when the "
             "spread between the most- and least-leader-loaded live "
             "tservers reaches 2, step one leader down toward the "
             "least-loaded tserver (one move per pass; reference: "
             "the leader-balancing half of cluster_balance.cc)",
             ("evolving", "runtime"))
