"""Sustained-traffic replay harness: seeded mixed-protocol traffic
against a live mini-cluster while tablets split, leaders move, and
followers roll — invariants and latency SLOs checked per round.

Reference analog: the sustained-workload integration tests of
src/yb/integration-tests (tablet-split-itest.cc driving splits under
load, load_balancer-test.cc asserting leader moves) crossed with the
YCSB/TPC-H workload shapes the reference benchmarks against.

The generator is OPEN-LOOP and fully seeded: one ``random.Random(seed)``
drives the protocol mix, the zipfian key choice, and every written
value, so any failing sweep replays byte-for-byte from its seed
(``python -m yugabyte_db_tpu.integration.traffic_sweep <seed>``).

Protocol mix (zipfian hot keys, exponent 0.99):

==========  ==============================================================
``ycsb_a``  50/50 point read / upsert (YCSB workload A: update-heavy).
``ycsb_b``  95/5 point read / upsert (YCSB workload B: read-mostly).
``ycsb_e``  Short paged range scans (LIMIT 10) with 5% inserts
            (YCSB workload E: scan-heavy).
``tpch``    Aggregate pushdown shaped like TPC-H Q1 (sum/count/avg over
            the whole table) and Q6 (sum under a range predicate).
``redis``   RESP SET/GET through the in-process Redis service (its own
            ``redis`` table, the port-6379 proxy path).
==========  ==============================================================

Mid-stream cluster events, one catalog entry per round:

- **Round 0** — the first seed tablet is split through the
  ``master.split_tablet`` RPC from a background thread while the op
  loop keeps running (the seal -> fork -> seed -> commit protocol races
  live traffic; writes re-route per-row, reads re-plan from refreshed
  locations).
- **Round 1** — the second seed tablet splits the same way while a
  FOLLOWER-heavy tserver is stopped and restarted mid-round (rolling
  restart under load: bootstrap replay + catch-up while the split's
  child tablets elect leaders).
- **Round 2** — every traffic-table leader is piled onto one tserver
  (stepdown skew), then forced ``master.rebalance`` passes walk the
  spread back under 2, one leader move per pass.

Invariants after every round (fault-sweep contract):

1. **No acked write lost** — every acknowledged SQL and Redis write is
   visible at its exact value; writes whose ack was lost to a restart
   hold either the old or attempted value, never anything else.
2. **No leaked residency pins** — ``hbm_cache().pinned_bytes() == 0``
   once quiesced (split forks/seeds must unwind their pins).
3. **MemTracker baseline** — the device subtree returns to its anchor
   (re-anchored after each committed split: child-tablet residency is
   legitimate; anything above it is a leak).

Final checks: at least ``min_splits`` splits and one leader move
actually happened mid-stream; the post-split full scan and the Q1/Q6
aggregates are byte-identical to a no-split CPU-oracle replay of the
same seed (the oracle dict IS that replay: the same seeded op stream
applied to a plain dict); per-protocol p50/p99 latency SLOs hold.
"""

from __future__ import annotations

import bisect
import json
import random
import threading
import time

from yugabyte_db_tpu.client.client import TabletOpFailed
from yugabyte_db_tpu.client.session import YBSession
from yugabyte_db_tpu.integration.mini_cluster import MiniCluster
from yugabyte_db_tpu.models.datatypes import DataType
from yugabyte_db_tpu.models.schema import ColumnKind, ColumnSchema
from yugabyte_db_tpu.storage.residency import hbm_cache
from yugabyte_db_tpu.storage.scan_spec import AggSpec, Predicate, ScanSpec
from yugabyte_db_tpu.utils.memtracker import root_tracker
from yugabyte_db_tpu.utils.metrics import count_swallowed
from yugabyte_db_tpu.utils.status import TabletSplit

PROTOCOLS = ("ycsb_a", "ycsb_b", "ycsb_e", "tpch", "redis")

# Cumulative protocol mix (rng.random() thresholds): A 30%, B 25%,
# E 15%, TPC-H 10%, Redis 20%.
_MIX = (("ycsb_a", 0.30), ("ycsb_b", 0.55), ("ycsb_e", 0.70),
        ("tpch", 0.80), ("redis", 1.00))

# Per-protocol p99 ceilings (seconds). Generous for CI: an op that
# lands in a split's seal->commit window legitimately spins on 50ms
# re-plan sleeps until the commit swap, and on a loaded CI box the
# whole seal->seed->commit protocol can take several seconds — these
# bound tail damage, not steady-state latency.
SLO_P99_S = {"ycsb_a": 10.0, "ycsb_b": 10.0, "ycsb_e": 20.0,
             "tpch": 20.0, "redis": 10.0}
SLO_P50_S = {p: 2.0 for p in PROTOCOLS}

ABSENT = object()


def _percentile(samples: list[float], q: float) -> float:
    if not samples:
        return 0.0
    s = sorted(samples)
    idx = min(len(s) - 1, int(q * len(s)))
    return s[idx]


class _Zipf:
    """Seeded zipfian sampler over ``n`` ranks (exponent ~0.99): the
    YCSB hot-key distribution, so splits land on genuinely skewed
    traffic rather than uniform keys."""

    def __init__(self, n: int, theta: float = 0.99):
        acc, self._cdf = 0.0, []
        for rank in range(1, n + 1):
            acc += 1.0 / rank ** theta
            self._cdf.append(acc)
        self._total = acc

    def sample(self, rng: random.Random) -> int:
        return bisect.bisect_left(self._cdf, rng.random() * self._total)


class TrafficSweep:
    """One seeded sweep: a MiniCluster with a TPU-engine traffic table
    plus the Redis service, a mixed open-loop workload, one cluster
    event per round, invariants + SLOs after each. ``run()`` returns
    the TRAFFIC_METRICS summary dict or raises AssertionError with
    every violation (prefixed by the seed)."""

    def __init__(self, data_root: str, seed: int, rounds: int = 3,
                 ops_per_round: int = 60, keyspace: int = 96,
                 num_tservers: int = 3, num_tablets: int = 2,
                 min_splits: int = 2):
        self.data_root = data_root
        self.seed = seed
        self.rounds = rounds
        self.ops_per_round = ops_per_round
        self.keys = [f"u{i:05d}" for i in range(keyspace)]
        self.num_tservers = num_tservers
        self.num_tablets = num_tablets
        self.min_splits = min_splits
        self.rng = random.Random(seed)
        self.zipf = _Zipf(keyspace)
        # SQL oracle: key -> last acked value; ambiguous: key -> set of
        # acceptable values while an ack was lost (fault-sweep contract).
        self.oracle: dict[str, object] = {}
        self.ambiguous: dict[str, set] = {}
        # Redis oracle (its own keyspace in the redis table).
        self.r_oracle: dict[str, object] = {}
        self.r_ambiguous: dict[str, set] = {}
        self._next_value = 0
        self.latencies: dict[str, list[float]] = {p: [] for p in PROTOCOLS}
        self.ops_done: dict[str, int] = {p: 0 for p in PROTOCOLS}
        # Ops that timed out client-side (split stalled past the
        # re-plan deadline by a concurrent restart, or every replica
        # of a tablet unreachable). Bounded in _final_checks.
        self.aborted: dict[str, int] = {p: 0 for p in PROTOCOLS}
        self.splits: list[dict] = []
        self.leader_moves: list[dict] = []
        self.errors: list[str] = []
        self.mc: MiniCluster | None = None
        self.client = None
        self.table = None
        self.redis = None

    # -- lifecycle -----------------------------------------------------------

    def setup(self) -> None:
        from yugabyte_db_tpu.yql.redis.server import RedisServiceImpl

        self.mc = MiniCluster(
            self.data_root, num_tservers=self.num_tservers,
            engine_options={"breaker_cooldown_s": 0.05,
                            "breaker_failure_threshold": 1}).start()
        self.mc.wait_tservers_registered()
        self.client = self.mc.client()
        self.client.create_table("traffic", [
            ColumnSchema("k", DataType.STRING, ColumnKind.HASH),
            ColumnSchema("v", DataType.INT64)],
            num_tablets=self.num_tablets, engine="tpu")
        self.table = self.client.open_table("traffic")
        self.redis = RedisServiceImpl(self.mc.client("traffic-redis"),
                                      num_tablets=2)
        # Pre-fill so the first split has a populated median to cut at.
        s = YBSession(self.client)
        for k in self.keys:
            v = self._bump_value()
            s.insert(self.table, {"k": k, "v": v})
            self.oracle[k] = v
        s.flush()
        self._flush_tablets()
        self._scan_cluster()  # warm the device path
        self._anchor_baseline()
        # The two seed tablets, in partition order: the rounds split
        # them one per round while traffic runs.
        locs = self.client.meta_cache.locations("traffic", refresh=True)
        self.seed_tablets = [t.tablet_id for t in locs.tablets]

    def teardown(self) -> None:
        if self.mc is not None:
            self.mc.shutdown()
            self.mc = None

    def run(self) -> dict:
        self.setup()
        try:
            t0 = time.monotonic()
            for rnd in range(self.rounds):
                self._run_round(rnd)
                self.errors.extend(
                    f"round {rnd} (seed {self.seed}): {e}"
                    for e in self.check_invariants())
            self._traffic_s = time.monotonic() - t0
            self.errors.extend(f"final (seed {self.seed}): {e}"
                               for e in self._final_checks())
            if self.errors:
                raise AssertionError(
                    "traffic sweep invariants violated:\n  "
                    + "\n  ".join(self.errors))
            return self._metrics()
        finally:
            self.teardown()

    # -- rounds --------------------------------------------------------------

    def _run_round(self, rnd: int) -> None:
        splitter = None
        event_at = self.ops_per_round // 3
        restart_at = (2 * self.ops_per_round) // 3
        victim = None
        for i in range(self.ops_per_round):
            if i == event_at:
                if rnd < min(2, len(self.seed_tablets)):
                    splitter = self._fire_split(self.seed_tablets[rnd])
                elif rnd == 2:
                    self._skew_and_rebalance()
            if rnd == 1 and i == restart_at:
                victim = self._stop_follower_heavy()
            self._one_op()
        if victim is not None:
            self.mc.restart_tserver(victim)
            self.mc.wait_tservers_registered()
        if splitter is not None:
            splitter.join(timeout=60.0)
            # Child tablets bring their own (legitimate) device
            # residency: re-anchor so the baseline check measures
            # leaks, not the split.
            self._anchor_baseline()

    def _fire_split(self, tablet_id: str) -> threading.Thread:
        """Split ``tablet_id`` through the admin RPC from a background
        thread — the protocol races the op loop's live traffic."""

        def run():
            try:
                resp = self.client.master_rpc(
                    "master.split_tablet",
                    {"table": "traffic", "tablet_id": tablet_id,
                     "timeout": 45.0}, timeout_s=55.0)
            except Exception as e:  # noqa: BLE001 — surfaced as a failure
                self.errors.append(f"split {tablet_id} died: {e!r}")
                return
            if resp.get("code") != "ok":
                self.errors.append(f"split {tablet_id} failed: {resp}")
                return
            self.splits.append({"parent": tablet_id,
                                "children": resp.get("children", [])})

        t = threading.Thread(target=run, name=f"split-{tablet_id}",
                             daemon=True)
        t.start()
        return t

    def _stop_follower_heavy(self) -> str:
        """Stop the tserver holding the FEWEST leaders (a follower-heavy
        roll: quorum holds, in-flight ops retry through live leaders)."""
        counts = {
            uuid: sum(1 for p in ts.tablet_manager.peers()
                      if p.is_leader())
            for uuid, ts in self.mc.tservers.items()}
        victim = min(counts, key=counts.get)
        self.mc.stop_tserver(victim)
        return victim

    def _skew_and_rebalance(self) -> None:
        """Pile every traffic-table leader onto one tserver, then let
        forced balancer passes walk the spread back under 2 — each pass
        moves at most one leader (the churn bound)."""
        target = self.mc.tserver_uuids[0]
        locs = self.client.meta_cache.locations("traffic", refresh=True)
        for t in locs.tablets:
            leader = t.leader
            if leader == target or target not in t.replicas:
                continue
            try:
                resp = self.client.transport.send(
                    leader or t.replicas[0], "ts.transfer_leadership",
                    {"tablet_id": t.tablet_id, "target": target},
                    timeout=5.0)
                if resp.get("code") != "ok":
                    count_swallowed("traffic.skew_transfer",
                                    resp.get("code"))
            except Exception as e:  # noqa: BLE001 — skew is best-effort
                count_swallowed("traffic.skew_transfer", e)
        deadline = time.monotonic() + 45.0
        while time.monotonic() < deadline:
            resp = self.client.master_rpc("master.rebalance", {},
                                          timeout_s=10.0)
            move = resp.get("move")
            if move:
                self.leader_moves.append(move)
            elif self.leader_moves:
                return  # balanced: spread walked back under 2
            # Pace to the heartbeat interval either way: the balancer's
            # skew input is heartbeat-fed, so a tight loop would keep
            # re-moving against a stale count.
            time.sleep(0.3)
        if not self.leader_moves:
            self.errors.append("rebalance made no leader move")

    # -- one op --------------------------------------------------------------

    def _one_op(self) -> None:
        r = self.rng.random()
        for proto, ceil in _MIX:
            if r < ceil:
                break
        t0 = time.monotonic()
        try:
            getattr(self, "_op_" + proto)()
        except (TabletOpFailed, TabletSplit) as e:
            # Client-visible timeout: a restart landing mid-split can
            # stall the seal->commit window past the re-plan deadline,
            # and a real client's op times out. Reads return nothing
            # to check; writes that got this far never reached flush
            # (flush failures are already recorded as ambiguous by the
            # op itself). Count it — SLOs measure completed ops, and
            # _final_checks bounds the abort fraction so a systemic
            # outage still fails the sweep.
            self.aborted[proto] += 1
            count_swallowed("traffic.op_aborted", e)
            return
        dt = time.monotonic() - t0
        self.latencies[proto].append(dt)
        self.ops_done[proto] += 1

    def _zkey(self) -> str:
        return self.keys[self.zipf.sample(self.rng)]

    def _op_ycsb_a(self) -> None:
        self._kv_op(read_ratio=0.5)

    def _op_ycsb_b(self) -> None:
        self._kv_op(read_ratio=0.95)

    def _kv_op(self, read_ratio: float) -> None:
        k = self._zkey()
        if self.rng.random() < read_ratio:
            row = YBSession(self.client).get(self.table, {"k": k})
            actual = row[1] if row else ABSENT
            acceptable = self.ambiguous.get(k) or {
                self.oracle.get(k, ABSENT)}
            if actual not in acceptable:
                self.errors.append(
                    f"read {k} = "
                    f"{'ABSENT' if actual is ABSENT else actual}, "
                    f"acceptable {sorted(map(str, acceptable))}")
            return
        v = self._bump_value()
        s = YBSession(self.client)
        s.insert(self.table, {"k": k, "v": v})
        try:
            s.flush()
        except Exception:  # noqa: BLE001 — ack lost; outcome ambiguous
            self.ambiguous[k] = {self._current(k), v}
            return
        self.oracle[k] = v
        self.ambiguous.pop(k, None)

    def _op_ycsb_e(self) -> None:
        if self.rng.random() < 0.05:
            self._kv_op(read_ratio=0.0)
            return
        res = YBSession(self.client).scan(
            self.table, ScanSpec(projection=["k", "v"], limit=10))
        if not res.rows:
            self.errors.append("ycsb_e: empty first page on a "
                               "pre-filled table")

    def _op_tpch(self) -> None:
        spec = self._tpch_spec(self.rng.random() < 0.5)
        res = YBSession(self.client).scan(self.table, spec)
        if not res.rows:
            self.errors.append("tpch: aggregate returned no row")

    def _tpch_spec(self, q1: bool) -> ScanSpec:
        if q1:  # Q1 shape: full-table sum/count/avg
            return ScanSpec(aggregates=[
                AggSpec("sum", "v"), AggSpec("count", None),
                AggSpec("avg", "v")])
        # Q6 shape: sum under a selective range predicate
        return ScanSpec(
            predicates=[Predicate("v", ">=", self._next_value // 2)],
            aggregates=[AggSpec("sum", "v"), AggSpec("count", None)])

    def _op_redis(self) -> None:
        k = "r" + self._zkey()
        if self.rng.random() < 0.5:
            reply = self.redis.handle([b"GET", k.encode()])
            actual = self._resp_bulk(reply)
            acceptable = self.r_ambiguous.get(k) or {
                self.r_oracle.get(k, ABSENT)}
            if actual not in acceptable:
                self.errors.append(
                    f"redis GET {k} = {actual!r}, acceptable "
                    f"{sorted(map(str, acceptable))}")
            return
        v = str(self._bump_value())
        try:
            reply = self.redis.handle([b"SET", k.encode(), v.encode()])
        except (TabletOpFailed, TabletSplit):
            # The SET may or may not have applied before the timeout —
            # record the ambiguity, then let _one_op count the abort.
            self.r_ambiguous[k] = {self._r_current(k), v}
            raise
        if reply.startswith(b"+OK"):
            self.r_oracle[k] = v
            self.r_ambiguous.pop(k, None)
        else:
            self.r_ambiguous[k] = {self._r_current(k), v}

    @staticmethod
    def _resp_bulk(reply: bytes):
        """Decode a RESP bulk-string reply (``$-1`` -> ABSENT)."""
        if reply.startswith(b"$-1"):
            return ABSENT
        if not reply.startswith(b"$"):
            return f"<resp {reply[:40]!r}>"
        body = reply.split(b"\r\n", 1)[1]
        return body[: int(reply[1:reply.index(b"\r")])].decode()

    def _current(self, k: str):
        amb = self.ambiguous.get(k)
        return next(iter(amb)) if amb else self.oracle.get(k, ABSENT)

    def _r_current(self, k: str):
        amb = self.r_ambiguous.get(k)
        return next(iter(amb)) if amb else self.r_oracle.get(k, ABSENT)

    def _bump_value(self) -> int:
        self._next_value += 1
        return self._next_value

    # -- cluster access ------------------------------------------------------

    def _scan_cluster(self) -> dict:
        res = YBSession(self.client).scan(
            self.table, ScanSpec(projection=["k", "v"]))
        return dict(res.rows)

    def _flush_tablets(self) -> None:
        for ts in self.mc.tservers.values():
            for peer in ts.tablet_manager.peers():
                peer.flush()

    def _quiesce_device(self) -> None:
        for ts in self.mc.tservers.values():
            for peer in ts.tablet_manager.peers():
                eng = peer.tablet.engine
                if hasattr(eng, "_drop_overlay_cache"):
                    eng._drop_overlay_cache()
            if hasattr(ts, "mesh_scan"):
                ts.mesh_scan.drop_stacks()
        hbm_cache().evict_unpinned()

    def _anchor_baseline(self) -> None:
        self._quiesce_device()
        self._device_baseline = root_tracker().child("device").consumption

    # -- invariants ----------------------------------------------------------

    def check_invariants(self) -> list[str]:
        errs = []
        errs.extend(self.check_acked_writes())
        errs.extend(self.check_residency_pins())
        errs.extend(self.check_memtracker_baseline())
        return errs

    def check_acked_writes(self) -> list[str]:
        got = self._scan_cluster()
        errs = []
        for k in self.keys:
            actual = got.get(k, ABSENT)
            acceptable = self.ambiguous.get(k) or {
                self.oracle.get(k, ABSENT)}
            if actual not in acceptable:
                errs.append(
                    f"acked write lost: {k} = "
                    f"{'ABSENT' if actual is ABSENT else actual}")
        for k in got:
            if k not in self.keys:
                errs.append(f"phantom row {k!r}")
        for k, v in self.r_oracle.items():
            if k in self.r_ambiguous:
                continue
            actual = self._resp_bulk(self.redis.handle([b"GET",
                                                        k.encode()]))
            if actual != v:
                errs.append(f"redis acked write lost: {k} = {actual!r}, "
                            f"want {v!r}")
        return errs

    def check_residency_pins(self) -> list[str]:
        self._quiesce_device()
        pinned = hbm_cache().pinned_bytes()
        external = self._external_bytes()
        if pinned > external:
            return [f"leaked residency pins: {pinned} pinned bytes "
                    f"({external} external)"]
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

    # -- final checks --------------------------------------------------------

    def _final_checks(self) -> list[str]:
        errs = []
        if len(self.splits) < self.min_splits:
            errs.append(f"only {len(self.splits)} splits fired "
                        f"(want >= {self.min_splits})")
        if self.rounds >= 3 and not self.leader_moves:
            errs.append("no leader move happened mid-stream")
        total = sum(self.ops_done.values())
        aborted = sum(self.aborted.values())
        if aborted > max(2, (total + aborted) // 5):
            errs.append(f"{aborted}/{total + aborted} ops aborted "
                        "(client-visible timeouts) — systemic, not a "
                        "split stall")
        errs.extend(self._check_oracle_identity())
        errs.extend(self._check_slos())
        return errs

    def _check_oracle_identity(self) -> list[str]:
        """Post-split results must be byte-identical to the no-split
        CPU-oracle replay of the same seed. The oracle dict IS that
        replay (the same seeded op stream applied to a plain dict), so:
        re-fix any ack-ambiguous key with a fresh acked write, then
        byte-compare the full scan AND the Q1/Q6 aggregates against
        oracle-computed answers."""
        errs = []
        for k in sorted(self.ambiguous):
            v = self._bump_value()
            s = YBSession(self.client)
            s.insert(self.table, {"k": k, "v": v})
            try:
                s.flush()
            except Exception as e:  # noqa: BLE001
                return [f"could not re-fix ambiguous key {k}: {e!r}"]
            self.oracle[k] = v
            self.ambiguous.pop(k, None)
        got = sorted(self._scan_cluster().items())
        want = sorted((k, v) for k, v in self.oracle.items()
                      if v is not ABSENT)
        if repr(got).encode() != repr(want).encode():
            miss = [k for k, v in want if dict(got).get(k) != v]
            errs.append(
                f"post-split scan diverged from CPU-oracle replay: "
                f"{len(got)} rows vs {len(want)} "
                f"(first mismatches {miss[:5]})")
        vals = [v for _k, v in want]
        q1 = YBSession(self.client).scan(
            self.table, self._tpch_q1()).rows
        q1_want = [(sum(vals), len(vals), sum(vals) / len(vals))]
        if repr(q1).encode() != repr(q1_want).encode():
            errs.append(f"Q1 aggregate diverged: {q1} vs oracle "
                        f"{q1_want}")
        cut = self._next_value // 2
        q6 = YBSession(self.client).scan(
            self.table, self._tpch_q6(cut)).rows
        hit = [v for v in vals if v >= cut]
        q6_want = [(sum(hit) if hit else None, len(hit))]
        if repr(q6).encode() != repr(q6_want).encode():
            errs.append(f"Q6 aggregate diverged: {q6} vs oracle "
                        f"{q6_want}")
        return errs

    @staticmethod
    def _tpch_q1() -> ScanSpec:
        return ScanSpec(aggregates=[AggSpec("sum", "v"),
                                    AggSpec("count", None),
                                    AggSpec("avg", "v")])

    @staticmethod
    def _tpch_q6(cut: int) -> ScanSpec:
        return ScanSpec(predicates=[Predicate("v", ">=", cut)],
                        aggregates=[AggSpec("sum", "v"),
                                    AggSpec("count", None)])

    def _check_slos(self) -> list[str]:
        errs = []
        for proto, samples in self.latencies.items():
            if not samples:
                continue
            p50 = _percentile(samples, 0.50)
            p99 = _percentile(samples, 0.99)
            if p50 > SLO_P50_S[proto]:
                errs.append(f"{proto} p50 {p50:.3f}s > SLO "
                            f"{SLO_P50_S[proto]}s")
            if p99 > SLO_P99_S[proto]:
                errs.append(f"{proto} p99 {p99:.3f}s > SLO "
                            f"{SLO_P99_S[proto]}s")
        return errs

    # -- reporting -----------------------------------------------------------

    def _metrics(self) -> dict:
        dur = max(getattr(self, "_traffic_s", 0.0), 1e-9)
        protos = {}
        for proto, samples in self.latencies.items():
            protos[proto] = {
                "ops": self.ops_done[proto],
                "ops_per_sec": round(self.ops_done[proto] / dur, 2),
                "p50_ms": round(_percentile(samples, 0.50) * 1e3, 3),
                "p99_ms": round(_percentile(samples, 0.99) * 1e3, 3),
            }
        return {"seed": self.seed, "rounds": self.rounds,
                "traffic_s": round(dur, 3),
                "ops_per_sec": round(sum(self.ops_done.values()) / dur, 2),
                "protocols": protos,
                "splits_fired": len(self.splits),
                "split_lineage": self.splits,
                "leader_moves": len(self.leader_moves),
                "aborted_ops": sum(self.aborted.values()),
                "keys": len(self.oracle) + len(self.r_oracle)}


def run_sweep(data_root: str, seed: int, **kwargs) -> dict:
    """Run one seeded traffic sweep; returns its TRAFFIC_METRICS dict."""
    return TrafficSweep(data_root, seed, **kwargs).run()


if __name__ == "__main__":  # replay a failing seed: python -m ... <seed>
    import sys
    import tempfile

    from yugabyte_db_tpu.utils.jitting import enable_compile_cache

    enable_compile_cache()
    with tempfile.TemporaryDirectory() as root:
        out = run_sweep(root, int(sys.argv[1]) if len(sys.argv) > 1
                        else 1234)
        print("TRAFFIC_METRICS " + json.dumps(out, sort_keys=True))
