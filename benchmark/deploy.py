"""The system under test, brought up as a configuration file describes it:
masters, tservers and the PG and CQL proxies in ONE process on one chip
(``integration/mini_cluster.py`` over loopback sockets), the only RF=3
topology one chip allows. Everything here calls the program; nothing
here judges it (the references do) or times it (the clients do).
"""

from __future__ import annotations

import os
import shutil
import tempfile
import time


class Deployment:
    def __init__(self, config: dict):
        self.cfg = config
        self.data_root = tempfile.mkdtemp(prefix="bench_data_")
        self.addr: dict[str, tuple] = {}

    # -- bring-up -----------------------------------------------------------
    def start(self) -> None:
        from yugabyte_db_tpu.consensus.raft import RaftOptions
        from yugabyte_db_tpu.integration.mini_cluster import MiniCluster
        from yugabyte_db_tpu.tools.admin_client import AdminClient

        c = self.cfg["cluster"]
        # The daemon's own Raft defaults (MiniCluster's are test-fast),
        # but for the tservers' failure detection, which the
        # configuration file gives with its reason.
        self.mc = MiniCluster(
            self.data_root, num_masters=c["masters"],
            num_tservers=c["tservers"], transport="socket",
            fsync=c["fsync"], raft_opts=RaftOptions(),
            engine_options=c.get("engine_options") or None)
        for uuid in self.mc.master_uuids:
            self.mc.start_master(uuid)
        self.mc.raft_opts = RaftOptions(
            election_timeout_s=c["tserver_election_timeout_s"])
        for uuid in self.mc.tserver_uuids:
            self.mc.start_tserver(uuid)
        self.mc.wait_tservers_registered()
        kw = dict(engine=c["engine"], num_tablets=c["tablets"],
                  replication_factor=c["replication_factor"],
                  rpc_timeout_s=c["proxy_rpc_timeout_s"])
        self.pg_server, self.addr["pg"] = self.mc.start_pg_server(**kw)
        self.cql_server, self.addr["cql"] = self.mc.start_cql_server(**kw)
        self.admin = AdminClient(self.mc.transport, self.mc.master_uuids)
        self.client = self.mc.client("bench-loader")

    def create_table(self, ddl: list, table: str) -> None:
        from benchmark.clients.minicql import CqlConnection
        from benchmark.clients.minipg import PgConnection

        for wire, statement in ddl:
            if wire == "pg":
                conn = PgConnection(*self.addr["pg"], timeout=60)
            else:
                conn = CqlConnection(*self.addr["cql"], timeout=60)
            try:
                conn.execute(statement)
            finally:
                conn.close()
        self.table_name = table
        self.table = self.client.open_table(table)
        if self.table.engine != self.cfg["cluster"]["engine"]:
            raise RuntimeError(f"table engine is {self.table.engine}")
        self.place_leaders()

    def tablets(self):
        locs = self.client.meta_cache.locations(self.table_name, refresh=True)
        return sorted(locs.tablets, key=lambda t: t.partition_start)

    def leaders_now(self) -> dict:
        return {t.tablet_id: t.leader for t in self.tablets()}

    def place_leaders(self) -> None:
        """Elections land where they land; runs must not differ by it.
        Tablet i's leader goes where the configuration says, by the RPC an
        operator has (yb_admin leader_stepdown)."""
        order = self.cfg["cluster"]["leaders"]
        want = {t.tablet_id: order[i % len(order)]
                for i, t in enumerate(self.tablets())}
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            wrong = [t for t in self.tablets()
                     if t.leader != want[t.tablet_id]]
            if not wrong:
                self.leader_map = want
                return
            for t in wrong:
                self.admin.leader_stepdown(t.tablet_id, want[t.tablet_id])
            time.sleep(1.0)
        raise RuntimeError("leaders did not settle where placed")

    # -- data ---------------------------------------------------------------
    def load(self, batches) -> int:
        """Rows through client batches: RF=3 Raft and fsync as any write."""
        from yugabyte_db_tpu.client.session import YBSession

        sess = YBSession(self.client)
        total = 0
        for rows in batches:
            for row in rows:
                sess.insert(self.table, row)
            n = sess.pending_ops
            acked = sess.flush(timeout_s=120.0)
            if acked != n:
                raise RuntimeError(f"session acked {acked} of {n} ops")
            total += n
        return total

    def peers(self):
        """(tserver uuid, peer) of this table's replicas."""
        ids = {t.tablet_id for t in self.tablets()}
        for uuid, ts in sorted(self.mc.tservers.items()):
            for p in ts.tablet_manager.peers():
                if p.tablet_id in ids:
                    yield uuid, p

    def flush(self, which: str) -> int:
        """``leaders``: yb_admin flush_table, which reaches leaders only.
        ``all``: the same ``ts.flush`` RPC sent to every replica, for a
        deployment whose followers flush on their own later on."""
        if which == "leaders":
            return self.admin.flush_table(self.table_name)
        self.wait_applied()
        n = 0
        for uuid, p in self.peers():
            resp = self.mc.transport.send(
                uuid, "ts.flush", {"tablet_id": p.tablet_id}, timeout=600.0)
            if resp.get("code") != "ok":
                raise RuntimeError(f"ts.flush on {uuid}: {resp}")
            n += 1
        return n

    def set_flags(self, flags: dict) -> None:
        from yugabyte_db_tpu.utils.flags import FLAGS

        for name, value in flags.items():
            FLAGS.set(name, value)

    def wait_applied(self, timeout_s: float = 120.0) -> None:
        """Until every replica has applied all its leader has."""
        deadline = time.monotonic() + timeout_s
        while True:
            by_tablet: dict[str, list] = {}
            for _uuid, p in self.peers():
                by_tablet.setdefault(p.tablet_id, []).append(p.raft.stats())
            lag = sum(max(s["last_index"] for s in ss) - s["applied_index"]
                      for ss in by_tablet.values() for s in ss)
            if lag == 0:
                return
            if time.monotonic() > deadline:
                raise RuntimeError(f"apply lag still {lag} ops")
            time.sleep(0.05)

    # -- what the readers and the verdict look at ---------------------------
    def registry_text(self) -> str:
        from yugabyte_db_tpu.utils.metrics import process_registry

        return process_registry().prometheus_text() + "".join(
            ts.metrics.prometheus_text() for ts in self.mc.tservers.values())

    def replica_state(self) -> list[str]:
        out = []
        for uuid, p in self.peers():
            st = p.tablet.engine.stats()
            out.append(
                f"{uuid} {p.tablet_id[-5:]} "
                f"{'leader' if p.is_leader() else 'follower'} "
                f"runs={st['num_runs']} run_versions={st['run_versions']} "
                f"memtable_versions={st['memtable_versions']} "
                f"device_bytes={st['device_bytes']}")
        return out

    def breaker_problems(self) -> list[str]:
        bad = []
        for uuid, p in self.peers():
            b = p.tablet.engine.breaker.stats()
            if b["trips"] or b["last_error"] is not None:
                bad.append(f"breaker {uuid}/{p.tablet_id[:8]}: {b}")
        return bad

    def read_from_replicas(self, key_values: list[dict]) -> dict:
        """{tserver uuid: {first key column: last value column}} read
        straight from each replica's engine (no leader, no lease): what
        that replica would serve if it were all that was left."""
        from yugabyte_db_tpu.models.encoding import prefix_successor
        from yugabyte_db_tpu.storage.scan_spec import ScanSpec

        self.wait_applied()
        out: dict[str, dict] = {}
        for uuid, p in self.peers():
            rows = out.setdefault(uuid, {})
            for kv in key_values:
                key = self.table.encode_key(kv)
                res = p.tablet.engine.scan(ScanSpec(
                    lower=key, upper=prefix_successor(key), limit=1))
                for r in res.rows:
                    rows[r[0]] = r[-1]
        return out

    def stop(self) -> None:
        for s in (self.cql_server, self.pg_server):
            s.shutdown()
        self.mc.shutdown()
        shutil.rmtree(self.data_root, ignore_errors=True)


def build_native(root: str) -> float:
    """``make`` the native modules only when a ``.so`` is missing or older
    than its source; returns the seconds it took."""
    import glob
    import subprocess
    import sys

    t0 = time.perf_counter()
    src = glob.glob(os.path.join(root, "native", "*.cc")) \
        + glob.glob(os.path.join(root, "native", "*.h")) \
        + [os.path.join(root, "native", "Makefile")]
    built = glob.glob(os.path.join(root, "yugabyte_db_tpu", "native",
                                   "yb_*.so"))
    newest_src = max(os.path.getmtime(p) for p in src if os.path.exists(p))
    if len(built) < 3 or min(map(os.path.getmtime, built)) < newest_src:
        subprocess.run(["make", "-C", os.path.join(root, "native"),
                        f"PY={sys.executable}"], check=True,
                       stdout=subprocess.DEVNULL)
    return time.perf_counter() - t0
