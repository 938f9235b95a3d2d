"""AdminClient: the cluster-administration RPC surface behind yb-admin.

Reference analog: src/yb/tools/yb-admin_client.cc (ClusterAdminClient) —
list tables/tablets/tservers, change a tablet's Raft config, leader
stepdown, flush/compact, delete table — over the same master/tserver
RPCs the regular client uses.
"""

from __future__ import annotations

import time

from yugabyte_db_tpu.consensus.transport import TransportError


class AdminError(Exception):
    pass


# Flush and compaction run for as long as the tablet's data takes, and
# compile device programs the first time: a control RPC's budget (10 s,
# 3 s a send) is not theirs. Timing one out reads as "leader down", and
# the failover then sends the same maintenance op to the followers.
MAINTENANCE_RPC_TIMEOUT_S = 2000.0     # 600 s a send


class AdminClient:
    """Thin admin wrapper over a cluster Transport.

    Works over both the in-process LocalTransport (tests) and
    SocketTransport (real daemons); ``connect()`` bootstraps the latter
    from a master address the way yb-admin's -master_addresses does.
    """

    def __init__(self, transport, master_uuids: list[str]):
        self.transport = transport
        self.master_uuids = list(master_uuids)

    @classmethod
    def connect(cls, master_addrs: str) -> "AdminClient":
        """Bootstrap over TCP from comma-separated master ``host:port``
        addresses (yb-admin's -master_addresses). Pass ALL masters of a
        multi-master cluster so the leader is reachable whichever node
        holds it; tserver addresses are learned from the master's
        registry."""
        from yugabyte_db_tpu.rpc import SocketTransport

        transport = SocketTransport()
        uuids = []
        for addr in master_addrs.split(","):
            addr = addr.strip()
            if not addr:
                continue
            if ":" not in addr:
                raise AdminError(f"bad master address {addr!r} "
                                 "(want host:port)")
            host, port = addr.rsplit(":", 1)
            boot_uuid = f"master@{addr}"
            transport.set_address(boot_uuid, host, int(port))
            uuids.append(boot_uuid)
        if not uuids:
            raise AdminError("no master addresses given")
        c = cls(transport, uuids)
        c.refresh_addresses()
        return c

    def refresh_addresses(self) -> None:
        """Learn tserver uuid -> address mappings (socket mode)."""
        if not hasattr(self.transport, "set_address"):
            return
        for d in self.list_tservers():
            addr = d.get("addr")
            if isinstance(addr, (list, tuple)) and len(addr) == 2:
                self.transport.set_address(d["uuid"], addr[0], int(addr[1]))

    # -- master RPCs ---------------------------------------------------------
    def master_rpc(self, method: str, payload: dict | None = None,
                   timeout_s: float = 10.0,
                   send_timeout_s: float = 2.0) -> dict:
        """Try masters until one answers as leader (yb-admin's leader
        master discovery loop). One send waits ``send_timeout_s``: 2 s
        moves on from a dead master quickly. A request the master works
        on for longer must pass its own budget, because this loop sends
        again after a send that timed out, while the first request may
        still be running or have committed."""
        deadline = time.monotonic() + timeout_s
        last = None
        while time.monotonic() < deadline:
            for m in list(self.master_uuids):
                try:
                    resp = self.transport.send(m, method, payload or {},
                                               timeout=send_timeout_s)
                except TransportError as e:
                    last = str(e)
                    continue
                if resp.get("code") == "not_leader":
                    hint = resp.get("leader_hint")
                    if hint and hint in self.master_uuids:
                        self.master_uuids.remove(hint)
                        self.master_uuids.insert(0, hint)
                    last = "not_leader"
                    continue
                return resp
            time.sleep(0.1)
        raise AdminError(f"no leader master answered {method}: {last}")

    def list_tables(self) -> list[dict]:
        return self.master_rpc("master.list_tables")["tables"]

    def list_tservers(self) -> list[dict]:
        return self.master_rpc("master.list_tservers")["tservers"]

    def table_locations(self, table: str) -> list[dict]:
        resp = self.master_rpc("master.get_table_locations",
                               {"name": table})
        if resp.get("code") != "ok":
            raise AdminError(f"table {table}: {resp.get('code')}")
        # Socket mode: keep the address book current with the replica
        # addresses the master reports (covers tservers that joined after
        # connect()).
        if hasattr(self.transport, "set_address"):
            for t in resp["tablets"]:
                for r in t["replicas"]:
                    addr = r.get("addr")
                    if isinstance(addr, (list, tuple)) and len(addr) == 2:
                        self.transport.set_address(r["uuid"], addr[0],
                                                   int(addr[1]))
        return resp["tablets"]

    def delete_table(self, table: str) -> None:
        resp = self.master_rpc("master.delete_table", {"name": table})
        if resp.get("code") != "ok":
            raise AdminError(f"delete {table}: {resp.get('code')}")

    def split_tablet(self, table: str, tablet_id: str,
                     timeout_s: float = 30.0) -> dict:
        """Manually split one tablet at its median resident key
        (yb-admin split_tablet): the master drives the whole seal →
        fork → seed → commit protocol and answers with the child
        tablet ids. The one send waits as long as the master may take:
        sent again after 2 s, a split still running is refused as
        "already running" and one that has committed as ``not_found``
        (the parent has left the catalog)."""
        resp = self.master_rpc("master.split_tablet",
                               {"table": table, "tablet_id": tablet_id,
                                "timeout": timeout_s},
                               timeout_s=timeout_s + 5.0,
                               send_timeout_s=timeout_s + 5.0)
        if resp.get("code") != "ok":
            raise AdminError(
                f"split_tablet {tablet_id}: "
                f"{resp.get('message', resp.get('code'))}")
        return resp

    def rebalance(self) -> dict:
        """Run one forced leader-balancing pass on the master
        (yb-admin's rebalance trigger); returns the move made (if any)
        plus the per-tserver leader counts."""
        resp = self.master_rpc("master.rebalance", {})
        if resp.get("code") != "ok":
            raise AdminError(
                f"rebalance: {resp.get('message', resp.get('code'))}")
        return resp

    def locate_tablet(self, tablet_id: str) -> dict:
        resp = self.master_rpc("master.locate_tablet",
                               {"tablet_id": tablet_id})
        if resp.get("code") != "ok":
            raise AdminError(f"tablet {tablet_id}: {resp.get('code')}")
        return resp

    # -- tserver RPCs --------------------------------------------------------
    def _leader_rpc(self, tablet_id: str, method: str, payload: dict,
                    timeout_s: float = 10.0) -> dict:
        """Send to the tablet's leader, following not_leader hints and
        failing over to other replicas when the reported leader is down
        (re-fetching the location each round — it may have moved). One
        send may take 0.3 of the budget, so a leader that is down leaves
        time to reach the next replica."""
        deadline = time.monotonic() + timeout_s
        rpc_timeout_s = 0.3 * timeout_s
        last = "unreachable"
        while True:
            loc = self.locate_tablet(tablet_id)
            hint = loc.get("leader")
            candidates = ([hint] if hint else []) + [
                r for r in loc["replicas"] if r != hint
            ]
            for target in candidates:
                try:
                    resp = self.transport.send(target, method, payload,
                                               timeout=rpc_timeout_s)
                except TransportError as e:
                    last = str(e)
                    continue
                if resp.get("code") == "not_leader":
                    last = "not_leader"
                    h = resp.get("leader_hint")
                    already = candidates[:candidates.index(target)]
                    if (h and h != target and h in loc["replicas"]
                            and h not in already):
                        try:
                            resp = self.transport.send(h, method, payload,
                                                       timeout=rpc_timeout_s)
                            if resp.get("code") != "not_leader":
                                return resp
                        except TransportError as e:
                            last = str(e)
                    continue
                return resp
            if time.monotonic() >= deadline:
                raise AdminError(
                    f"{method} on {tablet_id}: no leader reachable ({last})")
            time.sleep(0.2)

    def change_config(self, tablet_id: str, peers: list[str]) -> None:
        resp = self._leader_rpc(tablet_id, "ts.change_config",
                                {"tablet_id": tablet_id, "peers": peers})
        if resp.get("code") != "ok":
            raise AdminError(f"change_config: {resp.get('code')}")

    def leader_stepdown(self, tablet_id: str, target: str) -> None:
        resp = self._leader_rpc(tablet_id, "ts.transfer_leadership",
                                {"tablet_id": tablet_id, "target": target})
        if resp.get("code") != "ok":
            raise AdminError(f"leader_stepdown: {resp.get('code')}")

    def _maintenance(self, table: str, method: str, **payload) -> int:
        n = 0
        for t in self.table_locations(table):
            resp = self._leader_rpc(
                t["tablet_id"], method,
                dict(payload, tablet_id=t["tablet_id"]),
                timeout_s=MAINTENANCE_RPC_TIMEOUT_S)
            if resp.get("code") != "ok":
                raise AdminError(f"{method} on {t['tablet_id']}: "
                                 f"{resp.get('message', resp.get('code'))}")
            n += 1
        return n

    def flush_table(self, table: str) -> int:
        return self._maintenance(table, "ts.flush")

    def compact_table(self, table: str, history_cutoff_ht: int = 0) -> int:
        return self._maintenance(table, "ts.compact",
                                 history_cutoff_ht=history_cutoff_ht)

    def snapshot_table(self, table: str, snapshot_id: str,
                       op: str = "create_snapshot") -> int:
        """Run a snapshot op (create/restore/delete) on every tablet of a
        table (reference: the snapshot RPCs of backup.proto driven by
        yb-admin create_snapshot)."""
        n = 0
        for t in self.table_locations(table):
            resp = self._leader_rpc(t["tablet_id"], "ts.snapshot_op",
                                    {"tablet_id": t["tablet_id"],
                                     "snapshot_id": snapshot_id, "op": op})
            if resp.get("code") != "ok":
                raise AdminError(
                    f"{op} {snapshot_id} on {t['tablet_id']}: "
                    f"{resp.get('message', resp.get('code'))}")
            n += 1
        return n

    def cluster_snapshot(self, action: str, table: str | None = None,
                         snapshot_id: str | None = None) -> dict:
        """Master-coordinated cluster snapshot (yb-admin
        create_snapshot / restore_snapshot / delete_snapshot /
        list_snapshots): the MASTER fans the per-tablet ops and tracks
        the snapshot's state in the replicated sys catalog."""
        payload = {"action": action}
        if table is not None:
            payload["table"] = table
        if snapshot_id is not None:
            payload["snapshot_id"] = snapshot_id
        resp = self.master_rpc("master.snapshot_op", payload)
        if resp.get("code") != "ok":
            raise AdminError(
                f"snapshot {action}: "
                f"{resp.get('message', resp.get('code'))}")
        return resp

    def list_snapshots(self, table: str) -> dict[str, list[str]]:
        out = {}
        for t in self.table_locations(table):
            resp = self._leader_rpc(t["tablet_id"], "ts.list_snapshots",
                                    {"tablet_id": t["tablet_id"]})
            out[t["tablet_id"]] = resp.get("snapshots", [])
        return out

    def tserver_status(self, uuid: str) -> dict:
        return self.transport.send(uuid, "ts.status", {}, timeout=3.0)

    def checksum(self, tablet_id: str, replica: str,
                 read_ht: int | None = None) -> dict:
        payload = {"tablet_id": tablet_id}
        if read_ht is not None:
            payload["read_ht"] = read_ht
        return self.transport.send(replica, "ts.checksum", payload,
                                   timeout=15.0)
