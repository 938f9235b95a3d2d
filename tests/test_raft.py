"""Raft consensus tests: election, replication, failover, divergence,
restart recovery, membership change.

Reference test analog: src/yb/consensus/raft_consensus-test.cc and
raft_consensus-itest.cc (kill/restart via ExternalMiniCluster; here via
LocalTransport isolation — same black-box effect, one process).
"""

import time

import pytest

from yugabyte_db_tpu.consensus import (LocalTransport, NotLeader, RaftOptions)
from yugabyte_db_tpu.models.datatypes import DataType
from yugabyte_db_tpu.models.partition import compute_hash_code
from yugabyte_db_tpu.models.schema import ColumnKind, ColumnSchema, Schema
from yugabyte_db_tpu.storage import RowVersion, ScanSpec
from yugabyte_db_tpu.tablet import TabletMetadata, TabletPeer

FAST = RaftOptions(election_timeout_s=0.15, heartbeat_interval_s=0.03,
                   lease_s=0.4, rpc_timeout_s=0.5)


def make_schema():
    return Schema([
        ColumnSchema("k", DataType.STRING, ColumnKind.HASH),
        ColumnSchema("v", DataType.INT64),
    ], table_id="t")


def enc(schema, k):
    return schema.encode_primary_key({"k": k}, compute_hash_code(schema, {"k": k}))


def wait_for(pred, timeout=5.0, interval=0.01, msg="condition"):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        v = pred()
        if v:
            return v
        time.sleep(interval)
    raise AssertionError(f"timed out waiting for {msg}")


class Group:
    """A 3-replica (by default) raft group over a LocalTransport."""

    def __init__(self, tmp_path, n=3, engine="cpu"):
        self.schema = make_schema()
        self.transport = LocalTransport()
        self.tmp_path = tmp_path
        self.nodes = [f"node-{i}" for i in range(n)]
        self.peers = {}
        for uuid in self.nodes:
            self.start_node(uuid)

    def start_node(self, uuid):
        meta = TabletMetadata("tablet-1", "t", self.schema, 0, 65536)
        root = str(self.tmp_path / uuid)
        peer = TabletPeer(uuid, meta, root, self.transport.bind(uuid),
                          self.nodes, fsync=False, raft_opts=FAST)
        self.transport.register(uuid, lambda m, p, _pr=peer: _pr.raft.handle(m, p))
        self.peers[uuid] = peer
        peer.start()
        return peer

    def stop_node(self, uuid):
        self.transport.unregister(uuid)
        self.peers.pop(uuid).shutdown()

    def leader(self):
        return wait_for(
            lambda: next((p for p in self.peers.values()
                          if p.raft.is_leader() and p.raft.has_lease()), None),
            msg="leader election")

    def shutdown(self):
        for p in list(self.peers.values()):
            p.shutdown()

    def row(self, k, v):
        cid = {c.name: c.col_id for c in self.schema.columns}
        return RowVersion(enc(self.schema, k), ht=0, liveness=True,
                          columns={cid["v"]: v})

    def read_all(self, peer):
        res = peer.scan(ScanSpec(read_ht=peer.tablet.clock.now().value),
                        allow_stale=True)
        return sorted(res.rows)


@pytest.fixture
def group(tmp_path):
    g = Group(tmp_path)
    yield g
    g.shutdown()


def test_elects_single_leader_and_replicates(group):
    leader = group.leader()
    for i in range(20):
        leader.write([group.row(f"k{i}", i)])
    want = group.read_all(leader)
    assert len(want) == 20
    for uuid, p in group.peers.items():
        wait_for(lambda p=p: p.raft.stats()["applied_index"]
                 >= leader.raft.stats()["applied_index"],
                 msg=f"{uuid} catchup")
        assert group.read_all(p) == want


def test_only_leader_accepts_writes(group):
    leader = group.leader()
    follower = next(p for p in group.peers.values() if p is not leader)
    with pytest.raises(NotLeader) as ei:
        follower.write([group.row("x", 1)])
    assert ei.value.leader_hint == leader.node_uuid


def test_leader_failover_and_rejoin(group):
    leader = group.leader()
    leader.write([group.row("a", 1)])
    group.transport.isolate(leader.node_uuid)
    new_leader = wait_for(
        lambda: next((p for p in group.peers.values()
                      if p is not leader and p.raft.is_leader()
                      and p.raft.has_lease()), None),
        msg="new leader after isolation")
    new_leader.write([group.row("b", 2)])
    # Old leader no longer holds a lease, so it refuses reads.
    wait_for(lambda: not leader.raft.has_lease(), msg="old lease expiry")
    with pytest.raises(NotLeader):
        leader.scan(ScanSpec())
    # Heal: old leader steps down to follower and catches up.
    group.transport.heal(leader.node_uuid)
    wait_for(lambda: not leader.raft.is_leader(), msg="old leader steps down")
    wait_for(lambda: group.read_all(leader) == group.read_all(new_leader),
             msg="old leader catches up")
    assert len(group.read_all(leader)) == 2


def test_divergent_suffix_truncated(group):
    """A partitioned leader's uncommitted writes are erased on rejoin."""
    leader = group.leader()
    leader.write([group.row("committed", 1)])
    others = [p for p in group.peers.values() if p is not leader]
    group.transport.isolate(leader.node_uuid)
    # This write can't commit (no majority): it lands in the old leader's
    # log only. Use a short timeout.
    with pytest.raises((TimeoutError, NotLeader)):
        leader.write([group.row("orphan", 9)], timeout=0.4)
    new_leader = wait_for(
        # (ready: a leader takes writes once its own term's no-op has
        # applied, some milliseconds after the election under load)
        lambda: next((p for p in others if p.raft.is_leader()
                      and p.raft.leader_ready()), None),
        msg="new leader")
    new_leader.write([group.row("winner", 2)])
    group.transport.heal(leader.node_uuid)
    wait_for(lambda: sorted(group.read_all(leader))
             == sorted(group.read_all(new_leader)),
             msg="rejoined log convergence")
    keys = group.read_all(leader)
    assert len(keys) == 2  # committed + winner, no orphan


def test_restart_recovers_data(group):
    leader = group.leader()
    for i in range(10):
        leader.write([group.row(f"k{i}", i)])
    want = group.read_all(leader)
    for uuid in list(group.peers):
        group.stop_node(uuid)
    for uuid in group.nodes:
        group.start_node(uuid)
    leader2 = group.leader()
    assert group.read_all(leader2) == want


def test_change_config_add_then_remove(group, tmp_path):
    leader = group.leader()
    for i in range(5):
        leader.write([group.row(f"k{i}", i)])
    # Add a fourth, empty peer; it must catch up from index 1.
    new_uuid = "node-3"
    meta = TabletMetadata("tablet-1", "t", group.schema, 0, 65536)
    new_peer = TabletPeer(new_uuid, meta, str(tmp_path / new_uuid),
                          group.transport.bind(new_uuid),
                          group.nodes + [new_uuid], fsync=False,
                          raft_opts=FAST)
    group.transport.register(new_uuid,
                             lambda m, p: new_peer.raft.handle(m, p))
    group.peers[new_uuid] = new_peer
    new_peer.start()
    leader.raft.change_config(group.nodes + [new_uuid])
    wait_for(lambda: group.read_all(new_peer) == group.read_all(leader),
             msg="new peer catchup")
    assert leader.raft.stats()["config"]["peers"] == group.nodes + [new_uuid]
    # Remove it again; it stops being part of majorities.
    leader.raft.change_config(group.nodes)
    wait_for(lambda: leader.raft.stats()["config"]["peers"] == group.nodes,
             msg="config shrink commit")
    leader.write([group.row("after-shrink", 7)])


def test_rf1_instant_leadership(tmp_path):
    g = Group(tmp_path, n=1)
    try:
        leader = g.leader()
        # Writes are accepted once the own-term no-op applies
        # (leader_ready) — the exactly-once dedup registry completeness
        # guarantee; briefly rejected writes surface as NotLeader, which
        # cluster clients retry.
        deadline = time.monotonic() + 5.0
        while True:
            try:
                leader.write([g.row("solo", 1)])
                break
            except NotLeader:
                assert time.monotonic() < deadline
                time.sleep(0.01)
        assert len(g.read_all(leader)) == 1
    finally:
        g.shutdown()


def test_no_progress_without_majority(group):
    leader = group.leader()
    for p in group.peers.values():
        if p is not leader:
            group.transport.isolate(p.node_uuid)
    with pytest.raises((TimeoutError, NotLeader)):
        leader.write([group.row("stuck", 1)], timeout=0.4)
    group.transport.heal()
    # After healing, the group makes progress again (any leader).
    def can_write():
        for p in group.peers.values():
            try:
                p.write([group.row("ok", 2)], timeout=1.0)
                return True
            except (NotLeader, TimeoutError):
                continue
        return False
    wait_for(can_write, timeout=10.0, msg="post-heal write")


def test_message_borne_lease_expires_when_isolated(group):
    """The leader holds its lease only while a majority's explicit
    grants (shipped in AppendEntries, echoed in acks) are running;
    isolating it must drop has_lease within one lease window
    (reference: leader_lease.h message-borne leases)."""
    leader = group.leader()
    assert leader.raft.has_lease()
    group.transport.isolate(leader.node_uuid)
    # grants were measured from send time: within effective_lease_s the
    # isolated leader must stop serving lease reads
    wait_for(lambda: not leader.raft.has_lease(), timeout=3.0,
             msg="lease expiry after isolation")
    # and the remaining majority elects a replacement only AFTER their
    # promises to the old leader expired — there is never a moment with
    # two lease-holding leaders
    new = wait_for(
        lambda: next((p for p in group.peers.values()
                      if p.node_uuid != leader.node_uuid
                      and p.raft.is_leader() and p.raft.has_lease()),
                     None), timeout=5.0, msg="replacement leader")
    assert not leader.raft.has_lease()
    group.transport.heal()
    wait_for(lambda: not leader.raft.is_leader(), timeout=5.0,
             msg="old leader steps down")
    assert new.raft.has_lease()


def test_wall_clock_jump_does_not_affect_leases_or_order(group, monkeypatch):
    """Jump one node's WALL clock far ahead: leases (monotonic-duration
    arithmetic) must be unaffected, and hybrid-time causality must hold
    — writes after the jump get larger hybrid times everywhere
    (reference: SkewedClock tests, clock_synchronization-itest.cc)."""
    import yugabyte_db_tpu.utils.hybrid_time as HT

    leader = group.leader()
    ht1 = leader.write([group.row("before-jump", 1)])

    # jump the wall clock +1 hour for every NEW physical reading
    real_time = HT.time.time
    monkeypatch.setattr(HT.time, "time", lambda: real_time() + 3600.0)

    assert leader.raft.has_lease()  # monotonic lease unaffected
    ht2 = leader.write([group.row("after-jump", 2)])
    assert ht2.value > ht1.value
    # followers ratchet to the jumped clock through message hybrid times
    # (causality), so a failover cannot go back in time
    wait_for(lambda: all(
        p.tablet.clock.now().value > ht2.value
        for p in group.peers.values()), timeout=3.0,
        msg="clock propagation")

    # restore the wall clock: hybrid time must NEVER regress
    monkeypatch.setattr(HT.time, "time", real_time)
    ht3 = leader.write([group.row("after-restore", 3)])
    assert ht3.value > ht2.value
    assert leader.raft.has_lease()
