"""RPC framework tests: framing, multiplexing, errors, deadlines, foreign
protocol contexts, and a raft group over real loopback sockets.

Reference test analog: src/yb/rpc/rpc-test.cc, rpc_stub-test.cc, and
raft_consensus-itest.cc running over real server sockets.
"""

import socket
import threading
import time

import pytest

from yugabyte_db_tpu.consensus import RaftOptions
from yugabyte_db_tpu.models.datatypes import DataType
from yugabyte_db_tpu.models.partition import compute_hash_code
from yugabyte_db_tpu.models.schema import ColumnKind, ColumnSchema, Schema
from yugabyte_db_tpu.rpc import (ConnectionContext, Messenger, Proxy,
                                 RpcCallError, SocketTransport)
from yugabyte_db_tpu.storage import RowVersion, ScanSpec
from yugabyte_db_tpu.tablet import TabletMetadata
from yugabyte_db_tpu.tablet.tablet_peer import TabletPeer
from yugabyte_db_tpu.utils import metrics


@pytest.fixture
def messenger():
    m = Messenger("test")
    yield m
    m.shutdown()


def echo_handler(method, body):
    if method == "echo":
        return body
    if method == "slow":
        time.sleep(body["sleep_s"])
        return "done"
    if method == "boom":
        raise ValueError("intentional failure")
    raise KeyError(method)


def test_echo_roundtrip(messenger):
    host, port = messenger.listen("127.0.0.1", 0, echo_handler)
    proxy = Proxy(host, port)
    assert proxy.call("echo", {"x": [1, 2.5, "s", b"b", None, True]}) == \
        {"x": [1, 2.5, "s", b"b", None, True]}
    proxy.close()


def test_concurrent_calls_multiplex(messenger):
    host, port = messenger.listen("127.0.0.1", 0, echo_handler)
    proxy = Proxy(host, port)
    results = {}
    errors = []

    def worker(i):
        try:
            results[i] = proxy.call("echo", {"i": i})
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(50)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    assert all(results[i] == {"i": i} for i in range(50))
    proxy.close()


def test_remote_error_propagates(messenger):
    host, port = messenger.listen("127.0.0.1", 0, echo_handler)
    proxy = Proxy(host, port)
    with pytest.raises(RpcCallError, match="intentional failure"):
        proxy.call("boom", None)
    # connection still usable after a handler error
    assert proxy.call("echo", 42) == 42
    proxy.close()


def test_call_deadline(messenger):
    host, port = messenger.listen("127.0.0.1", 0, echo_handler)
    proxy = Proxy(host, port)
    with pytest.raises(TimeoutError):
        proxy.call("slow", {"sleep_s": 2.0}, timeout=0.2)
    proxy.close()


def test_large_payload(messenger):
    host, port = messenger.listen("127.0.0.1", 0, echo_handler)
    proxy = Proxy(host, port)
    blob = b"\xab" * (4 * 1024 * 1024)
    assert proxy.call("echo", blob) == blob
    proxy.close()


def test_connect_refused():
    with pytest.raises(OSError):
        Proxy("127.0.0.1", 1, connect_timeout=0.5)


class LineContext(ConnectionContext):
    """A trivial newline-delimited text protocol, standing in for RESP/CQL
    to prove foreign protocols ride the same reactor."""

    def __init__(self):
        self._buf = bytearray()

    def feed(self, data):
        self._buf.extend(data)
        calls = []
        while b"\n" in self._buf:
            line, _, rest = bytes(self._buf).partition(b"\n")
            self._buf = bytearray(rest)
            calls.append((None, "line", line.decode()))
        return calls

    def serialize(self, response):
        _, _, body = response
        return (body + "\n").encode()


def test_foreign_protocol_context(messenger):
    def upper(method, line):
        return line.upper()

    host, port = messenger.listen("127.0.0.1", 0, upper,
                                  context_factory=LineContext)
    import socket
    s = socket.create_connection((host, port))
    s.sendall(b"hello\nworld\n")
    got = b""
    while got.count(b"\n") < 2:
        got += s.recv(1024)
    assert got == b"HELLO\nWORLD\n"
    s.close()


# -- the reply's way back: written by the worker, read by the caller -----------

_writes, _reads = metrics.rpc_reply_writes, metrics.rpc_reply_reads


def _grew(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in after}


def _small_send_buffer(messenger):
    """The one accepted connection's send buffer fixed at a few KB (no
    autotuning): a reply of megabytes is then surely taken in parts."""
    (conn,) = messenger._conns
    conn.sock.setsockopt(socket.SOL_SOCKET, socket.SO_SNDBUF, 16 * 1024)


@pytest.mark.parametrize("size, by", [(16, "worker"),
                                      (4 * 1024 * 1024, "reactor")])
def test_reply_is_written_by_the_worker_and_the_rest_by_the_reactor(
        messenger, size, by):
    """A reply the socket takes whole never sees the reactor; one it
    takes in parts (4 MB against a small send buffer) arrives whole,
    its rest written by the reactor, and counts once."""
    host, port = messenger.listen(
        "127.0.0.1", 0, lambda method, n: b"\x5a" * n)
    proxy = Proxy(host, port)
    assert proxy.call("blob", 1) == b"\x5a"
    _small_send_buffer(messenger)
    before = _writes()
    assert proxy.call("blob", size) == b"\x5a" * size
    assert _grew(before, _writes()) == {
        "worker": int(by == "worker"), "reactor": int(by == "reactor")}
    # the connection is sound, and the worker's again, after a big reply
    before = _writes()
    assert proxy.call("blob", 3) == b"\x5a" * 3
    assert _grew(before, _writes()) == {"worker": 1, "reactor": 0}
    proxy.close()


def test_respond_span_ends_before_the_reply_leaves(messenger, monkeypatch):
    seen = []
    write = Messenger._write_reply

    def spy(self, conn, out):
        seen.append(metrics.rpc_respond_histogram("echo").count)
        write(self, conn, out)

    monkeypatch.setattr(Messenger, "_write_reply", spy)
    host, port = messenger.listen("127.0.0.1", 0, echo_handler)
    proxy = Proxy(host, port)
    n = metrics.rpc_respond_histogram("echo").count
    assert proxy.call("echo", 1) == 1
    assert seen == [n + 1]
    proxy.close()


def test_ordered_replies_keep_their_order_small_and_large(messenger):
    """Pipelined calls of a foreign (ordered) context: a large reply is
    left in part to the reactor, and the small one behind it queues
    behind it instead of overtaking it on the socket."""
    def sized(method, line):
        tag, n = line.split(":")
        return tag + ":" + "x" * int(n)

    host, port = messenger.listen("127.0.0.1", 0, sized,
                                  context_factory=LineContext)
    sizes = [3, 2_000_000, 5, 1_500_000, 0, 7, 3_000_000, 1]
    s = socket.create_connection((host, port))
    s.settimeout(20)
    s.sendall(b"first:1\n")
    assert s.recv(64) == b"first:x\n"
    _small_send_buffer(messenger)
    before = _writes()
    s.sendall("".join(f"r{i}:{n}\n" for i, n in enumerate(sizes)).encode())
    got = bytearray()
    while got.count(b"\n") < len(sizes):
        data = s.recv(1 << 20)
        assert data, "server closed the connection"
        got.extend(data)
    s.close()
    lines = got.decode().split("\n")[:-1]
    assert [(ln.split(":")[0], len(ln.split(":")[1])) for ln in lines] == \
        [(f"r{i}", n) for i, n in enumerate(sizes)]
    grew = _grew(before, _writes())
    assert grew["worker"] + grew["reactor"] == len(sizes)
    assert grew["reactor"] >= 3


def test_a_lone_caller_reads_its_own_reply_and_no_thread_is_started(
        messenger):
    host, port = messenger.listen("127.0.0.1", 0, echo_handler)
    threads = set(threading.enumerate())
    proxy = Proxy(host, port)
    before = _reads()
    for i in range(20):
        assert proxy.call("echo", i) == i
    assert _grew(before, _reads()) == {"caller": 20, "peer": 0}
    # the messenger's pool grows by a worker a call; the proxy has none
    started = [t.name for t in set(threading.enumerate()) - threads
               if not t.name.startswith("test-svc")]
    assert started == []
    assert not [t for t in threading.enumerate()
                if t.name.startswith("proxy-read")]
    proxy.close()


def test_sixteen_callers_of_one_proxy_each_get_their_own_body():
    """Replies come back out of order (the later a call, the sooner its
    answer), so most are read by a thread that did not ask for them."""
    m = Messenger("test16", num_workers=16)
    try:
        def handler(method, body):
            time.sleep(0.002 * (16 - body["i"] % 16))
            return {"i": body["i"], "pad": "p" * (body["i"] * 37 % 5000)}

        host, port = m.listen("127.0.0.1", 0, handler)
        proxy = Proxy(host, port)
        before = _reads()
        wrong, errors = [], []

        def caller(t):
            try:
                for k in range(12):
                    i = t + 16 * k
                    got = proxy.call("x", {"i": i}, timeout=20)
                    if got != {"i": i, "pad": "p" * (i * 37 % 5000)}:
                        wrong.append((i, got))
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=caller, args=(t,))
                   for t in range(16)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(60)
        assert not any(t.is_alive() for t in threads)
        assert not errors and not wrong
        grew = _grew(before, _reads())
        assert grew["caller"] + grew["peer"] == 16 * 12
        assert grew["peer"] > 0
        proxy.close()
    finally:
        m.shutdown()


def test_a_reader_that_times_out_hands_the_socket_on(messenger):
    """The first caller reads for both; its own budget ends first:
    TimeoutError for it, and the call still pending reads on and gets
    its reply. The late reply to the abandoned id is dropped by
    whoever reads next, and the proxy stays sound."""
    host, port = messenger.listen("127.0.0.1", 0, echo_handler)
    proxy = Proxy(host, port)
    out = {}

    def patient():
        out["b"] = proxy.call("slow", {"sleep_s": 0.6}, timeout=10)

    def impatient():
        try:
            proxy.call("slow", {"sleep_s": 0.9}, timeout=0.2)
        except TimeoutError as e:
            out["a"] = e

    a = threading.Thread(target=impatient)
    a.start()
    time.sleep(0.05)          # a reads by now
    b = threading.Thread(target=patient)
    b.start()
    a.join(5)
    b.join(5)
    assert not a.is_alive() and not b.is_alive()
    assert isinstance(out.get("a"), TimeoutError)
    assert out.get("b") == "done"
    time.sleep(0.5)           # the abandoned call's reply is on the socket
    before = _reads()
    assert proxy.call("echo", "next") == "next"
    assert _grew(before, _reads()) == {"caller": 1, "peer": 0}
    assert not proxy.closed
    proxy.close()


def test_a_waiter_times_out_while_another_caller_reads(messenger):
    host, port = messenger.listen("127.0.0.1", 0, echo_handler)
    proxy = Proxy(host, port)
    out = {}

    def reader():
        out["r"] = proxy.call("slow", {"sleep_s": 0.6}, timeout=10)

    r = threading.Thread(target=reader)
    r.start()
    time.sleep(0.05)
    t0 = time.monotonic()
    with pytest.raises(TimeoutError):
        proxy.call("slow", {"sleep_s": 2.0}, timeout=0.2)
    assert time.monotonic() - t0 < 0.5
    r.join(5)
    assert out.get("r") == "done"
    proxy.close()


def test_server_closing_mid_call_fails_every_pending_call_and_the_transport_reconnects():
    m = Messenger("going")
    host, port = m.listen("127.0.0.1", 0, echo_handler)
    transport = SocketTransport({"peer": (host, port)})
    assert transport.send("peer", "echo", 1) == 1
    proxy = transport._proxy_for("peer")
    errors = []

    def caller():
        try:
            proxy.call("slow", {"sleep_s": 5.0}, timeout=10)
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    threads = [threading.Thread(target=caller) for _ in range(5)]
    for t in threads:
        t.start()
    time.sleep(0.2)
    t0 = time.monotonic()
    m.shutdown()
    for t in threads:
        t.join(5)
    assert not any(t.is_alive() for t in threads)
    assert time.monotonic() - t0 < 6
    assert len(errors) == 5
    assert all(isinstance(e, ConnectionError) for e in errors), errors
    assert proxy.closed
    with pytest.raises(ConnectionError):
        proxy.call("echo", 1)

    m2 = Messenger("back")
    try:
        m2.listen(host, port, echo_handler)
        assert transport.send("peer", "echo", 2) == 2
        assert transport._proxy_for("peer") is not proxy
    finally:
        transport.close()
        m2.shutdown()


def test_a_peer_that_went_away_while_idle_fails_the_first_send_fast():
    """Nobody watches an idle connection: the first call after the peer
    went away fails at once (no wait for its timeout) and marks the
    proxy closed; the next send connects anew."""
    from yugabyte_db_tpu.rpc.interface import TransportError

    m = Messenger("idle")
    host, port = m.listen("127.0.0.1", 0, echo_handler)
    transport = SocketTransport({"peer": (host, port)})
    assert transport.send("peer", "echo", 1) == 1
    m.shutdown()
    m2 = Messenger("idle2")
    try:
        m2.listen(host, port, echo_handler)
        t0 = time.monotonic()
        with pytest.raises(TransportError):
            transport.send("peer", "echo", 2, timeout=5.0)
        assert time.monotonic() - t0 < 1.0
        assert transport.send("peer", "echo", 3) == 3
    finally:
        transport.close()
        m2.shutdown()


# -- raft over sockets -------------------------------------------------------

def test_raft_group_over_sockets(tmp_path):
    schema = Schema([
        ColumnSchema("k", DataType.STRING, ColumnKind.HASH),
        ColumnSchema("v", DataType.INT64),
    ], table_id="t")
    cid = {c.name: c.col_id for c in schema.columns}
    opts = RaftOptions(election_timeout_s=0.25, heartbeat_interval_s=0.05,
                       lease_s=0.6, rpc_timeout_s=1.0)
    nodes = ["s-0", "s-1", "s-2"]
    transport = SocketTransport()
    messengers, peers = {}, {}
    try:
        for uuid in nodes:
            m = Messenger(uuid)
            meta = TabletMetadata("tablet-1", "t", schema, 0, 65536)
            peer = TabletPeer(uuid, meta, str(tmp_path / uuid), transport,
                              nodes, fsync=False, raft_opts=opts)
            host, port = m.listen(
                "127.0.0.1", 0,
                lambda method, body, _p=peer: _p.raft.handle(method, body))
            transport.set_address(uuid, host, port)
            messengers[uuid], peers[uuid] = m, peer
        for p in peers.values():
            p.start()

        deadline = time.monotonic() + 10
        leader = None
        while time.monotonic() < deadline and leader is None:
            leader = next((p for p in peers.values()
                           if p.raft.is_leader() and p.raft.has_lease()), None)
            time.sleep(0.02)
        assert leader is not None, "no leader over sockets"

        key = schema.encode_primary_key(
            {"k": "sock"}, compute_hash_code(schema, {"k": "sock"}))
        for i in range(10):
            leader.write([RowVersion(key, ht=0, liveness=True,
                                     columns={cid["v"]: i})])
        # all replicas converge
        deadline = time.monotonic() + 5
        target = leader.raft.stats()["applied_index"]
        while time.monotonic() < deadline:
            if all(p.raft.stats()["applied_index"] >= target
                   for p in peers.values()):
                break
            time.sleep(0.02)
        for p in peers.values():
            res = p.scan(ScanSpec(read_ht=p.tablet.clock.now().value),
                         allow_stale=True)
            assert res.rows == [("sock", 9)], (p.node_uuid, res.rows)
    finally:
        for p in peers.values():
            p.shutdown()
        transport.close()
        for m in messengers.values():
            m.shutdown()
