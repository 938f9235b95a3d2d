"""Proxy: outbound RPC client with call-id multiplexing.

Reference analog: src/yb/rpc/proxy.cc + outbound_call.cc — many concurrent
calls share one connection; responses are matched by call id; deadlines are
per-call. The proxy has no thread of its own (the reference reads on its
reactor): the thread that wants a reply reads it. A caller that has sent
its frame and finds nobody reading takes the read lock and reads frames
itself, handing replies for other pending calls to their waiters, until
its own reply is in (or its budget is spent); then it lets go and wakes
one pending caller to read on. A caller that finds somebody reading waits
for that thread to hand its reply over. With one call in flight that is a
blocking read in the caller; with many it is leader/followers.
"""

from __future__ import annotations

import logging
import select
import socket
import struct
import threading
import time

from yugabyte_db_tpu.rpc.messenger import MAX_FRAME, RpcCallError
from yugabyte_db_tpu.utils import codec, metrics, trace
from yugabyte_db_tpu.utils.retry import Deadline

_LEN = struct.Struct("<I")
_READS_BY_CALLER = metrics.rpc_reply_reads_counter("caller")
_READS_BY_PEER = metrics.rpc_reply_reads_counter("peer")


class _PendingCall:
    __slots__ = ("event", "status", "body")

    def __init__(self):
        self.event = threading.Event()
        self.status = None
        self.body = None


class Proxy:
    def __init__(self, host: str, port: int, connect_timeout: float = 5.0):
        self.addr = (host, port)
        self._sock = socket.create_connection((host, port),
                                              timeout=connect_timeout)
        self._sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        self._sock.settimeout(None)
        self._send_lock = threading.Lock()
        self._lock = threading.Lock()
        self._pending: dict[int, _PendingCall] = {}
        self._next_id = 1
        self._closed = False
        # Whoever holds the read lock is the one thread that reads the
        # socket, and owns the buffer and the poll object.
        self._read_lock = threading.Lock()
        self._buf = bytearray()
        self._poll = select.poll()
        self._poll.register(self._sock, select.POLLIN)

    def call(self, method: str, body, timeout: float = 10.0,
             deadline: Deadline | None = None):
        """Send one call and wait for its response. ``deadline`` (the
        propagated utils.retry budget) caps ``timeout`` at the caller's
        remaining budget, so a retry loop's later attempts never wait
        longer than the one deadline they all debit."""
        if deadline is not None:
            timeout = deadline.timeout(timeout)
        # The caller's side of the round trip, under the caller's Trace
        # where it has one: what the server's rpc.queue, handler and
        # rpc.respond leave of it is the sockets, the server's reactor
        # on the way in and the hand-offs between threads.
        wall_ns, t0 = time.time_ns(), time.perf_counter_ns()
        try:
            return self._call(method, body, timeout)
        finally:
            trace.record_span("rpc.call", wall_ns,
                              (time.perf_counter_ns() - t0) // 1000,
                              metrics.rpc_call_histogram(method),
                              method=method)

    def _call(self, method: str, body, timeout: float):
        with self._lock:
            if self._closed:
                raise ConnectionError(f"proxy to {self.addr} is closed")
            call_id = self._next_id
            self._next_id += 1
            pc = _PendingCall()
            self._pending[call_id] = pc
        payload = codec.encode([call_id, method, body])
        frame = _LEN.pack(len(payload)) + payload
        try:
            with self._send_lock:
                self._sock.sendall(frame)
        except OSError as e:
            with self._lock:
                self._pending.pop(call_id, None)
            self.close()
            raise ConnectionError(f"send to {self.addr} failed: {e}") from e
        read_by = self._await_reply(pc, time.monotonic() + timeout)
        if read_by is None:
            with self._lock:
                self._pending.pop(call_id, None)
            # Whoever was woken to read on may be this call: pass it on.
            self._wake_one()
            raise TimeoutError(f"rpc {method} to {self.addr} timed out")
        if pc.status == "conn_closed":
            # Transport-level loss, NOT a remote handler error: callers'
            # failover paths key on ConnectionError.
            raise ConnectionError(f"connection to {self.addr} dropped "
                                  f"mid-call ({method})")
        read_by.increment()
        if pc.status != "ok":
            raise RpcCallError(pc.body)
        return pc.body

    def _await_reply(self, pc: _PendingCall, give_up: float):
        """Wait until ``pc`` has its reply or ``give_up`` (a
        time.monotonic() instant) has passed: reading the socket where
        nobody else does, else waiting for the reading thread to hand
        the reply over or to wake this one to read on. Returns the
        ``rpc_reply_reads`` counter of who read the reply, None when
        the budget is spent."""
        while True:
            if self._read_lock.acquire(blocking=False):
                # (handed over between the event's wait and the lock?)
                read_by = _READS_BY_CALLER if pc.status is None \
                    else _READS_BY_PEER
                try:
                    self._read_until(pc, give_up)
                finally:
                    self._read_lock.release()
                if pc.status is None:
                    return None
                # Replies may be in flight for the calls still pending:
                # one of them reads on.
                self._wake_one()
                return read_by
            if not pc.event.wait(give_up - time.monotonic()):
                return None
            # Cleared BEFORE the status is read: a reply handed over
            # after the clear sets the event again.
            pc.event.clear()
            if pc.status is not None:
                return _READS_BY_PEER

    def _read_until(self, own: _PendingCall, give_up: float) -> None:
        """Holding the read lock: read frames and hand them to their
        calls until ``own`` has its reply or its budget is spent. The
        wait on the socket is bounded by the budget through poll, not a
        socket timeout: a concurrent sendall keeps its blocking socket.
        A lost link closes the proxy, which fails every pending call,
        ``own`` among them."""
        try:
            while own.status is None:
                wait_ms = (give_up - time.monotonic()) * 1000
                if wait_ms <= 0:
                    break
                if not self._poll.poll(wait_ms):
                    continue
                try:
                    data = self._sock.recv(256 * 1024, socket.MSG_DONTWAIT)
                except (BlockingIOError, InterruptedError):
                    continue
                if not data:
                    raise ConnectionError("closed by the peer")
                self._buf.extend(data)
                self._hand_out_frames(own)
        except (OSError, ValueError):
            self.close()
        except Exception:  # decode/dispatch bug — never die silently
            logging.getLogger(__name__).exception(
                "proxy read to %s failed", self.addr)
            self.close()

    def _hand_out_frames(self, own: _PendingCall) -> None:
        """Every complete frame of the buffer to its pending call; a
        late reply for an abandoned call id is dropped. ``own`` is the
        reading thread's: it needs no event."""
        buf = self._buf
        while len(buf) >= _LEN.size:
            (length,) = _LEN.unpack_from(buf, 0)
            if length > MAX_FRAME:
                raise ValueError("oversized frame")
            end = _LEN.size + length
            if len(buf) < end:
                break
            call_id, status, body = codec.decode(bytes(buf[_LEN.size:end]))
            del buf[:end]
            with self._lock:
                pc = self._pending.pop(call_id, None)
            if pc is None:
                continue
            # The body first: a waiter woken for another reason takes a
            # status that is set as the whole reply.
            pc.body = body
            pc.status = status
            if pc is not own:
                pc.event.set()

    def _wake_one(self) -> None:
        """Nobody may be reading now: wake one pending call to try, the
        newest: its reply is the likeliest to come last, so it reads for
        the others longest before the socket changes hands again (the
        oldest would meet its own reply next and hand on at once). A
        call woken while somebody does read goes back to waiting."""
        with self._lock:
            pc = next(reversed(self._pending.values()), None)
        if pc is not None:
            pc.event.set()

    def close(self) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
            pending = list(self._pending.values())
            self._pending.clear()
        for pc in pending:
            pc.status, pc.body = "conn_closed", None
            pc.event.set()
        try:
            # A thread blocked reading this socket is woken by the
            # shutdown; the close alone would leave it to its timeout.
            self._sock.shutdown(socket.SHUT_RDWR)
        except OSError:
            pass
        try:
            self._sock.close()
        except OSError:
            pass

    @property
    def closed(self) -> bool:
        return self._closed
