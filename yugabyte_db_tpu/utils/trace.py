"""Per-request tracing + the /rpcz sample store.

Reference analog: src/yb/util/trace.{h,cc} — a Trace is a ring of
timestamped messages attached to the current request (TRACE("...") from
anywhere below the dispatch), dumped for slow RPCs — plus the rpcz
sampling of src/yb/server/rpcz-path-handler.cc and
src/yb/rpc/rpcz_store.cc: recent and slowest samples per method,
browsable over HTTP while the server runs.

Usage::

    with trace_request("ts.write") as t:
        ...
        TRACE("submitted to raft")      # from any frame below
        ...
    store.record(t)                      # duration + messages sampled

TRACE() is a no-op (one contextvar read) when no trace is active, so
library code can trace unconditionally.

Spans are the timed form of the same thing::

    with span("engine.issue", histogram=h, route="page"):
        ...

On exit a span (a) is appended to the active Trace, so a /rpcz sample
shows phases with durations and parents, (b) observes one histogram of
a metric registry (``yb_span_us{span=<name>}`` on the process registry
unless the caller names another), (c) becomes one /tracing.json slice.
With no active Trace it costs the histogram only. A request keeps one
``trace_id`` from the wire frontend to the engine: the frontend opens
the Trace, :func:`inject` puts the id and the parent span into the
tablet RPC's payload, and the server's ``trace_request`` adopts them.
docs/observability.md lists every span and who reads it.
"""

from __future__ import annotations

import contextvars
import functools
import random
import threading
import time
from collections import deque

from yugabyte_db_tpu.utils import metrics

_current: contextvars.ContextVar["Trace | None"] = \
    contextvars.ContextVar("active_trace", default=None)
# Name of the innermost open span: the parent of the next one.
_open_span: contextvars.ContextVar["str | None"] = \
    contextvars.ContextVar("open_span", default=None)

MAX_MESSAGES = 64
MAX_SPANS = 64


class Trace:
    __slots__ = ("method", "trace_id", "parent_span", "start_wall", "start",
                 "entries", "spans", "duration_us", "dropped", "_done")

    def __init__(self, method: str, trace_id: str | None = None,
                 parent_span: str | None = None):
        self.method = method
        # One identifier from the wire frontend to the engine: a server
        # adopts its caller's (RPC payload), a frontend makes its own.
        self.trace_id = trace_id or "%016x" % random.getrandbits(64)
        self.parent_span = parent_span
        self.start_wall = time.time()
        self.start = time.monotonic()
        self.entries: list[tuple[float, str]] = []
        # (name, start_wall_ns, duration_us, parent, labels); appended
        # from any thread that runs under this trace's context.
        self.spans: list[tuple] = []
        self.duration_us: int = 0
        self.dropped = 0
        self._done = False

    def trace(self, msg: str) -> None:
        if len(self.entries) >= MAX_MESSAGES:
            self.dropped += 1
            return
        self.entries.append((time.monotonic() - self.start, msg))

    def finish(self) -> None:
        """Idempotent: the first call fixes the duration (the sample may
        already be recorded when a later finish runs)."""
        if not self._done:
            self._done = True
            self.duration_us = int((time.monotonic() - self.start) * 1e6)

    def add_span(self, name: str, start_wall_ns: int, duration_us: int,
                 parent: str | None, labels: dict | None) -> None:
        if len(self.spans) >= MAX_SPANS:
            self.dropped += 1
            return
        self.spans.append((name, start_wall_ns, duration_us, parent,
                           labels))

    def dump(self) -> dict:
        t0_ns = int(self.start_wall * 1e9)
        out = {
            "method": self.method,
            "trace_id": self.trace_id,
            "start": self.start_wall,
            "duration_us": self.duration_us,
            "messages": [f"{dt * 1e6:8.0f}us {m}"
                         for dt, m in self.entries],
            "spans": [dict(labels or {}, name=name, parent=parent,
                           start_us=(start_ns - t0_ns) // 1000,
                           duration_us=dur)
                      for name, start_ns, dur, parent, labels
                      in self.spans],
        }
        if self.parent_span is not None:
            out["parent_span"] = self.parent_span
        if self.dropped:
            out["dropped_messages"] = self.dropped
        return out


def TRACE(msg: str, *args) -> None:  # noqa: N802 — reference macro name
    """Append to the active request trace, if any (trace.h TRACE())."""
    t = _current.get()
    if t is not None:
        t.trace(msg % args if args else msg)


class trace_request:
    """Context manager: install a Trace as the active one for this
    (thread/context) for the duration of a request. ``trace_id`` and
    ``parent_span`` are the caller's, as :func:`inject` put them into
    the RPC payload; without them the request starts its own."""

    __slots__ = ("trace", "_token", "_span_token")

    def __init__(self, method: str, trace_id: str | None = None,
                 parent_span: str | None = None):
        self.trace = Trace(method, trace_id, parent_span)
        self._token = None
        self._span_token = None

    def __enter__(self) -> Trace:
        self._token = _current.set(self.trace)
        self._span_token = _open_span.set(None)
        return self.trace

    def __exit__(self, *exc) -> None:
        _open_span.reset(self._span_token)
        _current.reset(self._token)
        self.trace.finish()
        return None


def inject(payload: dict) -> None:
    """Put the active request's identity into an outgoing RPC payload
    (beside ``propagated_ht``): ``trace_id`` and ``parent_span``, the
    innermost open span or the request itself. No active Trace, no
    keys."""
    t = _current.get()
    if t is not None:
        payload["trace_id"] = t.trace_id
        payload["parent_span"] = _open_span.get() or t.method


def adopted(method: str, payload) -> trace_request:
    """The server side of :func:`inject`."""
    if isinstance(payload, dict):
        return trace_request(method, payload.get("trace_id"),
                             payload.get("parent_span"))
    return trace_request(method)


# -- the wait before a handler ------------------------------------------------
# Stamped by the messenger when a frame is parsed (rpc/messenger.py),
# taken by the handler's owner when the handler starts: the time a call
# waited for a worker of the service pool, or behind the earlier calls
# of an ordered connection.
_arrival: contextvars.ContextVar["tuple | None"] = \
    contextvars.ContextVar("rpc_arrival", default=None)


def arrival_stamp() -> tuple:
    return (time.time_ns(), time.perf_counter_ns())


def set_arrival(stamp: "tuple | None") -> None:
    _arrival.set(stamp)


def record_queue_wait(histogram, **labels) -> None:
    """The span ``rpc.queue`` for the call this thread is handling, if
    the messenger stamped one (a call over LocalTransport has none).
    Taking it clears it: a call made in-thread from this handler is
    not charged its caller's wait."""
    a = _arrival.get()
    if a is None:
        return
    _arrival.set(None)
    record_span("rpc.queue", a[0],
                (time.perf_counter_ns() - a[1]) // 1000, histogram,
                **labels)


def in_context(fn):
    """``fn`` bound to a copy of the caller's context, for a worker
    pool: the active Trace and open span follow the work to its
    thread (a ThreadPoolExecutor carries no context of its own)."""
    return functools.partial(contextvars.copy_context().run, fn)


class RpczStore:
    """Recent + slowest samples per method (rpc/rpcz_store.cc)."""

    def __init__(self, recent_per_method: int = 8, slow_keep: int = 32,
                 slow_threshold_us: int = 500_000):
        self.recent_per_method = recent_per_method
        self.slow_threshold_us = slow_threshold_us
        self._recent: dict[str, deque] = {}
        self._slow: deque = deque(maxlen=slow_keep)
        self._lock = threading.Lock()

    def record(self, trace: Trace) -> None:
        with self._lock:
            dq = self._recent.get(trace.method)
            if dq is None:
                dq = self._recent[trace.method] = deque(
                    maxlen=self.recent_per_method)
            dq.append(trace)
            if trace.duration_us >= self.slow_threshold_us:
                self._slow.append(trace)
        # every sampled request is also one /tracing.json slice
        TRACE_EVENTS.record(trace.method, int(trace.start_wall * 1e9),
                            trace.duration_us, None, trace.trace_id)

    def dump(self) -> dict:
        with self._lock:
            return {
                "methods": {
                    m: [t.dump() for t in dq]
                    for m, dq in sorted(self._recent.items())
                },
                "slow": [t.dump() for t in self._slow],
                "slow_threshold_us": self.slow_threshold_us,
            }


# The wire frontends' samples: a frontend has no daemon of its own, so
# its statements' traces are kept here and shown by the tserver's /rpcz
# under "frontends" (the reference shape: the tserver spawns them).
FRONTEND_RPCZ = RpczStore()


class statement(trace_request):
    """One statement at a wire frontend (``proto``: pg, cql, redis), from
    its message decoded to its reply bytes built: opens the request's
    Trace (the id every tablet RPC below it carries), takes the wait the
    messenger stamped, and on exit observes
    ``yb_request_latency_seconds{proto}`` (``n`` times for a batch of
    ``n`` statements answered together) and samples the trace."""

    __slots__ = ("proto", "n")

    def __init__(self, proto: str, n: int = 1):
        super().__init__(proto + ".statement")
        self.proto = proto
        self.n = n

    def __enter__(self) -> Trace:
        t = super().__enter__()
        record_queue_wait(metrics.rpc_queue_histogram(self.proto))
        return t

    def __exit__(self, *exc) -> None:
        super().__exit__(*exc)
        for _ in range(self.n):
            metrics.observe_request_latency(
                self.proto, self.trace.duration_us / 1e6)
        FRONTEND_RPCZ.record(self.trace)
        return None


# -- chromium trace events (/tracing.json) -----------------------------------

class TraceEventLog:
    """Process-wide ring of Chromium trace-event records, browsable in
    Perfetto / chrome://tracing (reference: the Chromium trace-event
    header under src/yb/util/debug/ + the /tracing.json handler,
    tracing-path-handlers.cc). Complete events ("ph":"X") only — each
    traced request and each span under one is one slice; ``ts`` is
    wall-clock microseconds since the epoch."""

    def __init__(self, capacity: int = 4096):
        self._lock = threading.Lock()
        self._events: deque = deque(maxlen=capacity)

    def record(self, name: str, start_wall_ns: int, duration_us: int,
               labels: dict | None = None,
               trace_id: str | None = None) -> None:
        # (a tuple now, the event's dict at dump: this is on every
        # request's path and a dump is an operator's click)
        ev = (name, threading.get_ident(), start_wall_ns, duration_us,
              labels, trace_id)
        with self._lock:
            self._events.append(ev)

    def dump(self) -> dict:
        with self._lock:
            events = list(self._events)
        out = []
        for name, tid, start_ns, dur, labels, trace_id in events:
            ev = {"name": name, "ph": "X", "pid": 1, "tid": tid,
                  "ts": start_ns // 1000, "dur": int(dur)}
            args = dict(labels) if labels else {}
            if trace_id is not None:
                args["trace_id"] = trace_id
            if args:
                ev["args"] = args
            out.append(ev)
        return {"traceEvents": out, "displayTimeUnit": "ms"}


TRACE_EVENTS = TraceEventLog()


def record_span(name: str, start_wall_ns: int, duration_us: int,
                histogram=None, seconds: bool = False,
                **labels) -> None:
    """The one place a finished span goes: (a) the active Trace, with
    its parent, (b) one histogram, ``yb_span_us{span=name}`` on the
    process registry unless ``histogram`` names another (microseconds;
    ``seconds=True`` for a histogram kept in seconds), (c) the
    /tracing.json ring. (a) and (c) only under an active Trace: with
    none, a span costs its histogram. :class:`span` ends here; so does
    a duration that was stamped in one place and is known in another
    (a queue wait, a compile seen after the call)."""
    if histogram is None:
        histogram = metrics.span_histogram(name)
    histogram.observe(duration_us / 1e6 if seconds else duration_us)
    t = _current.get()
    if t is not None:
        t.add_span(name, start_wall_ns, duration_us, _open_span.get()
                   or t.method, labels or None)
        TRACE_EVENTS.record(name, start_wall_ns, duration_us,
                            labels or None, t.trace_id)


class span:
    """Span context manager::

        with span("engine.issue", histogram=h) as sp:
            ...
            sp.labels["route"] = "page"   # known only at the end

    Start on the wall clock the ring uses (``time.time_ns()``),
    duration on a monotonic one. ``histogram`` may be set before exit
    too. See :func:`record_span`."""

    __slots__ = ("name", "histogram", "seconds", "labels", "_wall", "_t0",
                 "_token")

    def __init__(self, name: str, histogram=None, seconds: bool = False,
                 **labels):
        self.name = name
        self.histogram = histogram
        self.seconds = seconds
        self.labels = labels

    def __enter__(self):
        self._wall = time.time_ns()
        self._t0 = time.perf_counter_ns()
        self._token = _open_span.set(self.name)
        return self

    def __exit__(self, *exc):
        dur_us = (time.perf_counter_ns() - self._t0) // 1000
        _open_span.reset(self._token)
        record_span(self.name, self._wall, dur_us, self.histogram,
                    self.seconds, **self.labels)
        return False


def dump_stacks() -> str:
    """All live threads' Python stacks (the pprof/stacks analog of
    src/yb/server/pprof-path-handlers.cc, for a Python runtime)."""
    import sys
    import traceback

    names = {t.ident: t.name for t in threading.enumerate()}
    out = []
    for tid, frame in sys._current_frames().items():
        out.append(f"--- thread {tid} ({names.get(tid, '?')}) ---")
        out.extend(line.rstrip()
                   for line in traceback.format_stack(frame))
    return "\n".join(out) + "\n"
