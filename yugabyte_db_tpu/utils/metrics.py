"""Metrics: counters, gauges, histograms + Prometheus text exposition.

Reference analog: src/yb/util/metrics.h — MetricRegistry/MetricEntity
with METRIC_DEFINE_* metrics attached to entities (server, tablet), HDR
histograms for latencies, and the PrometheusWriter (metrics.h:584) that
renders the registry for scraping.

Shapes:
- Counter: monotonically increasing int.
- Gauge: set() directly, or constructed with a callback sampled at
  scrape time (how per-tablet row counts surface without bookkeeping).
- Histogram: exponential buckets (powers of 2 in microseconds by
  default) with count/sum — the Prometheus histogram contract; covers
  the reference's HDR-histogram latency use.

Entities carry label sets (e.g. tablet_id); the registry renders
everything in one pass, grouping series by metric name.
"""

from __future__ import annotations

import bisect
import functools
import logging
import threading
import time
import weakref


class Counter:
    __slots__ = ("value", "_lock")

    def __init__(self):
        self.value = 0
        self._lock = threading.Lock()

    def increment(self, by: int = 1) -> None:
        with self._lock:
            self.value += by

    def get(self) -> int:
        return self.value


class Gauge:
    __slots__ = ("_value", "_fn")

    def __init__(self, fn=None):
        self._value = 0
        self._fn = fn

    def set(self, v) -> None:
        self._value = v

    def get(self):
        if self._fn is not None:
            try:
                return self._fn()
            except Exception:  # noqa: BLE001 — scrape must not die
                return 0
        return self._value


# Exponential bucket bounds (microseconds): 64us .. ~67s
DEFAULT_BUCKETS = tuple(64 * (2 ** i) for i in range(21))


class Histogram:
    __slots__ = ("buckets", "counts", "count", "sum", "_lock")

    def __init__(self, buckets=DEFAULT_BUCKETS):
        self.buckets = buckets
        self.counts = [0] * (len(buckets) + 1)
        self.count = 0
        self.sum = 0
        self._lock = threading.Lock()

    def observe(self, v) -> None:
        i = bisect.bisect_left(self.buckets, v)   # first bound >= v
        with self._lock:
            self.counts[i] += 1
            self.count += 1
            self.sum += v

    def observe_duration_us(self, start_monotonic: float) -> None:
        self.observe(int((time.monotonic() - start_monotonic) * 1e6))

    def percentile(self, p: float):
        """Approximate percentile from bucket upper bounds."""
        with self._lock:
            if self.count == 0:
                return 0
            target = self.count * p
            acc = 0
            for i, n in enumerate(self.counts):
                acc += n
                if acc >= target:
                    return (self.buckets[i] if i < len(self.buckets)
                            else self.buckets[-1])
            return self.buckets[-1]


class MetricEntity:
    """One labeled owner of metrics (server / tablet / table)."""

    def __init__(self, registry: "MetricRegistry", labels: dict):
        self.registry = registry
        self.labels = dict(labels)
        self._metrics: dict[str, object] = {}
        self._lock = threading.Lock()

    def counter(self, name: str) -> Counter:
        return self._get(name, Counter)

    def gauge(self, name: str, fn=None) -> Gauge:
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = Gauge(fn)
            elif fn is not None:
                m._fn = fn
            return m

    def histogram(self, name: str, buckets=None) -> Histogram:
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = (
                    Histogram(buckets) if buckets is not None
                    else Histogram())
            return m

    def _get(self, name, cls):
        with self._lock:
            m = self._metrics.get(name)
            if m is None:
                m = self._metrics[name] = cls()
            return m


class MetricRegistry:
    """All of one process's metrics; renders Prometheus text."""

    def __init__(self):
        self._lock = threading.Lock()
        self._entities: list[MetricEntity] = []
        self._collectors: list = []  # callables refreshing gauges pre-scrape

    def entity(self, **labels) -> MetricEntity:
        e = MetricEntity(self, labels)
        with self._lock:
            self._entities.append(e)
        return e

    def remove_entity(self, entity: MetricEntity) -> None:
        with self._lock:
            try:
                self._entities.remove(entity)
            except ValueError:
                pass

    def add_collector(self, fn) -> None:
        """fn() runs before each scrape (register/refresh dynamic
        entities, e.g. per-tablet gauges after tablets move)."""
        with self._lock:
            self._collectors.append(fn)

    def prometheus_text(self) -> str:
        with self._lock:
            collectors = list(self._collectors)
        for fn in collectors:
            try:
                fn()
            except Exception as e:  # noqa: BLE001 — scrape must not die
                count_swallowed("metrics.collector", e)
        with self._lock:
            entities = list(self._entities)
        by_name: dict[str, list] = {}
        for e in entities:
            with e._lock:
                metrics = dict(e._metrics)
            for name, m in metrics.items():
                by_name.setdefault(name, []).append((e.labels, m))
        out = []
        for name in sorted(by_name):
            series = by_name[name]
            kind = ("counter" if isinstance(series[0][1], Counter)
                    else "histogram" if isinstance(series[0][1], Histogram)
                    else "gauge")
            out.append(f"# TYPE {name} {kind}")
            for labels, m in series:
                ls = _labels(labels)
                if isinstance(m, Histogram):
                    with m._lock:
                        counts = list(m.counts)
                        total, s = m.count, m.sum
                    acc = 0
                    for i, b in enumerate(m.buckets):
                        acc += counts[i]
                        out.append(
                            f"{name}_bucket{_labels(labels, le=b)} {acc}")
                    out.append(
                        f'{name}_bucket{_labels(labels, le="+Inf")} {total}')
                    out.append(f"{name}_sum{ls} {s}")
                    out.append(f"{name}_count{ls} {total}")
                else:
                    out.append(f"{name}{ls} {m.get()}")
        return "\n".join(out) + "\n"


def _labels(labels: dict, **extra) -> str:
    items = {**labels, **extra}
    if not items:
        return ""
    inner = ",".join(f'{k}="{v}"' for k, v in sorted(items.items()))
    return "{" + inner + "}"


# -- process-wide registry + swallowed-error accounting ----------------------
# Daemons construct their own registries for per-server metrics; this one
# exists so cross-cutting health counters (swallowed errors, scrape
# failures) have a home regardless of which daemon — or no daemon — is
# running in the process.
_PROCESS_REGISTRY = MetricRegistry()
_SWALLOW_LOG = logging.getLogger("yugabyte_db_tpu.swallowed")
_SWALLOW_ENTITIES: dict[str, MetricEntity] = {}
_SWALLOW_LOCK = threading.Lock()


def process_registry() -> MetricRegistry:
    return _PROCESS_REGISTRY


def count_swallowed(site: str, exc: object = None) -> None:
    """Record a deliberately-swallowed exception: bump
    ``yb_swallowed_errors{site=...}`` on the process registry and leave a
    debug-level trace. For best-effort paths (retry loops, shutdown,
    scrapes) where the except block would otherwise discard the error
    invisibly — the counter makes a noisy failure site show up on a
    dashboard even when nobody has debug logging on. Never raises."""
    try:
        with _SWALLOW_LOCK:
            ent = _SWALLOW_ENTITIES.get(site)
            if ent is None:
                ent = _PROCESS_REGISTRY.entity(site=site)
                _SWALLOW_ENTITIES[site] = ent
        ent.counter("yb_swallowed_errors").increment()
        _SWALLOW_LOG.debug("swallowed at %s: %r", site, exc)
    except Exception:  # noqa: BLE001 — error accounting must not throw
        _SWALLOW_LOG.debug("count_swallowed failed at site %s", site)


def swallowed_errors() -> dict[str, int]:
    """The ``yb_swallowed_errors`` snapshot, {site: count} — what a
    fallback hid. A run that must prove no fallback fired reads this."""
    with _SWALLOW_LOCK:
        ents = dict(_SWALLOW_ENTITIES)
    return {site: ent.counter("yb_swallowed_errors").get()
            for site, ent in sorted(ents.items())}


# -- fault-injection observability -------------------------------------------
_FAULT_ENTITIES: dict[str, MetricEntity] = {}
_FAULT_LOCK = threading.Lock()


def count_fault_fired(name: str) -> None:
    """Bump ``yb_faults_fired{name=...}`` on the process registry: one
    series per fault point, incremented every time the fault actually
    fires. The fault-sweep harness asserts its injection schedule
    against this counter, so a fault point that silently stops being
    evaluated shows up as a sweep failure. Never raises."""
    try:
        with _FAULT_LOCK:
            ent = _FAULT_ENTITIES.get(name)
            if ent is None:
                ent = _PROCESS_REGISTRY.entity(name=name)
                _FAULT_ENTITIES[name] = ent
        ent.counter("yb_faults_fired").increment()
    except Exception:  # noqa: BLE001 — accounting must not throw
        _SWALLOW_LOG.debug("count_fault_fired failed for %s", name)


def faults_fired(name: str) -> int:
    """Current ``yb_faults_fired{name=...}`` value (0 if never fired)."""
    with _FAULT_LOCK:
        ent = _FAULT_ENTITIES.get(name)
    return ent.counter("yb_faults_fired").get() if ent is not None else 0


# -- compile-discipline observability -----------------------------------------
_JIT_ENTITIES: dict[str, MetricEntity] = {}
_JIT_LOCK = threading.Lock()


def count_jit_compile(entry: str, n: int = 1) -> None:
    """Bump ``yb_jit_compiles{entry=...}`` on the process registry: one
    series per @compile_contract entry point (utils/jitting.py),
    incremented on every actual XLA trace/compile event. Steady-state
    growth of any series is a retrace bug — bench rounds snapshot these
    counters around the measured loop to prove zero recompiles on hot
    scan/aggregate keys. Never raises."""
    try:
        with _JIT_LOCK:
            ent = _JIT_ENTITIES.get(entry)
            if ent is None:
                ent = _PROCESS_REGISTRY.entity(entry=entry)
                _JIT_ENTITIES[entry] = ent
        ent.counter("yb_jit_compiles").increment(n)
    except Exception:  # noqa: BLE001 — accounting must not throw
        _SWALLOW_LOG.debug("count_jit_compile failed for %s", entry)


def jit_compiles(entry: str | None = None):
    """Current ``yb_jit_compiles`` value for one entry (0 if never
    compiled), or the full {entry: count} snapshot when ``entry`` is
    None."""
    with _JIT_LOCK:
        ents = dict(_JIT_ENTITIES)
    if entry is not None:
        ent = ents.get(entry)
        return ent.counter("yb_jit_compiles").get() if ent else 0
    return {e: ent.counter("yb_jit_compiles").get()
            for e, ent in sorted(ents.items())}


# -- serving-path observability ----------------------------------------------
# Batch-size bucket bounds (ops per drained request batch): 1 .. 4096.
BATCH_SIZE_BUCKETS = tuple(2 ** i for i in range(13))

_SERVE_ENTITIES: dict[str, MetricEntity] = {}
_SERVE_LOCK = threading.Lock()


def observe_serve_batch(proto: str, ops: int) -> None:
    """Record one request batch entering a serving path: bump the
    per-protocol batch-size histogram ``yb_serve_batch_ops{proto=...}``
    on the process registry. The distribution answers the question the
    native request-batch path (docs/serving-path.md) lives on: are
    clients actually pipelining, and how much per-batch work does one
    native call amortize? Never raises."""
    try:
        with _SERVE_LOCK:
            ent = _SERVE_ENTITIES.get(proto)
            if ent is None:
                ent = _PROCESS_REGISTRY.entity(proto=proto)
                _SERVE_ENTITIES[proto] = ent
        ent.histogram("yb_serve_batch_ops",
                      buckets=BATCH_SIZE_BUCKETS).observe(ops)
    except Exception:  # noqa: BLE001 — accounting must not throw
        _SWALLOW_LOG.debug("observe_serve_batch failed for %s", proto)


# -- HBM residency-cache observability ----------------------------------------
_HBM_ENTITY: MetricEntity | None = None
_HBM_DEVICE_ENTITIES: dict[str, MetricEntity] = {}


def hbm_cache_entity() -> MetricEntity:
    """The process-registry entity carrying the HBM residency-cache
    series (``yb_hbm_cache_hits``/``misses``/``evictions``,
    ``yb_hbm_demand_upload_bytes``, ``yb_hbm_resident_bytes``) — same
    pattern as ``yb_serve_batch_ops``: the cache is process-wide, so its
    series render on every daemon's /metrics scrape."""
    global _HBM_ENTITY
    with _SERVE_LOCK:
        if _HBM_ENTITY is None:
            _HBM_ENTITY = _PROCESS_REGISTRY.entity()
        return _HBM_ENTITY


def hbm_device_entity(device: str) -> MetricEntity:
    """Per-device HBM residency series: one ``{device=...}``-labeled
    entity per mesh device, carrying
    ``yb_hbm_resident_bytes{device=...}`` and
    ``yb_hbm_demand_upload_bytes{device=...}``.  The unlabeled totals on
    :func:`hbm_cache_entity` stay — both render under the same metric
    name, the labeled series break the totals down by chip."""
    with _SERVE_LOCK:
        ent = _HBM_DEVICE_ENTITIES.get(device)
        if ent is None:
            ent = _PROCESS_REGISTRY.entity(device=device)
            _HBM_DEVICE_ENTITIES[device] = ent
        return ent


_HOST_VERIFY_ENTITY: MetricEntity | None = None


# -- resource-witness observability -------------------------------------------
# Lock-hold duration bucket bounds (seconds): 1us .. ~4.2s, powers of 4.
LOCK_HOLD_S_BUCKETS = tuple(1e-6 * (4 ** i) for i in range(12))

_LOCK_HOLD_ENTITIES: dict[str, MetricEntity] = {}
_RESOURCE_WITNESS_ENTITY: MetricEntity | None = None


def observe_lock_hold_s(cls: str, seconds: float) -> None:
    """Record one lock hold interval (acquire -> release by one thread)
    into the per-owner-class histogram ``yb_lock_hold_seconds{cls=...}``
    on the process registry. Fed by the resource witness
    (utils/resources.py, ``--pin_witness``); the p99 of this series is
    the iholds/ story told live — a lock held across fsync/RPC shows up
    as a fat tail on its class. Never raises."""
    try:
        with _SERVE_LOCK:
            ent = _LOCK_HOLD_ENTITIES.get(cls)
            if ent is None:
                ent = _PROCESS_REGISTRY.entity(cls=cls)
                _LOCK_HOLD_ENTITIES[cls] = ent
        ent.histogram("yb_lock_hold_seconds",
                      buckets=LOCK_HOLD_S_BUCKETS).observe(seconds)
    except Exception:  # noqa: BLE001 — accounting must not throw
        _SWALLOW_LOG.debug("observe_lock_hold_s failed for %s", cls)


def resource_witness_entity() -> MetricEntity:
    """The process-registry entity carrying the resource-witness
    counters (``yb_resource_pin_acquires`` /
    ``yb_resource_pin_releases``; holds across blocking calls are in the
    witness dump, with their sites) — process-wide, so the
    series render on every daemon's /metrics scrape."""
    global _RESOURCE_WITNESS_ENTITY
    with _SERVE_LOCK:
        if _RESOURCE_WITNESS_ENTITY is None:
            _RESOURCE_WITNESS_ENTITY = _PROCESS_REGISTRY.entity()
        return _RESOURCE_WITNESS_ENTITY


# -- write-path observability --------------------------------------------------
# WAL sync latency bucket bounds (milliseconds): 1/16 ms .. ~32 s.
WAL_SYNC_MS_BUCKETS = tuple(0.0625 * (2 ** i) for i in range(20))

_WRITE_PATH_ENTITY: MetricEntity | None = None
_FLUSH_PATH_ENTITIES: dict[str, MetricEntity] = {}


def _write_path_entity() -> MetricEntity:
    global _WRITE_PATH_ENTITY
    with _SERVE_LOCK:
        if _WRITE_PATH_ENTITY is None:
            _WRITE_PATH_ENTITY = _PROCESS_REGISTRY.entity()
        return _WRITE_PATH_ENTITY


def observe_group_commit_batch(entries: int) -> None:
    """Record one leader-side group-commit round: bump the
    ``yb_group_commit_batch_size`` histogram with the number of Raft
    entries coalesced into this WAL sync + AppendEntries window. A p50
    stuck at 1 means concurrent writers are not actually sharing
    replication rounds. Never raises."""
    try:
        _write_path_entity().histogram(
            "yb_group_commit_batch_size",
            buckets=BATCH_SIZE_BUCKETS).observe(entries)
    except Exception:  # noqa: BLE001 — accounting must not throw
        _SWALLOW_LOG.debug("observe_group_commit_batch failed")


def observe_wal_sync_ms(ms: float) -> None:
    """Record one WAL group-commit sync duration (flush + fsync) on the
    ``yb_wal_sync_ms`` histogram. Never raises."""
    try:
        _write_path_entity().histogram(
            "yb_wal_sync_ms", buckets=WAL_SYNC_MS_BUCKETS).observe(ms)
    except Exception:  # noqa: BLE001 — accounting must not throw
        _SWALLOW_LOG.debug("observe_wal_sync_ms failed")


def count_flush_path(path: str) -> None:
    """Bump ``yb_flush_device{path=device|host}``: which build path a
    memtable flush took. ``device`` = the op log replayed into columnar
    planes with the sort permutation applied on-device (ops/flush.py);
    ``host`` = the numpy/native fallback. Never raises."""
    try:
        with _SERVE_LOCK:
            ent = _FLUSH_PATH_ENTITIES.get(path)
            if ent is None:
                ent = _PROCESS_REGISTRY.entity(path=path)
                _FLUSH_PATH_ENTITIES[path] = ent
        ent.counter("yb_flush_device").increment()
    except Exception:  # noqa: BLE001 — accounting must not throw
        _SWALLOW_LOG.debug("count_flush_path failed for %s", path)


def flush_path_count(path: str) -> int:
    """Current ``yb_flush_device{path=...}`` value (0 if never bumped)."""
    with _SERVE_LOCK:
        ent = _FLUSH_PATH_ENTITIES.get(path)
    return ent.counter("yb_flush_device").get() if ent is not None else 0


# -- plane-encoding observability ---------------------------------------------
# Compressed-plane accounting (--tpu_plane_encoding): engines register
# themselves as providers; the gauges below sample them at scrape time,
# so a closed/collected engine silently drops out (weakrefs, no
# unregister call needed). Label values cover every encoding leaf kind
# the columnar encoder can emit plus "plain" for unencoded planes.
PLANE_ENCODINGS = ("plain", "bits", "const", "delta16", "rle", "dict")

_PLANE_LOCK = threading.Lock()
_PLANE_PROVIDERS: dict[int, weakref.ref] = {}
_PLANE_ENTITIES: dict[str, MetricEntity] = {}
_PLANE_RATIO_ENTITY: MetricEntity | None = None


def register_plane_stats(provider) -> None:
    """Register an engine-like ``provider`` whose ``plane_stats()``
    returns ``{"tablet": str, "by_encoding": {kind: bytes},
    "encoded_bytes": int, "logical_bytes": int}`` for its current run
    set. First registration lazily creates the process-registry series
    ``yb_plane_bytes{encoding=...}`` (stored bytes per plane encoding)
    and ``yb_plane_encoded_ratio`` (stored / logical across all
    providers; 1.0 when nothing is encoded). Never raises."""
    global _PLANE_RATIO_ENTITY
    try:
        with _PLANE_LOCK:
            _PLANE_PROVIDERS[id(provider)] = weakref.ref(provider)
            if _PLANE_RATIO_ENTITY is None:
                for k in PLANE_ENCODINGS:
                    ent = _PROCESS_REGISTRY.entity(encoding=k)
                    _PLANE_ENTITIES[k] = ent
                    ent.gauge("yb_plane_bytes",
                              fn=lambda k=k: plane_stats_snapshot()
                              ["by_encoding"].get(k, 0))
                _PLANE_RATIO_ENTITY = _PROCESS_REGISTRY.entity()
                _PLANE_RATIO_ENTITY.gauge(
                    "yb_plane_encoded_ratio",
                    fn=lambda: plane_stats_snapshot()["encoded_ratio"])
    except Exception:  # noqa: BLE001 — accounting must not throw
        _SWALLOW_LOG.debug("register_plane_stats failed")


def plane_stats_snapshot() -> dict:
    """Aggregate plane-encoding stats over every live provider:
    ``{"tablets": [per-provider dicts], "by_encoding": {kind: bytes},
    "encoded_bytes", "logical_bytes", "encoded_ratio"}``. The ratio is
    stored-over-logical bytes (< 1.0 means compression is winning)."""
    with _PLANE_LOCK:
        refs = list(_PLANE_PROVIDERS.items())
    tablets = []
    by: dict[str, int] = {}
    for pid, ref in refs:
        p = ref()
        if p is None:
            with _PLANE_LOCK:
                _PLANE_PROVIDERS.pop(pid, None)
            continue
        try:
            st = p.plane_stats()
        except Exception:  # noqa: BLE001 — scrape must not die
            count_swallowed("metrics.plane_stats")
            continue
        tablets.append(st)
        for k, v in st.get("by_encoding", {}).items():
            by[k] = by.get(k, 0) + int(v)
    encoded = sum(by.values())
    logical = sum(int(t.get("logical_bytes", 0)) for t in tablets)
    return {"tablets": tablets, "by_encoding": by,
            "encoded_bytes": encoded, "logical_bytes": logical,
            "encoded_ratio": (encoded / logical) if logical else 1.0}


def count_host_verify_rows(n: int) -> None:
    """Bump ``yb_scan_host_verify_rows`` by the number of fetched rows
    the host re-verified after a device scan. The device predicate mask
    for string columns is a conservative SUPERSET (ops/scan.py: ``!=``
    on strings stays all-true), so every masked row crosses back for
    host-side verification — this counter makes that silent cliff
    measurable. Never raises."""
    global _HOST_VERIFY_ENTITY
    try:
        with _SERVE_LOCK:
            if _HOST_VERIFY_ENTITY is None:
                _HOST_VERIFY_ENTITY = _PROCESS_REGISTRY.entity()
        _HOST_VERIFY_ENTITY.counter("yb_scan_host_verify_rows").increment(n)
    except Exception:  # noqa: BLE001 — accounting must not throw
        _SWALLOW_LOG.debug("count_host_verify_rows failed")


# -- cluster-elasticity observability -----------------------------------------
# Splits and leader moves are rare, cluster-shaping events: both get
# process-wide counters the master bumps as each operation COMMITS (a
# dispatched-but-failed split does not count), and the traffic-sweep
# harness asserts its own ledger against them exactly like the fault
# sweep does against yb_faults_fired.
_ELASTICITY_ENTITY: MetricEntity | None = None
_REQ_LATENCY_ENTITIES: dict[str, MetricEntity] = {}

# Request latencies are seconds at the frontend: sub-ms point ops up
# through multi-second split-stall retries must all land in-range.
REQUEST_LATENCY_S_BUCKETS = tuple(1e-5 * (2 ** i) for i in range(22))


def _elasticity_entity() -> MetricEntity:
    global _ELASTICITY_ENTITY
    with _SERVE_LOCK:
        if _ELASTICITY_ENTITY is None:
            _ELASTICITY_ENTITY = _PROCESS_REGISTRY.entity()
        return _ELASTICITY_ENTITY


def count_tablet_split() -> None:
    """Bump ``yb_tablet_splits_total``: one committed tablet split
    (parent swapped for both children in the catalog). Never raises."""
    try:
        _elasticity_entity().counter("yb_tablet_splits_total").increment()
    except Exception:  # noqa: BLE001 — accounting must not throw
        _SWALLOW_LOG.debug("count_tablet_split failed")


def tablet_splits_total() -> int:
    """Current ``yb_tablet_splits_total`` value (0 if none committed)."""
    return _elasticity_entity().counter("yb_tablet_splits_total").get()


def count_leader_move() -> None:
    """Bump ``yb_leader_moves_total``: one leader-balancer stepdown
    actually issued to a tserver. Never raises."""
    try:
        _elasticity_entity().counter("yb_leader_moves_total").increment()
    except Exception:  # noqa: BLE001 — accounting must not throw
        _SWALLOW_LOG.debug("count_leader_move failed")


def leader_moves_total() -> int:
    """Current ``yb_leader_moves_total`` value (0 if none issued)."""
    return _elasticity_entity().counter("yb_leader_moves_total").get()


def observe_request_latency(proto: str, seconds: float) -> None:
    """Record one statement's latency as its wire frontend saw it
    (message decoded to reply bytes built) into
    ``yb_request_latency_seconds{proto=pg|cql|redis}`` on the process
    registry. Fed by the frontends themselves (yql/pgsql/wire.py,
    yql/cql/server.py, yql/redis/server.py), never by a client. Never
    raises."""
    try:
        with _SERVE_LOCK:
            ent = _REQ_LATENCY_ENTITIES.get(proto)
            if ent is None:
                ent = _PROCESS_REGISTRY.entity(proto=proto)
                _REQ_LATENCY_ENTITIES[proto] = ent
        ent.histogram("yb_request_latency_seconds",
                      buckets=REQUEST_LATENCY_S_BUCKETS).observe(seconds)
    except Exception:  # noqa: BLE001 — accounting must not throw
        _SWALLOW_LOG.debug("observe_request_latency failed for %s", proto)


# -- span observability (utils/trace.py) ---------------------------------------
# One labeled entity per (series, label values), made on first use. A
# span's histogram is looked up on every span, from threads that have
# just been woken with cold caches, where the entity's own look-up (a
# key tuple, a labels dict, two dicts and a lock) measured 4-7 us of a
# span's 10-16 (PERF.md section 6, PR 39): the helpers that return a
# histogram remember it by their arguments (``functools.cache``).
# docs/observability.md lists every series here with the span that
# feeds it and who reads it.
_SPAN_ENTITIES: dict[tuple, MetricEntity] = {}


def _span_entity(key: tuple, **labels) -> MetricEntity:
    ent = _SPAN_ENTITIES.get(key)
    if ent is None:
        with _SERVE_LOCK:
            ent = _SPAN_ENTITIES.get(key)
            if ent is None:
                ent = _SPAN_ENTITIES[key] = _PROCESS_REGISTRY.entity(
                    **labels)
    return ent


@functools.cache
def span_histogram(name: str) -> Histogram:
    """``yb_span_us{span=name}``: where a span with no histogram of
    its own is observed (microseconds)."""
    return _span_entity(("span", name), span=name).histogram("yb_span_us")


@functools.cache
def rpc_queue_histogram(method: str) -> Histogram:
    """``rpc_queue_us{method=pg|cql|redis}`` for the wire frontends (a
    tserver or master keeps its own, per method, beside
    ``rpc_latency_us`` in its registry): frame parsed to handler
    started."""
    return _span_entity(("queue", method), method=method).histogram(
        "rpc_queue_us")


@functools.cache
def engine_phase_histogram(phase: str, route: str) -> Histogram:
    """``yb_engine_phase_us{phase=issue|wait_fetch|finish, route}``:
    one observation per scan batch and phase (storage/tpu_engine.py);
    ``route`` is ``_plan_scan``'s tag of the batch's plans (``mixed``
    when they differ, ``breaker_host`` for the breaker's fallback)."""
    return _span_entity(("phase", phase, route), phase=phase,
                        route=route).histogram("yb_engine_phase_us")


@functools.cache
def engine_issue_part_histogram(part: str) -> Histogram:
    """``yb_engine_issue_part_us{part=plan|dispatch|copy_out}``: the
    issue phase of a device scan batch in three parts, one observation
    each per batch (storage/tpu_engine.py ``_scan_batch_async_device``)."""
    return _span_entity(("issue_part", part), part=part).histogram(
        "yb_engine_issue_part_us")


@functools.cache
def pg_statement_part_histogram(part: str) -> Histogram:
    """``yb_pg_statement_part_us{part=parse|plan|scans|combine|reply}``:
    a PG statement outside its scan units' RPCs, one observation of
    each part a statement that reaches it (yql/pgsql/wire.py ``_query``,
    yql/pgsql/executor.py ``PgProcessor.execute``)."""
    return _span_entity(("pg_part", part), part=part).histogram(
        "yb_pg_statement_part_us")


@functools.cache
def rpc_call_histogram(method: str) -> Histogram:
    """``rpc_call_us{method}``: the caller's side of a call over a
    socket, request encoded until the reply's body is in hand
    (rpc/proxy.py ``Proxy.call``)."""
    return _span_entity(("call", method), method=method).histogram(
        "rpc_call_us")


@functools.cache
def rpc_respond_histogram(method: str) -> Histogram:
    """``rpc_respond_us{method}``: a handler's answer serialised and
    queued on its connection (rpc/messenger.py ``_dispatch``)."""
    return _span_entity(("respond", method), method=method).histogram(
        "rpc_respond_us")


RPC_REPLY_WRITERS = ("worker", "reactor")
RPC_REPLY_READERS = ("caller", "peer")


@functools.cache
def rpc_reply_writes_counter(by: str) -> Counter:
    """``rpc_reply_writes{by=worker|reactor}``: one a reply of a socket
    server (rpc/messenger.py ``_write_reply``): ``worker`` where the
    thread that ran the handler wrote the whole frame to the socket
    itself, ``reactor`` where any part of it was queued for the reactor
    (the socket took a part or nothing, or frames were queued already).
    ``worker`` is counted before the send and taken back where the
    socket took only a part, so that nothing runs after a send that
    took it all."""
    return _span_entity(("reply_writes", by), by=by).counter(
        "rpc_reply_writes")


def rpc_reply_writes() -> dict[str, int]:
    """Current ``rpc_reply_writes`` by writer."""
    return {by: rpc_reply_writes_counter(by).get()
            for by in RPC_REPLY_WRITERS}


@functools.cache
def rpc_reply_reads_counter(by: str) -> Counter:
    """``rpc_reply_reads{by=caller|peer}``: one a call over a socket
    that got its reply (rpc/proxy.py ``Proxy._call``): ``caller`` where
    the calling thread read the reply's frame from the socket itself,
    ``peer`` where another caller of the same proxy, reading for its
    own reply, handed it over."""
    return _span_entity(("reply_reads", by), by=by).counter(
        "rpc_reply_reads")


def rpc_reply_reads() -> dict[str, int]:
    """Current ``rpc_reply_reads`` by reader."""
    return {by: rpc_reply_reads_counter(by).get()
            for by in RPC_REPLY_READERS}


rpc_reply_writes()
rpc_reply_reads()


@functools.cache
def mesh_issue_part_histogram(part: str) -> Histogram:
    """``yb_mesh_issue_part_us{part=lower|dispatch}``: the issue phase
    of a grouped mesh request in two parts, one observation each per
    request (parallel/sharded.py ``sharded_grouped_aggregate``)."""
    return _span_entity(("mesh_issue_part", part), part=part).histogram(
        "yb_mesh_issue_part_us")


@functools.cache
def apply_stall_histogram() -> Histogram:
    """``yb_apply_stall_us``: how long a memtable flush held the thread
    that applies committed Raft entries (span ``engine.flush`` with
    ``thread=apply``, storage/tpu_engine.py ``_after_apply``)."""
    return _span_entity(("apply_stall",)).histogram("yb_apply_stall_us")


@functools.cache
def compaction_histogram(route: str, kind: str) -> Histogram:
    """``yb_compaction_us{route, kind}``: one compaction from the runs
    as they were to the run list swapped (span ``engine.compact``).
    ``route``: where the retention mask was computed (``device``,
    ``host``) or ``host_merge`` for the heap merge of keys beyond the
    device prefix; ``kind``: ``full`` or ``subset``."""
    return _span_entity(("compaction", route, kind), route=route,
                        kind=kind).histogram("yb_compaction_us")


def count_compaction(route: str, kind: str, by: str) -> None:
    """``yb_compactions{route, kind, by}``, beside the histogram; ``by``
    is whose thread ran it: ``worker`` (the tablet peer's background
    thread), ``apply`` (an engine with no peer, where the write was
    applied) or ``caller`` (a manual compaction)."""
    _span_entity(("compactions", route, kind, by), route=route, kind=kind,
                 by=by).counter("yb_compactions").increment()


@functools.cache
def jit_compile_histogram(entry: str) -> Histogram:
    """``yb_jit_compile_seconds{entry}``: seconds a dispatch spent
    tracing and compiling, beside ``yb_jit_compiles{entry}``."""
    return _span_entity(("compile", entry), entry=entry).histogram(
        "yb_jit_compile_seconds", buckets=REQUEST_LATENCY_S_BUCKETS)


@functools.cache
def device_upload_histogram() -> Histogram:
    """``yb_device_upload_seconds``: host time of one run's upload
    (pad, ``device_put`` of every plane)."""
    return _span_entity(("upload",)).histogram(
        "yb_device_upload_seconds", buckets=REQUEST_LATENCY_S_BUCKETS)


def count_device_upload_bytes(n: int) -> None:
    """``yb_device_upload_bytes``: bytes those uploads put on the
    device."""
    _span_entity(("upload",)).counter("yb_device_upload_bytes").increment(n)


def count_device_dispatch(entry: str, read_bytes: int, h2d: int,
                          d2h: int) -> None:
    """One device program dispatched for ``entry``:
    ``yb_device_dispatches{entry}`` += 1,
    ``yb_device_program_read_bytes{entry}`` += the resident bytes of the
    planes its signature names (``ops.device_run.program_read_bytes``),
    the bytes a roofline sets against the program's device time, and
    ``yb_device_transfers{dir=h2d|d2h, entry}`` += the arrays the
    dispatch hands the runtime to move: parameters up, outputs down
    (each costs the host a call, whatever its size)."""
    ent = _span_entity(("dispatch", entry), entry=entry)
    ent.counter("yb_device_dispatches").increment()
    ent.counter("yb_device_program_read_bytes").increment(read_bytes)
    for direction, n in (("h2d", h2d), ("d2h", d2h)):
        _span_entity(("transfer", entry, direction), dir=direction,
                     entry=entry).counter(
                         "yb_device_transfers").increment(n)


GROUPED_AGG_FALLBACK_REASONS = ("negs", "collision", "decode")


def _grouped_fallback_counter(reason: str) -> Counter:
    return _span_entity(("grouped_fallback", reason),
                        reason=reason).counter("yb_grouped_agg_fallbacks")


def count_grouped_agg_fallback(reason: str) -> None:
    """``yb_grouped_agg_fallbacks{reason=negs|collision|decode}``: a
    grouped-aggregate program's answer was thrown away and the scan
    served again as a host row scan (storage/tpu_engine.py
    ``_finish_grouped``): a negative base or factor, two groups in one
    bucket, a group value the host cannot decode."""
    _grouped_fallback_counter(reason).increment()


def grouped_agg_fallbacks() -> dict[str, int]:
    """Current ``yb_grouped_agg_fallbacks`` by reason."""
    return {r: _grouped_fallback_counter(r).get()
            for r in GROUPED_AGG_FALLBACK_REASONS}


# (every reason reads 0 on /metrics from the start: a series that is
# missing cannot be told from one that never grew)
grouped_agg_fallbacks()


@functools.cache
def overlay_build_histogram(how: str) -> Histogram:
    """``yb_overlay_build_us{how=full|delta|mini_run}``: one build of
    the delta overlay's state (span ``engine.overlay.build``,
    storage/tpu_engine.py ``_overlay``): ``full`` collects every dirty
    key and masks the primary, ``delta`` advances a cached state by the
    memtable's versions since, ``mini_run`` lays the dirty keys' version
    lists out as the small device run a grouped aggregate folds."""
    return _span_entity(("overlay_build", how), how=how).histogram(
        "yb_overlay_build_us")


def count_overlay_scan(kind: str, outcome: str, reason: str = "") -> None:
    """``yb_overlay_scans{kind=grouped|flat, outcome=device|host,
    reason}``: one aggregate over several sources (overlapping runs, a
    live memtable): answered by device programs over the delta overlay,
    or by the host row scan because the dirty set passed half the
    primary (``dirty_set``) or the spec cannot be lowered (``spec``)."""
    _span_entity(("overlay_scan", kind, outcome, reason), kind=kind,
                 outcome=outcome, reason=reason).counter(
                     "yb_overlay_scans").increment()


def overlay_scans() -> dict[tuple, int]:
    """Current ``yb_overlay_scans`` by (kind, outcome, reason)."""
    return {key[1:]: ent.counter("yb_overlay_scans").get()
            for key, ent in list(_SPAN_ENTITIES.items())
            if key[0] == "overlay_scan"}


def set_overlay_size(dirty_keys: int, delta_versions: int) -> None:
    """Gauges ``yb_overlay_dirty_keys`` and ``yb_overlay_delta_versions``:
    the dirty keys of the overlay state this process built last, and
    the versions their merged lists hold (the mini-run's rows)."""
    ent = _span_entity(("overlay_size",))
    ent.gauge("yb_overlay_dirty_keys").set(dirty_keys)
    ent.gauge("yb_overlay_delta_versions").set(delta_versions)


# (the device outcomes read 0 on /metrics from the start)
for _kind in ("grouped", "flat"):
    _span_entity(("overlay_scan", _kind, "device", ""), kind=_kind,
                 outcome="device", reason="").counter("yb_overlay_scans")
    _span_entity(("overlay_scan", _kind, "host", "dirty_set"), kind=_kind,
                 outcome="host", reason="dirty_set").counter(
                     "yb_overlay_scans")
set_overlay_size(0, 0)


GROUPED_PRESENCE_FORMS = ("packed", "rows")


def _grouped_presence_counter(form: str) -> Counter:
    return _span_entity(("grouped_presence", form),
                        form=form).counter("yb_grouped_presence")


def count_grouped_presence(form: str) -> None:
    """``yb_grouped_presence{form=packed|rows}``: a grouped-aggregate
    program with group columns was traced (ops/group_agg.py
    ``grouped_aggregate``; once a compiled program, nothing a request
    pays) and handed its kernel the presence masks combined on the
    packed words of "bits" leaves (``packed``) or unpacked plane by
    plane (``rows``: a run that is not flat, a plain bool plane among
    the presence planes)."""
    _grouped_presence_counter(form).increment()


def grouped_presence() -> dict[str, int]:
    """Current ``yb_grouped_presence`` by form."""
    return {f: _grouped_presence_counter(f).get()
            for f in GROUPED_PRESENCE_FORMS}


grouped_presence()


GROUPED_BUCKET_FORMS = ("direct", "hashed")


def _grouped_buckets_counter(form: str) -> Counter:
    return _span_entity(("grouped_buckets", form),
                        form=form).counter("yb_grouped_buckets")


def count_grouped_buckets(form: str) -> None:
    """``yb_grouped_buckets{form=direct|hashed}``: one grouped-aggregate
    program with group columns was dispatched (beside
    ``yb_device_dispatches``: a vmapped batch and a mesh program are one
    each) whose buckets are addressed by the group columns' dictionary
    codes (``direct``: every group column a "dict" leaf of the run, the
    product of the caps within ops.group_agg.NUM_BUCKETS) or by a hash
    of the key planes (``hashed``: everything else)."""
    _grouped_buckets_counter(form).increment()


def grouped_buckets() -> dict[str, int]:
    """Current ``yb_grouped_buckets`` by form."""
    return {f: _grouped_buckets_counter(f).get()
            for f in GROUPED_BUCKET_FORMS}


grouped_buckets()


GROUPED_RESOLVE_FORMS = ("flat", "lookback", "segmented")


def _grouped_resolve_counter(form: str) -> Counter:
    return _span_entity(("grouped_resolve", form),
                        form=form).counter("yb_grouped_resolve")


def count_grouped_resolve(form: str) -> None:
    """``yb_grouped_resolve{form=flat|lookback|segmented}``: one
    ops.group_agg program was dispatched, with group columns or without
    (beside ``yb_device_dispatches``: a vmapped batch and a mesh program
    are one each), whose windows merge their versions elementwise
    (``flat``: a run of one version a key), by static shifts and selects
    (``lookback``: a run that is not flat whose largest key group is
    within ops.lookback_fold.MAX_LOOKBACK, as the delta overlay's
    mini-run) or by segment ops and gathers (``segmented``: past the
    bound, and every mesh stack that is not flat)."""
    _grouped_resolve_counter(form).increment()


def grouped_resolve() -> dict[str, int]:
    """Current ``yb_grouped_resolve`` by form."""
    return {f: _grouped_resolve_counter(f).get()
            for f in GROUPED_RESOLVE_FORMS}


grouped_resolve()


MESH_SCAN_KINDS = ("agg", "rows")
MESH_SCAN_OUTCOMES = ("served", "ineligible", "chip_loss")


def _mesh_scan_counter(kind: str, outcome: str) -> Counter:
    return _span_entity(("mesh_scan", kind, outcome), kind=kind,
                        outcome=outcome).counter("yb_mesh_scans")


def count_mesh_scan(kind: str, outcome: str) -> None:
    """``yb_mesh_scans{kind=agg|rows, outcome}``: one multi-tablet
    request at a tserver's mesh (tserver/mesh_scan.py): ``served`` as
    one device program over the node's chips, ``ineligible`` (an engine
    state or a spec the mesh cannot answer exactly: the client's
    per-tablet path serves it) or ``chip_loss`` (the dispatch lost a
    chip; the stacks were dropped and the per-tablet path serves)."""
    _mesh_scan_counter(kind, outcome).increment()


def mesh_scans() -> dict[tuple[str, str], int]:
    """Current ``yb_mesh_scans`` by (kind, outcome)."""
    return {(k, o): _mesh_scan_counter(k, o).get()
            for k in MESH_SCAN_KINDS for o in MESH_SCAN_OUTCOMES}


mesh_scans()


def count_mesh_stack_build(how: str) -> None:
    """``yb_mesh_stack_builds{how=build|update}``: a tserver stacked its
    tablets' runs over the mesh anew (``build``: host stack, encode,
    sharded upload; span ``mesh.stack_build``) or rewrote one tablet's
    slot of a cached stack in place (``update``)."""
    _span_entity(("mesh_stack", how), how=how).counter(
        "yb_mesh_stack_builds").increment()
