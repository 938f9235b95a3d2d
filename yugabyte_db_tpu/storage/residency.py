"""Device/host residency manager: the HBM block-cache analog.

Reference analog: src/yb/rocksdb/util/cache.cc — the LRU block cache
with a high-pri/low-pri pool split (sized and wired for docdb in
docdb_rocksdb_util.cc) that lets SSTable working sets exceed RAM.  Here
the cached unit is a whole columnar run's device plane group: the
host-side ``ColumnarRun`` stays authoritative, ``TpuRun`` demand-uploads
its ``DeviceRun`` through this cache on first access, and when a
device's budget (``--tpu_hbm_budget_bytes``, PER DEVICE — each chip has
its own HBM) is exceeded the least recently used unpinned plane group
*on that device* is dropped, releasing its device buffers and debiting
the owning engine's ``device`` MemTracker subtree so /memz and /metrics
show true residency.

The budget is a per-device map, not one process-wide pool: every entry
belongs to exactly one owning device (demand re-uploads go back to it),
except sharded mesh stacks, whose external registration carries a
per-device byte map — one shard's bytes charged to the chip actually
holding it.  Admission and eviction are scoped to the admitting
device, so a hot working set on chip 0 never evicts chip 3's shards.
On a single-device host the map has one bucket and behavior is
byte-identical to the old process-wide budget.

Scan resistance mirrors the reference's two-pool policy: point-get and
bounded-scan traffic is admitted to (or promoted into) the protected
high-pri pool; full-table-scan traffic is admitted to the low-pri pool,
so one large scan streams through the low pool and cannot flush the hot
working set.  A configurable fraction of the budget
(``HIGH_PRI_POOL_RATIO``) caps the high pool; overflow demotes its LRU
entries into the low pool, exactly like the reference's high-pri pointer
walk.

Pins keep a plane group resident across a dispatch window (issue→finish
in ``scan_batch_async``, compaction's ``resident_gc_mask``, the cached
delta-overlay primary, the sharded mesh arrays).  Pinned entries are
never evicted — a pinned set larger than the budget overflows it
(non-strict capacity, as in the reference's pinned-usage accounting)
rather than failing the dispatch.

This module deliberately imports no device framework: payloads are built
by caller-supplied closures, so /memz handlers and tests can import it
without touching jax.
"""

from __future__ import annotations

import threading
import weakref
from collections import OrderedDict, deque

from yugabyte_db_tpu.utils.flags import FLAGS
from yugabyte_db_tpu.utils.locking import guarded_by
from yugabyte_db_tpu.utils.memtracker import root_tracker
from yugabyte_db_tpu.utils.metrics import hbm_cache_entity, hbm_device_entity
from yugabyte_db_tpu.utils.sync_point import sync_point

# Fraction of the budget reserved for the protected (high-pri) pool.
HIGH_PRI_POOL_RATIO = 0.8

# Device bucket for callers that never name a device (single-chip hosts,
# tests driving the cache directly).  Callers on a real mesh pass
# "<platform>:<id>" strings (ops.device_run.device_label).
DEFAULT_DEVICE = "device:0"

# Sentinel payload for externally-owned residency (bytes uploaded outside
# the cache but accounted through it, e.g. the sharded mesh arrays).
_EXTERNAL = object()


def _pin_witness():
    """The resource witness when enabled, else None — every pin-count
    transition below reports through this (utils/resources.py)."""
    from yugabyte_db_tpu.utils import resources

    w = resources.witness()
    return w if w.enabled else None


class _Entry:
    __slots__ = ("key", "label", "tracker", "owner_ref", "payload",
                 "nbytes", "pins", "pool", "external",
                 "encoding", "device", "dev_bytes", "retired")

    def __init__(self, key: int, label: str, tracker,
                 device: str = DEFAULT_DEVICE):
        self.key = key
        self.label = label
        self.tracker = tracker
        self.owner_ref = None
        self.payload = None
        self.nbytes = 0
        self.pins = 0
        self.pool = "high"
        self.external = False
        # Plane-format tag of the resident payload ("plain", "encoded",
        # "external"); sampled duck-typed from the payload at admit so
        # /memz can show which runs hold compressed bytes in HBM.
        self.encoding = "plain"
        # The owning device: demand re-uploads target it, and its budget
        # bucket is the one this entry's bytes count against.
        self.device = device
        # External mesh stacks only: per-device byte map (one shard's
        # bytes on the chip holding it).  None for single-device entries.
        self.dev_bytes: dict | None = None
        # The owner left its run set while a dispatch window still held
        # a pin (:meth:`HbmCache.retire`): dropped at the last unpin.
        self.retired = False


# _dead is deliberately NOT declared: the weakref death callback
# appends to it lock-free (atomic deque), _drain_dead consumes under
# _lock — see register().
@guarded_by("_lock", "_entries", "_pools", "_next_key", "_resident",
            "_peak_resident", "_dev_resident")
class HbmCache:
    """Process-wide capacity-budgeted cache of device plane groups.

    Keys are integer tokens handed out by :meth:`register`; each token is
    tied to its owner by a weakref, so a dropped run releases its device
    bytes without the cache pinning the host run alive.  ``acquire`` is
    the one read path: hit → LRU touch (plus promotion into the
    protected pool when the access is ``priority="high"``), miss → evict
    down to budget, build the payload via the caller's closure (the
    demand re-upload), charge the owner's MemTracker, admit.
    """

    def __init__(self):
        self._lock = threading.RLock()
        self._entries: dict[int, _Entry] = {}
        # Keys whose owners were collected.  Weakref death callbacks run
        # at arbitrary allocation points — including re-entrantly on a
        # thread already inside the cache (the lock is an RLock) — so
        # they must not mutate _entries/_pools directly; they append
        # here (deque.append is atomic) and every public method drains
        # the queue under the lock before touching shared state.
        self._dead: deque[int] = deque()
        # Eviction order: oldest first.  "low" drains before "high".
        self._pools: dict[str, OrderedDict] = {"low": OrderedDict(),
                                               "high": OrderedDict()}
        self._next_key = 1
        self._resident = 0
        self._peak_resident = 0
        # Per-device residency + demand-upload accounting.  Buckets are
        # created on first charge; each gets its {device=...}-labeled
        # gauge/counter pair on the process registry.
        self._dev_resident: dict[str, int] = {}
        self._dev_upload: dict[str, object] = {}
        ent = hbm_cache_entity()
        self._m_hits = ent.counter("yb_hbm_cache_hits")
        self._m_misses = ent.counter("yb_hbm_cache_misses")
        self._m_evictions = ent.counter("yb_hbm_cache_evictions")
        self._m_upload = ent.counter("yb_hbm_demand_upload_bytes")
        ent.gauge("yb_hbm_resident_bytes", self.resident_bytes)
        ent.gauge("yb_hbm_pinned_bytes", self.pinned_bytes)
        ent.gauge("yb_hbm_budget_bytes", self.budget)

    # -- configuration --------------------------------------------------------

    @staticmethod
    def budget() -> int:
        """Current byte budget PER DEVICE; 0 means unbounded."""
        try:
            return int(FLAGS.get("tpu_hbm_budget_bytes"))
        except KeyError:
            return 0

    # -- registration ---------------------------------------------------------

    def register(self, owner, tracker=None, label: str = "",
                 device: str = DEFAULT_DEVICE) -> int:
        """A residency key for ``owner`` (a TpuRun or similar).  The
        entry auto-invalidates when ``owner`` is collected; ``tracker``
        (the engine's device MemTracker) is charged while resident.
        ``device`` names the owning chip's budget bucket — demand
        re-uploads for this key must target that device."""
        with self._lock:
            self._drain_dead()
            key = self._next_key
            self._next_key += 1
            e = _Entry(key, label or type(owner).__name__, tracker,
                       device=device or DEFAULT_DEVICE)
            if owner is not None:
                # Deliberate: the death callback only ENQUEUES into a
                # deque (append is atomic under the GIL); _drain_dead
                # consumes under _lock. This is the deferred-mutation
                # shape the rule prescribes, not the race it flags.
                e.owner_ref = weakref.ref(
                    owner,
                    # yb-lint: disable=iraces/callback-into-locked-state
                    lambda _r, k=key: self._dead.append(k))
            self._entries[key] = e
            return key

    def add_external(self, owner, nbytes: int, tracker=None,
                     label: str = "external",
                     device: str = DEFAULT_DEVICE,
                     dev_bytes: dict | None = None) -> int:
        """Account ``nbytes`` of device residency uploaded outside the
        cache (sharded mesh arrays, the overlay's masked valid plane).
        External entries are permanently pinned until invalidated (or
        their owner is collected); they overflow the budget rather than
        being evictable.  ``dev_bytes`` (device name -> bytes) charges a
        multi-device upload per shard — the sharded mesh stacks — and
        overrides ``nbytes``/``device`` when given."""
        key = self.register(owner, tracker, label, device=device)
        with self._lock:
            self._drain_dead()
            e = self._entries.get(key)
            if e is None:  # owner died during registration
                return key
            e.external = True
            e.payload = _EXTERNAL
            e.encoding = "external"
            if dev_bytes:
                e.dev_bytes = {d: int(n) for d, n in dev_bytes.items()}
                e.nbytes = sum(e.dev_bytes.values())
            else:
                e.nbytes = int(nbytes)
            e.pins = 1
            w = _pin_witness()
            if w is not None:
                w.pin_acquired(key, label=e.label, external=True)
            self._pools["high"][key] = e
            self._charge(e, e.nbytes)
        return key

    def invalidate(self, key: int) -> None:
        """Drop the entry entirely: release device bytes and forget the
        key.  Owner-teardown only — a later acquire() on this key takes
        the unmanaged fallback.  For owners that stay live (planes
        rebuilt in place), use :meth:`release` instead."""
        with self._lock:
            self._drain_dead()
            e = self._entries.pop(key, None)
            if e is not None and e.payload is not None:
                self._release_entry(e, evicted=False)

    def retire(self, key: int) -> None:
        """:meth:`invalidate` for an owner that readers may still hold
        (a run that a compaction replaced): while a pin is out the
        planes stay resident and accounted, as they do for an eviction,
        and the last :meth:`unpin` drops the entry."""
        with self._lock:
            self._drain_dead()
            e = self._entries.get(key)
            if e is not None and e.pins > 0 and e.payload is not None:
                e.retired = True
            else:
                self.invalidate(key)

    def release(self, key: int) -> None:
        """Drop the entry's resident payload but keep the registration:
        the next acquire() demand-rebuilds through the cache, still
        budgeted and MemTracker-accounted.  The right call when the
        owner outlives its current upload (e.g. ALTER grows the host
        planes and the stale device copy must go)."""
        with self._lock:
            self._drain_dead()
            e = self._entries.get(key)
            if e is not None and e.payload is not None:
                self._release_entry(e, evicted=False)

    # -- the read path --------------------------------------------------------

    def acquire(self, key: int, build, nbytes_hint: int | None = None,
                priority: str | None = None, pin: bool = False):
        """The payload for ``key``, demand-built on miss.

        ``build`` returns ``(payload, nbytes)`` — it runs under the cache
        lock, serializing uploads (by design: concurrent uploads under
        memory pressure would overshoot the budget).  ``nbytes_hint``
        lets the cache evict *before* uploading so residency never
        transiently exceeds the budget.  ``priority`` is "high", "low",
        or None; None admits high but never promotes an existing low
        entry (so follow-up accesses inside a full scan don't defeat
        scan resistance).  ``pin=True`` takes a pin before returning.
        """
        with self._lock:
            self._drain_dead()
            e = self._entries.get(key)
            if e is None:
                # Owner already unregistered (e.g. a scan finishing after
                # compaction dropped its run): serve unmanaged so in-flight
                # reads stay correct; nothing to account.
                payload, _ = build()
                return payload
            if e.payload is not None:
                pool = self._pools[e.pool]
                pool.move_to_end(key)
                if priority == "high" and e.pool == "low":
                    self._move_pool(e, "high")
                if pin:
                    e.pins += 1
                    w = _pin_witness()
                    if w is not None:
                        w.pin_acquired(key, label=e.label)
                hit = True
                payload = e.payload
            else:
                payload = self._admit(e, build, nbytes_hint, priority,
                                      pin)
                hit = False
        (self._m_hits if hit else self._m_misses).increment()
        return payload

    def pin(self, key: int, build, nbytes_hint: int | None = None,
            priority: str | None = None):
        """Acquire + pin: the payload stays resident until :meth:`unpin`."""
        return self.acquire(key, build, nbytes_hint, priority, pin=True)

    def peek(self, key: int):
        """The resident payload, or None — never builds, never reorders
        the LRU pools.  For opportunistic reuse of planes that happen to
        be on device (e.g. feeding a stacked-mesh tablet update from the
        device-flush output) where a miss should NOT trigger an upload."""
        with self._lock:
            self._drain_dead()
            e = self._entries.get(key)
            return e.payload if e is not None else None

    def unpin(self, key: int) -> None:
        with self._lock:
            self._drain_dead()
            e = self._entries.get(key)
            if e is None:
                return
            if e.pins > 0:
                e.pins -= 1
                w = _pin_witness()
                if w is not None:
                    w.pin_released(key)
            if e.retired and e.pins == 0:
                del self._entries[key]
                if e.payload is not None:
                    self._release_entry(e, evicted=False)
                return
            # Unpinning may unlock deferred evictions on this device.
            b = self.budget()
            if b and self._dev_resident.get(e.device, 0) > b:
                self._evict_until(b, e.device)

    # -- internals ------------------------------------------------------------

    def _drain_dead(self) -> None:
        """Reap entries whose owners were collected (lock held).  The
        weakref callbacks only enqueue; all structural mutation happens
        here, at a point where no pool iteration is in progress."""
        while True:
            try:
                key = self._dead.popleft()
            except IndexError:
                return
            e = self._entries.pop(key, None)
            if e is not None and e.payload is not None:
                self._release_entry(e, evicted=False)

    def _admit(self, e: _Entry, build, hint, priority, pin: bool):
        b = self.budget()
        # The device MemTracker limit is the SUM of per-device budgets:
        # one flag value per chip seen so far.
        ndev = max(1, len(self._dev_resident))
        root_tracker().child("device").set_limit((b * ndev) or None)
        if b and hint:
            self._evict_until(max(b - int(hint), 0), e.device)
        payload, nbytes = build()
        e.payload = payload
        # DeviceRun payloads carry .encoded (compressed plane tree vs
        # plain planes under --tpu_plane_encoding); anything else —
        # including a demand re-upload after eviction — defaults plain.
        e.encoding = ("encoded" if getattr(payload, "encoded", False)
                      else "plain")
        e.nbytes = int(nbytes)
        e.pool = "low" if priority == "low" else "high"
        self._pools[e.pool][e.key] = e
        if pin:
            e.pins += 1
            w = _pin_witness()
            if w is not None:
                w.pin_acquired(e.key, label=e.label)
        self._charge(e, e.nbytes)
        self._m_upload.increment(e.nbytes)
        up = self._dev_upload.get(e.device)
        if up is not None:
            up.increment(e.nbytes)
        if b:
            self._rebalance_high(b, e.device)
            self._evict_until(b, e.device)
        sync_point("hbm_cache:admit", e.label)
        return payload

    def _bump_dev(self, device: str, nbytes: int) -> None:
        """Adjust one device's residency bucket (lock held); first touch
        lazily creates the {device=...}-labeled metric series."""
        if device not in self._dev_resident:
            self._dev_resident[device] = 0
            ent = hbm_device_entity(device)
            ent.gauge("yb_hbm_resident_bytes",
                      lambda d=device: self.device_resident_bytes(d))
            self._dev_upload[device] = ent.counter(
                "yb_hbm_demand_upload_bytes")
        self._dev_resident[device] += nbytes

    def _charge(self, e: _Entry, nbytes: int) -> None:
        self._resident += nbytes
        if self._resident > self._peak_resident:
            self._peak_resident = self._resident
        if e.dev_bytes is not None and nbytes == e.nbytes:
            # External multi-device initial charge: split per shard.
            for d, n in e.dev_bytes.items():
                self._bump_dev(d, n)
        else:
            self._bump_dev(e.device, nbytes)
        if e.tracker is not None:
            e.tracker.consume(nbytes)

    def _move_pool(self, e: _Entry, pool: str) -> None:
        self._pools[e.pool].pop(e.key, None)
        e.pool = pool
        self._pools[pool][e.key] = e

    def _rebalance_high(self, b: int, device: str) -> None:
        """High-pool cap, per device: one chip's protected working set
        can't demote another chip's."""
        cap = int(b * HIGH_PRI_POOL_RATIO)
        high = self._pools["high"]
        hb = sum(en.nbytes for en in high.values()
                 if not en.external and en.device == device)
        for k in list(high.keys()):
            if hb <= cap:
                break
            en = high[k]
            if en.external or en.device != device:
                continue
            self._move_pool(en, "low")
            hb -= en.nbytes

    def _evict_until(self, target: int, device: str | None = None) -> None:
        """Evict LRU-first until ``device``'s bucket (or, with
        device=None, global residency) is within ``target``."""
        def over():
            if device is None:
                return self._resident > target
            return self._dev_resident.get(device, 0) > target
        while over():
            if not self._evict_one(device):
                break  # everything left is pinned: allowed overflow

    def _evict_one(self, device: str | None = None) -> bool:
        for pool_name in ("low", "high"):
            for en in self._pools[pool_name].values():
                if en.pins == 0 and not en.external and (
                        device is None or en.device == device):
                    self._release_entry(en, evicted=True)
                    return True
        return False

    def _release_entry(self, e: _Entry, evicted: bool) -> None:
        total = e.nbytes
        w = _pin_witness()
        if w is not None:
            # Entry teardown retires every pin on the key at once
            # (invalidate / owner collected) — balanced, not a leak.
            w.pins_cleared(e.key)
        self._pools[e.pool].pop(e.key, None)
        e.payload = None
        self._resident -= total
        if e.dev_bytes is not None:
            for d, n in e.dev_bytes.items():
                self._bump_dev(d, -n)
            e.dev_bytes = None
        else:
            self._bump_dev(e.device, -total)
        if e.tracker is not None:
            e.tracker.release(total)
        e.nbytes = 0
        e.pins = 0
        e.encoding = "plain"
        if evicted:
            self._m_evictions.increment()
            sync_point("hbm_cache:evict", e.label)

    # -- introspection --------------------------------------------------------

    def resident_bytes(self) -> int:
        with self._lock:
            self._drain_dead()
            return self._resident

    def device_resident_bytes(self, device: str | None = None):
        """One device's resident bytes, or the full {device: bytes} map
        when ``device`` is None."""
        with self._lock:
            if device is not None:
                return self._dev_resident.get(device, 0)
            return dict(self._dev_resident)

    def pinned_bytes(self) -> int:
        with self._lock:
            self._drain_dead()
            return sum(e.nbytes
                       for pool in self._pools.values()
                       for e in pool.values() if e.pins > 0)

    def peak_resident_bytes(self) -> int:
        with self._lock:
            return self._peak_resident

    def evict_unpinned(self) -> int:
        """Drop every unpinned entry (test hook for eviction pressure);
        returns how many entries were evicted."""
        n = 0
        with self._lock:
            self._drain_dead()
            while self._evict_one():
                n += 1
        return n

    def stats(self) -> dict:
        with self._lock:
            self._drain_dead()
            pools = {
                name: {"entries": len(pool),
                       "bytes": sum(e.nbytes for e in pool.values())}
                for name, pool in self._pools.items()}
            by_enc: dict[str, dict] = {}
            for pool in self._pools.values():
                for e in pool.values():
                    d = by_enc.setdefault(e.encoding,
                                          {"entries": 0, "bytes": 0})
                    d["entries"] += 1
                    d["bytes"] += e.nbytes
            b = self.budget()
            by_dev: dict[str, dict] = {
                dev: {"resident_bytes": n, "budget_bytes": b,
                      "entries": 0, "pinned_bytes": 0}
                for dev, n in sorted(self._dev_resident.items())}
            for pool in self._pools.values():
                for e in pool.values():
                    devs = (e.dev_bytes if e.dev_bytes is not None
                            else {e.device: e.nbytes})
                    for dev, n in devs.items():
                        d = by_dev.setdefault(
                            dev, {"resident_bytes": 0, "budget_bytes": b,
                                  "entries": 0, "pinned_bytes": 0})
                        d["entries"] += 1
                        if e.pins > 0:
                            d["pinned_bytes"] += n
            out = {
                "budget_bytes": b,
                "resident_bytes": self._resident,
                "peak_resident_bytes": self._peak_resident,
                "registered": len(self._entries),
                "pools": pools,
                "by_encoding": by_enc,
                "by_device": by_dev,
            }
        out["pinned_bytes"] = self.pinned_bytes()
        out["hits"] = self._m_hits.get()
        out["misses"] = self._m_misses.get()
        out["evictions"] = self._m_evictions.get()
        out["demand_upload_bytes"] = self._m_upload.get()
        return out


def device_nbytes(tree) -> int:
    """Device bytes of a nested dict/list/tuple of arrays (duck-typed:
    anything with .size and .dtype.itemsize) — the footprint charged for
    cache payloads."""
    total = 0
    stack = [tree]
    while stack:
        node = stack.pop()
        if isinstance(node, dict):
            stack.extend(node.values())
        elif isinstance(node, (list, tuple)):
            stack.extend(node)
        elif node is not None:
            total += int(node.size) * node.dtype.itemsize
    return total


_CACHE: HbmCache | None = None
_CACHE_LOCK = threading.Lock()


def hbm_cache() -> HbmCache:
    """The process-wide residency cache (one HBM, one budget)."""
    global _CACHE
    if _CACHE is None:
        with _CACHE_LOCK:
            if _CACHE is None:
                _CACHE = HbmCache()
    return _CACHE
