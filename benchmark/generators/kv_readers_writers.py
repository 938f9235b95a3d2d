"""Closed-loop readers and writers over the CQL wire, as upstream's
CassandraKeyValue sample app runs them side by side: each thread is one
connection with prepared statements that waits for every reply.

A reader SELECTs a key drawn uniformly from those acknowledged so far. A
writer INSERTs single rows, alternately the next unwritten key of the
app's sequence and a new version of a uniformly drawn written key. The
key space is split among the generator processes by ``n % processes``, so
that the process that reads a key is the one that knows which version of
it was last acknowledged: a read must return a version no older than
that, and after the window every key written is read back.

Traffic parameters: ``readers`` and ``writers`` (over all processes),
``timeout_s``, ``overwrite_every`` (2: every second write is an
overwrite), ``replica_sample`` (keys, over all processes, handed to the
harness to be read from each replica's engine).
"""

from __future__ import annotations

import random
import threading
import time

from benchmark.clients.minicql import CqlConnection, CqlError
from benchmark.references import cassandra_keyvalue as kv

PIPELINE_CHUNK = 2048


class Generator:
    def __init__(self, plan: dict):
        p = plan["params"]
        cfg = plan["config"]
        self.seed = plan["seed"]
        self.worker, self.workers = plan["worker"], plan["workers"]
        self.n_readers = _share(int(p["readers"]), self.worker, self.workers)
        self.n_writers = _share(int(p["writers"]), self.worker, self.workers)
        self.timeout_s = float(p.get("timeout_s", 60))
        self.overwrite_every = int(p.get("overwrite_every", 2))
        self.replica_sample = int(p.get("replica_sample", 0))
        self.addr = tuple(plan["addr"]["cql"])
        self.table = cfg["schema"]["cql_table"]
        keys = int(cfg["scale"]["keys"])
        # Local key j is key number j * workers + worker.
        self.base = _share(keys, self.worker, self.workers)
        self.lock = threading.Lock()
        self.acked: list[int] = [0] * self.base   # last acknowledged version
        self.sent: list[int] = [0] * self.base    # newest version sent
        self.busy: set[int] = set()               # a write is in flight
        self.unknown: set[int] = set()            # a write failed: either
        self.extra: list[int] = []                # new locals, as acked
        self.written: set[int] = set()            # written in the window
        self.next_new = self.base
        self.reads: list[tuple] = []
        self.conns: list[CqlConnection] = []

    # -- connections --------------------------------------------------------
    def _conn(self):
        c = CqlConnection(*self.addr, timeout=self.timeout_s)
        ins = c.prepare(f"INSERT INTO {self.table} (k, v) VALUES (?, ?)")
        sel = c.prepare(f"SELECT v FROM {self.table} WHERE k = ?")
        return c, ins, sel

    def connect(self) -> None:
        self.conns = [self._conn()
                      for _ in range(self.n_readers + self.n_writers)]

    def close(self) -> None:
        for c, _i, _s in self.conns:
            c.close()

    def _n(self, j: int) -> int:
        return j * self.workers + self.worker

    # -- warm-up ------------------------------------------------------------
    def warmup(self) -> dict:
        """Each writer connection writes version 1 of one key and each
        reader connection reads it back: the cell's own two statements."""
        t0 = time.perf_counter()
        errors = []
        try:
            for t in range(self.n_writers):
                _c, ins, _s = self.conns[self.n_readers + t]
                _c.execute_prepared(ins, [kv.key_of(self._n(t)),
                                          kv.value(self.seed, self._n(t), 1)])
                self.acked[t] = self.sent[t] = 1
            for t in range(self.n_readers):
                c, _i, sel = self.conns[t]
                j = t % max(1, self.n_writers)
                rows = c.execute_prepared(sel, [kv.key_of(self._n(j))]).rows
                if not rows or rows[0][0] != kv.value(
                        self.seed, self._n(j), self.acked[j]):
                    errors.append(f"warm-up read of {kv.key_of(self._n(j))}")
        except (CqlError, OSError) as e:
            errors.append(repr(e))
        return {"seconds": time.perf_counter() - t0, "errors": errors}

    # -- the window ---------------------------------------------------------
    def run(self, seconds: float) -> dict:
        results: list[dict] = []
        start = time.perf_counter()

        def reader(t: int) -> None:
            rng = random.Random(f"r/{self.seed}/{self.worker}/{t}")
            conn, _ins, sel = self.conns[t]
            ok = failed = 0
            lat, done, errors = [], [], []
            while time.perf_counter() - start < seconds:
                r = rng.randrange(self.base + len(self.extra))
                j = r if r < self.base else self.extra[r - self.base]
                before = self.acked[j]
                t0 = time.perf_counter()
                try:
                    rows = conn.execute_prepared(
                        sel, [kv.key_of(self._n(j))]).rows
                except (CqlError, OSError) as e:
                    failed += 1
                    errors.append(f"read {kv.key_of(self._n(j))}: {e!r}")
                    conn, _ins, sel = self.conns[t] = self._reconnect(conn)
                    continue
                t1 = time.perf_counter()
                self.reads.append((j, before, self.sent[j],
                                   rows[0][0] if rows else None))
                ok += 1
                lat.append((t1 - t0) * 1e3)
                done.append(t1 - start)
            results.append({"ok": ok, "done": done,
                            "elapsed": time.perf_counter() - start,
                            "failed": failed, "kind": "read", "lat": lat,
                            "errors": errors})

        def writer(t: int) -> None:
            rng = random.Random(f"w/{self.seed}/{self.worker}/{t}")
            conn, ins, _sel = self.conns[self.n_readers + t]
            ok = failed = 0
            lat, done, errors = [], [], []
            i = t
            while time.perf_counter() - start < seconds:
                i += 1
                with self.lock:
                    if i % self.overwrite_every:
                        j = self.next_new
                        self.next_new += 1
                        self.acked.append(-1)
                        self.sent.append(0)
                        ver = 0
                    else:
                        while True:
                            r = rng.randrange(self.base + len(self.extra))
                            j = r if r < self.base \
                                else self.extra[r - self.base]
                            if j not in self.busy:
                                break
                        ver = self.acked[j] + 1
                        self.sent[j] = ver
                    self.busy.add(j)
                n = self._n(j)
                t0 = time.perf_counter()
                try:
                    conn.execute_prepared(
                        ins, [kv.key_of(n), kv.value(self.seed, n, ver)])
                except (CqlError, OSError) as e:
                    failed += 1
                    errors.append(f"write {kv.key_of(n)} v{ver}: {e!r}")
                    self.unknown.add(j)   # stays busy: never touched again
                    conn, ins, _sel = self.conns[self.n_readers + t] = \
                        self._reconnect(conn)
                    continue
                t1 = time.perf_counter()
                with self.lock:
                    self.acked[j] = ver
                    self.busy.discard(j)
                    self.written.add(j)
                    if ver == 0:
                        self.extra.append(j)
                ok += 1
                lat.append((t1 - t0) * 1e3)
                done.append(t1 - start)
            results.append({"ok": ok, "done": done,
                            "elapsed": time.perf_counter() - start,
                            "failed": failed, "kind": "write", "lat": lat,
                            "errors": errors})

        threads = [threading.Thread(target=reader, args=(t,), daemon=True)
                   for t in range(self.n_readers)]
        threads += [threading.Thread(target=writer, args=(t,), daemon=True)
                    for t in range(self.n_writers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        return {
            "streams": [[r["ok"], r["elapsed"]] for r in results],
            "latency_ms": {k: [x for r in results if r["kind"] == k
                               for x in r["lat"]]
                           for k in ("read", "write")},
            "done_s": {k: [x for r in results if r["kind"] == k
                           for x in r["done"]]
                       for k in ("read", "write")},
            "attempted": sum(r["ok"] + r["failed"] for r in results),
            "failed": sum(r["failed"] for r in results),
            "errors": [e for r in results for e in r["errors"]][:5],
        }

    def _reconnect(self, conn):
        conn.close()
        return self._conn()

    # -- after the window ---------------------------------------------------
    def after(self) -> dict:
        """Each read of the window against the reference, then every key
        written in the window read back over the wire."""
        unsound = sum(
            not kv.read_is_sound(self.seed, self._n(j), got, before, sent)
            for j, before, sent, got in self.reads)
        keys = sorted(self.written - self.unknown)
        c, _ins, sel = self.conns[0]
        mismatch = 0
        t0 = time.perf_counter()
        for at in range(0, len(keys), PIPELINE_CHUNK):
            chunk = keys[at:at + PIPELINE_CHUNK]
            got = c.execute_prepared_many(
                sel, [[kv.key_of(self._n(j))] for j in chunk])
            for j, g in zip(chunk, got):
                want = kv.value(self.seed, self._n(j), self.acked[j])
                if isinstance(g, Exception) or not g.rows \
                        or g.rows[0][0] != want:
                    mismatch += 1
        rng = random.Random(f"s/{self.seed}/{self.worker}")
        take = min(len(keys), _share(self.replica_sample, self.worker,
                                     self.workers))
        sample = [[self._n(j), self.acked[j]]
                  for j in rng.sample(keys, take)]
        return {"compared": {"reads_checked": [len(self.reads), None],
                             "reads_unsound": [unsound, 0],
                             "keys_read_back": [len(keys), None],
                             "readback_mismatch": [mismatch, 0],
                             "writes_of_unknown_outcome":
                                 [len(self.unknown), None]},
                "wrong": unsound + mismatch,
                "readback_seconds": time.perf_counter() - t0,
                "replica_sample": sample}


def _share(total: int, worker: int, workers: int) -> int:
    return total // workers + (1 if worker < total % workers else 0)
