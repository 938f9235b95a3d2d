#!/usr/bin/env python3
"""chip_smoke.py — the served path (wire -> tserver -> TPU engine) on one chip.

One process, the one that owns the chip. It loads TPC-H ``lineitem``
(yql/pgsql/tpch.py, 9 columns) into an in-process RF=3 cluster over
loopback sockets with fsync on, flushes, updates, compacts, and answers
count(*)/Q1/Q6 over the PG wire, paged scans and point SELECTs over the
CQL wire and mesh scans through the client API — every answer, in every
state of the table, compared exactly with one computed from the seeded
generator by ``Reference`` below, which shares no code with the engine.
Then it reads the counters that would show a fallback and fails on any.
See ISSUE 21 / PERF.md for what each printed figure is for.

Rows: 1.5M of SF1's 6,001,215 by default, printed under ``reduced``. A
read over several runs or a live memtable that is not a plain aggregate
is merged on the host row by row, the query set asks ten such passes
over the table, and at SF1 they alone outlast the 1200 s this script
has (PERF.md section 5). ``--rows 6001215`` runs all of it at SF1, given
an hour.

    python3 chip_smoke.py                     # on the chip: 1.5M rows
    python3 chip_smoke.py --rows 6001215      # SF1: about an hour
    python3 chip_smoke.py --rehearse-cpu      # CPU, tiny: proves nothing
                                              # about the chip

Exit code 0 and a last stdout line ``{"ok": true, "device": {...}}`` only
when every phase passed on a TPU (or in a rehearsal, which says so).
"""

from __future__ import annotations

import argparse
import itertools
import json
import os
import shutil
import statistics
import subprocess
import sys
import tempfile
import time

import numpy as np

ROOT = os.path.dirname(os.path.abspath(__file__))
SF1_ROWS = 6_001_215
DEFAULT_ROWS = 1_500_000       # the cut that fits 1200 s; see above
REHEARSAL_ROWS = 6_000
TABLE = "default.lineitem"     # PG name; CQL sees keyspace "default"
LOAD_WAVES = 5
BATCH_OPS = 16_384
PAGE = 100
MIN_PAGES = 20
POINT_SELECTS = 1_000
Q1_CUTOFF = 10471
Q6_LO, Q6_DISC, Q6_QTY = 9131, 6, 24
# A query over several runs or a live memtable is merged on the host, row
# by row: minutes at SF1. The budget of one statement, for the drivers'
# sockets and for the proxies' tablet RPCs alike (10 s by default).
STATEMENT_TIMEOUT_S = 900.0
ELECTION_TIMEOUT_S = 10.0      # see Smoke.start
T0 = time.perf_counter()
COMPILE = {"n": 0, "s": 0.0}   # backend compiles, by jax.monitoring
SCRATCH_DIRS: list[str] = []   # removed when the process ends, pass or fail


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - T0:7.1f}s] {msg}", flush=True)


def fail(msg: str) -> None:
    """A failed check: say what, exit non-zero, print no result line."""
    print(f"CHIP_SMOKE_FAILED: {msg}", file=sys.stderr, flush=True)
    sys.exit(1)


def check(cond: bool, msg: str) -> None:
    if not cond:
        fail(msg)


# -- the plain reference ------------------------------------------------------

class Reference:
    """The table as plain arrays indexed by row number, filled from the
    generator's dicts and mutated by the same logical operations the
    cluster is sent. Queries are direct transcriptions of the SQL."""

    COLS = ("l_quantity", "l_extendedprice", "l_discount", "l_tax",
            "l_returnflag", "l_linestatus", "l_shipdate")

    def __init__(self, n: int):
        self.n = n
        self.alive = np.zeros(n, bool)
        self.col = {c: np.zeros(n, np.int64) for c in self.COLS}

    @staticmethod
    def key_of(i: int) -> dict:
        return {"l_orderkey": i // 4 + 1, "l_linenumber": i % 4 + 1}

    def put(self, first: int, rows: list[dict]) -> None:
        """Rows ``first``.. as the generator made them (one array fill per
        column: a per-row store would cost more than the load itself)."""
        span = slice(first, first + len(rows))
        self.alive[span] = True
        for c in self.COLS:
            text = isinstance(rows[0][c], str)
            self.col[c][span] = np.fromiter(
                ((ord(r[c]) for r in rows) if text else
                 (r[c] for r in rows)), np.int64, len(rows))

    def update(self, i: int, values: dict) -> None:
        # An UPDATE of a missing row creates no row here: the smoke only
        # updates rows that are alive.
        for c, v in values.items():
            self.col[c][i] = v

    def delete(self, i: int) -> None:
        self.alive[i] = False

    def count(self) -> int:
        return int(self.alive.sum())

    def row(self, i: int):
        """The row as SELECT * returns it, or None when deleted."""
        if not self.alive[i]:
            return None
        k = self.key_of(i)
        c = self.col
        return (k["l_orderkey"], k["l_linenumber"], int(c["l_quantity"][i]),
                int(c["l_extendedprice"][i]), int(c["l_discount"][i]),
                int(c["l_tax"][i]), chr(c["l_returnflag"][i]),
                chr(c["l_linestatus"][i]), int(c["l_shipdate"][i]))

    def q1(self) -> list[tuple]:
        c = self.col
        m = self.alive & (c["l_shipdate"] <= Q1_CUTOFF)
        out = []
        flags, stats = c["l_returnflag"][m], c["l_linestatus"][m]
        qty, price = c["l_quantity"][m], c["l_extendedprice"][m]
        disc_price = price * (100 - c["l_discount"][m])
        charge = disc_price * (100 + c["l_tax"][m])
        for f in np.unique(flags):
            for s in np.unique(stats[flags == f]):
                g = (flags == f) & (stats == s)
                n = int(g.sum())
                sq, sp = int(qty[g].sum()), int(price[g].sum())
                out.append((chr(f), chr(s), sq, sp,
                            int(disc_price[g].sum()), int(charge[g].sum()),
                            sq / n, sp / n, n))
        return sorted(out)

    def q6(self):
        c = self.col
        m = (self.alive & (c["l_shipdate"] >= Q6_LO)
             & (c["l_shipdate"] < Q6_LO + 365)
             & (c["l_discount"] >= Q6_DISC - 1)
             & (c["l_discount"] <= Q6_DISC + 1)
             & (c["l_quantity"] < Q6_QTY))
        return (int((c["l_extendedprice"][m] * c["l_discount"][m]).sum())
                if m.any() else None)

    def shipdate_band(self, lo: int, hi: int) -> list[tuple]:
        c = self.col
        m = self.alive & (c["l_shipdate"] >= lo) & (c["l_shipdate"] < hi)
        return sorted(self.row(int(i)) for i in np.nonzero(m)[0])

    def band_aggregates(self, lo: int):
        c = self.col
        m = self.alive & (c["l_shipdate"] >= lo)
        return (int(m.sum()), int(c["l_quantity"][m].sum()),
                int(c["l_shipdate"][m].min()),
                int(c["l_extendedprice"][m].max()))


# -- the deployment -----------------------------------------------------------

class Smoke:
    def __init__(self, args, jax):
        self.args = args
        self.jax = jax
        self.rows = args.rows
        self.phases: dict[str, float] = {}
        self.findings: list[str] = []
        self.mesh_first_s: dict[str, float] = {}
        # The route is the node's own chip count (client/mesh_route.py):
        # only a host with several chips sends mesh requests.
        self.multi_chip = len(jax.local_devices()) > 1
        self.ref = Reference(self.rows)
        self.rng = np.random.default_rng(args.seed)
        # Keys the point SELECTs must cover, by what happened to them.
        self.touched: dict[str, list[int]] = {}
        self.point_stmt = None

    def timed(self, name: str, t0: float) -> float:
        dt = time.perf_counter() - t0
        self.phases[name] = round(self.phases.get(name, 0.0) + dt, 3)
        log(f"phase {name}: {dt:.1f}s (backend compiles so far: "
            f"{COMPILE['n']} in {COMPILE['s']:.1f}s)")
        return dt

    # -- bring-up -------------------------------------------------------------
    def start(self) -> None:
        from yugabyte_db_tpu.consensus.raft import RaftOptions
        from yugabyte_db_tpu.drivers.minicql import CqlConnection
        from yugabyte_db_tpu.drivers.minipg import PgConnection
        from yugabyte_db_tpu.integration.mini_cluster import MiniCluster
        from yugabyte_db_tpu.models.datatypes import DataType
        from yugabyte_db_tpu.tools.admin_client import AdminClient
        from yugabyte_db_tpu.yql.pgsql import tpch

        t0 = time.perf_counter()
        self.data_root = tempfile.mkdtemp(prefix="chip_smoke_")
        SCRATCH_DIRS.append(self.data_root)
        log(f"data dir {self.data_root} "
            f"(fsync median {self.fsync_ms():.3f} ms over 20)")
        # The daemon's own Raft defaults (MiniCluster's are test-fast), but
        # for the tservers' failure detection: three replicas share this
        # interpreter, a flush or compaction stalls all of them for longer
        # than the 0.5 s default, and every such stall would move a leader
        # onto a replica that holds the whole table in its memtable. The
        # lease (0.5 s) and what it guarantees stay as they are.
        self.mc = MiniCluster(self.data_root, num_tservers=3,
                              transport="socket", fsync=True,
                              raft_opts=RaftOptions())
        for uuid in self.mc.master_uuids:
            self.mc.start_master(uuid)
        self.mc.raft_opts = RaftOptions(election_timeout_s=ELECTION_TIMEOUT_S)
        for uuid in self.mc.tserver_uuids:
            self.mc.start_tserver(uuid)
        self.mc.wait_tservers_registered()
        self.pg_server, pg_addr = self.mc.start_pg_server(
            engine="tpu", rpc_timeout_s=STATEMENT_TIMEOUT_S)
        self.cql_server, cql_addr = self.mc.start_cql_server(
            engine="tpu", rpc_timeout_s=STATEMENT_TIMEOUT_S)
        self.pg = PgConnection(*pg_addr, timeout=STATEMENT_TIMEOUT_S)
        self.cql = CqlConnection(*cql_addr, timeout=STATEMENT_TIMEOUT_S)
        self.admin = AdminClient(self.mc.transport, self.mc.master_uuids)

        # Enough tablets for a mesh group, few enough that one tablet's
        # compaction union can exceed HOST_GC_MASK_MAX (see compact()).
        from yugabyte_db_tpu.storage import tpu_engine

        versions = int(self.rows * 1.06)
        self.num_tablets = max(2, min(
            3, versions // (tpu_engine.HOST_GC_MASK_MAX + 1)))
        sql_type = {DataType.INT64: "BIGINT", DataType.INT32: "INT",
                    DataType.INT8: "TINYINT", DataType.STRING: "TEXT"}
        cols = ", ".join(f"{c.name} {sql_type[c.dtype]}"
                         for c in tpch.LINEITEM_COLUMNS)
        self.pg.execute(
            f'CREATE TABLE "{TABLE}" ({cols}, '
            f"PRIMARY KEY ((l_orderkey), l_linenumber)) "
            f"SPLIT INTO {self.num_tablets} TABLETS")
        self.client = self.mc.client("chip-smoke")
        self.table = self.client.open_table(TABLE)
        check(self.table.engine == "tpu", "table engine is not tpu")
        self.place_leaders()
        self.timed("start", t0)

    def fsync_ms(self) -> float:
        path = os.path.join(self.data_root, "fsync_probe")
        samples = []
        with open(path, "wb") as f:
            for _ in range(20):
                f.write(b"x" * 4096)
                f.flush()
                t0 = time.perf_counter()
                os.fsync(f.fileno())
                samples.append((time.perf_counter() - t0) * 1000)
        os.unlink(path)
        return statistics.median(samples)

    def tablets(self):
        locs = self.client.meta_cache.locations(TABLE, refresh=True)
        return sorted(locs.tablets, key=lambda t: t.partition_start)

    def place_leaders(self) -> None:
        """The mesh serves CONSECUTIVE tablets one tserver leads. Elections
        land where they land, so move leaders with the admin RPC an
        operator has (yb_admin leader_stepdown): the first two tablets on
        ts-0, the rest on ts-1."""
        want = {t.tablet_id: ("ts-0" if i < 2 else "ts-1")
                for i, t in enumerate(self.tablets())}
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            wrong = [t for t in self.tablets()
                     if t.leader != want[t.tablet_id]]
            if not wrong:
                self.leader_map = want
                log(f"leaders placed: {sorted(want.values())}")
                return
            for t in wrong:  # (an immediate election on the target)
                self.admin.leader_stepdown(t.tablet_id, want[t.tablet_id])
            time.sleep(1.0)
        fail("leaders did not settle where placed")

    def leaders_now(self) -> dict:
        return {t.tablet_id: t.leader for t in self.tablets()}

    # -- writes ---------------------------------------------------------------
    def session(self):
        from yugabyte_db_tpu.client.session import YBSession

        return YBSession(self.client)

    def flush_ops(self, sess) -> int:
        n = sess.pending_ops
        acked = sess.flush(timeout_s=120.0)
        check(acked == n, f"session acked {acked} of {n} ops")
        return acked

    def insert_rows(self, gen, first: int, count: int) -> None:
        sess = self.session()
        for at in range(first, first + count, BATCH_OPS):
            batch = list(itertools.islice(
                gen, min(BATCH_OPS, first + count - at)))
            self.ref.put(at, batch)
            for row in batch:
                sess.insert(self.table, row)
            self.flush_ops(sess)

    def admin_flush(self) -> None:
        from yugabyte_db_tpu.utils import metrics

        d0, h0 = (metrics.flush_path_count("device"),
                  metrics.flush_path_count("host"))
        n = self.admin.flush_table(TABLE)
        log(f"  flush_table: {n} leaders flushed, routes "
            f"device +{metrics.flush_path_count('device') - d0} "
            f"host +{metrics.flush_path_count('host') - h0}")

    def load(self, gen) -> None:
        """(a) five load waves, each followed by yb_admin flush."""
        self.tail = max(PAGE, min(20_000, self.rows // 50))
        bulk = self.rows - self.tail
        t_load = 0.0
        first = 0
        for w in range(LOAD_WAVES):
            count = bulk // LOAD_WAVES + (bulk % LOAD_WAVES
                                          if w == LOAD_WAVES - 1 else 0)
            t0 = time.perf_counter()
            self.insert_rows(gen, first, count)
            t_load += self.timed("load", t0)
            first += count
            t0 = time.perf_counter()
            self.admin_flush()
            self.timed("flush", t0)
        self.bulk = bulk
        self.load_rows_per_s = bulk / t_load
        log(f"loaded {bulk} rows, {self.load_rows_per_s:.0f} rows/s "
            "(generate + client batch + RF=3 replicate + fsync)")

    def update_wave(self) -> None:
        """(b) ~5% of rows updated (some twice), some deleted, some of
        those re-inserted; flushed. Runs are now several, multi-version."""
        t0 = time.perf_counter()
        rng, ref = self.rng, self.ref
        n_upd = max(50, self.bulk // 20)
        n_del = max(20, self.bulk // 200)
        picked = rng.choice(self.bulk, n_upd + n_del, replace=False)
        upd, dele = picked[:n_upd], picked[n_upd:]
        again = upd[: max(10, n_upd // 10)]
        reins = dele[: max(10, n_del // 10)]

        def updates(idx, cols):
            sess = self.session()
            for i in idx.tolist():
                vals = {"l_quantity": int(rng.integers(1, 51)),
                        "l_discount": int(rng.integers(0, 11)),
                        "l_shipdate": int(rng.integers(8766, 10957))}
                vals = {c: vals[c] for c in cols}
                ref.update(i, vals)
                sess.update(self.table, ref.key_of(i), vals)
                if sess.pending_ops >= BATCH_OPS:
                    self.flush_ops(sess)
            self.flush_ops(sess)

        updates(upd, ("l_quantity", "l_discount", "l_shipdate"))
        updates(again, ("l_quantity",))
        sess = self.session()
        for i in dele.tolist():
            ref.delete(i)
            sess.delete(self.table, ref.key_of(i))
        self.flush_ops(sess)
        sess = self.session()
        from yugabyte_db_tpu.yql.pgsql import tpch

        fresh = tpch.generate_lineitem(len(reins), seed=self.args.seed + 1)
        for i in reins.tolist():
            row = dict(next(fresh), **ref.key_of(i))
            ref.put(i, [row])
            sess.insert(self.table, row)
        self.flush_ops(sess)
        self.touched.update(updated=upd.tolist(), updated_twice=again.tolist(),
                            deleted=dele[len(reins):].tolist(),
                            reinserted=reins.tolist())
        self.timed("update_wave", t0)
        t0 = time.perf_counter()
        self.admin_flush()
        self.timed("flush", t0)

    def compact(self) -> None:
        """(c) yb_admin compact: one run per tablet."""
        from yugabyte_db_tpu.storage import tpu_engine
        from yugabyte_db_tpu.utils import metrics

        unions = [sum(t.crun.num_versions for t in p.tablet.engine.runs)
                  for _u, p in self.leader_peers()]
        # (the mask is also the device's where every run is resident)
        resident = [all(t.peek_device() is not None
                        for t in p.tablet.engine.runs)
                    for _u, p in self.leader_peers()]
        c0 = metrics.jit_compiles("resident_gc_mask")
        t0 = time.perf_counter()
        self.admin.compact_table(TABLE)
        self.timed("compact", t0)
        device_route = metrics.jit_compiles("resident_gc_mask") > c0
        big = [u for u in unions if u > tpu_engine.HOST_GC_MASK_MAX]
        log(f"compaction unions {unions}; HOST_GC_MASK_MAX "
            f"{tpu_engine.HOST_GC_MASK_MAX}: {len(big)} above it, every run "
            f"resident {resident}, retention mask route "
            f"{'device' if device_route else 'host'}")
        check((bool(big) or any(resident)) == device_route,
              "compaction route does not match the union sizes")
        if not big:
            self.findings.append(
                "no compaction union exceeded HOST_GC_MASK_MAX at this "
                "row count: the device retention mask ran only in the "
                "direct-compile phase")
        self.print_replicas("after compaction")
        for u, p in self.leader_peers():
            check(len(p.tablet.engine.runs) == 1,
                  f"compaction left {len(p.tablet.engine.runs)} runs on "
                  f"{u}/{p.tablet_id[:8]} (leaders now {self.leaders_now()},"
                  f" placed {self.leader_map})")

    def tail_wave(self, gen) -> None:
        """(d) a last small wave left unflushed — the overlay path."""
        t0 = time.perf_counter()
        self.insert_rows(gen, self.bulk, self.tail)
        rng, ref = self.rng, self.ref
        alive = np.nonzero(ref.alive[:self.bulk])[0]
        picked = rng.choice(alive, min(400, len(alive) // 4), replace=False)
        upd, dele = picked[: len(picked) // 2], picked[len(picked) // 2:]
        sess = self.session()
        for i in upd.tolist():
            vals = {"l_tax": int(rng.integers(0, 9)),
                    "l_shipdate": int(rng.integers(8766, 10957))}
            ref.update(i, vals)
            sess.update(self.table, ref.key_of(i), vals)
        for i in dele.tolist():
            ref.delete(i)
            sess.delete(self.table, ref.key_of(i))
        self.flush_ops(sess)
        self.touched.update(
            tail_inserted=list(range(self.bulk, self.rows)),
            tail_updated=upd.tolist(), tail_deleted=dele.tolist())
        self.timed("tail_wave", t0)

    # -- reads ----------------------------------------------------------------
    def leader_peers(self):
        out = []
        for uuid, ts in sorted(self.mc.tservers.items()):
            for p in ts.tablet_manager.peers():
                if p.is_leader():
                    out.append((uuid, p))
        return out

    def print_replicas(self, when: str) -> None:
        log(f"replicas {when}:")
        for uuid, ts in sorted(self.mc.tservers.items()):
            for p in sorted(ts.tablet_manager.peers(),
                            key=lambda p: p.tablet_id):
                st = p.tablet.engine.stats()
                log(f"  {uuid} {p.tablet_id[-5:]} "
                    f"{'leader  ' if p.is_leader() else 'follower'} "
                    f"runs={st['num_runs']} run_versions={st['run_versions']}"
                    f" memtable_versions={st['memtable_versions']}"
                    f" device_bytes={st['device_bytes']}")

    def checkpoint(self, when: str, one_run: bool) -> None:
        """The query set, each answer exact against the reference.

        ``one_run``: every tablet is a single run with an empty memtable,
        so every query is a device program and, on a host with several
        chips, PG's Q1 and Q6 and the session's scans must ride the mesh
        (a one-chip host keeps one request a tablet: the route is the
        node's own chip count). Otherwise reads are multi-source, and whatever the
        engine cannot keep on the device — any row scan, any grouped or
        expression aggregate, and plain aggregates when no overlay
        applies — is merged on the host row by row inside one ts.scan
        per tablet: a pass over the table each."""
        from yugabyte_db_tpu.yql.pgsql import tpch

        log(f"checkpoint {when}")
        ref = self.ref
        quoted = f'"{TABLE}"'
        # On a host with several chips the PG frontend sends a leader's
        # tablets as ONE ts.multi_agg_scan (client/mesh_route.py): with
        # one run a tablet, count(*), Q1 and Q6 must each be a mesh
        # program.
        on_mesh = one_run and self.multi_chip
        t0 = time.perf_counter()
        before = self.mesh_counters()
        got = self.pg.execute(f"SELECT count(*) FROM {quoted}").rows
        check(got == [(ref.count(),)],
              f"{when}: count(*) {got} != {ref.count()}")
        if on_mesh:
            self.check_rode_mesh(when, "PG count(*)", before, "served", 1)
        self.timed(f"{when}.count", t0)
        t0 = time.perf_counter()
        before = self.mesh_counters()
        got = self.pg.execute(tpch.q1_sql(Q1_CUTOFF, table=quoted)).rows
        want = ref.q1()
        check([tuple(r) for r in got] == want,
              f"{when}: Q1 differs\n got {got}\nwant {want}")
        if on_mesh:
            self.mesh_first_s.setdefault(
                "pg_q1", round(time.perf_counter() - t0, 2))
            self.check_rode_mesh(when, "PG Q1", before, "served", 1)
        self.timed(f"{when}.q1", t0)
        t0 = time.perf_counter()
        before = self.mesh_counters()
        got = self.pg.execute(
            tpch.q6_sql(Q6_LO, Q6_DISC, Q6_QTY, table=quoted)).rows
        check(got == [(ref.q6(),)], f"{when}: Q6 {got} != {ref.q6()}")
        if on_mesh:
            self.check_rode_mesh(when, "PG Q6", before, "served", 1)
        self.timed(f"{when}.q6", t0)

        # A shipdate band wide enough for MIN_PAGES+ pages of PAGE rows:
        # every row of the band, from every tablet, exactly once.
        lo = 9400
        width = max(1, -(-(MIN_PAGES + 5) * PAGE * 2191 // self.rows))
        want_rows = ref.shipdate_band(lo, lo + width)
        check(len(want_rows) >= MIN_PAGES * PAGE,
              f"band too narrow: {len(want_rows)} rows")
        t0 = time.perf_counter()
        self.cql_pages(when, lo, lo + width, want_rows)
        self.timed(f"{when}.cql_pages", t0)
        t0 = time.perf_counter()
        self.session_pages(when, lo, lo + width, want_rows, on_mesh)
        self.session_aggregate(when, lo, on_mesh)
        self.timed(f"{when}.session_scans", t0)
        t0 = time.perf_counter()
        self.point_selects(when)
        self.timed(f"{when}.point_selects", t0)

    def cql_pages(self, when, lo, hi, want_rows) -> None:
        q = (f"SELECT * FROM lineitem WHERE l_shipdate >= {lo} "
             f"AND l_shipdate < {hi}")
        res = self.cql.execute(q, page_size=PAGE)
        rows, pages = list(res.rows), 1
        while res.has_more_pages:
            res = self.cql.execute(q, page_size=PAGE,
                                   paging_state=res.paging_state)
            rows.extend(res.rows)
            pages += 1
        check(pages >= MIN_PAGES, f"{when}: only {pages} CQL pages")
        check(sorted(tuple(r) for r in rows) == want_rows,
              f"{when}: CQL paged band differs "
              f"({len(rows)} rows, want {len(want_rows)})")
        log(f"  cql: {pages} pages of {PAGE}, {len(rows)} rows exact")

    def band_preds(self, lo, hi=None):
        from yugabyte_db_tpu.storage.scan_spec import Predicate

        return [Predicate("l_shipdate", ">=", lo)] + (
            [Predicate("l_shipdate", "<", hi)] if hi is not None else [])

    def band_aggs(self):
        from yugabyte_db_tpu.storage.scan_spec import AggSpec

        return [AggSpec("count", None), AggSpec("sum", "l_quantity"),
                AggSpec("min", "l_shipdate"),
                AggSpec("max", "l_extendedprice")]

    def mesh_counters(self) -> dict:
        """The tservers' mesh counters, summed."""
        names = ("served", "served_rows", "fallbacks", "chip_losses")
        return {n: sum(getattr(ts.mesh_scan, n)
                       for ts in self.mc.tservers.values()) for n in names}

    def check_rode_mesh(self, when, what, before, counter, n) -> None:
        """``n`` session requests since ``before`` were each answered by
        the mesh (one program, or more where a page resumes): none
        swallowed, refused or served per tablet."""
        from yugabyte_db_tpu.utils import metrics

        after = self.mesh_counters()
        grew = {k: after[k] - before[k] for k in after}
        check(grew[counter] >= n and not grew["fallbacks"]
              and not grew["chip_losses"],
              f"{when}: {n} {what} moved the mesh counters by {grew}")
        swallowed = {k: v for k, v in metrics.swallowed_errors().items()
                     if k.startswith(("session.multi_", "mesh_route.")) and v}
        check(not swallowed, f"{when}: the session swallowed {swallowed}")

    def session_pages(self, when, lo, hi, want_rows, one_run) -> None:
        """LIMIT pages through the client API (what tools/load_test.py
        drives): the path that reaches ts.multi_row_scan and the mesh.
        With one run per tablet every page must ride it, the first one
        included — it pays for stacking the tablets' planes on the host,
        uploading the stack and compiling the mesh program."""
        from yugabyte_db_tpu.storage.scan_spec import ScanSpec

        sess = self.session()
        preds = self.band_preds(lo, hi)
        before = self.mesh_counters()
        rows, pages, lower = [], 0, b""
        while True:
            t0 = time.perf_counter()
            res = sess.scan(self.table, ScanSpec(
                lower=lower, limit=PAGE, predicates=preds),
                timeout_s=STATEMENT_TIMEOUT_S)
            if one_run and not pages:
                self.mesh_first_s["row_page"] = round(
                    time.perf_counter() - t0, 2)
            pages += 1
            rows.extend(res.rows)
            if len(res.rows) < PAGE:
                break
            last = res.rows[-1]
            lower = self.table.encode_key(
                {"l_orderkey": last[0], "l_linenumber": last[1]}) + b"\x00"
        check(pages >= MIN_PAGES, f"{when}: only {pages} session pages")
        check(sorted(rows) == want_rows and len(set(rows)) == len(rows),
              f"{when}: session paged band differs "
              f"({len(rows)} rows, want {len(want_rows)})")
        log(f"  session: {pages} LIMIT-{PAGE} pages, {len(rows)} rows exact")
        if one_run:
            # A page that ends the leading group's tablets short of the
            # limit is completed from the next group in the same scan.
            self.check_rode_mesh(when, "session pages", before,
                                 "served_rows", pages)
            log(f"  session: all {pages} pages rode the mesh, the first "
                f"in {self.mesh_first_s['row_page']}s")

    def session_aggregate(self, when, lo, one_run) -> None:
        from yugabyte_db_tpu.storage.scan_spec import ScanSpec

        before = self.mesh_counters()
        t0 = time.perf_counter()
        res = self.session().scan(self.table, ScanSpec(
            predicates=self.band_preds(lo), aggregates=self.band_aggs()),
            timeout_s=STATEMENT_TIMEOUT_S)
        want = self.ref.band_aggregates(lo)
        check(res.rows == [want],
              f"{when}: session aggregate {res.rows} != {want}")
        if one_run:
            self.mesh_first_s.setdefault(
                "aggregate", round(time.perf_counter() - t0, 2))
            self.check_rode_mesh(when, "session aggregate", before,
                                 "served", 1)

    def point_selects(self, when) -> None:
        rng, ref = self.rng, self.ref
        idx: list[int] = []
        per = max(1, POINT_SELECTS // (2 * max(1, len(self.touched))))
        for name, pool in sorted(self.touched.items()):
            take = min(per, len(pool))
            idx.extend(rng.choice(pool, take, replace=False).tolist())
        loaded = self.bulk + (self.tail if "tail_inserted" in self.touched
                              else 0)
        idx.extend(rng.integers(0, loaded,
                                POINT_SELECTS - len(idx)).tolist())
        keys = [ref.key_of(i) for i in idx]
        keys.append({"l_orderkey": self.rows + 10, "l_linenumber": 1})
        want = [ref.row(i) for i in idx] + [None]
        if self.point_stmt is None:
            self.point_stmt = self.cql.prepare(
                "SELECT * FROM lineitem WHERE l_orderkey = ? "
                "AND l_linenumber = ?")
        got = self.cql.execute_prepared_many(
            self.point_stmt,
            [[k["l_orderkey"], k["l_linenumber"]] for k in keys])
        bad = 0
        for k, g, w in zip(keys, got, want):
            check(not isinstance(g, Exception), f"{when}: point {k}: {g}")
            rows = [tuple(r) for r in g.rows]
            if rows != ([w] if w is not None else []):
                bad += 1
                log(f"  point {k}: got {rows} want {w}")
        check(bad == 0, f"{when}: {bad} point SELECTs differ")
        log(f"  cql: {len(keys)} prepared point SELECTs exact "
            f"({sum(w is None for w in want)} deleted/absent)")

    # -- the chip's own numbers ----------------------------------------------
    def fetch_cycle_ms(self) -> float:
        """Median of 50 small synchronous device_get cycles — the number
        the pipeline depths, HOST_GC_MASK_MAX and storage/host_page.py
        were tuned against at ~100 ms."""
        jax = self.jax
        import jax.numpy as jnp

        bump = jax.jit(lambda x: x + 1)
        x = jnp.zeros((8, 128), jnp.int32)
        jax.device_get(bump(x))
        samples = []
        for _ in range(50):
            t0 = time.perf_counter()
            x = bump(x)
            jax.device_get(x)
            samples.append((time.perf_counter() - t0) * 1000)
        return statistics.median(samples)

    def memory_report(self) -> dict:
        from yugabyte_db_tpu.ops.device_run import device_label
        from yugabyte_db_tpu.storage.residency import hbm_cache

        by_dev = hbm_cache().stats()["by_device"]
        out = {}
        for d in self.jax.devices():
            label = device_label(d)
            stats = d.memory_stats() or {}
            out[label] = {
                "accounted_bytes": by_dev.get(label, {}).get(
                    "resident_bytes", 0),
                "bytes_in_use": stats.get("bytes_in_use"),
                "peak_bytes_in_use": stats.get("peak_bytes_in_use")}
        for label in by_dev:
            check(label in out, f"residency on unknown device {label}")
        return out

    # -- the verdict ----------------------------------------------------------
    def verdict(self, compiles_served: dict) -> list[str]:
        """Everything that would let the run pass without the chip, read
        at the end; returns what it found (empty = none of it)."""
        from yugabyte_db_tpu.storage.residency import hbm_cache
        from yugabyte_db_tpu.utils import metrics

        bad: list[str] = []
        platform = "cpu" if self.args.rehearse_cpu else "tpu"
        if self.jax.default_backend() != platform:
            bad.append(f"backend is {self.jax.default_backend()}")
        for label in hbm_cache().stats()["by_device"]:
            if not label.startswith(platform + ":"):
                bad.append(f"planes resident on {label}")
        for uuid, ts in sorted(self.mc.tservers.items()):
            for p in ts.tablet_manager.peers():
                b = p.tablet.engine.breaker.stats()
                if b["trips"] or b["last_error"] is not None:
                    bad.append(f"breaker {uuid}/{p.tablet_id[:8]}: {b}")
        swallowed = metrics.swallowed_errors()
        log(f"swallowed errors by site: {swallowed}")
        for site, n in swallowed.items():
            if n and (site in ("session.multi_row_scan",
                               "mesh_route.multi_agg_scan")
                      or site.startswith("tpu_engine.")):
                bad.append(f"{n} swallowed errors at {site}")
        if not metrics.flush_path_count("device"):
            bad.append("no flush took the device route")
        mesh = {u: ts.mesh_scan for u, ts in sorted(self.mc.tservers.items())}
        for u, m in mesh.items():
            log(f"mesh {u}: served={m.served} served_rows={m.served_rows} "
                f"updated={m.updated} fallbacks={m.fallbacks} "
                f"chip_losses={m.chip_losses}")
            if m.chip_losses:
                bad.append(f"{m.chip_losses} mesh chip losses on {u}")
        if self.multi_chip and not any(m.served > 0 and m.served_rows > 0
                                       for m in mesh.values()):
            bad.append("no tserver served both an aggregate and a row "
                       "page on the mesh")
        if not self.multi_chip and any(m.served or m.served_rows
                                       for m in mesh.values()):
            bad.append("a one-chip host served a mesh request")
        for group in (("replay_flush",),
                      ("gather_batch", "scan_window", "dist_page"),
                      ("flat_aggregate", "lookback_aggregate", "batched_agg",
                       "dist_agg"),
                      ("grouped_aggregate", "batched_grouped",
                       "dist_grouped_aggregate")):
            if not any(compiles_served.get(e, 0) for e in group):
                bad.append(f"the served path compiled none of {group}")
        if self.leaders_now() != self.leader_map:
            self.findings.append(
                f"leaders moved during the run: {self.leaders_now()} "
                f"(placed {self.leader_map})")
        return bad

    def stop(self) -> None:
        self.cql.close()
        self.pg.close()
        self.cql_server.shutdown()
        self.pg_server.shutdown()
        self.mc.shutdown()


# -- every device program meets the compiler ----------------------------------

def compile_unreached(smoke: Smoke, served: dict) -> dict:
    """Entries the served path did not reach, compiled and run once
    directly at the engine's real block shape (R=2048) on a standalone
    engine, each result compared with the CPU oracle engine or with the
    route the served path did take. Returns {entry: how}."""
    import yugabyte_db_tpu.storage.tpu_engine as te
    from yugabyte_db_tpu.ops import compact as dcompact
    from yugabyte_db_tpu.parallel import ShardedTablets
    from yugabyte_db_tpu.storage import (AggSpec, Predicate, ScanSpec,
                                         make_engine)
    from yugabyte_db_tpu.utils import jitting, metrics
    from yugabyte_db_tpu.yql.pgsql import tpch

    R = 2048
    schema = tpch.lineitem_schema()
    n = 3_000 if smoke.args.rehearse_cpu else 150_000
    opts = {"rows_per_block": R}

    def engines(updates: bool):
        """A (tpu, cpu-oracle) pair holding the same rows; with
        ``updates`` a second wave makes the groups multi-version."""
        pair = [make_engine("tpu", schema, opts), make_engine("cpu", schema)]
        ht = 0
        for e in pair:
            ht = tpch.load_engine(e, schema, n, seed=smoke.args.seed)
        if updates:
            from yugabyte_db_tpu.models.partition import compute_hash_code
            from yugabyte_db_tpu.storage.row_version import RowVersion

            qty = {c.name: c.col_id for c in schema.columns}["l_quantity"]
            rows = []
            for j in range(0, n, 7):
                kv = Reference.key_of(j)
                key = schema.encode_primary_key(
                    kv, compute_hash_code(schema, kv))
                ht += 1
                rows.append(RowVersion(key, ht=ht, columns={qty: j % 50 + 1}))
            for e in pair:
                e.apply(rows)
                e.flush()
        return pair[0], pair[1], ht + 1

    agg = ScanSpec(aggregates=[AggSpec("count", None),
                               AggSpec("sum", "l_extendedprice"),
                               AggSpec("min", "l_shipdate")],
                   predicates=[Predicate("l_quantity", "<", 30)])

    def at(spec, ht, **kw):
        import dataclasses

        return dataclasses.replace(spec, read_ht=ht, **kw)

    how: dict[str, str] = {}
    missing = [e for e in jitting.declared_contracts()
               if not served.get(e)]
    log(f"direct-compile phase for: {missing}")

    flat_t, flat_c, flat_ht = engines(updates=False)
    mv_t, mv_c, mv_ht = engines(updates=True)
    mv_t.compact()
    mv_c.compact()
    check(mv_t.runs[0].crun.max_group_versions > 1, "no multi-version run")

    def forced_routes(eng, oracle, ht):
        """Every aggregate fold that supports this run's signature, forced
        in turn through the engine's own dispatch."""
        import jax
        from yugabyte_db_tpu.ops import flat_fold, lookback_fold, seg_fold

        spec = at(agg, ht)
        want = oracle.scan(spec).rows
        trun = eng.runs[0]
        exact = eng._split_predicates(spec)[0]
        prep = eng._device_agg_prep(trun, spec, exact)
        sig = prep[0]
        routes = [("flat", flat_fold.supports(sig)),
                  ("lookback", lookback_fold.supports(sig)),
                  ("seg", seg_fold.supports(sig)), ("full", True)]
        for route, ok in routes:
            if not ok:
                continue
            outs, fin = eng._dispatch_prepped(
                trun, spec, (sig, route) + tuple(prep[2:]))
            got = fin(jax.device_get(outs)).rows
            check(got == want, f"forced {route} fold {got} != {want}")
            how[f"{route}_aggregate"] = "forced route vs CPU oracle"

    forced_routes(flat_t, flat_c, flat_ht)
    forced_routes(mv_t, mv_c, mv_ht)

    # Batched (vmapped) flat + grouped programs: same signature, distinct
    # literals/read points in one scan_batch.
    specs = [at(agg, mv_ht, predicates=[Predicate("l_quantity", "<", q)])
             for q in (10, 20, 30)]
    check([r.rows for r in mv_t.scan_batch(specs)]
          == [mv_c.scan(s).rows for s in specs], "batched_agg differs")
    how["batched_agg"] = "3-spec scan_batch vs CPU oracle"
    q1s = [tpch.q1_spec(flat_ht, cut) for cut in (10471, 10000)]
    check([r.rows for r in flat_t.scan_batch(q1s)]
          == [flat_c.scan(s).rows for s in q1s], "batched_grouped differs")
    check(flat_t.scan(q1s[0]).rows == flat_c.scan(q1s[0]).rows,
          "grouped_aggregate differs")
    # (a multi-version run: the XLA prologue gathers each group's newest
    # planes, the grouped kernel is handed the same row vectors)
    q1_mv = tpch.q1_spec(mv_ht, 10471)
    check(mv_t.scan(q1_mv).rows == mv_c.scan(q1_mv).rows,
          "grouped_aggregate over a multi-version run differs")
    how["batched_grouped"] = how["grouped_aggregate"] = \
        "Q1 spec(s) vs CPU oracle: the grouped kernel, alone, under " \
        "vmap and behind a multi-version run"

    # Row paths: a multi-version LIMIT page (gather), then a second run
    # makes the scan multi-source (scan_window) and the aggregate an
    # overlay over a plain flush-seeded primary (scatter_invalid).
    page = ScanSpec(limit=PAGE, predicates=[Predicate("l_shipdate", ">=",
                                                      9400)])
    check(mv_t.scan(at(page, mv_ht)).rows == mv_c.scan(at(page, mv_ht)).rows,
          "gather page differs")
    how["gather_batch"] = "multi-version LIMIT page vs CPU oracle"
    # The mesh's three programs, where the served path did not take them
    # (a host with one chip sends one request a tablet): the node's own
    # mesh over a stack of the same run twice, against the oracle's
    # answer combined with itself.
    from yugabyte_db_tpu.parallel import (sharded_aggregate,
                                          sharded_grouped_aggregate,
                                          sharded_row_page)
    from yugabyte_db_tpu.storage.scan_spec import combine_grouped

    mesh = next(iter(smoke.mc.tservers.values())).mesh_scan._get_mesh()
    twice = ShardedTablets(schema, [mv_t.runs[0].crun] * 2, mesh)
    for entry, fn, spec in (
            ("dist_agg", sharded_aggregate, at(agg, mv_ht)),
            ("dist_grouped_aggregate",
             lambda st, sp: sharded_grouped_aggregate(st, sp, mv_t), q1_mv)):
        one = mv_c.scan(spec)
        check(fn(twice, spec).rows == combine_grouped(spec, [one, one]).rows,
              f"{entry} over the run stacked twice differs")
        how[entry] = "stack of one run twice vs CPU oracle combined"
    check(sharded_row_page(twice, at(page, mv_ht)).rows
          == mv_c.scan(at(page, mv_ht)).rows, "dist_page differs")
    how["dist_page"] = "first LIMIT page of a two-tablet stack vs CPU oracle"
    twice.close()
    extra = list(tpch.generate_lineitem(200, seed=smoke.args.seed + 2))
    from yugabyte_db_tpu.models.partition import compute_hash_code
    from yugabyte_db_tpu.storage.row_version import RowVersion

    cid = {c.name: c.col_id for c in schema.columns}
    keyn = {c.name for c in schema.key_columns}
    rvs = []
    for j, row in enumerate(extra):
        kv = {"l_orderkey": 10_000_000 + j, "l_linenumber": 1}
        rvs.append(RowVersion(
            schema.encode_primary_key(kv, compute_hash_code(schema, kv)),
            ht=max(flat_ht, mv_ht) + j, liveness=True,
            columns={cid[k]: v for k, v in row.items() if k not in keyn}))
    ht2 = max(flat_ht, mv_ht) + len(extra) + 1
    count = ScanSpec(aggregates=[AggSpec("count", None),
                                 AggSpec("sum", "l_quantity")])
    # (the compacted run re-uploaded encoded: its valid plane is bit-packed)
    for t, c in ((flat_t, flat_c), (mv_t, mv_c)):
        t.apply(rvs)
        c.apply(rvs)
        check(t.scan(at(count, ht2)).rows == c.scan(at(count, ht2)).rows,
              "overlay aggregate (memtable) differs")
    for e in (flat_t, flat_c):
        e.flush()
    check(flat_t.scan(at(count, ht2)).rows == flat_c.scan(at(count, ht2)).rows,
          "overlay aggregate (two runs) differs")
    check(flat_t.scan(at(page, ht2)).rows == flat_c.scan(at(page, ht2)).rows,
          "multi-source page differs")
    how["scan_window"] = "two-run LIMIT page vs CPU oracle"
    # What a multi-source grouped aggregate costs: Q1 over a key range of
    # the two-run engine is merged on the host (a host figure, not the
    # chip's).
    crun = flat_t.runs[0].crun
    span = min(20_000, crun.total_rows() - 1)
    q1 = at(tpch.q1_spec(ht2), ht2, lower=crun.key_at(0),
            upper=crun.key_at(span))
    t0 = time.perf_counter()
    got = flat_t.scan(q1)
    smoke.host_merge_us_per_row = round(
        (time.perf_counter() - t0) / max(1, got.rows_scanned) * 1e6, 1)
    check(got.rows == flat_c.scan(q1).rows, "host-merged Q1 differs")
    log(f"host-merged Q1: {smoke.host_merge_us_per_row} us/row over "
        f"{got.rows_scanned} rows")
    how["scatter_invalid"] = how["scatter_invalid_bits"] = \
        "overlay aggregates vs CPU oracle"

    # Compaction's device retention mask: the same union through both
    # routes (the module constant decides; it is put back).
    keep = te.HOST_GC_MASK_MAX
    te.HOST_GC_MASK_MAX = 0
    flat_t.compact()
    te.HOST_GC_MASK_MAX = keep
    flat_c.compact()
    check([(k, [(v.ht, v.tombstone, v.columns) for v in vs])
           for k, vs in flat_t.dump_entries()]
          == [(k, [(v.ht, v.tombstone, v.columns) for v in vs])
              for k, vs in flat_c.dump_entries()],
          "device-mask compaction differs from the CPU oracle's")
    how["resident_gc_mask"] = "two-run compaction vs CPU oracle"

    # gc_mask has no caller in the engine (resident_gc_mask and the host
    # twin replaced it): the union-shipping variant, on a synthetic union.
    if not served.get("gc_mask"):
        N = 1 << 16
        z = np.zeros(N, np.int32)
        pair = np.arange(N) % 2 == 0      # two versions per key, newer first
        s = {"new_group": pair, "ht_hi": z, "ht_lo": pair.astype(np.int32),
             "exp_hi": z, "exp_lo": z, "tomb": np.zeros(N, bool),
             "live": np.ones(N, bool), "set_": np.ones((2, N), bool)}
        cut = (0, np.iinfo(np.int32).max) * 2
        dev = np.asarray(dcompact.compiled_gc_mask(2, N)(s, cut))
        host = np.asarray(dcompact.gc_mask_host(2, s, cut))
        check((dev.astype(bool) == host.astype(bool)).all()
              and host.astype(bool).sum() == N // 2,
              "gc_mask differs from its host twin")
        how["gc_mask"] = "synthetic union vs host twin (no engine caller)"

    # stack_update: only un-encoded stacks update in place, and stacks
    # encode by default, so the served path rebuilds instead.
    run = mv_t.runs[0].crun
    st = ShardedTablets(schema, [run, run], mesh, encode=False)
    check(st.update_tablet(1, run), "stack_update refused a same-shape run")
    st.close()
    how["stack_update"] = "un-encoded 2-tablet stack, slot rewritten"

    for e in (flat_t, mv_t):
        b = e.breaker.stats()
        check(not b["trips"] and b["last_error"] is None,
              f"direct-compile engine breaker recorded {b}")
    for e in (flat_t, flat_c, mv_t, mv_c):
        e.close()
    after = metrics.jit_compiles()
    still = [e for e in jitting.declared_contracts() if not after.get(e)]
    check(not still, f"entries that never met the compiler: {still}")
    return {e: how.get(e, "reached while compiling another entry")
            for e in missing}


# -- main ---------------------------------------------------------------------

def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=21)
    ap.add_argument("--rows", type=int, default=None,
                    help=f"lineitem rows (default {DEFAULT_ROWS}; SF1 is "
                         f"{SF1_ROWS}); less than SF1 is printed under "
                         "'reduced'")
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny run on the CPU backend: checks the script, "
                         "proves nothing about the chip")
    args = ap.parse_args(argv)
    if args.rows is None:
        args.rows = REHEARSAL_ROWS if args.rehearse_cpu else DEFAULT_ROWS

    for part in ("yugabyte_db_tpu", "native"):
        check(os.path.isdir(os.path.join(ROOT, part)),
              f"chip_smoke.py drives the repository it stands in; there is "
              f"no {part}/ beside it")
    # The chip first: no accelerator, no result. JAX_PLATFORMS is never set
    # here — on the chip machine JAX's default is the TPU.
    import jax

    def on_duration(event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            COMPILE["n"] += 1
            COMPILE["s"] += secs

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if args.rehearse_cpu:
        log("REHEARSAL on the CPU backend at a tiny size: this run checks "
            "the script and proves NOTHING about the chip")
        check(device["platform"] == "cpu",
              "--rehearse-cpu needs JAX_PLATFORMS=cpu")
    else:
        check(device["platform"] == "tpu",
              f"JAX found no TPU (platform {device['platform']}); "
              "--rehearse-cpu is the only run that passes without one")
    log(f"device {json.dumps(device)} jax {jax.__version__}")

    # Step 0: the native modules, rebuilt from the tracked sources. A .so
    # that happens to lie on disk is not evidence.
    t0 = time.perf_counter()
    subprocess.run(["make", "-C", os.path.join(ROOT, "native"), "-B",
                    f"PY={sys.executable}"], check=True,
                   stdout=subprocess.DEVNULL)
    sys.path.insert(0, ROOT)
    from yugabyte_db_tpu import native

    for name in ("yb_codec", "yb_wp", "yb_rb"):
        check(getattr(native, name) is not None,
              f"native module {name} did not build or import")
    from yugabyte_db_tpu.utils import jitting, metrics

    cache_dir = jitting.enable_compile_cache()
    cache_entries = (len(os.listdir(cache_dir))
                     if cache_dir and os.path.isdir(cache_dir) else 0)
    log(f"compile cache {cache_dir}: {cache_entries} entries at start")

    smoke = Smoke(args, jax)
    smoke.timed("native_build", t0)
    # Besides the cut of scale, what is not as a daemon runs it, so the
    # next deployment taken from this one does not inherit it unnoticed.
    reduced = ([] if args.rows == SF1_ROWS else
               [f"rows {args.rows} of SF1's {SF1_ROWS}"]) + [
        f"tserver election timeout {ELECTION_TIMEOUT_S:g} s (daemon "
        "default 0.5 s): three replicas share one interpreter",
        f"PG/CQL proxies' tablet RPC budget {STATEMENT_TIMEOUT_S:g} s "
        "(default 10 s): host-merged reads take longer"]
    smoke.start()
    from yugabyte_db_tpu.yql.pgsql import tpch

    gen = tpch.generate_lineitem(args.rows, seed=args.seed)
    smoke.load(gen)
    smoke.update_wave()
    smoke.print_replicas("after the update wave (leaders flushed only)")
    smoke.checkpoint("after_update", one_run=False)
    smoke.compact()
    smoke.checkpoint("after_compact", one_run=True)
    smoke.tail_wave(gen)
    smoke.checkpoint("after_tail", one_run=False)
    smoke.print_replicas("at the end")

    served = metrics.jit_compiles()
    t0 = time.perf_counter()
    direct = compile_unreached(smoke, served)
    smoke.timed("direct_compile", t0)
    fetch_ms = smoke.fetch_cycle_ms()
    memory = smoke.memory_report()
    for entry, how in direct.items():
        smoke.findings.append(f"served path never compiled {entry}: {how}")
    problems = smoke.verdict(served)
    smoke.stop()

    report = {
        "jax": jax.__version__, "device": device,
        "rehearsal": args.rehearse_cpu, "seed": args.seed,
        "rows": args.rows, "reduced": reduced,
        "tablets": smoke.num_tablets,
        "load_rows_per_s": round(smoke.load_rows_per_s, 1),
        "phases_s": smoke.phases,
        "wall_s": round(time.perf_counter() - T0, 1),
        "compile_cache": {"dir": cache_dir,
                          "entries_at_start": cache_entries},
        "backend_compiles": {"count": COMPILE["n"],
                             "seconds": round(COMPILE["s"], 1)},
        "compiles_served_path": {e: served.get(e, 0)
                                 for e in jitting.declared_contracts()},
        "compiles_total": metrics.jit_compiles(),
        "flushes": {"device": metrics.flush_path_count("device"),
                    "host": metrics.flush_path_count("host")},
        "memory_by_device": memory,
        "device_get_cycle_ms_median_of_50": fetch_ms,
        "host_merge_q1_us_per_row": smoke.host_merge_us_per_row,
        "mesh_first_request_s": smoke.mesh_first_s,
        "findings": smoke.findings,
        "problems": problems,
    }
    print("CHIP_SMOKE_REPORT " + json.dumps(report), flush=True)
    if not args.rehearse_cpu:
        out_dir = os.path.join(ROOT, "chiprun_out")
        os.makedirs(out_dir, exist_ok=True)
        with open(os.path.join(out_dir, "chip_smoke_report.jsonl"), "a") as f:
            f.write(json.dumps(report) + "\n")
    if problems:
        fail("; ".join(problems))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    # Whatever happened, the process ends here and now: a failed phase
    # leaves server threads behind, and none of them may outlive it.
    try:
        code = main()
    except SystemExit as e:
        code = e.code if isinstance(e.code, int) else 1
    except BaseException:
        import traceback

        traceback.print_exc()
        code = 1
    for d in SCRATCH_DIRS:
        shutil.rmtree(d, ignore_errors=True)
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
