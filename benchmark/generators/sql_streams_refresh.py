"""``sql_streams`` after TPC-H's refresh function RF2: the first worker's
solo warm-up deletes the reference's delete keys over the PG wire before
it asks its first query, so that every query of the warm-up and of the
window reads the table a refresh stream has left (RF1's rows arrive
before, through ``run.py``'s burst phase; the harness has no other way
in for a delete than a generator's warm-up).

One ``DELETE FROM {table} WHERE l_orderkey = {k}`` an order: the first
``SOLO_KEYS`` one after the other on one connection (a tablet's first
range scan uploads its run and builds its bloom filter, most of the
proxy's RPC budget by itself at 750,000 rows; six cold at once outlast
it), the rest over ``refresh_connections`` connections (traffic
parameter). Each statement's ``DELETE n`` is held against the reference's
line count. A statement that raises (a time-out) is asked again on a new
connection, ``sql_streams.SOLO_ATTEMPTS`` times in all, as the solo
warm-up asks its queries again; asked again it may find fewer lines (the
attempt before may have deleted some before it failed), never more. A
statement that still fails, or a count that differs, is an error of the
warm-up, and the run is refused. Reports ``refresh_seconds``,
``deleted_rows`` and what was asked again (``retried``).
"""

from __future__ import annotations

import threading
import time

from benchmark.clients.minipg import PgConnection
from benchmark.generators import sql_streams
from benchmark.references.tpch_lineitem_refresh import delete_keys

SOLO_KEYS = 4       # orders deleted one by one before the rest


class Generator(sql_streams.Generator):
    def __init__(self, plan: dict):
        super().__init__(plan)
        self.refresh_connections = int(
            plan["params"].get("refresh_connections", 1))
        self.refresh_keys = delete_keys(plan["config"], plan["seed"])

    def _delete(self, conn, key: int, lines: int, errors: list,
                retried: list):
        """One order's DELETE, asked again where it raises. -> the
        connection to go on with, or None after an error."""
        sql = f"DELETE FROM {self.table} WHERE l_orderkey = {key}"
        for attempt in range(1, sql_streams.SOLO_ATTEMPTS + 1):
            try:
                tag = conn.execute(sql).command_tag
            except Exception as e:  # noqa: BLE001 — asked again, reported
                note = f"order {key}, attempt {attempt}: {e!r}"
                try:
                    conn.close()
                except OSError:
                    pass
                if attempt == sql_streams.SOLO_ATTEMPTS:
                    errors.append(note)
                    return None
                retried.append(note)
                conn = PgConnection(*self.addr, timeout=self.timeout_s)
                continue
            verb, _, n = tag.partition(" ")
            if verb != "DELETE" or not n.isdigit() or not (
                    int(n) == lines or attempt > 1 and int(n) < lines):
                errors.append(f"order {key}: {tag!r} for DELETE {lines}")
                conn.close()
                return None
            return conn

    def refresh(self) -> dict:
        """RF2. -> {"refresh_seconds", "deleted_rows", "errors",
        "retried"}."""
        errors: list[str] = []
        retried: list[str] = []
        solo, rest = (self.refresh_keys[:SOLO_KEYS],
                      self.refresh_keys[SOLO_KEYS:])

        def one(keys) -> None:
            try:
                conn = PgConnection(*self.addr, timeout=self.timeout_s)
            except OSError as e:
                errors.append(f"refresh connection: {e!r}")
                return
            for key, lines in keys:
                conn = self._delete(conn, key, lines, errors, retried)
                if conn is None:
                    return
            conn.close()

        t0 = time.perf_counter()
        one(solo)
        if not errors:
            threads = [threading.Thread(
                target=one, args=(rest[c::self.refresh_connections],),
                daemon=True) for c in range(self.refresh_connections)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
        return {"refresh_seconds": time.perf_counter() - t0,
                "deleted_rows": 0 if errors else sum(
                    lines for _key, lines in self.refresh_keys),
                "errors": errors[:5], "retried": retried}

    def warmup_solo(self) -> dict:
        done = self.refresh()
        if done["errors"]:
            return dict(done, seconds=done["refresh_seconds"])
        warm = super().warmup_solo()
        return dict(warm, refresh_seconds=done["refresh_seconds"],
                    deleted_rows=done["deleted_rows"],
                    retried=done["retried"] + warm["retried"],
                    seconds=warm["seconds"] + done["refresh_seconds"])
