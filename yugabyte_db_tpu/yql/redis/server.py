"""RedisServer: RESP commands lowered onto framework rows.

Reference analog: src/yb/yql/redis/redisserver/redis_service.cc + the
per-command registry of redis_commands.cc:69-154 (~85 commands)
executing as DocDB operations (redis_operation.cc). This server covers
the same families: strings, hashes, sets, sorted sets, lists,
time series (TS*), TTL (EXPIRE/PEXPIRE/EXPIREAT/PERSIST/...), rename,
multi-database (CREATEDB/LISTDB/DELETEDB/SELECT), FLUSHDB/FLUSHALL,
AUTH/CONFIG, and pubsub/MONITOR with real server-push frames.

Data model (module docstring of yql.redis): one table keyed
(rkey hash, field range) with a value column. The stored rkey is
"<db>\\x00<user key>" (database namespacing); the field's first byte
encodes the datatype, mirroring how the reference's RedisWriteOperation
tags subdocument types:

  ""            string value
  "\\x01"+f     hash field f
  "\\x02"+m     set member m
  "\\x03"+m     sorted-set member m      (value = score)
  "\\x04"+ts17  time-series entry        (ts17: order-preserving hex)
  "\\x05"+idx19 list element             (idx19: order-preserving dec)

TTL maps to the engine's native per-version expiry, so expiration needs
no background reaper — exactly the reference's DocDB TTL reuse.
"""

from __future__ import annotations

import fnmatch
import threading
import time

from yugabyte_db_tpu.client import YBSession
from yugabyte_db_tpu.client.client import YBClient
from yugabyte_db_tpu.models.datatypes import DataType
from yugabyte_db_tpu.models.encoding import prefix_successor
from yugabyte_db_tpu.models.schema import ColumnKind, ColumnSchema
from yugabyte_db_tpu.rpc.messenger import Messenger
from yugabyte_db_tpu.utils import trace
from yugabyte_db_tpu.storage.scan_spec import ScanSpec
from yugabyte_db_tpu.utils.metrics import (count_swallowed,
                                           observe_serve_batch)
from yugabyte_db_tpu.yql.redis import resp

try:
    from yugabyte_db_tpu.native import yb_rb as _yb_rb
except ImportError:  # native serving module not built: Python path only
    _yb_rb = None

REDIS_TABLE = "sys.redis"

COLUMNS = [
    ColumnSchema("rkey", DataType.STRING, ColumnKind.HASH),
    ColumnSchema("field", DataType.STRING, ColumnKind.RANGE),
    ColumnSchema("value", DataType.STRING),
]

# field-name type tags
_HASH, _SET, _ZSET, _TS, _LIST = "\x01", "\x02", "\x03", "\x04", "\x05"
_TS_OFF = 1 << 63
_LIST_OFF = 5 * 10 ** 18
_DB_REGISTRY = "\x00dbs"   # registry rows: rkey=_DB_REGISTRY, field=<db>


def _enc_ts(ts: int) -> str:
    if not -_TS_OFF <= ts < _TS_OFF:
        raise ValueError("timestamp out of range")
    return format(ts + _TS_OFF, "017x")


def _dec_ts(field: str) -> int:
    return int(field[1:], 16) - _TS_OFF


def _fmt_score(s: float) -> str:
    return str(int(s)) if s == int(s) else repr(s)


class _ConnState:
    __slots__ = ("db", "authed", "subs", "psubs", "monitor")

    def __init__(self):
        self.db = "0"
        self.authed = False
        self.subs: set[str] = set()
        self.psubs: set[str] = set()
        self.monitor = False


class RedisServiceImpl:
    def __init__(self, client: YBClient, num_tablets: int = 4,
                 replication_factor: int = 3, messenger=None):
        self.client = client
        self.messenger = messenger
        try:
            self.table = client.create_table(
                REDIS_TABLE, COLUMNS, num_tablets=num_tablets,
                replication_factor=replication_factor)
        except Exception as e:  # noqa: BLE001
            if "exist" not in str(e).lower():
                raise
            self.table = client.open_table(REDIS_TABLE)
        self.session = YBSession(client)
        self.commands_served = 0
        self.config: dict[str, str] = {}
        # Redis guarantees per-command atomicity; the messenger runs
        # handlers for DIFFERENT connections concurrently on a worker
        # pool, and one session's op buffer is shared — so commands are
        # serialized here (the single-shard execution model of the
        # reference's redis proxy, one op per batcher flush).
        self._lock = threading.Lock()
        self._states: dict = {}          # conn -> _ConnState
        self._default_state = _ConnState()
        self._cur = self._default_state  # state of the command in flight
        self._subscribers: dict = {}     # conn -> _ConnState (subs alive)
        self._monitors: set = set()      # conns in MONITOR mode
        if not self._registry_dbs():
            self._registry_add("0")

    # -- db registry ---------------------------------------------------------
    def _registry_dbs(self) -> list[str]:
        hc = self.table.hash_code({"rkey": _DB_REGISTRY})
        from yugabyte_db_tpu.models.encoding import encode_doc_key_prefix

        lower = encode_doc_key_prefix(hc, [(_DB_REGISTRY, DataType.STRING)], [])
        spec = ScanSpec(lower=lower, upper=prefix_successor(lower),
                        projection=["field"])
        return sorted(r[0] for r in self.session.scan(self.table, spec).rows)

    def _registry_add(self, db: str) -> None:
        self.session.insert(self.table, {"rkey": _DB_REGISTRY,
                                         "field": db, "value": ""})
        self.session.flush()

    # -- row helpers ---------------------------------------------------------
    def _rk(self, key: str) -> str:
        """Storage rkey: current database + NUL + user key."""
        return f"{self._cur.db}\x00{key}"

    def _get(self, key: str, field: str):
        row = self.session.get(self.table,
                               {"rkey": self._rk(key), "field": field})
        return None if row is None else row[2]

    def _put(self, key: str, field: str, value: str,
             ttl_us: int | None = None, flush: bool = True):
        # TTLs ride as RELATIVE microseconds; the tablet leader resolves
        # them against the write's own stamped hybrid time (client wall
        # clocks and tablet hybrid clocks legitimately disagree).
        self.session.insert(self.table, {
            "rkey": self._rk(key), "field": field, "value": value,
        }, ttl_us=ttl_us)
        if flush:
            self.session.flush()

    def _del(self, key: str, field: str, flush: bool = True):
        self.session.delete(self.table,
                            {"rkey": self._rk(key), "field": field})
        if flush:
            self.session.flush()

    def _fields(self, key: str):
        """All (field, value) rows of one redis key (one hash-routed
        range scan over the key's row group)."""
        from yugabyte_db_tpu.models.encoding import encode_doc_key_prefix

        rkey = self._rk(key)
        hc = self.table.hash_code({"rkey": rkey})
        lower = encode_doc_key_prefix(hc, [(rkey, DataType.STRING)], [])
        spec = ScanSpec(lower=lower, upper=prefix_successor(lower),
                        projection=["field", "value"])
        return self.session.scan(self.table, spec).rows

    def _typed(self, key: str, tag: str):
        return [(f[1:], v) for f, v in self._fields(key)
                if f.startswith(tag)]

    def _all_rows(self, db: str | None):
        """(rkey, field) of every row in one db (None = every db)."""
        rows = self.session.scan(
            self.table, ScanSpec(projection=["rkey", "field"])).rows
        out = []
        for rk, f in rows:
            if rk == _DB_REGISTRY:
                continue
            if db is None or rk.startswith(db + "\x00"):
                out.append((rk, f))
        return out

    # -- dispatch ------------------------------------------------------------
    _PREAUTH = frozenset(["AUTH", "PING", "QUIT", "COMMAND"])

    def handle_batch(self, cmds: list[list[bytes]], conn=None) -> bytes:
        """Pipelined execution: one call per socket read's worth of
        parsed commands. Runs of plain GETs serve through ONE batched
        multi-key read (ts.scan_batch via session.get_many) and runs of
        plain SETs buffer into ONE flush — the shape that makes the
        reference's RedisPipelinedKeyValue numbers possible (its proxy
        batches ops through the async client; docs/yb-perf-v1.0.7.md:
        18-19). Everything else takes the per-command path."""
        observe_serve_batch("redis", len(cmds))
        out = []
        i = 0
        n = len(cmds)
        while i < n:
            c = cmds[i]
            name = c[0].decode().upper() if c else ""
            # Reply-count invariant: the batch MUST emit exactly one
            # reply per command even when a storage call throws — a
            # short reply stream would permanently desync the RESP
            # pairing on this connection.
            if name == "GET" and len(c) == 2:
                j = i
                keys = []
                while j < n and len(cmds[j]) == 2 and \
                        cmds[j][0].decode().upper() == "GET":
                    keys.append(cmds[j][1].decode("utf-8",
                                                  "surrogateescape"))
                    j += 1
                if j - i > 1:
                    try:
                        out.append(self._batch_get(keys, conn))
                    except Exception as e:  # noqa: BLE001
                        out.append(resp.error(str(e)) * len(keys))
                    with self._lock:
                        self.commands_served += j - i
                    i = j
                    continue
            elif name == "SET" and len(c) == 3:
                j = i
                sets = []
                while j < n and len(cmds[j]) == 3 and \
                        cmds[j][0].decode().upper() == "SET":
                    sets.append(
                        (cmds[j][1].decode("utf-8", "surrogateescape"),
                         cmds[j][2].decode("utf-8", "surrogateescape")))
                    j += 1
                if j - i > 1:
                    try:
                        out.append(self._batch_set(sets, conn))
                    except Exception as e:  # noqa: BLE001
                        out.append(resp.error(str(e)) * len(sets))
                    with self._lock:
                        self.commands_served += j - i
                    i = j
                    continue
            try:
                out.append(self.handle(c, conn))
            except Exception as e:  # noqa: BLE001
                out.append(resp.error(str(e)))
            i += 1
        return b"".join(out)

    def _enter(self, conn, name: str) -> bytes | None:
        """Per-command session state + auth gate (callers hold _lock)."""
        if conn is None:
            self._cur = self._default_state
        else:
            st = self._states.get(conn)
            if st is None:
                st = self._states[conn] = _ConnState()
            self._cur = st
        if self.config.get("requirepass") and not self._cur.authed \
                and name not in self._PREAUTH:
            return resp.error("NOAUTH Authentication required.")
        return None

    def _batch_get(self, keys: list[str], conn) -> bytes:
        # Session state (auth, _cur.db for rkeys, MONITOR feeds) resolves
        # under the lock; the storage fetch runs OUTSIDE it so other
        # connections' commands aren't serialized behind this batch's
        # RPC round-trips. Pipelined GETs are not atomic in Redis (that
        # is MULTI), so interleaved writes between them are legal.
        with self._lock:
            err = self._enter(conn, "GET")
            if err is not None:
                return err * len(keys)
            if self._monitors:
                for k in keys:
                    self._feed_monitors(conn, "GET", [k])
            rkeys = [self._rk(k) for k in keys]
        return b"".join(resp.bulk(v) for v in self._fetch_values(rkeys))

    def _get_values(self, keys: list[str]) -> list:
        """Values of plain string keys (field "") in key order. Callers
        hold _lock (self._cur.db feeds the storage rkey)."""
        return self._fetch_values([self._rk(k) for k in keys])

    def _fetch_values(self, rkeys: list[str]) -> list:
        """Fetch resolved rkeys — the native batch serving path when
        every hop is eligible (raw stored payload bytes),
        session.get_many otherwise (str). resp.bulk encodes bytes and
        str to IDENTICAL reply bytes: the stored column payload is
        exactly the value's utf-8 surrogateescape encoding (tagcodec
        T_STR). Needs no lock: rkeys are pre-resolved and the session
        handles are immutable."""
        values = self._native_get_values(rkeys)
        if values is None:
            values = [False] * len(rkeys)
        # False entries: native couldn't answer definitively (module
        # absent, tablet fallback, non-string stored value) — serve
        # those through the canonical Python read path.
        need = [i for i, v in enumerate(values) if v is False]
        if need:
            rows = self.session.get_many(
                self.table,
                [{"rkey": rkeys[i], "field": ""} for i in need])
            for i, r in zip(need, rows):
                values[i] = None if r is None else r[2]
        return values

    def _native_get_values(self, rkeys: list[str]):
        """One ts.redis_read_batch RPC per tablet for a batch of point
        keys, served from the native memtable (docs/serving-path.md).
        None = native path unavailable; per-key False = fall back for
        that key (a tablet replying "fallback" leaves its whole group
        False)."""
        if _yb_rb is None:
            return None
        try:
            locs = self.client.meta_cache.locations(self.table.name)
            tablets = sorted(locs.tablets,
                             key=lambda t: t.partition_start)
            routed = _yb_rb.encode_point_keys(
                (3,), (3,), [(rk, "") for rk in rkeys],
                [t.partition_start for t in tablets], 1)
        except Exception as e:  # noqa: BLE001 — Python path is canonical
            count_swallowed("redis.native_route", e)
            return None
        groups: dict[int, tuple[list, list]] = {}
        for i, (part, key) in enumerate(routed):
            g = groups.get(part)
            if g is None:
                g = groups[part] = ([], [])
            g[0].append(i)
            g[1].append(key)
        values: list = [False] * len(rkeys)
        col_id = self.table.col_id["value"]
        for part, (idxs, keys) in groups.items():
            try:
                r = self.client.tablet_rpc(
                    self.table.name, tablets[part],
                    "ts.redis_read_batch",
                    {"keys": keys, "col_id": col_id})
            except Exception as e:  # noqa: BLE001 — per-group fallback
                count_swallowed("redis.native_read_batch", e)
                continue
            if r.get("fallback"):
                continue
            for i, v in zip(idxs, r["values"]):
                values[i] = v
        return values

    def _batch_set(self, sets: list[tuple[str, str]], conn) -> bytes:
        with self._lock:
            err = self._enter(conn, "SET")
            if err is not None:
                return err * len(sets)
            if self._monitors:
                for k, v in sets:
                    self._feed_monitors(conn, "SET", [k, v])
            try:
                for k, v in sets:
                    self.session.insert(self.table, {
                        "rkey": self._rk(k), "field": "", "value": v})
                self.session.flush()
            finally:
                self.session._ops.clear()
            return resp.simple("OK") * len(sets)

    def handle(self, args: list[bytes], conn=None) -> bytes:
        with self._lock:
            self.commands_served += 1
        name = args[0].decode().upper()
        fn = getattr(self, "cmd_" + name.lower(), None)
        if fn is None:
            return resp.error(f"unknown command '{name}'")
        try:
            with self._lock:
                err = self._enter(conn, name)
                if err is not None:
                    return err
                decoded = [a.decode("utf-8", "surrogateescape")
                           for a in args[1:]]
                self._feed_monitors(conn, name, decoded)
                try:
                    return fn(decoded, conn) if getattr(
                        fn, "wants_conn", False) else fn(decoded)
                finally:
                    # A handler that errored mid-buffer must not leak its
                    # partial ops into the next command's flush.
                    self.session._ops.clear()
        except IndexError:
            return resp.error(
                f"wrong number of arguments for '{name.lower()}' command")
        except ValueError:
            return resp.error("value is not an integer or out of range")

    def _push(self, conn, data: bytes) -> None:
        if self.messenger is not None and conn is not None \
                and not getattr(conn, "closed", False):
            self.messenger.send_on(conn, data)

    def _feed_monitors(self, conn, name, args) -> None:
        if not self._monitors:
            return
        line = " ".join([f"{time.time():.6f}", f'"{name}"']
                        + [f'"{a}"' for a in args])
        for mc in list(self._monitors):
            if getattr(mc, "closed", False):
                self._monitors.discard(mc)
            elif mc is not conn:
                self._push(mc, resp.simple(line))

    # -- server commands -----------------------------------------------------
    def cmd_ping(self, a):
        return resp.bulk(a[0]) if a else resp.simple("PONG")

    def cmd_echo(self, a):
        return resp.bulk(a[0])

    def cmd_quit(self, a):
        return resp.simple("OK")

    def cmd_select(self, a):
        db = a[0]
        if db not in self._registry_dbs():
            return resp.error(f"DB {db} does not exist")
        self._cur.db = db
        return resp.simple("OK")

    def cmd_createdb(self, a):
        if not a[0] or "\x00" in a[0]:
            return resp.error("invalid database name")
        self._registry_add(a[0])
        return resp.simple("OK")

    def cmd_listdb(self, a):
        return resp.array(self._registry_dbs())

    def cmd_deletedb(self, a):
        db = a[0]
        dbs = self._registry_dbs()
        if db not in dbs:
            return resp.error(f"DB {db} does not exist")
        if db == "0":
            return resp.error("cannot delete DB 0")
        for rk, f in self._all_rows(db):
            self.session.delete(self.table, {"rkey": rk, "field": f})
        self.session.delete(self.table, {"rkey": _DB_REGISTRY, "field": db})
        self.session.flush()
        return resp.simple("OK")

    def cmd_command(self, a):
        return resp.array([])

    def cmd_info(self, a):
        return resp.bulk(f"# Server\nredis_compat:yedis\n"
                         f"commands_served:{self.commands_served}\n")

    def cmd_role(self, a):
        return resp.array(["master"])

    def cmd_auth(self, a):
        pw = self.config.get("requirepass")
        if pw is None:
            return resp.error(
                "Client sent AUTH, but no password is set")
        if a[0] != pw:
            return resp.error("invalid password")
        self._cur.authed = True
        return resp.simple("OK")

    def cmd_config(self, a):
        sub = a[0].upper()
        if sub == "SET":
            # cmd_* handlers run under self._lock: handle()/handle_batch
            # dispatch them via getattr("cmd_" + name), which the call
            # graph cannot resolve into an edge.
            # yb-lint: disable=iraces/guarded-read-unguarded-write
            self.config[a[1].lower()] = a[2]
            return resp.simple("OK")
        if sub == "GET":
            k = a[1].lower()
            if k in self.config:
                return resp.array([k, self.config[k]])
            return resp.array([])
        return resp.error(f"unknown CONFIG subcommand {a[0]}")

    def cmd_cluster(self, a):
        if a and a[0].upper() == "INFO":
            return resp.bulk("cluster_enabled:0\r\ncluster_state:ok\r\n")
        return resp.array([])

    def cmd_debugsleep(self, a):
        time.sleep(float(a[0]))
        return resp.simple("OK")

    def cmd_monitor(self, a, conn=None):
        if conn is not None:
            # Runs under self._lock via handle()'s getattr dispatch,
            # invisible to the call graph (see cmd_config).
            # yb-lint: disable=iraces/unguarded-shared-write
            self._monitors.add(conn)
        return resp.simple("OK")
    cmd_monitor.wants_conn = True

    def cmd_flushdb(self, a):
        for rk, f in self._all_rows(self._cur.db):
            self.session.delete(self.table, {"rkey": rk, "field": f})
        self.session.flush()
        return resp.simple("OK")

    def cmd_flushall(self, a):
        for rk, f in self._all_rows(None):
            self.session.delete(self.table, {"rkey": rk, "field": f})
        self.session.flush()
        return resp.simple("OK")

    # -- pubsub --------------------------------------------------------------
    def cmd_publish(self, a):
        channel, message = a[0], a[1]
        n = 0
        for conn, st in list(self._subscribers.items()):
            if getattr(conn, "closed", False):
                del self._subscribers[conn]
                continue
            if channel in st.subs:
                self._push(conn, resp.array(["message", channel, message]))
                n += 1
            for pat in st.psubs:
                if fnmatch.fnmatchcase(channel, pat):
                    self._push(conn, resp.array(
                        ["pmessage", pat, channel, message]))
                    n += 1
        return resp.integer(n)

    def _sub_frames(self, conn, chans, pats, subscribe: bool) -> bytes:
        st = self._cur
        out = []
        for ch in chans:
            if subscribe:
                st.subs.add(ch)
            else:
                st.subs.discard(ch)
            out.append(resp.array(
                ["subscribe" if subscribe else "unsubscribe", ch,
                 len(st.subs) + len(st.psubs)]))
        for p in pats:
            if subscribe:
                st.psubs.add(p)
            else:
                st.psubs.discard(p)
            out.append(resp.array(
                ["psubscribe" if subscribe else "punsubscribe", p,
                 len(st.subs) + len(st.psubs)]))
        if conn is not None:
            if st.subs or st.psubs:
                self._subscribers[conn] = st
            else:
                self._subscribers.pop(conn, None)
        return b"".join(out)

    def cmd_subscribe(self, a, conn=None):
        return self._sub_frames(conn, a, [], True)
    cmd_subscribe.wants_conn = True

    def cmd_unsubscribe(self, a, conn=None):
        chans = a if a else sorted(self._cur.subs)
        return self._sub_frames(conn, chans, [], False)
    cmd_unsubscribe.wants_conn = True

    def cmd_psubscribe(self, a, conn=None):
        return self._sub_frames(conn, [], a, True)
    cmd_psubscribe.wants_conn = True

    def cmd_punsubscribe(self, a, conn=None):
        pats = a if a else sorted(self._cur.psubs)
        return self._sub_frames(conn, [], pats, False)
    cmd_punsubscribe.wants_conn = True

    def cmd_pubsub(self, a):
        sub = a[0].upper()
        states = [st for c, st in self._subscribers.items()
                  if not getattr(c, "closed", False)]
        if sub == "CHANNELS":
            pat = a[1] if len(a) > 1 else "*"
            chans = sorted({ch for st in states for ch in st.subs
                            if fnmatch.fnmatchcase(ch, pat)})
            return resp.array(chans)
        if sub == "NUMSUB":
            out = []
            for ch in a[1:]:
                out.extend([ch, sum(1 for st in states if ch in st.subs)])
            return resp.array(out)
        if sub == "NUMPAT":
            return resp.integer(
                len({p for st in states for p in st.psubs}))
        return resp.error(f"unknown PUBSUB subcommand {a[0]}")

    # -- strings -------------------------------------------------------------
    def cmd_set(self, a):
        key, value = a[0], a[1]
        ttl_us = None
        i = 2
        nx = xx = False
        while i < len(a):
            opt = a[i].upper()
            if opt == "EX":
                ttl_us = int(float(a[i + 1]) * 1_000_000)
                i += 2
            elif opt == "PX":
                ttl_us = int(float(a[i + 1]) * 1_000)
                i += 2
            elif opt == "NX":
                nx = True
                i += 1
            elif opt == "XX":
                xx = True
                i += 1
            else:
                return resp.error("syntax error")
        if nx or xx:
            cur = self._get(key, "")
            if (nx and cur is not None) or (xx and cur is None):
                return resp.bulk(None)
        self._put(key, "", value, ttl_us)
        return resp.simple("OK")

    def cmd_setex(self, a):
        self._put(a[0], "", a[2], int(float(a[1]) * 1_000_000))
        return resp.simple("OK")

    def cmd_psetex(self, a):
        self._put(a[0], "", a[2], int(float(a[1]) * 1_000))
        return resp.simple("OK")

    def cmd_setnx(self, a):
        if self._get(a[0], "") is not None:
            return resp.integer(0)
        self._put(a[0], "", a[1])
        return resp.integer(1)

    def cmd_get(self, a):
        return resp.bulk(self._get(a[0], ""))

    def cmd_getset(self, a):
        old = self._get(a[0], "")
        self._put(a[0], "", a[1])
        return resp.bulk(old)

    def cmd_append(self, a):
        cur = self._get(a[0], "") or ""
        new = cur + a[1]
        self._put(a[0], "", new)
        return resp.integer(len(new))

    def cmd_strlen(self, a):
        v = self._get(a[0], "")
        return resp.integer(len(v) if v else 0)

    def cmd_getrange(self, a):
        v = self._get(a[0], "") or ""
        start, end = int(a[1]), int(a[2])
        n = len(v)
        if start < 0:
            start = max(n + start, 0)
        if end < 0:
            end = n + end
        return resp.bulk(v[start:end + 1] if end >= start else "")

    def cmd_setrange(self, a):
        key, off, chunk = a[0], int(a[1]), a[2]
        if off < 0:
            return resp.error("offset is out of range")
        cur = self._get(key, "") or ""
        if len(cur) < off:
            cur = cur + "\x00" * (off - len(cur))
        new = cur[:off] + chunk + cur[off + len(chunk):]
        self._put(key, "", new)
        return resp.integer(len(new))

    def cmd_mget(self, a):
        # Same batched serving path as pipelined GET runs: one native
        # multiget (or one ts.scan_batch) instead of a scan per key.
        return resp.array(self._get_values(list(a)))

    def cmd_mset(self, a):
        if not a or len(a) % 2:
            return resp.error("wrong number of arguments for 'mset' command")
        for i in range(0, len(a), 2):
            self._put(a[i], "", a[i + 1], flush=False)
        self.session.flush()
        return resp.simple("OK")

    def cmd_incr(self, a):
        return self._incrby(a[0], "", 1)

    def cmd_incrby(self, a):
        return self._incrby(a[0], "", int(a[1]))

    def cmd_decr(self, a):
        return self._incrby(a[0], "", -1)

    def cmd_decrby(self, a):
        return self._incrby(a[0], "", -int(a[1]))

    def _incrby(self, key, field, by):
        cur = self._get(key, field)
        if cur is not None:
            try:
                cur = int(cur)
            except ValueError:
                return resp.error(
                    "value is not an integer or out of range")
        new = (cur or 0) + by
        self._put(key, field, str(new))
        return resp.integer(new)

    def cmd_del(self, a):
        n = 0
        for key in a:
            rows = self._fields(key)
            for field, _v in rows:
                self._del(key, field, flush=False)
            if rows:
                n += 1
        self.session.flush()
        return resp.integer(n)

    def cmd_exists(self, a):
        return resp.integer(sum(1 for k in a if self._fields(k)))

    def cmd_rename(self, a):
        src, dst = a[0], a[1]
        rows = self._fields(src)
        if not rows:
            return resp.error("no such key")
        for field, _v in self._fields(dst):
            self._del(dst, field, flush=False)
        for field, value in rows:
            self._put(dst, field, value, flush=False)
            self._del(src, field, flush=False)
        self.session.flush()
        return resp.simple("OK")

    # -- TTL -----------------------------------------------------------------
    def _set_ttl(self, key: str, ttl_us: int | None) -> bytes:
        rows = self._fields(key)
        if not rows:
            return resp.integer(0)
        if ttl_us is not None and ttl_us <= 0:
            return self.cmd_del([key])
        for field, value in rows:
            self._put(key, field, value, ttl_us, flush=False)
        self.session.flush()
        return resp.integer(1)

    def cmd_expire(self, a):
        return self._set_ttl(a[0], int(float(a[1]) * 1_000_000))

    def cmd_pexpire(self, a):
        return self._set_ttl(a[0], int(float(a[1]) * 1_000))

    def cmd_expireat(self, a):
        return self._set_ttl(
            a[0], int((float(a[1]) - time.time()) * 1_000_000))

    def cmd_pexpireat(self, a):
        return self._set_ttl(
            a[0], int(float(a[1]) * 1_000 - time.time() * 1_000_000))

    def cmd_persist(self, a):
        return self._set_ttl(a[0], None)

    def cmd_ttl(self, a):
        # Without surfacing expire_ht through the read path this reports
        # -1 (no TTL) for live keys, -2 for missing (reference's contract
        # subset).
        return resp.integer(-1 if self._fields(a[0]) else -2)

    def cmd_pttl(self, a):
        return resp.integer(-1 if self._fields(a[0]) else -2)

    def cmd_keys(self, a):
        pattern = a[0] if a else "*"
        prefix = self._cur.db + "\x00"
        spec = ScanSpec(projection=["rkey"])
        rows = self.session.scan(self.table, spec).rows
        keys = sorted({r[0][len(prefix):] for r in rows
                       if r[0].startswith(prefix)})
        return resp.array([k for k in keys
                           if fnmatch.fnmatchcase(k, pattern)])

    # -- hashes --------------------------------------------------------------
    def cmd_hset(self, a):
        key = a[0]
        if len(a) < 3 or len(a) % 2 == 0:
            return resp.error("wrong number of arguments for 'hset' command")
        n = 0
        for i in range(1, len(a), 2):
            if self._get(key, _HASH + a[i]) is None:
                n += 1
            self._put(key, _HASH + a[i], a[i + 1], flush=False)
        self.session.flush()
        return resp.integer(n)

    def cmd_hmset(self, a):
        self.cmd_hset(a)
        return resp.simple("OK")

    def cmd_hget(self, a):
        return resp.bulk(self._get(a[0], _HASH + a[1]))

    def cmd_hmget(self, a):
        return resp.array([self._get(a[0], _HASH + f) for f in a[1:]])

    def cmd_hincrby(self, a):
        return self._incrby(a[0], _HASH + a[1], int(a[2]))

    def cmd_hstrlen(self, a):
        v = self._get(a[0], _HASH + a[1])
        return resp.integer(len(v) if v else 0)

    def cmd_hdel(self, a):
        n = 0
        for f in a[1:]:
            if self._get(a[0], _HASH + f) is not None:
                self._del(a[0], _HASH + f)
                n += 1
        return resp.integer(n)

    def cmd_hexists(self, a):
        return resp.integer(
            0 if self._get(a[0], _HASH + a[1]) is None else 1)

    def cmd_hgetall(self, a):
        out = []
        for f, v in self._typed(a[0], _HASH):
            out.extend([f, v])
        return resp.array(out)

    def cmd_hkeys(self, a):
        return resp.array([f for f, _v in self._typed(a[0], _HASH)])

    def cmd_hvals(self, a):
        return resp.array([v for _f, v in self._typed(a[0], _HASH)])

    def cmd_hlen(self, a):
        return resp.integer(len(self._typed(a[0], _HASH)))

    # -- sets ----------------------------------------------------------------
    def cmd_sadd(self, a):
        key = a[0]
        n = 0
        for m in a[1:]:
            if self._get(key, _SET + m) is None:
                n += 1
            self._put(key, _SET + m, "", flush=False)
        self.session.flush()
        return resp.integer(n)

    def cmd_srem(self, a):
        n = 0
        for m in a[1:]:
            if self._get(a[0], _SET + m) is not None:
                self._del(a[0], _SET + m)
                n += 1
        return resp.integer(n)

    def cmd_smembers(self, a):
        return resp.array(sorted(f for f, _v in self._typed(a[0], _SET)))

    def cmd_sismember(self, a):
        return resp.integer(
            0 if self._get(a[0], _SET + a[1]) is None else 1)

    def cmd_scard(self, a):
        return resp.integer(len(self._typed(a[0], _SET)))

    # -- sorted sets ---------------------------------------------------------
    def _zitems(self, key):
        """[(score, member)] sorted by (score, member)."""
        items = [(float(v), f) for f, v in self._typed(key, _ZSET)]
        items.sort()
        return items

    def cmd_zadd(self, a):
        key = a[0]
        i = 1
        ch = False
        while i < len(a) and a[i].upper() in ("NX", "XX", "CH", "INCR"):
            if a[i].upper() == "CH":
                ch = True
                i += 1
            else:
                return resp.error(
                    f"ZADD option {a[i]} is not supported")
        pairs = a[i:]
        if not pairs or len(pairs) % 2:
            return resp.error("syntax error")
        added = changed = 0
        for j in range(0, len(pairs), 2):
            score = float(pairs[j])
            member = pairs[j + 1]
            old = self._get(key, _ZSET + member)
            if old is None:
                added += 1
            elif float(old) != score:
                changed += 1
            self._put(key, _ZSET + member, repr(score), flush=False)
        self.session.flush()
        return resp.integer(added + changed if ch else added)

    def cmd_zrem(self, a):
        n = 0
        for m in a[1:]:
            if self._get(a[0], _ZSET + m) is not None:
                self._del(a[0], _ZSET + m)
                n += 1
        return resp.integer(n)

    def cmd_zscore(self, a):
        v = self._get(a[0], _ZSET + a[1])
        return resp.bulk(None if v is None else _fmt_score(float(v)))

    def cmd_zcard(self, a):
        return resp.integer(len(self._typed(a[0], _ZSET)))

    def _zrange_out(self, items, withscores):
        out = []
        for score, member in items:
            out.append(member)
            if withscores:
                out.append(_fmt_score(score))
        return resp.array(out)

    def _rank_slice(self, items, start, stop):
        n = len(items)
        if start < 0:
            start = max(n + start, 0)
        if stop < 0:
            stop = n + stop
        return items[start:stop + 1] if stop >= start else []

    def cmd_zrange(self, a):
        withscores = len(a) > 3 and a[3].upper() == "WITHSCORES"
        items = self._rank_slice(self._zitems(a[0]), int(a[1]), int(a[2]))
        return self._zrange_out(items, withscores)

    def cmd_zrevrange(self, a):
        withscores = len(a) > 3 and a[3].upper() == "WITHSCORES"
        items = self._rank_slice(self._zitems(a[0])[::-1],
                                 int(a[1]), int(a[2]))
        return self._zrange_out(items, withscores)

    @staticmethod
    def _score_bound(s: str, is_min: bool):
        """min/max bound -> (value, exclusive)."""
        excl = s.startswith("(")
        if excl:
            s = s[1:]
        if s in ("-inf", "+inf", "inf"):
            return float(s.replace("+", "")), excl
        return float(s), excl

    def cmd_zrangebyscore(self, a):
        lo, lo_x = self._score_bound(a[1], True)
        hi, hi_x = self._score_bound(a[2], False)
        withscores = len(a) > 3 and a[3].upper() == "WITHSCORES"
        items = [(s, m) for s, m in self._zitems(a[0])
                 if (s > lo if lo_x else s >= lo)
                 and (s < hi if hi_x else s <= hi)]
        return self._zrange_out(items, withscores)

    # -- lists (reference v1.2.4 surface: push/pop/len) ----------------------
    def _list_items(self, key):
        """[(index, value)] in list order."""
        return sorted((int(f) - _LIST_OFF, v)
                      for f, v in self._typed(key, _LIST))

    def cmd_lpush(self, a):
        items = self._list_items(a[0])
        left = items[0][0] if items else 0
        for i, v in enumerate(a[1:]):
            self._put(a[0], _LIST + f"{left - 1 - i + _LIST_OFF:019d}", v,
                      flush=False)
        self.session.flush()
        return resp.integer(len(items) + len(a) - 1)

    def cmd_rpush(self, a):
        items = self._list_items(a[0])
        right = items[-1][0] if items else 0
        for i, v in enumerate(a[1:]):
            self._put(a[0], _LIST + f"{right + 1 + i + _LIST_OFF:019d}", v,
                      flush=False)
        self.session.flush()
        return resp.integer(len(items) + len(a) - 1)

    def cmd_lpop(self, a):
        items = self._list_items(a[0])
        if not items:
            return resp.bulk(None)
        idx, v = items[0]
        self._del(a[0], _LIST + f"{idx + _LIST_OFF:019d}")
        return resp.bulk(v)

    def cmd_rpop(self, a):
        items = self._list_items(a[0])
        if not items:
            return resp.bulk(None)
        idx, v = items[-1]
        self._del(a[0], _LIST + f"{idx + _LIST_OFF:019d}")
        return resp.bulk(v)

    def cmd_llen(self, a):
        return resp.integer(len(self._typed(a[0], _LIST)))

    # -- time series ---------------------------------------------------------
    def cmd_tsadd(self, a):
        key = a[0]
        pairs = a[1:]
        if not pairs or len(pairs) % 2:
            return resp.error("wrong number of arguments for 'tsadd' command")
        for i in range(0, len(pairs), 2):
            self._put(key, _TS + _enc_ts(int(pairs[i])), pairs[i + 1],
                      flush=False)
        self.session.flush()
        return resp.simple("OK")

    def cmd_tsget(self, a):
        return resp.bulk(self._get(a[0], _TS + _enc_ts(int(a[1]))))

    def cmd_tsrem(self, a):
        n = 0
        for ts in a[1:]:
            if self._get(a[0], _TS + _enc_ts(int(ts))) is not None:
                self._del(a[0], _TS + _enc_ts(int(ts)))
                n += 1
        return resp.integer(n)

    def cmd_tscard(self, a):
        return resp.integer(len(self._typed(a[0], _TS)))

    def _ts_bound(self, s: str, lo: bool) -> int:
        if s in ("-inf", "+inf", "inf"):
            return (-_TS_OFF) if s == "-inf" else _TS_OFF - 1
        return int(s)

    def _ts_range(self, key, lo, hi):
        return [(_dec_ts(_TS + f), v) for f, v in self._typed(key, _TS)
                if lo <= _dec_ts(_TS + f) <= hi]

    def cmd_tsrangebytime(self, a):
        lo = self._ts_bound(a[1], True)
        hi = self._ts_bound(a[2], False)
        out = []
        for ts, v in self._ts_range(a[0], lo, hi):
            out.extend([str(ts), v])
        return resp.array(out)

    def cmd_tsrevrangebytime(self, a):
        lo = self._ts_bound(a[1], True)
        hi = self._ts_bound(a[2], False)
        out = []
        for ts, v in reversed(self._ts_range(a[0], lo, hi)):
            out.extend([str(ts), v])
        return resp.array(out)

    def cmd_tslastn(self, a):
        n = int(a[1])
        items = self._ts_range(a[0], -_TS_OFF, _TS_OFF - 1)[-n:]
        out = []
        for ts, v in items:
            out.extend([str(ts), v])
        return resp.array(out)


class RedisServer:
    """RESP wire server over the messenger (the yb-tserver's port-6379
    proxy, tablet_server_main.cc:191)."""

    def __init__(self, client: YBClient, messenger: Messenger | None = None,
                 **kwargs):
        self._own_messenger = messenger is None
        self.messenger = messenger or Messenger(name="redis")
        self.service = RedisServiceImpl(client, messenger=self.messenger,
                                        **kwargs)

    def listen(self, host: str = "127.0.0.1", port: int = 0):
        def handler(conn, method, args):
            if method == "redis_batch":
                with trace.statement("redis", n=len(args)):
                    return self.service.handle_batch(args, conn)
            with trace.statement("redis"):
                return self.service.handle(args, conn)
        handler.takes_conn = True

        from yugabyte_db_tpu.yql.redis.resp import RedisConnectionContext

        return self.messenger.listen(host, port, handler,
                                     context_factory=RedisConnectionContext)

    def shutdown(self) -> None:
        if self._own_messenger:
            self.messenger.shutdown()
