"""PgProcessor: parse -> plan -> execute SQL against the cluster seam.

Reference analog: the YSQL execution stack — the PostgreSQL executor's
foreign-scan path (ybc_fdw.c:364 ybcIterateForeignScan) feeding
PgsqlReadOperation with WHERE pushdown and per-tablet partial aggregates
(src/yb/docdb/pgsql_operation.cc:345,473), and the DML path through
PgDocWriteOp (src/yb/yql/pggate/pg_doc_op.h:142). Here the planner
lowers SELECT straight to ScanSpecs on the shared Cluster seam (the
same LocalCluster / ClientCluster objects the CQL processor drives),
with grouped/expression aggregates pushed down to the storage engine —
on the TPU engine that is one device dispatch per tablet (ops.group_agg)
— and per-tablet partials combined above the scan (operations.py).

SQL semantic notes (vs the CQL processor):
- INSERT enforces primary-key uniqueness (PG errors on duplicates;
  CQL upserts).
- UPDATE/DELETE accept arbitrary WHERE: non-PK predicates resolve via a
  predicate-pushdown scan, then write per matching row.
- avg() lowers to sum+count partials and is derived after the combine
  (partial averages cannot be merged across tablets).
"""

from __future__ import annotations

import time
from dataclasses import dataclass, field

from yugabyte_db_tpu.models.datatypes import DataType
from yugabyte_db_tpu.models.schema import ColumnKind, ColumnSchema, Schema
from yugabyte_db_tpu.storage import expr as X
from yugabyte_db_tpu.storage.row_version import MAX_HT, RowVersion
from yugabyte_db_tpu.storage.scan_spec import AggSpec, Predicate, ScanSpec
from yugabyte_db_tpu.utils import metrics, trace
from yugabyte_db_tpu.utils.status import AlreadyPresent, InvalidArgument
from yugabyte_db_tpu.yql.pgsql import ast
from yugabyte_db_tpu.yql.pgsql.operations import combine_grouped
from yugabyte_db_tpu.yql.pgsql.parser import parse_statement


class SerializationFailure(Exception):
    """Transaction conflict/abort (PG error code 40001): retry it."""


class FailedTransaction(Exception):
    """Statement issued inside an aborted block (PG code 25P02)."""


class _StatementClock:
    """Where one ``execute`` spent its time around its scan units, for
    the spans ``pg.plan`` (entered until ``_prefetch_scans`` is first
    called), ``pg.scans`` (the statement's own thread blocked on a
    unit's result, summed; label ``units``) and ``pg.combine`` (the
    last unit's result in hand until ``execute`` returns): children of
    ``pg.statement``, ``yb_pg_statement_part_us{part}``. A statement
    that scans nothing observes none of the three. Until ``finish`` it
    only reads clocks: nothing is recorded between a statement's parse
    and its units' replies."""

    __slots__ = ("wall_ns", "t0", "planned", "blocked_ns", "units", "last")

    def __init__(self):
        self.wall_ns = time.time_ns()
        self.t0 = time.perf_counter_ns()
        self.planned = None      # when _prefetch_scans was first called
        self.blocked_ns = 0
        self.units = 0
        self.last = self.t0

    def scans_begin(self) -> None:
        if self.planned is None:
            self.planned = time.perf_counter_ns()

    def blocked(self, since_ns: int) -> None:
        self.last = time.perf_counter_ns()
        self.blocked_ns += self.last - since_ns
        self.units += 1

    def finish(self) -> None:
        if self.planned is None:
            return
        now = time.perf_counter_ns()
        for part, since, dur_ns, labels in (
                ("plan", self.t0, self.planned - self.t0, {}),
                ("scans", self.planned, self.blocked_ns,
                 {"units": self.units}),
                ("combine", self.last, now - self.last, {})):
            trace.record_span(
                "pg." + part, self.wall_ns + since - self.t0, dur_ns // 1000,
                metrics.pg_statement_part_histogram(part), **labels)


@dataclass
class PgResult:
    """Rows returned to the driver (the wire server turns this into
    RowDescription + DataRow messages)."""

    columns: list[str] = field(default_factory=list)
    rows: list[tuple] = field(default_factory=list)
    command: str = "SELECT"    # CommandComplete tag prefix

    def __iter__(self):
        return iter(self.rows)

    def dicts(self) -> list[dict]:
        return [dict(zip(self.columns, r)) for r in self.rows]


class PgProcessor:
    """One SQL session over a Cluster seam.

    Transactions (BEGIN/COMMIT/ROLLBACK) run on the distributed seam's
    TransactionManager: DML inside a transaction buffers intents through
    a YBTransaction (snapshot isolation, first-committer-wins conflicts
    surfaced as 40001); point SELECTs read-your-writes, range SELECTs
    read the transaction's snapshot (own uncommitted writes are not
    merged into range scans — the documented client-txn contract)."""

    def __init__(self, cluster):
        self.cluster = cluster
        self._txn = None
        self._txn_failed = False  # aborted block awaiting COMMIT/ROLLBACK
        self._yb_tables: dict = {}
        self._currvals: dict[str, int] = {}  # per-session currval state
        self._clock = _StatementClock()      # of the statement executing

    @property
    def in_txn(self) -> bool:
        return self._txn is not None or self._txn_failed

    @property
    def txn_status(self) -> str:
        """The ReadyForQuery status byte: I idle, T in txn, E failed."""
        if self._txn_failed:
            return "E"
        return "T" if self._txn is not None else "I"

    # -- entry point -------------------------------------------------------
    def execute(self, sql, params: list | None = None) -> PgResult | None:
        clock = self._clock = _StatementClock()
        res = self._execute(sql, params)
        clock.finish()
        return res

    def _execute(self, sql, params: list | None) -> PgResult | None:
        stmt = parse_statement(sql) if isinstance(sql, str) else sql
        self._params = params or []
        if isinstance(stmt, ast.TxnControl):
            return self._exec_txn_control(stmt)
        if self._txn_failed:
            # PG 25P02: the block already failed; only COMMIT/ROLLBACK
            # (both of which roll back) end it
            raise FailedTransaction(
                "current transaction is aborted, commands ignored until "
                "end of transaction block")
        fn = {
            ast.CreateTable: self._exec_create_table,
            ast.DropTable: self._exec_drop_table,
            ast.AlterTable: self._exec_alter_table,
            ast.CreateIndex: self._exec_create_index,
            ast.DropIndex: self._exec_drop_index,
            ast.Insert: self._exec_insert,
            ast.Update: self._exec_update,
            ast.Delete: self._exec_delete,
            ast.Select: self._exec_query,
            ast.Union: self._exec_query,
            ast.CreateView: self._exec_create_view,
            ast.DropView: self._exec_drop_view,
            ast.CreateSequence: self._exec_create_sequence,
            ast.DropSequence: self._exec_drop_sequence,
        }[type(stmt)]
        try:
            return fn(stmt)
        except Exception:
            if self._txn is not None:
                # a failed statement aborts the whole block (PG
                # semantics): nothing from it may ever commit
                self._txn.abort()
                self._txn = None
                self._txn_failed = True
            raise

    # -- transactions ------------------------------------------------------
    def _exec_txn_control(self, stmt: ast.TxnControl):
        from yugabyte_db_tpu.txn.errors import (TransactionAborted,
                                                TransactionConflict)

        if stmt.kind == "begin":
            if self.in_txn:
                raise InvalidArgument(
                    "there is already a transaction in progress")
            mgr_fn = getattr(self.cluster, "transaction_manager", None)
            if mgr_fn is None:
                raise InvalidArgument(
                    "transactions require a distributed cluster")
            self._txn = mgr_fn().begin()
            return PgResult(command="BEGIN")
        if stmt.kind in ("savepoint", "rollback_to", "release"):
            if self._txn_failed:
                # Divergence from PG, stated plainly: a failed statement
                # aborts the WHOLE block here (statement-level
                # subtransactions are not implemented), so a savepoint
                # cannot resurrect it.
                raise FailedTransaction(
                    "current transaction is aborted (savepoints cannot "
                    "recover a failed block in this implementation)")
            if self._txn is None:
                raise InvalidArgument(
                    "SAVEPOINT can only be used in transaction blocks")
            if stmt.kind == "savepoint":
                self._txn.savepoint(stmt.name)
                return PgResult(command="SAVEPOINT")
            try:
                if stmt.kind == "rollback_to":
                    self._txn.rollback_to_savepoint(stmt.name)
                    return PgResult(command="ROLLBACK")
                self._txn.release_savepoint(stmt.name)
                return PgResult(command="RELEASE")
            except KeyError as e:
                raise InvalidArgument(str(e)) from None
        if self._txn_failed:
            # COMMIT of a failed block is a rollback (PG reports it so)
            self._txn_failed = False
            return PgResult(command="ROLLBACK")
        if self._txn is None:
            raise InvalidArgument("no transaction in progress")
        txn, self._txn = self._txn, None
        if stmt.kind == "rollback":
            txn.abort()
            return PgResult(command="ROLLBACK")
        try:
            txn.commit()
        except (TransactionConflict, TransactionAborted) as e:
            raise SerializationFailure(str(e)) from e
        return PgResult(command="COMMIT")

    def _yb_table(self, name: str):
        t = self._yb_tables.get(name)
        if t is None:
            t = self._yb_tables[name] = self.cluster.open_yb_table(name)
        return t

    def _read_ht(self, tablet) -> int:
        """The read point for scans: the txn snapshot inside a
        transaction, the tablet's safe time otherwise."""
        if self._txn is not None:
            return self._txn.read_ht
        return tablet.read_time().value

    # -- binding / coercion ------------------------------------------------
    def _resolve(self, value):
        if isinstance(value, ast.BindMarker):
            try:
                return self._params[value.index]
            except IndexError:
                raise InvalidArgument(
                    f"bind marker ${value.index + 1} has no value") from None
        if isinstance(value, ast.SeqFunc):
            return self._resolve_seq_func(value)
        return value

    def _coerce(self, col: ColumnSchema, value):
        from yugabyte_db_tpu.yql.common import coerce_value

        value = self._resolve(value)
        # PG-style input conversion: extended-protocol parameters arrive
        # as TEXT ('123'), and PG coerces string literals to the target
        # type; mirror that here (CQL stays strict in its own coercer).
        if isinstance(value, str):
            dt = col.dtype
            try:
                if dt.is_integer:
                    value = int(value)
                elif dt in (DataType.DOUBLE, DataType.FLOAT):
                    value = float(value)
                elif dt == DataType.BOOL:
                    low = value.lower()
                    if low in ("t", "true", "1", "on", "yes"):
                        value = True
                    elif low in ("f", "false", "0", "off", "no"):
                        value = False
                    else:
                        raise ValueError(value)
            except ValueError:
                raise InvalidArgument(
                    f"invalid input syntax for {dt.name}: {value!r}") \
                    from None
        return coerce_value(col, value)

    # -- DDL ---------------------------------------------------------------
    def _exec_create_table(self, stmt: ast.CreateTable):
        if stmt.name in self.cluster.tables:
            if stmt.if_not_exists:
                return None
            raise AlreadyPresent(f"relation {stmt.name} already exists")
        by_name = {c.name for c in stmt.columns}
        for k in stmt.hash_keys + stmt.range_keys:
            if k not in by_name:
                raise InvalidArgument(f"primary key column {k} not defined")
        cols = []
        for c in stmt.columns:
            if c.name in stmt.hash_keys:
                kind = ColumnKind.HASH
            elif c.name in stmt.range_keys:
                kind = ColumnKind.RANGE
            else:
                kind = ColumnKind.REGULAR
            if kind != ColumnKind.REGULAR and \
                    c.dtype in (DataType.FLOAT, DataType.DOUBLE):
                raise InvalidArgument(
                    f"floating-point column {c.name} cannot be a key")
            cols.append(ColumnSchema(c.name, c.dtype, kind,
                                     nullable=kind == ColumnKind.REGULAR))
        schema = Schema(cols, table_id=stmt.name)
        self.cluster.create_table(stmt.name, schema, stmt.num_tablets)
        self._yb_tables.pop(stmt.name, None)
        return PgResult(command="CREATE TABLE")

    def _exec_drop_table(self, stmt: ast.DropTable):
        from yugabyte_db_tpu.utils.status import NotFound

        try:
            self.cluster.drop_table(stmt.name)
        except NotFound:
            if not stmt.if_exists:
                raise
        self._yb_tables.pop(stmt.name, None)
        return PgResult(command="DROP TABLE")

    def _exec_alter_table(self, stmt: ast.AlterTable):
        """Schema evolution by stable column ids (ADD -> NULL for
        existing rows, DROP retires the id, RENAME touches no data)."""
        from yugabyte_db_tpu.yql.common import evolve_schema

        handle = self.cluster.table(stmt.name)
        self.cluster.alter_table(handle, evolve_schema(
            handle, stmt.action, stmt.column, stmt.dtype, stmt.new_name))
        self._yb_tables.pop(stmt.name, None)
        return PgResult(command="ALTER TABLE")

    def _exec_create_index(self, stmt: ast.CreateIndex):
        handle = self.cluster.table(stmt.table)
        if any(i["name"] == stmt.name
               for i in getattr(handle, "indexes", [])):
            if stmt.if_not_exists:
                return None
            raise AlreadyPresent(f"index {stmt.name} exists")
        if not handle.schema.has_column(stmt.column):
            raise InvalidArgument(f"unknown column {stmt.column}")
        if handle.schema.column(stmt.column).is_key:
            raise InvalidArgument(f"cannot index key column {stmt.column}")
        itable = self.cluster.create_index(handle, stmt.name, stmt.column)
        self._backfill_index(handle, stmt.column, itable)
        return PgResult(command="CREATE INDEX")

    def _backfill_index(self, handle, column: str, itable: str) -> None:
        """Populate the index from existing base rows (reference: the
        online index backfill job; here a scan + index-entry writes)."""
        from yugabyte_db_tpu.index import index_entry

        ih = self.cluster.table(itable)
        key_names = [c.name for c in handle.schema.key_columns]
        proj = key_names + [column]
        for tablet in handle.tablets:
            res = tablet.scan(ScanSpec(
                read_ht=tablet.read_time().value, projection=proj))
            for row in res.rows:
                value = row[-1]
                if value is None:
                    continue
                base_kv = dict(zip(key_names, row[:-1]))
                hc, rv = index_entry(ih.schema, value, base_kv)
                self.cluster.tablet_for_hash(ih, hc).write([rv])

    def _exec_drop_index(self, stmt: ast.DropIndex):
        from yugabyte_db_tpu.utils.status import NotFound

        for name in list(self.cluster.tables):
            try:
                handle = self.cluster.table(name)
            except NotFound:
                continue
            for idx in getattr(handle, "indexes", []):
                if idx["name"] == stmt.name:
                    self.cluster.drop_index(handle, stmt.name)
                    return PgResult(command="DROP INDEX")
        if not stmt.if_exists:
            raise NotFound(f"index {stmt.name} not found")
        return PgResult(command="DROP INDEX")

    # -- DML ---------------------------------------------------------------
    def _key_and_tablet(self, handle, key_values: dict):
        from yugabyte_db_tpu.yql.common import key_and_tablet

        return key_and_tablet(self.cluster, handle, key_values)

    def _write_row(self, handle, key_values: dict, key: bytes, tablet,
                   row: RowVersion, if_not_exists: bool = False) -> None:
        if getattr(handle, "indexes", None) and \
                getattr(self.cluster, "maintain_indexes", None):
            from yugabyte_db_tpu.index import normalize_index

            indexed_cids = set()
            for i in handle.indexes:
                ni = normalize_index(i)
                for cname in ni["columns"] + ni["include"]:
                    indexed_cids.add(handle.schema.column(cname).col_id)
            if row.tombstone or (indexed_cids & row.columns.keys()):
                # Conditional INSERT: the row must not exist, so the old
                # state is absent by contract — no tombstones. A later
                # duplicate rejection then leaves at most a stale extra
                # entry (base-verified away), never a removed one.
                old = (None if if_not_exists
                       else tablet.current_row_values(key))
                self.cluster.maintain_indexes(handle, key_values, old, row)
        tablet.write([row], if_not_exists=if_not_exists)

    def _exec_insert(self, stmt: ast.Insert):
        handle = self.cluster.table(stmt.table)
        schema = handle.schema
        for cname in stmt.columns:
            if not schema.has_column(cname):
                raise InvalidArgument(f"unknown column {cname}")
        n = 0
        for values in stmt.rows:
            provided = dict(zip(stmt.columns, values))
            key_values, columns = {}, {}
            for c in schema.key_columns:
                v = (self._coerce(c, provided[c.name])
                     if c.name in provided else None)
                if v is None:  # checked AFTER bind resolution: $N may be None
                    raise InvalidArgument(
                        f"null value in column {c.name} violates "
                        f"not-null constraint")
                key_values[c.name] = v
            for c in schema.value_columns:
                if c.name in provided:
                    columns[c.col_id] = self._coerce(c, provided[c.name])
            if self._txn is not None:
                # Uniqueness inside a txn: read-your-writes existence
                # check; overlapping inserts from OTHER txns resolve at
                # the intent level (first-committer-wins).
                yt = self._yb_table(stmt.table)
                if self._txn.get(yt, key_values) is not None:
                    raise AlreadyPresent(
                        "duplicate key value violates unique constraint")
                vals = dict(key_values)
                vals.update({c.name: columns[c.col_id]
                             for c in schema.value_columns
                             if c.col_id in columns})
                self._txn.insert(yt, vals)
                n += 1
                continue
            key, tablet = self._key_and_tablet(handle, key_values)
            # PG semantics: duplicate key is an error (23505), not an
            # upsert. The check is ATOMIC with the write — it runs on the
            # tablet under the same lock as the apply (Tablet.write
            # if_not_exists / the tserver's intent-admission lock).
            self._write_row(handle, key_values, key, tablet, RowVersion(
                key, ht=0, liveness=True, columns=columns),
                if_not_exists=True)
            n += 1
        return PgResult(command=f"INSERT 0 {n}")

    def _match_rows(self, handle, where: list[ast.Rel]):
        """Resolve WHERE to (key_values, row-dict) pairs. Full-PK equality
        short-circuits to a point read; anything else scans with
        predicate pushdown."""
        schema = handle.schema
        where, ok = self._fold_exists(where)
        if not ok:
            return []
        key_names = [c.name for c in schema.key_columns]
        eq = {r.column: r.value for r in where if r.op == "="}
        if set(key_names) <= set(eq) and len(where) == len(key_names):
            kv = {n: self._coerce(schema.column(n), eq[n])
                  for n in key_names}
            if self._txn is not None:
                got = self._txn_point_get(handle, kv)
                return [] if got is None else [got]
            key, tablet = self._key_and_tablet(handle, kv)
            res = tablet.scan(ScanSpec(
                lower=key, upper=key + b"\x00",
                read_ht=self._read_ht(tablet), projection=None))
            return [(kv, dict(zip(res.columns, r))) for r in res.rows]
        preds = self._predicates(schema, where)
        out = []
        for tablet, lower, upper in self._hash_section(handle, eq):
            res = tablet.scan(ScanSpec(
                lower=lower, upper=upper,
                read_ht=self._read_ht(tablet), predicates=preds))
            for r in res.rows:
                d = dict(zip(res.columns, r))
                out.append(({n: d[n] for n in key_names}, d))
        if self._txn is not None:
            out = self._overlay_own_writes(handle, preds, out)
        return out

    def _hash_section(self, handle, eq: dict):
        """(tablet, lower, upper) to scan for a WHERE whose equalities
        are ``eq``: where every hash column is bound (``DELETE ... WHERE
        l_orderkey = k``: TPC-H's RF2), the one tablet that owns the
        hash code and the key range of that hash section; else every
        tablet, unbounded."""
        from yugabyte_db_tpu.models.encoding import (encode_doc_key_prefix,
                                                     prefix_successor)
        from yugabyte_db_tpu.models.partition import compute_hash_code

        schema = handle.schema
        hash_cols = schema.hash_columns
        if not hash_cols or any(
                c.name not in eq or isinstance(eq[c.name], ast.SubQuery)
                for c in hash_cols):
            return [(t, b"", b"") for t in handle.tablets]
        kv = {c.name: self._coerce(c, eq[c.name]) for c in hash_cols}
        hc = compute_hash_code(schema, kv)
        prefix = encode_doc_key_prefix(
            hc, [(kv[c.name], c.dtype) for c in hash_cols], [])
        return [(self.cluster.tablet_for_hash(handle, hc), prefix,
                 prefix_successor(prefix))]

    def _txn_point_get(self, handle, kv):
        """Point resolution inside a txn: read-your-writes (own buffered
        and flushed intents overlay the committed snapshot). Returns
        (kv, row-dict) or None."""
        row = self._txn.get(self._yb_table(handle.name), kv)
        if row is None:
            return None
        names = [c.name for c in handle.schema.columns]
        return (kv, dict(zip(names, row)))

    def _overlay_own_writes(self, handle, preds, snapshot_rows):
        """Statements inside a transaction must see earlier statements'
        effects: merge the txn's own buffered writes over the snapshot
        match set (replace matched rows, drop tombstoned ones, add newly
        inserted ones that match the predicates)."""
        from yugabyte_db_tpu.models.encoding import decode_doc_key
        from yugabyte_db_tpu.models.partition import compute_hash_code

        schema = handle.schema
        key_names = [c.name for c in schema.key_columns]
        own = self._txn.own_rows(self._yb_table(handle.name))
        if not own:
            return snapshot_rows
        by_id = {c.col_id: c.name for c in schema.value_columns}
        out = []
        seen = set()
        for kv, d in snapshot_rows:
            key = schema.encode_primary_key(
                kv, compute_hash_code(schema, kv))
            row = own.get(key)
            if row is None:
                out.append((kv, d))
                continue
            seen.add(key)
            if row.tombstone:
                continue
            merged = dict(d)
            for cid, v in row.columns.items():
                if cid in by_id:
                    merged[by_id[cid]] = v
            if all(p.matches(merged.get(p.column)) for p in preds):
                out.append((kv, merged))
        for key, row in own.items():
            if key in seen or row.tombstone:
                continue
            _, hashed, ranges = decode_doc_key(key)
            kv = dict(zip(key_names, hashed + ranges))
            # full state (committed base + own overlay) via the point
            # get — the snapshot row may exist but have been excluded by
            # the pre-overlay predicate values, and building from only
            # the buffered columns would invent NULLs
            got = self._txn_point_get(handle, kv)
            if got is None:
                continue
            d = got[1]
            if all(p.matches(d.get(p.column)) for p in preds):
                out.append((kv, d))
        return out

    def _resolve_subquery(self, rel: ast.Rel) -> ast.Rel:
        """Execute an uncorrelated subquery used as a WHERE value.
        Scalar NULL / empty results lower to the never-matching IN ()
        (PG: comparison with NULL selects no rows, not an error)."""
        res = self._exec_select(rel.value.select)
        if len(res.columns) != 1:
            raise InvalidArgument("subquery must return a single column")
        if rel.op == "IN":
            # NULL elements can never satisfy '=' — drop them.
            vals = tuple(r[0] for r in res.rows if r[0] is not None)
            return ast.Rel(rel.column, "IN", vals)
        if len(res.rows) > 1:
            raise InvalidArgument(
                "more than one row returned by a subquery used as "
                "an expression")
        v = res.rows[0][0] if res.rows else None
        if v is None:
            return ast.Rel(rel.column, "IN", ())
        return ast.Rel(rel.column, rel.op, v)

    def _resolved_where(self, where: list[ast.Rel]) -> list[ast.Rel]:
        return [self._resolve_subquery(r)
                if isinstance(r.value, ast.SubQuery)
                and r.op not in ("EXISTS", "NOT EXISTS") else r
                for r in where]

    def _fold_exists(self, where: list[ast.Rel]):
        """Evaluate uncorrelated [NOT] EXISTS conjuncts once; returns
        (remaining_rels, ok) — ok False means no row can match. Used by
        paths without per-row subplan support (aggregates, UPDATE /
        DELETE); the row-select path runs EXISTS per row instead."""
        out, ok = [], True
        for rel in where:
            if rel.op in ("EXISTS", "NOT EXISTS"):
                try:
                    res = self._exec_query(rel.value.select)
                except InvalidArgument as e:
                    # Only an unresolvable outer-column reference means
                    # the subquery is correlated; a typo'd table or
                    # column inside the subquery must surface as-is.
                    msg = str(e)
                    if ("cannot be used as a comparison value" in msg
                            or "unknown table alias" in msg):
                        raise InvalidArgument(
                            "correlated [NOT] EXISTS is supported only "
                            "in a single-table SELECT WHERE clause "
                            f"({e})") from e
                    raise
                if bool(res.rows) != (rel.op == "EXISTS"):
                    ok = False
                continue
            out.append(rel)
        return out, ok

    def _predicates(self, schema: Schema, where: list[ast.Rel]):
        preds = []
        for rel in where:
            if rel.op in ("EXISTS", "NOT EXISTS"):
                raise InvalidArgument(
                    "EXISTS is not supported in this clause")
            if isinstance(rel.value, ast.SubQuery):
                rel = self._resolve_subquery(rel)
            if isinstance(rel.value, X.Col):
                raise InvalidArgument(
                    f"column reference {rel.value.name} cannot be used "
                    f"as a comparison value in this clause")
            if not schema.has_column(rel.column):
                raise InvalidArgument(f"unknown column {rel.column}")
            col = schema.column(rel.column)
            if rel.op == "IN":
                vals = tuple(self._coerce(col, v)
                             for v in self._resolve(rel.value))
                preds.append(Predicate(rel.column, "IN", vals))
            else:
                preds.append(Predicate(rel.column, rel.op,
                                       self._coerce(col, rel.value)))
        return preds

    def _exec_update(self, stmt: ast.Update):
        handle = self.cluster.table(stmt.table)
        schema = handle.schema
        sets = []
        for cname, rhs in stmt.assignments:
            if not schema.has_column(cname):
                raise InvalidArgument(f"unknown column {cname}")
            col = schema.column(cname)
            if col.is_key:
                raise InvalidArgument(f"cannot SET key column {cname}")
            sets.append((col, rhs))
        n = 0
        for kv, old in self._match_rows(handle, stmt.where):
            set_values = {}
            for col, rhs in sets:
                if isinstance(rhs, (X.Col, X.Const, X.BinOp)):
                    v = X.eval_expr(rhs, lambda name: old.get(name))
                    if col.dtype in (DataType.DOUBLE, DataType.FLOAT) \
                            and isinstance(v, int):
                        v = float(v)
                    set_values[col.name] = v
                else:
                    set_values[col.name] = self._coerce(col, rhs)
            if self._txn is not None:
                self._txn.update(self._yb_table(stmt.table), kv,
                                 set_values)
                n += 1
                continue
            columns = {handle.schema.column(nm).col_id: v
                       for nm, v in set_values.items()}
            key, tablet = self._key_and_tablet(handle, kv)
            self._write_row(handle, kv, key, tablet,
                            RowVersion(key, ht=0, columns=columns))
            n += 1
        return PgResult(command=f"UPDATE {n}")

    def _exec_delete(self, stmt: ast.Delete):
        handle = self.cluster.table(stmt.table)
        n = 0
        for kv, _old in self._match_rows(handle, stmt.where):
            if self._txn is not None:
                self._txn.delete_row(self._yb_table(stmt.table), kv)
                n += 1
                continue
            key, tablet = self._key_and_tablet(handle, kv)
            self._write_row(handle, kv, key, tablet,
                            RowVersion(key, ht=0, tombstone=True))
            n += 1
        return PgResult(command=f"DELETE {n}")

    # -- SELECT ------------------------------------------------------------
    # -- views / sequences --------------------------------------------------
    def _exec_create_view(self, stmt):
        from yugabyte_db_tpu.utils.status import AlreadyPresent

        try:
            self.cluster.create_view(stmt.name, stmt.query_sql,
                                     stmt.replace)
        except AlreadyPresent:
            raise InvalidArgument(f"view {stmt.name} exists") from None
        return PgResult(command="CREATE VIEW")

    def _exec_drop_view(self, stmt):
        from yugabyte_db_tpu.utils.status import NotFound

        try:
            self.cluster.drop_view(stmt.name)
        except NotFound:
            if not stmt.if_exists:
                raise InvalidArgument(
                    f"view {stmt.name} does not exist") from None
        return PgResult(command="DROP VIEW")

    def _exec_create_sequence(self, stmt):
        from yugabyte_db_tpu.utils.status import AlreadyPresent

        try:
            self.cluster.create_sequence(stmt.name)
        except AlreadyPresent:
            if not stmt.if_not_exists:
                raise InvalidArgument(
                    f"sequence {stmt.name} exists") from None
        return PgResult(command="CREATE SEQUENCE")

    def _exec_drop_sequence(self, stmt):
        from yugabyte_db_tpu.utils.status import NotFound

        try:
            self.cluster.drop_sequence(stmt.name)
        except NotFound:
            if not stmt.if_exists:
                raise InvalidArgument(
                    f"sequence {stmt.name} does not exist") from None
        return PgResult(command="DROP SEQUENCE")

    def _resolve_seq_func(self, f):
        if f.kind == "nextval":
            from yugabyte_db_tpu.utils.status import NotFound

            try:
                v = self.cluster.sequence_next(f.sequence)
            except NotFound:
                raise InvalidArgument(
                    f"sequence {f.sequence} does not exist") from None
            self._currvals[f.sequence] = v
            return v
        v = self._currvals.get(f.sequence)
        if v is None:
            raise InvalidArgument(
                f"currval of sequence {f.sequence} is not yet defined "
                "in this session")
        return v

    def _view_sql(self, name: str):
        """The defining query if ``name`` is a view. Local registries
        answer from memory; the distributed seam is consulted only when
        the name is not a known TABLE (so the read hot path never pays
        a master round trip for plain tables)."""
        if not hasattr(self.cluster, "get_view"):
            return None
        views = getattr(self.cluster, "views", None)
        if views is not None:  # in-process registry: free lookup
            return views.get(name)
        if name in self._yb_tables:
            return None
        try:
            self._yb_table(name)
            return None        # a real table
        except Exception:      # noqa: BLE001 — unknown name: try views
            return self.cluster.get_view(name)

    def _select_from_view(self, stmt: ast.Select, view_sql: str):
        """A SELECT whose FROM names a view: run the stored defining
        query, then evaluate the outer query over its rows in memory
        (views inside JOINs are not supported yet)."""
        if stmt.joins:
            raise InvalidArgument("views cannot be joined yet")
        self._view_depth = getattr(self, "_view_depth", 0) + 1
        try:
            if self._view_depth > 8:
                raise InvalidArgument(
                    "view nesting too deep (cyclic definition?)")
            inner = self._exec_query(parse_statement(view_sql))
        finally:
            self._view_depth -= 1
        return self._select_over_rows(stmt, inner.columns, inner.rows)

    def _select_over_rows(self, stmt: ast.Select, columns: list[str],
                          in_rows: list[tuple]) -> PgResult:
        """Evaluate a SELECT over an in-memory relation (view result or
        CTE): WHERE (incl. subquery values), expression/function items,
        aggregates + GROUP BY + HAVING, DISTINCT, ORDER BY,
        LIMIT/OFFSET — the executor work stock PG runs over a
        tuplestore scan (nodeCtescan.c / nodeSubqueryscan.c)."""
        prefix = (stmt.alias + ".") if stmt.alias else None
        dicts = []
        for r in in_rows:
            d = dict(zip(columns, r))
            if prefix:
                for c, v in zip(columns, r):
                    d[prefix + c] = v
            dicts.append(d)
        known = set(columns) | ({prefix + c for c in columns}
                                if prefix else set())
        for rel in self._resolved_where(stmt.where):
            if rel.op in ("EXISTS", "NOT EXISTS"):
                # Uncorrelated over an in-memory relation: one execution
                # decides the whole conjunct.
                res = self._exec_query(rel.value.select)
                if bool(res.rows) != (rel.op == "EXISTS"):
                    dicts = []
                continue
            if rel.column not in known:
                raise InvalidArgument(
                    f"column {rel.column} is not in the relation")
            val = self._resolve(rel.value)
            if isinstance(val, X.Col):
                if val.name not in known:
                    raise InvalidArgument(
                        f"column {val.name} is not in the relation")
                op = rel.op
                dicts = [d for d in dicts
                         if self._cmp(op, d.get(rel.column),
                                      d.get(val.name))]
                continue
            p = Predicate(rel.column, rel.op,
                          tuple(val) if rel.op == "IN" else val)
            dicts = [d for d in dicts if p.matches(d.get(p.column))]
        names, exprs = [], []
        for it in stmt.items:
            if it.expr == "*":
                names.extend(columns)
                exprs.extend(X.Col(c) for c in columns)
                continue
            if isinstance(it.expr, ast.Agg):
                arg = it.expr.arg
                names.append(it.alias or
                             f"{it.expr.fn}({'*' if arg is None else '...'})")
            elif isinstance(it.expr, X.Col):
                names.append(it.alias or it.expr.name.split(".")[-1])
            else:
                names.append(it.alias or "?column?")
            exprs.append(it.expr)
        for e in exprs:
            for c in self._item_columns(e):
                if c not in known:
                    raise InvalidArgument(
                        f"column {c} is not in the relation")
        has_agg = (stmt.group_by
                   or any(isinstance(e, ast.Agg) for e in exprs)
                   or any(isinstance(h.expr, ast.Agg)
                          for h in stmt.having))
        limit = self._limit(stmt)
        if has_agg:
            rows = self._host_aggregate(stmt, dicts, exprs)
            if stmt.distinct:
                rows = list(dict.fromkeys(rows))
            rows = self._order_and_limit(stmt, names, rows, limit)
            return PgResult(columns=names, rows=rows)
        hidden = 0
        for ob in stmt.order_by:
            if ob.column not in names and ob.column in known:
                names.append(ob.column)
                exprs.append(X.Col(ob.column))
                hidden += 1
        rows = [tuple(self._eval_item(e, d) for e in exprs)
                for d in dicts]
        return self._dedup_order_trim(stmt, names, rows, limit, hidden)

    def _select_window(self, stmt: ast.Select) -> PgResult:
        """SELECT with window-function items. Rewrite as a two-stage
        plan: fetch the full relation (base table / view / CTE / join —
        the inner SELECT reuses every existing path), then evaluate
        windows host-side and project — the split stock PG's planner
        makes between the scan below and WindowAgg above the FDW
        (reference capability:
        src/postgres/src/backend/executor/nodeWindowAgg.c)."""
        import dataclasses as _dc

        if (stmt.group_by or stmt.having
                or any(isinstance(it.expr, ast.Agg) for it in stmt.items)):
            raise InvalidArgument(
                "window functions cannot be combined with GROUP BY or "
                "plain aggregates")
        if stmt.table is None:
            # FROM-less window (PG: SELECT row_number() OVER () -> 1):
            # the relation is one empty row.
            dicts, star, known = [{}], [], set()
        elif stmt.joins:
            dicts, tables, handles, _q, owners = self._join_rows(stmt)
            star = [f"{a}.{c.name}" for a, _t in tables
                    for c in handles[a].schema.columns]
            known = set(star) | {n for n, als in owners.items()
                                 if len(als) == 1}
        else:
            stmt = self._strip_qualifiers(stmt)
            inner = _dc.replace(stmt, items=[ast.SelectItem("*")],
                                order_by=[], limit=None, offset=None,
                                distinct=False)
            base = self._exec_select(inner)
            star = list(base.columns)
            dicts = [dict(zip(star, r)) for r in base.rows]
            known = set(star)
        for it in stmt.items:
            if it.expr == "*":
                continue
            for c in self._item_columns(it.expr):
                if c not in known:
                    raise InvalidArgument(
                        f"column {c} is not in the relation")
        names: list[str] = []
        series: list[list] = []
        for it in stmt.items:
            e = it.expr
            if e == "*":
                for c in star:
                    names.append(c.split(".")[-1])
                    series.append([d.get(c) for d in dicts])
                continue
            if isinstance(e, ast.WindowFunc):
                names.append(it.alias or e.fn)
                series.append(self._eval_window(e, dicts))
            else:
                if isinstance(e, X.Col):
                    names.append(it.alias or e.name.split(".")[-1])
                else:
                    names.append(it.alias or "?column?")
                series.append([self._eval_item(e, d) for d in dicts])
        # Hidden ORDER BY columns (may reference non-projected columns;
        # PG allows this for non-DISTINCT selects).
        hidden = 0
        for ob in stmt.order_by:
            if ob.column not in names and ob.column in known:
                names.append(ob.column)
                series.append([d.get(ob.column) for d in dicts])
                hidden += 1
        rows = [tuple(s[i] for s in series) for i in range(len(dicts))]
        return self._dedup_order_trim(stmt, names, rows,
                                      self._limit(stmt), hidden)

    def _eval_window(self, wf: ast.WindowFunc, dicts: list[dict]) -> list:
        """One window function over the relation: returns a value per
        input row (input order preserved by the caller). Aggregate
        windows with ORDER BY use PG's default frame — RANGE UNBOUNDED
        PRECEDING .. CURRENT ROW — so order-key peers share the running
        value; without ORDER BY the frame is the whole partition."""
        for c in wf.partition_by + [ob.column for ob in wf.order_by]:
            if dicts and c not in dicts[0]:
                raise InvalidArgument(
                    f"column {c} is not in the relation")
        off = self._resolve(wf.offset)
        default = self._resolve(wf.default)
        if wf.fn in ("lag", "lead") and (not isinstance(off, int)
                                         or isinstance(off, bool)
                                         or off < 0):
            raise InvalidArgument(f"{wf.fn} offset must be a "
                                  "non-negative integer")
        parts: dict[tuple, list[int]] = {}
        for i, d in enumerate(dicts):
            parts.setdefault(tuple(d.get(c) for c in wf.partition_by),
                             []).append(i)
        out: list = [None] * len(dicts)
        for order in parts.values():
            order = list(order)  # stable within equal order keys
            for ob in reversed(wf.order_by):
                order.sort(key=lambda i, c=ob.column:
                           ((dicts[i].get(c) is None), dicts[i].get(c)),
                           reverse=ob.desc)
            okeys = [tuple(dicts[i].get(ob.column) for ob in wf.order_by)
                     for i in order]
            fn = wf.fn
            if fn == "row_number":
                for pos, i in enumerate(order):
                    out[i] = pos + 1
            elif fn in ("rank", "dense_rank"):
                rank = dense = 0
                prev: object = object()
                for pos, i in enumerate(order):
                    if okeys[pos] != prev:
                        rank, prev = pos + 1, okeys[pos]
                        dense += 1
                    out[i] = rank if fn == "rank" else dense
            elif fn in ("lag", "lead"):
                vals = [self._eval_item(wf.arg, dicts[i]) for i in order]
                step = off if fn == "lag" else -off
                for pos, i in enumerate(order):
                    j = pos - step
                    out[i] = (vals[j] if 0 <= j < len(vals)
                              else default)
            else:  # sum/count/avg/min/max over the frame
                star = wf.arg is None
                args = ([None] * len(order) if star else
                        [self._eval_item(wf.arg, dicts[i])
                         for i in order])
                if not wf.order_by:
                    val = self._win_agg(fn, args, len(order), star)
                    for i in order:
                        out[i] = val
                else:
                    # Incremental accumulator: carry count/sum/min/max
                    # across peer-group boundaries (the frame only ever
                    # grows), O(n) per partition.
                    n_seen = cnt = 0
                    total = lo = hi = None
                    pos = 0
                    while pos < len(order):
                        end = pos
                        while end < len(order) and okeys[end] == okeys[pos]:
                            end += 1
                        n_seen = end
                        for v in args[pos:end]:
                            if v is None:
                                continue
                            cnt += 1
                            total = v if total is None else total + v
                            lo = v if lo is None or v < lo else lo
                            hi = v if hi is None or v > hi else hi
                        if fn == "count":
                            val = n_seen if star else cnt
                        elif cnt == 0:
                            val = None
                        elif fn == "sum":
                            val = total
                        elif fn == "avg":
                            val = total / cnt
                        elif fn == "min":
                            val = lo
                        elif fn == "max":
                            val = hi
                        else:
                            raise InvalidArgument(
                                f"unknown window aggregate {fn}")
                        for p in range(pos, end):
                            out[order[p]] = val
                        pos = end
        return out

    @staticmethod
    def _win_agg(fn: str, args: list, n_rows: int, star: bool):
        if fn == "count":
            return n_rows if star else sum(v is not None for v in args)
        vals = [v for v in args if v is not None]
        if not vals:
            return None
        if fn == "sum":
            return sum(vals)
        if fn == "avg":
            return sum(vals) / len(vals)
        if fn == "min":
            return min(vals)
        if fn == "max":
            return max(vals)
        raise InvalidArgument(f"unknown window aggregate {fn}")

    def _exec_query(self, stmt):
        """Dispatch a query statement (SELECT or UNION chain), handling
        a WITH clause once for both kinds: evaluate each CTE in order
        (PG materializes CTEs; later CTEs and the body see earlier
        names), scoped to this statement and restored after."""
        if getattr(stmt, "ctes", None):
            saved = dict(getattr(self, "_cte_results", {}) or {})
            self._cte_results = dict(saved)
            try:
                for name, sel in stmt.ctes:
                    self._cte_results[name] = self._exec_query(sel)
                import dataclasses as _dc

                return self._exec_query(_dc.replace(stmt, ctes=[]))
            finally:
                self._cte_results = saved
        if isinstance(stmt, ast.Union):
            return self._exec_union(stmt)
        return self._exec_select(stmt)

    def _exec_union(self, u: ast.Union) -> PgResult:
        """Set operations: evaluate each branch, require equal arity,
        combine per joint — UNION (dedup unless ALL), EXCEPT (dedup lhs
        minus rhs; ALL subtracts per-occurrence), INTERSECT (dedup
        both-sides; ALL keeps multiset minimum counts) — then apply the
        chain-level ORDER BY/LIMIT/OFFSET (the work stock PG's
        Append/SetOp nodes do above the FDW; reference capability:
        src/postgres/src/backend/executor/nodeSetOp.c)."""
        from collections import Counter

        results = [self._exec_query(b) for b in u.branches]
        n = len(results[0].columns)
        for r in results[1:]:
            if len(r.columns) != n:
                raise InvalidArgument(
                    "each query in a set operation must have the same "
                    "number of columns")
        kinds = u.kinds or ["union"] * len(u.alls)

        def hkey(v):
            # Canonical hashable view of a cell (jsonb rows carry
            # dicts/lists; PG supports them in set operations).
            if isinstance(v, dict):
                return ("\x00d", tuple(sorted(
                    (k, hkey(x)) for k, x in v.items())))
            if isinstance(v, (list, tuple)):
                return ("\x00l", tuple(hkey(x) for x in v))
            if isinstance(v, set):
                return ("\x00s", tuple(sorted(map(hkey, v),
                                              key=repr)))
            return v

        def rkey(row):
            return tuple(hkey(v) for v in row)

        def dedup(rows):
            seen = {}
            for t in rows:
                seen.setdefault(rkey(t), t)
            return list(seen.values())

        acc = list(results[0].rows)
        for r, is_all, kind in zip(results[1:], u.alls, kinds):
            rows = list(r.rows)
            if kind == "union":
                acc = ([*acc, *rows] if is_all
                       else dedup([*acc, *rows]))
            elif kind == "except":
                if is_all:
                    remove = Counter(map(rkey, rows))
                    out = []
                    for t in acc:
                        k = rkey(t)
                        if remove[k] > 0:
                            remove[k] -= 1
                        else:
                            out.append(t)
                    acc = out
                else:
                    right = set(map(rkey, rows))
                    acc = [t for t in dedup(acc)
                           if rkey(t) not in right]
            else:  # intersect
                if is_all:
                    counts = Counter(map(rkey, rows))
                    out = []
                    for t in acc:
                        k = rkey(t)
                        if counts[k] > 0:
                            counts[k] -= 1
                            out.append(t)
                    acc = out
                else:
                    right = set(map(rkey, rows))
                    acc = [t for t in dedup(acc) if rkey(t) in right]
        names = list(results[0].columns)
        shim = ast.Select(items=[], table=None, order_by=u.order_by,
                          limit=u.limit, offset=u.offset)
        rows = self._order_and_limit(shim, names, acc,
                                     self._limit(shim))
        return PgResult(columns=names, rows=rows)

    def _exec_select(self, stmt: ast.Select):
        if getattr(stmt, "ctes", None):
            # WITH rides the shared query dispatcher (CTE handling for
            # SELECT and UNION lives in one place).
            return self._exec_query(stmt)
        if any(isinstance(it.expr, ast.WindowFunc) for it in stmt.items):
            return self._select_window(stmt)
        cte = (getattr(self, "_cte_results", None) or {}).get(stmt.table)
        if cte is not None:
            if stmt.joins:
                raise InvalidArgument("CTEs cannot be joined yet")
            return self._select_over_rows(stmt, cte.columns, cte.rows)
        if stmt.table is None:
            # FROM-less SELECT: constant / sequence-function items.
            names, row = [], []
            from yugabyte_db_tpu.storage import expr as X

            for i, it in enumerate(stmt.items):
                e = it.expr
                if isinstance(e, ast.SeqFunc):
                    names.append(it.alias or e.kind)
                    row.append(self._resolve_seq_func(e))
                elif isinstance(e, X.Const):
                    names.append(it.alias or f"?column?")
                    row.append(e.value)
                else:
                    raise InvalidArgument(
                        "FROM-less SELECT supports constants and "
                        "sequence functions")
            return PgResult(columns=names, rows=[tuple(row)],
                            command="SELECT 1")
        view_sql = self._view_sql(stmt.table)
        if view_sql is not None:
            return self._select_from_view(stmt, view_sql)
        if not stmt.joins:
            from yugabyte_db_tpu.yql.pgsql import vtables as PV

            if PV.is_virtual(stmt.table):
                return PV.virtual_select(self, stmt)
        if stmt.joins:
            return self._select_join(stmt)
        stmt = self._strip_qualifiers(stmt)
        handle = self.cluster.table(stmt.table)
        schema = handle.schema
        has_agg = (any(isinstance(it.expr, ast.Agg) for it in stmt.items)
                   or any(isinstance(h.expr, ast.Agg) for h in stmt.having))
        if has_agg or stmt.group_by:
            return self._select_aggregate(handle, stmt)
        return self._select_rows(handle, stmt)

    def _strip_qualifiers(self, stmt: ast.Select) -> ast.Select:
        """Single-table SELECT: rewrite 'alias.col' refs to bare names
        (the storage seam knows bare columns only)."""
        alias = stmt.alias or stmt.table
        prefix = alias + "."

        def fix(name: str) -> str:
            if isinstance(name, str) and name.startswith(prefix):
                return name[len(prefix):]
            if isinstance(name, str) and "." in name:
                raise InvalidArgument(
                    f"unknown table alias in reference {name}")
            return name

        def fix_expr(e):
            if isinstance(e, X.Col):
                return X.Col(fix(e.name)) if "." in e.name else e
            if isinstance(e, X.BinOp):
                return X.BinOp(e.op, fix_expr(e.left), fix_expr(e.right))
            if isinstance(e, ast.JsonPath):
                return ast.JsonPath(fix(e.column), e.steps)
            if isinstance(e, ast.Agg):
                return ast.Agg(e.fn, None if e.arg is None
                               else fix_expr(e.arg))
            if isinstance(e, ast.WindowFunc):
                return ast.WindowFunc(
                    e.fn, None if e.arg is None else fix_expr(e.arg),
                    [fix(c) for c in e.partition_by],
                    [ast.OrderBy(fix(o.column), o.desc)
                     for o in e.order_by],
                    offset=e.offset, default=e.default)
            return e

        needs = (any(r.column and "." in r.column for r in stmt.where)
                 or any(isinstance(r.value, X.Col) and "." in r.value.name
                        for r in stmt.where)
                 or any("." in g for g in stmt.group_by)
                 or any("." in o.column for o in stmt.order_by))
        items = [ast.SelectItem(fix_expr(it.expr)
                                if it.expr != "*" else "*", it.alias)
                 for it in stmt.items]
        having = [ast.HavingRel(fix_expr(h.expr), h.op, h.value)
                  for h in stmt.having]
        if not needs and items == stmt.items and having == stmt.having:
            return stmt
        return ast.Select(
            items, stmt.table,
            [ast.Rel(fix(r.column), r.op,
                     X.Col(fix(r.value.name))
                     if isinstance(r.value, X.Col) else r.value)
             for r in stmt.where],
            [fix(g) for g in stmt.group_by],
            [ast.OrderBy(fix(o.column), o.desc) for o in stmt.order_by],
            stmt.limit, stmt.distinct, stmt.alias, [], having,
            offset=stmt.offset)

    # -- joins (above the storage seam; reference capability: the PG
    # executor's hash/merge joins over FDW scans, src/postgres executor) --
    def _select_join(self, stmt: ast.Select):
        joined, tables, handles, qualify, _owners = self._join_rows(stmt)
        return self._finish_select(stmt, joined, tables, handles, qualify)

    def _join_rows(self, stmt: ast.Select):
        """Produce the joined relation as dicts keyed by both qualified
        ('a.col') and unambiguous bare names. Returns (dicts, tables,
        handles, qualify, owners) for _finish_select / window
        evaluation; owners maps bare column name -> owning aliases (the
        single source of the bare-name-resolution rule)."""
        where_rels, exists_ok = self._fold_exists(stmt.where)
        if len(where_rels) != len(stmt.where):
            import dataclasses as _dc

            stmt = _dc.replace(stmt, where=where_rels)
        base_alias = stmt.alias or stmt.table
        tables = [(base_alias, stmt.table)]
        tables += [(j.alias or j.table, j.table) for j in stmt.joins]
        if len({a for a, _ in tables}) != len(tables):
            raise InvalidArgument("duplicate table alias in FROM")
        handles = {a: self.cluster.table(t) for a, t in tables}
        owners: dict[str, list[str]] = {}
        for a, _t in tables:
            for c in handles[a].schema.columns:
                owners.setdefault(c.name, []).append(a)

        def qualify(ref: str) -> tuple[str, str]:
            if "." in ref:
                a, c = ref.split(".", 1)
                if a not in handles:
                    raise InvalidArgument(f"unknown table alias {a}")
                if not handles[a].schema.has_column(c):
                    raise InvalidArgument(f"unknown column {ref}")
                return a, c
            als = owners.get(ref)
            if not als:
                raise InvalidArgument(f"unknown column {ref}")
            if len(als) > 1:
                raise InvalidArgument(
                    f"column reference {ref} is ambiguous")
            return als[0], ref

        # Resolve subqueries once; split WHERE into per-table pushdowns.
        where = self._resolved_where(stmt.where)
        per: dict[str, list[ast.Rel]] = {a: [] for a, _ in tables}
        for rel in where:
            a, c = qualify(rel.column)
            per[a].append(ast.Rel(c, rel.op, rel.value))

        rows_by_alias: dict[str, list[dict]] = {}
        for a, _tname in tables:
            h = handles[a]
            preds = self._predicates(h.schema, per[a])
            rows_by_alias[a] = [
                {f"{a}.{k}": v for k, v in d.items()}
                for d in self._scan_dicts(h, per[a], preds, None, None)]

        joined = rows_by_alias[base_alias]
        seen_aliases = {base_alias}
        for j, (a, _tname) in zip(stmt.joins, tables[1:]):
            lkeys, rkeys = [], []
            for lref, rref in j.on:
                la, lc = qualify(lref)
                ra, rc = qualify(rref)
                if ra != a:  # written right-to-left: flip
                    la, lc, ra, rc = ra, rc, la, lc
                if ra != a or la not in seen_aliases:
                    raise InvalidArgument(
                        f"ON must relate {a} to an earlier table")
                lkeys.append(f"{la}.{lc}")
                rkeys.append(f"{a}.{rc}")
            index: dict[tuple, list[dict]] = {}
            for d in rows_by_alias[a]:
                kt = tuple(d[k] for k in rkeys)
                if any(v is None for v in kt):
                    continue  # SQL: NULL never joins
                index.setdefault(kt, []).append(d)
            null_right = {f"{a}.{c.name}": None
                          for c in handles[a].schema.columns}
            null_left = {f"{la}.{c.name}": None
                         for la in seen_aliases
                         for c in handles[la].schema.columns}
            out = []
            matched_right: set[int] = set()
            for ld in joined:
                kt = tuple(ld[k] for k in lkeys)
                matches = (index.get(kt)
                           if not any(v is None for v in kt) else None)
                if matches:
                    for rd in matches:
                        m = dict(ld)
                        m.update(rd)
                        out.append(m)
                        if j.kind in ("right", "full"):
                            matched_right.add(id(rd))
                elif j.kind in ("left", "full"):
                    m = dict(ld)
                    m.update(null_right)
                    out.append(m)
            if j.kind in ("right", "full"):
                # Right side preserved: NULL-extend every column
                # accumulated so far for unmatched right rows (also
                # rows whose join key is NULL — they never match).
                for rd in rows_by_alias[a]:
                    if id(rd) not in matched_right:
                        m = dict(null_left)
                        m.update(rd)
                        out.append(m)
            joined = out
            seen_aliases.add(a)

        # Bare-name aliases for unambiguous columns (output resolution).
        bare = [(n, f"{als[0]}.{n}") for n, als in owners.items()
                if len(als) == 1]
        for d in joined:
            for n, qn in bare:
                d[n] = d[qn]

        # Re-verify WHERE post-join: predicates pushed below a LEFT JOIN's
        # right side must still filter NULL-extended rows (PG applies
        # WHERE after the join).
        if where and any(j.kind in ("left", "right", "full")
                         for j in stmt.joins):
            post = []
            for rel in where:
                a, c = qualify(rel.column)
                col = handles[a].schema.column(c)
                if rel.op == "IN":
                    val = tuple(self._coerce(col, v)
                                for v in self._resolve(rel.value))
                else:
                    val = self._coerce(col, rel.value)
                post.append(Predicate(f"{a}.{c}", rel.op, val))
            joined = [d for d in joined
                      if all(p.matches(d.get(p.column)) for p in post)]

        if not exists_ok:
            joined = []
        return joined, tables, handles, qualify, owners

    @classmethod
    def _eval_item(cls, expr, d: dict):
        """Evaluate one select-item expression over a row dict: scalar
        trees (Col/Const/BinOp with SQL NULL propagation), scalar
        function calls (ast.Func), jsonb paths — the expression work
        stock PG's executor does above the FDW."""
        if isinstance(expr, X.Col):
            return d.get(expr.name)
        if isinstance(expr, X.Const):
            return expr.value
        if isinstance(expr, X.BinOp):
            left = cls._eval_item(expr.left, d)
            right = cls._eval_item(expr.right, d)
            if left is None or right is None:
                return None
            return {"+": lambda: left + right,
                    "-": lambda: left - right,
                    "*": lambda: left * right}[expr.op]()
        if isinstance(expr, ast.Func):
            return cls._eval_func(expr.name,
                                  [cls._eval_item(a, d)
                                   for a in expr.args])
        if isinstance(expr, ast.JsonPath):
            import json

            v = d.get(expr.column)
            for op, key in expr.steps:
                if v is None:
                    return None
                if isinstance(v, dict):
                    v = v.get(key)
                elif isinstance(v, list) and isinstance(key, int) \
                        and -len(v) <= key < len(v):
                    v = v[key]
                else:
                    return None
                if op == "->>" and v is not None:
                    v = (json.dumps(v, separators=(",", ":"))
                         if isinstance(v, (dict, list)) else
                         ("true" if v is True else "false"
                          if v is False else str(v)))
            return v
        return X.eval_expr(expr, lambda n: d.get(n))

    @staticmethod
    def _eval_func(name: str, args: list):
        """SQL scalar-function semantics (PG behavior: NULL in -> NULL
        out except coalesce/concat/greatest/least/nullif)."""
        if name == "coalesce":
            return next((a for a in args if a is not None), None)
        if name == "nullif":
            a, b = args
            return None if a == b else a
        if name == "greatest":
            vals = [a for a in args if a is not None]
            return max(vals) if vals else None
        if name == "least":
            vals = [a for a in args if a is not None]
            return min(vals) if vals else None
        if name == "concat":  # PG concat() treats NULL as ''
            return "".join("" if a is None else
                           ("t" if a is True else "f") if isinstance(
                               a, bool) else str(a) for a in args)
        if any(a is None for a in args):
            return None
        if name == "abs":
            return abs(args[0])
        if name == "upper":
            return str(args[0]).upper()
        if name == "lower":
            return str(args[0]).lower()
        if name == "length":
            return len(str(args[0]))
        if name == "round":
            import math

            v = args[0]
            if len(args) == 2:
                # PG rounds halves away from zero (Python: to even).
                nd = int(args[1])
                if isinstance(v, int):
                    if nd >= 0:
                        return v
                    scale = 10 ** (-nd)
                    q = (abs(v) + scale // 2) // scale * scale
                    return -q if v < 0 else q
                scale = 10.0 ** nd
                scaled = v * scale
                r = (math.floor(scaled + 0.5) if scaled >= 0
                     else math.ceil(scaled - 0.5))
                return r / scale
            if isinstance(v, int):
                return v
            return float(math.floor(v + 0.5) if v >= 0
                         else math.ceil(v - 0.5))
        if name == "floor":
            import math

            return (args[0] if isinstance(args[0], int)
                    else float(math.floor(args[0])))
        if name in ("ceil", "ceiling"):
            import math

            return (args[0] if isinstance(args[0], int)
                    else float(math.ceil(args[0])))
        if name == "mod":
            a, b = args
            # PG mod() takes the dividend's sign (Python %: divisor's);
            # exact int arithmetic (math.fmod loses >2^53 precision).
            if isinstance(a, int) and isinstance(b, int):
                r = abs(a) % abs(b)
                return -r if a < 0 else r
            import math

            return math.fmod(a, b)
        if name in ("substring", "substr"):
            s = str(args[0])
            start = int(args[1])
            ln = int(args[2]) if len(args) > 2 else None
            # PG 1-based; start can be <= 0 (consumes into the length).
            if ln is None:
                return s[max(start - 1, 0):]
            end = start - 1 + ln
            return s[max(start - 1, 0):max(end, 0)]
        raise InvalidArgument(f"unknown function {name}")

    @classmethod
    def _item_columns(cls, expr) -> set:
        if isinstance(expr, ast.JsonPath):
            return {expr.column}
        if isinstance(expr, ast.Func):
            out: set = set()
            for a in expr.args:
                out |= cls._item_columns(a)
            return out
        if isinstance(expr, ast.Agg):
            return (cls._item_columns(expr.arg)
                    if expr.arg is not None else set())
        if isinstance(expr, ast.WindowFunc):
            out = (cls._item_columns(expr.arg)
                   if expr.arg is not None else set())
            out |= set(expr.partition_by)
            out |= {ob.column for ob in expr.order_by}
            return out
        if isinstance(expr, X.BinOp):
            return cls._item_columns(expr.left) | \
                cls._item_columns(expr.right)
        return X.columns_of(expr)

    def _outer_refs(self, sub: ast.Select, outer_schema,
                    outer_alias: str):
        """Outer-column references inside a subquery's WHERE: values
        spelled as column refs that resolve to the OUTER relation
        (qualified with its alias, or unqualified names the inner table
        lacks). Returns {ref_name: outer_column} or None when the
        subquery is uncorrelated."""
        try:
            inner_schema = (self.cluster.table(sub.table).schema
                            if sub.table else None)
        except Exception:  # noqa: BLE001 — CTE/view inner: treat plain
            inner_schema = None
        prefix = outer_alias + "."
        refs = {}
        for rel in sub.where:
            v = rel.value
            if not isinstance(v, X.Col):
                continue
            name = v.name
            if name.startswith(prefix):
                refs[name] = name[len(prefix):]
            elif "." not in name and inner_schema is not None \
                    and not inner_schema.has_column(name) \
                    and outer_schema.has_column(name):
                refs[name] = name
        return refs or None

    def _eval_correlated(self, rel: ast.Rel, refs: dict, d: dict,
                         cache: dict) -> bool:
        """One correlated-subquery conjunct against one outer row: bind
        the outer refs to the row's values, run the subquery (memoized
        on the binding tuple), compare (PG subplan semantics: NULL /
        empty scalar never matches; >1 scalar row errors)."""
        key = tuple(d.get(c) for c in refs.values())
        hit = cache.get(key)
        if hit is None:
            import dataclasses as _dc

            sub = rel.value.select
            new_where = []
            for r in sub.where:
                if isinstance(r.value, X.Col) and r.value.name in refs:
                    new_where.append(ast.Rel(
                        r.column, r.op, d.get(refs[r.value.name])))
                else:
                    new_where.append(r)
            res = self._exec_select(_dc.replace(sub, where=new_where))
            if rel.op not in ("EXISTS", "NOT EXISTS") \
                    and len(res.columns) != 1:
                raise InvalidArgument(
                    "subquery must return a single column")
            hit = cache[key] = [r[0] if r else None for r in res.rows]
        if rel.op in ("EXISTS", "NOT EXISTS"):
            return bool(hit) == (rel.op == "EXISTS")
        if rel.op == "IN":
            left = d.get(rel.column)
            return left is not None and any(
                left == v for v in hit if v is not None)
        if len(hit) > 1:
            raise InvalidArgument(
                "more than one row returned by a subquery used as "
                "an expression")
        v = hit[0] if hit else None
        return v is not None and self._cmp(rel.op, d.get(rel.column), v)

    def _select_rows(self, handle, stmt: ast.Select):
        schema = handle.schema
        outer_alias = stmt.alias or stmt.table
        plain, correlated, colcol = [], [], []
        for rel in stmt.where:
            if rel.op in ("EXISTS", "NOT EXISTS"):
                # Correlated or not, [NOT] EXISTS rides the per-row
                # subplan path (uncorrelated = one memoized execution
                # under the empty binding tuple).
                refs = self._outer_refs(rel.value.select, schema,
                                        outer_alias)
                correlated.append((rel, refs or {}, {}))
                continue
            if isinstance(rel.value, X.Col):
                for name in (rel.column, rel.value.name):
                    if not schema.has_column(name):
                        raise InvalidArgument(f"unknown column {name}")
                colcol.append(rel)  # col-vs-col: host filter
                continue
            refs = (self._outer_refs(rel.value.select, schema,
                                     outer_alias)
                    if isinstance(rel.value, ast.SubQuery) else None)
            if refs is not None:
                correlated.append((rel, refs, {}))
            else:
                plain.append(rel)
        if correlated or colcol:
            import dataclasses as _dc

            # Fetch candidates with the plain predicates pushed down,
            # then run each correlated subplan per outer row (memoized
            # per outer-binding tuple — PG's SubPlan rescan shape) and
            # col-vs-col filters, and finish projection/order/limit
            # over the survivors.
            preds = self._predicates(schema, plain)
            all_names = [c.name for c in schema.columns]
            survivors = []
            for d in self._scan_dicts(handle, plain, preds, all_names,
                                      None):
                if not all(self._cmp(r.op, d.get(r.column),
                                     d.get(r.value.name))
                           for r in colcol):
                    continue
                if all(self._eval_correlated(rel, refs, d, cache)
                       for rel, refs, cache in correlated):
                    survivors.append(tuple(d.get(c) for c in all_names))
            return self._select_over_rows(
                _dc.replace(stmt, where=[]), all_names, survivors)
        preds = self._predicates(schema, stmt.where)
        all_names = [c.name for c in schema.columns]
        names, exprs = [], []
        for it in stmt.items:
            if it.expr == "*":
                names.extend(all_names)
                exprs.extend(X.Col(n) for n in all_names)
                continue
            if isinstance(it.expr, X.Col):
                if not schema.has_column(it.expr.name):
                    raise InvalidArgument(f"unknown column {it.expr.name}")
                names.append(it.alias or it.expr.name)
            else:
                names.append(it.alias or "?column?")
            exprs.append(it.expr)
        # ORDER BY may reference table columns outside the select list
        # (PG semantics): carry them as hidden trailing columns.
        hidden = 0
        for ob in stmt.order_by:
            if ob.column not in names and schema.has_column(ob.column):
                names.append(ob.column)
                exprs.append(X.Col(ob.column))
                hidden += 1
        needed = sorted({c for e in exprs for c in self._item_columns(e)})
        limit = self._limit(stmt)
        offset = self._offset(stmt)
        # Engine-level LIMIT is only a safe pushdown when no later sort
        # reorders rows and a single tablet preserves global key order;
        # OFFSET rows are still consumed host-side, so push their count.
        push_limit = (limit + (offset or 0)
                      if limit is not None and not stmt.order_by
                      and len(handle.tablets) == 1 else None)
        if stmt.distinct:
            if hidden:
                raise InvalidArgument(
                    "for SELECT DISTINCT, ORDER BY expressions must "
                    "appear in the select list")
            push_limit = None  # dedup may need more input rows
        rows = []
        for d in self._scan_dicts(handle, stmt.where, preds, needed,
                                  push_limit):
            rows.append(tuple(self._eval_item(e, d) for e in exprs))
        return self._dedup_order_trim(stmt, names, rows, limit, hidden)

    _SCAN_POOL = None
    _SCAN_POOL_LOCK = __import__("threading").Lock()

    @classmethod
    def _scan_pool(cls):
        if cls._SCAN_POOL is None:
            with cls._SCAN_POOL_LOCK:
                if cls._SCAN_POOL is None:
                    from concurrent.futures import ThreadPoolExecutor

                    cls._SCAN_POOL = ThreadPoolExecutor(
                        max_workers=4, thread_name_prefix="pg-docop")
        return cls._SCAN_POOL

    def _prefetch_scans(self, tablets, spec_of):
        """PgDocOp-style prefetching (reference:
        src/yb/yql/pggate/pg_doc_op.h:111 — async batched doc ops):
        keep several tablets' reads in flight and yield results in
        tablet order, so the next tablet's fetch overlaps this one's
        result consumption. Single-tablet plans stay synchronous.

        The span ``pg.scan_wait`` is the time a scan waited for a
        ``pg-docop`` worker (submit until the worker starts it; 0 on the
        synchronous branch): the pool is one for every session of the
        process."""
        clock = self._clock
        clock.scans_begin()
        if len(tablets) <= 1:
            for t in tablets:
                trace.record_span("pg.scan_wait", time.time_ns(), 0)
                since = time.perf_counter_ns()
                res = t.scan(spec_of(t))
                clock.blocked(since)
                yield t, res
            return
        import collections

        def scan_after_wait(t, spec, wall_ns, t0_ns):
            trace.record_span("pg.scan_wait", wall_ns,
                              (time.perf_counter_ns() - t0_ns) // 1000)
            return t.scan(spec)

        pool = self._scan_pool()
        futs = collections.deque()
        idx = 0
        inflight = 3
        while idx < len(tablets) or futs:
            while idx < len(tablets) and len(futs) < inflight:
                t = tablets[idx]
                # (in_context: the statement's Trace follows the scan to
                # the worker, and from there into the RPC's payload)
                futs.append((t, pool.submit(
                    trace.in_context(scan_after_wait), t, spec_of(t),
                    time.time_ns(), time.perf_counter_ns())))
                idx += 1
            t, fut = futs.popleft()
            since = time.perf_counter_ns()
            res = fut.result()
            clock.blocked(since)
            yield t, res

    def _scan_dicts(self, handle, where, preds, needed, push_limit):
        """Row dicts matching WHERE: index-driven when an '='-bound
        column is indexed (index-table hash scan -> base point reads,
        re-verifying predicates against the base row), full predicate-
        pushdown scan otherwise."""
        schema = handle.schema
        if self._txn is not None:
            # full-PK point SELECT inside a txn: read-your-writes
            key_names = [c.name for c in schema.key_columns]
            eq = {r.column: r.value for r in where if r.op == "="}
            if set(key_names) <= set(eq) and len(where) == len(key_names):
                kv = {n: self._coerce(schema.column(n), eq[n])
                      for n in key_names}
                got = self._txn_point_get(handle, kv)
                if got is not None:
                    yield got[1]
                return
        from yugabyte_db_tpu.index import normalize_index

        idx_info = None
        for rel in where:
            if rel.op != "=":
                continue
            for idx in getattr(handle, "indexes", []):
                ni = normalize_index(idx)
                # The SQL planner lowers only single-column indexes; a
                # compound index needs every hash column bound.
                if ni["columns"] == [rel.column]:
                    idx_info = (ni, rel)
                    break
            if idx_info:
                break
        if idx_info is None:
            for _tablet, res in self._prefetch_scans(
                    handle.tablets,
                    lambda t: ScanSpec(read_ht=self._read_ht(t),
                                       predicates=preds,
                                       projection=needed,
                                       limit=push_limit)):
                for r in res.rows:
                    yield dict(zip(res.columns, r))
            return
        from yugabyte_db_tpu.models.encoding import (encode_doc_key_prefix,
                                                     prefix_successor)
        from yugabyte_db_tpu.models.partition import compute_hash_code

        idx, rel = idx_info
        ih = self.cluster.table(idx["index_table"])
        ischema = ih.schema
        value = self._coerce(schema.column(rel.column), rel.value)
        hc = compute_hash_code(ischema, {rel.column: value})
        prefix = encode_doc_key_prefix(
            hc, [(value, ischema.hash_columns[0].dtype)], [])
        key_names = [c.name for c in schema.key_columns]
        itablet = self.cluster.tablet_for_hash(ih, hc)
        ires = itablet.scan(ScanSpec(
            lower=prefix, upper=prefix_successor(prefix),
            read_ht=self._read_ht(itablet), projection=key_names))
        for irow in ires.rows:
            base_kv = dict(zip(key_names, irow))
            key, btablet = self._key_and_tablet(handle, base_kv)
            res = btablet.scan(ScanSpec(
                lower=key, upper=key + b"\x00",
                read_ht=self._read_ht(btablet),
                predicates=preds, projection=needed, limit=1))
            for r in res.rows:
                yield dict(zip(res.columns, r))

    @staticmethod
    def _cmp(op: str, left, right) -> bool:
        """SQL comparison for HAVING / post-join verification: NULL on
        either side fails every operator."""
        if left is None or right is None:
            return False
        return {"=": left == right, "!=": left != right,
                "<": left < right, "<=": left <= right,
                ">": left > right, ">=": left >= right}[op]

    def _finish_select(self, stmt: ast.Select, dicts: list[dict],
                       tables, handles, qualify=None) -> PgResult:
        """Host projection/aggregation over joined row dicts (the work
        PG's executor does above the FDW scans)."""
        if qualify is not None:
            # Validate every column reference (catches ambiguous bare
            # names, which would otherwise silently read as NULL).
            def check(e):
                if isinstance(e, ast.Agg):
                    if e.arg is not None:
                        check(e.arg)
                    return
                for c in self._item_columns(e):
                    qualify(c)
            for it in stmt.items:
                if it.expr != "*":
                    check(it.expr)
            for h in stmt.having:
                check(h.expr)
            for g in stmt.group_by:
                qualify(g)
        names, exprs = [], []
        for it in stmt.items:
            if it.expr == "*":
                for a, _t in tables:
                    for c in handles[a].schema.columns:
                        names.append(c.name)
                        exprs.append(X.Col(f"{a}.{c.name}"))
                continue
            if isinstance(it.expr, ast.Agg):
                arg = it.expr.arg
                names.append(it.alias or
                             f"{it.expr.fn}({'*' if arg is None else '...'})")
            elif isinstance(it.expr, X.Col):
                names.append(it.alias or it.expr.name.split(".")[-1])
            else:
                names.append(it.alias or "?column?")
            exprs.append(it.expr)
        has_agg = (stmt.group_by
                   or any(isinstance(e, ast.Agg) for e in exprs)
                   or any(isinstance(h.expr, ast.Agg)
                          for h in stmt.having))
        limit = self._limit(stmt)
        if has_agg:
            rows = self._host_aggregate(stmt, dicts, exprs)
            if stmt.distinct:
                rows = list(dict.fromkeys(rows))
            rows = self._order_and_limit(stmt, names, rows, limit)
            return PgResult(columns=names, rows=rows)
        hidden = 0
        for ob in stmt.order_by:
            if ob.column not in names:
                names.append(ob.column)
                exprs.append(X.Col(ob.column))
                hidden += 1
        rows = [tuple(self._eval_item(e, d) for e in exprs)
                for d in dicts]
        return self._dedup_order_trim(stmt, names, rows, limit, hidden)

    def _host_aggregate(self, stmt: ast.Select, dicts: list[dict],
                        exprs) -> list[tuple]:
        """Group + fold on host over row dicts; returns output rows in
        group-key order (HAVING applied)."""
        group_by = list(stmt.group_by)
        agg_items: list[tuple] = []     # (fn, arg)
        out_plan: list[tuple] = []      # ("agg", slot) | ("expr", e)
        for e in exprs:
            if isinstance(e, ast.Agg):
                out_plan.append(("agg", len(agg_items)))
                agg_items.append((e.fn, e.arg))
            else:
                out_plan.append(("expr", e))
        having_plan: list[tuple] = []
        for h in stmt.having:
            if isinstance(h.expr, ast.Agg):
                having_plan.append(("agg", len(agg_items), h.op, h.value))
                agg_items.append((h.expr.fn, h.expr.arg))
            else:
                having_plan.append(("expr", h.expr, h.op, h.value))

        def new_accs():
            return [[0, 0, None, None] for _ in agg_items]  # n,s,mn,mx

        groups: dict[tuple, tuple] = {}
        order: list[tuple] = []
        for d in dicts:
            gk = tuple(self._eval_item(X.Col(g), d) for g in group_by)
            st = groups.get(gk)
            if st is None:
                st = groups[gk] = (d, new_accs())
                order.append(gk)
            for acc, (fn, arg) in zip(st[1], agg_items):
                if fn == "count" and arg is None:
                    acc[0] += 1
                    continue
                v = self._eval_item(arg, d)
                if v is None:
                    continue
                acc[0] += 1
                if fn in ("sum", "avg"):
                    acc[1] += v
                if acc[2] is None or v < acc[2]:
                    acc[2] = v
                if acc[3] is None or v > acc[3]:
                    acc[3] = v

        def finalize(fn, acc):
            n, s, mn, mx = acc
            if fn == "count":
                return n
            if fn == "sum":
                return s if n else None
            if fn == "avg":
                return s / n if n else None
            return mn if fn == "min" else mx

        if not group_by and not groups:
            groups[()] = ({}, new_accs())   # PG: aggregates over zero
            order.append(())                # rows yield one row
        order.sort(key=lambda gk: tuple((v is None, v) for v in gk))
        rows = []
        for gk in order:
            rep, accs = groups[gk]
            keep = True
            for hp in having_plan:
                if hp[0] == "agg":
                    _k, slot, op, lit = hp
                    fn, _arg = agg_items[slot]
                    val = finalize(fn, accs[slot])
                else:
                    _k, e, op, lit = hp
                    val = self._eval_item(e, rep)
                if not self._cmp(op, val, self._resolve(lit)):
                    keep = False
                    break
            if not keep:
                continue
            out = []
            for kind, payload in out_plan:
                if kind == "agg":
                    fn, _arg = agg_items[payload]
                    out.append(finalize(fn, accs[payload]))
                else:
                    out.append(self._eval_item(payload, rep))
            rows.append(tuple(out))
        return rows

    def _select_aggregate(self, handle, stmt: ast.Select):
        schema = handle.schema
        where, ok = self._fold_exists(stmt.where)
        if not ok:
            # An EXISTS conjunct failed: aggregate over no rows — PG
            # still yields one row (count 0 / NULL sums) when there is
            # no GROUP BY. An impossible IN () predicate on any column
            # produces exactly the zero-row aggregate; keyless schemas
            # (virtual tables) use their first column.
            cols = schema.key_columns or schema.columns
            where = [ast.Rel(cols[0].name, "IN", ())]
        if where is not stmt.where:
            import dataclasses as _dc

            stmt = _dc.replace(stmt, where=where)
        preds = self._predicates(schema, stmt.where)
        group_by = list(stmt.group_by)
        for g in group_by:
            if not schema.has_column(g):
                raise InvalidArgument(f"unknown column {g}")

        # Output plan: each item maps to (kind, payload) where kind is
        # "group" (index into group_by) or "agg"; avg lowers into
        # sum+count partial slots derived after the combine.
        aggs: list[AggSpec] = []
        out_plan = []
        names = []
        for it in stmt.items:
            if isinstance(it.expr, ast.Agg):
                fn, arg = it.expr.fn, it.expr.arg
                label = it.alias or (
                    f"{fn}({'*' if arg is None else '...'})")
                if fn == "avg":
                    si = len(aggs)
                    aggs.append(self._agg_spec("sum", arg, f"_avg_s{si}"))
                    aggs.append(self._agg_spec("count", arg, f"_avg_c{si}"))
                    out_plan.append(("avg", si))
                else:
                    out_plan.append(("agg", len(aggs)))
                    aggs.append(self._agg_spec(fn, arg, label))
                names.append(label)
            elif isinstance(it.expr, X.Col):
                if it.expr.name not in group_by:
                    raise InvalidArgument(
                        f"column {it.expr.name} must appear in GROUP BY")
                out_plan.append(("group", group_by.index(it.expr.name)))
                names.append(it.alias or it.expr.name)
            else:
                raise InvalidArgument(
                    "non-aggregate expressions must be GROUP BY columns")

        # HAVING conjuncts ride as hidden aggregate slots through the
        # same per-tablet partial combine (avg lowers to sum+count).
        having_plan = []
        for h in stmt.having:
            if isinstance(h.expr, ast.Agg):
                fn, arg = h.expr.fn, h.expr.arg
                if fn == "avg":
                    si = len(aggs)
                    aggs.append(self._agg_spec("sum", arg, f"_hv_s{si}"))
                    aggs.append(self._agg_spec("count", arg, f"_hv_c{si}"))
                    having_plan.append(("avg", si, h.op, h.value))
                else:
                    having_plan.append(("agg", len(aggs), h.op, h.value))
                    aggs.append(self._agg_spec(fn, arg, f"_hv{len(aggs)}"))
            elif isinstance(h.expr, X.Col):
                if h.expr.name not in group_by:
                    raise InvalidArgument(
                        f"HAVING column {h.expr.name} must appear in "
                        f"GROUP BY")
                having_plan.append(
                    ("group", group_by.index(h.expr.name), h.op, h.value))
            else:
                raise InvalidArgument("unsupported HAVING expression")

        spec = ScanSpec(read_ht=MAX_HT, predicates=preds,
                        aggregates=aggs, group_by=group_by or None)
        # Partial aggregates with PgDocOp-style prefetching: every unit's
        # read is in flight while partials combine. A unit is a tablet
        # (ts.scan) or, where a tserver with several chips leads two or
        # more of the table's tablets, that leader's group as ONE
        # ts.multi_agg_scan (client/mesh_route.py; the tserver combines
        # its tablets on its mesh).
        units = (handle.aggregate_units() if self._txn is None
                 and hasattr(handle, "aggregate_units") else handle.tablets)
        results = [res for _t, res in self._prefetch_scans(
            units,
            lambda t: ScanSpec(read_ht=self._read_ht(t),
                               predicates=preds, aggregates=aggs,
                               group_by=group_by or None))]
        combined = combine_grouped(spec, results)
        ngb = len(group_by)

        def slot(row, kind, payload):
            if kind == "group":
                return row[payload]
            if kind == "agg":
                # combined columns: group cols, then aggs in order
                return row[ngb + payload]
            # avg: sum at payload, count at payload+1
            s, c = row[ngb + payload], row[ngb + payload + 1]
            return s / c if c else None

        rows = []
        for row in combined.rows:
            if not all(self._cmp(op, slot(row, kind, payload),
                                 self._resolve(lit))
                       for kind, payload, op, lit in having_plan):
                continue
            rows.append(tuple(slot(row, kind, payload)
                              for kind, payload in out_plan))
        if stmt.distinct:
            rows = list(dict.fromkeys(rows))
        rows = self._order_and_limit(stmt, names, rows, self._limit(stmt))
        return PgResult(columns=names, rows=rows)

    def _agg_spec(self, fn: str, arg, label: str) -> AggSpec:
        if arg is None:
            return AggSpec("count", None, label=label)
        if isinstance(arg, X.Col):
            return AggSpec(fn, arg.name, label=label)
        if fn not in ("sum",):
            raise InvalidArgument(
                f"{fn} over an expression is not supported")
        return AggSpec(fn, None, expr=arg, label=label)

    def _limit(self, stmt: ast.Select):
        limit = self._resolve(stmt.limit)
        if limit is not None and (not isinstance(limit, int)
                                  or isinstance(limit, bool) or limit < 0):
            raise InvalidArgument("LIMIT must be a non-negative integer")
        return limit

    def _offset(self, stmt: ast.Select):
        off = self._resolve(getattr(stmt, "offset", None))
        if off is not None and (not isinstance(off, int)
                                or isinstance(off, bool) or off < 0):
            raise InvalidArgument("OFFSET must be a non-negative integer")
        return off

    def _dedup_order_trim(self, stmt: ast.Select, names: list[str],
                          rows: list[tuple], limit, hidden: int):
        """Shared SELECT tail: DISTINCT dedup (hidden ORDER BY columns
        are invalid under DISTINCT, as in PG), ORDER BY + LIMIT/OFFSET,
        then trim hidden trailing columns."""
        if stmt.distinct:
            if hidden:
                raise InvalidArgument(
                    "for SELECT DISTINCT, ORDER BY expressions must "
                    "appear in the select list")
            rows = list(dict.fromkeys(rows))
        rows = self._order_and_limit(stmt, names, rows, limit)
        if hidden:
            rows = [r[:-hidden] for r in rows]
            names = names[:-hidden]
        return PgResult(columns=names, rows=rows)

    def _order_and_limit(self, stmt: ast.Select, names: list[str], rows,
                         limit):
        if stmt.order_by:
            pos = {}
            for ob in stmt.order_by:
                if ob.column not in names:
                    raise InvalidArgument(
                        f"ORDER BY column {ob.column} is not in the "
                        f"select list")
                pos[ob.column] = names.index(ob.column)
            for ob in reversed(stmt.order_by):
                i = pos[ob.column]
                # PG defaults: ASC -> NULLS LAST, DESC -> NULLS FIRST
                rows.sort(key=lambda r: ((r[i] is None), r[i]),
                          reverse=ob.desc)
        offset = self._offset(stmt)
        if offset:
            rows = rows[offset:]
        if limit is not None:
            rows = rows[:limit]
        return rows
