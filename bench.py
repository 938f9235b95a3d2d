"""Benchmark suite: TPU engine vs honest baselines (BASELINE.md).

Workloads (all on the real chip, identical data/queries verified against
the CPU oracle engine):

  aggregate   TPC-H-Q6-flavored aggregate range scan (the headline)
  ycsb_e      YCSB-E-shaped row scans: concurrent LIMIT-100 pages served
              as serialized CQL wire bytes (native page server)
  point_read  YCSB-C / CassandraKeyValue-shaped exact-key GETs
  ycsb_a/f    mixed read/update and read-modify-write over a live
              memtable (bloom-pruned point path)
  redis       pipelined GET/SET through the RESP proxy over MiniCluster
  tpch_q1/q6  grouped / expression aggregates over lineitem
  write       batched write throughput into the engine (apply+flush)
  compact     multi-run merge + history GC throughput

Baselines, stated explicitly (BASELINE.md):
  - The reference's own published node-level numbers: YCSB-E 14,007
    scan-ops/s on 3x n1-standard-16 => ~292 scan-ops/s/vCPU, i.e. about
    29K scanned rows/s/vCPU and ~470K scanned rows/s per 16-vCPU NODE.
    ``vs_baseline`` for scan metrics = this chip vs that calibrated
    C++-class NODE (not the in-repo Python oracle).
  - ``vs_cpu_engine`` = same workload on the in-repo CPU oracle engine —
    an implementation-for-implementation ratio on identical code paths.
  - TPC-H has no in-reference numbers (YSQL was beta): Q1/Q6 report
    vs_cpu_engine only and carry vs_baseline = null.

Prints one JSON line per sub-metric (prefixed "#" as comments) and ends
with ONE final JSON line for the headline:
  {"metric", "value", "unit", "vs_baseline", "details": {...}}
"""

from __future__ import annotations

import json
import random
import sys
import time

# Optional flags (scanned out before the positional NUM_KEYS):
#   --compile_witness         count XLA trace/compile events per
#                             @compile_contract jit entry (utils/jitting)
#   --compile-witness-out P   dump the compile witness to P for
#                             yb-lint --witness-check
#   --only a,b / --skip a,b   run only / all-but the named sections
#                             (section names printed in the final JSON's
#                             "sections" map). The cluster sections run
#                             isolated in child interpreters on a full
#                             run, so --only is also how the parent asks
#                             a child for exactly one section.
_ARGV = sys.argv[1:]
COMPILE_WITNESS = "--compile_witness" in _ARGV


def _flag_value(flag):
    return _ARGV[_ARGV.index(flag) + 1] if flag in _ARGV else None


CWITNESS_OUT = _flag_value("--compile-witness-out")
_ONLY_RAW = _flag_value("--only")
_SKIP_RAW = _flag_value("--skip")
ONLY = set(_ONLY_RAW.split(",")) if _ONLY_RAW else None
SKIP = set(_SKIP_RAW.split(",")) if _SKIP_RAW else set()
_FLAG_VALS = {v for v in (CWITNESS_OUT, _ONLY_RAW, _SKIP_RAW)
              if v is not None}
_POS = [a for a in _ARGV if not a.startswith("--") and a not in _FLAG_VALS]
NUM_KEYS = int(_POS[0]) if _POS else 200_000
TIMED_ITERS = 5

# BASELINE.md calibration: ~29K scanned rows/s/vCPU on the reference's
# C++ DocDB; a 16-vCPU node => ~470K rows/s. YCSB-E node share:
# 14,007 scan-ops/s across 3 nodes => ~4,669 scan-ops/s per node.
CPP_NODE_SCAN_ROWS_S = 29_000 * 16
CPP_NODE_YCSBE_OPS_S = 14_007 / 3
# CassandraBatchKeyValue 258K ops/s across 3 nodes => ~86K rows/s/node.
CPP_NODE_BATCH_WRITE_ROWS_S = 258_000 / 3


def _median(f, iters=TIMED_ITERS):
    ts = []
    for _ in range(iters):
        t0 = time.perf_counter()
        f()
        ts.append(time.perf_counter() - t0)
    return sorted(ts)[len(ts) // 2]


def bench_aggregate(schema, rows, max_ht, make_engine, S, n_concurrent=32,
                    depth=6, n_batches=12):
    """Aggregate scans two ways: single-scan latency (one synchronous
    device->host fetch cycle sits inside it) and SERVER THROUGHPUT —
    concurrent aggregate scans pipelined through the async batch API,
    the shape a tserver actually runs, where the fetch cycle amortizes
    across whole batches and the device's scan rate is what's measured.
    The headline is the throughput number; latency rides in the details."""
    import collections

    tpu = make_engine("tpu", schema, {"rows_per_block": 2048})
    t0 = time.perf_counter()
    tpu.apply(rows)
    tpu.flush()
    load_s = time.perf_counter() - t0

    def spec(lo=-500_000):
        return S.ScanSpec(
            read_ht=max_ht + 1,
            predicates=[S.Predicate("d", ">=", lo)],
            aggregates=[S.AggSpec("count", None), S.AggSpec("sum", "a"),
                        S.AggSpec("min", "a"), S.AggSpec("max", "a"),
                        S.AggSpec("sum", "d")])

    warm = tpu.scan(spec())
    lat = _median(lambda: tpu.scan(spec()))
    versions = tpu.runs[0].crun.num_versions

    cpu = make_engine("cpu", schema)
    cpu.apply(rows)
    cpu.flush()
    # Same-workload CPU throughput: the oracle gains nothing from
    # concurrency (single-thread compute), so its rate on 2 of the
    # concurrent specs extrapolates linearly to the whole workload.
    t0 = time.perf_counter()
    cres, _c2 = cpu.scan_batch([spec(), spec(-500_007)])
    cpu_dt = (time.perf_counter() - t0) / 2
    cpu_rows_s = versions / cpu_dt
    for g, w in zip(warm.rows[0], cres.rows[0]):
        if isinstance(w, float):
            assert g is not None and abs(g - w) <= 1e-3 + 1e-5 * abs(w)
        else:
            assert g == w, (g, w)

    # Throughput: n_batches batches of n_concurrent DISTINCT aggregate
    # scans (varying literals), depth-pipelined; every scan walks the
    # whole table.
    batches = [[spec(-500_000 - 7 * (b * n_concurrent + i))
                for i in range(n_concurrent)] for b in range(n_batches)]

    def pipeline(bs):
        q = collections.deque()
        for batch in bs:
            q.append(tpu.scan_batch_async(batch))
            if len(q) > depth:
                q.popleft().finish()
        while q:
            q.popleft().finish()

    pipeline(batches[: depth + 2])  # warm compiles
    # Steady state starts here: every program the measured region needs
    # exists, so any further compile is a recompile charged to a request
    # (yb_jit_compiles{entry} + the compile witness when enabled).
    from yugabyte_db_tpu.utils import jitting, metrics

    warm_compiles = dict(metrics.jit_compiles())
    jitting.mark_steady_state()
    t0 = time.perf_counter()
    pipeline(batches)
    tdt = time.perf_counter() - t0
    tpu_rows_s = versions * n_concurrent * n_batches / tdt
    steady_recompiles = {
        k: v - warm_compiles.get(k, 0)
        for k, v in metrics.jit_compiles().items()
        if v != warm_compiles.get(k, 0)}

    return tpu, cpu, versions, {
        "metric": "aggregate_range_scan_rows_per_sec",
        "value": round(tpu_rows_s, 1),
        "unit": (f"rows/s ({n_concurrent} concurrent aggregate scans, "
                 f"depth-{depth} pipeline)"),
        "vs_baseline": round(tpu_rows_s / CPP_NODE_SCAN_ROWS_S, 2),
        "vs_cpu_engine": round(tpu_rows_s / cpu_rows_s, 2),
        "single_scan_latency_ms": round(lat * 1000, 1),
        "single_scan_rows_per_sec": round(versions / lat, 1),
        "load_s": round(load_s, 1),
        # {} proves the measured region recompiled nothing.
        "steady_state_recompiles": steady_recompiles,
    }


def bench_ycsb_e(schema, tpu, cpu, max_ht, S, n_pages=256, n_batches=40):
    """Steady-state server throughput: batches of concurrent LIMIT-100
    predicate pages served as SERIALIZED CQL WIRE BYTES — the shape the
    reference actually measures (YCSB-E ops return rows_data the CQL
    service forwards; src/yb/common/ql_rowblock.h:66). scan_batch_wire
    emits every page's result-frame cells straight from the run's plane
    buffers in C (native serve_page_wire_batch): no Python value object
    is ever constructed on the hot path. Byte-parity with the CPU
    oracle's scan + Python serialization is asserted on a full batch.
    The row-tuple API path (scan_batch, the r4 metric) rides along as a
    detail for round-over-round continuity."""
    import collections

    from yugabyte_db_tpu.models.partition import compute_hash_code

    rng = random.Random(11)

    def make_batch(k):
        out = []
        for _ in range(k):
            i = rng.randrange(NUM_KEYS)
            lo = schema.encode_primary_key(
                {"k": f"user{i:06d}", "r": 0},
                compute_hash_code(schema, {"k": f"user{i:06d}"}))
            out.append(S.ScanSpec(
                lower=lo, read_ht=max_ht + 1,
                predicates=[S.Predicate("d", ">=", -500_000)],
                projection=["k", "r", "a", "d"], limit=100))
        return out

    batches = [make_batch(n_pages) for _ in range(n_batches)]

    # Correctness: wire bytes identical to the CPU oracle's serialized
    # pages (independent implementations: C plane emitter vs Python
    # scan + models.wirefmt), and identical row tuples engine-vs-engine.
    aw = cpu.scan_batch_wire(batches[0], "cql")
    bw = tpu.scan_batch_wire(batches[0], "cql")
    assert [(p.data, p.nrows, p.resume) for p in aw] == \
        [(p.data, p.nrows, p.resume) for p in bw]
    a = cpu.scan_batch(batches[1])
    b = tpu.scan_batch(batches[1])
    assert [r.rows for r in a] == [r.rows for r in b]

    tpu.scan_batch_wire(batches[0], "cql")  # warm blob/mask caches
    t0 = time.perf_counter()
    nrows = nbytes = 0
    for batch in batches:
        for pg in tpu.scan_batch_wire(batch, "cql"):
            nrows += pg.nrows
            nbytes += len(pg.data)
    tdt = time.perf_counter() - t0
    ops_s = n_pages * n_batches / tdt

    # CPU oracle on identical work (2 batches, extrapolated linearly).
    t0 = time.perf_counter()
    cpu.scan_batch_wire(batches[0], "cql")
    cpu.scan_batch_wire(batches[1], "cql")
    cdt = (time.perf_counter() - t0) / 2 * n_batches

    # r4-continuity detail: the row-tuple scan path, depth-pipelined.
    def pipeline(bs, depth=6):
        q = collections.deque()
        n = 0
        for batch in bs:
            q.append(tpu.scan_batch_async(batch))
            if len(q) > depth:
                n += sum(len(r.rows) for r in q.popleft().finish())
        while q:
            n += sum(len(r.rows) for r in q.popleft().finish())
        return n

    pipeline(batches[:8])  # warm
    t0 = time.perf_counter()
    pipeline(batches[:12])
    tup_dt = time.perf_counter() - t0
    tup_ops_s = n_pages * 12 / tup_dt

    page_lat = _median(
        lambda: tpu.scan_batch_wire([batches[2][0]], "cql"), iters=7)
    return {
        "metric": "ycsb_e_scan_ops_per_sec",
        "value": round(ops_s, 1),
        "unit": (f"scan-ops/s (LIMIT-100 pages as serialized CQL wire "
                 f"bytes, batches of {n_pages})"),
        "vs_baseline": round(ops_s / CPP_NODE_YCSBE_OPS_S, 2),
        "vs_cpu_engine": round(cdt / tdt, 2),
        "result_rows_per_sec": round(nrows / tdt, 1),
        "wire_mb_per_sec": round(nbytes / tdt / 1e6, 1),
        "rowtuple_ops_per_sec": round(tup_ops_s, 1),
        "rowtuple_vs_baseline": round(tup_ops_s / CPP_NODE_YCSBE_OPS_S, 2),
        "single_page_latency_ms": round(page_lat * 1000, 3),
    }


def bench_point_reads(schema, tpu, cpu, max_ht, S, n_ops=256,
                      n_batches=40):
    """YCSB-C / CassandraKeyValue-shaped point reads: batched exact-key
    GETs ([key, key+0xff), LIMIT 1) served as wire bytes. Baseline:
    CassandraKeyValue reads 220K ops/s across 3 nodes => ~73.3K
    ops/s/node (docs/yb-perf-v1.0.7.md:7)."""
    from yugabyte_db_tpu.models.partition import compute_hash_code

    rng = random.Random(13)

    def make_batch(k):
        out = []
        for _ in range(k):
            i = rng.randrange(NUM_KEYS)
            key = schema.encode_primary_key(
                {"k": f"user{i:06d}", "r": i % 7},
                compute_hash_code(schema, {"k": f"user{i:06d}"}))
            out.append(S.ScanSpec(
                lower=key, upper=key + b"\xff", read_ht=max_ht + 1,
                projection=["k", "r", "a", "d"], limit=1))
        return out

    batches = [make_batch(n_ops) for _ in range(n_batches)]
    aw = cpu.scan_batch_wire(batches[0], "cql")
    bw = tpu.scan_batch_wire(batches[0], "cql")
    assert [(p.data, p.nrows) for p in aw] == \
        [(p.data, p.nrows) for p in bw]

    t0 = time.perf_counter()
    hits = 0
    for batch in batches:
        for pg in tpu.scan_batch_wire(batch, "cql"):
            hits += pg.nrows
    tdt = time.perf_counter() - t0
    ops_s = n_ops * n_batches / tdt

    t0 = time.perf_counter()
    cpu.scan_batch_wire(batches[0], "cql")
    cpu.scan_batch_wire(batches[1], "cql")
    cdt = (time.perf_counter() - t0) / 2 * n_batches
    return {
        "metric": "point_read_ops_per_sec",
        "value": round(ops_s, 1),
        "unit": (f"GET ops/s (exact-key LIMIT-1 wire pages, "
                 f"batches of {n_ops})"),
        "vs_baseline": round(ops_s / (220_000 / 3), 2),
        "vs_cpu_engine": round(cdt / tdt, 2),
        "hit_rate": round(hits / (n_ops * n_batches), 3),
    }


def bench_ycsb_mix(make_engine, S, n_keys=None):
    """YCSB-A (50/50 read-update) and YCSB-F (read-modify-write) on a
    dedicated engine pair: updates land in the live memtable, reads take
    the bloom-pruned point path over memtable + runs — the real mixed
    steady state (the reference's YCSB numbers,
    docs/yb-perf-v1.0.7.md:585-601; per-node = /3)."""
    from __graft_entry__ import _make_rows, _make_schema
    from yugabyte_db_tpu.models.partition import compute_hash_code
    from yugabyte_db_tpu.storage.row_version import RowVersion

    n_keys = n_keys or max(NUM_KEYS // 2, 10_000)
    schema = _make_schema()
    rows, ht = _make_rows(schema, n_keys, seed=5)
    tpu = make_engine("tpu", schema, {"rows_per_block": 2048})
    cpu = make_engine("cpu", schema)
    for e in (tpu, cpu):
        e.apply(rows)
        e.flush()
    cid = {c.name: c.col_id for c in schema.value_columns}
    rng = random.Random(23)

    # Keys pre-encoded outside the timed loops: the reference's YCSB
    # measures SERVER throughput — key construction happens on client
    # machines (docs/yb-perf-v1.0.7.md workload setup) and is not part
    # of the reported ops/s.
    keys = [schema.encode_primary_key(
        {"k": f"user{i:06d}", "r": i % 7},
        compute_hash_code(schema, {"k": f"user{i:06d}"}))
        for i in range(n_keys)]

    def key_of(i):
        return keys[i]

    def get_spec(i, rht):
        return S.ScanSpec(lower=keys[i], upper=keys[i] + b"\xff",
                          read_ht=rht, projection=["k", "r", "a", "d"],
                          limit=1)

    out = []
    # A: 50/50 in batches of 64 reads + 64 updates.
    n_rounds = 60
    ops = 0
    # Warm + parity on one round against the oracle.
    specs = [get_spec(rng.randrange(n_keys), ht + 1) for _ in range(64)]
    assert [p.data for p in tpu.scan_batch_wire(specs, "cql")] == \
        [p.data for p in cpu.scan_batch_wire(specs, "cql")]
    t0 = time.perf_counter()
    for _ in range(n_rounds):
        upd = []
        for _ in range(64):
            i = rng.randrange(n_keys)
            ht += 1
            upd.append(RowVersion(key_of(i), ht=ht, columns={
                cid["d"]: rng.randrange(-10**6, 10**6)}))
        tpu.apply(upd)
        specs = [get_spec(rng.randrange(n_keys), ht + 1)
                 for _ in range(64)]
        for pg in tpu.scan_batch_wire(specs, "cql"):
            pass
        ops += 128
    a_dt = time.perf_counter() - t0
    out.append({
        "metric": "ycsb_a_ops_per_sec",
        "value": round(ops / a_dt, 1),
        "unit": "ops/s (50/50 point-read/update, live memtable)",
        "vs_baseline": round(ops / a_dt / (107_120 / 3), 2),
    })
    # F: read-modify-write (read the row, rewrite column d).
    ops = 0
    t0 = time.perf_counter()
    for _ in range(n_rounds):
        idxs = [rng.randrange(n_keys) for _ in range(64)]
        specs = [get_spec(i, ht + 1) for i in idxs]
        pages = tpu.scan_batch_wire(specs, "cql")
        upd = []
        for i, pg in zip(idxs, pages):
            ht += 1
            upd.append(RowVersion(key_of(i), ht=ht, columns={
                cid["d"]: pg.nrows + 1}))
        tpu.apply(upd)
        ops += 64
    f_dt = time.perf_counter() - t0
    # Spot-check: the mixed state still matches the oracle that applied
    # nothing — only on keys never updated is that meaningful, so replay
    # the tpu updates into the oracle lazily via dump comparison cost is
    # excessive; instead verify a fresh parity batch through the point
    # path (memtable + run merge) against the SAME engine's row API.
    specs = [get_spec(rng.randrange(n_keys), ht + 1) for _ in range(32)]
    pages = tpu.scan_batch_wire(specs, "cql")
    rows_api = tpu.scan_batch(specs)
    from yugabyte_db_tpu.models.wirefmt import serialize_rows
    for pg, rr, sp in zip(pages, rows_api, specs):
        dts = [schema.column(n).dtype for n in rr.columns]
        assert pg.data == serialize_rows("cql", dts, rr.rows)
    out.append({
        "metric": "ycsb_f_ops_per_sec",
        "value": round(ops / f_dt, 1),
        "unit": "RMW ops/s (point read + rewrite, live memtable)",
        "vs_baseline": round(ops / f_dt / (72_185 / 3), 2),
    })
    return out


def bench_index(n_rows=4000, n_reads=4000):
    """Secondary-index write maintenance + index-driven reads over the
    RF=3 MiniCluster through the real CQL wire server, driven by the
    vendored driver with prepared statements (the
    CassandraSecondaryIndex workload shape). Baselines per node:
    5.9K idx writes /3, 200K idx reads /3
    (docs/yb-perf-v1.0.7.md:9-10)."""
    import tempfile

    from yugabyte_db_tpu.drivers import CqlConnection
    from yugabyte_db_tpu.integration.mini_cluster import MiniCluster
    from yugabyte_db_tpu.yql.cql.client_cluster import ClientCluster
    from yugabyte_db_tpu.yql.cql.server import CQLServer

    with tempfile.TemporaryDirectory() as root:
        mc = MiniCluster(root, num_tservers=3).start()
        try:
            mc.wait_tservers_registered()
            server = CQLServer(ClientCluster(mc.client()))
            host, port = server.listen("127.0.0.1", 0)
            conn = CqlConnection(host, port)
            conn.execute("CREATE KEYSPACE bench")
            conn.execute("USE bench")
            conn.execute("CREATE TABLE users (id bigint PRIMARY KEY, "
                         "email text, v bigint)")
            conn.execute("CREATE INDEX users_email ON users (email)")
            emails = [f"u{i}@x.io" for i in range(n_rows)]
            # Stream-multiplexed pipelining on one connection — the
            # in-flight request window every stock driver keeps.
            ins = conn.prepare(
                "INSERT INTO users (id, email, v) VALUES (?, ?, ?)")
            sel = conn.prepare("SELECT id, v FROM users WHERE email = ?")
            rng = random.Random(7)
            picks = [rng.randrange(n_rows) for _ in range(n_reads)]
            t0 = time.perf_counter()
            conn.execute_prepared_many(
                ins, [[i, emails[i], i * 3] for i in range(n_rows)])
            w_dt = time.perf_counter() - t0
            r = conn.execute_prepared(sel, [emails[picks[0]]])
            assert r.rows == [(picks[0], picks[0] * 3)], r.rows
            t0 = time.perf_counter()
            res = conn.execute_prepared_many(
                sel, [[emails[i]] for i in picks])
            r_dt = time.perf_counter() - t0
            assert all(r.rows for r in res)
            conn.close()
            server.shutdown()
        finally:
            mc.shutdown()
    return [{
        "metric": "index_write_ops_per_sec",
        "value": round(n_rows / w_dt, 1),
        "unit": "indexed-INSERT ops/s (CQL wire, prepared, RF=3)",
        "vs_baseline": round(n_rows / w_dt / (5_900 / 3), 2),
    }, {
        "metric": "index_read_ops_per_sec",
        "value": round(n_reads / r_dt, 1),
        "unit": "index-driven SELECT ops/s (CQL wire, prepared, RF=3)",
        "vs_baseline": round(n_reads / r_dt / (200_000 / 3), 2),
    }]


def bench_redis(n_keys=20_000, pipeline=256):
    """Redis proxy over the RF=3 MiniCluster through a real RESP socket,
    pipelined (the RedisPipelinedKeyValue shape): SET load then GET
    sweep. Baselines per node: pipelined reads 538K/3 => ~179K ops/s,
    writes 536K/3 => ~179K (docs/yb-perf-v1.0.7.md:18-19)."""
    import socket
    import tempfile

    from yugabyte_db_tpu.integration.mini_cluster import MiniCluster
    from yugabyte_db_tpu.yql.redis import RedisServer

    with tempfile.TemporaryDirectory() as root:
        mc = MiniCluster(root, num_tservers=3).start()
        try:
            mc.wait_tservers_registered()
            server = RedisServer(mc.client("redis-bench"))
            host, port = server.listen("127.0.0.1", 0)
            sock = socket.create_connection((host, port))
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            f = sock.makefile("rwb")

            def run(cmds):
                n = 0
                for c0 in range(0, len(cmds), pipeline):
                    chunk = cmds[c0:c0 + pipeline]
                    f.write(b"".join(chunk))
                    f.flush()
                    for _ in chunk:
                        line = f.readline()
                        if line[:1] == b"$":
                            ln = int(line[1:])
                            if ln >= 0:
                                f.read(ln + 2)
                        n += 1
                return n

            def resp(*args):
                parts = [b"*%d\r\n" % len(args)]
                for a in args:
                    b = a if isinstance(a, bytes) else str(a).encode()
                    parts.append(b"$%d\r\n%s\r\n" % (len(b), b))
                return b"".join(parts)

            sets = [resp("SET", f"bk{i:07d}", f"val{i}")
                    for i in range(n_keys)]
            t0 = time.perf_counter()
            run(sets)
            set_dt = time.perf_counter() - t0
            rng = random.Random(3)
            gets = [resp("GET", f"bk{rng.randrange(n_keys):07d}")
                    for _ in range(n_keys)]
            t0 = time.perf_counter()
            run(gets)
            get_dt = time.perf_counter() - t0
            sock.close()
            server.shutdown()
        finally:
            mc.shutdown()
    return [{
        "metric": "redis_pipelined_get_ops_per_sec",
        "value": round(n_keys / get_dt, 1),
        "unit": f"GET ops/s (RESP socket, pipeline {pipeline}, RF=3)",
        "vs_baseline": round(n_keys / get_dt / (538_000 / 3), 2),
    }, {
        "metric": "redis_pipelined_set_ops_per_sec",
        "value": round(n_keys / set_dt, 1),
        "unit": f"SET ops/s (RESP socket, pipeline {pipeline}, RF=3)",
        "vs_baseline": round(n_keys / set_dt / (536_000 / 3), 2),
    }]


def bench_serving_path(n_keys=20_000, pipeline=256, cql_rows=2_000,
                       cql_ops=10_000, window=128):
    """The native request-batch serving path (docs/serving-path.md)
    against its own Python per-op fallback, same sockets, same data:
    pipelined RESP GETs and pipelined prepared CQL point SELECTs, each
    timed with the native batch executors enabled and then force-
    disabled. NEW metric keys — the pre-existing redis_pipelined_* keys
    keep measuring whatever path the server picks by default."""
    import socket
    import tempfile

    from yugabyte_db_tpu.integration.mini_cluster import MiniCluster
    from yugabyte_db_tpu.yql.cql import wire_protocol as W
    from yugabyte_db_tpu.yql.cql.client_cluster import ClientCluster
    from yugabyte_db_tpu.yql.cql.processor import QLProcessor
    from yugabyte_db_tpu.yql.cql.server import CQLServer
    from yugabyte_db_tpu.yql.redis import RedisServer
    from yugabyte_db_tpu.yql.redis.server import RedisServiceImpl

    out = []
    with tempfile.TemporaryDirectory() as root:
        mc = MiniCluster(root, num_tservers=3).start()
        try:
            mc.wait_tservers_registered()
            # -- redis: pipelined GET sweep, native vs forced-Python ----
            server = RedisServer(mc.client("redis-bench-native"))
            host, port = server.listen("127.0.0.1", 0)
            sock = socket.create_connection((host, port))
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
            f = sock.makefile("rwb")

            def run(cmds):
                for c0 in range(0, len(cmds), pipeline):
                    chunk = cmds[c0:c0 + pipeline]
                    f.write(b"".join(chunk))
                    f.flush()
                    for _ in chunk:
                        line = f.readline()
                        if line[:1] == b"$":
                            ln = int(line[1:])
                            if ln >= 0:
                                f.read(ln + 2)

            def resp(*args):
                parts = [b"*%d\r\n" % len(args)]
                for a in args:
                    b = a if isinstance(a, bytes) else str(a).encode()
                    parts.append(b"$%d\r\n%s\r\n" % (len(b), b))
                return b"".join(parts)

            run([resp("SET", f"nk{i:07d}", f"val{i}")
                 for i in range(n_keys)])
            rng = random.Random(7)
            gets = [resp("GET", f"nk{rng.randrange(n_keys):07d}")
                    for _ in range(n_keys)]
            run(gets[:pipeline])  # warm both paths' caches
            t0 = time.perf_counter()
            run(gets)
            native_dt = time.perf_counter() - t0
            native_get = RedisServiceImpl._native_get_values
            RedisServiceImpl._native_get_values = \
                lambda self, rkeys: None
            try:
                t0 = time.perf_counter()
                run(gets)
                py_dt = time.perf_counter() - t0
            finally:
                RedisServiceImpl._native_get_values = native_get
            sock.close()
            server.shutdown()
            out.append({
                "metric": "redis_native_batch_get_ops_per_sec",
                "value": round(n_keys / native_dt, 1),
                "unit": f"GET ops/s (native batch path, pipeline "
                        f"{pipeline}, RF=3)",
                "vs_baseline": round(n_keys / native_dt / (538_000 / 3),
                                     2),
                "python_per_op_ops_per_sec": round(n_keys / py_dt, 1),
                "speedup_vs_python": round(py_dt / native_dt, 2),
            })

            # -- CQL: pipelined prepared point SELECTs ------------------
            cql = CQLServer(ClientCluster(mc.client()))
            host, port = cql.listen("127.0.0.1", 0)
            sock = socket.create_connection((host, port))
            sock.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)

            def send(stream, opcode, body):
                sock.sendall(W.HEADER.pack(W.VERSION_REQ, 0, stream,
                                           opcode, len(body)) + body)

            def recvn(n):
                buf = b""
                while len(buf) < n:
                    chunk = sock.recv(n - len(buf))
                    assert chunk
                    buf += chunk
                return buf

            def recv_frame():
                hdr = recvn(W.HEADER.size)
                _v, _fl, _s, op, ln = W.HEADER.unpack(hdr)
                return op, recvn(ln)

            def query(q):
                w = W.Writer().long_string(q).short(1).byte(0)
                send(1, W.OP_QUERY, w.getvalue())
                op, body = recv_frame()
                assert op == W.OP_RESULT, body

            w = W.Writer()
            w.short(1)
            w.string("CQL_VERSION").string("3.4.4")
            send(0, W.OP_STARTUP, w.getvalue())
            assert recv_frame()[0] == W.OP_READY
            query("CREATE KEYSPACE IF NOT EXISTS bench_sp")
            query("USE bench_sp")
            query("CREATE TABLE t (k bigint PRIMARY KEY, v text)")
            for i in range(cql_rows):
                query(f"INSERT INTO t (k, v) VALUES ({i}, 'val{i}')")
            send(1, W.OP_PREPARE,
                 W.Writer().long_string(
                     "SELECT k, v FROM t WHERE k = ?").getvalue())
            op, body = recv_frame()
            assert op == W.OP_RESULT, body
            r = W.Reader(body)
            assert r.int32() == W.RESULT_PREPARED
            stmt_id = r.short_bytes()

            def exec_frames(keys):
                frames = []
                for s, k in enumerate(keys):
                    w = W.Writer().short_bytes(stmt_id)
                    w.short(1).byte(0x01).short(1)
                    w.bytes_(k.to_bytes(8, "big", signed=True))
                    b = w.getvalue()
                    frames.append(W.HEADER.pack(
                        W.VERSION_REQ, 0, s + 2, W.OP_EXECUTE, len(b))
                        + b)
                return b"".join(frames)

            keys = [rng.randrange(cql_rows) for _ in range(cql_ops)]
            bufs = [exec_frames(keys[c0:c0 + window])
                    for c0 in range(0, len(keys), window)]

            def sweep():
                for buf, c0 in zip(bufs, range(0, len(keys), window)):
                    sock.sendall(buf)
                    for _ in range(len(keys[c0:c0 + window])):
                        recv_frame()

            sweep()  # warm
            t0 = time.perf_counter()
            sweep()
            native_dt = time.perf_counter() - t0
            batch = QLProcessor.execute_wire_point_batch
            QLProcessor.execute_wire_point_batch = \
                lambda self, items: [None] * len(items)
            try:
                t0 = time.perf_counter()
                sweep()
                py_dt = time.perf_counter() - t0
            finally:
                QLProcessor.execute_wire_point_batch = batch
            sock.close()
            cql.shutdown()
            out.append({
                "metric": "ycql_native_point_select_ops_per_sec",
                "value": round(cql_ops / native_dt, 1),
                "unit": f"prepared point SELECT ops/s (native batch "
                        f"path, window {window}, RF=3)",
                "vs_baseline": None,
                "python_per_op_ops_per_sec": round(cql_ops / py_dt, 1),
                "speedup_vs_python": round(py_dt / native_dt, 2),
            })
        finally:
            mc.shutdown()
    return out


def bench_multisource(schema, tpu, cpu, max_ht, S, waves=4):
    """Post-write scans: after heavy update traffic the engine holds a
    live memtable + overlapping runs (the VERDICT-flagged shape real
    workloads spend most time in). Applies 4 waves of updates to 2% of
    keys (flushing between the first 3 — leaving 4 runs + a non-empty
    memtable), verifies results against the CPU oracle, and measures the
    steady-state aggregate scan against the single-run number measured
    beforehand. The delta overlay (storage.tpu_engine._overlay) is what
    keeps this a pure device scan; its one-time build cost is reported
    separately."""
    from yugabyte_db_tpu.models.partition import compute_hash_code
    from yugabyte_db_tpu.storage.row_version import RowVersion

    def spec(rht, lo=-500_000):
        return S.ScanSpec(
            read_ht=rht, predicates=[S.Predicate("d", ">=", lo)],
            aggregates=[S.AggSpec("count", None), S.AggSpec("sum", "a"),
                        S.AggSpec("min", "a"), S.AggSpec("max", "a")])

    tpu.scan(spec(max_ht + 1))
    t_single = _median(lambda: tpu.scan(spec(max_ht + 1)))

    rng = random.Random(5)
    cid = {c.name: c.col_id for c in schema.value_columns}
    ht = max_ht
    for wave in range(waves):
        batch = []
        for _ in range(NUM_KEYS // 50):
            i = rng.randrange(NUM_KEYS)
            ht += 1
            key = schema.encode_primary_key(
                {"k": f"user{i:06d}", "r": i % 7},
                compute_hash_code(schema, {"k": f"user{i:06d}"}))
            batch.append(RowVersion(
                key, ht=ht,
                columns={cid["d"]: rng.randrange(-10**6, 10**6)}))
        tpu.apply(batch)
        cpu.apply(batch)
        if wave < waves - 1:
            tpu.flush()
            cpu.flush()

    a = cpu.scan(spec(ht + 1))
    t0 = time.perf_counter()
    b = tpu.scan(spec(ht + 1))  # first scan pays the full overlay build
    t_first_build = time.perf_counter() - t0
    assert a.rows == b.rows, (a.rows, b.rows)
    t_multi = _median(lambda: tpu.scan(spec(ht + 1)))

    # Steady state: one more memtable-only write wave, then the overlay
    # advances INCREMENTALLY by the memtable delta (versions_since) —
    # this is the recurring per-wave cost, the number that was 899ms
    # when every wave re-collected the whole dirty set.
    batch = []
    for _ in range(NUM_KEYS // 50):
        i = rng.randrange(NUM_KEYS)
        ht += 1
        key = schema.encode_primary_key(
            {"k": f"user{i:06d}", "r": i % 7},
            compute_hash_code(schema, {"k": f"user{i:06d}"}))
        batch.append(RowVersion(
            key, ht=ht, columns={cid["d"]: rng.randrange(-10**6, 10**6)}))
    tpu.apply(batch)
    cpu.apply(batch)
    t0 = time.perf_counter()
    tpu._overlay(tpu.memtable)  # the delta apply, isolated from the scan
    t_delta = time.perf_counter() - t0
    a = cpu.scan(spec(ht + 1))
    b = tpu.scan(spec(ht + 1))
    assert a.rows == b.rows, (a.rows, b.rows)

    versions = sum(t.crun.num_versions for t in tpu.runs) + \
        tpu.memtable.num_versions
    return {
        "metric": "postwrite_scan_rows_per_sec",
        "value": round(versions / t_multi, 1),
        "unit": (f"rows/s (memtable + {len(tpu.runs)} overlapping runs, "
                 "single aggregate scan)"),
        "vs_baseline": round(
            (versions / t_multi) / CPP_NODE_SCAN_ROWS_S, 2),
        "vs_single_run": round(t_single / t_multi, 2),
        "latency_ms": round(t_multi * 1000, 1),
        "overlay_build_ms": round(t_delta * 1000, 1),
        "overlay_first_build_ms": round(t_first_build * 1000, 1),
    }


def bench_oversubscribed(schema, rows, max_ht, make_engine, S, parts=4,
                         rounds=3):
    """Working set ≈ 4× the HBM budget: four single-run engines share
    the process-wide residency cache with ``--tpu_hbm_budget_bytes``
    shrunk to about one run's planes, so each round-robin scan
    demand-re-uploads what the previous scans evicted (the RocksDB
    block-cache oversubscription shape). End-to-end and honest: the
    measured time includes every re-upload."""
    from yugabyte_db_tpu.storage.residency import hbm_cache
    from yugabyte_db_tpu.utils.flags import FLAGS

    def spec():
        return S.ScanSpec(
            read_ht=max_ht + 1,
            aggregates=[S.AggSpec("count", None), S.AggSpec("sum", "a"),
                        S.AggSpec("min", "a"), S.AggSpec("max", "a")])

    chunk = len(rows) // parts
    engines = []
    versions = 0
    for p in range(parts):
        e = make_engine("tpu", schema, {"rows_per_block": 2048})
        e.apply(rows[p * chunk:(p + 1) * chunk])
        e.flush()
        engines.append(e)
        versions += sum(t.crun.num_versions for t in e.runs)
    total_planes = sum(t._nbytes_hint() for e in engines for t in e.runs)
    cache = hbm_cache()
    old_budget = FLAGS.get("tpu_hbm_budget_bytes")
    FLAGS.set("tpu_hbm_budget_bytes", max(total_planes // parts, 1))
    try:
        for e in engines:  # compile warmup (first upload included below)
            e.scan(spec())
        m0 = cache.stats()["misses"]
        u0 = cache.stats()["demand_upload_bytes"]
        t0 = time.perf_counter()
        for _ in range(rounds):
            for e in engines:
                e.scan(spec())
        dt = time.perf_counter() - t0
        st = cache.stats()
        churn = st["misses"] - m0
        upload_mb = (st["demand_upload_bytes"] - u0) / 1e6
        # Compressed-plane accounting: how much smaller each demand
        # re-upload is than the plain format would have been
        # (--tpu_plane_encoding). Ratio < 1.0 is budget headroom.
        enc_b = sum(e.plane_stats()["encoded_bytes"] for e in engines)
        log_b = sum(e.plane_stats()["logical_bytes"] for e in engines)
        enc_ratio = round(enc_b / log_b, 3) if log_b else 1.0
    finally:
        FLAGS.set("tpu_hbm_budget_bytes", old_budget)
        for e in engines:
            e.close()
    return {
        "metric": "oversubscribed_scan_rows_per_sec",
        "value": round(versions * rounds / dt, 1),
        "unit": (f"rows/s ({parts} single-run engines round-robin, "
                 f"budget = working set / {parts})"),
        "vs_baseline": round(
            (versions * rounds / dt) / CPP_NODE_SCAN_ROWS_S, 2),
        "demand_reuploads": churn,
        "demand_upload_mb": round(upload_mb, 1),
        "plane_encoded_ratio": enc_ratio,
        "latency_ms": round(dt * 1000 / (parts * rounds), 1),
    }


def bench_oversubscribed_friendly(make_engine, S, parts=4, rounds=3,
                                  n=None):
    """The oversubscription shape on dictionary/RLE-friendly columns
    (low-cardinality strings, long int runs, small per-block deltas) —
    the workloads compressed planes exist for. Measures the SAME budget
    twice: --tpu_plane_encoding=auto (compressed re-uploads) then =off
    (plain re-uploads), and reports the re-upload byte reduction."""
    import random as _r

    from yugabyte_db_tpu.models.datatypes import DataType
    from yugabyte_db_tpu.models.partition import compute_hash_code
    from yugabyte_db_tpu.models.schema import (
        ColumnKind, ColumnSchema, Schema,
    )
    from yugabyte_db_tpu.storage.residency import hbm_cache
    from yugabyte_db_tpu.storage.row_version import RowVersion
    from yugabyte_db_tpu.utils.flags import FLAGS

    n = n or max(NUM_KEYS // 2, 20_000)
    schema = Schema([
        ColumnSchema("k", DataType.STRING, ColumnKind.HASH),
        ColumnSchema("r", DataType.INT64, ColumnKind.RANGE),
        ColumnSchema("city", DataType.STRING),
        ColumnSchema("grp", DataType.INT32),
        ColumnSchema("seq", DataType.INT32),
    ], table_id="bench_enc")
    cid = {c.name: c.col_id for c in schema.columns}
    cities = [f"city{j:03d}" for j in range(64)]
    rng = _r.Random(13)
    rows = []
    ht = 100
    for i in range(n):
        key = schema.encode_primary_key(
            {"k": f"user{i:06d}", "r": i % 7},
            compute_hash_code(schema, {"k": f"user{i:06d}"}))
        ht += 1
        rows.append(RowVersion(key, ht=ht, liveness=True, columns={
            cid["city"]: rng.choice(cities),
            cid["grp"]: (i // 4096) * 1_000_000,
            cid["seq"]: i % 10_000,
        }))

    def spec():
        return S.ScanSpec(
            read_ht=ht + 1,
            predicates=[S.Predicate("city", "<", "city032")],
            aggregates=[S.AggSpec("count", None), S.AggSpec("sum", "grp"),
                        S.AggSpec("max", "seq")])

    cache = hbm_cache()
    old_budget = FLAGS.get("tpu_hbm_budget_bytes")
    old_enc = FLAGS.get("tpu_plane_encoding")
    chunk = len(rows) // parts
    engines = []
    versions = 0
    try:
        for p in range(parts):
            e = make_engine("tpu", schema, {"rows_per_block": 2048})
            e.apply(rows[p * chunk:(p + 1) * chunk])
            e.flush()
            engines.append(e)
            versions += sum(t.crun.num_versions for t in e.runs)
        total_planes = sum(t._nbytes_hint()
                           for e in engines for t in e.runs)
        FLAGS.set("tpu_hbm_budget_bytes", max(total_planes // parts, 1))

        def measure():
            for e in engines:  # warmup (compiles + first uploads)
                e.scan(spec())
            u0 = cache.stats()["demand_upload_bytes"]
            t0 = time.perf_counter()
            for _ in range(rounds):
                for e in engines:
                    e.scan(spec())
            dt = time.perf_counter() - t0
            return cache.stats()["demand_upload_bytes"] - u0, dt

        FLAGS.set("tpu_plane_encoding", "auto")
        for e in engines:
            for t in e.runs:
                t._dev_nbytes_hint = None
                t.invalidate_device()
        up_enc, dt_enc = measure()
        FLAGS.set("tpu_plane_encoding", "off")
        for e in engines:
            for t in e.runs:
                t._dev_nbytes_hint = None
                t.invalidate_device()
        up_plain, dt_plain = measure()
    finally:
        FLAGS.set("tpu_hbm_budget_bytes", old_budget)
        FLAGS.set("tpu_plane_encoding", old_enc)
        for e in engines:
            e.close()
    return {
        "metric": "oversubscribed_friendly_scan_rows_per_sec",
        "value": round(versions * rounds / dt_enc, 1),
        "unit": (f"rows/s ({parts} engines round-robin, dict/RLE-friendly "
                 f"columns, budget = working set / {parts}, encoded)"),
        "vs_baseline": round(
            (versions * rounds / dt_enc) / CPP_NODE_SCAN_ROWS_S, 2),
        "vs_plain_planes": round(dt_plain / dt_enc, 2),
        "demand_upload_mb": round(up_enc / 1e6, 1),
        "demand_upload_mb_plain": round(up_plain / 1e6, 1),
        "reupload_reduction_x": round(up_plain / up_enc, 2)
        if up_enc else None,
    }


def bench_tpch(make_engine):
    from yugabyte_db_tpu.yql.pgsql import tpch

    n = max(NUM_KEYS, 100_000)
    schema = tpch.lineitem_schema()
    tpu = make_engine("tpu", schema)
    cpu = make_engine("cpu", schema)
    ht = tpch.load_engine(tpu, schema, n)
    tpch.load_engine(cpu, schema, n)
    import collections

    out = []
    for name, build in (("tpch_q1", tpch.q1_spec), ("tpch_q6", tpch.q6_spec)):
        spec = build(ht + 1)
        a = cpu.scan(spec)
        b = tpu.scan(spec)
        assert a.rows == b.rows, name
        tdt = _median(lambda: tpu.scan(spec))
        t0 = time.perf_counter()
        cpu.scan(spec)
        cdt = time.perf_counter() - t0
        # Server throughput: concurrent copies of the query pipelined
        # through the async batch API (single-scan latency is one
        # synchronous fetch on the link and rides in the details).
        # vs_cpu_engine compares THROUGHPUT on the same 80-query
        # workload: the single-thread oracle gains nothing from
        # concurrency, so its serial per-query time extrapolates
        # linearly (same convention as bench_aggregate).
        batches = [[build(ht + 1) for _ in range(8)] for _ in range(10)]
        q = collections.deque()
        for bt in batches[:4]:
            q.append(tpu.scan_batch_async(bt))
        while q:
            q.popleft().finish()
        t0 = time.perf_counter()
        for bt in batches:
            q.append(tpu.scan_batch_async(bt))
            if len(q) > 4:
                q.popleft().finish()
        while q:
            q.popleft().finish()
        pdt = time.perf_counter() - t0
        # Two metrics, honestly named: the pipelined number measures a
        # different quantity (8 concurrent queries, depth-4 pipeline)
        # than the single-query scan rate, so it must not ship under
        # the plain rows_per_sec name history already tracks.
        out.append({
            "metric": f"{name}_pipelined_rows_per_sec",
            "value": round(n * 80 / pdt, 1),
            "unit": "rows/s (8 concurrent queries, depth-4 pipeline)",
            "vs_baseline": None,  # no TPC-H numbers exist in-reference
            "vs_cpu_engine": round(cdt * 80 / pdt, 2),
            "single_query_latency_ms": round(tdt * 1000, 1),
        })
        out.append({
            "metric": f"{name}_rows_per_sec",
            "value": round(n / tdt, 1),
            "unit": "rows/s (single query, synchronous)",
            "vs_baseline": None,
            "vs_cpu_engine": round(cdt / tdt, 2),
            "single_query_latency_ms": round(tdt * 1000, 1),
        })
    return out


def bench_kernel_scan(n_rows=16 * 1024 * 1024, R=2048, iters=12):
    """Device-resident scan-kernel throughput at HBM scale: 10M+ rows
    pre-staged as columnar planes in HBM, jit-warm, one full-run
    aggregate dispatch per iteration. Reports rows/s AND achieved GB/s
    (bytes = the planes the kernel actually reads per pass) for the
    flat path and the segmented MVCC-resolve path. The per-dispatch
    link overhead is removed by differencing a 1-dispatch and an
    N-dispatch timing (both end in one blocking fetch)."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from yugabyte_db_tpu.ops import agg_fold
    from yugabyte_db_tpu.ops import scan as dscan
    from yugabyte_db_tpu.utils import planes as P

    B = n_rows // R
    rng = np.random.default_rng(7)

    # Synthetic planes, directly in device layout (building 16M rows
    # through the memtable would measure Python, not the kernel).
    idx = np.arange(n_rows, dtype=np.int64)
    # MVCC shape: 2 versions per key group, newest first.
    ht_vals = (n_rows - (idx // 2) * 2) - (idx % 2)
    ht_hi, ht_lo = P.ht_to_planes(ht_vals)
    maxhi, maxlo = P.scalar_ht_planes((1 << 62))
    a_vals = rng.integers(-10**12, 10**12, n_rows, dtype=np.int64)
    a_hi, a_lo = P.i64_to_ordered_planes(a_vals)
    d_vals = rng.integers(-10**6, 10**6, n_rows, dtype=np.int32)

    def shape(x, extra=()):
        return np.ascontiguousarray(x.reshape((B, R) + tuple(extra)))

    dev = jax.devices()[0]

    def up(x):
        return jax.device_put(x, dev)

    arrays = {
        "valid": up(np.ones((B, R), dtype=bool)),
        "tomb": up(np.zeros((B, R), dtype=bool)),
        "live": up(np.ones((B, R), dtype=bool)),
        "group_start": up(shape((idx % 2 == 0))),
        "ht_hi": up(shape(ht_hi)),
        "ht_lo": up(shape(ht_lo)),
        "exp_hi": up(np.full((B, R), maxhi, dtype=np.int32)),
        "exp_lo": up(np.full((B, R), maxlo, dtype=np.int32)),
        "cols": {
            1: {"set": up(np.ones((B, R), dtype=bool)),
                "isnull": up(np.zeros((B, R), dtype=bool)),
                "cmp": up(shape(np.stack([a_hi, a_lo], axis=-1), (2,)))},
            2: {"set": up(np.ones((B, R), dtype=bool)),
                "isnull": up(np.zeros((B, R), dtype=bool)),
                "cmp": up(shape(d_vals, (1,)))},
        },
    }

    K = agg_fold.safe_window_blocks(R, agg_fold.FULL_WINDOW_BLOCKS)
    cols = (dscan.ColSig(1, "i64"), dscan.ColSig(2, "i32"))
    preds = (dscan.PredSig(2, "i32", ">="),)
    aggs = (dscan.AggSig("count", None, None),
            dscan.AggSig("sum", 1, "i64"),
            dscan.AggSig("max", 1, "i64"))
    r_hi, r_lo = P.scalar_ht_planes(1 << 61)
    e_hi, e_lo = P.scalar_ht_planes(1 << 61)
    pred_lits = (jnp.int32(-500_000),)
    W = B // K

    # Expected values (host numpy) for a correctness pin.
    flat_mask = d_vals >= -500_000
    mvcc_mask = flat_mask & ((idx % 2) == 0)  # newest version per group

    from yugabyte_db_tpu.ops import flat_fold, lookback_fold

    out = []
    for label, flat, mask in (("flat", True, flat_mask),
                              ("mvcc", False, mvcc_mask)):
        sig = dscan.ScanSig(B=B, R=R, K=K, cols=cols, preds=preds,
                            aggs=aggs, apply_preds=True, flat=flat,
                            lookback=0 if flat else 2)
        # The engine's fused full-array programs (flat_fold for flat
        # runs; bounded-lookback resolve for multi-version runs — the
        # route _plan_device_aggregate takes for this run shape).
        fn = (flat_fold.compiled_flat_aggregate(sig) if flat
              else lookback_fold.compiled_lookback_aggregate(sig))
        args = (arrays, jnp.int32(0), jnp.int32(n_rows),
                jnp.int32(r_hi), jnp.int32(r_lo),
                jnp.int32(e_hi), jnp.int32(e_lo), pred_lits)
        ivec, fvec = fn(*args)
        jax.block_until_ready(ivec)
        acc, _scanned = agg_fold.unpack(aggs, ivec, fvec)
        got_count = agg_fold.finalize(aggs[0], acc[0], "count")
        got_sum = agg_fold.finalize(aggs[1], acc[1], "sum")
        assert got_count == int(mask.sum()), (label, got_count)
        assert got_sum == int(a_vals[mask].sum()), label

        def run_n(n):
            t0 = time.perf_counter()
            res = None
            for _ in range(n):
                res = fn(*args)
            jax.block_until_ready(res)
            return time.perf_counter() - t0

        run_n(2)  # warm
        t1 = min(run_n(1) for _ in range(3))
        tm = min(run_n(iters) for _ in range(3))
        t_pass = max((tm - t1) / (iters - 1), 1e-9)

        bytes_per_pass = sum(
            x.nbytes for x in (
                arrays["valid"], arrays["tomb"], arrays["live"],
                arrays["ht_hi"], arrays["ht_lo"], arrays["exp_hi"],
                arrays["exp_lo"],
                arrays["cols"][1]["set"], arrays["cols"][1]["isnull"],
                arrays["cols"][1]["cmp"],
                arrays["cols"][2]["set"], arrays["cols"][2]["isnull"],
                arrays["cols"][2]["cmp"]))
        if not flat:
            # Free the ~600MB of staged planes before later benches: the
            # residue skews their upload-bound phases (measured on the
            # engine write bench).
            for leaf in jax.tree.leaves(arrays):
                leaf.delete()
        if not flat:
            bytes_per_pass += arrays["group_start"].nbytes
        out.append({
            "metric": f"kernel_{label}_scan_rows_per_sec",
            "value": round(n_rows / t_pass, 1),
            "unit": (f"rows/s ({n_rows/1e6:.0f}M-row HBM-resident run, "
                     "single full-run aggregate dispatch)"),
            "vs_baseline": round(
                (n_rows / t_pass) / CPP_NODE_SCAN_ROWS_S, 2),
            "hbm_gb_per_sec": round(bytes_per_pass / t_pass / 1e9, 1),
            "pass_ms": round(t_pass * 1000, 2),
        })
    return out


def bench_write(schema, rows, make_engine):
    eng = make_engine("tpu", schema, {"rows_per_block": 2048})

    def run():
        for i in range(0, len(rows), 4096):
            eng.apply(rows[i:i + 4096])
        eng.flush()

    t0 = time.perf_counter()
    run()
    dt = time.perf_counter() - t0
    rows_s = len(rows) / dt
    return {
        "metric": "batched_write_rows_per_sec",
        "value": round(rows_s, 1),
        "unit": "rows/s (engine apply+flush)",
        "vs_baseline": round(rows_s / CPP_NODE_BATCH_WRITE_ROWS_S, 2),
    }


def bench_cluster_write(n_rows=60_000, writers=4, batch=256):
    """Cluster write path end-to-end: MiniCluster RF=3, concurrent batched
    sessions -> tserver write RPC -> WAL append -> Raft replication to 2
    followers -> majority ack -> engine apply. The reference's comparable
    number is CassandraBatchKeyValue: 258K ops/s across 3 nodes => ~86K
    rows/s per node (this is ONE in-process 3-tserver cluster on one
    machine, fsync off — the reference bench also rode the SSD page
    cache). A real multi-process topology exists (tools.yb_ctl spawns
    1 master + 3 tserver processes; the same sessions drive it over
    TCP) but measures LOWER than in-process — the per-RPC socket/codec
    cost outweighs the extra interpreters — so the in-process number is
    the honest best configuration and stays comparable across rounds."""
    import tempfile
    import threading

    from yugabyte_db_tpu.client.session import YBSession
    from yugabyte_db_tpu.integration.mini_cluster import MiniCluster
    from yugabyte_db_tpu.models.datatypes import DataType
    from yugabyte_db_tpu.models.schema import ColumnKind, ColumnSchema

    with tempfile.TemporaryDirectory() as root:
        mc = MiniCluster(root, num_tservers=3).start()
        try:
            mc.wait_tservers_registered()
            client = mc.client()
            client.create_table("kv", [
                ColumnSchema("k", DataType.STRING, ColumnKind.HASH),
                ColumnSchema("v", DataType.STRING),
            ], num_tablets=6)
            table = client.open_table("kv")
            warm = YBSession(mc.client("warm"))
            for i in range(2000):
                warm.insert(table, {"k": f"w{i:08d}", "v": f"val{i}"})
                if warm.pending_ops >= batch:
                    warm.flush()
            warm.flush()

            per = n_rows // writers
            errors = []
            t0 = time.perf_counter()

            def worker(w):
                try:
                    s = YBSession(mc.client(f"w{w}"))
                    for i in range(w * per, (w + 1) * per):
                        s.insert(table, {"k": f"key{i:08d}", "v": f"val{i}"})
                        if s.pending_ops >= batch:
                            s.flush()
                    s.flush()
                except Exception as e:  # surfaced after join
                    errors.append(e)

            threads = [threading.Thread(target=worker, args=(w,))
                       for w in range(writers)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            dt = time.perf_counter() - t0
            if errors:
                raise errors[0]
            rows_s = per * writers / dt
        finally:
            mc.shutdown()
    return {
        "metric": "cluster_write_rows_per_sec",
        "value": round(rows_s, 1),
        "unit": (f"rows/s (RF=3 Raft+WAL, {writers} writers, "
                 f"batch {batch})"),
        "vs_baseline": round(rows_s / CPP_NODE_BATCH_WRITE_ROWS_S, 2),
    }


def bench_ycsb_a_cluster(n_keys=20_000, n_ops=24_000, workers=4,
                         batch=64, theta=0.99):
    """YCSB-A at cluster scope: 50/50 zipfian point-read/update through
    the full RF=3 write path (session batcher -> tserver RPC -> WAL ->
    Raft group commit -> commit-ack) — the mixed workload the write-path
    overhaul targets, where writes previously throttled the whole mix.
    Baseline: YCSB-A 107,120 ops/s across 3 nodes => ~35.7K per node
    (docs/yb-perf-v1.0.7.md:585-601)."""
    import bisect
    import tempfile
    import threading

    from yugabyte_db_tpu.client.session import YBSession
    from yugabyte_db_tpu.integration.mini_cluster import MiniCluster
    from yugabyte_db_tpu.models.datatypes import DataType
    from yugabyte_db_tpu.models.schema import ColumnKind, ColumnSchema

    # Zipfian(theta) CDF over the keyspace — YCSB's request distribution.
    weights = [1.0 / (i + 1) ** theta for i in range(n_keys)]
    cdf = []
    acc = 0.0
    for w in weights:
        acc += w
        cdf.append(acc)

    def zipf(rng):
        return bisect.bisect_left(cdf, rng.random() * acc)

    with tempfile.TemporaryDirectory() as root:
        mc = MiniCluster(root, num_tservers=3).start()
        try:
            mc.wait_tservers_registered()
            client = mc.client()
            client.create_table("ycsba", [
                ColumnSchema("k", DataType.STRING, ColumnKind.HASH),
                ColumnSchema("v", DataType.STRING),
            ], num_tablets=6)
            table = client.open_table("ycsba")
            load = YBSession(mc.client("load"))
            for i in range(n_keys):
                load.insert(table, {"k": f"user{i:08d}", "v": f"val{i}"})
                if load.pending_ops >= 256:
                    load.flush()
            load.flush()

            per = n_ops // workers
            errors = []

            def worker(w):
                try:
                    rng = random.Random(100 + w)
                    s = YBSession(mc.client(f"mix{w}"))
                    done = 0
                    while done < per:
                        half = min(batch, per - done) // 2 or 1
                        for _ in range(half):
                            i = zipf(rng)
                            s.insert(table, {"k": f"user{i:08d}",
                                             "v": f"v{rng.random():.6f}"})
                        s.flush()
                        got = s.get_many(table, [
                            {"k": f"user{zipf(rng):08d}"}
                            for _ in range(half)])
                        assert all(r is not None for r in got)
                        done += 2 * half
                except Exception as e:  # surfaced after join
                    errors.append(e)

            t0 = time.perf_counter()
            threads = [threading.Thread(target=worker, args=(w,))
                       for w in range(workers)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            dt = time.perf_counter() - t0
            if errors:
                raise errors[0]
        finally:
            mc.shutdown()
    return {
        "metric": "ycsb_a_mixed_ops_per_sec",
        "value": round(n_ops / dt, 1),
        "unit": (f"ops/s (50/50 zipfian read/write, RF=3 cluster, "
                 f"{workers} sessions, batch {batch})"),
        "vs_baseline": round(n_ops / dt / (107_120 / 3), 2),
    }


def bench_traffic(seed=1234):
    """Sustained-traffic replay: the seeded mixed-protocol sweep
    (YCSB-A/B/E + TPC-H Q1/Q6 + Redis, zipfian hot keys) against a live
    RF=3 cluster WHILE both seed tablets split, a follower rolls, and
    the leader balancer moves leaders — the elasticity scenario, not a
    steady-state ceiling. Emits the sweep's TRAFFIC_METRICS line
    (per-protocol p50/p99 + ops/s, splits fired, leader moves) and
    returns it as the section sub-metric."""
    import tempfile

    from yugabyte_db_tpu.integration.traffic_sweep import run_sweep

    with tempfile.TemporaryDirectory() as root:
        out = run_sweep(root, seed)
    print("TRAFFIC_METRICS " + json.dumps(out, sort_keys=True))
    return {
        "metric": "traffic",
        "value": out["ops_per_sec"],
        "unit": ("ops/s (mixed YCSB/TPC-H/Redis under splits + "
                 "rolling restart + leader rebalance, RF=3)"),
        "splits_fired": out["splits_fired"],
        "leader_moves": out["leader_moves"],
        "protocols": out["protocols"],
    }


def bench_device_flush(schema, rows, make_engine, n=65_536):
    """Flush cost after the device-side overhaul: one memtable of n rows
    built into a sorted columnar run. The device path stages the op log,
    computes the sort permutation host-side, and materializes the padded
    planes in one jitted scatter (ops/flush.py) — seeding HBM residency
    with no separate upload; the host path is the pre-overhaul numpy /
    native build, timed on identical contents."""
    from yugabyte_db_tpu.utils.flags import FLAGS
    from yugabyte_db_tpu.utils.metrics import flush_path_count

    work = rows[:n]
    old = FLAGS.get("tpu_device_flush")

    def timed_flush(device):
        FLAGS.set("tpu_device_flush", device)
        eng = make_engine("tpu", schema, {"rows_per_block": 2048})
        eng.apply(work)
        t0 = time.perf_counter()
        eng.flush()
        dt = time.perf_counter() - t0
        eng.close()
        return dt

    try:
        timed_flush(True)  # warm the scatter compile for this bucket
        d0 = flush_path_count("device")
        dev_dt = min(timed_flush(True) for _ in range(3))
        assert flush_path_count("device") == d0 + 3, \
            "device flush fell back to host"
        host_dt = min(timed_flush(False) for _ in range(2))
    finally:
        FLAGS.set("tpu_device_flush", old)
    return {
        "metric": "postflush_device_flush_ms",
        "value": round(dev_dt * 1000, 1),
        "unit": f"ms (device-path memtable flush, {len(work)} rows)",
        "vs_baseline": None,  # no comparable in-reference microbenchmark
        "host_flush_ms": round(host_dt * 1000, 1),
        "speedup_vs_host": round(host_dt / dev_dt, 2),
        "rows_per_sec": round(len(work) / dev_dt, 1),
    }


def bench_compact(schema, rows, max_ht, make_engine):
    """4-run merge with REAL history GC: base load + 3 update/delete
    waves over the same keyspace (multi-version groups, tombstones),
    compacted at the max cutoff — the shape update traffic actually
    leaves behind (a disjoint-run merge would never exercise the
    retention filter). Output content is pinned to the CPU oracle."""
    from yugabyte_db_tpu.models.partition import compute_hash_code
    from yugabyte_db_tpu.storage.row_version import RowVersion

    cid = {c.name: c.col_id for c in schema.value_columns}
    per_wave = max(1, int(NUM_KEYS * 0.35))

    def load(name):
        e = make_engine(name, schema, {"rows_per_block": 2048})
        e.apply(rows)
        e.flush()
        rng = random.Random(9)
        ht = max_ht
        for _wave in range(3):
            batch = []
            for _ in range(per_wave):
                i = rng.randrange(NUM_KEYS)
                ht += 1
                key = schema.encode_primary_key(
                    {"k": f"user{i:06d}", "r": i % 7},
                    compute_hash_code(schema, {"k": f"user{i:06d}"}))
                if rng.random() < 0.1:
                    batch.append(RowVersion(key, ht=ht, tombstone=True))
                else:
                    batch.append(RowVersion(
                        key, ht=ht,
                        columns={cid["d"]: rng.randrange(-10**6, 10**6)}))
            e.apply(batch)
            e.flush()
        return e, ht

    n_versions = len(rows) + 3 * per_wave
    tpu, cut = load("tpu")
    tpu.compact(cut)  # includes one-time compile/warm costs
    tpu2, cut = load("tpu")
    t0 = time.perf_counter()
    tpu2.compact(cut)
    tdt = time.perf_counter() - t0
    cpu, cut2 = load("cpu")
    t0 = time.perf_counter()
    cpu.compact(cut2)
    cdt = time.perf_counter() - t0
    ca, cb = cpu.dump_entries(), tpu2.dump_entries()
    assert [k for k, _ in ca] == [k for k, _ in cb]
    for (k1, v1), (_k2, v2) in zip(ca, cb):
        assert [(r.ht, r.tombstone, r.columns) for r in v1] == \
            [(r.ht, r.tombstone, r.columns) for r in v2], k1
    return {
        "metric": "compaction_versions_per_sec",
        "value": round(n_versions / tdt, 1),
        "unit": "versions/s (4-run merge + full history GC)",
        "vs_baseline": None,  # no comparable in-reference microbenchmark
        "vs_cpu_engine": round(cdt / tdt, 2),
    }


def _section_subprocess(name, timeout_s=1800):
    """Run one bench section isolated in a child interpreter (via
    ``--only name``): a native crash — the known in-process MiniCluster
    segfault under bench_cluster_write — costs that section its rc, not
    the whole headline run. Returns (sub-metric dicts, rc)."""
    import subprocess

    cmd = [sys.executable, __file__, "--only", name, str(NUM_KEYS)]
    try:
        proc = subprocess.run(cmd, capture_output=True, text=True,
                              timeout=timeout_s)
        rc, out = proc.returncode, proc.stdout
    except subprocess.TimeoutExpired as e:
        rc, out = -1, (e.stdout or "")
    subs = []
    for line in out.splitlines():
        if not line.startswith("# "):
            continue
        try:
            d = json.loads(line[2:])
        except ValueError:
            continue
        if isinstance(d, dict) and d.get("metric") not in (
                None, "jit_compiles_per_entry", "device"):
            subs.append(d)
    if not subs:
        subs = [{"metric": name, "error": f"section subprocess rc={rc}"}]
    return subs, rc


# Sections whose numbers are device metrics (they build a "tpu" engine or
# run device kernels): refused on any backend but the TPU, because the
# same code runs on XLA's CPU backend and would print CPU timings under
# device metric names.
_DEVICE_SECTIONS = frozenset((
    "aggregate", "ycsb_e", "point_read", "ycsb_mix", "multisource",
    "oversubscribed", "oversubscribed_friendly", "kernel_scan", "tpch",
    "write", "device_flush", "compact", "traffic"))
# Sections that consume the shared engine pair bench_aggregate builds.
_DEP_AGG = ("aggregate", "ycsb_e", "point_read", "multisource")
# Sections that consume the shared (schema, rows) dataset.
_NEED_ROWS = _DEP_AGG + ("oversubscribed", "write", "device_flush",
                         "compact")


def main() -> int:
    import yugabyte_db_tpu.storage.tpu_engine  # noqa: F401 registers 'tpu'
    from yugabyte_db_tpu import storage as S
    from yugabyte_db_tpu.storage import make_engine
    from yugabyte_db_tpu.utils import jitting

    # Exported to the section children below, so one command shares one
    # compile cache across its processes.
    jitting.enable_compile_cache()
    if COMPILE_WITNESS or CWITNESS_OUT:
        jitting.enable_compile_witness()

    def want(name):
        return (ONLY is None or name in ONLY) and name not in SKIP

    # name -> rc (0 ok; 1 exception; 2 refused; <0 signal/timeout)
    sections = {}
    subs = []
    device = {}

    def refused(name) -> bool:
        if name not in _DEVICE_SECTIONS or device["platform"] == "tpu":
            return False
        sections[name] = 2
        subs.append({"metric": name, "error":
                     f"refused: device section on the "
                     f"{device['platform']} backend"})
        return True

    def run(name, fn):
        if not want(name) or refused(name):
            return
        try:
            out = fn()
            sections[name] = 0
        except Exception as e:  # noqa: BLE001 — a section must not kill the run
            sections[name] = 1
            out = {"metric": name, "error": repr(e)}
        subs.extend(out if isinstance(out, (list, tuple)) else [out])

    # Cluster sections first (host-CPU-bound: they measure low after the
    # TPU workloads' background threads/memory are resident). On a full
    # run each one is isolated in a child interpreter, and they run
    # BEFORE this process initialises its JAX backend: a chip belongs to
    # one process, and a child that needs it ("traffic") would fail or
    # hang under a parent that holds it. With --only we ARE the child
    # (or the user asked for exactly this section): in-process, below.
    cluster_sections = (("cluster_write", bench_cluster_write),
                        ("ycsb_a_cluster", bench_ycsb_a_cluster),
                        ("traffic", bench_traffic))
    if ONLY is None:
        for cname, _cfn in cluster_sections:
            if want(cname):
                csubs, rc = _section_subprocess(cname)
                sections[cname] = rc
                subs.extend(csubs)

    import jax

    devs = jax.devices()
    device.update(platform=devs[0].platform, kind=devs[0].device_kind,
                  count=len(devs), jax=jax.__version__)
    print("# " + json.dumps({"metric": "device", **device}))
    if ONLY is not None:
        for cname, cfn in cluster_sections:
            run(cname, cfn)

    schema = rows = max_ht = None
    if any(want(n) for n in _NEED_ROWS):
        from __graft_entry__ import _make_rows, _make_schema

        schema = _make_schema()
        rows, max_ht = _make_rows(schema, NUM_KEYS)

    tpu = cpu = headline = None
    if any(want(n) for n in _DEP_AGG) and not refused("aggregate"):
        try:
            tpu, cpu, versions, headline = bench_aggregate(
                schema, rows, max_ht, make_engine, S)
            sections["aggregate"] = 0
        except Exception as e:  # noqa: BLE001 — dependents degrade, run continues
            sections["aggregate"] = 1
            subs.append({"metric": "aggregate", "error": repr(e)})
    if tpu is not None:
        run("ycsb_e", lambda: bench_ycsb_e(schema, tpu, cpu, max_ht, S))
        run("point_read",
            lambda: bench_point_reads(schema, tpu, cpu, max_ht, S))
    run("ycsb_mix", lambda: bench_ycsb_mix(make_engine, S))
    run("index", bench_index)
    run("redis", bench_redis)
    run("serving_path", bench_serving_path)
    if tpu is not None:
        run("multisource",
            lambda: bench_multisource(schema, tpu, cpu, max_ht, S))
    run("oversubscribed",
        lambda: bench_oversubscribed(schema, rows, max_ht, make_engine, S))
    run("oversubscribed_friendly",
        lambda: bench_oversubscribed_friendly(make_engine, S))
    run("kernel_scan", bench_kernel_scan)
    run("tpch", lambda: bench_tpch(make_engine))
    run("write", lambda: bench_write(schema, rows, make_engine))
    run("device_flush",
        lambda: bench_device_flush(schema, rows, make_engine))
    run("compact", lambda: bench_compact(schema, rows, max_ht, make_engine))

    details = {}
    for sub in subs:
        print("# " + json.dumps(sub))
        details[sub["metric"]] = {k: v for k, v in sub.items()
                                  if k != "metric"}

    from yugabyte_db_tpu.utils import metrics
    compiles = metrics.jit_compiles()
    print("# " + json.dumps({"metric": "jit_compiles_per_entry",
                             "value": sum(compiles.values()),
                             "unit": "XLA compiles (whole suite)",
                             "per_entry": compiles}))
    if CWITNESS_OUT:
        from yugabyte_db_tpu.utils import jitting
        jitting.dump_compile_witness(CWITNESS_OUT)

    if headline is not None and want("aggregate"):
        headline["details"] = details
        headline["sections"] = sections
        headline["device"] = device
        headline["baseline_note"] = (
            "vs_baseline compares one chip against a calibrated C++-class "
            "16-vCPU reference NODE (~29K scanned rows/s/vCPU, BASELINE.md); "
            "vs_cpu_engine compares against the in-repo CPU oracle engine")
        print(json.dumps(headline))
    else:
        # Partial run (--only/--skip without the headline section):
        # still end with ONE machine-readable JSON line.
        print(json.dumps({"metric": "bench_sections", "device": device,
                          "sections": sections, "details": details}))
    return 1 if any(sections.values()) else 0


if __name__ == "__main__":
    sys.exit(main())
