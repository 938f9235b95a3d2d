"""Tracing tests: Trace/TRACE plumbing, RpczStore sampling, /rpcz
endpoint over the embedded webserver.

Reference test analog: src/yb/util/trace-test.cc + the rpcz handler of
src/yb/server/rpcz-path-handler.cc.
"""

import json
import threading
import urllib.request

from yugabyte_db_tpu.integration import MiniCluster
from yugabyte_db_tpu.models.datatypes import DataType
from yugabyte_db_tpu.models.schema import ColumnKind, ColumnSchema
from yugabyte_db_tpu.utils.trace import (TRACE, RpczStore, Trace,
                                         trace_request)

COLUMNS = [
    ColumnSchema("k", DataType.STRING, ColumnKind.HASH),
    ColumnSchema("v", DataType.INT64),
]


def test_trace_collects_messages_below_dispatch():
    def nested():
        TRACE("deep %d", 42)

    with trace_request("svc.method") as t:
        TRACE("start")
        nested()
    assert t.duration_us >= 0
    msgs = [m for _dt, m in t.entries]
    assert msgs == ["start", "deep 42"]
    d = t.dump()
    assert d["method"] == "svc.method" and len(d["messages"]) == 2


def test_trace_without_active_request_is_noop():
    TRACE("nobody listening")  # must not raise


def test_trace_is_context_isolated():
    errs = []

    def worker(i):
        with trace_request(f"m{i}") as t:
            for j in range(10):
                TRACE(f"w{i}-{j}")
        if [m for _d, m in t.entries] != [f"w{i}-{j}" for j in range(10)]:
            errs.append(i)

    threads = [threading.Thread(target=worker, args=(i,)) for i in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert not errs


def test_trace_message_cap():
    with trace_request("m") as t:
        for i in range(200):
            TRACE(f"msg{i}")
    assert len(t.entries) == 64
    assert t.dump()["dropped_messages"] == 136


def test_rpcz_store_recent_and_slow():
    store = RpczStore(recent_per_method=2, slow_threshold_us=1000)
    for i in range(5):
        t = Trace("a.b")
        t.finish()
        store.record(t)
    slow = Trace("a.b")
    slow.finish()
    slow.duration_us = 5000
    store.record(slow)
    d = store.dump()
    assert len(d["methods"]["a.b"]) == 2  # bounded per method
    assert len(d["slow"]) == 1 and d["slow"][0]["duration_us"] == 5000


def test_rpcz_endpoint_serves_request_traces(tmp_path):
    c = MiniCluster(str(tmp_path), num_masters=1, num_tservers=1).start()
    try:
        c.wait_tservers_registered()
        client = c.client()
        table = client.create_table("tr", COLUMNS, num_tablets=1,
                                    replication_factor=1)
        from yugabyte_db_tpu.client import YBSession
        s = YBSession(client)
        s.insert(table, {"k": "a", "v": 1})
        s.flush()
        from yugabyte_db_tpu.storage.scan_spec import ScanSpec
        s.scan(table, ScanSpec())

        addrs = c.start_webservers()
        ts_uuid = next(iter(c.tservers))
        host, port = addrs[ts_uuid]
        with urllib.request.urlopen(
                f"http://{host}:{port}/rpcz", timeout=5) as r:
            d = json.load(r)
        # The session's write pipeline admits via ts.write_admit
        # (two-phase); ts.write remains the one-shot form.
        assert "ts.write_admit" in d["methods"]
        assert "ts.scan" in d["methods"]
        write_sample = d["methods"]["ts.write_admit"][-1]
        assert write_sample["duration_us"] >= 0
        assert any("stamped" in m for m in write_sample["messages"])
        scan_sample = d["methods"]["ts.scan"][-1]
        assert any("row(s)" in m for m in scan_sample["messages"])
    finally:
        c.shutdown()


def test_trace_events_and_stacks():
    from yugabyte_db_tpu.utils.trace import (TRACE_EVENTS, dump_stacks,
                                             span)

    with trace_request("svc.method") as t:     # the ring is fed under a
        with span("unit-span", tablet="t1"):   # request's trace
            pass
    events = TRACE_EVENTS.dump()["traceEvents"]
    mine = [e for e in events if e["name"] == "unit-span"]
    assert mine and mine[-1]["ph"] == "X" and mine[-1]["dur"] >= 0
    assert mine[-1]["args"] == {"tablet": "t1", "trace_id": t.trace_id}
    stacks = dump_stacks()
    assert "MainThread" in stacks and "test_trace_events_and_stacks" in stacks


def test_tracing_json_over_http():
    import json
    import urllib.request

    from yugabyte_db_tpu.utils.metrics import MetricRegistry
    from yugabyte_db_tpu.server.webserver import Webserver

    ws = Webserver(MetricRegistry(), "trace-test")
    host, port = ws.start()
    try:
        data = json.loads(urllib.request.urlopen(
            f"http://{host}:{port}/tracing.json", timeout=5).read())
        assert "traceEvents" in data
        stacks = urllib.request.urlopen(
            f"http://{host}:{port}/stacks", timeout=5).read().decode()
        assert "thread" in stacks
    finally:
        ws.stop()
