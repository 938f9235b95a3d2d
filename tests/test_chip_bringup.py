"""What keeps the chip run honest (ISSUE 21): the compile cache can be placed
from outside, yb_ctl gives the chip to at most one process, and
``chip_smoke.py`` passes only on a TPU — or in a rehearsal that says so."""

import json
import os
import shutil
import subprocess
import sys

import pytest

from yugabyte_db_tpu.tools.yb_ctl import ClusterCtl
from yugabyte_db_tpu.utils import jitting

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO, "chip_smoke.py")


def _cache_probe(env_overrides: dict) -> dict:
    """enable_compile_cache() in a fresh interpreter that never
    initialises a backend; returns what it chose and what jax holds."""
    env = dict(os.environ)
    env.pop("JAX_PLATFORMS", None)
    env.pop("JAX_COMPILATION_CACHE_DIR", None)
    env.update(env_overrides)
    code = (
        "import json, os\n"
        "from yugabyte_db_tpu.utils.jitting import enable_compile_cache\n"
        "path = enable_compile_cache()\n"
        "import jax\n"
        "print(json.dumps({'path': path,\n"
        "  'jax_dir': jax.config.jax_compilation_cache_dir,\n"
        "  'env': os.environ.get('JAX_COMPILATION_CACHE_DIR'),\n"
        "  'min_s': jax.config.jax_persistent_cache_min_compile_time_secs}))\n")
    proc = subprocess.run([sys.executable, "-c", code], cwd=REPO, env=env,
                          capture_output=True, text=True, timeout=120)
    assert proc.returncode == 0, proc.stderr[-2000:]
    return json.loads(proc.stdout.strip().splitlines()[-1])


def test_compile_cache_fixed_path_when_unset():
    a, b = _cache_probe({}), _cache_probe({})
    assert a == b, "two processes must agree on one directory"
    assert a["path"] == a["jax_dir"] == a["env"] == jitting.COMPILE_CACHE_DIR
    assert a["path"].startswith(REPO + os.sep)
    assert a["min_s"] == 0.0


def test_compile_cache_env_wins(tmp_path):
    got = _cache_probe({"JAX_COMPILATION_CACHE_DIR": str(tmp_path)})
    assert got["path"] == got["jax_dir"] == got["env"] == str(tmp_path)


def test_compile_cache_off_when_pinned_to_cpu():
    import jax

    assert os.environ["JAX_PLATFORMS"] == "cpu"  # conftest's pin
    assert jitting.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir is None


def test_yb_ctl_child_environments(tmp_path):
    master = {"role": "master", "uuid": "m-0"}
    tserver = {"role": "tserver", "uuid": "ts-0"}
    for engine, want in (("cpu", "cpu"), ("tpu", "tpu")):
        state = {"engine": engine}
        assert ClusterCtl.daemon_env(state, master)["JAX_PLATFORMS"] == "cpu"
        assert ClusterCtl.daemon_env(state, tserver)["JAX_PLATFORMS"] == want
    ctl = ClusterCtl(str(tmp_path / "c"))
    with pytest.raises(SystemExit, match="one process"):
        ctl.create(num_masters=1, num_tservers=2, engine="tpu")
    assert not os.path.exists(ctl.state_path), "refusal spawned nothing"


def _run_smoke(args, cwd=REPO, script=SMOKE, prelude="", timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    code = (f"import sys; sys.argv = [{script!r}] + {list(args)!r}\n"
            f"{prelude}\n"
            f"import runpy; runpy.run_path({script!r}, run_name='__main__')")
    return subprocess.run([sys.executable, "-c", code], cwd=cwd, env=env,
                          capture_output=True, text=True, timeout=timeout)


def _result_lines(proc):
    return [ln for ln in proc.stdout.splitlines() if ln.startswith('{"ok"')]


def test_smoke_fails_without_a_tpu():
    proc = _run_smoke([])
    assert proc.returncode != 0
    assert "no TPU" in proc.stderr and not _result_lines(proc)


def test_smoke_fails_alone_in_a_directory(tmp_path):
    """chip_smoke.py and nothing else of the repo: no result, even asked
    to rehearse."""
    script = shutil.copy(SMOKE, tmp_path)
    proc = _run_smoke(["--rehearse-cpu"], cwd=str(tmp_path), script=script)
    assert proc.returncode != 0 and not proc.stdout
    assert "the repository it stands in" in proc.stderr


@pytest.mark.slow
def test_smoke_rehearsal_passes_and_says_what_it_is():
    proc = _run_smoke(["--rehearse-cpu"])
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "proves NOTHING about the chip" in proc.stdout
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert last == {"ok": True, "device": {"platform": "cpu", "kind": "cpu",
                                           "count": last["device"]["count"]}}
    report = json.loads(next(
        ln for ln in proc.stdout.splitlines()
        if ln.startswith("CHIP_SMOKE_REPORT "))[len("CHIP_SMOKE_REPORT "):])
    assert report["rehearsal"] and report["reduced"]
    assert report["flushes"]["device"] > 0
    assert all(report["compiles_total"].get(e)
               for e in report["compiles_served_path"])


@pytest.mark.slow
def test_smoke_rehearsal_fails_on_a_hidden_device_fault():
    """One injected dispatch fault: the engine re-serves from the host
    and every answer stays exact — the breaker's record must fail the run."""
    proc = _run_smoke(
        ["--rehearse-cpu"],
        prelude="from yugabyte_db_tpu.utils.fault_injection import "
                "arm_fault_once; arm_fault_once('fault.tpu_dispatch')")
    assert proc.returncode != 0 and not _result_lines(proc)
    assert "breaker" in proc.stderr


@pytest.mark.slow
def test_smoke_rehearsal_fails_when_a_scan_leaves_the_mesh():
    """One dropped mesh dispatch after the compaction: the tserver serves
    the page from the host, exact — the mesh counters must fail the run."""
    proc = _run_smoke(
        ["--rehearse-cpu"],
        prelude="from yugabyte_db_tpu.utils.fault_injection import "
                "arm_fault_once; arm_fault_once('fault.mesh_dispatch')")
    assert proc.returncode != 0 and not _result_lines(proc)
    assert "mesh counters" in proc.stderr


class _RecordingTransport:
    """Answers every RPC with one reply and remembers the transport and
    server-side timeouts each was given."""

    def __init__(self, reply: dict):
        self.reply = reply
        self.sent = []

    def send(self, target, method, payload, timeout=None):
        self.sent.append((method, timeout, payload.get("timeout")))
        return dict(self.reply)


def test_scan_attempt_gets_the_callers_whole_budget():
    """A scan works for as long as the data under it takes (a multi-run
    read is merged on the host): capped at 5 s an attempt, and retried,
    it never returned past ~100K rows a tablet (found on the chip)."""
    from yugabyte_db_tpu.client.client import YBClient
    from yugabyte_db_tpu.client.meta_cache import TabletLocation

    tr = _RecordingTransport({"code": "ok"})
    client = YBClient(tr, ["m-0"])
    loc = TabletLocation("t1", 0, 65536, ["ts-0"], "ts-0")
    for method in ("ts.scan", "ts.scan_wire", "ts.write"):
        client.tablet_rpc("tbl", loc, method, {}, timeout_s=60.0)
    (_, scan_s, scan_srv), (_, wire_s, _), (_, write_s, _) = tr.sent
    assert 59.0 < scan_s <= 60.0 and 59.0 < wire_s <= 60.0
    assert scan_srv == pytest.approx(0.8 * scan_s, abs=0.01)
    assert write_s == 5.0


def test_mesh_attempts_get_the_callers_budget():
    """The first mesh request for a new run set builds, uploads and
    compiles the stack (8-12 s at 2M versions a tablet on the chip): at
    a fixed 5 s the session swallowed the timeout and paged per tablet."""
    from yugabyte_db_tpu.client.client import YBClient, YBTable
    from yugabyte_db_tpu.client.meta_cache import (TableLocations,
                                                   TabletLocation)
    from yugabyte_db_tpu.client.session import YBSession
    from yugabyte_db_tpu.storage import wire
    from yugabyte_db_tpu.storage.scan_spec import (AggSpec, ScanResult,
                                                   ScanSpec)
    from yugabyte_db_tpu.utils import metrics
    from yugabyte_db_tpu.yql.pgsql import tpch

    tr = _RecordingTransport(dict(
        wire.encode_result(ScanResult([], [], None, 0)), code="ok"))
    client = YBClient(tr, ["m-0"])
    locs = TableLocations("id", {}, [
        TabletLocation("t1", 0, 32768, ["ts-0"], "ts-0", {}, {"ts-0": 4}),
        TabletLocation("t2", 32768, 65536, ["ts-0"], "ts-0", {},
                       {"ts-0": 4})])   # (a node with four chips)
    client.meta_cache.locations = lambda name, refresh=False: locs
    table = YBTable("tbl", "id", tpch.lineitem_schema(), engine="tpu")
    before = metrics.swallowed_errors()
    sess = YBSession(client)
    sess.scan(table, ScanSpec(limit=10), timeout_s=60.0)
    sess.scan(table, ScanSpec(aggregates=[AggSpec("count", None)]),
              timeout_s=60.0)
    assert [(m, t, srv) for m, t, srv in tr.sent] == [
        ("ts.multi_row_scan", 60.0, 48.0), ("ts.multi_agg_scan", 60.0, 48.0)]
    assert metrics.swallowed_errors() == before


def test_read_gate_wait_stays_bounded_under_a_long_scan_budget():
    """The other half of the scan budget: waiting for a replica to reach
    the read point is failure detection, not work, and keeps its 4 s."""
    from types import SimpleNamespace as NS

    from yugabyte_db_tpu.storage import wire
    from yugabyte_db_tpu.storage.scan_spec import ScanSpec
    from yugabyte_db_tpu.tserver.tablet_server import TabletServer
    from yugabyte_db_tpu.utils.retry import Deadline

    waits = []

    def never_safe(ht, timeout):
        waits.append(timeout)
        return False

    peer = NS(_split_sealing=False, ops_seen=0, tablet_id="t", tablet=NS(
        meta=NS(split_sealed=False), clock=NS(update=lambda ht: None),
        mvcc=NS(wait_for_safe_time=never_safe)))
    ts = NS(tablet_manager=NS(get=lambda tid: peer),
            READ_GATE_WAIT_S=TabletServer.READ_GATE_WAIT_S,
            _pin_read_point=TabletServer._pin_read_point)
    got = TabletServer._read_gate(
        ts, {"tablet_id": "t",
             "spec": wire.encode_spec(ScanSpec(read_ht=12345))},
        deadline=Deadline.after(720.0))
    assert got == (None, None, {"code": "timed_out"}) and waits == [4.0]
