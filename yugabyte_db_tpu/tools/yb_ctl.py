"""yb-ctl: local multi-process cluster orchestrator.

Reference analog: bin/yb-ctl — create/start/stop/status/destroy a local
cluster of REAL master and tserver processes (each with its own
interpreter, Messenger, data dir, and webserver), wired over loopback
TCP with deterministic ports.

  python -m yugabyte_db_tpu.tools.yb_ctl --data-dir /tmp/ybt create \
      --num-masters 1 --num-tservers 3
  python -m yugabyte_db_tpu.tools.yb_ctl --data-dir /tmp/ybt status
  python -m yugabyte_db_tpu.tools.yb_ctl --data-dir /tmp/ybt destroy

``--engine`` names the JAX backend the tservers may use. ``cpu`` (the
default) pins every daemon to the CPU backend: a ``tpu`` table then runs
its device programs on XLA's CPU backend. ``tpu`` starts the tserver with
``JAX_PLATFORMS=tpu``, so it claims the chip at start-up and a missing
chip is JAX's own hard error. A chip belongs to one process, so ``create``
refuses more than one such tserver per host. Masters never touch a device.
"""

from __future__ import annotations

import argparse
import json
import os
import shutil
import signal
import socket
import subprocess
import sys
import time

from yugabyte_db_tpu.utils.metrics import count_swallowed

STATE_FILE = "cluster.json"


def _free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


def _pid_alive(pid: int) -> bool:
    try:
        os.kill(pid, 0)
    except OSError:
        return False
    return True


def _http_ok(port: int, path: str = "/healthz",
             timeout: float = 1.0) -> bool:
    import urllib.request

    try:
        with urllib.request.urlopen(
                f"http://127.0.0.1:{port}{path}", timeout=timeout) as r:
            return r.status == 200
    except Exception:  # noqa: BLE001
        return False


class ClusterCtl:
    def __init__(self, data_dir: str):
        self.data_dir = os.path.abspath(data_dir)
        self.state_path = os.path.join(self.data_dir, STATE_FILE)

    # -- state ---------------------------------------------------------------
    def load(self) -> dict:
        with open(self.state_path) as f:
            return json.load(f)

    def save(self, state: dict) -> None:
        os.makedirs(self.data_dir, exist_ok=True)
        with open(self.state_path, "w") as f:
            json.dump(state, f, indent=1)

    # -- commands ------------------------------------------------------------
    def create(self, num_masters: int, num_tservers: int,
               engine: str = "cpu", fsync: bool = False) -> dict:
        if os.path.exists(self.state_path):
            raise SystemExit(f"cluster already exists at {self.data_dir} "
                             f"(use start/destroy)")
        if engine not in ("cpu", "tpu"):
            raise SystemExit(f"unknown --engine {engine!r} (cpu or tpu)")
        if engine == "tpu" and num_tservers > 1:
            raise SystemExit(
                f"--engine tpu with {num_tservers} tservers refused: a TPU "
                "chip belongs to one process at a time, so a second tserver "
                "on this host would fail or hang at JAX start-up. Use "
                "--num-tservers 1 (several chips per host are not mapped "
                "onto several tservers yet).")
        daemons = []
        for i in range(num_masters):
            daemons.append({"role": "master", "uuid": f"m-{i}"})
        for i in range(num_tservers):
            daemons.append({"role": "tserver", "uuid": f"ts-{i}"})
        for d in daemons:
            d["rpc_port"] = _free_port()
            d["web_port"] = _free_port()
        state = {
            "engine": engine,
            "fsync": fsync,
            "daemons": daemons,
            "topology": ",".join(
                f"{d['uuid']}=127.0.0.1:{d['rpc_port']}" for d in daemons),
            "masters": ",".join(d["uuid"] for d in daemons
                                if d["role"] == "master"),
        }
        self.save(state)
        self.start()
        return state

    @staticmethod
    def daemon_env(state: dict, d: dict) -> dict:
        """The child environment of one daemon. FORCED, not setdefault:
        on a chip machine the ambient JAX default is the TPU, and a
        daemon that was not given the chip must never initialise it —
        the chip's one owner would fail or hang."""
        env = dict(os.environ)
        owns_chip = d["role"] == "tserver" and state.get("engine") == "tpu"
        env["JAX_PLATFORMS"] = "tpu" if owns_chip else "cpu"
        return env

    def _spawn(self, state: dict, d: dict) -> int:
        log_path = os.path.join(self.data_dir, f"{d['uuid']}.log")
        log = open(log_path, "ab")
        env = self.daemon_env(state, d)
        cmd = [sys.executable, "-m",
               "yugabyte_db_tpu.server.daemon_main",
               "--role", d["role"], "--uuid", d["uuid"],
               "--data-dir", os.path.join(self.data_dir, d["uuid"]),
               "--topology", state["topology"],
               "--masters", state["masters"],
               "--web-port", str(d["web_port"])]
        if not state.get("fsync", False):
            cmd.append("--no-fsync")
        proc = subprocess.Popen(cmd, stdout=log, stderr=log, env=env,
                                start_new_session=True)
        log.close()
        return proc.pid

    def start(self) -> None:
        state = self.load()
        for d in state["daemons"]:
            if d.get("pid") and _pid_alive(d["pid"]):
                continue
            d["pid"] = self._spawn(state, d)
        self.save(state)
        deadline = time.monotonic() + 30.0
        pending = list(state["daemons"])
        while pending and time.monotonic() < deadline:
            pending = [d for d in pending if not _http_ok(d["web_port"])]
            if pending:
                time.sleep(0.2)
        if pending:
            raise SystemExit(
                "daemons failed to become healthy: "
                + ", ".join(d["uuid"] for d in pending)
                + f" (logs in {self.data_dir})")

    def stop(self) -> None:
        state = self.load()
        for d in state["daemons"]:
            pid = d.get("pid")
            if pid and _pid_alive(pid):
                try:
                    os.kill(pid, signal.SIGTERM)
                except OSError:
                    pass
        deadline = time.monotonic() + 15.0
        while time.monotonic() < deadline:
            if not any(d.get("pid") and _pid_alive(d["pid"])
                       for d in state["daemons"]):
                break
            time.sleep(0.1)
        for d in state["daemons"]:
            pid = d.get("pid")
            if pid and _pid_alive(pid):
                os.kill(pid, signal.SIGKILL)
            d["pid"] = None
        self.save(state)

    def status(self) -> list[dict]:
        state = self.load()
        out = []
        for d in state["daemons"]:
            alive = bool(d.get("pid")) and _pid_alive(d["pid"])
            out.append({
                "uuid": d["uuid"], "role": d["role"],
                "pid": d.get("pid"), "alive": alive,
                "healthy": alive and _http_ok(d["web_port"]),
                "rpc": f"127.0.0.1:{d['rpc_port']}",
                "web": f"http://127.0.0.1:{d['web_port']}",
            })
        return out

    def destroy(self) -> None:
        if os.path.exists(self.state_path):
            self.stop()
        shutil.rmtree(self.data_dir, ignore_errors=True)

    def wait_tservers_registered(self, n: int | None = None,
                                 timeout_s: float = 30.0) -> None:
        """Block until n tservers are registered live with the master
        (the cluster is usable for create_table only after that)."""
        from yugabyte_db_tpu.tools.admin_client import AdminClient

        state = self.load()
        want = n if n is not None else sum(
            1 for d in state["daemons"] if d["role"] == "tserver")
        admin = AdminClient.connect(self.master_addresses())
        deadline = time.monotonic() + timeout_s
        while time.monotonic() < deadline:
            try:
                if len(admin.list_tservers()) >= want:
                    return
            except Exception as e:  # noqa: BLE001 — master still electing
                count_swallowed("yb_ctl.wait_tservers", e)
            time.sleep(0.2)
        raise SystemExit(f"tservers did not register within {timeout_s}s")

    def master_addresses(self) -> str:
        state = self.load()
        return ",".join(f"127.0.0.1:{d['rpc_port']}"
                        for d in state["daemons"]
                        if d["role"] == "master")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="yb-ctl")
    ap.add_argument("--data-dir", required=True)
    sub = ap.add_subparsers(dest="cmd", required=True)
    p = sub.add_parser("create")
    p.add_argument("--num-masters", type=int, default=1)
    p.add_argument("--num-tservers", type=int, default=3)
    p.add_argument("--engine", default="cpu", choices=("cpu", "tpu"),
                   help="JAX backend for the tserver: tpu = the one "
                        "tserver owns the chip (see module docstring)")
    p.add_argument("--fsync", action="store_true")
    sub.add_parser("start")
    sub.add_parser("stop")
    sub.add_parser("status")
    sub.add_parser("destroy")
    sub.add_parser("master_addresses")
    args = ap.parse_args(argv)
    ctl = ClusterCtl(args.data_dir)
    if args.cmd == "create":
        ctl.create(args.num_masters, args.num_tservers, args.engine,
                   args.fsync)
        print(f"cluster up; masters at {ctl.master_addresses()}")
    elif args.cmd == "start":
        ctl.start()
        print("cluster started")
    elif args.cmd == "stop":
        ctl.stop()
        print("cluster stopped")
    elif args.cmd == "status":
        for row in ctl.status():
            print(json.dumps(row))
    elif args.cmd == "destroy":
        ctl.destroy()
        print("cluster destroyed")
    elif args.cmd == "master_addresses":
        print(ctl.master_addresses())
    return 0


if __name__ == "__main__":
    sys.exit(main())
