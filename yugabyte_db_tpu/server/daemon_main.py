"""Daemon entry point: run one master or tserver as a real OS process.

Reference analog: src/yb/master/master_main.cc and
src/yb/tserver/tablet_server_main.cc:107 — the production processes
yb-ctl spawns. Each process owns a Messenger listening on its RPC port,
a SocketTransport with the cluster's address book, and an embedded
webserver.

Usage (normally via tools.yb_ctl, not by hand):
  python -m yugabyte_db_tpu.server.daemon_main --role tserver \
      --uuid ts-0 --data-dir /data/ts-0 \
      --topology m-0=127.0.0.1:7100,ts-0=127.0.0.1:9100,... \
      --masters m-0 --web-port 9200
"""

from __future__ import annotations

import argparse
import signal
import sys
import threading


def parse_topology(spec: str) -> dict[str, tuple[str, int]]:
    out = {}
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        uuid, addr = part.split("=", 1)
        host, port = addr.rsplit(":", 1)
        out[uuid] = (host, int(port))
    return out


def claim_device() -> str:
    """The device identity for the start-up line. A tserver pinned to
    the CPU backend (``JAX_PLATFORMS=cpu`` — every daemon yb_ctl starts
    without ``--engine tpu``) does not initialise JAX at all. Any other
    tserver initialises its backend NOW, so a missing chip stops the
    daemon at start-up with JAX's own error instead of at the first
    flush."""
    from yugabyte_db_tpu.utils.jitting import enable_compile_cache

    if enable_compile_cache() is None:
        return "cpu(pinned)"
    import jax

    devs = jax.devices()
    return f"{devs[0].platform}:{devs[0].device_kind}x{len(devs)}"


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(prog="yb-daemon")
    ap.add_argument("--role", choices=("master", "tserver"), required=True)
    ap.add_argument("--uuid", required=True)
    ap.add_argument("--data-dir", required=True)
    ap.add_argument("--topology", required=True,
                    help="uuid=host:port,... for every daemon")
    ap.add_argument("--masters", required=True,
                    help="comma-separated master uuids")
    ap.add_argument("--web-port", type=int, default=0)
    ap.add_argument("--no-fsync", action="store_true")
    args = ap.parse_args(argv)

    from yugabyte_db_tpu.rpc import Messenger, SocketTransport

    topology = parse_topology(args.topology)
    if args.uuid not in topology:
        ap.error(f"--topology lacks own uuid {args.uuid}")
    host, port = topology[args.uuid]
    master_uuids = [u.strip() for u in args.masters.split(",") if u.strip()]

    transport = SocketTransport()
    for uuid, (h, p) in topology.items():
        transport.set_address(uuid, h, p)

    if args.role == "master":
        from yugabyte_db_tpu.master.master import Master

        daemon = Master(args.uuid, args.data_dir, transport, master_uuids,
                        fsync=not args.no_fsync)
    else:
        from yugabyte_db_tpu.tserver.tablet_server import TabletServer

        daemon = TabletServer(args.uuid, args.data_dir, transport,
                              master_uuids, fsync=not args.no_fsync,
                              engine_options=None)
    device = claim_device() if args.role == "tserver" else "none"
    messenger = Messenger(args.uuid, num_workers=16)
    # Consensus traffic rides a dedicated pool: user writes block their
    # workers on majority replication, and the raft RPCs that complete
    # that majority must never queue behind them (reference: separate
    # ServicePools per service, src/yb/rpc/service_pool.cc).
    messenger.add_service_pool("raft.", 8)
    bound = messenger.listen(host, port, daemon.handle)
    daemon.advertised_addr = bound
    daemon.start()
    web_addr = daemon.start_webserver("127.0.0.1", args.web_port)
    print(f"{args.role} {args.uuid} rpc={bound[0]}:{bound[1]} "
          f"web={web_addr[0]}:{web_addr[1]} device={device}", flush=True)

    stop = threading.Event()

    def _sig(_signum, _frame):
        stop.set()

    signal.signal(signal.SIGTERM, _sig)
    signal.signal(signal.SIGINT, _sig)
    stop.wait()
    daemon.shutdown()
    messenger.shutdown()
    return 0


if __name__ == "__main__":
    sys.exit(main())
