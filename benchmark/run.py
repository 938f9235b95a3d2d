#!/usr/bin/env python3
"""One run of one cell of the benchmark.

    python3 benchmark/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

This process is the server: it alone imports JAX and holds the chip. It
finds the cell in ``BENCHMARK.json`` and everything the cell is made of
by name (``configs/``, ``traffic/``, ``generators/``, ``references/``,
``end_to_end/``, ``layer_metrics/``, ``readers/``), starts the load
generators (child processes that never import JAX) before it touches
JAX, brings the deployment up, loads it from ``--seed``, warms up the
cell's own statements, gives the go signal, and after the window checks
every answer against the plain reference. The last line of its stdout is
the result, validated by ``contract.py`` before it is printed.

Without a TPU it exits 1 and prints no result. ``--rehearse-cpu`` is a
tiny run on the CPU backend that checks the harness and proves NOTHING
about the chip; it says so and names the CPU in ``device``.
"""

from __future__ import annotations

import time

T0 = time.perf_counter()

import argparse  # noqa: E402
import copy  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import contract, registry, trace_reduce  # noqa: E402


def log(msg: str) -> None:
    print(f"[{time.perf_counter() - T0:7.1f}s] {msg}", flush=True)


class Refused(Exception):
    """The run cannot give a result; exit non-zero, print none."""


def load_json(*parts: str) -> dict:
    with open(os.path.join(ROOT, *parts)) as f:
        return json.load(f)


def merged(base: dict, over: dict) -> dict:
    out = copy.deepcopy(base)
    for k, v in over.items():
        out[k] = merged(out[k], v) if isinstance(v, dict) \
            and isinstance(out.get(k), dict) else v
    return out


# -- the load generators ------------------------------------------------------

class Child:
    def __init__(self):
        env = {k: v for k, v in os.environ.items()
               if k not in ("JAX_PLATFORMS", "XLA_FLAGS")}
        self.proc = subprocess.Popen(
            [sys.executable, os.path.join(HERE, "loadgen.py")],
            stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
            cwd=ROOT, env=env)

    def send(self, msg: dict) -> None:
        self.proc.stdin.write(json.dumps(msg) + "\n")
        self.proc.stdin.flush()

    def recv(self, key: str):
        line = self.proc.stdout.readline()
        if not line:
            raise Refused(f"a load generator died (exit "
                          f"{self.proc.poll()}) before answering {key}")
        msg = json.loads(line)
        if "error" in msg:
            raise Refused("load generator: " + msg["error"])
        return msg[key]

    def stop(self) -> None:
        try:
            self.send({"do": "quit"})
            self.proc.stdin.close()
        except (OSError, ValueError):
            pass
        try:
            self.proc.wait(timeout=10)
        except subprocess.TimeoutExpired:
            self.proc.kill()
            self.proc.wait()


def ask_all(children: list[Child], msg: dict, key: str) -> list:
    for c in children:
        c.send(msg)
    return [c.recv(key) for c in children]


def merge_client(results: list[dict]) -> dict:
    """Latencies and completion times are by class of operation, as the
    traffic file names them (read, write, or a statement's name)."""
    classes = sorted({k for r in results for k in r["latency_ms"]})
    return {
        "streams": [s for r in results for s in r["streams"]],
        "latency_ms": {k: [x for r in results
                           for x in r["latency_ms"].get(k, [])]
                       for k in classes},
        "done_s": {k: [x for r in results for x in r["done_s"].get(k, [])]
                   for k in classes},
        "attempted": sum(r["attempted"] for r in results),
        "failed": sum(r["failed"] for r in results),
        "answers": [a for r in results for a in r.get("answers", [])],
        "errors": [e for r in results for e in r.get("errors", [])],
    }


def thirds(client: dict, seconds: float) -> dict:
    """By class of operation and third of the window (by the time an
    operation was answered): [median latency in ms, operations answered,
    mean number in flight]. What one run's level is made of: thirds that
    differ among themselves as runs do are drift inside a run, flat
    thirds at a level of the run's own are a mode of the process."""
    third = seconds / 3
    out = {}
    for k, lats in client["latency_ms"].items():
        parts: list[list[float]] = [[], [], []]
        for ms, t in zip(lats, client["done_s"][k]):
            parts[min(2, int(t // third))].append(ms)
        out[k] = [[statistics.median(p) if p else None, len(p),
                   sum(p) / 1e3 / third] for p in parts]
    return out


# -- the metrics --------------------------------------------------------------

def read_metrics(bench: dict, workload: str, trace: bool, ctx: dict) -> dict:
    """Each metric of this run through the reader its file names."""
    group, folder = (("per_layer", "layer_metrics") if trace
                     else ("end_to_end", "end_to_end"))
    out = {}
    for m in bench[group]:
        if workload not in m.get("workloads", [workload]):
            continue
        spec = load_json("benchmark", folder, m["name"] + ".json")
        reader = importlib.import_module(
            "benchmark.readers." + spec["reader"])
        out[m["name"]] = reader.read(spec.get("args", {}), ctx)
    return out


def device_peak(kind: str) -> dict:
    """The chip's peaks; an unknown kind is an error, not a default."""
    peaks = load_json("benchmark", "peaks.json")["devices"]
    if kind not in peaks:
        raise Refused(f"device kind {kind!r} is not in benchmark/peaks.json")
    return peaks[kind]


# -- one run ------------------------------------------------------------------

def run(args) -> int:
    bench = contract.load_benchmark(ROOT)
    cell = next((w for w in bench["workloads"]
                 if w["name"] == args.workload), None)
    if cell is None:
        raise Refused(f"no workload {args.workload!r} in BENCHMARK.json")
    cfg_entry = next(c for c in bench["configs"]
                     if c["name"] == cell["config"])
    cfg = load_json(cfg_entry["file"])
    traffic = load_json("benchmark", "traffic", cell["traffic"] + ".json")
    if args.rehearse_cpu:
        cfg = merged(cfg, cfg.get("rehearsal", {}))
        traffic = merged(traffic, traffic.get("rehearsal", {}))
    for part in ("yugabyte_db_tpu", "native"):
        if not os.path.isdir(os.path.join(ROOT, part)):
            raise Refused(f"the benchmark drives the repository it stands "
                          f"in; there is no {part}/ beside it")

    children = [Child() for _ in range(int(traffic["processes"]))]
    started: list = []      # the deployment, once there is one to stop
    scratch = tempfile.mkdtemp(prefix="bench_run_")
    try:
        return measure(args, bench, cell, cfg, traffic, children, scratch,
                       started)
    finally:
        for c in children:
            c.stop()
        for dep in started:
            try:
                dep.stop()
            except Exception:  # noqa: BLE001 — the process ends anyway
                traceback.print_exc()
        shutil.rmtree(scratch, ignore_errors=True)


def measure(args, bench, cell, cfg, traffic, children, scratch,
            started) -> int:
    # The chip first: no accelerator, no result. JAX_PLATFORMS is never
    # set here; on the machine with the chip JAX's default is the TPU.
    import jax

    compiles = {"n": 0, "s": 0.0}

    def on_duration(event: str, secs: float, **_kw) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            compiles["n"] += 1
            compiles["s"] += secs

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if args.rehearse_cpu:
        log("REHEARSAL on the CPU backend at a tiny size: this run checks "
            "the harness and proves NOTHING about the chip")
        if device["platform"] != "cpu":
            raise Refused("--rehearse-cpu needs JAX_PLATFORMS=cpu")
    elif jax.default_backend() != "tpu":
        raise Refused(f"JAX found no TPU (backend {jax.default_backend()})")
    peaks = None if args.rehearse_cpu else device_peak(device["kind"])
    if len(devs) < cell["chips"]:
        raise Refused(f"{len(devs)} chips, the cell asks for {cell['chips']}")
    log(f"device {json.dumps(device)} jax {jax.__version__}")

    from benchmark import deploy

    log(f"native modules: {deploy.build_native(ROOT):.1f}s")
    from yugabyte_db_tpu.utils import jitting, metrics

    # The program keeps its cache where JAX_COMPILATION_CACHE_DIR says,
    # else at <checkout>/.jax_compile_cache: a fixed path either way.
    log(f"compile cache {jitting.enable_compile_cache()}")

    ref_mod = importlib.import_module(
        "benchmark.references." + cfg["reference"])
    ref = ref_mod.Reference(cfg, args.seed)
    dep = deploy.Deployment(cfg)
    started.append(dep)
    t = time.perf_counter()
    dep.start()
    dep.create_table(ref.ddl, ref.table)
    log(f"cluster up, leaders {dep.leader_map}: "
        f"{time.perf_counter() - t:.1f}s")
    t = time.perf_counter()
    n = dep.load(ref.batches())
    dt = time.perf_counter() - t
    log(f"loaded {n} rows in {dt:.1f}s ({n / dt:.0f} rows/s: client "
        "batches + RF=3 Raft + fsync)")
    t = time.perf_counter()
    flushed = dep.flush(cfg["load"]["flush"])
    log(f"flushed {flushed} replicas ({cfg['load']['flush']}) in "
        f"{time.perf_counter() - t:.1f}s; routes device "
        f"{metrics.flush_path_count('device')} host "
        f"{metrics.flush_path_count('host')}")
    dep.set_flags(cfg.get("flags_after_load", {}))
    routes_at_flags = {r: metrics.flush_path_count(r)
                       for r in ("device", "host")}
    t = time.perf_counter()
    n = dep.load(ref.batches("burst"))
    if n:
        dep.wait_applied()
        log(f"burst of {n} rows under {cfg['flags_after_load']} in "
            f"{time.perf_counter() - t:.1f}s; routes device "
            f"{metrics.flush_path_count('device')} host "
            f"{metrics.flush_path_count('host')}")

    plan = {"generator": traffic["generator"], "params": traffic["params"],
            "seed": args.seed, "workers": len(children),
            "addr": {k: list(v) for k, v in dep.addr.items()},
            "config": {k: cfg[k] for k in ("schema", "scale", "load")}}
    for i, c in enumerate(children):
        c.send({"plan": dict(plan, worker=i)})
    for c in children:
        c.recv("ready")
    t = time.perf_counter()
    # One client alone first: in a checkout with an empty compile cache
    # the first request of each kind compiles its device programs, and
    # many clients that all start cold outlast the program's RPC budget.
    warm = ask_all(children[:1], {"do": "warmup_solo"}, "warmup_solo")
    log(f"warm-up, one client alone: {time.perf_counter() - t:.1f}s; "
        f"backend compiles so far {compiles['n']} in {compiles['s']:.1f}s")
    for note in (n for w in warm for n in w.get("retried", [])):
        log(f"  asked again: {note[:300]}")
    if not any(w.get("errors") for w in warm):
        warm += ask_all(children, {"do": "warmup"}, "warmup")
    errors = [e for w in warm for e in w.get("errors", [])]
    if errors:
        raise Refused(f"warm-up failed: {errors[:3]}")
    dep.wait_applied()
    # The program compiles some programs in background threads once a
    # flush has left a second run; the window opens when none has
    # finished for two seconds.
    seen, quiet_since = compiles["n"], time.perf_counter()
    while time.perf_counter() - quiet_since < 2.0 \
            and time.perf_counter() - t < 120:
        time.sleep(0.1)
        if compiles["n"] != seen:
            seen, quiet_since = compiles["n"], time.perf_counter()
    log(f"warm-up {time.perf_counter() - t:.1f}s; backend compiles so far "
        f"{compiles['n']} in {compiles['s']:.1f}s")
    for line in dep.replica_state():
        log("  " + line)

    # -- the window -----------------------------------------------------------
    before = registry.parse(dep.registry_text())
    leaders_before = dep.leaders_now()
    compiles_before = compiles["n"]
    entries_before = dict(metrics.jit_compiles())
    swallowed_before = dict(metrics.swallowed_errors())
    trace_dir = os.path.join(scratch, "trace")
    if args.trace:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        # (the CPU backend's stand-in for module events is a host event)
        opts.host_tracer_level = 2 if args.rehearse_cpu else 0
        jax.profiler.start_trace(trace_dir, profiler_options=opts)
    t_go = time.perf_counter()
    setup_s = t_go - T0
    log(f"go: {args.seconds:g}s window, set-up took {setup_s:.1f}s")
    for c in children:
        c.send({"do": "run", "seconds": args.seconds})
    trace, traced_s = None, None
    if args.trace:
        # A device that is busy most of the window writes millions of op
        # events; the traffic file says how much of the window a traced
        # run traces (all of it, if it does not say).
        time.sleep(min(args.seconds,
                       float(traffic.get("trace_seconds", args.seconds))))
        traced_s = time.perf_counter() - t_go
        jax.profiler.stop_trace()
        log(f"traced the first {traced_s:.2f}s; stop_trace took "
            f"{time.perf_counter() - t_go - traced_s:.1f}s")
    client = merge_client([c.recv("run") for c in children])
    window_s = time.perf_counter() - t_go
    if args.trace:
        t = time.perf_counter()
        loaded = trace_reduce.load_xplane(trace_reduce.find_xplane(trace_dir))
        log(f"trace read in {time.perf_counter() - t:.1f}s: "
            f"{sum(len(d['ops']) for d in loaded['devices'].values())} op "
            f"events; planes and lines: "
            f"{json.dumps(loaded['planes'])[:1200]}")
        if args.keep_trace:
            os.makedirs(os.path.dirname(args.keep_trace) or ".",
                        exist_ok=True)
            head = {n: {k: sorted(v, key=lambda e: e[1])[:300]
                        for k, v in d.items()}
                    for n, d in loaded["devices"].items()}
            with open(args.keep_trace, "w") as f:
                json.dump({"planes": loaded["planes"], "devices": head}, f)
        trace = trace_reduce.reduce_events(loaded, traced_s)
        trace["traced_s"] = traced_s
    after = registry.parse(dep.registry_text())
    compiles_in_window = compiles["n"] - compiles_before
    leaders_after = dep.leaders_now()
    log(f"window closed after {window_s:.2f}s")

    # -- the check, outside the window ----------------------------------------
    t = time.perf_counter()
    compared: dict = {}
    wrong = 0
    sample = []
    for a in ask_all(children, {"do": "after"}, "after"):
        wrong += a["wrong"]
        sample += a.get("replica_sample", [])
        for name, (value, limit) in a["compared"].items():
            old = compared.get(name, [0, limit])
            compared[name] = [old[0] + value, limit]
    verdict = ref.check(client["answers"])
    wrong += verdict["wrong"]
    compared.update(verdict["compared"])
    for ex in verdict["examples"]:
        log(f"  WRONG: {json.dumps(ex)[:600]}")
    if sample:
        rep = ref.check_replicas(sample, dep.read_from_replicas)
        wrong += rep["wrong"]
        compared.update(rep["compared"])
        for b in rep["examples"]:
            log(f"  WRONG replica row: {b}")
    log(f"check took {time.perf_counter() - t:.1f}s")

    flush_dev = registry.delta(before, after, "yb_flush_device",
                               {"path": "device"})
    flush_host = registry.delta(before, after, "yb_flush_device",
                                {"path": "host"})
    # -- what would let a run pass without the chip, or on a sick one ---------
    problems = dep.breaker_problems()
    swallowed = {k: v - swallowed_before.get(k, 0)
                 for k, v in metrics.swallowed_errors().items()}
    for site, n in metrics.swallowed_errors().items():
        if n and site.startswith("tpu_engine."):
            problems.append(f"{n} swallowed errors at {site}")
    # A deployment whose flushes are the chip's work says so; there a
    # flush that fell back to the host route is a fault.
    # (The bulk load's one flush is past the memtable op log's cap and
    # is built on the host by design; the rule holds from the moment the
    # deployment's flush threshold is set.)
    if cfg.get("expect", {}).get("flush_route") == "device":
        host = metrics.flush_path_count("host") - routes_at_flags["host"]
        if host:
            problems.append(f"{host} flushes took the host route")
        if not flush_dev:
            problems.append("no flush in the window took the device route")
    if client["failed"]:
        problems.append(f"{client['failed']} operations failed or timed out")
    if compiles_in_window:
        problems.append(f"{compiles_in_window} backend compiles inside the "
                        "window")
    rpcs = {}
    for (name, labels), v in after.items():
        if name == "rpc_requests_total":
            m = dict(labels).get("method", "?")
            rpcs[m] = rpcs.get(m, 0) + int(v - before.get((name, labels), 0))
    log("samples: " + json.dumps(dict(
        {k: len(v) for k, v in client["latency_ms"].items()},
        streams=len(client["streams"]), attempted=client["attempted"],
        failed=client["failed"])))
    log(f"compiles in the window: {compiles_in_window} (whole run "
        f"{compiles['n']} in {compiles['s']:.1f}s); by entry in the "
        f"window { {e: n - entries_before.get(e, 0) for e, n in metrics.jit_compiles().items() if n - entries_before.get(e, 0)} }"
        f", whole run {dict(metrics.jit_compiles())}")
    step = max(1.0, args.seconds / 15)
    log(f"operations answered per {step:g}s of the window: " + json.dumps({
        k: [sum(i * step <= t < (i + 1) * step for t in ts)
            for i in range(int(max(ts, default=0) // step) + 1)]
        for k, ts in client["done_s"].items() if ts}))
    log("thirds of the window by class [p50 ms, answered, mean in flight]: "
        + json.dumps(thirds(client, args.seconds)))
    log(f"flushes in the window: device {flush_dev:g} host {flush_host:g}; "
        f"whole run device {metrics.flush_path_count('device')} host "
        f"{metrics.flush_path_count('host')}")
    log(f"leader moves in the window: "
        f"{sum(leaders_before[t] != leaders_after.get(t) for t in leaders_before)}"
        f" (yb_leader_moves_total {metrics.leader_moves_total()})")
    log(f"swallowed errors in the window by site: "
        f"{ {k: v for k, v in swallowed.items() if v} }")
    log(f"tserver RPCs in the window by method: "
        f"{ {k: v for k, v in sorted(rpcs.items()) if v} }")
    for e in client["errors"][:5]:
        log(f"  client error: {e}")
    for line in dep.replica_state():
        log("  " + line)
    for p in problems:
        log(f"  PROBLEM: {p}")
    correct = wrong == 0 and not problems
    # Everything `correct` rests on, each number beside its limit: the
    # reference's comparison, then what else makes a run not correct.
    compared.update(operations_failed=[client["failed"], 0],
                    compiles_in_window=[compiles_in_window, 0],
                    problems=[len(problems), 0])
    log("compared (value, limit): " + json.dumps(compared))

    ctx = {"client": client, "setup_s": setup_s, "window_s": window_s,
           "registry": (before, after), "trace": trace,
           "rehearsal": args.rehearse_cpu,
           # (a rehearsal reads the one table there is: it proves nothing)
           "peaks": peaks or next(iter(load_json(
               "benchmark", "peaks.json")["devices"].values()))}
    values = read_metrics(bench, cell["name"], bool(args.trace), ctx)
    stats = [d.memory_stats() or {} for d in devs]
    peak = max((s.get("peak_bytes_in_use", 0) for s in stats), default=0)
    if not peak and args.rehearse_cpu:
        import resource  # the CPU backend keeps no device memory figure

        peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss * 1024
        log("memory_peak_bytes is this process's peak RSS: a rehearsal")
    device["memory_peak_bytes"] = peak
    breakdown = None
    if trace is not None:
        device["window_s"] = traced_s
        device["busy_s"] = trace["busy_s"]
        breakdown = trace["breakdown"]
        log(f"device busy {trace['busy_s']:.4f}s of {traced_s:.4f}s on "
            f"{trace['devices']} device(s); the trace holds "
            f"{trace['recorded_s']:.4f}s from its first device event to "
            f"its last, cut to the window; module calls "
            f"{json.dumps(trace['module_calls'])}")
    line = contract.build(
        correct=correct, attempted=client["attempted"],
        failed=min(client["failed"] + wrong, client["attempted"]),
        metrics=values,
        units=contract.cell_metrics(bench, cell["name"], bool(args.trace)),
        device=device, breakdown=breakdown,
        compared={k: v for k, v in compared.items() if v[1] is not None})
    contract.validate(line, bench, cell["name"], bool(args.trace))
    for name, (value, limit) in compared.items():
        print(f"compared {name}: {value} (limit {limit})", file=sys.stderr)
    print(json.dumps(line), flush=True)
    return 0


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny run on the CPU backend: checks the harness, "
                         "proves nothing about the chip")
    ap.add_argument("--keep-trace", metavar="FILE",
                    help="with --trace 1: also write the first 300 device "
                         "events of each kind, as JSON, to look at by hand")
    args = ap.parse_args(argv)
    try:
        return run(args)
    except (Refused, contract.Malformed) as e:
        print(f"BENCHMARK_RUN_REFUSED: {e}", file=sys.stderr, flush=True)
        return 1


if __name__ == "__main__":
    # Whatever happened, the process ends here and now: server threads of
    # a failed phase may be left behind, and none may outlive the run.
    try:
        code = main()
    except SystemExit as e:
        code = e.code if isinstance(e.code, int) else 1
    except BaseException:  # noqa: BLE001 — report, then leave
        traceback.print_exc()
        code = 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
