#!/usr/bin/env python3
"""Rehearse every cell before a chip minute is spent.

    JAX_PLATFORMS=cpu python3 benchmark/selfcheck.py [cell ...]

For every cell of ``BENCHMARK.json`` (or those named): one ``--trace 0``
and one ``--trace 1`` run of ``run.py --rehearse-cpu`` at a tiny size,
each last line passed through ``contract.validate``; then the trace
reduction against the recorded trace, and ``BENCHMARK.json`` against the
files it names. Exit 0 only when all of it holds. It proves the harness,
and nothing about the chip.
"""

from __future__ import annotations

import json
import os
import subprocess
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import contract, trace_reduce  # noqa: E402

SECONDS = {0: "6", 1: "8"}


def check_files(bench: dict) -> list[str]:
    bad = []
    for c in bench["configs"]:
        if not os.path.isfile(os.path.join(ROOT, c["file"])):
            bad.append(f"config file {c['file']} is missing")
            continue
        with open(os.path.join(ROOT, c["file"])) as f:
            cfg = json.load(f)
        if sorted(cfg.get("reduced", {})) != sorted(c["reduced"]):
            bad.append(f"{c['name']}: reduced differs between "
                       "BENCHMARK.json and the configuration file")
        ref = os.path.join(HERE, "references", cfg["reference"] + ".py")
        if not os.path.isfile(ref):
            bad.append(f"{c['name']}: no reference {ref}")
    for w in bench["workloads"]:
        t = os.path.join(HERE, "traffic", w["traffic"] + ".json")
        if not os.path.isfile(t):
            bad.append(f"{w['name']}: no traffic file {t}")
    for group, folder in (("end_to_end", "end_to_end"),
                          ("per_layer", "layer_metrics")):
        for m in bench[group]:
            path = os.path.join(HERE, folder, m["name"] + ".json")
            if not os.path.isfile(path):
                bad.append(f"metric {m['name']}: no {path}")
                continue
            with open(path) as f:
                spec = json.load(f)
            if not os.path.isfile(os.path.join(
                    HERE, "readers", spec["reader"] + ".py")):
                bad.append(f"metric {m['name']}: no reader {spec['reader']}")
            if group == "per_layer" and spec.get("layer") != m["layer"]:
                bad.append(f"metric {m['name']}: layer differs between "
                           "BENCHMARK.json and its file")
    return bad


def check_recorded_trace() -> list[str]:
    with open(os.path.join(HERE, "testdata", "recorded_trace.json")) as f:
        rec = json.load(f)
    got = trace_reduce.reduce_events(rec["trace"], rec["window_s"])
    bad = []
    for key, want in rec["expect"].items():
        have = got[key]
        if isinstance(want, dict):
            for k, v in want.items():
                if abs(have.get(k, float("nan")) - v) > 1e-9:
                    bad.append(f"recorded trace: {key}[{k}] is "
                               f"{have.get(k)}, recorded {v}")
        elif abs(have - want) > 1e-9:
            bad.append(f"recorded trace: {key} is {have}, recorded {want}")
    return bad


def rehearse(bench: dict, cell: str, trace: int) -> list[str]:
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(HERE, "run.py"), "--workload", cell,
         "--seed", str(2_147_483_659 + trace), "--seconds", SECONDS[trace],
         "--trace", str(trace), "--rehearse-cpu"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=900)
    what = f"{cell} --trace {trace}"
    if proc.returncode != 0:
        return [f"{what}: exit {proc.returncode}: "
                f"{proc.stderr.strip()[-800:]}"]
    last = proc.stdout.strip().splitlines()[-1]
    try:
        line = json.loads(last)
        contract.validate(line, bench, cell, bool(trace))
    except (ValueError, contract.Malformed) as e:
        return [f"{what}: last line refused: {e}: {last[:300]}"]
    print(f"ok   {what}: correct={line['correct']} attempted="
          f"{line['attempted']} failed={line['failed']} metrics="
          f"{sorted(line['metrics'])}", flush=True)
    return []


def main(argv) -> int:
    bench = contract.load_benchmark(ROOT)
    cells = argv or [w["name"] for w in bench["workloads"]]
    bad = check_files(bench) + check_recorded_trace()
    for cell in cells:
        for trace in (0, 1):
            bad += rehearse(bench, cell, trace)
    for b in bad:
        print(f"FAIL {b}", flush=True)
    print("selfcheck: a CPU rehearsal; it proves nothing about the chip. "
          + ("ALL OK" if not bad else f"{len(bad)} FAILED"), flush=True)
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
