#!/usr/bin/env python3
"""The control of a cell's comparison: it has to come out as NOT correct.

    python3 benchmark/control.py --workload <cell> --seed <n> [--small]

Builds the cell's plain reference from the seed at the cell's own size,
draws a window's worth of operations as the cell's traffic would, and
puts through the cell's comparison (a) the reference's own answers, which
must all pass, and (b) the control's: the reference in the program's
place with one stated guarantee broken (for exact integer aggregates, sums
in the nearest lower precision; for the key-value table, a stale read, a
torn value and a lost acknowledged write). Exit 0 only if (a) passes and
(b) fails. Needs no chip and touches no JAX; the benchmark's own runs do
not run it. ``--small`` is the size the tests use.
"""

from __future__ import annotations

import argparse
import importlib
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
sys.path.insert(0, ROOT)

from benchmark import contract  # noqa: E402
from benchmark.run import load_json, merged  # noqa: E402


def control(workload: str, seed: int, small: bool) -> dict:
    bench = contract.load_benchmark(ROOT)
    cell = next(w for w in bench["workloads"] if w["name"] == workload)
    cfg = load_json(next(c["file"] for c in bench["configs"]
                         if c["name"] == cell["config"]))
    traffic = load_json("benchmark", "traffic", cell["traffic"] + ".json")
    if small:
        cfg = merged(cfg, cfg.get("rehearsal", {}))
    ref = importlib.import_module("benchmark.references." + cfg["reference"])
    out = ref.control(cfg, seed, traffic)
    out["passed"] = out["sound_wrong"] == 0 and out["control_wrong"] > 0
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--small", action="store_true")
    args = ap.parse_args(argv)
    out = control(args.workload, args.seed, args.small)
    print("CONTROL " + json.dumps(dict(out, workload=args.workload,
                                       seed=args.seed)), flush=True)
    return 0 if out["passed"] else 1


if __name__ == "__main__":
    sys.exit(main())
