#!/usr/bin/env python3
"""A load-generator process: one general driver for any traffic mix.

Started by ``run.py`` before it touches JAX; imports neither JAX nor the
program, only the benchmark's own wire clients and the generator named in
the traffic file. Speaks lines of JSON on stdin/stdout:

    <- {"plan": {...}}      -> {"ready": true}
    <- {"do": "warmup_solo"} -> {"warmup_solo": {...}}  first worker only:
                            one client alone, where the generator has it
    <- {"do": "warmup"}     -> {"warmup": {...}}
    <- {"do": "run", "seconds": s}
                            -> {"run": {...}}    closed loops, timed here
    <- {"do": "after"}      -> {"after": {...}}  checks outside the window
    <- {"do": "quit"}
"""

from __future__ import annotations

import importlib
import json
import os
import sys
import traceback

HERE = os.path.dirname(os.path.abspath(__file__))


def main() -> int:
    sys.path.insert(0, os.path.dirname(HERE))
    out = sys.stdout
    gen = None
    try:
        for raw in sys.stdin:
            msg = json.loads(raw)
            if "plan" in msg:
                plan = msg["plan"]
                mod = importlib.import_module(
                    "benchmark.generators." + plan["generator"])
                gen = mod.Generator(plan)
                gen.connect()
                reply = {"ready": True}
            elif msg["do"] == "quit":
                break
            elif msg["do"] == "run":
                reply = {"run": gen.run(float(msg["seconds"]))}
            elif msg["do"] == "warmup_solo" \
                    and not hasattr(gen, "warmup_solo"):
                reply = {"warmup_solo": {}}     # (the generator has none)
            else:
                reply = {msg["do"]: getattr(gen, msg["do"])()}
            out.write(json.dumps(reply) + "\n")
            out.flush()
    except Exception:  # noqa: BLE001 — the parent must hear why
        out.write(json.dumps({"error": traceback.format_exc()}) + "\n")
        out.flush()
        return 1
    finally:
        if gen is not None:
            gen.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
