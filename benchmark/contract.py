"""The last line of a run: one function builds it, one validates it.

``run.py`` passes its own line through :func:`validate` before printing
it, and ``selfcheck.py`` does the same for every cell in rehearsal, so a
line the driver could not read is found here and not by the driver.
"""

from __future__ import annotations

import json
import math
import os

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
MAX_BREAKDOWN = 10


class Malformed(ValueError):
    """The line does not meet the contract; the message says where."""


def load_benchmark(root: str = ROOT) -> dict:
    with open(os.path.join(root, "BENCHMARK.json")) as f:
        return json.load(f)


def cell_metrics(bench: dict, workload: str, trace: bool) -> dict[str, str]:
    """{metric name: unit} this cell reports: its end-to-end metrics in a
    plain run, its per-layer metrics in a traced one. A metric without a
    ``workloads`` key belongs to every cell."""
    group = bench["per_layer" if trace else "end_to_end"]
    return {m["name"]: m["unit"] for m in group
            if workload in m.get("workloads", [workload])}


def build(*, correct: bool, attempted: int, failed: int, metrics: dict,
          units: dict[str, str], device: dict,
          breakdown: dict | None = None,
          compared: dict | None = None) -> dict:
    """``metrics`` is {name: number}; a name the cell does not declare, or
    one whose reader found nothing (None), is left out. ``compared`` is
    {name: [number, limit]}, what ``correct`` was decided from; it comes
    last in the line."""
    line = {
        "correct": bool(correct), "attempted": int(attempted),
        "failed": int(failed),
        "metrics": {n: {"value": metrics[n], "unit": units[n]}
                    for n in units if metrics.get(n) is not None},
        "device": device,
    }
    if breakdown is not None:
        line["breakdown"] = breakdown
    if compared is not None:
        line["compared"] = compared
    return line


def _number(v, what: str) -> None:
    if isinstance(v, bool) or not isinstance(v, (int, float)) \
            or not math.isfinite(v):
        raise Malformed(f"{what} is not a finite number: {v!r}")


def validate(line: dict, bench: dict, workload: str, trace: bool) -> None:
    """Raise :class:`Malformed` unless ``line`` is what the driver reads."""
    if not isinstance(line, dict):
        raise Malformed("the line is not a JSON object")
    json.loads(json.dumps(line))  # must survive the trip
    want = {"correct", "attempted", "failed", "metrics", "device"}
    missing = want - line.keys()
    if missing:
        raise Malformed(f"missing keys {sorted(missing)}")
    extra = line.keys() - want - {"breakdown", "compared"}
    if extra:
        raise Malformed(f"unknown keys {sorted(extra)}")
    if "compared" in line:
        if list(line)[-1] != "compared" \
                or not isinstance(line["compared"], dict):
            raise Malformed("compared is not an object at the line's end")
        for name, pair in line["compared"].items():
            if not (isinstance(pair, list) and len(pair) == 2):
                raise Malformed(f"compared.{name} is not [number, limit]")
            _number(pair[0], f"compared.{name}")
            _number(pair[1], f"compared.{name}'s limit")
    if not isinstance(line["correct"], bool):
        raise Malformed("correct is not a boolean")
    for k in ("attempted", "failed"):
        if isinstance(line[k], bool) or not isinstance(line[k], int) \
                or line[k] < 0:
            raise Malformed(f"{k} is not a count: {line[k]!r}")
    if line["failed"] > line["attempted"]:
        raise Malformed("failed exceeds attempted")

    units = cell_metrics(bench, workload, trace)
    got = line["metrics"]
    if not isinstance(got, dict):
        raise Malformed("metrics is not an object")
    for name in got.keys() - units.keys():
        raise Malformed(f"metric {name} is not one of this run's "
                        f"({'per-layer' if trace else 'end-to-end'})")
    # Every end-to-end metric of the cell must be there; so must every
    # per-layer metric that lists the cell (a reader that finds nothing
    # to read in a cell it lists is a fault of the listing).
    for name in units.keys() - got.keys():
        raise Malformed(f"metric {name} of workload {workload} is missing")
    for name, m in got.items():
        if not isinstance(m, dict) or m.keys() != {"value", "unit"}:
            raise Malformed(f"metric {name} is not {{value, unit}}")
        _number(m["value"], f"metric {name}")
        if m["unit"] != units[name]:
            raise Malformed(f"metric {name} has unit {m['unit']!r}, "
                            f"BENCHMARK.json says {units[name]!r}")
        if not trace and m["value"] <= 0:
            raise Malformed(f"end-to-end metric {name} is {m['value']}")
        if trace and ("roofline" in name or "mfu" in name) \
                and m["value"] > 105:
            raise Malformed(f"{name} reads {m['value']}% of a peak")

    dev = line["device"]
    if not isinstance(dev, dict):
        raise Malformed("device is not an object")
    need = {"platform", "kind", "count", "memory_peak_bytes"}
    if trace:
        need |= {"window_s", "busy_s"}
    if need - dev.keys():
        raise Malformed(f"device lacks {sorted(need - dev.keys())}")
    if not isinstance(dev["platform"], str) or not isinstance(dev["kind"], str):
        raise Malformed("device platform/kind are not strings")
    if isinstance(dev["count"], bool) or not isinstance(dev["count"], int) \
            or dev["count"] < 1:
        raise Malformed(f"device count {dev['count']!r}")
    _number(dev["memory_peak_bytes"], "memory_peak_bytes")
    if dev["memory_peak_bytes"] <= 0:
        raise Malformed("memory_peak_bytes is not above 0")
    if trace:
        _number(dev["window_s"], "window_s")
        _number(dev["busy_s"], "busy_s")
        if not 0 < dev["busy_s"] <= dev["window_s"]:
            raise Malformed(f"busy_s {dev['busy_s']} is not in "
                            f"(0, window_s {dev['window_s']}]")
    if "breakdown" in line:
        if not trace:
            raise Malformed("breakdown in a run that was not traced")
        bd = line["breakdown"]
        if not isinstance(bd, dict) \
                or bd.keys() - {"device_ops", "idle_gaps"}:
            raise Malformed("breakdown has keys other than device_ops, "
                            "idle_gaps")
        for key, entries in bd.items():
            if not isinstance(entries, list) or len(entries) > MAX_BREAKDOWN:
                raise Malformed(f"breakdown.{key} is not a list of at most "
                                f"{MAX_BREAKDOWN}")
            for e in entries:
                if not (isinstance(e, list) and len(e) == 2
                        and isinstance(e[0], str)):
                    raise Malformed(f"breakdown.{key} entry {e!r} is not "
                                    "[name, seconds]")
                _number(e[1], f"breakdown.{key} {e[0]}")
