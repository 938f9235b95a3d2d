#!/usr/bin/env python3
"""From the driver's own readings, and from sets of runs of one tree, to the
bound of each end-to-end metric.

    python3 benchmark/spread.py --ledger PERF_LEDGER.jsonl --record benchmark/spread_record.json
    python3 benchmark/spread.py [--record FILE] SET [SET ...]
                                                (or last lines on stdin)

A SET is a file that holds the last line of several runs (one JSON object
a line; lines that are none are skipped, so the runs' whole logs may be
concatenated). A set has 4 runs or more; the sets of one cell use the same
seeds.

Per metric and set: n, median, quartiles (``statistics.quantiles(n=4)``)
and the spread, IQR / median. Then the two readings the driver's check
takes of such runs: ``tight``, the mean of the sets' spreads, each without
its run farthest from the median (a bound under twice that is too tight:
a PR that holds the metric cannot be told from one that harms it), and
``loose``, the wider of the sets' spreads with no run left out (a bound
over eight times that is too loose). The rule puts the bound midway between
the two limits, with the same factor of room on either side:

    bound = min(0.25, max(0.01, round_up_to_0.005(4 x sqrt(tight x loose))))

which is four times the spread where the two readings agree, from the
widest ``tight`` and the middle ``loose`` on record (``statistics.median_low``:
a reading that was read). Not the narrowest: the least of N readings only
falls as the record grows while the widest only rises, so a rule on both
ends closes its own window (the rates' at 25 readings, Q1's at the check of
PR 46, which read a tight of 5.0% where 8 x the narrowest loose was 8.2%);
and the driver holds a bound against the widest spread of a whole check, all
cells and sets, not against the quietest line there ever was. The driver's
machines judge
every PR and are not the builder's, so the readings the driver itself
printed are kept in a record (``spread_record.json``), and **where the
record holds a driver's ``tight`` and a driver's ``loose`` of a metric the
bound comes from the driver's readings alone**: the given sets are then a
check (each set's spread under the bound), not an input. Where it holds
none (a metric new to the benchmark), the given sets and whatever the
record has are the input. One bound a metric: the readings of every cell
that reports it are taken together.

``--ledger`` (once a file) merges into the record every reading a
``PERF_LEDGER.jsonl`` holds and the record does not: ``loose``, the ``spread``
field of a line beside the line's median (change side); ``tight``, a spread
named in words in a line's ``notes`` or in an ``unresolved`` line's
``reason``. A text that names a metric and does not parse is reported on
standard error and skipped, and so is a ``loose`` under the ``tight`` the
driver named for the same PR and cell (all the runs spread no less than the
runs without the farthest: that field was not a share of this median). Lines
of a PR before the record's ``since`` are not read. The ledger drops old
lines, so the merge never removes an entry and a second merge changes
nothing; the versions git holds (``git show <commit>:PERF_LEDGER.jsonl``) are
merged the same way. The record keeps each reading as it was read (value,
unit, the median beside it) and the newest accepted median of each metric
and cell (``medians``). The share is worked out when the rule is applied: a
latency's spread is the host's jitter in milliseconds, which did not shrink
with the medians, so it is taken over the newest accepted median of its
cell; a rate's over the median it was read at.

``setup_s`` is not set by spread (0.25, judged by its median alone). Imports
nothing but the standard library.
"""

from __future__ import annotations

import argparse
import json
import math
import os
import re
import statistics
import sys

FACTOR = 4.0      # sqrt(2 x 8): midway between the driver's two limits
STEP = 0.005
FLOOR, CEILING = 0.01, 0.25
MIN_RUNS = 4
NOT_BY_SPREAD = {"setup_s": 0.25}
TIME_UNITS = {"ns", "us", "ms", "s"}
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

NUM = r"([0-9][0-9.eE+-]*)"
NOTE_RE = re.compile(
    r"the runs of (\S+) on workload (\S+) spread by " + NUM + r" (\S+?),")
REASON_RE = re.compile(
    r"whether (\S+) on workload (\S+) changed: the spread is " + NUM
    + r" (\S+) at the parent and " + NUM + r" (\S+) with the change")
# a benchmark PR's check, refused as too tight: the mean of the two sets'
# spreads is the tight reading the driver holds against half the bound
CHECK_RES = (
    re.compile(r"bound on (\S+) on workload (\S+): in two sets of runs of "
               r"the same code the spread is " + NUM + " and " + NUM
               + r" (\S+?),"),
    re.compile(r"(\S+) / (\S+): the middle half of \d+ runs spread " + NUM
               + " and " + NUM + r" (\S+) in the two sets"))


class TooFew(ValueError):
    """A set holds fewer runs than a quartile can stand on."""


def last_lines(text: str) -> list[dict]:
    """Every line of ``text`` that is a run's last line."""
    out = []
    for raw in text.splitlines():
        raw = raw.strip()
        if not raw.startswith("{"):
            continue
        try:
            line = json.loads(raw)
        except ValueError:
            continue
        if isinstance(line, dict) and isinstance(line.get("metrics"), dict):
            out.append(line)
    return out


def read_set(path: str) -> list[dict]:
    with open(path, errors="replace") as f:
        return last_lines(f.read())


def spread(values: list[float]) -> dict:
    """n, median, quartiles and IQR / median of one metric's runs."""
    if len(values) < MIN_RUNS:
        raise TooFew(f"{len(values)} runs; a spread needs {MIN_RUNS}")
    q1, _q2, q3 = statistics.quantiles(values, n=4)
    med = statistics.median(values)
    return {"n": len(values), "median": med, "q1": q1, "q3": q3,
            "spread": (q3 - q1) / med}


def trimmed(values: list[float]) -> float:
    """The spread without the run farthest from the median."""
    med = statistics.median(values)
    rest = sorted(values, key=lambda v: abs(v - med))[:-1]
    return spread(rest)["spread"] if len(rest) >= MIN_RUNS \
        else spread(values)["spread"]


def round_up(x: float, step: float = STEP) -> float:
    """``x`` rounded up to a whole number of steps (0.0201 -> 0.025)."""
    return round(math.ceil(round(x / step, 9)) * step, 6)


def bound_for(tight: float, loose: float) -> float:
    return min(CEILING, max(FLOOR, round_up(
        FACTOR * math.sqrt(tight * loose))))


def flags(bound: float, tight: float, loose: float) -> dict:
    """The driver's two limits against one bound."""
    return {"too_tight": tight > bound / 2,
            "too_loose": bound > FLOOR and bound > 8 * loose}


def marks(f: dict) -> str:
    return (" TOO TIGHT" if f["too_tight"] else "") \
        + (" TOO LOOSE" if f["too_loose"] else "")


def share(r: dict, medians: dict) -> float:
    """A reading as a share of a median: a time over the newest accepted
    median of its cell (``medians``: cell -> {"median", "pr"}), anything
    else, and a time in a cell with none yet, over the one it was read at."""
    newest = medians.get(r.get("cell"), {}).get("median")
    return r["value"] / (newest if r.get("unit") in TIME_UNITS and newest
                         else r["median"])


def on_record(readings: list[dict], medians: dict | None = None,
              runs: dict | None = None) -> dict:
    """The widest ``tight`` and the middle ``loose`` the rule takes, as
    shares. The driver's readings alone where the record has one of each;
    else every reading on record with these ``runs``' two shares
    (``{"tight", "loose"}``)."""
    pool = [dict(r, share=share(r, medians or {})) for r in readings]
    mine = [r for r in pool if r.get("by", "driver") == "driver"]
    alone = {"tight", "loose"} <= {r["kind"] for r in mine}
    if alone:
        pool = mine
    else:
        pool += [{"kind": k, "share": v} for k, v in (runs or {}).items()]
    tights = [r for r in pool if r["kind"] == "tight"]
    looses = [r for r in pool if r["kind"] == "loose"]
    if not tights or not looses:
        return {"drivers_alone": False, "tight": None, "loose": None}
    t = max(tights, key=lambda r: r["share"])
    lo = sorted(looses, key=lambda r: r["share"])[(len(looses) - 1) // 2]
    return {"drivers_alone": alone, "tight": t["share"], "loose": lo["share"],
            "tight_of": t, "loose_of": lo}


def judge(sets: list[list[float]], record: list[dict] = (),
          medians: dict | None = None) -> dict:
    """One metric over its sets: each set's spread, the two readings of
    these runs, and the bound the rule gives: from the driver's readings on
    ``record`` alone where it has both, else from these runs' and the
    record's. ``over`` marks a set that spreads by the bound or more."""
    per_set = [spread(v) for v in sets]
    runs_loose = max(s["spread"] for s in per_set)
    runs_tight = statistics.mean(trimmed(v) for v in sets)
    took = on_record(list(record), medians,
                     {"tight": runs_tight, "loose": runs_loose})
    tight, loose = took["tight"], took["loose"]
    bound = bound_for(tight, loose)
    return {"sets": per_set, "runs_tight": runs_tight,
            "runs_loose": runs_loose, "tight": tight, "loose": loose,
            "drivers_alone": took["drivers_alone"], "bound": bound,
            **flags(bound, tight, loose),
            "over": [s["spread"] >= bound for s in per_set],
            "second_median_off": (per_set[1]["median"] / per_set[0]["median"]
                                  - 1 if len(per_set) > 1 else None)}


def report(sets_of_lines: list[list[dict]],
           record: dict | None = None) -> dict:
    record = record or {}
    names = sorted({n for s in sets_of_lines for ln in s
                    for n in ln["metrics"]})
    out = {}
    for name in names:
        sets = [[ln["metrics"][name]["value"] for ln in s
                 if name in ln["metrics"]] for s in sets_of_lines]
        out[name] = judge([v for v in sets if v],
                          record.get("readings", {}).get(name, ()),
                          record.get("medians", {}).get(name))
    return out


# -- the record ---------------------------------------------------------------

def key_of(r: dict) -> tuple:
    return (r["kind"], r.get("by", "driver"), r.get("pr"), r.get("cell"),
            r.get("side"))


def add(record: dict, metric: str, reading: dict) -> bool:
    """Append ``reading`` unless the record has one of the same kind, PR,
    cell and side. Nothing is ever replaced or removed."""
    have = record.setdefault("readings", {}).setdefault(metric, [])
    if any(key_of(r) == key_of(reading) for r in have):
        return False
    have.append(reading)
    return True


def texts(line: dict) -> list[tuple[str, str]]:
    """(field, text) of each sentence the driver wrote into a line."""
    notes = line.get("notes") or []
    notes = [notes] if isinstance(notes, str) else list(notes)
    out = [("notes", t) for t in notes if isinstance(t, str)]
    if isinstance(line.get("reason"), str):
        out.append(("reason", line["reason"]))
    return out


def parse_text(text: str) -> list[dict]:
    """The spreads a sentence of the driver's names: metric, cell, side,
    value and unit. Empty where it names none in a form known here."""
    out = []
    for m in NOTE_RE.finditer(text):
        out.append({"metric": m[1], "cell": m[2], "side": "change",
                    "value": float(m[3]), "unit": m[4]})
    for m in REASON_RE.finditer(text):
        out.append({"metric": m[1], "cell": m[2], "side": "parent",
                    "value": float(m[3]), "unit": m[4]})
        out.append({"metric": m[1], "cell": m[2], "side": "change",
                    "value": float(m[5]), "unit": m[6]})
    for i, rx in enumerate(CHECK_RES):
        for m in rx.finditer(text):
            metric, cell = (m[1], m[2]) if i == 0 else (m[2], m[1])
            out.append({"metric": metric, "cell": cell, "side": "check",
                        "value": (float(m[3]) + float(m[4])) / 2,
                        "unit": m[5]})
    return out


def merge_ledger(record: dict, lines: list[dict], units: dict,
                 err=sys.stderr) -> list[str]:
    """Every reading that ``lines`` hold of the metrics in ``units`` and
    ``record`` lacks, added, and the newest accepted median of each metric
    and cell kept. Returns what was added, one text a reading."""
    added = []
    medians = record.setdefault("medians", {})
    lines = [ln for ln in lines
             if ln.get("pr") is not None and ln["pr"] >= record.get("since", 0)]
    by_spread = {m for m in units if m not in NOT_BY_SPREAD}
    e2e = {}      # (pr, cell) -> that line's end_to_end
    for ln in lines:
        pr, cell = ln["pr"], ln.get("workload")
        if not cell or not isinstance(ln.get("end_to_end"), dict):
            continue
        e2e[(pr, cell)] = ln["end_to_end"]
        for metric, sides in ln["end_to_end"].items():
            if ln.get("verdict") != "accepted" or metric not in by_spread \
                    or not isinstance(sides, list) or sides[-1] is None:
                continue
            old = medians.setdefault(metric, {}).get(cell)
            if old is None or old["pr"] <= pr:
                medians[metric][cell] = {"median": sides[-1], "pr": pr}

    def keep(kind, metric, pr, cell, side, value, median):
        r = {"kind": kind, "by": "driver", "pr": pr, "cell": cell,
             "side": side, "value": round(value, 6), "unit": units[metric],
             "median": median}
        if add(record, metric, r):
            added.append(f"{metric} {kind} PR {pr} {cell} {side}: "
                         f"{r['value']} {r['unit']} at {median}")

    # the sentences first: a line's loose is held against its PR's tight
    for ln in lines:
        pr = ln["pr"]
        for field, text in texts(ln):
            found = parse_text(text)
            if not found and any(n in text for n in units):
                print(f"spread.py: PR {pr} {field} names a metric and does "
                      f"not parse, skipped: {text[:160]!r}", file=err)
            for f in found:
                if f["metric"] not in by_spread:
                    continue
                sides = e2e.get((pr, f["cell"]), {}).get(f["metric"])
                med = sides[0 if f["side"] == "parent" else -1] \
                    if isinstance(sides, list) else None
                med = med or medians.get(f["metric"], {}).get(
                    f["cell"], {}).get("median")
                if not med:
                    print(f"spread.py: PR {pr} {field}: no median of "
                          f"{f['metric']} in {f['cell']} to read "
                          f"{f['value']} {f['unit']} against, skipped",
                          file=err)
                    continue
                keep("tight", f["metric"], pr, f["cell"], f["side"],
                     f["value"], med)
    for ln in lines:
        pr, cell = ln["pr"], ln.get("workload")
        for metric, field in (ln.get("spread") or {}).items():
            sides = e2e.get((pr, cell), {}).get(metric)
            if metric not in by_spread or not isinstance(sides, list) \
                    or sides[-1] is None or field is None:
                continue
            value = field * sides[-1]
            under = [r for r in record.get("readings", {}).get(metric, ())
                     if r["kind"] == "tight" and r["value"] > value
                     and (r.get("pr"), r.get("cell")) == (pr, cell)]
            if under:
                print(f"spread.py: PR {pr} {cell} {metric}: spread {field} of "
                      f"{sides[-1]} is {value:.6g} {units[metric]}, under the "
                      f"{under[0]['value']} the driver named for the same "
                      "runs without the farthest: not a share of this "
                      "median, skipped", file=err)
                continue
            keep("loose", metric, pr, cell, "change", value, sides[-1])
    return added


def committed(root: str = ROOT) -> tuple[dict, dict]:
    """(unit, bound) of each end-to-end metric in ``BENCHMARK.json``."""
    try:
        with open(os.path.join(root, "BENCHMARK.json")) as f:
            e2e = json.load(f)["end_to_end"]
    except (OSError, ValueError, KeyError):
        return {}, {}
    return ({m["name"]: m["unit"] for m in e2e},
            {m["name"]: m["bound"] for m in e2e})


def origin(r: dict) -> str:
    return (f"PR {r.get('pr')} {r.get('cell')} {r.get('side')}: "
            f"{r.get('value')} {r.get('unit')} at {r.get('median')}")


def print_record(record: dict, bounds: dict) -> None:
    """Per metric the bound the rule gives from the record alone."""
    for metric, readings in record.get("readings", {}).items():
        took = on_record(readings, record.get("medians", {}).get(metric))
        if took["tight"] is None:
            print(f"{metric}: no tight and loose on record")
            continue
        bound = bound_for(took["tight"], took["loose"])
        text = (f"{metric}: on record widest tight {took['tight']:.6f} "
                f"({origin(took['tight_of'])}) middle loose "
                f"{took['loose']:.6f} ({origin(took['loose_of'])}) -> bound "
                f"{bound}" + marks(flags(bound, took["tight"], took["loose"])))
        if metric in bounds:
            text += f"; BENCHMARK.json has {bounds[metric]}" + marks(
                flags(bounds[metric], took["tight"], took["loose"]))
        print(text)


def print_sets(result: dict) -> None:
    """Per metric each set's spread, these runs' two readings and the bound."""
    for name, j in result.items():
        for i, s in enumerate(j["sets"]):
            print(f"{name} set {i + 1}: n {s['n']} median {s['median']:.6g} "
                  f"quartiles {s['q1']:.6g} .. {s['q3']:.6g} spread "
                  f"{s['spread']:.5f}"
                  + (" OVER THE BOUND" if j["over"][i]
                     and name not in NOT_BY_SPREAD else ""))
        if name in NOT_BY_SPREAD:
            print(f"{name}: bound {NOT_BY_SPREAD[name]} (not by spread)")
            continue
        off = j["second_median_off"]
        print(f"{name}: these runs tight {j['runs_tight']:.5f} loose "
              f"{j['runs_loose']:.5f}; on record"
              + (" (the driver's alone)" if j["drivers_alone"] else "")
              + f" tight {j['tight']:.5f} loose {j['loose']:.5f} -> bound "
              f"{j['bound']}" + marks(j)
              + (f"; second median off by {off:+.5f}" if off is not None
                 else ""))


def main(argv: list[str]) -> int:
    p = argparse.ArgumentParser(prog="spread.py")
    p.add_argument("--record")
    p.add_argument("--ledger", action="append", default=[])
    p.add_argument("sets", nargs="*")
    args = p.parse_args(argv)
    record = {}
    if args.record:
        with open(args.record) as f:
            record = json.load(f)
    before = json.dumps(record)
    units, bounds = committed()
    for path in args.ledger:
        with open(path) as f:
            lines = [json.loads(ln) for ln in f if ln.strip()]
        added = merge_ledger(record, lines, units)
        print(f"{path}: {len(added)} reading(s) new to the record"
              + "".join("\n  " + a for a in added))
    if args.ledger and not args.sets:
        print_record(record, bounds)
    result, bad = {}, 0
    if args.sets or not args.ledger:
        try:
            sets = [read_set(s) for s in args.sets] if args.sets \
                else [last_lines(sys.stdin.read())]
            result = report(sets, record)
        except TooFew as e:
            print(f"spread.py: {e}", file=sys.stderr)
            return 1
        bad = sum(not ln["correct"] for s in sets for ln in s)
        print(f"{sum(map(len, sets))} runs in {len(sets)} set(s), {bad} of "
              "them not correct")
    print_sets(result)
    if args.record and json.dumps(record) != before:
        with open(args.record, "w") as f:
            json.dump(record, f, indent=1, ensure_ascii=False)
            f.write("\n")
        print(f"{args.record} rewritten")
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
