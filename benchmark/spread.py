#!/usr/bin/env python3
"""From sets of runs of one tree to the bound of each end-to-end metric.

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

which is four times the spread where the two readings agree. The
driver's machines are not the builder's: ``--record`` names a file of the
readings the driver itself printed (``spread_record.json``: per metric a
list of {"tight" or "loose": share of today's median, "from": where the
ledger has it}), and the rule then takes the widest ``tight`` and the
narrowest ``loose`` on record, these runs' among them. ``setup_s`` is not
set by spread (0.25, judged by its median alone). Imports nothing but the
standard library.
"""

from __future__ import annotations

import json
import math
import statistics
import sys

FACTOR = 4.0      # sqrt(2 x 8): midway between the driver's two limits
STEP = 0.005
FLOOR, CEILING = 0.01, 0.25
MIN_RUNS = 4
NOT_BY_SPREAD = {"setup_s": 0.25}


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


def judge(sets: list[list[float]], record: list[dict] = ()) -> dict:
    """One metric over its sets: each set's spread, the two readings of
    these runs, and the bound the rule gives from the widest ``tight``
    and the narrowest ``loose`` among them and the ``record``'s."""
    per_set = [spread(v) for v in sets]
    runs_loose = max(s["spread"] for s in per_set)
    runs_tight = statistics.mean(trimmed(v) for v in sets)
    tight = max([runs_tight] + [r["tight"] for r in record if "tight" in r])
    loose = min([runs_loose] + [r["loose"] for r in record if "loose" in r])
    bound = bound_for(tight, loose)
    return {"sets": per_set, "runs_tight": runs_tight,
            "runs_loose": runs_loose, "tight": tight, "loose": loose,
            "bound": bound, "too_tight": tight > bound / 2,
            "too_loose": bound > FLOOR and bound > 8 * loose,
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
        out[name] = judge([v for v in sets if v], record.get(name, ()))
    return out


def main(argv: list[str]) -> int:
    record = {}
    if argv[:1] == ["--record"]:
        with open(argv[1]) as f:
            record = json.load(f)
        argv = argv[2:]
    try:
        sets = [read_set(p) for p in argv] if argv \
            else [last_lines(sys.stdin.read())]
        result = report(sets, record)
    except TooFew as e:
        print(f"spread.py: {e}", file=sys.stderr)
        return 1
    bad = sum(not ln["correct"] for s in sets for ln in s)
    print(f"{sum(map(len, sets))} runs in {len(sets)} set(s), {bad} of them "
          "not correct")
    for name, j in result.items():
        for i, s in enumerate(j["sets"]):
            print(f"{name} set {i + 1}: n {s['n']} median {s['median']:.6g} "
                  f"quartiles {s['q1']:.6g} .. {s['q3']:.6g} spread "
                  f"{s['spread']:.5f}")
        if name in NOT_BY_SPREAD:
            print(f"{name}: bound {NOT_BY_SPREAD[name]} (not by spread)")
            continue
        off = j["second_median_off"]
        print(f"{name}: these runs tight {j['runs_tight']:.5f} loose "
              f"{j['runs_loose']:.5f}; on record tight {j['tight']:.5f} "
              f"loose {j['loose']:.5f} -> bound {j['bound']}"
              + (" TOO TIGHT" if j["too_tight"] else "")
              + (" TOO LOOSE" if j["too_loose"] else "")
              + (f"; second median off by {off:+.5f}" if off is not None
                 else ""))
    return 1 if bad else 0


if __name__ == "__main__":
    sys.exit(main(sys.argv[1:]))
