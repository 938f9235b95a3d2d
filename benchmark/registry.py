"""The program's counters and histograms, read through the one format it
publishes them in: Prometheus text (``MetricRegistry.prometheus_text``)."""

from __future__ import annotations

import re

_LINE = re.compile(r"^([A-Za-z_:][\w:]*)(?:\{(.*)\})?\s+(\S+)$")
_LABEL = re.compile(r'(\w+)="([^"]*)"')


def parse(text: str) -> dict:
    """{(series name, ((label, value), ...)): number}; a series printed
    more than once (one registry per tserver) is summed."""
    out: dict = {}
    for line in text.splitlines():
        if not line or line[0] == "#":
            continue
        m = _LINE.match(line)
        if not m:
            continue
        try:
            value = float(m.group(3))
        except ValueError:
            continue
        key = (m.group(1), tuple(sorted(_LABEL.findall(m.group(2) or ""))))
        out[key] = out.get(key, 0.0) + value
    return out


def delta(before: dict, after: dict, name: str, labels: dict) -> float:
    """Growth over the window of every series called ``name`` whose labels
    include ``labels`` ({label: value or list of values}), summed."""
    total = 0.0
    for (n, ls), v in after.items():
        if n != name:
            continue
        have = dict(ls)
        if all(have.get(k) in (w if isinstance(w, list) else [w])
               for k, w in labels.items()):
            total += v - before.get((n, ls), 0.0)
    return total
