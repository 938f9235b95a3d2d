"""Device time of named XLA programs in the traced window, in
milliseconds: the sum of the durations of their events on the device's
``XLA Modules`` line, per call of them (``per``: "call") or per operation
that the clients completed while the trace ran (``per``: "operation").
Arguments: ``modules`` (names as the trace has them, ``jit_<function>``;
a trailing ``*`` matches a prefix), ``per``, and optionally
``calls_per_operation`` ([least, most]): how many of the programs one
operation of the cell's traffic runs. Outside it something else shares
the names, and the reader reads nothing. (A CPU rehearsal's stand-in
events come two to a call and are not held to it.)"""

from __future__ import annotations


def matches(name: str, patterns: list[str]) -> bool:
    return any(name.startswith(p[:-1]) if p.endswith("*") else name == p
               for p in patterns)


def read(args: dict, ctx: dict):
    trace = ctx.get("trace")
    if trace is None:
        return None
    names = [m for m in trace["module_s"] if matches(m, args["modules"])]
    if not names:
        return None
    seconds = sum(trace["module_s"][m] for m in names)
    calls = sum(trace["module_calls"][m] for m in names)
    if args["per"] == "call":
        n = calls
    else:   # "operation": of any class
        n = sum(t <= trace["traced_s"]
                for ts in ctx["client"]["done_s"].values() for t in ts)
    if not n:
        return None
    least, most = args.get("calls_per_operation", (0, float("inf")))
    if not ctx.get("rehearsal") and not least <= calls / n <= most:
        return None
    return seconds / n * 1e3
