"""A percentile of the latency of one class of operation (``class``:
read, write, or a statement's name as its traffic file has it), over
every operation of that class that the window answered,
in milliseconds. Arguments: ``class``, ``p``, ``min_samples``."""

import statistics


def read(args: dict, ctx: dict):
    xs = ctx["client"]["latency_ms"].get(args["class"], [])
    if len(xs) < max(2, args.get("min_samples", 2)):
        return None
    if args["p"] == 50:
        return statistics.median(xs)
    return statistics.quantiles(xs, n=100, method="inclusive")[args["p"] - 1]
