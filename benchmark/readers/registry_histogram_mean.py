"""Mean of a histogram of the program over the window: growth of its
``_sum`` over growth of its ``_count``, times ``scale``.
Arguments: ``name``, ``labels`` ({label: value or [values]}), ``scale``."""

from benchmark import registry


def read(args: dict, ctx: dict):
    before, after = ctx["registry"]
    labels = args.get("labels", {})
    n = registry.delta(before, after, args["name"] + "_count", labels)
    if n <= 0:
        return None
    s = registry.delta(before, after, args["name"] + "_sum", labels)
    return s / n * args.get("scale", 1.0)
