"""Growth of a counter of the program over the window.
Arguments: ``name``, ``labels`` ({label: value or [values]})."""

from benchmark import registry


def read(args: dict, ctx: dict):
    before, after = ctx["registry"]
    if not any(n == args["name"] for n, _ls in after):
        return None
    return registry.delta(before, after, args["name"], args.get("labels", {}))
