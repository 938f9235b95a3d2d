"""Device time per call of programs the program names after their entry
and signature (``jit_<entry>_<tag>``): ``trace_module_time`` with
``per: "call"``, with one more case. A program that counts no
``yb_device_dispatches`` was built before its entries had names (every
one is ``jit__unknown`` there; the parent commit of the PR that adds the
metric): no time can be told apart, and the metric reads 0. Where the
program does name its entries and none of ``modules`` ran in the traced
window, the metric reads nothing, and the run is refused for it.
Arguments: ``modules`` (exact names, or prefixes ending in ``*``)."""

from benchmark.readers import trace_module_time


def names_its_entries(ctx: dict) -> bool:
    _before, after = ctx["registry"]
    return any(n == "yb_device_dispatches" for n, _ls in after)


def read(args: dict, ctx: dict):
    if ctx.get("trace") is None:
        return None
    if not names_its_entries(ctx):
        return 0.0
    return trace_module_time.read(dict(args, per="call"), ctx)
