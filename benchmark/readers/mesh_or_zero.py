"""A metric of the mesh path (``ts.multi_agg_scan`` -> one ``shard_map``
program over the node's chips), read through the reader its arguments
name, with one more case. A program whose registry holds no
``yb_mesh_scans`` series at all was built before the mesh counted its
requests and named its program (the parent commit of the PR that adds
these metrics, which the driver runs with this benchmark laid over it):
it serves the cell a tablet at a time, attributes nothing to the mesh,
and the metric reads 0 there. Where the series is there, the named
reader decides: a cell that lists the metric and whose statements fell
back to per-tablet programs reads nothing, and the run is refused.
Arguments: ``reader`` (a module of ``benchmark/readers``), ``args`` (its
arguments)."""

import importlib


def read(args: dict, ctx: dict):
    _before, after = ctx["registry"]
    if not any(n == "yb_mesh_scans" for n, _ls in after):
        return 0.0
    reader = importlib.import_module("benchmark.readers." + args["reader"])
    return reader.read(args.get("args", {}), ctx)
