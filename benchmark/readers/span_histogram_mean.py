"""Mean of a histogram that a span of the program feeds, over the window:
``registry_histogram_mean`` with one more case. A program that publishes
no series of that name at all was built before the span existed (the
parent commit of the PR that adds the metric, which the driver runs with
this benchmark laid over it): it attributes no time to the span, and the
metric reads 0 there. A series that is there and did not grow in the
window reads nothing, as before: a cell that lists the metric and never
enters the span is a fault of the listing.
Arguments: ``name``, ``labels`` ({label: value or [values]}), ``scale``."""

from benchmark.readers import registry_histogram_mean


def read(args: dict, ctx: dict):
    _before, after = ctx["registry"]
    if not any(n == args["name"] + "_count" for n, _ls in after):
        return 0.0
    return registry_histogram_mean.read(args, ctx)
