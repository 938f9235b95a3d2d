"""Growth over the window of a counter that stands beside a span of the
program: ``registry_counter_delta`` with one more case. A program that
publishes no series of that name at all was built before the span and
its counter existed (the parent commit of the PR that adds the metric,
which the driver runs with this benchmark laid over it): it counted
nothing, and the metric reads 0 there, as ``span_histogram_mean`` does.
Arguments: ``name``, ``labels`` ({label: value or [values]})."""

from benchmark.readers import registry_counter_delta


def read(args: dict, ctx: dict):
    value = registry_counter_delta.read(args, ctx)
    return 0.0 if value is None else value
