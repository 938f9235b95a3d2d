"""Share of the chip's HBM bandwidth that the named device programs
reach, in percent: the bytes one call has to read, over the device time
one call takes, over the peak.

* bytes a call: growth over the window of
  ``yb_device_program_read_bytes{entry}`` over growth of
  ``yb_device_dispatches{entry}``, summed over ``entries``: the program
  counts, at each dispatch, the resident bytes of the planes the
  signature names (its columns' value planes, every column's presence
  planes, the MVCC planes), not every plane of the run;
* seconds a call: device time of ``modules`` in the traced window over
  their calls (``trace_module_time``'s figures);
* peak: ``hbm_bytes_per_s`` of the device's entry in ``peaks.json``.

The counters cover the whole window and the trace its first seconds;
both are means per call of the same programs. A program without the
counters (built before them) reads 0; counters that did not grow, or no
such module in the trace, read nothing.
Arguments: ``entries`` (values of the ``entry`` label), ``modules``."""

from benchmark import registry
from benchmark.readers import named_program_time, trace_module_time


def read(args: dict, ctx: dict):
    trace = ctx.get("trace")
    if trace is None:
        return None
    if not named_program_time.names_its_entries(ctx):
        return 0.0
    before, after = ctx["registry"]
    labels = {"entry": args["entries"]}
    calls = registry.delta(before, after, "yb_device_dispatches", labels)
    if calls <= 0:
        return None
    nbytes = registry.delta(before, after, "yb_device_program_read_bytes",
                            labels)
    names = [m for m in trace["module_s"]
             if trace_module_time.matches(m, args["modules"])]
    n = sum(trace["module_calls"][m] for m in names)
    seconds = sum(trace["module_s"][m] for m in names)
    if not n or seconds <= 0:
        return None
    return (nbytes / calls) / (seconds / n) \
        / ctx["peaks"]["hbm_bytes_per_s"] * 100.0
