"""{"kind": "trace_collective", "over": "mean"}: the share of the
traced window in which a collective ran on a device and no other
operation did. None on one device (there is nothing to exchange)."""
from benchmarks.sources import reduce_values


def read(source, ctx):
    trace = ctx.get("trace")
    if trace is None or len(trace.devices) < 2 or not trace.window_s:
        return None
    return reduce_values(
        [s / trace.window_s
         for s in trace.collective_exposed_s_per_device()],
        source.get("over", "mean"))
