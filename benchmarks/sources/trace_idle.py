"""{"kind": "trace_idle", "over": "mean" | "max"}: 1 - (union of the
device's operation intervals / traced window), over the chips."""
from benchmarks.sources import reduce_values


def read(source, ctx):
    trace = ctx.get("trace")
    if trace is None:
        return None
    return reduce_values(trace.idle_share_per_device(),
                         source.get("over", "mean"))
