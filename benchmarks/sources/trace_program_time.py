"""{"kind": "trace_program_time", "match": regex, "stat": "median"}: a
statistic over the device durations (seconds) of every execution of
the jitted programs whose module name matches, read from the trace's
``XLA Modules`` line; averaged over the devices that ran it."""
from benchmarks.sources import reduce_values


def read(source, ctx):
    trace = ctx.get("trace")
    if trace is None:
        return None
    per_device = [reduce_values(d, source.get("stat", "median"))
                  for d in trace.program_durations_s(source["match"])]
    per_device = [v for v in per_device if v is not None]
    return sum(per_device) / len(per_device) if per_device else None
