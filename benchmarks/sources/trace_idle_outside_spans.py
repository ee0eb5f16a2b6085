"""{"kind": "trace_idle_outside_spans", "prefix": "ddls."}: of the
first device's idle time inside the traced window, the share that falls
inside no host span of the prefix — the program's ``telemetry.span``s,
which are ``ddls.<name>`` annotations on the profiler's clock. What is
left is idle time the program's own spans cannot name. None where the
trace has no span of the prefix (a program that writes none) or the
device was never idle."""
from benchmarks.reduce import xplane


def read(source, ctx):
    trace = ctx.get("trace")
    if trace is None or not trace.window:
        return None
    spans = [(e.start_ns, e.end_ns)
             for e in trace.host_spans(prefix=source["prefix"])]
    busy = trace.busy_intervals()
    if not spans or not busy:
        return None
    idle = xplane.gaps(busy[0], *trace.window)
    if not xplane.total(idle):
        return None
    return xplane.total(xplane.subtract(idle, spans)) / xplane.total(idle)
