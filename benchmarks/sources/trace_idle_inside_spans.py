"""{"kind": "trace_idle_inside_spans", "names": ["ddls.<span>", ...]}:
the complement of ``trace_idle_outside_spans``: of the first device's
idle time inside the traced window, the share that falls inside the
NAMED host spans (the program's ``telemetry.span``s are ``ddls.<name>``
annotations on the profiler's clock). Over every ``ddls.*`` name the
two sum to 1. None where the trace holds none of the named spans (a
program that writes none) or the device was never idle."""
import os

from benchmarks.reduce import xplane


def read(source, ctx):
    trace = ctx.get("trace")
    if trace is None or not trace.window:
        return None
    names = set(source["names"])
    spans = [(e.start_ns, e.end_ns) for e in trace.host_spans(
        prefix=os.path.commonprefix(list(names))) if e.name in names]
    busy = trace.busy_intervals()
    if not spans or not busy:
        return None
    idle = xplane.gaps(busy[0], *trace.window)
    if not xplane.total(idle):
        return None
    return 1.0 - xplane.total(xplane.subtract(idle, spans)) \
        / xplane.total(idle)
