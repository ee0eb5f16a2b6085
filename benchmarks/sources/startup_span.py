"""{"kind": "startup_span", "names": [span names]}: seconds of set-up
that the program's start-up registry (``ddls_tpu.telemetry.startup``,
always on: set-up runs before the benchmark turns telemetry on) holds
under the named spans, as the UNION of their intervals — jax reports
the trace of an inner jit inside its outer program's, so a sum would
count it twice. None where the program has no start-up registry or no
span of these names."""
from benchmarks.reduce import xplane


def read(source, ctx):
    try:
        from ddls_tpu.telemetry import startup
    except ImportError:
        return None
    names = set(source["names"])
    spans = [(t0, t1) for name, t0, t1
             in startup.registry().span_intervals() if name in names]
    return xplane.total(xplane.union(spans)) if spans else None
