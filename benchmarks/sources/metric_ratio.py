"""{"kind": "metric_ratio", "num": metric, "den": metric}: one
per-layer metric over another, each read (and scaled) as its own file
says. None where either is, or the denominator is 0."""
from benchmarks import harness


def read(source, ctx):
    num = harness.read_layer_metric(source["num"], ctx)
    den = harness.read_layer_metric(source["den"], ctx)
    if num is None or not den:
        return None
    return num / den
