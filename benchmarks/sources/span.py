"""{"kind": "span", "origin": "bench" | "program", "name": ...,
"stat": "median"}: a statistic over the durations (seconds) of one
span inside the window. ``bench`` spans are the benchmark's own
wrappers; ``program`` spans are the program's ``telemetry.span``s."""
from benchmarks.sources import reduce_values


def read(source, ctx):
    spans = ctx.get("spans", {}).get(source.get("origin", "bench"), {})
    return reduce_values(spans.get(source["name"], ()),
                         source.get("stat", "median"))
