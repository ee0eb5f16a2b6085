"""One reader per KIND of per-layer source. ``read(source, ctx)`` takes
the ``source`` object of ``layer_metrics/<name>.json`` and the run's
context and returns a number, or ``None`` when there is nothing to
read (the harness then leaves the metric out). A per-layer metric of a
known kind is pure data; a new kind is one new file here.

The context: ``spans`` ({"bench" | "program": {name: [seconds]}}),
``counters`` ({name: number}, deltas over the window), ``compile``
({"setup" | "window": CompileMeter totals}), ``memory_stats`` (one dict
per device), ``trace`` (``reduce.xplane.Trace`` or None), ``device``, ``cell``.
"""
import statistics
from typing import Optional, Sequence


def reduce_values(values: Sequence[float], stat: str) -> Optional[float]:
    """median | mean | sum | max | min | p<q> of a list; None if empty."""
    values = list(values)
    if not values:
        return None
    if stat == "median":
        return statistics.median(values)
    if stat == "mean":
        return statistics.fmean(values)
    if stat in ("sum", "max", "min"):
        return {"sum": sum, "max": max, "min": min}[stat](values)
    if stat.startswith("p"):
        import numpy as np

        return float(np.percentile(values, float(stat[1:])))
    raise ValueError(f"unknown stat {stat!r}")
