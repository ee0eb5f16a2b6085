"""{"kind": "program_span_per_epoch", "names": [...], "stat": "median"}:
the program's own spans (``ddls_tpu.telemetry.span``), per EPOCH, over
every epoch of the window — from ``telemetry.span_intervals()``, the
(name, start, end) ring on the registry's clock that the train path
turns on with the traced window (``record_intervals``) and that keeps
its values after ``disable()``, as ``telemetry_counter`` relies on for
counters. The grouping is the program's (``telemetry.per_epoch_sums``,
which an operator's report reads too): an epoch runs from one
``train.fused_epoch`` start to the next, and the named spans that START
inside it are summed; ``stat`` is then taken over the epochs (seconds).
None where the program writes no ``train.fused_epoch``, or not every one
of the named spans (a program older than they are)."""
from benchmarks.sources import reduce_values


def read(source, ctx):
    from ddls_tpu import telemetry

    intervals = telemetry.span_intervals()
    names = set(source["names"])
    per_epoch_sums = getattr(telemetry, "per_epoch_sums", None)
    if (per_epoch_sums is None
            or not names <= {name for name, _, _ in intervals}):
        return None
    return reduce_values(per_epoch_sums(intervals, names),
                         source.get("stat", "median"))
