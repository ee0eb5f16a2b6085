"""{"kind": "telemetry_counter", "counter": name, "per_epoch": true}:
one of the program's own telemetry counters over the window —
``ddls_tpu.telemetry.snapshot()``: the train path resets the global
registry at the window's start and the registry keeps its values after
``disable()``, so what it holds IS the window. With ``per_epoch`` the
value is divided by the window's number of epochs. None where the
program has no counter of that name (a program older than the counter,
or a path that never counts it)."""


def read(source, ctx):
    from ddls_tpu import telemetry

    counters = telemetry.snapshot().get("counters", {})
    if source["counter"] not in counters:
        return None
    value = float(counters[source["counter"]])
    if source.get("per_epoch"):
        epochs = len(ctx.get("spans", {}).get("bench", {}).get("epoch", ()))
        if not epochs:
            return None
        value /= epochs
    return value
