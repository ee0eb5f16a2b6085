"""{"kind": "trace_scope_time", "program": regex, "scopes": [names],
"stat": "median", "share": "of_program" | "complement" (optional)}:
device seconds per execution of the matching jitted program in which an
operation traced under one of the ``jax.named_scope`` names ran (the
union of those operations' intervals on the first device), reduced over
the executions in the trace. With ``share`` the value is that time over
the execution's device time, or 1 minus it (``complement``: what no
listed scope names).

The scope of a device operation is not among what ``reduce/xplane.py``
keeps, so the cell's xplane is read again (``reduce/op_scopes.py``),
found the way ``run.py`` finds it and kept in the context for the next
metric: a run that has a trace but no file where ``run.py`` puts it
raises, because then the harness moved it and this reader must follow.
None without a trace, and None where no operation of the program
carries any of the scopes: a program that was never given them, which
is not the same as a scope that took no time."""
import os

from benchmarks import harness
from benchmarks.reduce import op_scopes, xplane
from benchmarks.sources import reduce_values


def device_ops(ctx):
    """The first device's operations with their scopes, or None."""
    if "device_ops" not in ctx:
        ctx["device_ops"] = None
        if ctx.get("trace") is not None:
            trace_dir = os.path.join(harness.OUT_DIR, "trace",
                                     ctx["cell"].name)
            path = xplane.find_xplane(trace_dir)
            if not path:
                raise FileNotFoundError(
                    f"the run has a trace but {trace_dir} holds no "
                    "xplane: benchmarks/run.py keeps it elsewhere now")
            devices = op_scopes.load_device_ops(path)
            ctx["device_ops"] = devices[0] if devices else None
    return ctx["device_ops"]


def read(source, ctx):
    device = device_ops(ctx)
    if device is None:
        return None
    runs = op_scopes.scoped_seconds(device, source["program"],
                                    source["scopes"])
    if not runs or not any(scoped for scoped, _ in runs):
        return None
    share = source.get("share")
    if share is None:
        values = [scoped for scoped, _ in runs]
    else:
        values = [scoped / whole for scoped, whole in runs if whole]
        if share == "complement":
            values = [1.0 - v for v in values]
    return reduce_values(values, source.get("stat", "median"))
