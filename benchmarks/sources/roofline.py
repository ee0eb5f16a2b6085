"""{"kind": "roofline", "match": regex, "shape_fn": name}: the least
time the chip could take for one execution of the program — the larger
of operations / peak FLOP/s and bytes / peak bytes/s, both computed
from the cell's shapes by ``benchmarks/shape_fns/<name>.py`` — over the
median device time of that program in the trace."""
import importlib

from benchmarks import harness
from benchmarks.sources import trace_program_time


def bound(source, ctx):
    """(flops, bytes, least seconds, which bound applies)."""
    fn = importlib.import_module(
        f"benchmarks.shape_fns.{source['shape_fn']}")
    flops, nbytes = fn.flops_and_bytes(ctx["cell"])
    peaks = harness.peaks_for(ctx["device"]["kind"])
    t_flops = flops / peaks[source.get("flops_peak", "bf16_flops_per_s")]
    t_bytes = nbytes / peaks["hbm_bytes_per_s"]
    return (flops, nbytes, max(t_flops, t_bytes),
            "compute" if t_flops >= t_bytes else "memory")


def read(source, ctx):
    device_s = trace_program_time.read(
        {"match": source["match"], "stat": "median"}, ctx)
    if not device_s:
        return None
    return bound(source, ctx)[2] / device_s
