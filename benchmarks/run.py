"""Run one cell of the benchmark once.

    python3 benchmarks/run.py --workload <name> --seed <n> \
        --seconds <run_seconds> --trace <0|1>

A fresh process: it fails (no result line) when jax finds no
accelerator or fewer chips than the cell asks for, warms up the cell's
own shapes as set-up, measures for ``--seconds``, checks the outputs,
and prints as its LAST line one JSON object with the keys ``correct``,
``attempted``, ``failed``, ``metrics``, ``device`` (and ``breakdown``
when traced). With ``--trace 0`` the metrics are the cell's end-to-end
metrics; with ``--trace 1`` its per-layer metrics, read from the
benchmark's spans, the program's counters and a profiler trace of a
short window. Facts beside the result go on earlier ``[bench]`` lines.
"""
import time

T_START = time.perf_counter()  # set-up runs from here to the window

import argparse
import os
import shutil
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--seconds", type=float, default=None)
    parser.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = parser.parse_args(argv)

    from benchmarks import harness

    cell = harness.load_cell(args.workload)
    if args.seconds is None:
        args.seconds = float(cell.run_seconds)

    cache_dir = harness.start_backend(cell.chips)
    import jax

    meter = harness.CompileMeter()
    devices = jax.devices()
    harness.note("start", {
        "workload": cell.name, "config": cell.config_name,
        "traffic": cell.traffic_name, "path": cell.path,
        "seed": args.seed, "seconds": args.seconds, "trace": args.trace,
        "jax": jax.__version__, "platform": devices[0].platform,
        "device_kind": devices[0].device_kind,
        "device_count": len(devices), "host_cores": os.cpu_count(),
        "compile_cache_dir": cache_dir})

    trace_dir = None
    if args.trace:
        # kept until the cell's next traced run (benchmarks/out/ is
        # git-ignored): tools/trim_trace.py cut the test trace from one
        trace_dir = os.path.join(harness.OUT_DIR, "trace", cell.name)
        shutil.rmtree(trace_dir, ignore_errors=True)
        os.makedirs(trace_dir, exist_ok=True)
    rec = harness.Recorder(trace_dir)
    out = harness.load_path(cell.path).run(cell, args, rec, meter, T_START)
    harness.note("compile", {"setup": out["ctx"]["compile"]["setup"],
                             "window": out["ctx"]["compile"]["window"]})

    device = harness.device_facts(out["ctx"].get("memory_stats"),
                                  out["ctx"].get("scratch_bytes", 0))
    breakdown = None
    if args.trace:
        from benchmarks.reduce import xplane

        ctx = out["ctx"]
        path = xplane.find_xplane(trace_dir)
        ctx["trace"] = xplane.Trace.from_file(path) if path else None
        ctx["device"], ctx["cell"] = device, cell
        metrics = harness.layer_metrics(cell, ctx)
        trace = ctx["trace"]
        if trace is not None and trace.busy_s():
            device["busy_s"] = trace.busy_s()
            device["window_s"] = trace.window_s
            breakdown = {
                "device_ops": [list(x) for x in trace.top_ops(10)],
                "idle_gaps": [list(x) for x in trace.idle_gaps(5)]}
            harness.note("trace", {
                "file": path, "bytes": os.path.getsize(path),
                "programs_device_s": trace.program_names(),
                "idle_share_per_device": trace.idle_share_per_device(),
                "idle_s_by_host_span": trace.idle_by_span()})
    else:
        metrics = {m["name"]: {"value": out["end_to_end"].get(m["name"]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end}
    missing = [k for k, v in metrics.items() if v["value"] is None]
    if missing:
        raise SystemExit(f"no value for {missing}")
    compared = out.get("compared")
    for name, pair in (compared or {}).items():
        print(f"[compared] {name} {pair['value']} limit {pair['limit']}",
              file=sys.stderr, flush=True)
    print(harness.result_line(out["correct"], out["attempted"],
                              out["failed"], metrics, device, breakdown,
                              compared),
          flush=True)
    return 0


if __name__ == "__main__":
    # spawned env workers re-import this file as __mp_main__: everything
    # that runs stays under this guard
    sys.exit(main())
