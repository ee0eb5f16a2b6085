"""Cut a recorded trace down to a test fixture:

    python3 benchmarks/tools/trim_trace.py <trace dir or .xplane.pb> \
        <out.xplane.pb.gz> [--ops-ms 150]

Keeps the device planes' ``XLA Modules`` line whole and of ``XLA Ops``
the events that start within ``--ops-ms`` of the traced window's start
(operation names shortened to ``fusion.12``), the benchmark's own
``bench.*`` host spans and nothing else. The window span is cut to the
kept operations, so busy and idle of the fixture are those of that
slice. Not part of a benchmark run.
"""
import argparse
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("source")
    parser.add_argument("out")
    parser.add_argument("--ops-ms", type=float, default=150.0)
    args = parser.parse_args(argv)

    from benchmarks.reduce import xplane as X

    path = (args.source if os.path.isfile(args.source)
            else X.find_xplane(args.source))
    full = X.Trace.from_file(path)
    lo, hi = full.window
    cut = lo + args.ops_ms * 1e6
    planes = []
    for plane in full.devices:
        ops = [X.Event(X.short_op_name(e.name), e.start_ns, e.end_ns)
               for e in plane.line(X.OPS_LINE).events
               if lo <= e.start_ns < cut]
        planes.append(X.Plane(plane.name, [
            X.Line(X.OPS_LINE, ops),
            X.Line(X.MODULES_LINE, plane.line(X.MODULES_LINE).events)]))
    for plane in full.hosts:
        lines = []
        for line in plane.lines:
            spans = [X.Event(e.name, e.start_ns, min(e.end_ns, cut))
                     if e.name == X.WINDOW_SPAN else e
                     for e in line.events
                     if e.name.startswith(X.BENCH_SPAN_PREFIX)]
            if spans:
                lines.append(X.Line(line.name, spans))
        planes.append(X.Plane(plane.name, lines))
    X.write_xplane(planes, args.out)
    trimmed = X.Trace.from_file(args.out)
    print({"from": path, "bytes": os.path.getsize(args.out),
           "ops_kept": sum(len(p.line(X.OPS_LINE).events)
                           for p in trimmed.devices),
           "window_s": trimmed.window_s, "busy_s": trimmed.busy_s(),
           "programs": trimmed.program_names()})
    return 0


if __name__ == "__main__":
    sys.exit(main())
