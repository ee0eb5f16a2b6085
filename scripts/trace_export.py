"""Export a flight-recorder trace to Chrome-trace/Perfetto JSON.

Usage::

    python scripts/trace_export.py trace.jsonl -o trace_perfetto.json

The input is a flight JSONL file (``ddls_tpu.telemetry.flight
.save_jsonl``, or ``scripts/trace_diff.py run --save-a``; flight records
inside a mixed telemetry sink are picked out automatically). The output
opens in ui.perfetto.dev or chrome://tracing — the same viewer as a jax
profiler capture (``experiment.profile_jax``), where telemetry's spans
appear as ``ddls.<name>`` annotations — with
one row per worker (jobs as duration slices), one per channel (flow
mounts), instant markers for arrivals/decisions/blocks, and a
running-jobs counter track.

Exit codes: 0 on success, 2 when the input is missing/holds no flight
events.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from ddls_tpu.telemetry import flight  # noqa: E402


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="flight trace JSONL -> Chrome-trace/Perfetto JSON")
    parser.add_argument("trace", help="flight JSONL file")
    parser.add_argument("-o", "--out", default=None,
                        help="output path (default: <trace>.perfetto.json)")
    args = parser.parse_args(argv)

    if not os.path.exists(args.trace):
        print(f"error: no such file: {args.trace}", file=sys.stderr)
        return 2
    events = flight.load_jsonl(args.trace)
    if not events:
        print(f"error: no flight events in {args.trace}", file=sys.stderr)
        return 2

    out_path = args.out or (os.path.splitext(args.trace)[0]
                            + ".perfetto.json")
    trace = flight.to_perfetto(events)
    with open(out_path, "w") as f:
        json.dump(trace, f)
    n_slices = sum(1 for e in trace["traceEvents"] if e.get("ph") == "X")
    n_markers = sum(1 for e in trace["traceEvents"] if e.get("ph") == "i")
    print(f"{out_path}: {len(trace['traceEvents'])} trace events "
          f"({n_slices} slices, {n_markers} markers) from "
          f"{len(events)} flight events — open in ui.perfetto.dev")
    return 0


if __name__ == "__main__":
    sys.exit(main())
