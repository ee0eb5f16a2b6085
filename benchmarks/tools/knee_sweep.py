"""Find the knee of the serve cell once, on the chip, in one process:

    python3 benchmarks/tools/knee_sweep.py --workload <serve cell> \
        --rates 250,500,1000,1500,2000,3000 --seconds 10

For each offered rate it drives the same pump as the cell for
``--seconds`` and prints one row: share answered by the policy, p50 and
p99 (ms, from the scheduled arrival), the generator's own lateness, the
backlog when the input ended. The knee is the highest rate with >= 99%
answered by the policy, p99 <= ``--slo-ms`` and no backlog; 0.8 x knee
then goes into the traffic file by hand, and the table into PERF.md.
Not part of a benchmark run.
"""
import argparse
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--rates", required=True)
    parser.add_argument("--seconds", type=float, default=10.0)
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--slo-ms", type=float, default=50.0)
    args = parser.parse_args(argv)

    import numpy as np

    from benchmarks import harness, loadgen
    from benchmarks.paths import serve

    cell = harness.load_cell(args.workload)
    harness.start_backend(cell.chips)
    server, _, _, pool, touched = serve.setup(cell, args.seed)
    harness.note("sweep_setup", {"buckets_touched": touched})
    rows = []
    for rate in (float(r) for r in args.rates.split(",")):
        server.stats = type(server.stats)()
        trace = loadgen.fixed_span_trace(args.seconds, rate, args.seed,
                                         **cell.traffic["arrivals"])
        rec = harness.Recorder()
        with harness.GcWatch() as gc_watch:
            out = serve.pump(server, pool, trace, rec,
                             cell.traffic["drain_timeout_s"])
        summary = serve.summarise(out, args.seconds,
                                  cell.traffic["percentile"],
                                  cell.traffic["subwindows"], rate)
        policy_share = 1.0 - summary["failed"] / summary["attempted"]
        late = np.asarray(rec.spans["generator_late"])
        row = {"offered_rps": rate, "attempted": summary["attempted"],
               "policy_share": policy_share,
               "decisions_per_s": summary["decisions_per_s"],
               "p50_ms": summary["p50_ms"], "p99_ms": summary["pq_ms"],
               "p99_ms_median_of_slices": summary[
                   "pq_ms_median_of_slices"],
               "pump_stalls": serve.pump_stalls(rec),
               "max_ms": summary["max_ms"],
               "generator_late_p99_ms": float(np.percentile(late, 99))
               * 1e3,
               "elapsed_s": out["elapsed_s"],
               "backlog_at_end": out["backlog_at_end"],
               "sources": summary["sources"],
               "gc_gen2": gc_watch.summary()["gen2"],
               "batch_occupancy": server.stats.summary()[
                   "batch_occupancy"]}
        row["meets"] = bool(policy_share >= 0.99
                            and summary["pq_ms"] is not None
                            and summary["pq_ms"] <= args.slo_ms
                            and out["backlog_at_end"] == 0)
        rows.append(row)
        print(f"[sweep] {json.dumps(row)}", flush=True)
    knee = max((r["offered_rps"] for r in rows if r["meets"]), default=None)
    print(json.dumps({"knee_rps": knee,
                      "rate_at_0.8": knee * 0.8 if knee else None}),
          flush=True)
    server.close()
    return 0


if __name__ == "__main__":
    sys.exit(main())
