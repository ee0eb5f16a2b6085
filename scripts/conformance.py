"""Scenario conformance runner: one spec, four backends, one verdict.

Drives each requested ScenarioSpec through the conformance harness
(``ddls_tpu/scenarios/conformance.py``): host vs C++ lookahead
(bit-exact), host vs jax lookahead and host decisions vs the jitted
episode kernel (1e-9, x64), the golden-stats fabric check, and the lint
engine's backend-surface-parity rule.

Usage::

    python scripts/conformance.py                       # all registry specs
    python scripts/conformance.py --spec failures       # one spec
    python scripts/conformance.py --spec my_spec.json   # spec file
    python scripts/conformance.py --json                # machine-readable
    python scripts/conformance.py --legs host_native golden lint

Exit codes: 0 every leg ok (skipped/unavailable legs are reported but
pass unless --strict), 1 divergence found, 2 usage/error.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# sim-only workload: pinned to the CPU whatever the caller exported (the
# parity tolerances are the CPU's), and x64 set before jax ever loads
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ.setdefault("JAX_ENABLE_X64", "1")


def main(argv=None) -> int:
    from ddls_tpu.scenarios import REGISTRY, get_spec
    from ddls_tpu.scenarios.conformance import DEFAULT_LEGS, run_conformance

    parser = argparse.ArgumentParser(
        description="run scenario conformance across simulator backends")
    parser.add_argument("--spec", nargs="*", default=None,
                        help="registry names or spec-JSON paths "
                             f"(default: all of {sorted(REGISTRY)})")
    parser.add_argument("--legs", nargs="*", default=None,
                        choices=list(DEFAULT_LEGS),
                        help="restrict to these legs")
    parser.add_argument("--seed", type=int, default=0)
    parser.add_argument("--max-decisions", type=int, default=500)
    parser.add_argument("--sim-seconds", type=float, default=None,
                        help="override the spec's episode horizon")
    parser.add_argument("--json", action="store_true",
                        help="emit one machine-readable JSON document")
    parser.add_argument("--strict", action="store_true",
                        help="treat skipped/unavailable legs as failures")
    parser.add_argument("--run-dir", default=None,
                        help="write a RunLedger directory (manifest + "
                             "telemetry sink + the report doc as "
                             "result.json — telemetry/runlog.py)")
    args = parser.parse_args(argv)

    ledger = None
    if args.run_dir:
        from ddls_tpu.telemetry.runlog import RunLedger

        ledger = RunLedger(args.run_dir, kind="conformance",
                           config={"spec": args.spec, "legs": args.legs,
                                   "seed": args.seed,
                                   "max_decisions": args.max_decisions,
                                   "sim_seconds": args.sim_seconds,
                                   "strict": args.strict}).open()

    names = args.spec if args.spec else sorted(REGISTRY)
    reports = []
    for name in names:
        try:
            spec = get_spec(name)
        except Exception as exc:
            print(f"error: {exc}", file=sys.stderr)
            if ledger is not None:
                ledger.finalize()
            return 2
        reports.append(run_conformance(
            spec, seed=args.seed, max_decisions=args.max_decisions,
            sim_seconds=args.sim_seconds, legs=args.legs))
    if ledger is not None and reports:
        # one conformance run may span several specs: record every
        # fingerprint in the manifest config (rewritten in place)
        ledger.update_config({"scenario_fingerprints": [
            r["spec"].get("fingerprint") for r in reports]})

    passing = ("ok",) if args.strict else ("ok", "skipped", "unavailable")
    ok = all(leg["status"] in passing
             for r in reports for leg in r["legs"])
    doc = {"ok": ok, "specs": reports}
    if ledger is not None:
        ledger.record_result(doc)
        ledger.finalize()
    if args.json:
        print(json.dumps(doc, indent=2, default=str))
    else:
        for r in reports:
            print(f"spec {r['spec']['name']} "
                  f"(fp {r['spec']['fingerprint']}):")
            for leg in r["legs"]:
                line = f"  {leg['leg']:<12} {leg['status']}"
                if leg.get("reason"):
                    line += f" ({leg['reason']})"
                if "events_a" in leg:
                    line += (f" [{leg['events_a']} vs {leg['events_b']} "
                             f"events, {leg['decisions']} decisions, "
                             f"rtol={leg['rtol']}]")
                print(line)
                if leg.get("divergence"):
                    print("    " + str(leg["divergence"]).replace(
                        "\n", "\n    "))
                for k, v in leg.get("mismatches", {}).items():
                    print(f"    {k}: got {v['got']} want {v['want']}")
                for f in leg.get("findings", []):
                    print(f"    {f}")
        print("CONFORMANCE " + ("OK" if ok else "FAILED"))
    return 0 if ok else 1


if __name__ == "__main__":
    sys.exit(main())
