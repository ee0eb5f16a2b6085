"""Cross-backend simulator trace diffing: find the FIRST divergent event.

The build carries three semantics-locked simulator backends (host Python,
C++ lookahead, fully-jitted episode kernels) whose parity
tests pin endpoints only — this tool turns "parity failed" into "event
412: lookahead jct 3.81 vs 3.84" by running ONE scenario through two
backends with the flight recorder on (ddls_tpu/telemetry/flight.py) and
reporting the first event where the ordered traces disagree, with both
sides' full payload context.

Usage::

    # seeded episode, host vs C++ lookahead engine (bit-exact expected)
    python scripts/trace_diff.py run --backend-a host --backend-b native

    # host decisions vs the fully-jitted episode replay (x64, 1e-9 rtol)
    python scripts/trace_diff.py run --backend-b jitted

    # any registry/spec-file scenario instead of the canonical setup
    python scripts/trace_diff.py run --scenario failures
    python scripts/trace_diff.py run --scenario my_spec.json

    # diff two previously saved traces (e.g. from --save-a/--save-b)
    python scripts/trace_diff.py files a.jsonl b.jsonl

Backends: ``host`` (pure-Python lookahead), ``native`` (C++ engine)
— ``conformance.HOST_BACKENDS`` — and ``jitted`` (the whole-episode
kernel ``sim/jax_env.py:make_episode_fn`` replaying the host action
sequence; compared at decision level — `action_decided` events only,
mask context dropped since the replay kernel sees no observation).

The episode/diff machinery lives in ``ddls_tpu/scenarios/conformance.py``
(this script is a thin wrapper over the conformance harness; the full
multi-leg run is ``scripts/conformance.py``).

The comparison excludes detail kinds (per-op/flow completions exist only
on the host engine) and context fields (``backend``, ``seq``, ``env``)
by default — see flight.comparable_events.

Exit codes: 0 traces identical, 1 divergence found, 2 usage/error,
3 requested backend unavailable.
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

# sim-only workload: pinned to the CPU whatever the caller exported
os.environ["JAX_PLATFORMS"] = "cpu"

from ddls_tpu.scenarios.conformance import HOST_BACKENDS  # noqa: E402


def _report(div, label_a: str, label_b: str, n_a: int, n_b: int) -> int:
    from ddls_tpu.telemetry import flight

    print(f"compared {n_a} ({label_a}) vs {n_b} ({label_b}) events")
    print(flight.format_divergence(div, label_a=label_a, label_b=label_b))
    return 0 if div is None else 1


def cmd_run(args) -> int:
    from ddls_tpu.scenarios import get_spec
    from ddls_tpu.scenarios.conformance import (build_env, decision_events,
                                                jitted_decision_events,
                                                run_recorded_episode)
    from ddls_tpu.telemetry import flight

    for b in (args.backend_a, args.backend_b):
        if b == "native":
            from ddls_tpu.native import native_available

            if not native_available():
                print("error: C++ lookahead engine unavailable "
                      "(ddls_tpu/native did not build/load)",
                      file=sys.stderr)
                return 3
    if args.backend_b == "jitted" and args.backend_a != "host":
        print("error: jitted decision diffs compare against the host "
              "backend (--backend-a host)", file=sys.stderr)
        return 2

    try:
        spec = get_spec(args.scenario)
    except Exception as exc:
        print(f"error: {exc}", file=sys.stderr)
        return 2

    env_a = build_env(spec, args.backend_a, dataset_dir=args.dataset,
                      sim_seconds=args.sim_seconds)
    events_a, actions = run_recorded_episode(
        env_a, args.seed, max_decisions=args.max_decisions,
        detail=args.detail)
    print(f"scenario {spec.name}: backend A ({args.backend_a}): "
          f"{len(events_a)} events over {len(actions)} decisions")
    if args.save_a:
        flight.save_jsonl(args.save_a, events_a)

    if args.backend_b == "jitted":
        a = decision_events(events_a)
        b = jitted_decision_events(env_a, events_a, actions)
        rtol = args.rtol if args.rtol is not None else 1e-9
    else:
        env_b = build_env(spec, args.backend_b, dataset_dir=args.dataset,
                          sim_seconds=args.sim_seconds)
        events_b, _ = run_recorded_episode(
            env_b, args.seed, actions=actions,
            max_decisions=args.max_decisions, detail=args.detail)
        print(f"backend B ({args.backend_b}): {len(events_b)} events")
        if args.save_b:
            flight.save_jsonl(args.save_b, events_b)
        a = flight.comparable_events(events_a,
                                     include_detail=args.include_detail)
        b = flight.comparable_events(events_b,
                                     include_detail=args.include_detail)
        rtol = args.rtol if args.rtol is not None else 0.0

    div = flight.first_divergence(a, b, rtol=rtol)
    return _report(div, args.backend_a, args.backend_b, len(a), len(b))


def cmd_files(args) -> int:
    from ddls_tpu.telemetry import flight

    for path in (args.trace_a, args.trace_b):
        if not os.path.exists(path):
            print(f"error: no such file: {path}", file=sys.stderr)
            return 2
    kinds = args.kinds or None
    a = flight.comparable_events(flight.load_jsonl(args.trace_a),
                                 kinds=kinds,
                                 include_detail=args.include_detail)
    b = flight.comparable_events(flight.load_jsonl(args.trace_b),
                                 kinds=kinds,
                                 include_detail=args.include_detail)
    div = flight.first_divergence(a, b, rtol=args.rtol or 0.0)
    return _report(div, os.path.basename(args.trace_a),
                   os.path.basename(args.trace_b), len(a), len(b))


def main(argv=None) -> int:
    parser = argparse.ArgumentParser(
        description="diff simulator flight traces across backends")
    sub = parser.add_subparsers(dest="cmd", required=True)

    run = sub.add_parser("run", help="run one scenario through two "
                                     "backends and diff the traces")
    run.add_argument("--backend-a", default="host", choices=HOST_BACKENDS)
    run.add_argument("--backend-b", default="native",
                     choices=HOST_BACKENDS + ("jitted",))
    run.add_argument("--scenario", default="canonical",
                     help="scenario registry name or spec-JSON path "
                          "(ddls_tpu/scenarios; default: canonical)")
    run.add_argument("--seed", type=int, default=0)
    run.add_argument("--dataset", default=None,
                     help="graph-file dir (default: the spec's "
                          "deterministic synthetic set)")
    run.add_argument("--sim-seconds", type=float, default=None,
                     help="simulated episode horizon (default: the "
                          "spec's own, canonical 2e4)")
    run.add_argument("--max-decisions", type=int, default=500)
    run.add_argument("--detail", action="store_true",
                     help="record per-op/flow lookahead detail events")
    run.add_argument("--include-detail", action="store_true",
                     help="ALSO diff detail kinds (host-engine only — "
                          "diverges by construction across backends)")
    run.add_argument("--rtol", type=float, default=None,
                     help="float tolerance (default 0 = bit-exact; "
                          "jitted mode defaults to 1e-9)")
    run.add_argument("--save-a", default=None, help="save trace A JSONL")
    run.add_argument("--save-b", default=None, help="save trace B JSONL")
    run.set_defaults(fn=cmd_run)

    files = sub.add_parser("files", help="diff two saved trace files")
    files.add_argument("trace_a")
    files.add_argument("trace_b")
    files.add_argument("--include-detail", action="store_true")
    files.add_argument("--rtol", type=float, default=0.0)
    files.add_argument("--kinds", nargs="*", default=None,
                       help="restrict the diff to these event kinds")
    files.set_defaults(fn=cmd_files)

    args = parser.parse_args(argv)
    try:
        import jax

        jax.config.update("jax_platforms", "cpu")
    except Exception:
        pass
    return args.fn(args)


if __name__ == "__main__":
    sys.exit(main())
