"""Backend-conformance harness: drive ONE ScenarioSpec through every
simulator backend and assert the pinned parity contracts.

Legs (per spec):

- ``host_native``: seeded host episode vs the C++ lookahead engine
  replaying the same actions — flight traces BIT-exact (rtol 0).
- ``host_jitted``: host decisions vs the fully-jitted episode kernel
  (``sim/jax_env.py make_episode_fn``, which runs the in-kernel
  lookahead) replaying the host action sequence — decision-level diff
  at 1e-9 (x64): the harness's jax backend. Excluded (with reason) off
  the dense single-channel complete topology, where the jitted backend
  does not exist.
- ``golden``: the spec's fabric reproduces the hand-computed golden
  stats (tests/test_stats_parity.py) EXACTLY on a single-op job.
- ``lint``: the lint engine's backend-surface-parity rule is clean —
  cause tables, episode fields, memo surface and the failure-event
  vocabulary all in sync.

``scripts/conformance.py --json`` is the CLI; ``scripts/trace_diff.py``
wraps the same episode machinery (run_recorded_episode /
decision_events / jitted_decision_events live HERE) for two-backend
interactive diffing.
"""
from __future__ import annotations

import os
from typing import List, Optional, Sequence

from ddls_tpu.scenarios.spec import (ScenarioSpec, build_runtime,
                                     env_kwargs, spec_fingerprint)

#: the lookahead engines a host env can be built with
HOST_BACKENDS = ("host", "native")
DEFAULT_LEGS = ("host_native", "host_jitted", "golden", "lint")


def build_env(spec: ScenarioSpec, backend: str = "host",
              dataset_dir: Optional[str] = None,
              sim_seconds: Optional[float] = None):
    """A RampJobPartitioningEnvironment for the spec with the requested
    lookahead backend and the spec's ScenarioRuntime attached (None when
    the spec is nominal)."""
    from ddls_tpu.envs import RampJobPartitioningEnvironment
    from ddls_tpu.hardware.topologies import build_topology

    if backend not in HOST_BACKENDS:
        raise ValueError(f"backend must be one of {HOST_BACKENDS}")
    runtime = build_runtime(spec, build_topology(spec.topology))
    return RampJobPartitioningEnvironment(
        **env_kwargs(spec, dataset_dir=dataset_dir,
                     sim_seconds=sim_seconds),
        use_native_lookahead=(backend == "native"),
        scenario_runtime=runtime)


def run_recorded_episode(env, seed: int, actions=None,
                         max_decisions: int = 500, detail: bool = False):
    """One seeded episode under a fresh flight recorder; returns
    (events, actions_taken). With ``actions`` given, replays that
    sequence (truncating when the episode ends early or a replayed
    action goes mask-invalid — both only happen past a divergence, which
    the diff will already have found)."""
    import numpy as np

    from ddls_tpu.telemetry import flight

    prev = (flight.recorder().enabled, flight.recorder().detail)
    flight.reset()
    flight.enable(detail=detail)
    try:
        obs = env.reset(seed=seed)
        rng = np.random.RandomState(seed)
        taken = []
        done = False
        while not done and len(taken) < max_decisions:
            if actions is not None:
                if len(taken) >= len(actions):
                    break
                action = int(actions[len(taken)])
            else:
                valid = np.flatnonzero(np.asarray(obs["action_mask"]))
                action = int(rng.choice(valid))
            try:
                obs, _, done, _ = env.step(action)
            except ValueError:
                break  # replayed action invalid here: post-divergence
            taken.append(action)
        events = flight.drain()
    finally:
        flight.reset()
        flight.recorder().enabled, flight.recorder().detail = prev
    return events, taken


def decision_events(events):
    """The decision-level view of a host trace: `action_decided` events
    with the observation-mask context dropped (the jitted replay kernel
    sees no observation, so the mask is host-only context here) and the
    blocked cause CANONICALISED through the trace-code maps — several
    host sub-action causes collapse onto one code (e.g. 'op_partition'
    -> op_placement), and the jitted side can only ever name the
    canonical string."""
    from ddls_tpu.sim.jax_env import CAUSE_CODE_TO_STR, CAUSE_STR_TO_CODE
    from ddls_tpu.telemetry import flight

    out = []
    for e in flight.comparable_events(events, kinds=("action_decided",)):
        e = {k: v for k, v in e.items() if k != "mask"}
        code = CAUSE_STR_TO_CODE.get(e.get("cause"))
        if code is not None:
            e["cause"] = CAUSE_CODE_TO_STR[code]
        out.append(e)
    return out


def jitted_decision_events(env, host_events, actions):
    """Replay the host action sequence through the fully-jitted episode
    kernel and express its per-decision trace as `action_decided`
    events (the job bank is rebuilt from the host trace's own
    job_arrived events)."""
    import jax.numpy as jnp
    import numpy as np

    from ddls_tpu.sim.jax_env import (CAUSE_CODE_TO_STR,
                                      build_episode_tables,
                                      build_job_bank, make_episode_fn)

    arrivals = [{"model": e["model"],
                 "num_training_steps": e["num_training_steps"],
                 "sla_frac": e["sla_frac"],
                 "time_arrived": e["t"]}
                for e in host_events if e["kind"] == "job_arrived"]
    et = build_episode_tables(env)
    bank = build_job_bank(et, arrivals)
    out = make_episode_fn(et)(
        {k: jnp.asarray(v) for k, v in bank.items()},
        jnp.asarray(actions, jnp.int32))
    reward, accept, cause, jct, t, has_job = (np.asarray(x)
                                              for x in out["trace"])
    events = []
    for i, action in enumerate(actions):
        if not has_job[i]:
            break  # kernel ran out of queued jobs (post-divergence)
        accepted = bool(accept[i])
        events.append({
            "kind": "action_decided", "t": float(t[i]), "job_idx": i,
            "degree": int(action), "accepted": accepted,
            "cause": CAUSE_CODE_TO_STR[int(cause[i])],
            "jct": float(jct[i]) if accepted else 0.0})
    return events


# ----------------------------------------------------------------- legs
def _x64_enabled() -> bool:
    import jax

    return bool(jax.config.jax_enable_x64)


def _jitted_supported(spec: ScenarioSpec):
    from ddls_tpu.hardware.topologies import build_topology

    dense = build_topology(spec.topology).dense_tables()
    if dense["pair_channel"] is None:
        return False, ("jitted episode backend exists only on the dense "
                       "single-channel complete topology")
    return True, None


def golden_stats_leg(spec: ScenarioSpec) -> dict:
    """The spec's fabric must reproduce the hand-computed golden stats
    (tests/test_stats_parity.py) EXACTLY: one single-op job (fwd=2,
    bwd=4, activation=100, parameter=10) x 5 steps on one worker. The
    scenario runtime is deliberately NOT attached — this leg pins the
    FABRIC; the inflation no-op is pinned by the full tier-1 suite
    running with scenario_runtime=None everywhere."""
    import tempfile

    from ddls_tpu.agents import (FirstFitDepPlacer, RampFirstFitOpPlacer,
                                 SRPTDepScheduler, SRPTOpScheduler)
    from ddls_tpu.agents.partitioners import build_partition_action
    from ddls_tpu.sim import Action, OpPartition, RampClusterEnvironment

    with tempfile.TemporaryDirectory() as td:
        with open(os.path.join(td, "tiny.txt"), "w") as fh:
            fh.write("node1 -- Linear(id=1) -- forward_compute_time=2.0, "
                     "backward_compute_time=4.0, activation_size=100.0, "
                     "parameter_size=10.0\n")
        cluster = RampClusterEnvironment(topology_config=spec.topology,
                                         node_config=spec.node_config)
        cluster.reset({
            "path_to_files": td,
            "job_interarrival_time_dist": {
                "_target_": "ddls_tpu.demands.distributions.Fixed",
                "val": 1e6},
            "max_acceptable_job_completion_time_frac_dist": {
                "_target_": "ddls_tpu.demands.distributions.Fixed",
                "val": 1.0},
            "replication_factor": 1,
            "num_training_steps": 5,
            "job_sampling_mode": "remove",
        }, max_simulation_run_time=None, seed=0)

        action_map = {}
        for job_id, job in cluster.job_queue.jobs.items():
            action_map[job_id] = build_partition_action(
                job.graph, min_op_run_time_quantum=0.01,
                max_partitions_per_op=1)
        op_partition = OpPartition(action_map, cluster=cluster)
        op_placement = RampFirstFitOpPlacer().get(op_partition, cluster)
        op_schedule = SRPTOpScheduler().get(op_partition, op_placement,
                                            cluster)
        dep_placement = FirstFitDepPlacer().get(op_partition, op_placement,
                                                cluster)
        dep_schedule = SRPTDepScheduler().get(op_partition, dep_placement,
                                              cluster)
        cluster.step(Action(op_partition=op_partition,
                            op_placement=op_placement,
                            op_schedule=op_schedule,
                            dep_placement=dep_placement,
                            dep_schedule=dep_schedule))

        e = cluster.episode_stats
        n_workers = len(cluster.topology.worker_to_server)
        expect = {
            "num_jobs_completed": 1,
            "job_completion_time": [30.0],
            "jobs_completed_total_operation_memory_cost": [220.0],
            "jobs_completed_total_dependency_size": [110.0],
            "jobs_completed_mean_mounted_worker_utilisation_frac": [1.0],
            "episode_time": 30.0,
            "cluster_info_processed": 330.0,
            "demand_total_info_processed": 320.0,
            "mean_cluster_worker_utilisation_frac": 1.0 / n_workers,
        }
        mismatches = {k: {"got": e[k], "want": v}
                      for k, v in expect.items() if e[k] != v}
    leg = {"leg": "golden", "status": "ok" if not mismatches
           else "divergence"}
    if mismatches:
        leg["mismatches"] = mismatches
    return leg


def lint_leg() -> dict:
    """The lint engine's backend-surface-parity rule over the live tree:
    cause tables bijective, episode fields in sync, memo surface intact,
    failure-event codes present in every backend vocabulary."""
    from ddls_tpu.lint.engine import run_lint
    from ddls_tpu.lint.rules.backend_parity import BackendSurfaceParityRule

    repo_root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))
    res = run_lint(roots=(), repo_root=repo_root,
                   rules=[BackendSurfaceParityRule()])
    bad = [f for f in res.findings
           if not getattr(f, "suppressed", False)]
    leg = {"leg": "lint", "status": "ok" if not bad else "divergence"}
    if bad:
        leg["findings"] = [f"{f.rel}:{f.line}: {f.message}" for f in bad]
    return leg


def run_conformance(spec: ScenarioSpec, seed: int = 0,
                    max_decisions: int = 500,
                    sim_seconds: Optional[float] = None,
                    legs: Optional[Sequence[str]] = None) -> dict:
    """Run the requested conformance legs for one spec; returns a
    JSON-able report. ``ok`` is True iff NO leg diverged or errored
    (skipped/unavailable legs are reported but do not fail)."""
    from ddls_tpu.telemetry import flight

    legs = tuple(legs) if legs else DEFAULT_LEGS
    unknown = sorted(set(legs) - set(DEFAULT_LEGS))
    if unknown:
        raise ValueError(f"unknown conformance legs {unknown} "
                         f"(choose from {DEFAULT_LEGS})")
    report: dict = {
        "spec": {"name": spec.name,
                 "fingerprint": spec_fingerprint(spec)},
        "seed": seed,
        "legs": [],
    }

    host_events = actions = host_env = None
    if any(l in legs for l in ("host_native", "host_jitted")):
        host_env = build_env(spec, "host", sim_seconds=sim_seconds)
        host_events, actions = run_recorded_episode(
            host_env, seed, max_decisions=max_decisions)

    def trace_leg(name: str, backend: str, rtol: float) -> dict:
        env_b = build_env(spec, backend, sim_seconds=sim_seconds)
        events_b, _ = run_recorded_episode(env_b, seed, actions=actions,
                                           max_decisions=max_decisions)
        a = flight.comparable_events(host_events)
        b = flight.comparable_events(events_b)
        div = flight.first_divergence(a, b, rtol=rtol)
        leg = {"leg": name, "status": "ok" if div is None
               else "divergence", "rtol": rtol,
               "events_a": len(a), "events_b": len(b),
               "decisions": len(actions)}
        if div is not None:
            leg["divergence"] = flight.format_divergence(
                div, label_a="host", label_b=backend)
        return leg

    for leg_name in legs:
        if leg_name == "host_native":
            from ddls_tpu.native import native_available

            if not native_available():
                report["legs"].append({
                    "leg": leg_name, "status": "unavailable",
                    "reason": "C++ lookahead engine did not build/load"})
            else:
                report["legs"].append(
                    trace_leg(leg_name, "native", rtol=0.0))
        elif leg_name == "host_jitted":
            supported, reason = _jitted_supported(spec)
            if not supported:
                report["legs"].append({"leg": leg_name,
                                       "status": "skipped",
                                       "reason": reason})
            elif not _x64_enabled():
                report["legs"].append({
                    "leg": leg_name, "status": "skipped",
                    "reason": "jitted decision parity is pinned at 1e-9 "
                              "under x64 only — set JAX_ENABLE_X64=1"})
            else:
                a = decision_events(host_events)
                b = jitted_decision_events(host_env, host_events,
                                           actions)
                div = flight.first_divergence(a, b, rtol=1e-9)
                leg = {"leg": leg_name,
                       "status": "ok" if div is None else "divergence",
                       "rtol": 1e-9, "events_a": len(a),
                       "events_b": len(b), "decisions": len(actions)}
                if div is not None:
                    leg["divergence"] = flight.format_divergence(
                        div, label_a="host", label_b="jitted")
                report["legs"].append(leg)
        elif leg_name == "golden":
            report["legs"].append(golden_stats_leg(spec))
        elif leg_name == "lint":
            report["legs"].append(lint_leg())

    report["ok"] = all(l["status"] in ("ok", "skipped", "unavailable")
                       for l in report["legs"])
    return report
