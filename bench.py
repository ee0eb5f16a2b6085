"""Headline benchmark: PAC-ML PPO training throughput (env-steps/sec).

Runs the full PPO loop — vectorised env rollouts with batched on-device
action sampling + the jitted, mesh-sharded PPO update — on the reference's
canonical experimental setup (BASELINE.md: RAMP 4x4x2 = 32 servers, A100
workers, 150-node obs padding, max_partitions_per_op 16, tuned GNN dims) and
prints ONE JSON line.

The reference repo publishes no benchmark numbers (BASELINE.json
"published": {}), so ``vs_baseline`` is measured against a documented
estimate of the reference pipeline's throughput: RLlib PPO with 8 rollout
workers, where each worker's env.step + per-sample DGL graph construction +
torch CPU policy inference sustains ~30 env-steps/s (SURVEY.md §3.1 marks the
per-sample DGL build a known perf sink), i.e. ~240 env-steps/s for the
8-worker reference setup. The full derivation and its estimate-not-
measurement status live in BASELINE.md ("The reference-throughput
denominator"); the JSON line also carries two fully-measured companions so
no claim rests on the estimate alone: ``sim_env_steps_per_sec`` (pure
simulator, same run) and ``loop_efficiency`` (= ppo/sim — the fraction of
its own simulator's throughput the training loop retains; no reference
estimate involved). The accelerator-side north star is the single-dispatch
jitted-episode decision throughput (``--mode jaxenv``).

Backend policy: the accelerator modes (ppo, jaxenv, serve) use the
backend JAX gives them, FAIL when that backend is the CPU unless the
caller set ``JAX_PLATFORMS=cpu`` on purpose, and never switch platform
after a failure; the host-only modes pin themselves to the CPU. Every
result line names ``platform``, ``device_kind`` and ``device_count``.
"""
from __future__ import annotations

import argparse
import hashlib
import json
import os
import subprocess
import sys
import tempfile
import time
import traceback

import numpy as np

from ddls_tpu import telemetry

REFERENCE_ENV_STEPS_PER_SEC = 240.0  # documented estimate, see module docstring
BASELINE_SOURCE = "estimate"  # reference publishes no numbers (BASELINE.json)

# dense peak FLOPs/s per chip by device kind, bf16 convention (the MXU's
# native matmul precision; MFU reported against it is the standard yardstick).
# Sources: public TPU spec sheets. CPU has no meaningful peak -> MFU null.
PEAK_FLOPS_BY_DEVICE_KIND = {
    "TPU v4": 275e12,
    "TPU v5 lite": 197e12,   # v5e
    "TPU v5e": 197e12,
    "TPU v5p": 459e12,
    "TPU v6 lite": 918e12,   # v6e / Trillium
    "TPU v6e": 918e12,
}


def peak_flops(device) -> float | None:
    """Peak FLOPs/s of ``device`` from the table above; None on the CPU
    (no meaningful peak). An accelerator the table does not know is an
    error, not an ``mfu: null``."""
    if device.platform == "cpu":
        return None
    try:
        return PEAK_FLOPS_BY_DEVICE_KIND[device.device_kind]
    except KeyError:
        raise KeyError(
            f"device_kind {device.device_kind!r} is not in "
            "PEAK_FLOPS_BY_DEVICE_KIND; add its published peak (with "
            "the source) before reporting MFU on it") from None


def _cost_flops(cost) -> float | None:
    if isinstance(cost, list):  # one dict per device program
        cost = cost[0] if cost else None
    if not cost:
        return None
    flops = float(cost.get("flops", 0.0))
    return flops if flops > 0 else None


def update_cost_analysis(jitted, *args, n_dev: int) -> float | None:
    """GLOBAL FLOPs of one update step from XLA's cost analysis of the
    COMPILED program — the one path that answers on every backend under
    the installed jax (the lowered, uncompiled analysis returns None on
    the TPU). The compiled analysis reports the PER-DEVICE partitioned
    program's FLOPs, so the result is scaled by ``n_dev``; that uniform
    scaling assumes the pure data-parallel mesh this bench builds
    (make_mesh dp-only) — a model-parallel update would need another
    global-FLOPs reconstruction. Call it after the update is warm: the
    compile is then served by the persistent compilation cache. None
    when XLA reports no FLOPs for the program."""
    flops = _cost_flops(jitted.lower(*args).compile().cost_analysis())
    return flops * n_dev if flops is not None else None


# opt-in run ledger (telemetry/runlog.py, ISSUE 18): set by main() when
# --run-dir is given; emit() mirrors every payload into result.json so
# the run directory is self-contained even on error/timeout emit paths
_RUN_LEDGER = None


def emit(payload: dict) -> None:
    """The driver parses exactly one JSON line from stdout."""
    print(json.dumps(payload), flush=True)
    if _RUN_LEDGER is not None:
        try:
            _RUN_LEDGER.record_result(payload)
        except Exception:
            pass  # the ledger must never break the JSON line contract
    # mirror the final registry state to the JSONL sink (no-op without
    # one) so --telemetry-jsonl files are self-contained even on the
    # error/timeout emit paths; serve mode's private server registry
    # rides along under the same "serve" key the JSON line uses
    tele = payload.get("telemetry") or {}
    telemetry.dump_snapshot(
        extra={"serve": tele["serve"]} if "serve" in tele else None)


def _dataset_pad_bounds(dataset_dir: str) -> dict:
    """Tight obs padding for the bench dataset: max op/dep counts over its
    graph files. Pad-to-dataset-bound is the reference's own observation
    policy (its 150-node pad IS the small_graphs dataset's bound,
    ddls/environments/ramp_job_partitioning/observations/...observation.py);
    padding a small dataset to 150/512 instead just drags dead masked rows
    through every GNN forward AND backward of the update (~10x dead rows at
    this dataset's 30-op bound), without changing a single output bit —
    padded rows are fully masked (docs/perf_round5.md)."""
    import glob

    from ddls_tpu.graphs.readers import read_graph_file

    paths = sorted(glob.glob(os.path.join(dataset_dir, "*.txt")))
    if not paths:
        # max_nodes=0 would read as "padding disabled" downstream and break
        # obs stacking with a far-away shape error; fail at the source
        raise FileNotFoundError(f"no *.txt graph files in {dataset_dir}")
    # cache key carries a cheap content fingerprint (file count + names +
    # mtimes), not the path alone: a dataset regenerated in-process at the
    # same path with different graph sizes must not serve stale bounds
    # (ADVICE r5 item 4 — the failure would surface as a far-away obs
    # stacking shape error, or silent over/under-padding)
    key = (dataset_dir, len(paths),
           tuple((os.path.basename(p), os.stat(p).st_mtime_ns)
                 for p in paths))
    if key in _PAD_BOUNDS_CACHE:
        return _PAD_BOUNDS_CACHE[key]
    max_ops = max_deps = 0
    for path in paths:
        g = read_graph_file(path)
        max_ops = max(max_ops, g.n_ops)
        max_deps = max(max_deps, g.n_deps)
    bounds = {"max_nodes": max_ops, "max_edges": max_deps}
    _PAD_BOUNDS_CACHE[key] = bounds
    return bounds


_PAD_BOUNDS_CACHE: dict = {}


def make_env_kwargs(dataset_dir: str,
                    pad_bounds: dict | None = None,
                    max_degree: int | None = None) -> dict:
    """Reference-scale env config (BASELINE.md env_dev.yaml analogue).

    ``max_degree`` overrides the canonical max_partitions_per_op=16
    (the --ab-degree A/B regime, docs/perf_round8.md: the jitted env
    pays the FULL padded placement/pricing/lookahead per decision with
    no memo cache, and the pad tables grow superlinearly in the degree
    cap — at 16 the canonical pads are 480 ops x 13072 deps and one
    in-kernel decision costs ~107 ms on a scalar CPU core, drowning any
    loop-structure difference; at 2 they are 60 x 178 and the fused-vs-
    pipelined comparison measures the LOOPS)."""
    if pad_bounds is None:
        pad_bounds = _dataset_pad_bounds(dataset_dir)
    kwargs = dict(
        topology_config={"type": "ramp", "kwargs": {
            "num_communication_groups": 4,
            "num_racks_per_communication_group": 4,
            "num_servers_per_rack": 2,
            "num_channels": 1,
            "total_node_bandwidth": 1.6e12,
            "intra_gpu_propagation_latency": 50e-9,
            "worker_io_latency": 100e-9}},
        node_config={"type_1": {"num_nodes": 32, "workers_config": [
            {"num_workers": 1, "worker": "A100"}]}},
        jobs_config={
            "path_to_files": dataset_dir,
            "job_interarrival_time_dist": {
                "_target_": "ddls_tpu.demands.distributions.Fixed",
                "val": 1000.0},
            "max_acceptable_job_completion_time_frac_dist": {
                "_target_": "ddls_tpu.demands.distributions.Uniform",
                "min_val": 0.1, "max_val": 1.0, "decimals": 2},
            "replication_factor": 100,
            "job_sampling_mode": "remove_and_repeat",
            "num_training_steps": 50},
        max_partitions_per_op=16,
        min_op_run_time_quantum=0.01,
        reward_function="job_acceptance",
        reward_function_kwargs={"fail_reward": -1, "success_reward": 1},
        max_simulation_run_time=1e6,
        # pad to the dataset bound (see _dataset_pad_bounds): same policy
        # as the reference's 150-node pad for ITS dataset, zero dead rows
        pad_obs_kwargs=dict(pad_bounds))
    if max_degree:
        kwargs["max_partitions_per_op"] = int(max_degree)
    return kwargs


def make_env_fn(dataset_dir: str):
    from ddls_tpu.envs import RampJobPartitioningEnvironment

    kwargs = make_env_kwargs(dataset_dir)

    def fn():
        return RampJobPartitioningEnvironment(**kwargs)

    return fn


def _available_cores() -> int:
    from ddls_tpu.utils.common import available_cores

    return available_cores()


def _make_vec_env(dataset_dir: str, num_envs: int, backend: str = "pipe",
                  max_degree: int | None = None):
    """Subprocess workers when there are cores for them, else in-process.
    ``backend`` selects the subprocess obs transport (rl/rollout.py):
    sim mode stays on ``pipe`` so the loop_efficiency denominator keeps
    the seed's exact cost profile; the ppo loop takes --vec-backend
    (default auto = shm where usable)."""
    from ddls_tpu.envs import RampJobPartitioningEnvironment
    from ddls_tpu.rl.rollout import ParallelVectorEnv, VectorEnv

    kwargs = make_env_kwargs(dataset_dir, max_degree=max_degree)
    seeds = list(range(num_envs))
    if _available_cores() > 1:
        return ParallelVectorEnv(RampJobPartitioningEnvironment, kwargs,
                                 num_envs, seeds=seeds, backend=backend)
    return VectorEnv([lambda: RampJobPartitioningEnvironment(**kwargs)
                      for _ in range(num_envs)], seeds=seeds)


# the bench workload's graph-set knobs: shared by _make_dataset and the
# sim record's scenario fingerprint so the two can never drift
_SIM_DATASET_KNOBS = {"n_cnn": 3, "n_translation": 2, "seed": 0,
                      "min_ops": 8, "max_ops": 16}


def _make_dataset() -> str:
    from ddls_tpu.graphs.synthetic import generate_pipedream_txt_files

    dataset_dir = tempfile.mkdtemp(prefix="bench_small_graphs_")
    generate_pipedream_txt_files(dataset_dir, **_SIM_DATASET_KNOBS)
    return dataset_dir


def _sim_scenario_block(kwargs: dict) -> dict:
    """The sim workload expressed as a fingerprinted ScenarioSpec
    (ddls_tpu/scenarios), so BENCH_* artifacts name the workload they
    measured: the fingerprint re-keys on ANY workload knob change
    (--ab-degree included) while the default bench setup itself stays
    the canonical reference-scale one (this block only reports)."""
    from ddls_tpu.scenarios import ScenarioSpec, spec_fingerprint

    jc = kwargs["jobs_config"]
    spec = ScenarioSpec(
        name="bench_canonical",
        topology=kwargs["topology_config"],
        node_config=kwargs["node_config"],
        jobs=dict(_SIM_DATASET_KNOBS),
        arrival={"kind": "fixed",
                 "interarrival": jc["job_interarrival_time_dist"]["val"]},
        sla={"kind": "uniform", "min": 0.1, "max": 1.0, "decimals": 2},
        replication_factor=jc["replication_factor"],
        num_training_steps=jc["num_training_steps"],
        job_sampling_mode=jc["job_sampling_mode"],
        max_partitions_per_op=kwargs["max_partitions_per_op"],
        min_op_run_time_quantum=kwargs["min_op_run_time_quantum"],
        sim_seconds=kwargs["max_simulation_run_time"],
        pad_obs=dict(kwargs["pad_obs_kwargs"]))
    return {"name": spec.name, "fingerprint": spec_fingerprint(spec)}


def run_sim_bench(args) -> dict:
    """Pure simulator throughput: vectorised env stepping with random valid
    actions, no learner in the loop. Isolates the host hot path
    (reference hot loop: ramp_job_partitioning_environment.py:300)."""
    dataset_dir = _make_dataset()
    vec = _make_vec_env(dataset_dir, args.num_envs,
                        max_degree=args.ab_degree)
    vec.reset()
    rng = np.random.RandomState(0)

    def random_actions():
        acts = np.zeros(vec.num_envs, dtype=np.int32)
        for i, o in enumerate(vec.obs):
            valid = np.nonzero(np.asarray(o["action_mask"]))[0]
            acts[i] = rng.choice(valid)
        return acts

    telemetry.enable()  # idempotent; main() resets + enables per run
    warmup = max(1, args.rollout_length // 2)
    with telemetry.span("bench.warmup"):
        for _ in range(warmup):
            vec.step(random_actions())
    n = 0
    with telemetry.span("bench.run") as run_span:
        while run_span.elapsed() < args.sim_seconds:
            vec.step(random_actions())
            n += vec.num_envs
    vec.close()
    value = n / run_span.duration_s
    return {
        "metric": "sim_env_steps_per_sec",
        "value": round(value, 2),
        "unit": "env_steps/s",
        # the 240/s estimate covers the reference's FULL ppo rollout loop
        # (env.step + DGL build + torch inference); sim mode measures
        # env.step only, so the ratio is not comparable — omit it
        "vs_baseline": None,
        "baseline_source": BASELINE_SOURCE,
        "num_envs": args.num_envs,
        "cores": _available_cores(),
        # which workload this number is about (fingerprinted spec)
        "scenario": _sim_scenario_block(
            make_env_kwargs(dataset_dir, max_degree=args.ab_degree)),
        # warmup/run wall split + the simulator's own cache counters
        # (lookahead/partition memo hit rates) from the same snapshot
        "telemetry": telemetry.snapshot(),
    }


def run_collect_bench(args) -> dict:
    """Interleaved same-process pipe-vs-shm A/B of the rollout-collection
    obs transport (ISSUE 5; the --loop-mode both discipline: S/P rounds
    alternate in ONE process so box-load drift can't masquerade as a
    backend effect, shm timed first = drift-conservative for its claim).

    Drives exactly the collect tax and nothing else: per step, stacked
    [B, ...] batch assembly + the [T, B, ...] trajectory materialisation
    (the pipe path pays pickle + stack + traj copy; the shm path's
    worker writes land straight in the [T+1, B, ...] slab), with
    deterministic first-valid actions so both backends step IDENTICAL
    env trajectories. No learner in the loop — the sampling cost is the
    same either way and would only dilute the measured difference.

    ``collect_bytes_per_step`` sums the rollout.obs.bytes_* telemetry
    counters (parent-side materialisations of obs bytes) over each
    backend's timed rounds — fully measured, no estimate.

    Padding: defaults to the REFERENCE 150-node obs pad (the canonical
    experimental setup the headline bench names; --collect-pad-nodes /
    --collect-pad-edges override). The transport tax scales with padded
    obs bytes, so the dataset-tight pads the ppo loop runs under
    (docs/perf_round2.md) shrink it to the noise floor of env stepping
    on a slow box — the A/B measures the regime the tax was indicted
    in (BENCH_r05 and arXiv 2012.04210 both describe full-pad
    transfers)."""
    from ddls_tpu.envs import RampJobPartitioningEnvironment
    from ddls_tpu.rl.rollout import OBS_KEYS, ParallelVectorEnv
    from ddls_tpu.rl.shm import shm_available

    dataset_dir = _make_dataset()
    kwargs = make_env_kwargs(dataset_dir)
    if args.collect_pad_nodes:
        kwargs["pad_obs_kwargs"] = {"max_nodes": args.collect_pad_nodes,
                                    "max_edges": args.collect_pad_edges}
    if args.collect_topology == "light":
        # transport-isolating env: an 8-server topology with a short
        # lookahead horizon makes sim stepping cheap, so the obs
        # transport term is a measurable fraction of the step wall
        # instead of ~3% noise under the canonical 32-server sim (the
        # obs SIZE — what transport cost scales with — is set by the
        # pad above, not the topology). Both backends still step
        # identical trajectories, so any paired difference is transport.
        kwargs["topology_config"]["kwargs"].update(
            num_communication_groups=2,
            num_racks_per_communication_group=2,
            num_servers_per_rack=2)
        kwargs["node_config"] = {"type_1": {
            "num_nodes": 8,
            "workers_config": [{"num_workers": 1, "worker": "A100"}]}}
        kwargs["jobs_config"]["num_training_steps"] = 2
        kwargs["max_simulation_run_time"] = 5e4
    T = args.rollout_length
    B = args.num_envs
    backends = ["pipe"] + (["shm"] if shm_available() else [])
    vecs = {}
    for backend in backends:
        vecs[backend] = ParallelVectorEnv(
            RampJobPartitioningEnvironment, kwargs, B,
            seeds=list(range(B)), backend=backend)
        vecs[backend].reset()
    # the shm env can silently fall back to pipe at reset (slab
    # allocation failure — e.g. /dev/shm too small for this pad); a
    # pipe-vs-pipe A/B must never be published under an "shm" label
    if "shm" in vecs and vecs["shm"].backend != "shm":
        vecs.pop("shm").close()
        backends.remove("shm")
    # pipe runs its BEST configuration (the round-6 out-of-order
    # prefetch assembly) so the A/B measures shm against the strongest
    # incumbent, not a strawman
    vecs["pipe"].prefetch_stacked = True

    telemetry.enable()
    trajs = {backend: None for backend in backends}

    def collect_segment(backend):
        """One [T, B] segment on ``backend``, the deferred-fetch
        collector's obs schedule minus the learner — including the shm
        side's one BULK copy of the slab rows into a fresh buffer at
        segment end (the collector's aliasing-safe staging, rollout.py
        _collect_deferred): T per-step copies on pipe vs one memcpy on
        shm, both counted in bytes_traj_copy."""
        vec = vecs[backend]
        ensure = getattr(vec, "ensure_traj_rows", None)
        use_slab = bool(ensure is not None and ensure(T + 1))
        if use_slab:
            vec.rebase_row0()
        traj = trajs[backend]
        for t in range(T):
            batched = vec.stacked_obs()
            # deterministic first-valid action (index 0 = do-not-place is
            # always valid): identical trajectories on both backends
            actions = np.asarray(batched["action_mask"]).argmax(axis=1)
            if not use_slab:
                if traj is None:
                    traj = trajs[backend] = {
                        k: np.empty((T,) + np.asarray(batched[k]).shape,
                                    np.asarray(batched[k]).dtype)
                        for k in OBS_KEYS}
                for k in OBS_KEYS:
                    traj[k][t] = batched[k]
                telemetry.inc("rollout.obs.bytes_traj_copy",
                              sum(np.asarray(batched[k]).nbytes
                                  for k in OBS_KEYS))
            vec.step(actions.astype(np.int32))
        if use_slab:
            staged = {k: np.array(v)
                      for k, v in vec.traj_obs_views(T).items()}
            telemetry.inc("rollout.obs.bytes_traj_copy",
                          sum(v.nbytes for v in staged.values()))
        return T * B

    def rollout_byte_counters() -> int:
        counters = telemetry.snapshot().get("counters") or {}
        return sum(int(v) for k, v in counters.items()
                   if k.startswith("rollout.obs.bytes_"))

    # warmup: past the memo-cache transient, both backends equally
    with telemetry.span("bench.warmup"):
        for _ in range(args.collect_warmup_segments):
            for backend in backends:
                collect_segment(backend)

    acc = {backend: {"steps": 0, "wall": 0.0, "bytes": 0, "segments": 0,
                     "rates": []} for backend in backends}
    # paired rounds, alternating lead: both backends step IDENTICAL
    # trajectories (same seeds, deterministic actions), so within a
    # round they do the same sim work adjacent in time — the per-round
    # rate ratio isolates the transport term from the box's drift
    # (invisible throttling swings absolute rates severalfold between
    # minutes; a totals ratio aliases that drift, the
    # MEDIAN of paired ratios does not)
    for r in range(args.collect_rounds):
        order = backends if r % 2 else list(reversed(backends))
        for backend in order:
            a = acc[backend]
            bytes_mark = rollout_byte_counters()
            with telemetry.span(f"bench.run_{backend}") as seg_span:
                n = collect_segment(backend)
            a["steps"] += n
            a["wall"] += seg_span.duration_s
            a["bytes"] += rollout_byte_counters() - bytes_mark
            a["segments"] += 1
            a["rates"].append(n / seg_span.duration_s)
    for vec in vecs.values():
        vec.close()

    results = {}
    for backend in backends:
        a = acc[backend]
        rates = np.asarray(a["rates"])
        results[backend] = {
            "env_steps_per_sec": round(a["steps"] / a["wall"], 2),
            "per_round_env_steps_per_sec": [round(float(x), 2)
                                            for x in rates],
            "median_round_env_steps_per_sec": round(
                float(np.median(rates)), 2),
            "collect_bytes_per_step": round(a["bytes"] / a["steps"], 1),
            "timed_segments": a["segments"],
        }
    headline = "shm" if "shm" in results else "pipe"
    payload = {
        "metric": "collect_env_steps_per_sec",
        "value": results[headline]["median_round_env_steps_per_sec"],
        "unit": "env_steps/s",
        "vs_baseline": None,
        "baseline_source": BASELINE_SOURCE,
        "vec_backend": headline,
        "topology": args.collect_topology,
        "vec_backends": results,
        "collect_bytes_per_step": results[headline][
            "collect_bytes_per_step"],
        "num_envs": B,
        "rollout_length": T,
        "cores": _available_cores(),
        "telemetry": telemetry.snapshot(),
    }
    if "shm" in results and "pipe" in results:
        paired = [s / p for s, p in zip(acc["shm"]["rates"],
                                        acc["pipe"]["rates"])]
        payload["paired_round_speedups"] = [round(x, 3) for x in paired]
        # the headline comparison: median over paired rounds (see above)
        payload["shm_speedup_vs_pipe"] = round(
            float(np.median(paired)), 3)
        payload["pipe_bytes_per_step_vs_shm"] = round(
            results["pipe"]["collect_bytes_per_step"]
            / max(results["shm"]["collect_bytes_per_step"], 1.0), 2)
    else:
        payload["platform_note"] = ("POSIX shared memory unavailable; "
                                    "pipe backend only")
    return payload


#: bench model for the impala depth A/B: small enough that a CPU update
#: completes in ~tens of ms (the A/B measures the LOOP schedule, not the
#: GNN), same shape vocabulary as the training configs
_IMPALA_BENCH_MODEL = {
    "fcnet_hiddens": [64],
    "custom_model_config": {"out_features_msg": 8,
                            "out_features_hidden": 16,
                            "out_features_node": 8,
                            "out_features_graph": 8},
}


def _impala_bench_env_kwargs(args, dataset_dir: str) -> dict:
    """The depth A/B env: same transport-isolating shape as collect mode
    (light topology + the reference 150-node pad by default) so the
    loop-schedule and transport terms are a measurable fraction of the
    epoch wall instead of canonical-sim noise."""
    kwargs = make_env_kwargs(dataset_dir)
    if args.collect_pad_nodes:
        kwargs["pad_obs_kwargs"] = {"max_nodes": args.collect_pad_nodes,
                                    "max_edges": args.collect_pad_edges}
    if args.impala_topology == "light":
        kwargs["topology_config"]["kwargs"].update(
            num_communication_groups=2,
            num_racks_per_communication_group=2,
            num_servers_per_rack=2)
        kwargs["node_config"] = {"type_1": {
            "num_nodes": 8,
            "workers_config": [{"num_workers": 1, "worker": "A100"}]}}
        kwargs["jobs_config"]["num_training_steps"] = 2
        kwargs["max_simulation_run_time"] = 5e4
    return kwargs


def run_impala_depth_bench(args) -> dict:
    """Interleaved same-process depth A/B of the IMPALA pipelined loop
    (ISSUE 15): one epoch loop per pipeline depth — 0, 1, and
    ``--pipeline-depth`` (K) — stepping identically-configured envs on
    the same seeds, timed in paired rounds with the lead rotating, the
    headline taken from the depth-K loop's median round rate and the
    comparison from the MEDIAN of paired per-round ratios (the
    collect/fused drift-control protocol). Depth 1 runs the LEGACY
    single-slab transport (``ring_segments=0`` — today's path, bulk
    defensive copy included) so ``depth_speedup_vs_depth1`` is
    ring-vs-incumbent, not ring-vs-ring; depths 0 and K ride the
    trajectory ring.

    Round walls are self-contained: each timed round ends only after
    the loop's dispatched updates AND its in-flight background
    collections settle, so a deeper queue can neither bleed CPU into a
    neighbour's round nor bank untimed work for its own next one —
    prefetched batches consumed at a round's start were paid for at the
    previous round's end, cancelling in the median over rounds.

    The `ring` block (segments/leases/stalls/mean params-age) is
    fetched ONCE from the depth-K loop at this reporting boundary —
    host ints off the ledger, never a device fetch (the PR 9 memo-block
    discipline)."""
    import jax

    from ddls_tpu.rl.shm import shm_available
    from ddls_tpu.train import make_epoch_loop

    dataset_dir = _make_dataset()
    env_kwargs = _impala_bench_env_kwargs(args, dataset_dir)
    B = args.num_envs
    T = args.rollout_length
    K = max(int(args.pipeline_depth), 2)
    depths = [0, 1, K]
    # the A/B is about the ring transport: subprocess workers + shm are
    # forced wherever POSIX shm exists, even on a 1-core box (the arms
    # timeshare identically, so the paired ratios stay fair); without
    # shm every depth falls back to in-process envs and the comparison
    # degrades to pure loop scheduling (flagged in the JSON line)
    use_parallel = shm_available() or _available_cores() > 1

    def make_loop(depth):
        loop = make_epoch_loop(
            "impala",
            path_to_env_cls="ddls_tpu.envs.partitioning_env."
                            "RampJobPartitioningEnvironment",
            env_config=env_kwargs,
            model=_IMPALA_BENCH_MODEL,
            algo_config={"train_batch_size": B * T, "num_workers": B},
            num_envs=B, rollout_length=T,
            n_devices=len(jax.devices()),
            use_parallel_envs=use_parallel,
            vec_env_backend=args.vec_backend,
            evaluation_interval=None, seed=0, loop_mode="pipelined",
            pipeline_depth=depth,
            metrics_sync_interval=1_000_000)
        if depth == 1:
            # today's depth-1 incumbent: single slab + bulk copy
            loop.collector.ring_segments = 0
        return loop

    loops = {d: make_loop(d) for d in depths}

    def settle(loop):
        """End-of-round sync: dispatched updates complete and the
        background queue drains, so the round wall owns ALL the work
        it scheduled (see docstring)."""
        jax.block_until_ready(loop.state.params)
        for future, _ in loop._collect_futures:
            future.result()

    telemetry.enable()
    warm = max(args.warmup_epochs, K + 2)  # per-segment alias probes
    with telemetry.span("bench.warmup"):
        for loop in loops.values():
            for _ in range(warm):
                loop.run()
            settle(loop)

    rounds = args.collect_rounds
    k_epochs = max(args.timed_epochs, 2)
    acc = {d: {"steps": 0, "wall": 0.0, "rates": []} for d in depths}
    bench_start = time.perf_counter()
    completed_rounds = 0
    for r in range(rounds):
        if time.perf_counter() - bench_start > 0.8 * args.budget_seconds:
            break  # a JSON line must land inside the driver's budget
        order = depths if r % 2 else list(reversed(depths))
        for d in order:
            loop = loops[d]
            steps = 0
            with telemetry.span(f"bench.run_depth{d}") as span:
                for _ in range(k_epochs):
                    steps += loop.run()["env_steps_this_iter"]
                settle(loop)
            a = acc[d]
            a["steps"] += steps
            a["wall"] += span.duration_s
            a["rates"].append(steps / span.duration_s)
        completed_rounds += 1
    if not completed_rounds:
        raise RuntimeError(
            f"no timed rounds completed (collect_rounds={rounds}, "
            f"budget_seconds={args.budget_seconds}) — nothing to report")

    ring_stats = loops[K].ring_stats()
    depth_results = {}
    for d in depths:
        a = acc[d]
        rates = np.asarray(a["rates"])
        depth_results[str(d)] = {
            "env_steps_per_sec": round(a["steps"] / a["wall"], 2),
            "median_round_env_steps_per_sec": round(
                float(np.median(rates)), 2),
            "per_round_env_steps_per_sec": [round(float(x), 2)
                                            for x in rates],
            "transport": ("single-slab (pre-ring incumbent)" if d == 1
                          else "trajectory-ring"),
            "ring": loops[d].ring_stats(),
        }
    for loop in loops.values():
        loop.close()

    paired_k1 = [a / b for a, b in zip(acc[K]["rates"], acc[1]["rates"])]
    paired_10 = [a / b for a, b in zip(acc[1]["rates"], acc[0]["rates"])]
    return {
        "metric": "impala_env_steps_per_sec",
        "value": depth_results[str(K)]["median_round_env_steps_per_sec"],
        "unit": "env_steps/s",
        "vs_baseline": None,
        "baseline_source": BASELINE_SOURCE,
        "platform": jax.devices()[0].platform,
        "pipeline_depth": K,
        "depths": depth_results,
        # the ISSUE 15 acceptance statistic: median of paired per-round
        # depth-K-on-ring vs depth-1-incumbent rate ratios
        "depth_speedup_vs_depth1": round(float(np.median(paired_k1)), 3),
        "paired_round_speedups_vs_depth1": [round(x, 3)
                                            for x in paired_k1],
        "depth1_speedup_vs_depth0": round(float(np.median(paired_10)), 3),
        "ring": ({"segments": ring_stats["segments"],
                  "leases": ring_stats["leases"],
                  "stalls": ring_stats["stalls"],
                  "mean_params_age": ring_stats["mean_params_age"],
                  "occupancy_counts": ring_stats["occupancy_counts"]}
                 if ring_stats is not None else None),
        "topology": args.impala_topology,
        "vec_env_backend": getattr(loops[K].vec_env, "backend", "inproc"),
        "num_envs": B,
        "rollout_length": T,
        # rounds that actually RAN (the budget guard may cut the
        # configured --collect-rounds short)
        "timed_rounds": completed_rounds,
        "timed_rounds_requested": rounds,
        "epochs_per_round": k_epochs,
        "cores": _available_cores(),
        "telemetry": telemetry.snapshot(),
    }


def run_fragments_bench(args) -> dict:
    """Same-box two-process fragments A/B (ISSUE 20,
    docs/perf_round14.md): the IMPALA pipelined loop collecting over the
    socket fragment transport (``collect_transport="socket"`` — one
    spawned actor-host process running the deferred-fetch shm collector,
    publishing ring segments as framed messages, rl/fragments.py)
    versus the in-process shm-ring incumbent, identically configured and
    timed in paired interleaved rounds with the lead rotating (the
    collect/impala drift-control protocol; headline = socket arm's
    median round rate, comparison = MEDIAN of paired per-round ratios).

    On one box the two arms timeshare the same cores, so the ratio is
    the PROTOCOL OVERHEAD plus whatever real two-process overlap the
    scheduler finds — the multi-host win case is extrapolated from
    ``collect_bytes_per_step`` (frame counters: params down + segment
    up per collect), not from this same-box rate ratio (BASELINE.md
    "fragments").

    The ``fragments`` block (per-actor-host segments/acks/transit,
    bytes per step) is fetched ONCE from the socket loop's collector at
    this reporting boundary — host ints off LearnerFragment's counters,
    never a device fetch; ring blocks likewise ride ``ring_stats()``."""
    import jax

    from ddls_tpu.rl.shm import shm_available
    from ddls_tpu.train import make_epoch_loop

    dataset_dir = _make_dataset()
    env_kwargs = _impala_bench_env_kwargs(args, dataset_dir)
    B = args.num_envs
    T = args.rollout_length
    depth = max(int(args.fragments_depth), 0)
    arms = ["inprocess", "socket"]
    # same forcing rationale as the impala A/B: the comparison is about
    # the TRANSPORT, so subprocess env workers + shm engage wherever
    # POSIX shm exists (the actor host runs the identical vec-env
    # config on its side of the socket)
    use_parallel = shm_available() or _available_cores() > 1

    def make_loop(transport):
        kwargs = dict(
            path_to_env_cls="ddls_tpu.envs.partitioning_env."
                            "RampJobPartitioningEnvironment",
            env_config=env_kwargs,
            model=_IMPALA_BENCH_MODEL,
            algo_config={"train_batch_size": B * T, "num_workers": B},
            num_envs=B, rollout_length=T,
            n_devices=len(jax.devices()),
            use_parallel_envs=use_parallel,
            vec_env_backend=args.vec_backend,
            evaluation_interval=None, seed=0, loop_mode="pipelined",
            pipeline_depth=depth,
            metrics_sync_interval=1_000_000)
        if transport == "socket":
            kwargs.update(collect_transport="socket",
                          socket_config={"transport": "unix"})
        return make_epoch_loop("impala", **kwargs)

    loops = {a: make_loop(a) for a in arms}

    def settle(loop):
        jax.block_until_ready(loop.state.params)
        for future, _ in loop._collect_futures:
            future.result()

    telemetry.enable()
    warm = max(args.warmup_epochs, depth + 2)  # alias probes + queues
    with telemetry.span("bench.warmup"):
        for loop in loops.values():
            for _ in range(warm):
                loop.run()
            settle(loop)

    rounds = args.collect_rounds
    k_epochs = max(args.timed_epochs, 2)
    acc = {a: {"steps": 0, "wall": 0.0, "rates": []} for a in arms}
    bench_start = time.perf_counter()
    completed_rounds = 0
    for r in range(rounds):
        if time.perf_counter() - bench_start > 0.8 * args.budget_seconds:
            break  # a JSON line must land inside the driver's budget
        order = arms if r % 2 else list(reversed(arms))
        for a in order:
            loop = loops[a]
            steps = 0
            with telemetry.span(f"bench.run_{a}") as span:
                for _ in range(k_epochs):
                    steps += loop.run()["env_steps_this_iter"]
                settle(loop)
            st = acc[a]
            st["steps"] += steps
            st["wall"] += span.duration_s
            st["rates"].append(steps / span.duration_s)
        completed_rounds += 1
    if not completed_rounds:
        raise RuntimeError(
            f"no timed rounds completed (collect_rounds={rounds}, "
            f"budget_seconds={args.budget_seconds}) — nothing to report")

    # reporting boundary: one counter fetch per arm, then teardown
    frag_stats = loops["socket"].collector.stats()
    transports = {}
    for a in arms:
        st = acc[a]
        rates = np.asarray(st["rates"])
        transports[a] = {
            "env_steps_per_sec": round(st["steps"] / st["wall"], 2),
            "median_round_env_steps_per_sec": round(
                float(np.median(rates)), 2),
            "per_round_env_steps_per_sec": [round(float(x), 2)
                                            for x in rates],
            "ring": loops[a].ring_stats(),
        }
    for loop in loops.values():
        loop.close()

    paired = [s / i for s, i in zip(acc["socket"]["rates"],
                                    acc["inprocess"]["rates"])]
    cbps = frag_stats.get("collect_bytes_per_step")
    return {
        "metric": "fragments_env_steps_per_sec",
        "value": transports["socket"]["median_round_env_steps_per_sec"],
        "unit": "env_steps/s",
        "vs_baseline": None,
        "baseline_source": BASELINE_SOURCE,
        "platform": jax.devices()[0].platform,
        "pipeline_depth": depth,
        "transports": transports,
        # the ISSUE 20 acceptance statistic: median of paired per-round
        # socket-vs-inprocess rate ratios (same-box overhead+overlap)
        "socket_ratio_vs_inprocess": round(float(np.median(paired)), 3),
        "paired_round_ratios": [round(x, 3) for x in paired],
        # the wire cost the multi-host extrapolation rides on
        "collect_bytes_per_step": (round(cbps, 1)
                                   if cbps is not None else None),
        "fragments": frag_stats,
        "topology": args.impala_topology,
        "num_envs": B,
        "rollout_length": T,
        "timed_rounds": completed_rounds,
        "timed_rounds_requested": rounds,
        "epochs_per_round": k_epochs,
        "cores": _available_cores(),
        "telemetry": telemetry.snapshot(),
    }


def run_partition_bench(args) -> dict:
    """Param-partition layout A/B (ISSUE 19, docs/perf_round13.md): one
    jitted PPO update per named layout of the partition-rule table
    (``parallel/partition.py`` replicated / fsdp / tp), driven by the
    SAME synthetic [T, B] trajectory tiled from one real canonical
    observation — the update cost is model+shape bound, so the obs
    content is irrelevant and the env stays out of the loop.

    Measures the two things the layouts differ in: per-device peak live
    state bytes (``live_bytes_per_device`` — aval metadata only, exact
    on virtual CPU meshes where allocator telemetry is not) and learner
    update throughput as env-steps/s consumed (batch env-steps per
    blocked update wall). Timed in interleaved rounds with the lead
    rotating (the collect-mode drift protocol); the per-round
    fsdp/replicated and tp/replicated rate ratios ride the payload as
    paired medians. On one socket of virtual CPU devices the sharded
    matmuls and their collectives timeshare the same cores, so the
    throughput ratios here are an overhead FLOOR — the ICI win needs
    real multi-chip silicon (ROADMAP item 1); the bytes ratios are
    exact everywhere. ``--model-scale wide`` is the over-budget config
    tests/test_partition.py pins (replicated > 2 MiB/device, fsdp
    under it); the headline value is fsdp's median round rate at the
    chosen scale."""
    import jax

    from ddls_tpu.envs import RampJobPartitioningEnvironment
    from ddls_tpu.models.policy import GNNPolicy, batched_policy_apply
    from ddls_tpu.parallel import partition as pt
    from ddls_tpu.rl.ppo import PPOConfig, PPOLearner

    n_dev = len(jax.devices())
    dataset_dir = _make_dataset()
    env = RampJobPartitioningEnvironment(**make_env_kwargs(dataset_dir))
    single = jax.tree_util.tree_map(np.asarray, env.reset(seed=0))
    n_actions = int(single["action_mask"].shape[0])
    # the wide config is the tests/test_partition.py over-budget model
    # (docs/perf_round13.md table) so bench numbers and the acceptance
    # test talk about the same architecture
    scale_kwargs = {
        "canonical": {},
        "wide": dict(out_features_msg=64, out_features_hidden=128,
                     out_features_node=64, out_features_graph=64,
                     fcnet_hiddens=(512, 512)),
    }[args.model_scale]
    model = GNNPolicy(n_actions=n_actions, **scale_kwargs)
    params = model.init(jax.random.PRNGKey(0), single)

    B = max((args.num_envs // n_dev) * n_dev, n_dev)
    T = args.rollout_length
    batch = B * T
    num_sgd_iter = min(args.num_sgd_iter, 10)  # CPU-pinned mode
    cfg = PPOConfig(num_sgd_iter=num_sgd_iter,
                    sgd_minibatch_size=min(128, batch),
                    train_batch_size=batch)

    def tile(v):
        return np.ascontiguousarray(
            np.broadcast_to(v, (T, B) + v.shape))

    rng_np = np.random.RandomState(0)
    traj = {"obs": {k: tile(v) for k, v in single.items()},
            "actions": np.zeros((T, B), np.int32),  # 0 = always valid
            "logp": np.log(np.full((T, B), 0.5, np.float32)),
            "values": rng_np.randn(T, B).astype(np.float32),
            "rewards": rng_np.randn(T, B).astype(np.float32),
            "dones": rng_np.rand(T, B) < 0.1}
    last_values = rng_np.randn(B).astype(np.float32)

    layouts = ["replicated", "fsdp", "tp"]
    skipped: dict = {}
    arms: dict = {}
    for i, layout in enumerate(list(layouts)):
        try:
            mesh = pt.mesh_for_layout(n_dev, layout,
                                      args.tp_size if layout == "tp"
                                      else None)
        except ValueError as e:
            # e.g. tp on a 1-device run: record why, keep the line
            skipped[layout] = str(e)
            layouts.remove(layout)
            continue
        learner = PPOLearner(
            lambda p, o, m=model: batched_policy_apply(m, p, o),
            cfg, mesh, param_sharding=layout)
        state = learner.init_state(params)
        # staged ONCE per layout: this mode pins the CPU backend, where
        # jit donation is disabled, so the staged batch survives updates
        straj, slv = learner.shard_traj(traj, last_values)
        arms[layout] = {
            "learner": learner, "state": state,
            "straj": straj, "slv": slv,
            "rng": jax.random.PRNGKey(i),
            "mesh_shape": dict(mesh.shape),
            "state_bytes": pt.live_bytes_per_device(state),
            "params_bytes": pt.live_bytes_per_device(state.params),
        }

    telemetry.enable()
    with telemetry.span("bench.warmup"):  # one compile per layout
        for a in arms.values():
            a["rng"], sub = jax.random.split(a["rng"])
            a["state"], metrics = a["learner"].train_step(
                a["state"], a["straj"], a["slv"], sub)
            jax.block_until_ready(metrics["total_loss"])

    acc = {layout: {"steps": 0, "wall": 0.0, "rates": []}
           for layout in layouts}
    start = time.perf_counter()
    completed_rounds = 0
    for r in range(args.partition_rounds):
        if time.perf_counter() - start > 0.8 * args.budget_seconds:
            break  # the JSON line must land inside the driver budget
        order = layouts if r % 2 else list(reversed(layouts))
        for layout in order:
            a, arm = acc[layout], arms[layout]
            arm["rng"], sub = jax.random.split(arm["rng"])
            with telemetry.span(f"bench.run_{layout}") as span:
                arm["state"], metrics = arm["learner"].train_step(
                    arm["state"], arm["straj"], arm["slv"], sub)
                jax.block_until_ready(metrics["total_loss"])
            a["steps"] += batch
            a["wall"] += span.duration_s
            a["rates"].append(batch / span.duration_s)
        completed_rounds += 1
    if not completed_rounds:
        raise RuntimeError(
            f"no timed rounds completed (partition_rounds="
            f"{args.partition_rounds}, budget_seconds="
            f"{args.budget_seconds}) — nothing to report")

    results = {}
    repl_bytes = arms.get("replicated", {}).get("state_bytes")
    for layout in layouts:
        a, arm = acc[layout], arms[layout]
        rates = np.asarray(a["rates"])
        results[layout] = {
            "env_steps_per_sec": round(a["steps"] / a["wall"], 2),
            "median_round_env_steps_per_sec": round(
                float(np.median(rates)), 2),
            "per_round_env_steps_per_sec": [round(float(x), 2)
                                            for x in rates],
            "update_ms": round(a["wall"] / len(a["rates"]) * 1e3, 2),
            "state_bytes_per_device": arm["state_bytes"],
            "params_bytes_per_device": arm["params_bytes"],
            "mesh": arm["mesh_shape"],
        }
        if repl_bytes and layout != "replicated":
            results[layout]["state_bytes_vs_replicated"] = round(
                arm["state_bytes"] / repl_bytes, 4)
    headline = "fsdp" if "fsdp" in results else layouts[0]
    payload = {
        "metric": "partition_update_env_steps_per_sec",
        "value": results[headline]["median_round_env_steps_per_sec"],
        "unit": "env_steps/s",
        "vs_baseline": None,
        "baseline_source": BASELINE_SOURCE,
        "platform": jax.devices()[0].platform,
        "headline_layout": headline,
        "model_scale": args.model_scale,
        "n_devices": n_dev,
        "tp_size": args.tp_size if "tp" in results else None,
        "num_envs": B,
        "rollout_length": T,
        "num_sgd_iter": num_sgd_iter,
        "batch_env_steps": batch,
        "layouts": results,
        "layouts_skipped": skipped or None,
        "timed_rounds": completed_rounds,
        "timed_rounds_requested": args.partition_rounds,
        "virtual_devices": jax.devices()[0].platform == "cpu",
        "throughput_caveat": (
            "virtual CPU devices timeshare one socket: sharded-layout "
            "rate ratios are an overhead floor, not the ICI win"
            if jax.devices()[0].platform == "cpu" else None),
        "cores": _available_cores(),
        "telemetry": telemetry.snapshot(),
    }
    for layout in ("fsdp", "tp"):
        if layout in acc and "replicated" in acc and acc[layout]["rates"]:
            paired = [s / p for s, p in zip(acc[layout]["rates"],
                                           acc["replicated"]["rates"])]
            payload[f"{layout}_speedup_vs_replicated"] = round(
                float(np.median(paired)), 3)
    return payload


def run_jaxenv_bench(args) -> dict:
    """Fully-jitted episode throughput (sim/jax_env.py): ONE device
    dispatch runs a whole padded episode, so the per-step dispatch that
    bounds host-driven stepping disappears. Measures compile time,
    steady single-episode decisions/s, and the vmap-8 aggregate (the
    rollout-collection shape; lockstep lanes lose on CPU, ride vector
    lanes on TPU — docs/jax_env_gonogo.md)."""
    import jax
    import jax.numpy as jnp

    from ddls_tpu.envs import RampJobPartitioningEnvironment
    from ddls_tpu.sim.jax_env import (build_episode_tables, build_job_bank,
                                      make_episode_fn)

    kwargs = make_env_kwargs(_make_dataset())
    # loaded regime so the decisions bind (env_load32 analogue)
    kwargs["jobs_config"]["job_interarrival_time_dist"]["val"] = 50.0
    kwargs["jobs_config"]["num_training_steps"] = 20
    kwargs["max_simulation_run_time"] = 2e4
    kwargs["max_partitions_per_op"] = args.jaxenv_max_degree
    env = RampJobPartitioningEnvironment(**kwargs)
    env.reset(seed=0)
    et = build_episode_tables(env)
    episode_fn = make_episode_fn(et)

    rng = np.random.RandomState(0)
    J, D = 420, 400
    degrees = [d for d in (0, 1, 2, 4, 8, 16)
               if d <= args.jaxenv_max_degree]

    def mk_bank(seed):
        r = np.random.RandomState(seed)
        recs = [{"model": et.types[int(r.randint(0, len(et.types)))],
                 "num_training_steps": 20,
                 "sla_frac": round(float(r.uniform(0.1, 1.0)), 2),
                 "time_arrived": 50.0 * i} for i, _ in enumerate(range(J))]
        return {k: jnp.asarray(v)
                for k, v in build_job_bank(et, recs).items()}

    actions = jnp.asarray(rng.choice(degrees, size=D), jnp.int32)
    telemetry.enable()
    # compile vs run split as uniform spans (ISSUE 3): same names across
    # every mode, so a sink/report compares them without bespoke keys
    with telemetry.span("bench.compile") as compile_span:
        out = jax.block_until_ready(episode_fn(mk_bank(0), actions))
    with telemetry.span("bench.run") as run_span:
        out = jax.block_until_ready(episode_fn(mk_bank(1), actions))
    n_dec = int(np.asarray(out["trace"][5]).sum())
    # in-kernel lookahead memo counters of the timed episode (the
    # single-lane kernel runs the memo by default — ISSUE 13); drained
    # here with the rest of the episode outputs, never per step
    memo_h = int(np.asarray(out["memo_hits"]))
    memo_m = int(np.asarray(out["memo_misses"]))
    memo_e = int(np.asarray(out["memo_evicts"]))

    # wide memo ON for the vmapped lanes (the make_episode_fn default,
    # ISSUE 17): the batched probe masks hit lanes out of the lookahead
    # while_loop — the 8-lane aggregate now measures the memo-served
    # kernel, the same contract as the single-lane line above
    vfn = jax.jit(jax.vmap(make_episode_fn(et), in_axes=(0, 0)))
    banks = [mk_bank(s) for s in range(8)]
    bb = {k: jnp.stack([b[k] for b in banks]) for k in banks[0]}
    aa = jnp.broadcast_to(actions, (8, D))
    with telemetry.span("bench.vmap8_compile"):
        jax.block_until_ready(vfn(bb, aa))
    with telemetry.span("bench.vmap8") as vmap_span:
        vout = jax.block_until_ready(vfn(bb, aa))
    vdec = int(np.asarray(vout["trace"][5]).sum())
    # lane-summed memo counters of the timed vmap8 episode batch, from
    # the same already-fetched episode outputs (ONE reporting-boundary
    # drain, never per step/lane)
    v_h = int(np.asarray(vout["memo_hits"]).sum())
    v_m = int(np.asarray(vout["memo_misses"]).sum())
    v_e = int(np.asarray(vout["memo_evicts"]).sum())

    return {
        "metric": "jaxenv_decisions_per_sec",
        "value": round(n_dec / run_span.duration_s, 2),
        "unit": "decisions/s",
        "vs_baseline": None,
        "baseline_source": BASELINE_SOURCE,
        "platform": jax.devices()[0].platform,
        "compile_seconds": round(compile_span.duration_s, 1),
        "vmap8_decisions_per_sec": round(vdec / vmap_span.duration_s, 2),
        "max_degree": args.jaxenv_max_degree,
        "pads": {"ops": et.pads.n_ops, "deps": et.pads.n_deps},
        "memo": {"hits": memo_h, "misses": memo_m, "evicts": memo_e,
                 "hit_rate": round(memo_h / (memo_h + memo_m), 4)
                 if memo_h + memo_m else 0.0},
        "vmap8_memo": {"hits": v_h, "misses": v_m, "evicts": v_e,
                       "hit_rate": round(v_h / (v_h + v_m), 4)
                       if v_h + v_m else 0.0},
        "telemetry": telemetry.snapshot(),
    }


def _serve_obs_pool(dataset_dir: str, n_obs: int) -> list:
    """Real encoded observations for the serving bench: step one env with
    random valid actions and snapshot each decision's obs (the arriving
    population a deployed server would see)."""
    from ddls_tpu.envs import RampJobPartitioningEnvironment

    env = RampJobPartitioningEnvironment(**make_env_kwargs(dataset_dir))
    obs = env.reset(seed=0)
    rng = np.random.RandomState(0)
    pool = []
    while len(pool) < n_obs:
        pool.append({k: np.copy(v) for k, v in obs.items()})
        valid = np.flatnonzero(np.asarray(obs["action_mask"]))
        obs, _, done, _ = env.step(int(rng.choice(valid)))
        if done:
            obs = env.reset(seed=len(pool))
    return pool


def run_serve_bench(args) -> dict:
    """Online-serving throughput/latency at configurable offered load
    (ISSUE 1): Poisson arrivals drive ddls_tpu.serve.PolicyServer —
    bucketed padding, deadline microbatching, one fixed-shape jitted
    forward per bucket, FixedDegreePacking fallback under saturation. The
    real-time loop submits each request at its arrival instant and pumps
    the server, so reported latency is true wall latency (queue wait +
    batch fill + forward), not just device time.

    Measures the serving half of the stack the way ``loop_efficiency``
    measures the training half: decisions/sec against the offered load,
    with p50/p99 latency, batch occupancy, and fallback rate riding in
    the same JSON line (BASELINE.md "Serving throughput")."""
    import jax

    from ddls_tpu.models.policy import GNNPolicy
    from ddls_tpu.serve import PolicyServer, default_buckets

    dataset_dir = _make_dataset()
    bounds = _dataset_pad_bounds(dataset_dir)
    pool = _serve_obs_pool(dataset_dir, min(64, args.serve_requests))
    n_actions = int(np.asarray(pool[0]["action_mask"]).shape[0])

    pool_graph_dim = int(np.asarray(pool[0]["graph_features"]).shape[0])
    if args.serve_checkpoint:
        # checkpoint-faithful architecture: the shipped checkpoints carry
        # algo-level model overrides (fcnet_hiddens), so the model must be
        # rebuilt from the training config tree or the restore cannot load
        from ddls_tpu.serve import (build_model_from_config,
                                    checkpoint_graph_feature_dim,
                                    load_checkpoint_params)

        model, cfg_actions, graph_dim = build_model_from_config(
            args.serve_config_path, args.serve_config_name,
            args.serve_override)
        if cfg_actions != n_actions or graph_dim != pool_graph_dim:
            raise ValueError(
                f"--serve-checkpoint config expects obs widths "
                f"(actions={cfg_actions}, graph={graph_dim}) but the "
                f"bench env emits ({n_actions}, {pool_graph_dim}); pass "
                f"a matching --serve-config-name/--serve-override")
        params = load_checkpoint_params(args.serve_checkpoint)
        # the config matching the bench env does not make the CHECKPOINT
        # match: restore is target-free, so e.g. the 51-wide price-trained
        # ppo_price_mixed params would load under the 34-wide default
        # config and fail the first warmup forward with a raw XLA shape
        # error; reject the pairing here with its actual cause instead
        ckpt_dim = checkpoint_graph_feature_dim(params)
        if ckpt_dim is not None and ckpt_dim != graph_dim:
            raise ValueError(
                f"checkpoint {args.serve_checkpoint} was trained at "
                f"graph width {ckpt_dim} but the serve config builds "
                f"{graph_dim}; pass the checkpoint's training config "
                f"(--serve-config-name/--serve-override)")
        params_source = args.serve_checkpoint
    else:
        model = GNNPolicy(n_actions=n_actions)
        graph_dim = pool_graph_dim
        # random init: serving cost is architecture+shape-bound, not
        # value-bound, so the smoke number needs no trained artifact
        params = model.init(jax.random.PRNGKey(0),
                            jax.tree_util.tree_map(np.asarray, pool[0]))
        params_source = "random_init"

    buckets = default_buckets(bounds["max_nodes"], bounds["max_edges"])

    if args.replicas > 1 or args.load == "trace":
        # the fleet path (ISSUE 8): trace-driven open-loop load through
        # the Router; also serves multi-replica Poisson (the trace
        # degenerates to plain Poisson with modulation knobs zeroed)
        return _run_serve_fleet_bench(args, model, params, graph_dim,
                                      pool, buckets, params_source)

    server = PolicyServer(model, params, buckets=buckets,
                          max_batch=args.serve_max_batch,
                          deadline_s=args.serve_deadline_ms / 1e3,
                          max_queue=args.serve_max_queue,
                          graph_feature_dim=graph_dim)

    # compile every bucket before timing (each bucket compiles exactly
    # once; the compile belongs to startup, not to steady-state latency)
    _warm_server(server, pool)

    telemetry.enable()
    rng = np.random.RandomState(args.load_seed)
    n = args.serve_requests
    arrivals = np.cumsum(rng.exponential(1.0 / args.serve_rps, size=n))
    responses = []
    with telemetry.span("bench.run") as run_span:
        start = time.perf_counter()
        i = 0
        while len(responses) < n:
            now = time.perf_counter()
            while i < n and now - start >= arrivals[i]:
                # charge latency (and the deadline clock) from the ARRIVAL
                # instant, not the submit-loop instant: arrivals that land
                # while the loop is blocked in a device forward must still
                # pay that wait, or p50/p99 are biased low exactly in
                # overload (classic coordinated omission)
                server.submit(pool[i % len(pool)], now=start + arrivals[i])
                i += 1
                now = time.perf_counter()
            responses.extend(server.poll())
            if len(responses) >= n:
                break
            # sleep to the next event (arrival or batch deadline), never
            # long
            next_events = [start + arrivals[i]] if i < n else []
            deadline = server.next_deadline()
            if deadline is not None:
                next_events.append(deadline)
            if next_events:
                time.sleep(min(max(min(next_events) - time.perf_counter(),
                                   0.0), 0.005))
            elif i >= n:
                responses.extend(server.drain())
    elapsed = run_span.duration_s

    s = server.stats.summary()
    return {
        "metric": "serve_decisions_per_sec",
        "value": round(len(responses) / elapsed, 2),
        "unit": "decisions/s",
        "vs_baseline": None,
        "baseline_source": BASELINE_SOURCE,
        "platform": jax.devices()[0].platform,
        "p50_latency_ms": (round(s["p50_latency_ms"], 3)
                           if s["p50_latency_ms"] is not None else None),
        "p99_latency_ms": (round(s["p99_latency_ms"], 3)
                           if s["p99_latency_ms"] is not None else None),
        "batch_occupancy": (round(s["batch_occupancy"], 3)
                            if s["batch_occupancy"] is not None else None),
        "fallback_rate": round(s["fallback_rate"], 4),
        "bucket_hits": s["bucket_hits"],
        "n_compiles": s["n_compiles"],
        "offered_rps": args.serve_rps,
        "num_requests": n,
        "max_batch": args.serve_max_batch,
        "deadline_ms": args.serve_deadline_ms,
        "buckets": [list(b) for b in buckets],
        "params_source": params_source,
        # reproducibility triplet (ISSUE 8 satellite): every serve line
        # names its load seed, content fingerprint, and replica count
        "replicas": 1,
        "load": {"mode": "poisson", "seed": args.load_seed,
                 "fingerprint": hashlib.sha256(
                     np.round(arrivals, 9).tobytes()).hexdigest()[:16]},
        "cores": _available_cores(),
        # global spans/probe counters + the server's private registry
        # (serve.latency_s histogram etc. — same window the p50/p99
        # fields above are computed from, so the two always agree)
        "telemetry": {**telemetry.snapshot(),
                      "serve": server.stats.registry.snapshot()},
    }


def _warm_server(server, pool) -> None:
    """Per-bucket compile warmup (one obs per bucket rung, then a stats
    reset): compile belongs to startup, never to measured serving.
    Shared by the single-server path and the fleet's ``warm_replica``
    hook so the warmup discipline cannot drift between them."""
    for spec_idx in range(len(server.bucketer.buckets)):
        for o in pool:
            n = int(np.asarray(o["node_split"]).reshape(-1)[0])
            m = int(np.asarray(o["edge_split"]).reshape(-1)[0])
            if server.bucketer.bucket_index(n, m) == spec_idx:
                server.submit(o)
                server.drain()
                break
    server.stats = type(server.stats)()  # warmup never counts


def _run_serve_fleet_bench(args, model, params, graph_dim, pool, buckets,
                           params_source) -> dict:
    """Multi-replica / trace-driven serving measurement (ISSUE 8): a
    seeded, fingerprinted open-loop trace (diurnal cycle + bursts +
    heavy-tailed sizes; plain Poisson when --load poisson) drives the
    fleet Router in real time. Every request is submitted with its
    SCHEDULED arrival as ``now`` — latency is charged from when the
    request was supposed to arrive, not when the loop got to it, so the
    reported p50/p99/p999 are coordinated-omission-correct exactly in
    overload. The JSON line carries SLO attainment + goodput against
    ``--slo-ms``, shed/degraded rates, per-replica occupancy, and the
    (seed, fingerprint, resolved-replica-count) triplet that makes serve
    numbers comparable across rounds."""
    import jax

    from ddls_tpu.serve import (AutoscaleConfig, AutoscaleController,
                                Autoscaler, build_fleet)
    from ddls_tpu.serve import loadgen

    n = args.serve_requests
    expected_duration = n / args.serve_rps
    is_trace = args.load == "trace"
    trace = loadgen.generate_trace(
        n_requests=n, base_rps=args.serve_rps, seed=args.load_seed,
        # periods default to fractions of the expected duration so a
        # short bench still sweeps full diurnal/burst cycles
        diurnal_period_s=(args.trace_diurnal_period_s
                          or expected_duration / 2),
        diurnal_amplitude=(args.trace_diurnal_amplitude if is_trace
                           else 0.0),
        burst_factor=args.trace_burst_factor if is_trace else 1.0,
        burst_period_s=(args.trace_burst_period_s
                        or expected_duration / 4),
        burst_duty=args.trace_burst_duty,
        size_tail_alpha=args.trace_size_alpha,
        n_tenants=args.trace_tenants)
    loadgen.validate_trace(trace)
    fingerprint = loadgen.trace_fingerprint(trace)

    def warm_replica(server):
        # the Router runs this for the initial fleet AND every autoscale
        # scale-up, so a mid-run replica addition never serves its first
        # batches cold
        _warm_server(server, pool)

    router = build_fleet(
        model, params, n_replicas=args.replicas,
        routing=args.serve_routing, shed_enabled=True,
        quota_rps=args.serve_quota_rps or None,
        warm_replica=warm_replica,
        buckets=buckets, max_batch=args.serve_max_batch,
        deadline_s=args.serve_deadline_ms / 1e3,
        max_queue=args.serve_max_queue, graph_feature_dim=graph_dim)

    if is_trace:
        # heavy-tailed size ranks map onto the obs pool sorted by true
        # graph size: rank 0 -> smallest arriving graph, rank ~1 ->
        # largest
        by_size = sorted(
            pool, key=lambda o: (int(np.asarray(o["node_split"])[0]),
                                 int(np.asarray(o["edge_split"])[0])))
        sized = [by_size[min(int(f * len(by_size)), len(by_size) - 1)]
                 for f in trace["size_frac"]]
    else:
        # poisson mode cycles the pool uniformly, exactly like the
        # single-server path — a --replicas 1 vs N comparison must
        # serve the SAME job-size mix (the trace's size_frac is unused)
        sized = [pool[i % len(pool)] for i in range(n)]
    router.reset_stats()

    controller = None
    if args.serve_autoscale:
        controller = AutoscaleController(router, Autoscaler(
            AutoscaleConfig(min_replicas=1,
                            max_replicas=args.serve_autoscale_max,
                            target_p99_ms=args.slo_ms)))

    telemetry.enable()
    arrivals = np.asarray(trace["arrival_s"])
    tenants = trace["tenant"]
    responses = []
    last_scale_t = 0.0
    with telemetry.span("bench.run") as run_span:
        start = time.perf_counter()
        i = 0
        while len(responses) < n:
            now = time.perf_counter()
            while i < n and now - start >= arrivals[i]:
                # scheduled-arrival timestamp, never the loop instant
                # (coordinated omission — see run_serve_bench); sheds
                # resolve inside submit and surface on the next poll
                router.submit(sized[i], now=start + arrivals[i],
                              tenant=tenants[i] if is_trace else None)
                i += 1
                now = time.perf_counter()
            responses.extend(router.poll())
            if len(responses) >= n:
                break
            if (controller is not None
                    and now - start - last_scale_t
                    >= args.serve_autoscale_interval_s):
                controller.step(now=now)
                last_scale_t = now - start
            next_events = [start + arrivals[i]] if i < n else []
            deadline = router.next_deadline()
            if deadline is not None:
                next_events.append(deadline)
            if next_events:
                time.sleep(min(max(min(next_events) - time.perf_counter(),
                                   0.0), 0.005))
            elif i >= n:
                responses.extend(router.drain())
    elapsed = run_span.duration_s

    slo = loadgen.slo_summary(responses, slo_s=args.slo_ms / 1e3,
                              duration_s=elapsed)
    per_replica = router.per_replica_summary()
    snapshots = router.registry_snapshots()
    payload = {
        "metric": "serve_decisions_per_sec",
        "value": round(slo["n_decided"] / elapsed, 2),
        "unit": "decisions/s",
        "vs_baseline": None,
        "baseline_source": BASELINE_SOURCE,
        "platform": jax.devices()[0].platform,
        "p50_latency_ms": (round(slo["p50_latency_ms"], 3)
                           if slo["p50_latency_ms"] is not None else None),
        "p99_latency_ms": (round(slo["p99_latency_ms"], 3)
                           if slo["p99_latency_ms"] is not None else None),
        "p999_latency_ms": (round(slo["p999_latency_ms"], 3)
                            if slo["p999_latency_ms"] is not None
                            else None),
        "slo_ms": args.slo_ms,
        "slo_attainment": round(slo["slo_attainment"], 4),
        "goodput_rps": round(slo["goodput_rps"], 2),
        "shed_rate": round(slo["shed_rate"], 4),
        "degraded_rate": round(slo["degraded_rate"], 4),
        "offered_rps": args.serve_rps,
        "num_requests": n,
        "max_batch": args.serve_max_batch,
        "deadline_ms": args.serve_deadline_ms,
        "buckets": [list(b) for b in buckets],
        "params_source": params_source,
        "routing": args.serve_routing,
        # the reproducibility triplet + per-replica occupancy the
        # acceptance names
        "replicas": len(router.replica_set.replicas),
        "replicas_requested": args.replicas,
        "per_replica": {
            rid: {"n_requests": s["n_requests"],
                  "batch_occupancy": (round(s["batch_occupancy"], 3)
                                      if s["batch_occupancy"] is not None
                                      else None),
                  "p99_latency_ms": (round(s["p99_latency_ms"], 3)
                                     if s["p99_latency_ms"] is not None
                                     else None),
                  "fallback_rate": round(s["fallback_rate"], 4)}
            for rid, s in per_replica.items()},
        "load": {"mode": args.load, "seed": args.load_seed,
                 "fingerprint": fingerprint,
                 "base_rps": args.serve_rps,
                 # burst/diurnal modulation lifts the true offered rate
                 # above base_rps (~1.4x at the defaults); record it so
                 # utilization reads straight off the artifact
                 "effective_rps": round(n / float(arrivals[-1]), 2),
                 **{k: trace["meta"][k]
                    for k in ("diurnal_period_s", "diurnal_amplitude",
                              "burst_factor", "burst_period_s",
                              "burst_duty", "size_tail_alpha",
                              "n_tenants")}},
        "cores": _available_cores(),
        "telemetry": {**telemetry.snapshot(), "serve": snapshots},
    }
    if controller is not None:
        payload["autoscale"] = {
            "max_replicas": args.serve_autoscale_max,
            "decisions": [{"target": d["target"], "reason": d["reason"],
                           "resolved": d["resolved"]}
                          for d in controller.decisions],
        }
    return payload


def _shape_structs(tree):
    """ShapeDtypeStruct skeleton of a pytree — what the cost-analysis
    ``lower()`` calls need. Captured instead of live arrays because the
    learner donates the staged batch on accelerator backends (its
    buffers are deleted the moment the update consumes them)."""
    import jax

    return jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=getattr(x, "sharding", None)),
        tree)


def run_bench(args, process_start: float) -> dict:
    import threading

    import jax

    if jax.devices()[0].platform == "cpu":
        # CPU (explicit, fallback, or accelerator-less host) is a smoke
        # measurement, not the headline: the scanned SGD update alone takes
        # minutes at full size on one host core, so shrink to something
        # that completes. Warmup matters: env stepping is ~5x slower for the
        # first ~300 steps of an env's life (memo caches filling, cluster
        # state maturing — docs/perf_round5.md), so the timed epochs must
        # start from steady state or they measure the transient
        args.num_envs = min(args.num_envs, 4)
        args.rollout_length = min(args.rollout_length, 32)
        args.timed_epochs = min(args.timed_epochs, 8)
        args.num_sgd_iter = min(args.num_sgd_iter, 10)
        # 10 epochs x 32 steps = 320 steps/env, past the ~300-step transient
        args.warmup_epochs = max(args.warmup_epochs, 10)

    from ddls_tpu.models.policy import GNNPolicy, batched_policy_apply
    from ddls_tpu.parallel.mesh import make_mesh
    from ddls_tpu.rl.ppo import PPOConfig, PPOLearner
    from ddls_tpu.rl.rollout import RolloutCollector

    n_dev = len(jax.devices())
    # the trajectory batch dim is sharded over the dp axis; keep num_envs a
    # multiple of the device count so shard_traj divides evenly
    if args.num_envs % n_dev != 0:
        args.num_envs = max((args.num_envs // n_dev) * n_dev, n_dev)

    dataset_dir = _make_dataset()
    vec = _make_vec_env(dataset_dir, args.num_envs,
                        backend=args.vec_backend,
                        max_degree=args.ab_degree)
    vec.reset()
    single = jax.tree_util.tree_map(np.asarray, vec.obs[0])
    # canonical 17 (degree cap 16 + do-not-place); --ab-degree shrinks it
    n_actions = int(single["action_mask"].shape[0])
    model = GNNPolicy(n_actions=n_actions)
    params = model.init(jax.random.PRNGKey(0), single)

    # the dp axis spans every device jax exposes
    mesh = make_mesh(len(jax.devices()))
    batch = args.num_envs * args.rollout_length
    cfg = PPOConfig(num_sgd_iter=args.num_sgd_iter,
                    sgd_minibatch_size=min(128, batch),
                    train_batch_size=batch)
    learner = PPOLearner(lambda p, o: batched_policy_apply(model, p, o),
                         cfg, mesh)
    state = learner.init_state(params)
    # one vec env, two loop schedules over it (the load-controlled
    # comparison the --loop-mode flag exists for): `sequential` is the
    # pre-round-6 loop — per-step host splits/fetches, a blocking wait
    # per update; `pipelined` is the restructured loop — deferred-fetch
    # collection, async update dispatch, metrics drained once per block
    collector_seq = RolloutCollector(vec, learner, args.rollout_length)
    collector_seq._needs_reset = False  # vec reset above
    collector_pipe = RolloutCollector(vec, learner, args.rollout_length,
                                      deferred_fetch=True)
    collector_pipe._needs_reset = False

    telemetry.enable(record_intervals=True)

    def one_epoch_sequential(state, rng):
        # params stay on device: sample_actions reads them in place rather
        # than re-uploading the whole tree every rollout step
        if hasattr(vec, "prefetch_stacked"):
            vec.prefetch_stacked = False  # seed-exact stepping path
        with telemetry.span("train.collect"):
            out = collector_seq.collect(state.params, rng)
            straj, slv = learner.shard_traj(out["traj"],
                                            out["last_values"])
        with telemetry.span("bench.update"):
            state, metrics = learner.train_step(state, straj, slv, rng)
            jax.block_until_ready(metrics["total_loss"])
        # the sequential loop's per-update metric fetch (RLEpochLoop
        # loop_mode="sequential" semantics: one host_sync per update)
        with telemetry.span("train.host_sync"):
            jax.device_get(metrics)
        return state, out["env_steps"], (straj, slv)

    # pipelined bookkeeping: unsynced metric futures + monitor threads
    # recording each update's true device wall (train.update_device)
    pending_metrics: list = []
    watchers: list = []

    def one_epoch_pipelined(state, rng):
        if hasattr(vec, "prefetch_stacked"):
            vec.prefetch_stacked = True
        with telemetry.span("train.collect"):
            out = collector_pipe.collect(state.params, rng)
            straj, slv = learner.shard_traj(out["traj"],
                                            out["last_values"])
        segment = out.get("ring_segment")
        if segment is not None:
            # the ring consumer token protocol lives in ONE place
            # (rl/ring.py note_staged/note_update) — bench mirrors the
            # training loop by calling it, never by re-implementing it
            out["ring"].note_staged(segment, straj["obs"],
                                    generation=out.get("ring_generation"))
        t0 = telemetry.clock_now()
        state, metrics = learner.train_step(state, straj, slv, rng)
        if segment is not None:
            out["ring"].note_update(segment, metrics["total_loss"],
                                    generation=out.get("ring_generation"))

        def watch(metrics=metrics, t0=t0):
            jax.block_until_ready(metrics)
            telemetry.record_span("train.update_device", t0)

        w = threading.Thread(target=watch, daemon=True)
        w.start()
        watchers.append(w)
        pending_metrics.append(metrics)
        return state, out["env_steps"], (straj, slv)

    def drain_pipeline(state):
        # the pipelined block's honest end: every dispatched update done,
        # the metric ring drained in ONE fetch, monitors settled
        jax.block_until_ready(state)
        with telemetry.span("train.host_sync"):
            jax.device_get(pending_metrics)
        pending_metrics.clear()
        for w in watchers:
            w.join(timeout=30)
        watchers.clear()

    epoch_fns = {"sequential": one_epoch_sequential,
                 "pipelined": one_epoch_pipelined}
    # --loop-mode both is the ROUND-8 A/B: interleaved pipelined/fused
    # rounds in one process, with the LEAD FLIPPING on every other pair
    # (see the rounds scheduler below) and the headline taken from the
    # median of paired per-round ratios — the collect-mode protocol.
    # Host-env memo warming only ever helps the PIPELINED side, which
    # additionally gets the FULL warmup budget (see warm_schedule), so
    # residual monotone drift biases AGAINST the fused claim
    modes = (["fused", "pipelined"] if args.loop_mode == "both"
             else [args.loop_mode])
    headline_mode = ("fused" if args.loop_mode == "both"
                     else args.loop_mode)

    # ---- fused mode: the one-dispatch-per-epoch jitted program
    # (rl/fused.py) over the in-kernel env, lanes/segment picked by the
    # program-size-aware autotuner (probe compile warms the training
    # executable). A fused mode that was asked for and cannot be built
    # raises — the round list never silently loses an arm.
    fused_driver = None
    fused_autotune = None
    fused_pending: list = []
    fused_rngs: list = []
    if "fused" in modes:
        from ddls_tpu.envs import RampJobPartitioningEnvironment
        from ddls_tpu.rl import fused as fused_mod
        from ddls_tpu.sim.jax_env import (build_episode_tables,
                                          build_obs_tables)

        fenv = RampJobPartitioningEnvironment(
            **make_env_kwargs(dataset_dir, max_degree=args.ab_degree))
        fenv.reset(seed=0)
        et = build_episode_tables(fenv)
        ot = build_obs_tables(fenv, et)
        # bank sized by the one sizing home (horizon + CLT margin —
        # exact here since the bench interarrival is Fixed)
        n_jobs = fused_mod.horizon_bank_jobs(fenv, seed=31)

        def build_driver(lanes, seg):
            return fused_mod.FusedEpochDriver(
                et, ot, model,
                fused_mod.stacked_job_banks(et, fenv, lanes, n_jobs),
                seg, args.fused_updates_per_epoch,
                train_step_fn=learner._train_step,
                state_shardings=learner._state_shardings(state),
                mesh=mesh)

        with telemetry.span("bench.fused_autotune"):
            fused_driver, fused_autotune = fused_mod.autotune_fused(
                build_driver, state, et,
                args.num_envs * args.rollout_length,
                args.fused_updates_per_epoch, int(mesh.shape["dp"]),
                max_lanes=args.num_envs,
                signature_extra=f"bench|{args.num_sgd_iter}",
                lanes=args.fused_lanes or None,
                segment_len=args.fused_segment_len or None)
        if fused_driver is None:
            raise RuntimeError(
                "fused autotune: no (lanes, segment_len) config "
                f"compiled — probed {fused_autotune.probed}")
        fused_rngs[:] = [jax.random.PRNGKey(2), jax.random.PRNGKey(3)]

    def one_epoch_fused(state, rng):
        del rng  # fused carries its own on-device key streams
        with telemetry.span("train.fused_epoch"):
            state, rngs, metrics, ep = fused_driver.fused_epoch(
                state, tuple(fused_rngs))
        fused_rngs[:] = rngs
        fused_pending.append((metrics, ep))
        return state, fused_driver.env_steps_per_epoch, None

    def drain_fused(state):
        # the fused block's honest end: dispatched epochs complete and
        # the pending metric/episode futures drained in ONE fetch
        jax.block_until_ready(state)
        if fused_pending:
            with telemetry.span("train.host_sync"):
                jax.device_get(fused_pending)
            fused_pending.clear()

    epoch_fns["fused"] = one_epoch_fused

    rng = jax.random.PRNGKey(1)
    update_args = None
    warmup_completed = 0
    # warmup schedule: every mode's program must compile before timing,
    # AND the host-env side must get its FULL warmup budget — the
    # ~300-step memo-cache transient lives in the HOST envs only, so
    # alternating modes would halve the host warmup and bias the A/B
    # TOWARD fused (the opposite of the conservative ordering the timed
    # rounds use). The fused program has no host transient and is
    # already compiled by the autotune probe: two epochs settle its
    # dispatch path.
    if len(modes) > 1 and "fused" in modes:
        host_mode = next(m for m in modes if m != "fused")
        warm_schedule = (["fused"] * min(2, args.warmup_epochs)
                         + [host_mode] * args.warmup_epochs)
    else:
        warm_schedule = [modes[0]] * args.warmup_epochs
    with telemetry.span("bench.warmup"):
        for i, warm_mode in enumerate(warm_schedule):
            rng, sub = jax.random.split(rng)
            # capture the update's arg shapes before dispatch (donation
            # deletes the arrays); fused epochs return None there
            fn = epoch_fns[warm_mode]
            state, _, ua = fn(state, sub)
            try:
                # shape skeletons only: the live arrays may already be
                # donated-and-deleted (shape/dtype survive deletion;
                # sharding access is the defensive part)
                update_args = (_shape_structs(ua[0]),
                               _shape_structs(ua[1]))
            except Exception:
                pass
            warmup_completed += 1
            # warmup must leave room for >=1 timed epoch + the JSON
            # emit; a short warmup only biases the smoke number slow,
            # never kills it
            if (time.perf_counter() - process_start
                    > 0.6 * args.budget_seconds):
                break
        drain_pipeline(state)
        if fused_driver is not None:
            drain_fused(state)

    # FLOPs of ONE compiled update step (cached compile: same shapes as
    # the warmed-up call). Grabbed before timing so it can't perturb the
    # clock.
    update_flops = None
    if update_args is not None:
        straj, slv = update_args
        update_flops = update_cost_analysis(
            learner._jit_train_step, _shape_structs(state), straj, slv,
            _shape_structs(rng), n_dev=n_dev)

    def _span_stats(name):
        s = telemetry.span_summaries().get(name)
        return (s["count"], s["total_s"]) if s else (0, 0.0)

    # update-span baseline: warmup epochs (incl. the compile) must not
    # contaminate the timed update_ms below
    warm_update_stats = {name: _span_stats(name)
                         for name in ("bench.update",
                                      "train.update_device")}

    # timed blocks on the same warmed envs/process, INTERLEAVED when both
    # modes run (P/S/P/S with half the epochs per round): env throughput
    # and box load drift monotonically on this class of box, so a
    # contiguous A-then-B layout aliases the drift into the comparison.
    # Per-epoch rates + loadavg land in the JSON so residual volatility
    # is diagnosable from the artifact.
    mode_results: dict = {}
    load_avg_start = os.getloadavg()[0]
    acc = {m: {"steps": 0, "wall": 0.0, "rates": [], "round_rates": [],
               "syncs": 0, "intervals": []} for m in modes}
    if len(modes) > 1:
        # MANY small alternating rounds with the lead flipping per pair
        # (collect mode's paired-round protocol): this box's invisible
        # minute-scale throttling swings absolute rates ±20%, so a
        # two-block A/B aliases the drift; adjacent paired rounds see
        # ~the same box state and their rate RATIO isolates the loop
        # difference (docs/perf_round7.md)
        pairs = 4
        k = max(1, args.timed_epochs // pairs)
        rounds = []
        for r in range(pairs):
            order = modes if r % 2 == 0 else list(reversed(modes))
            rounds.extend((m, k) for m in order)
    else:
        rounds = [(modes[0], args.timed_epochs)]
    for mode, n_epochs in rounds:
        if time.perf_counter() - process_start > args.budget_seconds:
            break  # later rounds must not run the emit past the budget
        a = acc[mode]
        interval_mark = len(telemetry.registry().span_intervals())
        sync_mark = (telemetry.span_summaries()
                     .get("train.host_sync", {}).get("count", 0))
        round_steps = 0
        with telemetry.span(f"bench.run_{mode}") as run_span:
            for i in range(n_epochs):
                rng, sub = jax.random.split(rng)
                t0 = time.perf_counter()
                state, n, _ = epoch_fns[mode](state, sub)
                a["rates"].append(n / (time.perf_counter() - t0))
                a["steps"] += n
                round_steps += n
                # a measurement must always land inside the driver's
                # budget; the clock is anchored at process start so
                # probe/setup time counts. Stop early (with >=1 timed
                # epoch recorded) rather than get killed
                if (time.perf_counter() - process_start
                        > args.budget_seconds):
                    break
            if mode == "pipelined":
                drain_pipeline(state)
            elif mode == "fused":
                drain_fused(state)
        # round-level rate: the HONEST per-round figure for every mode
        # (fused dispatch is async, so its per-epoch walls above measure
        # dispatch, not execution; the round wall ends at the drain)
        a["round_rates"].append(round_steps / run_span.duration_s)
        a["wall"] += run_span.duration_s
        a["syncs"] += (telemetry.span_summaries()
                       .get("train.host_sync", {}).get("count", 0)
                       - sync_mark)
        a["intervals"].extend(
            telemetry.registry().span_intervals()[interval_mark:])
    for mode in modes:
        a = acc[mode]
        if not a["rates"]:
            continue  # round skipped by the budget guard above
        # fused epochs dispatch asynchronously, so their per-epoch walls
        # measure dispatch only — the round-level rates (wall ends at
        # the drain) are the honest spread there
        rates = np.asarray(a["round_rates"] if mode == "fused"
                           else a["rates"])
        mode_results[mode] = {
            "env_steps_per_sec": round(a["steps"] / a["wall"], 2),
            "timed_epochs": len(a["rates"]),
            # per-epoch env_steps/s spread: host wall per epoch (the
            # pipelined rounds' final drains ride in the block total,
            # not any single epoch)
            "per_epoch_env_steps_per_sec": {
                "min": round(float(rates.min()), 2),
                "median": round(float(np.median(rates)), 2),
                "max": round(float(rates.max()), 2),
            },
            "per_round_env_steps_per_sec": [
                round(float(r), 2) for r in a["round_rates"]],
            "host_sync_spans_per_epoch": round(
                a["syncs"] / max(len(a["rates"]), 1), 3),
        }
        if mode == "pipelined":
            from ddls_tpu.telemetry import overlap_summary

            ov = overlap_summary(a["intervals"], prefix="train.")
            if ov.get("n_spans"):
                mode_results[mode]["overlap"] = {
                    "overlap_fraction": round(ov["overlap_fraction"], 4),
                    "covered_1_s": round(ov["covered_1_s"], 3),
                    "covered_2_s": round(ov["covered_2_s"], 3),
                }
        if mode == "fused" and fused_autotune is not None:
            # the ISSUE-12 artifact fields: the autotuner's chosen
            # config and its estimated vs actual program size
            mode_results[mode]["updates_per_epoch"] = \
                args.fused_updates_per_epoch
            mode_results[mode]["autotune"] = fused_autotune.as_dict()
        if mode == "fused" and fused_driver is not None:
            # ISSUE-13/17 artifact field: the in-kernel lookahead
            # memo's cumulative hit/miss/evict counts + hit rate,
            # summed over lanes — ONE fetch here at the reporting
            # boundary (counters ride the carried device state; the
            # wide probe keeps the memo ON at every lane count, so
            # multi-lane fused lines carry the block too)
            memo = fused_driver.memo_counters()
            if memo is not None:
                memo["hit_rate"] = round(memo["hit_rate"], 4)
                mode_results[mode]["memo"] = memo

    # trajectory-ring ledger (rl/ring.py): host ints, fetched ONCE here
    # at the reporting boundary (the PR 9 memo-block discipline) before
    # close() drops the ring
    traj_ring = getattr(vec, "traj_ring", None)
    ring_stats = traj_ring.stats() if traj_ring is not None else None
    vec.close()
    if headline_mode not in mode_results:
        # budget guard skipped the headline mode's rounds: report the
        # mode that did measure rather than crash past the emit
        headline_mode = next(iter(mode_results))
    payload_extra = {}
    if ("fused" in mode_results and "pipelined" in mode_results
            and acc["fused"]["round_rates"]
            and acc["pipelined"]["round_rates"]):
        # the headline A/B comparison: median of paired per-round rate
        # ratios (adjacent rounds see ~the same box state — the totals
        # ratio aliases this box's minute-scale drift, the paired
        # median does not; same protocol as collect mode)
        paired = [f / p for f, p in zip(acc["fused"]["round_rates"],
                                        acc["pipelined"]["round_rates"])]
        payload_extra = {
            "fused_paired_round_speedups": [round(x, 3) for x in paired],
            "fused_speedup_vs_pipelined": round(
                float(np.median(paired)), 3),
        }
    if args.loop_mode == "both" and len(mode_results) > 1:
        # headline = the faster measured mode, judged by the SAME
        # drift-controlled statistic the artifact reports (the paired
        # median; totals only when no paired rounds ran): fused on the
        # TPU and in the --ab-degree regime where the loops are what
        # differ, pipelined on the CPU canonical env where the
        # un-memoised in-kernel lookahead tax makes fused slower
        # (docs/perf_round8.md) — a bare run never regresses the
        # artifact trajectory to a known-slower mode, and the headline
        # can never contradict fused_speedup_vs_pipelined in the same
        # JSON line
        if "fused_speedup_vs_pipelined" in payload_extra:
            headline_mode = ("fused"
                             if payload_extra[
                                 "fused_speedup_vs_pipelined"] > 1.0
                             else "pipelined")
        else:
            headline_mode = max(mode_results,
                                key=lambda m: mode_results[m][
                                    "env_steps_per_sec"])
    headline = mode_results[headline_mode]
    value = headline["env_steps_per_sec"]
    epochs_run = headline["timed_epochs"]
    dev = jax.devices()[0]
    payload = {
        "metric": "ppo_env_steps_per_sec",
        "value": value,
        "unit": "env_steps/s",
        "vs_baseline": round(value / REFERENCE_ENV_STEPS_PER_SEC, 3),
        "baseline_source": BASELINE_SOURCE,
        "platform": dev.platform,
        "loop_mode": headline_mode,
        "loop_modes": mode_results,
        "num_envs": args.num_envs,  # after device-multiple rounding
        "rollout_length": args.rollout_length,
        "num_sgd_iter": args.num_sgd_iter,
        # 0 = canonical degree cap 16; the fused A/B regime sets 2
        "ab_degree": args.ab_degree,
        # the resolved obs transport ("inproc" = serial VectorEnv on a
        # 1-core box); sim's denominator below always measures on pipe
        "vec_env_backend": getattr(vec, "backend", "inproc"),
        "timed_epochs": epochs_run,
        # the early-break above can cut warmup short of the ~320 steps/env
        # the CPU smoke sizing targets; recording the achieved count makes
        # a transient-contaminated number distinguishable from steady
        # state (ADVICE r5 item 3)
        "warmup_epochs_completed": warmup_completed,
        "warmup_epochs_target": args.warmup_epochs,
        "cores": _available_cores(),
        # box-load volatility context for the per-epoch spread above
        # (round-5 docs claimed 284-311 steps/s where the driver saw
        # 204.46 — the artifact itself now says how loaded the box was)
        "load_avg_1m": {"start": round(load_avg_start, 2),
                        "end": round(os.getloadavg()[0], 2)},
        # per-update spans (collect rides inside the epoch wall;
        # bench.update isolates the blocking jitted update,
        # train.update_device the async one) + sim cache counters, one
        # vocabulary across modes
        "telemetry": telemetry.snapshot(),
    }
    payload.update(payload_extra)
    if ring_stats is not None:
        payload["ring"] = {
            "segments": ring_stats["segments"],
            "leases": ring_stats["leases"],
            "stalls": ring_stats["stalls"],
            "mean_params_age": ring_stats["mean_params_age"],
            "occupancy_counts": ring_stats["occupancy_counts"],
        }
    # achieved FLOPs / MFU of the jitted sharded update (VERDICT round-2
    # weakness 2: "fast" must mean something on the chip, not just vs the
    # invented 240 env-steps/s denominator). The device wall per update
    # comes from the blocking bench.update span when a sequential block
    # ran, else from the pipelined monitor span (same program, measured
    # by block_until_ready on another thread)
    update_wall, update_count = 0.0, 0
    for name in ("bench.update", "train.update_device"):
        count, total = _span_stats(name)
        warm_count, warm_total = warm_update_stats[name]
        if count - warm_count > 0:
            update_count = count - warm_count
            update_wall = total - warm_total
            break
    if update_count and update_wall > 0:
        payload["update_ms"] = round(update_wall / update_count * 1e3, 2)
        if update_flops is not None:
            achieved = update_flops * update_count / update_wall
            payload["update_flops"] = update_flops
            payload["update_gflops_per_sec"] = round(achieved / 1e9, 2)
            # update_flops is the GLOBAL computation's FLOPs, so the
            # aggregate rate is divided by the aggregate peak of every
            # chip the mesh spans
            peak = peak_flops(dev)
            # significant-digit rounding: this model's honest MFU is tiny
            # (a ~2 GFLOP GNN update on a 197 TFLOP/s chip) and fixed
            # 4-decimal rounding would report a literal 0.0
            payload["mfu"] = (float(f"{achieved / (peak * n_dev):.3g}")
                              if peak else None)
    # ride the pure-simulator figure along in the same JSON line when the
    # driver budget allows (VERDICT r2 #1: report ppo AND sim modes). The
    # rider is the real --mode sim CLI (identical env sizing to a
    # standalone run) in a subprocess with a hard timeout, AFTER the ppo
    # payload is complete — it can only ever add a field, never cost the
    # measurement its budget
    headroom = args.budget_seconds - (time.perf_counter() - process_start)
    if headroom > 60:
        try:
            out = subprocess.run(
                [sys.executable, os.path.abspath(__file__), "--mode", "sim",
                 "--sim-seconds", "10",
                 # same env parallelism as the ppo measurement (post
                 # device-multiple rounding), else loop_efficiency would
                 # compare different num_envs — and the same --ab-degree
                 # env, else the ratio would mix env regimes. The
                 # denominator itself stays on the pipe transport
                 # (loop_efficiency keeps the seed's cost profile)
                 "--num-envs", str(args.num_envs),
                 "--ab-degree", str(args.ab_degree)],
                capture_output=True, text=True,
                # this process holds the accelerator, which belongs to
                # one process: the child is pinned to the CPU by force
                # (sim mode also pins itself)
                env={**os.environ, "JAX_PLATFORMS": "cpu"},
                timeout=min(headroom - 15, 120))
            sim = json.loads(out.stdout.strip().splitlines()[-1])
            if sim.get("value") is not None:
                payload["sim_env_steps_per_sec"] = sim["value"]
                # fraction of its own simulator's throughput the full
                # training loop retains (BASELINE.md: fully measured, no
                # reference estimate in the ratio); reported per loop
                # mode so the sequential/pipelined comparison is load-
                # controlled against ONE simulator denominator
                payload["loop_efficiency"] = round(
                    value / sim["value"], 3)
                for mode, res in payload.get("loop_modes", {}).items():
                    res["loop_efficiency"] = round(
                        res["env_steps_per_sec"] / sim["value"], 3)
        except Exception:
            pass
    return payload


def _run_mode(args, runner, metric: str, unit: str,
              host_only: bool) -> int:
    """Run one mode and emit exactly one JSON line whatever happens.

    Host-only modes (no device in the loop, or an A/B whose arms differ
    only in HOST structure) pin this process to the CPU. Accelerator
    modes use the backend JAX gives them and fail on an unrequested CPU
    (``require_accelerator``); nothing here changes platform after a
    failure. Every result names the device it ran on."""
    from ddls_tpu.utils.runtime import (device_summary, pin_cpu_platform,
                                        require_accelerator)

    try:
        if host_only:
            pin_cpu_platform()
        else:
            require_accelerator(f"bench.py --mode {args.mode}")
        payload = runner(args)
        payload.update(device_summary())
        emit(payload)
        return 0
    except Exception:
        tb = traceback.format_exc().strip().splitlines()
        emit({"metric": metric, "value": None, "unit": unit,
              "vs_baseline": None, "error": " | ".join(tb[-3:])})
        return 1


def main(argv=None) -> int:
    process_start = time.perf_counter()
    parser = argparse.ArgumentParser()
    parser.add_argument("--mode",
                        choices=("ppo", "sim", "jaxenv", "serve",
                                 "collect", "impala", "partition",
                                 "fragments"),
                        default="ppo",
                        help="ppo: full train loop; sim: pure env "
                             "stepping; jaxenv: fully-jitted episodes; "
                             "serve: online policy serving at offered "
                             "load (ddls_tpu/serve); collect: "
                             "interleaved pipe-vs-shm obs-transport A/B "
                             "(rollout collection only, no learner); "
                             "impala: interleaved pipeline-depth A/B of "
                             "the IMPALA loop on the trajectory ring "
                             "(depths 0/1/--pipeline-depth, rl/ring.py); "
                             "partition: interleaved param-layout A/B "
                             "of the PPO update (replicated/fsdp/tp, "
                             "parallel/partition.py — env-steps/s + "
                             "peak live bytes per device per layout); "
                             "fragments: same-box two-process A/B of "
                             "the socket fragment transport vs the "
                             "in-process shm ring (rl/fragments.py — "
                             "env-steps/s + collect_bytes_per_step + "
                             "per-segment transit stats)")
    parser.add_argument("--model-scale", choices=("canonical", "wide"),
                        default="canonical",
                        help="partition mode's GNN config: canonical "
                             "(the checkpoint family) or wide (the "
                             "tests/test_partition.py over-budget "
                             "model — msg/node/graph 64, hidden 128, "
                             "fcnet 512x512)")
    parser.add_argument("--tp-size", type=int, default=2,
                        help="partition mode: mp-axis width of the tp "
                             "layout's (dp, mp) mesh (must divide the "
                             "device count; tp is skipped — with the "
                             "reason recorded — where it cannot)")
    parser.add_argument("--partition-rounds", type=int, default=6,
                        help="partition mode: interleaved timed rounds "
                             "(one blocked update per layout per round, "
                             "lead rotating; paired per-round ratios "
                             "give the drift-controlled comparison)")
    parser.add_argument("--pipeline-depth", type=int, default=2,
                        help="impala mode: the depth-K arm of the A/B "
                             "(>= 2; depth 1 runs the pre-ring "
                             "single-slab incumbent for comparison)")
    parser.add_argument("--fragments-depth", type=int, default=1,
                        help="fragments mode: pipeline depth of BOTH "
                             "arms (depth 1 gives each arm one "
                             "background collect overlapping the "
                             "update — the schedule where transport "
                             "latency can actually hide)")
    parser.add_argument("--impala-topology",
                        choices=("light", "canonical"), default="light",
                        help="impala/fragments mode env (same rationale "
                             "as "
                             "--collect-topology: light makes the loop "
                             "schedule a measurable fraction of the "
                             "epoch wall)")
    parser.add_argument("--vec-backend", choices=("auto", "pipe", "shm"),
                        default="auto",
                        help="ppo mode's subprocess obs transport "
                             "(rl/rollout.py; auto = shm where POSIX "
                             "shm is usable). sim mode always measures "
                             "on pipe — the loop_efficiency denominator "
                             "keeps the seed's cost profile")
    parser.add_argument("--collect-rounds", type=int, default=12,
                        help="collect mode: interleaved timed rounds "
                             "per backend (one [T, B] segment each, "
                             "lead backend alternating per round; the "
                             "headline speedup is the MEDIAN of paired "
                             "per-round ratios)")
    parser.add_argument("--collect-topology",
                        choices=("light", "canonical"), default="light",
                        help="collect mode env: light (8-server, short "
                             "horizon — cheap sim steps so the obs "
                             "transport term is measurable) or "
                             "canonical (the 32-server reference sim, "
                             "where transport is a few %% of the step "
                             "wall)")
    parser.add_argument("--collect-warmup-segments", type=int, default=10,
                        help="collect mode: warmup segments per backend "
                             "before timing (default 10 x 32 steps "
                             "clears the ~300-step memo-cache "
                             "transient)")
    parser.add_argument("--collect-pad-nodes", type=int, default=150,
                        help="collect mode obs pad (reference 150-node "
                             "canonical pad; 0 = the dataset-tight "
                             "bound the ppo loop uses)")
    parser.add_argument("--collect-pad-edges", type=int, default=512,
                        help="collect mode edge pad bound (with "
                             "--collect-pad-nodes)")
    parser.add_argument("--jaxenv-max-degree", type=int, default=8)
    parser.add_argument("--serve-requests", type=int, default=256)
    parser.add_argument("--serve-rps", type=float, default=200.0,
                        help="offered load (arrivals/sec; trace mode's "
                             "base rate before diurnal/burst "
                             "modulation)")
    parser.add_argument("--load", choices=("poisson", "trace"),
                        default="poisson",
                        help="serve mode's arrival process: poisson "
                             "(constant-rate) or trace (seeded "
                             "open-loop trace with diurnal cycle, "
                             "bursts, heavy-tailed job sizes and "
                             "tenants — ddls_tpu.serve.loadgen)")
    parser.add_argument("--replicas", type=int, default=1,
                        help="serve mode: PolicyServer replicas behind "
                             "the fleet Router (>1, or --load trace, "
                             "selects the fleet path)")
    parser.add_argument("--load-seed", type=int, default=1,
                        help="arrival-process seed; recorded with the "
                             "trace fingerprint in the JSON line")
    parser.add_argument("--slo-ms", type=float, default=50.0,
                        help="latency budget for SLO attainment / "
                             "goodput (measured from SCHEDULED "
                             "arrival — coordinated-omission-correct)")
    parser.add_argument("--serve-routing",
                        choices=("affinity", "least_loaded",
                                 "round_robin", "hash"),
                        default="affinity")
    parser.add_argument("--serve-quota-rps", type=float, default=0.0,
                        help="per-tenant token-bucket admission rate "
                             "(trace mode; 0 disables quotas)")
    parser.add_argument("--serve-autoscale", action="store_true",
                        help="run the telemetry-driven autoscaler "
                             "control loop during the serve bench")
    parser.add_argument("--serve-autoscale-max", type=int, default=4)
    parser.add_argument("--serve-autoscale-interval-s", type=float,
                        default=0.25)
    parser.add_argument("--trace-diurnal-period-s", type=float,
                        default=None,
                        help="default: half the expected trace "
                             "duration")
    parser.add_argument("--trace-diurnal-amplitude", type=float,
                        default=0.5)
    parser.add_argument("--trace-burst-factor", type=float, default=3.0)
    parser.add_argument("--trace-burst-period-s", type=float,
                        default=None,
                        help="default: a quarter of the expected trace "
                             "duration")
    parser.add_argument("--trace-burst-duty", type=float, default=0.2)
    parser.add_argument("--trace-size-alpha", type=float, default=1.5)
    parser.add_argument("--trace-tenants", type=int, default=4)
    parser.add_argument("--serve-max-batch", type=int, default=8)
    parser.add_argument("--serve-deadline-ms", type=float, default=5.0)
    parser.add_argument("--serve-max-queue", type=int, default=64)
    parser.add_argument("--serve-checkpoint", default=None,
                        help="serve a shipped checkpoint's params instead "
                             "of random init")
    parser.add_argument("--serve-config-path",
                        default=os.path.join(
                            os.path.dirname(os.path.abspath(__file__)),
                            "scripts", "ramp_job_partitioning_configs"),
                        help="training config tree for the checkpoint's "
                             "model architecture")
    parser.add_argument("--serve-config-name", default="rllib_config")
    parser.add_argument("--serve-override", action="append", default=[],
                        help="serve config override, e.g. "
                             "env_config=env_load32 (repeatable)")
    parser.add_argument("--loop-mode",
                        choices=("sequential", "pipelined", "fused",
                                 "both"),
                        default="both",
                        help="ppo mode's epoch schedule: sequential "
                             "(pre-round-6 loop: per-update blocking "
                             "host sync), pipelined (deferred metric "
                             "sync + async update dispatch), fused "
                             "(ONE jitted collect->update program per "
                             "epoch over the in-kernel env, rl/fused.py"
                             "), or both (default: interleaved "
                             "pipelined/fused rounds in ONE process, "
                             "headline = fused, so the round-8 A/B is "
                             "load-controlled)")
    parser.add_argument("--fused-updates-per-epoch", type=int, default=1,
                        help="fused mode: collect->update rounds per "
                             "jitted epoch dispatch. Raising it "
                             "amortises the per-epoch dispatch (not "
                             "measured on a chip); on CPU the dispatch "
                             "is ~free and each extra scan round costs "
                             "~10%% (docs/perf_round8.md), so the "
                             "default stays 1")
    parser.add_argument("--fused-lanes", type=int, default=0,
                        help="fused mode: pin the lane count (0 = "
                             "program-size-aware autotune)")
    parser.add_argument("--fused-segment-len", type=int, default=0,
                        help="fused mode: pin the per-lane segment "
                             "length (0 = autotune; lanes x segment "
                             "must equal num_envs x rollout_length)")
    parser.add_argument("--ab-degree", type=int, default=0,
                        help="ppo/sim env max_partitions_per_op "
                             "override (0 = canonical 16). The round-8 "
                             "fused A/B runs at 2: the jitted env pays "
                             "the full padded lookahead per decision "
                             "with no memo cache, so at the canonical "
                             "degree-16 pads the in-kernel tax drowns "
                             "the loop-structure difference on a CPU "
                             "core (docs/perf_round8.md); the sim "
                             "denominator rider inherits the same "
                             "degree so loop_efficiency stays "
                             "same-env")
    parser.add_argument("--num-envs", type=int, default=None)
    parser.add_argument("--rollout-length", type=int, default=32)
    parser.add_argument("--timed-epochs", type=int, default=3)
    parser.add_argument("--warmup-epochs", type=int, default=1)
    parser.add_argument("--num-sgd-iter", type=int, default=50)
    parser.add_argument("--sim-seconds", type=float, default=20.0)
    parser.add_argument("--budget-seconds", type=float, default=420.0,
                        help="stop timing epochs past this wall-clock "
                             "budget so a JSON line always lands")
    parser.add_argument("--telemetry-jsonl", default=None,
                        help="append span/event/snapshot records to this "
                             "JSONL sink (see scripts/telemetry_report.py;"
                             " env fallback: DDLS_TELEMETRY_JSONL)")
    parser.add_argument("--run-dir", default=None,
                        help="write a fingerprinted RunLedger directory "
                             "(manifest.json + telemetry.jsonl + "
                             "result.json + snapshot.json — "
                             "telemetry/runlog.py); merge into a "
                             "Perfetto trace with `python -m "
                             "ddls_tpu.telemetry.timeline <dir>`. "
                             "Overrides --telemetry-jsonl for the run's "
                             "sink")
    args = parser.parse_args(argv)
    from ddls_tpu.utils.runtime import configure_compile_cache

    configure_compile_cache()
    # fresh telemetry window per invocation (tests drive main() several
    # times in one process; each bench line must snapshot ITS run only),
    # and the PREVIOUS global state — enabled flag, sink, AND the
    # caller's accumulated metrics — is restored on the way out: an
    # in-process caller must neither inherit an enabled registry / stale
    # sink / bench's spans, nor lose its own metrics to bench's reset
    # (the golden/parity suites pin the telemetry-disabled behaviour)
    reg = telemetry.registry()
    prev_enabled, prev_sink = reg.enabled, reg.sink
    prev_metrics = reg.metrics_state()
    telemetry.reset()
    telemetry.enable(sink_path=(args.telemetry_jsonl
                                or telemetry.env_sink_path()))
    global _RUN_LEDGER
    if args.run_dir:
        from ddls_tpu.telemetry.runlog import RunLedger

        # opened inside bench's telemetry window: the ledger swaps the
        # sink to <run_dir>/telemetry.jsonl and finalize() hands the
        # prior sink back before the window's own restore below
        _RUN_LEDGER = RunLedger(
            args.run_dir, kind=f"bench:{args.mode}",
            config={k: v for k, v in vars(args).items()}).open()
    try:
        return _dispatch_mode(args, process_start)
    finally:
        if _RUN_LEDGER is not None:
            try:
                _RUN_LEDGER.finalize()
            finally:
                _RUN_LEDGER = None
        if reg.sink is not prev_sink and reg.sink is not None:
            reg.sink.close()
        reg.sink = prev_sink
        reg.enabled = prev_enabled
        reg.restore_metrics_state(prev_metrics)


# mode -> (runner, metric, unit, host_only). Host-only: sim/collect have
# no device in the loop; impala/fragments arms differ in HOST schedule /
# process structure; partition's bytes accounting and overhead floor are
# taken on the virtual 8-device CPU mesh (run it with
# XLA_FLAGS=--xla_force_host_platform_device_count=8).
_MODES = {
    "jaxenv": (run_jaxenv_bench,
               "jaxenv_decisions_per_sec", "decisions/s", False),
    "serve": (run_serve_bench,
              "serve_decisions_per_sec", "decisions/s", False),
    "sim": (run_sim_bench,
            "sim_env_steps_per_sec", "env_steps/s", True),
    "collect": (run_collect_bench,
                "collect_env_steps_per_sec", "env_steps/s", True),
    "impala": (run_impala_depth_bench,
               "impala_env_steps_per_sec", "env_steps/s", True),
    "fragments": (run_fragments_bench,
                  "fragments_env_steps_per_sec", "env_steps/s", True),
    "partition": (run_partition_bench,
                  "partition_update_env_steps_per_sec", "env_steps/s",
                  True),
}


def _dispatch_mode(args, process_start: float) -> int:
    if args.num_envs is None:
        cores = _available_cores()
        if cores == 1:
            # in-process serial envs cost the same host time regardless
            # of count; for the ppo loop each sampling call is one device
            # dispatch for the whole batch, so more envs amortise it. sim
            # mode has no device in the loop and 8 envs measure slightly
            # faster (less cache pressure)
            args.num_envs = 32 if args.mode == "ppo" else 8
        else:
            # one subprocess env worker per core (reference: 8 rollout
            # workers); more would just oversubscribe the host
            args.num_envs = max(2, min(16, cores))

    if args.mode == "ppo":
        return _run_mode(args,
                         lambda args: run_bench(args, process_start),
                         "ppo_env_steps_per_sec", "env_steps/s",
                         host_only=False)
    runner, metric, unit, host_only = _MODES[args.mode]
    return _run_mode(args, runner, metric, unit, host_only)


if __name__ == "__main__":
    sys.exit(main())
