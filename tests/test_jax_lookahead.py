"""The jitted array lookahead must reproduce the host tick engine's
JCT/overhead outputs on real mounted jobs (SURVEY.md §7.4.1: build the
host oracle first, then property-test the array engine against it)."""
import numpy as np
import pytest

from ddls_tpu.envs.partitioning_env import RampJobPartitioningEnvironment


def _make_env(dataset_dir, max_partitions=4):
    # the C++ engine (auto-enabled) would absorb every cache-miss lookahead
    # before the host/jax engines under test here ever ran
    return RampJobPartitioningEnvironment(
        use_native_lookahead=False,
        topology_config={"type": "ramp", "kwargs": {
            "num_communication_groups": 2,
            "num_racks_per_communication_group": 2,
            "num_servers_per_rack": 2,
            "num_channels": 1,
            "total_node_bandwidth": 1.6e12}},
        node_config={"type_1": {"num_nodes": 8, "workers_config": [
            {"num_workers": 1, "worker": "A100"}]}},
        jobs_config={
            "path_to_files": dataset_dir,
            "job_interarrival_time_dist": {
                "_target_": "ddls_tpu.demands.distributions.Fixed",
                "val": 100.0},
            "replication_factor": 4,
            "job_sampling_mode": "remove_and_repeat",
            "num_training_steps": 3},
        max_partitions_per_op=max_partitions,
        reward_function="job_acceptance",
        max_simulation_run_time=1e5,
        pad_obs_kwargs={"max_nodes": 64, "max_edges": 256})


def _collect_cases(env, actions, n_cases):
    """Step the env with the given action sequence, capturing
    (host lookahead outputs, padded arrays) per successfully placed job."""
    from ddls_tpu.sim.jax_lookahead import build_lookahead_arrays

    cases = []
    obs = env.reset(seed=0)
    rng = np.random.RandomState(0)
    cluster = env.cluster
    orig = cluster._run_lookahead

    def spy(job):
        jct, comm, comp, busy = orig(job)
        steps = job.num_training_steps
        arrays = build_lookahead_arrays(cluster, job, pad_ops=160,
                                        pad_deps=520, pad_links=2)
        cases.append({"host": (jct / steps, comm / steps, comp / steps),
                      "host_busy": busy,
                      "arrays": arrays})
        return jct, comm, comp, busy

    cluster._run_lookahead = spy
    try:
        i = 0
        while len(cases) < n_cases:
            mask = np.asarray(obs["action_mask"])
            valid = np.nonzero(mask)[0]
            if actions == "max":
                a = int(valid[-1])
            elif actions == "min":
                a = int(valid[0])
            else:
                a = int(rng.choice(valid))
            obs, _, done, _ = env.step(a)
            i += 1
            if done or i > 200:
                obs = env.reset(seed=i)
                # memo caches persist across resets (same workload); clear
                # so repeated episodes keep producing cache-miss lookaheads
                # for the spy to capture
                cluster.lookahead_cache.clear()
    finally:
        cluster._run_lookahead = orig
    return cases


@pytest.mark.parametrize("actions", ["max", "random"])
def test_matches_host_engine(dataset_dir, actions):
    from ddls_tpu.sim.jax_lookahead import arrays_as_args, lookahead_fn

    env = _make_env(dataset_dir)
    cases = _collect_cases(env, actions, n_cases=6)
    assert cases, "no lookahead cases captured"

    fns = {}
    for case in cases:
        a = case["arrays"]
        key = (a.num_workers, a.num_channels)
        fn = fns.setdefault(key, lookahead_fn(*key))
        t, comm, comp, busy, ok, _trips = fn(*arrays_as_args(a))
        assert bool(ok), "array engine failed to converge"
        host_t, host_comm, host_comp = case["host"]
        assert float(t) == pytest.approx(host_t, rel=1e-4), \
            f"jct mismatch: jax {float(t)} vs host {host_t}"
        assert float(comm) == pytest.approx(host_comm, rel=1e-4, abs=1e-6)
        assert float(comp) == pytest.approx(host_comp, rel=1e-4, abs=1e-6)
        assert float(busy) == pytest.approx(case["host_busy"], rel=1e-4,
                                            abs=1e-6)


def test_vmapped_batch(dataset_dir):
    """vmap over a batch of jobs padded to common shapes."""
    from ddls_tpu.sim.jax_lookahead import (arrays_as_args,
                                            batched_lookahead_fn)

    env = _make_env(dataset_dir)
    cases = _collect_cases(env, "random", n_cases=4)
    # pad worker/channel statics to the max across the batch
    W = max(c["arrays"].num_workers for c in cases)
    C = max(c["arrays"].num_channels for c in cases)
    fn = batched_lookahead_fn(W, C)
    batch = [np.stack([arrays_as_args(c["arrays"])[k] for c in cases])
             for k in range(13)]
    t, comm, comp, busy, ok, _trips = fn(*batch)
    assert bool(np.all(ok))
    for bi, case in enumerate(cases):
        assert float(t[bi]) == pytest.approx(case["host"][0], rel=1e-4)


def test_cluster_opt_in_backend_matches_host(dataset_dir):
    """use_jax_lookahead=True: a full episode's outcomes (JCTs, blocking,
    overheads, utilisation) match the host engine's episode to f32
    precision (docs/jax_lookahead_gonogo.md integration)."""
    episodes = {}
    for use_jax in (False, True):
        env = _make_env(dataset_dir)
        env.cluster.use_jax_lookahead = use_jax
        obs = env.reset(seed=0)
        done, steps = False, 0
        while not done and steps < 60:
            mask = np.asarray(obs["action_mask"])
            a = int(np.nonzero(mask)[0][-1])  # max parallelism: misses cache
            obs, _, done, _ = env.step(a)
            steps += 1
        episodes[use_jax] = env.cluster.episode_stats

    host, jaxe = episodes[False], episodes[True]
    assert jaxe["num_jobs_completed"] == host["num_jobs_completed"]
    assert jaxe["num_jobs_blocked"] == host["num_jobs_blocked"]
    assert jaxe["job_completion_time"] == pytest.approx(
        host["job_completion_time"], rel=1e-4)
    assert jaxe["job_communication_overhead_time"] == pytest.approx(
        host["job_communication_overhead_time"], rel=1e-4, abs=1e-6)
    assert jaxe["jobs_completed_mean_mounted_worker_utilisation_frac"] == (
        pytest.approx(
            host["jobs_completed_mean_mounted_worker_utilisation_frac"],
            rel=1e-4))
