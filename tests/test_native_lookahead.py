"""Parity tests for the C++ lookahead engine (ddls_tpu/native).

Contract: bit-exact f64 agreement with the host tick engine
(cluster._run_lookahead) — identical semantics AND identical arithmetic
order — so the native path can be enabled by default ("auto") without
perturbing the golden stats tests.
"""
import numpy as np
import pytest

from ddls_tpu.envs import RampJobPartitioningEnvironment
from ddls_tpu.native import native_available, run_lookahead

pytestmark = pytest.mark.skipif(
    not native_available(), reason="native toolchain unavailable")


def _env_kwargs(tmp_path, **overrides):
    from ddls_tpu.graphs.synthetic import generate_pipedream_txt_files

    dataset = str(tmp_path / "graphs")
    generate_pipedream_txt_files(dataset, n_cnn=2, n_translation=1, seed=0,
                                 min_ops=8, max_ops=14)
    kwargs = dict(
        topology_config={"type": "ramp", "kwargs": {
            "num_communication_groups": 2,
            "num_racks_per_communication_group": 2,
            "num_servers_per_rack": 2,
            "num_channels": 2,
            "total_node_bandwidth": 1.6e12,
            "intra_gpu_propagation_latency": 50e-9,
            "worker_io_latency": 100e-9}},
        node_config={"type_1": {"num_nodes": 8, "workers_config": [
            {"num_workers": 1, "worker": "A100"}]}},
        jobs_config={
            "path_to_files": dataset,
            "job_interarrival_time_dist": {
                "_target_": "ddls_tpu.demands.distributions.Fixed",
                "val": 500.0},
            "max_acceptable_job_completion_time_frac_dist": {
                "_target_": "ddls_tpu.demands.distributions.Uniform",
                "min_val": 0.3, "max_val": 1.0, "decimals": 2},
            "replication_factor": 20,
            "job_sampling_mode": "remove_and_repeat",
            "num_training_steps": 10},
        max_partitions_per_op=8,
        min_op_run_time_quantum=0.01,
        reward_function="job_acceptance",
        reward_function_kwargs={"fail_reward": -1, "success_reward": 1},
        max_simulation_run_time=1e6,
        pad_obs_kwargs={"max_nodes": 150, "max_edges": 512})
    kwargs.update(overrides)
    return kwargs


def test_native_bit_exact_with_host_engine(tmp_path):
    """Every cache-miss lookahead of real episodes agrees bit-for-bit."""
    env = RampJobPartitioningEnvironment(
        **_env_kwargs(tmp_path, use_native_lookahead=False))
    cluster = env.cluster
    host_engine = cluster._run_lookahead
    compared = []

    def spy(job):
        host = host_engine(job)
        native = cluster._run_native_lookahead(job)
        compared.append((host, native, job.graph.n_ops, job.graph.n_deps))
        return host

    cluster._run_lookahead = spy
    obs = env.reset(seed=0)
    rng = np.random.RandomState(0)
    for i in range(80):
        valid = np.nonzero(np.asarray(obs["action_mask"]))[0]
        obs, _, done, _ = env.step(int(rng.choice(valid)))
        if done:
            obs = env.reset(seed=100 + i)
            # caches persist across resets; clear so later episodes keep
            # producing cache-miss lookaheads for the spy to compare
            cluster.lookahead_cache.clear()

    assert len(compared) >= 5, "episodes produced too few cache-miss lookaheads"
    for host, native, n_ops, n_deps in compared:
        assert native is not None, f"native bailed on n={n_ops} m={n_deps}"
        # bit-exact: the native engine replicates the host's f64 arithmetic
        assert tuple(host) == tuple(native)


def test_full_episode_outcomes_identical(tmp_path):
    """A full episode with the native path auto-enabled reproduces the
    pure-host episode exactly (JCTs, rewards, blocking)."""
    outcomes = []
    for use_native in (False, True):
        env = RampJobPartitioningEnvironment(
            **_env_kwargs(tmp_path, use_native_lookahead=use_native))
        obs = env.reset(seed=3)
        rng = np.random.RandomState(3)
        rewards, done, steps = [], False, 0
        while not done and steps < 200:
            valid = np.nonzero(np.asarray(obs["action_mask"]))[0]
            obs, r, done, _ = env.step(int(rng.choice(valid)))
            rewards.append(r)
            steps += 1
        stats = env.cluster.episode_stats
        outcomes.append((rewards,
                         stats["num_jobs_completed"],
                         stats["num_jobs_blocked"],
                         tuple(stats.get("job_completion_time", []))))
    assert outcomes[0] == outcomes[1]


def test_native_bails_to_none_on_livelock():
    """A non-flow dep with positive remaining can never finish (the host
    engine raises); the native engine must return None (fall back)."""
    from ddls_tpu.native.arrays import LookaheadArrays

    arrays = LookaheadArrays(
        op_remaining=np.array([1.0], np.float64),
        op_valid=np.array([True]),
        op_worker=np.array([0], np.int32),
        op_score=np.array([1.0], np.float64),
        num_parents=np.array([0], np.int32),
        dep_remaining=np.array([5.0], np.float64),
        dep_valid=np.array([True]),
        dep_src=np.array([0], np.int32),
        dep_dst=np.array([0], np.int32),
        dep_mutual=np.array([True]),
        dep_is_flow=np.array([False]),
        dep_score=np.array([1.0], np.float64),
        dep_channel=np.full((1, 1), -1, np.int32),
        num_workers=1, num_channels=1)
    assert run_lookahead(arrays) is None


def test_auto_flag_enables_native(tmp_path):
    env = RampJobPartitioningEnvironment(**_env_kwargs(tmp_path))
    assert env.cluster.use_native_lookahead is True
