"""Batched candidate-degree pricing: parity with the cluster's own
lookahead, memo-cache prefetching, the backends it takes and refuses,
and the OracleJCT consumer (docs/jax_lookahead_gonogo.md point 2;
VERDICT r2 next #3)."""
import tempfile

import numpy as np
import pytest

from ddls_tpu.envs import RampJobPartitioningEnvironment
from ddls_tpu.envs.baselines import AcceptableJCT, OracleJCT
from ddls_tpu.graphs.synthetic import generate_pipedream_txt_files


def _env_kwargs(dataset_dir, **overrides):
    kwargs = dict(
        topology_config={"type": "ramp", "kwargs": {
            "num_communication_groups": 2,
            "num_racks_per_communication_group": 2,
            "num_servers_per_rack": 2,
            "num_channels": 1,
            "total_node_bandwidth": 1.6e12,
            "intra_gpu_propagation_latency": 50e-9,
            "worker_io_latency": 100e-9}},
        node_config={"type_1": {"num_nodes": 8, "workers_config": [
            {"num_workers": 1, "worker": "A100"}]}},
        jobs_config={
            "path_to_files": dataset_dir,
            "job_interarrival_time_dist": {
                "_target_": "ddls_tpu.demands.distributions.Fixed",
                "val": 100.0},
            "max_acceptable_job_completion_time_frac_dist": {
                "_target_": "ddls_tpu.demands.distributions.Uniform",
                "min_val": 0.2, "max_val": 1.0, "decimals": 2},
            "replication_factor": 15,
            "job_sampling_mode": "remove_and_repeat",
            "num_training_steps": 10},
        max_partitions_per_op=8,
        min_op_run_time_quantum=0.01,
        reward_function="job_acceptance",
        max_simulation_run_time=1.5e4,
        pad_obs_kwargs={"max_nodes": 150, "max_edges": 512})
    kwargs.update(overrides)
    return kwargs


@pytest.fixture(scope="module")
def dataset_dir():
    d = tempfile.mkdtemp(prefix="candidate_pricing_")
    generate_pipedream_txt_files(d, n_cnn=2, n_translation=1, seed=11)
    return d


def test_prices_match_step_lookahead_and_prefetch(dataset_dir):
    """For every step of a real episode: the price of the chosen action
    equals the cluster's own lookahead outcome EXACTLY (native backend is
    the same bit-exact C++ engine), and the step's lookahead is served
    from the prefetched memo entry (no engine call)."""
    env = RampJobPartitioningEnvironment(
        **_env_kwargs(dataset_dir, candidate_pricing="native"))
    obs = env.reset(seed=3)
    rng = np.random.RandomState(0)
    checked = 0
    engine_calls = []
    orig = env.cluster._run_native_lookahead

    def spy(job):
        engine_calls.append(job.job_id)
        return orig(job)

    env.cluster._run_native_lookahead = spy
    host_calls = []
    orig_host = env.cluster._run_lookahead
    env.cluster._run_lookahead = lambda job: (host_calls.append(job.job_id)
                                              or orig_host(job))
    for _ in range(25):
        prices = dict(env.candidate_prices)
        decided = None
        if len(env.cluster.job_queue.jobs):
            decided = next(iter(env.cluster.job_queue.jobs.values()))
        valid = np.nonzero(np.asarray(obs["action_mask"]))[0]
        action = int(rng.choice(valid))
        before = len(engine_calls) + len(host_calls)
        obs, reward, done, info = env.step(action)
        if action != 0 and decided is not None \
                and prices.get(action) is not None:
            # the chosen candidate was prefetched: the step ran NO engine
            assert len(engine_calls) + len(host_calls) == before, (
                f"step re-ran the lookahead engine for action {action}")
            # the job just decided carries EXACTLY the predicted JCT (the
            # lookahead detail lives on the PARTITIONED clone the cluster
            # runs, found by job_idx in whichever lifecycle dict holds it)
            ji = decided.details["job_idx"]
            if ji in env.cluster.jobs_blocked:
                # SLA block: the predicted JCT must indeed exceed the limit
                assert prices[action][0] > decided.max_acceptable_jct
            else:
                placed = (env.cluster.jobs_running.get(ji)
                          or env.cluster.jobs_completed.get(ji))
                assert placed is not None
                la = placed.details["lookahead_job_completion_time"]
                assert la == prices[action][0], (la, prices[action][0])
            checked += 1
        if done:
            break
    assert checked >= 5


def test_unplaceable_candidates_price_none(dataset_dir):
    """Degrees the cluster cannot host (no free block) price to None, and
    placeable ones carry finite positive JCTs."""
    env = RampJobPartitioningEnvironment(
        **_env_kwargs(dataset_dir, candidate_pricing="native"))
    env.reset(seed=1)
    prices = env.candidate_prices
    assert prices, "no prices for the first queued job"
    placeable = {a: p for a, p in prices.items() if p is not None}
    assert placeable, "first job on an empty cluster must be placeable"
    for a, (jct, comm, comp, busy) in placeable.items():
        assert np.isfinite(jct) and jct > 0
        assert busy > 0


@pytest.mark.parametrize("backend", ["jax", "host"])
def test_unknown_backend_raises(dataset_dir, backend):
    """The C++ engine is the only pricing backend: the retired value
    ``jax`` and any other string raise ``ValueError``, from the call and
    from the env's constructor alike."""
    env = RampJobPartitioningEnvironment(**_env_kwargs(dataset_dir))
    env.reset(seed=5)
    with pytest.raises(ValueError, match="candidate-pricing backend"):
        env.price_candidate_degrees(backend=backend)
    with pytest.raises(ValueError, match="candidate-pricing backend"):
        RampJobPartitioningEnvironment(
            **_env_kwargs(dataset_dir, candidate_pricing=backend))


@pytest.mark.parametrize("backend", ["auto", "native"])
def test_pricing_without_the_engine_is_refused_at_construction(
        dataset_dir, monkeypatch, backend):
    """Where the C++ engine does not build, ``candidate_pricing`` is
    refused when the env is constructed, by an error that names the
    engine and its toolchain: there is no slower backend to fall to."""
    from ddls_tpu import native

    monkeypatch.setattr(native, "native_available", lambda: False)
    with pytest.raises(RuntimeError, match=r"C\+\+ engine.*g\+\+"):
        RampJobPartitioningEnvironment(
            **_env_kwargs(dataset_dir, candidate_pricing=backend,
                          use_native_lookahead=False))
    # ... and pricing off still constructs there
    RampJobPartitioningEnvironment(
        **_env_kwargs(dataset_dir, use_native_lookahead=False))


def test_oracle_jct_respects_sla_better_than_approximation(dataset_dir):
    """Full-episode comparison: OracleJCT (true lookahead prices) must not
    lose to AcceptableJCT (sequential-time approximation) on the
    acceptance reward, and must run the whole episode with candidate
    pricing on."""

    def run(actor, pricing):
        env = RampJobPartitioningEnvironment(
            **_env_kwargs(dataset_dir, candidate_pricing=pricing))
        obs = env.reset(seed=9)
        done, total = False, 0.0
        while not done:
            job = None
            if len(env.cluster.job_queue.jobs):
                job = next(iter(env.cluster.job_queue.jobs.values()))
            a = actor.compute_action(obs, job_to_place=job, env=env)
            obs, r, done, _ = env.step(a)
            total += r
        return total

    oracle = run(OracleJCT(max_partitions_per_op=8), "native")
    approx = run(AcceptableJCT(max_partitions_per_op=8), None)
    assert oracle >= approx, (oracle, approx)


def test_price_features_in_observation(dataset_dir):
    """obs_include_candidate_prices appends one priced-JCT/SLA ratio per
    action, 0.5 at the acceptance boundary, 1.0 for unpriceable, matching
    env.candidate_prices exactly at every decision (prices are computed
    BEFORE the observation so they describe the CURRENT queued job)."""
    env = RampJobPartitioningEnvironment(
        **_env_kwargs(dataset_dir, candidate_pricing="native",
                      obs_include_candidate_prices=True))
    obs = env.reset(seed=5)
    n_actions = env.max_partitions_per_op + 1
    base_dim = env.observation_space["graph_features"].shape[0] - n_actions
    rng = np.random.RandomState(1)
    checked = 0
    for _ in range(12):
        job = next(iter(env.cluster.job_queue.jobs.values()))
        feats = np.asarray(obs["graph_features"])[base_dim:]
        assert feats.shape == (n_actions,)
        limit = job.max_acceptable_jct
        for a in range(n_actions):
            priced = env.candidate_prices.get(a)
            if priced is not None:
                expected = min(priced[0] / max(limit, 1e-30), 2.0) / 2.0
                assert feats[a] == pytest.approx(expected, rel=1e-6), a
                # boundary semantics: <= 0.5 iff the SLA accepts it
                assert (feats[a] <= 0.5 + 1e-9) == (priced[0] <= limit
                                                    or feats[a] == 0.5)
                checked += 1
            else:
                assert feats[a] == 1.0
        valid = np.nonzero(np.asarray(obs["action_mask"]))[0]
        obs, _, done, _ = env.step(int(rng.choice(valid)))
        if done:
            break
    assert checked >= 8


def test_price_features_require_pricing(dataset_dir):
    with pytest.raises(ValueError, match="requires candidate_pricing"):
        RampJobPartitioningEnvironment(
            **_env_kwargs(dataset_dir, obs_include_candidate_prices=True))
