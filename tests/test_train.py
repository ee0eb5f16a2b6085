"""L7 train-stack tests: logger backends, launcher control flow, the
RLEpochLoop end-to-end on a tiny config, checkpoint round-trip, and the
shipped heuristic config driving an EvalLoop."""
import os

import numpy as np
import pytest

from ddls_tpu.config import instantiate, load_config
from ddls_tpu.train import (Checkpointer, Launcher, Logger, RLEpochLoop,
                            RLEvalLoop, ppo_config_from_rllib)

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIGS = os.path.join(REPO, "scripts", "ramp_job_partitioning_configs")


def test_logger_gzip_round_trip(tmp_path):
    logger = Logger(path_to_save=str(tmp_path))
    logger.log({"epochs": [{"a": 1}], "scalar": 5})
    logger.log({"epochs": [{"a": 2}], "scalar": 6})
    logger.save(blocking=True)
    back = Logger.load(str(tmp_path / "results.pkl.gz"))
    assert back["epochs"] == [{"a": 1}, {"a": 2}]  # lists extend
    assert back["scalar"] == 6  # scalars overwrite


def test_logger_sqlite_accumulates_across_flushes(tmp_path):
    logger = Logger(path_to_save=str(tmp_path), use_sqlite_database=True)
    logger.log({"epochs": [{"a": 1}]})
    logger.save(blocking=True)
    assert logger.results == {}  # cleared after sqlite flush
    logger.log({"epochs": [{"a": 2}]})
    logger.save(blocking=True)
    back = Logger.load(str(tmp_path / "results.sqlite"))
    assert back["epochs"] == [{"a": 1}, {"a": 2}]


def test_ppo_config_from_rllib_maps_keys():
    cfg = ppo_config_from_rllib({
        "lr": 1e-3, "gamma": 0.9, "lambda": 0.95, "clip_param": 0.3,
        "train_batch_size": 128, "grad_clip": 2.0})
    assert cfg.lr == 1e-3
    assert cfg.gae_lambda == 0.95
    assert cfg.clip_param == 0.3
    assert cfg.train_batch_size == 128
    assert cfg.grad_clip == 2.0
    # unknown keys are rejected loudly, never silently no-oped
    with pytest.raises(ValueError, match="not consumed"):
        ppo_config_from_rllib({"lr": 1e-3, "unknown_key": 1})


class _CountingEpochLoop:
    def __init__(self):
        self.runs = 0
        self.checkpoints = []
        self.best_checkpoint_path = None
        self.best_metric_value = None

    def run(self):
        self.runs += 1
        return {"episodes_this_iter": 2, "env_steps_this_iter": 10,
                "episode_reward_mean": float(self.runs)}

    def log(self, results):
        pass

    def save_agent_checkpoint(self, path):
        self.checkpoints.append(path)

    def register_checkpoint(self, path, results):
        self.best_checkpoint_path = path


def test_launcher_stop_conditions_and_checkpoint_cadence(tmp_path):
    loop = _CountingEpochLoop()
    launcher = Launcher(epoch_loop=loop, num_epochs=5, verbose=False)
    ckpt = Checkpointer(path_to_save=str(tmp_path), epoch_checkpoint_freq=2)
    summary = launcher.run(checkpointer=ckpt)
    assert loop.runs == 5
    assert summary["epochs_run"] == 5
    assert summary["episodes_run"] == 10
    assert summary["actor_steps_run"] == 50
    # initial checkpoint + epochs 2 and 4
    assert len(loop.checkpoints) == 3

    loop = _CountingEpochLoop()
    launcher = Launcher(epoch_loop=loop, num_actor_steps=25, verbose=False)
    launcher.run()
    assert loop.runs == 3  # 10 steps/epoch -> stops after 3rd

    with pytest.raises(ValueError):
        Launcher(epoch_loop=loop)


def _tiny_epoch_loop(dataset_dir, tmp_path, **kwargs):
    env_config = dict(
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
                "val": 1000.0},
            "max_acceptable_job_completion_time_frac_dist": {
                "_target_": "ddls_tpu.demands.distributions.Uniform",
                "min_val": 0.1, "max_val": 1.0, "decimals": 2},
            "replication_factor": 5,
            "job_sampling_mode": "remove_and_repeat",
            "num_training_steps": 50},
        max_partitions_per_op=8,
        min_op_run_time_quantum=0.01,
        reward_function="job_acceptance",
        reward_function_kwargs={"fail_reward": -1, "success_reward": 1},
        max_simulation_run_time=2e4,
        pad_obs_kwargs={"max_nodes": 64, "max_edges": 256})
    defaults = dict(
        path_to_env_cls=("ddls_tpu.envs.partitioning_env."
                         "RampJobPartitioningEnvironment"),
        env_config=env_config,
        model={"fcnet_hiddens": [32],
               "custom_model_config": {"out_features_msg": 8,
                                       "out_features_hidden": 8,
                                       "out_features_node": 4,
                                       "out_features_graph": 4}},
        algo_config={"train_batch_size": 16, "sgd_minibatch_size": 8,
                     "num_sgd_iter": 2, "num_workers": 2},
        num_envs=2, rollout_length=4, n_devices=2,
        evaluation_interval=None, seed=0)
    defaults.update(kwargs)
    return RLEpochLoop(**defaults)


def test_rl_epoch_loop_end_to_end(dataset_dir, tmp_path):
    loop = _tiny_epoch_loop(dataset_dir, tmp_path)
    r1 = loop.run()
    assert r1["env_steps_this_iter"] == 8
    assert np.isfinite(r1["learner"]["total_loss"])
    # per-update phase spans land in the global telemetry registry when
    # enabled (ISSUE 3) — and stay absent while it is disabled (r1 above)
    from ddls_tpu import telemetry

    assert "train.collect" not in telemetry.span_summaries()
    telemetry.reset()
    telemetry.enable()
    try:
        r2 = loop.run()
        spans = telemetry.span_summaries()
        assert {"train.collect", "train.device_transfer",
                "train.train_step"} <= set(spans)
        # pipelined default (PR 4): metrics stay device futures — no
        # per-update host_sync; the update's device wall is carried by
        # the monitor-thread span instead, and an explicit sync drains
        # the ring under exactly one host_sync span
        assert "train.host_sync" not in spans
        loop.sync_metrics()
        if loop._watch_executor is not None:  # settle the monitor span
            loop._watch_executor.shutdown(wait=True)
            loop._watch_executor = None
        spans = telemetry.span_summaries()
        assert spans["train.host_sync"]["count"] == 1
        assert "train.update_device" in spans
        assert all(s["count"] == 1 for s in spans.values())
        # the fused epoch's anatomy (PR 34) is the fused / sebulba
        # loops' alone: this loop keeps the spans it had
        assert not {"train.device_wait", "train.telemetry_reduce",
                    "train.harvest"} & set(spans)
    finally:
        telemetry.reset()
        telemetry.disable()
    assert r2["total_env_steps"] == 16

    # greedy evaluation produces cluster stats
    ev = loop.evaluate(num_episodes=1, seed=123)
    assert "episode_reward_mean" in ev
    assert ev["episodes_this_iter"] == 1

    # checkpoint round-trip restores params exactly (host copy: the live
    # state is donated into the next train_step and its buffers deleted)
    import jax

    path = str(tmp_path / "ckpt")
    loop.save_agent_checkpoint(path)
    params_before = jax.device_get(loop.state.params)
    loop.run()  # moves params
    loop.load_agent_checkpoint(path)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        loop.state.params, params_before)
    loop.close()


def test_rl_eval_loop_from_checkpoint(dataset_dir, tmp_path):
    loop = _tiny_epoch_loop(dataset_dir, tmp_path)
    path = str(tmp_path / "ckpt2")
    loop.save_agent_checkpoint(path)
    eval_loop = RLEvalLoop(loop)
    results = eval_loop.run(checkpoint_path=path, seed=7)
    assert results["episode"]["episode_length"] > 0
    stats = results["episode_stats"]
    assert stats["num_jobs_arrived"] >= (stats["num_jobs_completed"]
                                         + stats["num_jobs_blocked"])
    loop.close()


def test_shipped_heuristic_config_runs(dataset_dir):
    cfg = load_config(CONFIGS, "heuristic_config", overrides=[
        "eval_loop.env.jobs_config.path_to_files=" + dataset_dir,
        "eval_loop.env.jobs_config.synthetic=null",
        "eval_loop.env.jobs_config.replication_factor=3",
        "eval_loop.env.max_simulation_run_time=2e4",
        "eval_loop.env.pad_obs_kwargs.max_nodes=64",
        "eval_loop.env.pad_obs_kwargs.max_edges=256",
    ])
    eval_loop = instantiate(cfg["eval_loop"])
    results = eval_loop.run(seed=0)
    stats = results["episode_stats"]
    assert results["episode_length"] > 0
    assert stats["num_jobs_arrived"] > 0
    assert "steps_log" in results


def test_evaluate_preserves_global_rng(dataset_dir, tmp_path):
    """Periodic evaluation must not leak its fixed test seed into the
    process-global RNG that training workload sampling draws from."""
    loop = _tiny_epoch_loop(dataset_dir, tmp_path, test_seed=1799)
    np.random.seed(12345)
    expected = np.random.RandomState(12345).rand(3)  # what should come next
    loop.evaluate(num_episodes=1)
    np.testing.assert_allclose(np.random.rand(3), expected)
    loop.close()


def test_metric_lookup_handles_slash_keys():
    results = {"evaluation": {"custom_metrics/blocking_rate_mean": 0.25,
                              "episode_reward_mean": 3.0}}
    assert RLEpochLoop._lookup_metric(
        results, "evaluation/custom_metrics/blocking_rate_mean") == 0.25
    assert RLEpochLoop._lookup_metric(
        results, "evaluation/episode_reward_mean") == 3.0
    assert RLEpochLoop._lookup_metric(results, "evaluation/missing") is None


def test_launcher_eval_overrides_wire_to_epoch_loop():
    loop = _CountingEpochLoop()
    loop.evaluation_interval = 1
    loop.evaluation_duration = 3
    Launcher(epoch_loop=loop, num_epochs=1, eval_freq=5,
             num_eval_episodes=7, verbose=False)
    assert loop.evaluation_interval == 5
    assert loop.evaluation_duration == 7


def test_batched_evaluation_runs_all_episodes(dataset_dir, tmp_path):
    """evaluation_duration > 1 drives parallel eval envs with one jitted
    greedy call per step (reference's parallel eval workers)."""
    loop = _tiny_epoch_loop(dataset_dir, tmp_path,
                            evaluation_interval=None)
    results = loop.evaluate(3)
    assert results["episodes_this_iter"] == 3
    assert np.isfinite(results["episode_reward_mean"])

    # per-episode RNG isolation: episode i consumes exactly the stream
    # seeded by base_seed + i, so the first episode of a 3-env batch is
    # bit-identical to a 1-env evaluation at the same seed, and repeated
    # evaluations reproduce exactly
    solo = loop._run_greedy_episodes_batched(1, base_seed=123)
    batch = loop._run_greedy_episodes_batched(3, base_seed=123)
    assert solo[0]["episode_return"] == batch[0]["episode_return"]
    assert solo[0]["episode_length"] == batch[0]["episode_length"]
    again = loop._run_greedy_episodes_batched(3, base_seed=123)
    assert [r["episode_return"] for r in batch] == (
        [r["episode_return"] for r in again])
    loop.close()


def test_device_collector_mesh_gate_falls_back(dataset_dir, tmp_path):
    """ADVICE r5 item 1: the device-collector gate must check
    divisibility by the mesh's dp axis (what DevicePPOCollector actually
    validates), not the local device count. n_devices=3 with num_envs=8
    divides the 8 local devices but not the dp=3 mesh — previously this
    passed the gate and raised ValueError in the collector; now it warns
    and collects on one device."""
    import warnings

    from ddls_tpu.rl.ppo_device import DevicePPOCollector

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        loop = _tiny_epoch_loop(
            dataset_dir, tmp_path, n_devices=3, num_envs=8,
            algo_config={"train_batch_size": 16, "sgd_minibatch_size": 8,
                         "num_sgd_iter": 2, "num_workers": 2,
                         "device_collector": True})
    assert isinstance(loop.collector, DevicePPOCollector)
    assert loop.collector.mesh is None
    assert any("mesh dp axis" in str(w.message) for w in caught)
    # collection itself runs single-device — the crash was at collector
    # construction. (A full loop.run() stays impossible for this config
    # with ANY collector: the learner's dp=3 mesh cannot shard B=8 in
    # shard_traj — a pre-existing training-side constraint, not the
    # collector gate's concern.)
    out = loop.collector.collect(loop.state.params,
                                 loop._split_collect_rng())
    assert out["traj"]["actions"].shape == (loop.rollout_length, 8)
    loop.close()


def test_device_collector_shards_smaller_mesh(dataset_dir, tmp_path):
    """The flip side of the dp-axis gate: n_devices=3 with num_envs=6
    failed the old local-device-count check (6 % 8 != 0) and silently
    collected on ONE device; the dp check (6 % 3 == 0) shards lanes over
    the configured mesh and the full epoch trains end-to-end."""
    loop = _tiny_epoch_loop(
        dataset_dir, tmp_path, n_devices=3, num_envs=6,
        algo_config={"train_batch_size": 16, "sgd_minibatch_size": 8,
                     "num_sgd_iter": 2, "num_workers": 2,
                     "device_collector": True})
    assert loop.collector.mesh is not None
    assert int(loop.collector.mesh.shape["dp"]) == 3
    r = loop.run()
    assert r["env_steps_this_iter"] == 24
    assert np.isfinite(r["learner"]["total_loss"])
    loop.close()


def test_device_collector_epoch_loop(dataset_dir, tmp_path):
    """algo_config device_collector=true: collection runs in the jitted
    env (rl/ppo_device.py) while eval/checkpointing stay on the host
    surface — the PPO-on-device product path."""
    loop = _tiny_epoch_loop(
        dataset_dir, tmp_path,
        algo_config={"train_batch_size": 16, "sgd_minibatch_size": 8,
                     "num_sgd_iter": 2, "num_workers": 2,
                     "device_collector": True})
    from ddls_tpu.rl.ppo_device import DevicePPOCollector

    assert isinstance(loop.collector, DevicePPOCollector)
    r1 = loop.run()
    assert r1["env_steps_this_iter"] == 8
    assert np.isfinite(r1["learner"]["total_loss"])
    # banks are per-lane distinct (sampled from the env's own workload
    # machinery with lane-offset seeds; arrival times are Fixed here, so
    # distinctness shows in the sampled job-type sequences)
    b = loop.collector.banks
    assert not np.array_equal(np.asarray(b["type"][0]),
                              np.asarray(b["type"][1]))
    # episodes eventually complete in-kernel and surface as records
    n_eps = 0
    for _ in range(60):
        r = loop.run()
        n_eps += len(r.get("episodes") or [])
        if n_eps:
            break
    assert n_eps >= 1
    # host evaluation surface still works alongside device collection
    ev = loop.evaluate(num_episodes=1, seed=5)
    assert "episode_reward_mean" in ev
    loop.close()
