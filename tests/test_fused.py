"""Fused on-device collect→update epochs (rl/fused.py, ISSUE 12).

The load-bearing pin is the x64 full-epoch parity driver: the fused
program (ONE jitted lax.scan over U collect→update rounds) must
reproduce the sequential device-collector path — `DevicePPOCollector`
collects, `PPOLearner.train_step` updates — EXACTLY: post-training
params bit-equal, per-update metrics equal, episode records equal, on
the virtual 8-device mesh with lanes sharded over dp. Same subprocess
isolation as tests/test_jax_episode.py (JAX_ENABLE_X64 is
process-global).

In-process (f32): the steady-state fused epoch is transfer-free under
``jax.transfer_guard("disallow")``; DQN/ES reject loop_mode='fused'
loudly before any env construction; an unpinned fused loop builds
``num_envs`` lanes x ``rollout_length`` steps whatever a cache file of
the retired shape tuner says; a ``fused_config`` pin is validated
before a driver is built; a fused loop whose program does not compile
RAISES the compiler's error with the shape (never another loop mode).
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

ENV_CLS = "ddls_tpu.envs.partitioning_env.RampJobPartitioningEnvironment"

_TINY_MODEL = {"fcnet_hiddens": [16],
               "custom_model_config": {"out_features_msg": 4,
                                       "out_features_hidden": 8,
                                       "out_features_node": 4,
                                       "out_features_graph": 4}}


def _env_config(dataset_dir, horizon=2e3):
    return dict(
        topology_config={"type": "ramp", "kwargs": {
            "num_communication_groups": 2,
            "num_racks_per_communication_group": 2,
            "num_servers_per_rack": 2, "num_channels": 1,
            "total_node_bandwidth": 1.6e12,
            "intra_gpu_propagation_latency": 50e-9,
            "worker_io_latency": 100e-9}},
        node_config={"type_1": {"num_nodes": 8, "workers_config": [
            {"num_workers": 1, "worker": "A100"}]}},
        jobs_config={
            "path_to_files": dataset_dir,
            "job_interarrival_time_dist": {
                "_target_": "ddls_tpu.demands.distributions.Fixed",
                "val": 60.0},
            "max_acceptable_job_completion_time_frac_dist": {
                "_target_": "ddls_tpu.demands.distributions.Uniform",
                "min_val": 0.2, "max_val": 1.0, "decimals": 2},
            "replication_factor": 10,
            "job_sampling_mode": "remove_and_repeat",
            "num_training_steps": 10},
        max_partitions_per_op=4, min_op_run_time_quantum=0.01,
        reward_function="job_acceptance", max_simulation_run_time=horizon,
        pad_obs_kwargs={"max_nodes": 32, "max_edges": 64})


def _make_fused_loop(dataset_dir, **kw):
    from ddls_tpu.train import make_epoch_loop

    defaults = dict(
        path_to_env_cls=ENV_CLS,
        env_config=_env_config(dataset_dir),
        model=_TINY_MODEL,
        algo_config={"train_batch_size": 16, "sgd_minibatch_size": 8,
                     "num_sgd_iter": 2, "num_workers": 8},
        num_envs=8, rollout_length=2, n_devices=8,
        use_parallel_envs=False, evaluation_interval=None, seed=0,
        loop_mode="fused", updates_per_epoch=2,
        fused_config={"lanes": 8, "segment_len": 2})
    defaults.update(kw)
    return make_epoch_loop("ppo", **defaults)


# ===================================================== x64 parity driver
# A fused loop of E epochs x U updates must equal U*E sequential
# device-collector epochs: params EXACTLY, per-update metrics (the
# LazyMetrics mean over each fused epoch equals the f64 mean of its
# sequential epochs' metrics), and episode records field-for-field —
# with episodes actually completing (the 6e2 horizon ends one per lane).
PARITY_DRIVER = r"""
import tempfile
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
assert jax.config.read("jax_enable_x64")
assert len(jax.devices()) == 8
from ddls_tpu.graphs.synthetic import generate_pipedream_txt_files
from ddls_tpu.train import make_epoch_loop

import test_fused as tf

d = tempfile.mkdtemp(prefix="fused_parity_")
generate_pipedream_txt_files(d, n_cnn=1, n_translation=1, seed=9)
algo = {"train_batch_size": 16, "sgd_minibatch_size": 8,
        "num_sgd_iter": 2, "num_workers": 8, "device_collector": True}
kw = dict(path_to_env_cls=tf.ENV_CLS,
          env_config=tf._env_config(d, horizon=6e2),
          model=tf._TINY_MODEL,
          num_envs=8, rollout_length=2, n_devices=8,
          use_parallel_envs=False, evaluation_interval=None, seed=0)

U, E = 2, 3
seq = make_epoch_loop("ppo", algo_config=dict(algo),
                      loop_mode="sequential", **kw)
seq_metrics, seq_episodes = [], []
for _ in range(U * E):
    r = seq.run()
    seq_metrics.append(dict(r["learner"]))
    seq_episodes.extend(r["episodes"])
seq_params = jax.device_get(seq.state.params)
seq.close()

fus = make_epoch_loop("ppo", algo_config=dict(algo), loop_mode="fused",
                      updates_per_epoch=U, metrics_sync_interval=1,
                      fused_config={"lanes": 8, "segment_len": 2}, **kw)
fus_means, fus_episodes = [], []
for _ in range(E):
    r = fus.run()
    assert r["learner"]["num_updates"] == U
    fus_means.append(dict(r["learner"]))
    fus_episodes.extend(r["episodes"])
fus_params = jax.device_get(fus.state.params)
fus.close()

# post-training params: EXACT (bitwise array equality)
jax.tree_util.tree_map(
    lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)),
    seq_params, fus_params)

# LazyMetrics values: each fused epoch's mean equals the f64 mean of
# its U sequential updates' (already-float) metrics
for e in range(E):
    want = {k: float(np.mean([seq_metrics[e * U + u][k]
                              for u in range(U)]))
            for k in seq_metrics[0]}
    got = {k: v for k, v in fus_means[e].items() if k in want}
    assert got == want, (e, got, want)

# episode records: same records, same order, same fields — and
# episodes genuinely completed (the horizon guarantees >= 1 per lane)
assert len(seq_episodes) >= 8, len(seq_episodes)
assert seq_episodes == fus_episodes
print(f"FUSED_PARITY_OK episodes={len(fus_episodes)}")
"""


def test_fused_full_epoch_parity_x64():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_ENABLE_X64"] = "1"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO, os.path.dirname(os.path.abspath(__file__))])
    res = subprocess.run([sys.executable, "-c", PARITY_DRIVER], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, (res.stdout[-4000:], res.stderr[-4000:])
    assert "FUSED_PARITY_OK" in res.stdout, res.stdout[-2000:]


# =================================================== steady-state guards
@pytest.fixture(scope="module")
def fused_dataset(tmp_path_factory):
    from ddls_tpu.graphs.synthetic import generate_pipedream_txt_files

    d = str(tmp_path_factory.mktemp("fused_jobs"))
    generate_pipedream_txt_files(d, n_cnn=1, n_translation=1, seed=9)
    return d


def test_fused_epoch_transfer_free_then_harvests(fused_dataset):
    """ISSUE 12 acceptance, one loop/compile for both halves: with the
    drain boundary at metrics_sync_interval=3, epoch 2 is a
    steady-state fused epoch performing NO implicit device<->host
    transfer (params, opt state, rng keys, metrics, and episode
    counters all stay on device), and epoch 3 hits the drain boundary —
    params moved, metrics are epoch-mean-shaped, and episode records
    surface with the host record schema."""
    import jax

    loop = _make_fused_loop(
        fused_dataset, metrics_sync_interval=3,
        env_config=_env_config(fused_dataset, horizon=6e2))
    try:
        before = jax.device_get(loop.state.params)
        r1 = loop.run()  # warm: compile + first-use constant transfers
        assert r1["episodes"] == []  # epoch 1: no drain boundary yet
        with jax.transfer_guard("disallow"):
            r2 = loop.run()
        assert r2["episodes"] == []  # still pending on device
        r3 = loop.run()  # epoch 3: the drain boundary
        for r in (r1, r2, r3):
            assert np.isfinite(r["learner"]["total_loss"])
            assert r["learner"]["num_updates"] == 2
            assert r["env_steps_this_iter"] == 2 * 2 * 8  # U * T * B
        assert (loop.fused.num_lanes, loop.fused.segment_len) == (8, 2)
        # ONE compile for all three epochs: the first call's sim state
        # and rng keys are placed on the mesh exactly as the program
        # returns them (jax keys its jit cache on the mesh an input's
        # sharding names — an unplaced first call compiled the whole
        # epoch program twice: 86 s instead of 41 s at the shipped size
        # on the v5e, and a 74 s second epoch instead of 12 s)
        assert loop.fused._jit_epoch._cache_size() == 1
        moved = jax.tree_util.tree_map(
            lambda a, b: float(np.abs(np.asarray(a)
                                      - np.asarray(b)).max()),
            before, jax.device_get(loop.state.params))
        assert max(jax.tree_util.tree_leaves(moved)) > 0
        episodes = r3["episodes"]
        assert episodes, "horizon 6e2 must complete episodes by epoch 3"
        for e in episodes:
            assert set(e) >= {"env_index", "episode_return",
                              "episode_length", "num_jobs_arrived",
                              "num_jobs_completed", "num_jobs_blocked",
                              "acceptance_rate", "blocking_rate"}
            assert (e["num_jobs_arrived"]
                    >= e["num_jobs_completed"] + e["num_jobs_blocked"])
    finally:
        loop.close()


# ======================================================== the shape rule
@pytest.mark.parametrize("num_envs,rollout_length", [(8, 2), (4, 4)])
def test_unpinned_fused_shape_is_num_envs_by_rollout_length(
        fused_dataset, num_envs, rollout_length):
    """``num_envs`` means on a fused loop what it means on every other
    loop mode: without a pin the program has ``num_envs`` lanes of
    ``rollout_length`` steps (on this 2-device mesh the retired tuner
    built 2 lanes x 8 steps for both)."""
    loop = _make_fused_loop(fused_dataset, fused_config=None,
                            n_devices=2, num_envs=num_envs,
                            rollout_length=rollout_length)
    try:
        assert (loop.fused.num_lanes,
                loop.fused.segment_len) == (num_envs, rollout_length)
    finally:
        loop.close()


@pytest.mark.parametrize("lanes,segment_len", [(4, 4), (2, 8)])
def test_fused_config_pin_refactorises_the_same_batch(fused_dataset, lanes,
                                                      segment_len):
    """A pin that is NOT num_envs x rollout_length: the driver and its
    job banks take the pinned lane count, and an epoch still steps
    U x num_envs x rollout_length env steps."""
    loop = _make_fused_loop(
        fused_dataset, n_devices=2,
        fused_config={"lanes": lanes, "segment_len": segment_len})
    try:
        assert (loop.fused.num_lanes,
                loop.fused.segment_len) == (lanes, segment_len)
        assert {int(v.shape[0]) for v in loop.fused._banks.values()} == {
            lanes}
        assert loop.fused.env_steps_per_epoch == 2 * 8 * 2
    finally:
        loop.close()


def test_fused_autotune_cache_on_disk_is_not_read(fused_dataset,
                                                  monkeypatch, tmp_path):
    """No file on disk shapes a run. The entry below is keyed as the
    retired tuner keyed THIS workload (computed at commit cafe656) and
    names another valid factorisation; that tuner obeyed it."""
    entry = {"41539d2c4fdb214ca6bea76f": {
        "lanes": 4, "segment_len": 4,
        "estimated_bytes": 1, "actual_bytes": 1}}
    cache = tmp_path / "fused_autotune.json"
    cache.write_text(json.dumps(entry))
    monkeypatch.setenv("DDLS_TPU_PROBE_DIR", str(tmp_path))
    loop = _make_fused_loop(fused_dataset, fused_config=None,
                            n_devices=2)
    try:
        assert (loop.fused.num_lanes, loop.fused.segment_len) == (8, 2)
    finally:
        loop.close()
    assert json.loads(cache.read_text()) == entry
    assert os.listdir(tmp_path) == ["fused_autotune.json"]


# ====================================================== loud rejections
@pytest.mark.parametrize("fused_config,match", [
    ({"lanes": 8}, "together"),
    ({"lanes": 4, "segment_len": 2}, "per-update batch"),
    ({"lanes": 4, "segment_len": 4}, "dp axis"),
    ({"lanes": 8, "segment_len": 2, "probe_dir": "/tmp/x"}, "probe_dir"),
], ids=["half_a_pin", "product_mismatch", "lanes_not_multiple_of_dp",
        "unknown_key"])
def test_fused_config_pin_is_validated_before_any_driver(
        fused_dataset, monkeypatch, fused_config, match):
    """A pin re-factorises the SAME per-update batch (8 envs x 2 steps
    on the 8-device mesh here) or raises — before a bank is sampled."""
    from ddls_tpu.rl import fused as fused_mod

    def no_banks(*a, **kw):
        raise AssertionError("banks sampled before the pin was checked")

    monkeypatch.setattr(fused_mod, "stacked_job_banks", no_banks)
    with pytest.raises(ValueError, match=match):
        _make_fused_loop(fused_dataset, fused_config=fused_config)


def test_fused_that_cannot_compile_raises_not_falls_back(fused_dataset):
    """An explicitly requested loop_mode='fused' whose program does not
    compile raises the compiler's own error, naming the shape — it
    never trains on loop_mode='pipelined' behind a warning."""
    loop = _make_fused_loop(fused_dataset)

    def refuse(*args):
        raise RuntimeError("RESOURCE_EXHAUSTED: program too large")

    loop.fused._jit_epoch = refuse
    try:
        with pytest.raises(RuntimeError, match="program too large") as ei:
            loop.run()
        assert any("8 lanes x 2 steps x 2 updates" in note
                   for note in ei.value.__notes__)
        assert loop.loop_mode == "fused"
        assert getattr(loop, "collector", None) is None
    finally:
        loop.close()


@pytest.mark.parametrize("algo", ["apex_dqn", "es"])
def test_fused_rejected_loudly_without_contract(algo):
    """DQN (host replay insertion) and ES (host population fitness)
    cannot run a fused in-kernel epoch; the rejection fires before any
    env/model construction (env_config={} would explode otherwise)."""
    from ddls_tpu.train import make_epoch_loop

    with pytest.raises(ValueError, match="fused"):
        make_epoch_loop(algo, path_to_env_cls=ENV_CLS, env_config={},
                        loop_mode="fused")


def test_fused_rejects_multiprocess_and_bad_mode():
    from ddls_tpu.train import make_epoch_loop

    with pytest.raises(ValueError, match="loop_mode"):
        make_epoch_loop("ppo", path_to_env_cls=ENV_CLS, env_config={},
                        loop_mode="bogus")


def test_lazy_metrics_stacked_dict_mean():
    """The fused epoch shape: one dict of [U]-stacked device arrays,
    reduced as the f64 mean per key (bit-matching the sequential loop's
    python-float mean over its per-update dicts)."""
    import jax.numpy as jnp

    from ddls_tpu.train.metrics import LazyMetrics

    vals = np.asarray([0.1, 0.2, 0.7], np.float32)
    lm = LazyMetrics({"loss": jnp.asarray(vals)}, reduce="mean",
                     extras={"num_updates": 3})
    assert lm.pending
    assert set(lm) == {"loss", "num_updates"}
    want = float(np.mean([float(v) for v in vals]))
    assert lm["loss"] == want
    assert lm["num_updates"] == 3.0
    assert not lm.pending
