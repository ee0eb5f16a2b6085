"""Sebulba actor/learner device split (rl/sebulba.py, ISSUE 17).

The load-bearing pin is the x64 depth-0 parity driver: the Sebulba loop
(in-kernel collection jitted over a 4-device actor sub-mesh, the
standalone PPO update over the 4-device learner complement, trajectories
handed over a device-mode ring) must reproduce a MANUAL sequential
reference built from the SAME sub-meshes — `DevicePPOCollector` on the
actor mesh, `PPOLearner` on the learner mesh — EXACTLY: post-training
params bit-equal, per-epoch metrics equal, episode records equal.
Matched partitioning is the contract (rl/ppo_device.py: the bootstrap
forward's partitioned accumulation order depends on the dp width), so
the reference is assembled on the split meshes rather than the stock
full-mesh sequential loop.

In-process (f32): the steady-state Sebulba epoch is transfer-free under
``jax.transfer_guard("disallow")`` (every cross-mesh hop is an explicit
device_put); infeasible meshes fall back to pipelined LOUDLY; DQN/ES
and multi-deep explicit splits reject loudly; the device-mode ring's
token protocol is exercised directly.
"""
import os
import subprocess
import sys
import warnings

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from test_fused import ENV_CLS, _TINY_MODEL, _env_config  # noqa: E402


@pytest.fixture(scope="module")
def sebulba_dataset(tmp_path_factory):
    from ddls_tpu.graphs.synthetic import generate_pipedream_txt_files

    d = str(tmp_path_factory.mktemp("sebulba_jobs"))
    generate_pipedream_txt_files(d, n_cnn=1, n_translation=1, seed=9)
    return d


def _make_sebulba_loop(dataset_dir, algo="ppo", **kw):
    from ddls_tpu.train import make_epoch_loop

    defaults = dict(
        path_to_env_cls=ENV_CLS,
        env_config=_env_config(dataset_dir, horizon=6e2),
        model=_TINY_MODEL,
        algo_config={"train_batch_size": 16, "sgd_minibatch_size": 8,
                     "num_sgd_iter": 2, "num_workers": 8},
        num_envs=8, rollout_length=2, n_devices=8,
        use_parallel_envs=False, evaluation_interval=None, seed=0,
        loop_mode="sebulba",
        sebulba_config={"actor_devices": 4})
    defaults.update(kw)
    return make_epoch_loop(algo, **defaults)


# ===================================================== x64 parity driver
# Depth-0 Sebulba over E epochs must equal E sequential collect→update
# steps on the SAME sub-mesh split: params EXACTLY (bitwise), per-epoch
# metrics equal, episode records field-for-field equal (the 6e2 horizon
# completes episodes).
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

d = tempfile.mkdtemp(prefix="sebulba_parity_")
generate_pipedream_txt_files(d, n_cnn=1, n_translation=1, seed=9)
algo = {"train_batch_size": 16, "sgd_minibatch_size": 8,
        "num_sgd_iter": 2, "num_workers": 8, "device_collector": True}
kw = dict(path_to_env_cls=tf.ENV_CLS,
          env_config=tf._env_config(d, horizon=6e2),
          model=tf._TINY_MODEL,
          num_envs=8, rollout_length=2, n_devices=8,
          use_parallel_envs=False, evaluation_interval=None, seed=0)
E = 6

# the MANUAL sequential reference on the SAME sub-mesh split: start
# from a stock sequential device-collector loop, then rebuild its
# learner on the learner sub-mesh and its collector on the actor
# sub-mesh (matched partitioning is the bit-parity contract). The
# loop's own rng bookkeeping (_split_collect_rng/_split_rng) is reused
# unchanged — both loops split the same seeds in the same order.
seq = make_epoch_loop("ppo", algo_config=dict(algo),
                      loop_mode="sequential", **kw)
from ddls_tpu.rl.ppo import PPOLearner
from ddls_tpu.rl.ppo_device import DevicePPOCollector
from ddls_tpu.rl.sebulba import split_meshes

actor_mesh, learner_mesh = split_meshes(
    4, devices=list(seq.mesh.devices.flat))
seq.mesh = learner_mesh
seq.learner = PPOLearner(seq.apply_fn, seq.ppo_cfg, learner_mesh)
seq.state = seq.learner.init_state(seq.params)
env0, et, ot = seq._device_tables()
stacked = seq._stacked_banks(et, env0, seq.num_envs)


class CrossMeshCollector(DevicePPOCollector):
    # the reference needs the SAME explicit learner->actor params hop
    # the Sebulba collector performs (state.params arrive committed to
    # the learner sub-mesh; device_put replication changes no bits)
    def collect(self, params, rng):
        from jax.sharding import NamedSharding, PartitionSpec as P
        params = jax.device_put(params, NamedSharding(self.mesh, P()))
        return super().collect(params, rng)


seq.collector = CrossMeshCollector(
    et, ot, seq.model, stacked, seq.rollout_length, mesh=actor_mesh,
    memo_cfg=seq._memo_knob())

seq_metrics, seq_episodes = [], []
for _ in range(E):
    r = seq.run()
    seq_metrics.append(dict(r["learner"]))
    seq_episodes.extend(r["episodes"])
seq_params = jax.device_get(seq.state.params)
seq.close()

seb = make_epoch_loop("ppo", algo_config=dict(algo),
                      loop_mode="sebulba", metrics_sync_interval=1,
                      sebulba_config={"actor_devices": 4}, **kw)
assert seb.loop_mode == "sebulba", "split must not have fallen back"
seb_metrics, seb_episodes = [], []
for _ in range(E):
    r = seb.run()
    seb_metrics.append(dict(r["learner"]))
    seb_episodes.extend(r["episodes"])
seb_params = jax.device_get(seb.state.params)
memo = seb.collector.memo_counters()
ring = seb.ring_stats()
seb.close()

# post-training params: EXACT (bitwise array equality)
jax.tree_util.tree_map(
    lambda a, b: np.testing.assert_array_equal(
        np.asarray(a), np.asarray(b)),
    seq_params, seb_params)

# per-epoch learner metrics: the LazyMetrics floats equal the
# sequential loop's blocking-fetch floats exactly (one update each)
for e in range(E):
    got = {k: v for k, v in seb_metrics[e].items() if k in seq_metrics[e]}
    assert got == seq_metrics[e], (e, got, seq_metrics[e])

# episode records: same records, same order, same fields — and
# episodes genuinely completed
assert len(seq_episodes) >= 8, len(seq_episodes)
assert seq_episodes == seb_episodes

# the actor lanes ran with the in-kernel memo (auto = on at 8 lanes)
assert memo is not None and memo["hits"] > 0, memo
# the device ring saw one lease+publish+release per epoch
assert ring["leases"] == E and ring["publishes"] == E, ring
print(f"SEBULBA_PARITY_OK episodes={len(seb_episodes)}")
"""


def test_sebulba_depth0_parity_vs_sequential_x64():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_ENABLE_X64"] = "1"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO, os.path.dirname(os.path.abspath(__file__))])
    res = subprocess.run([sys.executable, "-c", PARITY_DRIVER], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, (res.stdout[-4000:], res.stderr[-4000:])
    assert "SEBULBA_PARITY_OK" in res.stdout, res.stdout[-2000:]


# =================================================== steady-state guards
def test_sebulba_epoch_transfer_free_then_harvests(sebulba_dataset):
    """ISSUE 17 acceptance: with the drain boundary at
    metrics_sync_interval=3, epoch 2 is a steady-state Sebulba epoch
    performing NO implicit device<->host transfer (params hop
    learner→actor and trajectories actor→learner via EXPLICIT
    device_put only; metrics and episode counters stay on device), and
    epoch 3 hits the drain boundary — params moved, episode records
    surface with the host record schema."""
    import jax

    loop = _make_sebulba_loop(sebulba_dataset, metrics_sync_interval=3)
    try:
        assert loop.loop_mode == "sebulba"
        assert loop.actor_mesh is not None
        # disjoint silicon: the defining property of the split
        actor = set(loop.actor_mesh.devices.flat)
        learner = set(loop.mesh.devices.flat)
        assert actor and learner and not (actor & learner)
        before = jax.device_get(loop.state.params)
        r1 = loop.run()  # warm: compile + first-use constant transfers
        assert r1["episodes"] == []  # epoch 1: no drain boundary yet
        with jax.transfer_guard("disallow"):
            r2 = loop.run()
        assert r2["episodes"] == []  # still pending on device
        r3 = loop.run()  # epoch 3: the first drain boundary
        for r in (r1, r2, r3):
            assert np.isfinite(r["learner"]["total_loss"])
            assert r["env_steps_this_iter"] == 2 * 8  # T * B
        moved = jax.tree_util.tree_map(
            lambda a, b: float(np.abs(np.asarray(a)
                                      - np.asarray(b)).max()),
            before, jax.device_get(loop.state.params))
        assert max(jax.tree_util.tree_leaves(moved)) > 0
        # one update per epoch at T=2: 12 steps per lane by epoch 6
        # (the second drain boundary) — enough for the 6e2 horizon to
        # complete episodes
        episodes = list(r3["episodes"])
        for _ in range(3):
            episodes.extend(loop.run()["episodes"])
        assert episodes, "horizon 6e2 must complete episodes by epoch 6"
        for e in episodes:
            assert set(e) >= {"env_index", "episode_return",
                              "episode_length", "num_jobs_arrived",
                              "num_jobs_completed", "num_jobs_blocked",
                              "acceptance_rate", "blocking_rate"}
        stats = loop.ring_stats()
        assert stats["leases"] == 6 and stats["publishes"] == 6
        # slab-less segments: every probed alias verdict is "copied"
        # (the staged tree is a real device-to-device transfer)
        assert stats["aliased_segments"] and not any(
            stats["aliased_segments"])
    finally:
        loop.close()


def test_sebulba_impala_depth1_stale_queue(sebulba_dataset):
    """Depth-K rides along: IMPALA at pipeline_depth=1 keeps one batch
    in flight against pre-update params (background actor thread), the
    staleness shows up as ``params_age_updates`` in the metrics, and
    the ring accounts for it."""
    loop = _make_sebulba_loop(
        sebulba_dataset, algo="impala", metrics_sync_interval=1,
        pipeline_depth=1,
        algo_config={"lr": 1e-3, "train_batch_size": 16,
                     "num_workers": 8})
    try:
        assert loop.loop_mode == "sebulba"
        ages = []
        for _ in range(3):
            r = loop.run()
            ages.append(r["learner"]["params_age_updates"])
            assert np.isfinite(r["learner"]["clip_rho_fraction"])
        # batch 1 is collected inline (age 0); later batches come off
        # the depth-1 queue, collected before the preceding update
        assert ages[0] == 0.0 and max(ages[1:]) >= 1.0, ages
        stats = loop.ring_stats()
        assert stats["leases"] >= 3
        assert stats["mean_params_age"] is not None
    finally:
        loop.close()


def test_sebulba_infeasible_mesh_falls_back_loudly(sebulba_dataset):
    """A 1-device mesh cannot split: the loop warns and falls back to
    pipelined device collection instead of dying or silently
    single-meshing (the fused-fallback convention)."""
    from ddls_tpu.rl.ppo_device import DevicePPOCollector

    with warnings.catch_warnings(record=True) as caught:
        warnings.simplefilter("always")
        loop = _make_sebulba_loop(sebulba_dataset, n_devices=1,
                                  sebulba_config={})
    try:
        assert loop.loop_mode == "pipelined"
        assert isinstance(loop.collector, DevicePPOCollector)
        assert any("sebulba" in str(w.message) for w in caught)
    finally:
        loop.close()


def test_sebulba_explicit_bad_split_rejects(sebulba_dataset):
    """An explicit actor_devices that leaves a sub-mesh empty is a
    config error, not a fallback."""
    with pytest.raises(ValueError, match="sebulba"):
        _make_sebulba_loop(sebulba_dataset,
                           sebulba_config={"actor_devices": 8})


@pytest.mark.parametrize("algo", ["apex_dqn", "es"])
def test_sebulba_rejected_loudly_without_contract(algo):
    """DQN (host replay insertion) and ES (host population fitness)
    cannot collect in-kernel; the rejection fires before any env/model
    construction."""
    from ddls_tpu.train import make_epoch_loop

    with pytest.raises(ValueError, match="sebulba"):
        make_epoch_loop(algo, path_to_env_cls=ENV_CLS, env_config={},
                        loop_mode="sebulba")


def test_sebulba_rejects_depth_on_ppo(sebulba_dataset):
    """pipeline_depth > 0 under sebulba still needs an off-policy
    correction: PPO rejects exactly as in pipelined mode."""
    with pytest.raises(ValueError, match="stale"):
        _make_sebulba_loop(sebulba_dataset, pipeline_depth=1)


# ================================================== device-mode ring
def test_device_ring_token_protocol():
    """Slab-less segments: the alias probe over zero host views
    verdicts 'copied', so note_staged's phase-1 token (the staged
    device tree) releases the segment when ready; worker-attach
    surfaces reject loudly."""
    import jax.numpy as jnp

    from ddls_tpu.rl.ring import TrajRing

    ring = TrajRing(None, rows=3, num_envs=2, segments=2)
    try:
        seg = ring.lease()
        assert seg.views == {}
        ring.publish(seg)
        staged = {"obs": jnp.ones((3, 2))}
        ring.note_staged(seg, staged, generation=seg.generation)
        assert seg.aliased is False
        ring.sweep()  # the staged tree is ready -> released
        assert seg.state == "free"
        # phase 2 on an already-released segment is a harmless no-op
        ring.note_update(seg, jnp.zeros(()), generation=1)
        assert seg.state == "free"
        with pytest.raises(RuntimeError, match="device-mode"):
            ring.specs()
        with pytest.raises(RuntimeError, match="device-mode"):
            ring.segment_names()
    finally:
        ring.close()
