"""Partition-rule sharded learner (ISSUE 19, parallel/partition.py).

The rule engine (regex over /-joined param-tree paths -> PartitionSpec)
and its three named layouts: `replicated` (today's exact behaviour),
`fsdp` (large Dense kernels + adam moments sharded over the existing dp
axis — ZeRO-3), `tp` (output-feature tensor sharding over a second "mp"
mesh axis). Pins, per the acceptance criteria:

- engine semantics (first-match re.search, scalar leaves always
  replicated, unmatched non-scalar path is a LOUD error) and the
  canonical-path literal's sync with the runtime GNNPolicy tree (the
  lint frozen-param-tree cross-validation trusts that literal);
- x64 post-update parity: fsdp vs replicated on the SAME 1-D dp mesh is
  bitwise-class (<= 1e-12 measured 2.9e-16); tp vs replicated on the
  SAME (dp, mp) mesh is 1e-9-class (measured 5.8e-15). The tp baseline
  MUST share the mesh: PPO stratifies minibatches per dp shard, so a
  different dp width is genuinely different training math, not a layout
  effect. Subprocess-isolated like tests/test_jax_episode.py
  (JAX_ENABLE_X64 is process-global);
- a wide-GNN config whose replicated state exceeds a per-device budget
  trains under fsdp with measured peak live bytes under that budget;
- checkpoint round-trips: shipped checkpoints restore into the
  replicated layout bit-identically with the rule engine active, and a
  sharded state save/restores with its shardings re-applied (no silent
  de-shard);
- loud contract edges before env construction (DQN/ES, sebulba+tp,
  infeasible tp factorisation, layout/mesh mismatch);
- the steady-state fused epoch stays transfer-free under
  ``jax.transfer_guard("disallow")`` with the fsdp layout.
"""
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

import jax  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

import test_fused as tf  # noqa: E402
import test_rl as trl  # noqa: E402
from ddls_tpu.models.policy import (GNNPolicy,  # noqa: E402
                                    batched_policy_apply)
from ddls_tpu.parallel import make_mesh, partition as pt  # noqa: E402
from ddls_tpu.rl import PPOConfig, PPOLearner  # noqa: E402


def _tiny_model_and_params():
    model = GNNPolicy(n_actions=trl.N_ACTIONS, out_features_msg=4,
                      out_features_hidden=8, out_features_node=4,
                      out_features_graph=4, fcnet_hiddens=(16,))
    rng = np.random.RandomState(1)
    single = jax.tree_util.tree_map(lambda x: x[0],
                                    trl._fake_obs(rng, (1,)))
    return model, model.init(jax.random.PRNGKey(0), single)


def _ppo(mesh, model, layout, **cfg):
    defaults = dict(num_sgd_iter=2, sgd_minibatch_size=8, grad_clip=0.5)
    defaults.update(cfg)
    return PPOLearner(lambda p, o: batched_policy_apply(model, p, o),
                      PPOConfig(**defaults), mesh, param_sharding=layout)


# ======================================================== engine units
def test_match_first_rule_wins_and_scalars_replicate():
    tree = {"head": {"Dense_0": {"kernel": np.zeros((4, 4)),
                                 "bias": np.zeros(4)}},
            "step": np.zeros(())}
    rules = ((r"Dense_\d+/kernel$", P("dp", None)), (r".*", P()))
    specs = pt.match_partition_rules(rules, tree)
    assert specs["head"]["Dense_0"]["kernel"] == P("dp", None)
    assert specs["head"]["Dense_0"]["bias"] == P()
    # scalar leaves replicate even under a would-match sharding rule
    specs2 = pt.match_partition_rules(((r".*", P("dp")),),
                                      {"step": np.zeros(())})
    assert specs2["step"] == P()


def test_unmatched_path_is_loud():
    with pytest.raises(ValueError, match="partition rule not found"):
        pt.match_partition_rules(((r"kernel$", P()),),
                                 {"head": {"bias": np.zeros(4)}})


def test_canonical_paths_match_runtime_tree():
    """The literal the lint cross-validation trusts == the real default
    GNNPolicy param tree (suffix-relative: learners hold the tree under
    a flax 'params' wrapper and the rules re.search suffixes)."""
    model = GNNPolicy(n_actions=5)
    rng = np.random.RandomState(0)
    single = jax.tree_util.tree_map(lambda x: x[0],
                                    trl._fake_obs(rng, (1,)))
    params = model.init(jax.random.PRNGKey(0), single)
    got = sorted(pt.tree_paths(params["params"]))
    assert got == sorted(pt.CANONICAL_PARAM_PATHS)
    assert set(pt.LARGE_KERNEL_PATHS) <= set(pt.CANONICAL_PARAM_PATHS)
    # every layout fully covers the canonical tree (match raises if not)
    for layout in pt.LAYOUTS:
        specs = pt.match_partition_rules(pt.PARTITION_RULES[layout],
                                         params)
        for lk in pt.LARGE_KERNEL_PATHS:
            node = specs["params"]
            for part in lk.split("/"):
                node = node[part]
            if layout == "replicated":
                assert node == P()
            else:
                assert any(ax is not None for ax in node), (layout, lk)


def test_mesh_for_layout_and_validation():
    m1 = pt.mesh_for_layout(8, "replicated")
    assert m1.axis_names == ("dp",) and m1.shape["dp"] == 8
    assert pt.mesh_for_layout(8, "fsdp").axis_names == ("dp",)
    mtp = pt.mesh_for_layout(8, "tp")
    assert mtp.axis_names == ("dp", "mp")
    assert (mtp.shape["dp"], mtp.shape["mp"]) == (4, 2)
    mtp4 = pt.mesh_for_layout(8, "tp", tp_size=4)
    assert (mtp4.shape["dp"], mtp4.shape["mp"]) == (2, 4)
    with pytest.raises(ValueError, match="tp_size"):
        pt.mesh_for_layout(8, "tp", tp_size=3)
    with pytest.raises(ValueError, match="param_sharding"):
        pt.validate_layout("bogus")
    # tp on a mesh without the mp axis names the fix
    with pytest.raises(ValueError, match="mesh_for_layout"):
        pt.validate_mesh_for_layout(m1, "tp")
    pt.validate_mesh_for_layout(mtp, "tp")
    pt.validate_mesh_for_layout(mtp, "replicated")


def test_divisibility_fallback_replicates_per_leaf():
    """A leaf whose named dim doesn't divide the mesh axis replicates —
    pure in shapes, so canonical checkpoints load under ANY layout."""
    mesh = make_mesh(8)
    tree = {"big": np.zeros((16, 4)), "odd": np.zeros((3, 4))}
    specs = {"big": P("dp", None), "odd": P("dp", None)}
    sh = pt.specs_to_shardings(mesh, tree, specs)
    assert sh["big"].spec == P("dp", None)
    assert sh["odd"].spec == P()


def test_replicated_state_shardings_is_single_object():
    """The default layout returns ONE replicated sharding (same jit
    cache key, same program as pre-ISSUE-19 — the bit-identity claim)."""
    from ddls_tpu.parallel.mesh import replicated_sharding

    mesh = make_mesh(8)
    sh = pt.state_shardings(mesh, {"w": np.zeros((4, 4))}, "replicated")
    assert sh == replicated_sharding(mesh)


# ================================================== learner-level (f32)
def test_fsdp_learner_shards_large_kernels_and_trains():
    model, params = _tiny_model_and_params()
    mesh = pt.mesh_for_layout(8, "fsdp")
    learner = _ppo(mesh, model, "fsdp")
    state = learner.init_state(params)
    big = state.params["params"]["logit_head"]["Dense_0"]["kernel"]
    assert big.sharding.spec == P("dp", None)
    # adam moments follow the params layout (the ZeRO-3 point): every
    # opt-state leaf shaped like the big kernel carries its spec
    mu_specs = [x.sharding.spec for x in jax.tree_util.tree_leaves(
        state.opt_state) if getattr(x, "shape", None) == big.shape]
    assert mu_specs and all(s == P("dp", None) for s in mu_specs)
    rng = np.random.RandomState(2)
    traj = trl._fake_traj(rng, T=4, B=16)
    straj, slv = learner.shard_traj(traj, rng.randn(16).astype(np.float32))
    new_state, metrics = learner.train_step(state, straj, slv,
                                            jax.random.PRNGKey(3))
    assert np.isfinite(float(metrics["total_loss"]))
    nb = new_state.params["params"]["logit_head"]["Dense_0"]["kernel"]
    assert nb.sharding.spec == P("dp", None)  # layout survives the step


def test_wide_gnn_fsdp_fits_per_device_budget():
    """ISSUE 19 acceptance: a wide-GNN config whose replicated state
    exceeds a per-device budget trains under fsdp with lower measured
    peak live bytes (numbers: docs/perf_round13.md)."""
    BUDGET = 2 * 1024 * 1024  # bytes per device
    model = GNNPolicy(n_actions=trl.N_ACTIONS, out_features_msg=64,
                      out_features_hidden=128, out_features_node=64,
                      out_features_graph=64, fcnet_hiddens=(512, 512))
    rng = np.random.RandomState(1)
    single = jax.tree_util.tree_map(lambda x: x[0],
                                    trl._fake_obs(rng, (1,)))
    params = model.init(jax.random.PRNGKey(0), single)

    repl = _ppo(pt.mesh_for_layout(8, "replicated"), model, "replicated")
    bytes_repl = pt.live_bytes_per_device(repl.init_state(params))
    assert bytes_repl > BUDGET, bytes_repl  # genuinely over budget

    mesh = pt.mesh_for_layout(8, "fsdp")
    learner = _ppo(mesh, model, "fsdp")
    state = learner.init_state(params)
    bytes_fsdp = pt.live_bytes_per_device(state)
    assert bytes_fsdp < BUDGET, bytes_fsdp
    assert bytes_fsdp < bytes_repl / 4  # dp=8 shards the big kernels
    rng = np.random.RandomState(2)
    traj = trl._fake_traj(rng, T=2, B=16)
    straj, slv = learner.shard_traj(traj, rng.randn(16).astype(np.float32))
    new_state, metrics = learner.train_step(state, straj, slv,
                                            jax.random.PRNGKey(3))
    assert np.isfinite(float(metrics["total_loss"]))
    assert pt.live_bytes_per_device(new_state) < BUDGET


# ==================================================== x64 parity driver
PARITY_DRIVER = r"""
import numpy as np
import jax
jax.config.update("jax_platforms", "cpu")
assert jax.config.read("jax_enable_x64")
assert len(jax.devices()) == 8
import test_rl as trl
from ddls_tpu.models.policy import GNNPolicy, batched_policy_apply
from ddls_tpu.parallel import partition as pt
from ddls_tpu.rl import PPOConfig, PPOLearner

# CANONICAL widths, deliberately: toy widths (4/8-wide Dense) leave
# many near-zero gradients whose adam updates (m / (sqrt(v) + eps) with
# v ~ 0) amplify layout-reassociation dust to ~1e-7 even in f64 — the
# canonical tree measures 2e-15/3e-15 under the same schedule
model = GNNPolicy(n_actions=trl.N_ACTIONS)
rng = np.random.RandomState(1)
single = jax.tree_util.tree_map(lambda x: x[0], trl._fake_obs(rng, (1,)))
params = model.init(jax.random.PRNGKey(0), single)
# f64 state AND f64 trajectory floats: at f32 the loss pipeline rounds
# at f32 and adam's eps/sqrt amplifies layout-reassociation noise to
# ~1e-6 — the parity claim loses its teeth
params = jax.tree_util.tree_map(
    lambda x: np.asarray(x, np.float64), params)
rng2 = np.random.RandomState(2)
traj = trl._fake_traj(rng2, T=4, B=16)
for k in ("logp", "values", "rewards"):
    traj[k] = traj[k].astype(np.float64)
last_values = rng2.randn(16)

def run(mesh, layout, steps=3):
    learner = PPOLearner(
        lambda p, o: batched_policy_apply(model, p, o),
        PPOConfig(num_sgd_iter=2, sgd_minibatch_size=8, grad_clip=0.5),
        mesh, param_sharding=layout)
    state = learner.init_state(params)
    straj, slv = learner.shard_traj(traj, last_values)
    for i in range(steps):
        state, _ = learner.train_step(state, straj, slv,
                                      jax.random.PRNGKey(3 + i))
    return jax.device_get(state.params)

def maxdiff(a, b):
    return max(jax.tree_util.tree_leaves(jax.tree_util.tree_map(
        lambda x, y: float(np.abs(np.asarray(x)
                                  - np.asarray(y)).max()), a, b)))

# fsdp rides the SAME 1-D dp mesh as replicated: same minibatch
# stratification, same semantics — only the all-gather/reduce-scatter
# layout differs, so agreement is bitwise-class (measured 2.9e-16)
ref = run(pt.mesh_for_layout(8, "replicated"), "replicated")
d_fsdp = maxdiff(ref, run(pt.mesh_for_layout(8, "fsdp"), "fsdp"))
assert d_fsdp < 1e-12, d_fsdp

# tp changes the mesh geometry (dp 4 x mp 2), and PPO stratifies
# minibatches PER dp shard — so the replicated baseline must run ON
# the same 2-axis mesh or the two runs shuffle different minibatches
# (different training math, not a layout effect). Measured 5.8e-15;
# the pinned 1e-9 class absorbs cross-version reassociation drift.
mesh_tp = pt.mesh_for_layout(8, "tp")
ref_tp = run(mesh_tp, "replicated")
d_tp = maxdiff(ref_tp, run(mesh_tp, "tp"))
assert d_tp < 1e-9, d_tp
print(f"PARTITION_PARITY_OK fsdp={d_fsdp:.3e} tp={d_tp:.3e}")
"""


def test_layout_parity_x64():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_ENABLE_X64"] = "1"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.pathsep.join(
        [REPO, os.path.dirname(os.path.abspath(__file__))])
    res = subprocess.run([sys.executable, "-c", PARITY_DRIVER], env=env,
                         capture_output=True, text=True, timeout=600)
    assert res.returncode == 0, (res.stdout[-4000:], res.stderr[-4000:])
    assert "PARTITION_PARITY_OK" in res.stdout, res.stdout[-2000:]


# ================================================= checkpoint round-trip
CKPT = os.path.join(REPO, "checkpoints", "ppo_price_mixed")


def test_shipped_checkpoint_replicated_roundtrip():
    """Shipped checkpoints keep loading into the replicated layout
    bit-identically with the rule engine active — and the rule tables
    fully cover the SHIPPED param tree (match raises on a gap)."""
    from ddls_tpu.parallel.mesh import place_state_tree
    from ddls_tpu.train.checkpointer import restore_train_state

    raw = restore_train_state(CKPT)
    params = raw["params"]
    for layout in pt.LAYOUTS:  # full coverage of the shipped tree
        pt.match_partition_rules(pt.PARTITION_RULES[layout], params)
    mesh = pt.mesh_for_layout(8, "replicated")
    specs = pt.match_partition_rules(pt.PARTITION_RULES["replicated"],
                                     params)
    placed = place_state_tree(
        params, pt.specs_to_shardings(mesh, params, specs))
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        jax.device_get(placed), params)


def test_sharded_state_roundtrips_with_shardings(tmp_path):
    """An fsdp-trained state save/restores through train/checkpointer.py
    with its shardings re-applied — no silent de-shard on restore."""
    from ddls_tpu.train.checkpointer import (restore_train_state,
                                             save_train_state)

    model, params = _tiny_model_and_params()
    mesh = pt.mesh_for_layout(8, "fsdp")
    learner = _ppo(mesh, model, "fsdp")
    state = learner.init_state(params)
    save_train_state(state, str(tmp_path / "ck"))
    restored = restore_train_state(str(tmp_path / "ck"), target=state)
    big = restored.params["params"]["logit_head"]["Dense_0"]["kernel"]
    assert big.sharding.spec == P("dp", None)
    assert pt.live_bytes_per_device(restored) \
        == pt.live_bytes_per_device(state)
    jax.tree_util.tree_map(
        lambda a, b: np.testing.assert_array_equal(np.asarray(a),
                                                   np.asarray(b)),
        jax.device_get(restored.params), jax.device_get(state.params))


# ===================================================== loud contract edges
def test_learner_rejects_bad_layout_and_mesh():
    model, _ = _tiny_model_and_params()
    with pytest.raises(ValueError, match="param_sharding"):
        _ppo(make_mesh(8), model, "bogus")
    # tp layout on a mesh without the mp axis names the fix
    with pytest.raises(ValueError, match="mesh_for_layout"):
        _ppo(make_mesh(8), model, "tp")
    # the legacy knob and the rule engine cannot both drive the layout
    with pytest.raises(ValueError, match="shard_params_axis"):
        PPOLearner(lambda p, o: None, PPOConfig(), make_mesh(8),
                   shard_params_axis="dp", param_sharding="fsdp")


@pytest.mark.parametrize("algo", ["apex_dqn", "es"])
def test_loop_rejects_dqn_es_before_env_construction(algo):
    from ddls_tpu.train import make_epoch_loop

    with pytest.raises(ValueError, match="param_sharding"):
        make_epoch_loop(algo, path_to_env_cls=tf.ENV_CLS, env_config={},
                        param_sharding="fsdp")


def test_loop_rejects_sebulba_tp_and_bad_tp_size():
    from ddls_tpu.train import make_epoch_loop

    with pytest.raises(ValueError, match="sebulba"):
        make_epoch_loop("ppo", path_to_env_cls=tf.ENV_CLS, env_config={},
                        loop_mode="sebulba", param_sharding="tp")
    with pytest.raises(ValueError, match="tp_size"):
        make_epoch_loop("ppo", path_to_env_cls=tf.ENV_CLS, env_config={},
                        param_sharding="tp", tp_size=3)


def test_learner_ctor_rejects_dqn_es():
    from ddls_tpu.rl.dqn import ApexDQNLearner, DQNConfig
    from ddls_tpu.rl.es import ESConfig, ESLearner

    with pytest.raises(ValueError, match="param_sharding"):
        ApexDQNLearner(lambda p, o: None, DQNConfig(), make_mesh(8),
                       param_sharding="fsdp")
    with pytest.raises(ValueError, match="param_sharding"):
        ESLearner(lambda p, o: None, ESConfig(), make_mesh(8),
                  population=4, param_sharding="tp")


# ============================================ sharded end-to-end epochs
@pytest.fixture(scope="module")
def part_dataset(tmp_path_factory):
    from ddls_tpu.graphs.synthetic import generate_pipedream_txt_files

    d = str(tmp_path_factory.mktemp("part_jobs"))
    generate_pipedream_txt_files(d, n_cnn=1, n_translation=1, seed=9)
    return d


def test_device_collector_epoch_trains_fsdp(part_dataset):
    """The sequential device-collector loop trains under fsdp: the
    collector's forwards consume the learner's layout via explicit
    in_shardings (no implicit per-collect gather at dispatch)."""
    from ddls_tpu.train import make_epoch_loop

    algo = {"train_batch_size": 16, "sgd_minibatch_size": 8,
            "num_sgd_iter": 2, "num_workers": 8,
            "device_collector": True}
    loop = make_epoch_loop(
        "ppo", path_to_env_cls=tf.ENV_CLS,
        env_config=tf._env_config(part_dataset, horizon=6e2),
        model=tf._TINY_MODEL, algo_config=algo, num_envs=8,
        rollout_length=2, n_devices=8, use_parallel_envs=False,
        evaluation_interval=None, seed=0, loop_mode="sequential",
        param_sharding="fsdp")
    try:
        big = loop.state.params["params"]["logit_head"]["Dense_0"]["kernel"]
        assert big.sharding.spec == P("dp", None)
        before = jax.device_get(loop.state.params)
        for _ in range(2):
            r = loop.run()
            assert np.isfinite(r["learner"]["total_loss"])
        after = jax.device_get(loop.state.params)
        moved = jax.tree_util.tree_map(
            lambda a, b: float(np.abs(np.asarray(a)
                                      - np.asarray(b)).max()),
            before, after)
        assert max(jax.tree_util.tree_leaves(moved)) > 0
        nb = loop.state.params["params"]["logit_head"]["Dense_0"]["kernel"]
        assert nb.sharding.spec == P("dp", None)
    finally:
        loop.close()


def test_fused_epoch_transfer_free_fsdp(part_dataset):
    """ISSUE 19 acceptance: the steady-state epoch stays transfer-free
    under ``jax.transfer_guard("disallow")`` with a sharded layout (the
    fused scan carries the fsdp state in its own shardings)."""
    loop = tf._make_fused_loop(
        part_dataset, metrics_sync_interval=3, param_sharding="fsdp",
        env_config=tf._env_config(part_dataset, horizon=6e2))
    try:
        big = loop.state.params["params"]["logit_head"]["Dense_0"]["kernel"]
        assert big.sharding.spec == P("dp", None)
        r1 = loop.run()  # warm: compile + first-use constant transfers
        with jax.transfer_guard("disallow"):
            r2 = loop.run()
        for r in (r1, r2):
            assert np.isfinite(r["learner"]["total_loss"])
        nb = loop.state.params["params"]["logit_head"]["Dense_0"]["kernel"]
        assert nb.sharding.spec == P("dp", None)
    finally:
        loop.close()
