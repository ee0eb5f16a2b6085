"""The device tables carry each job type's observation on the smallest
rung of the halving ladder that holds the bank's largest graph
(`sim/jax_env.py:fit_obs_tables`, PR 38).

The rule: the rung each benchmark cell lands on (sizes counted on the
composed trees), the SAME arrays back where no rung under the pad fits,
never below the largest graph nor above the pad, and the cut rows equal
to `envs/obs.py:pad_obs_to` bit for bit — on synthetic tables and on a
live env through `_kernel_obs`.

Equivalence: the policy's logits, values and parameter gradients on
fitted observations against full-pad ones under both aggregator forms,
a tiny fused epoch with and without the fit, and the lowered epoch
program of a bank that fits no rung, text for text.
"""
import os
import sys

import numpy as np
import pytest

import jax
import jax.numpy as jnp

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from ddls_tpu.envs.obs import pad_obs_to  # noqa: E402
from ddls_tpu.models.policy import (GNNPolicy,  # noqa: E402
                                    batched_policy_apply)
from ddls_tpu.ops import segment as segment_ops  # noqa: E402
from ddls_tpu.serve.bucketing import default_buckets  # noqa: E402
from ddls_tpu.sim import jax_env  # noqa: E402
from ddls_tpu.sim.jax_env import (OBS_PAD_KEYS, fit_obs_tables,  # noqa: E402
                                  obs_pads)
from test_fused import _env_config, _make_fused_loop  # noqa: E402

SYNTHETIC = [(30, 31), (30, 37), (24, 31), (20, 21), (26, 29)]
#: cell: (each job type's nodes x edges, the configured pad, the rung the
#: tables carry) — counted on the composed trees of `benchmarks/configs`
CELLS = {
    "ramp32_dev": (SYNTHETIC, (150, 512), (38, 128)),
    "ramp32_load32": (SYNTHETIC, (150, 512), (38, 128)),
    "mimo_ramp32": ([(114, 165)] * 4, (150, 256), (150, 256)),
    "glm5_ramp32": ([(218, 347)] * 4, (250, 512), (250, 512)),
    "olmoe_ramp32": ([(262, 389)] * 4, (300, 512), (300, 512)),
    "trinity_ramp32": ([(570, 877)] * 4, (600, 1024), (600, 1024)),
    # two graph sizes of ONE model: the pad holds the larger
    "sala_ramp32": ([(486, 709)] * 2 + [(454, 645)] * 2, (500, 768),
                    (500, 768)),
}
N_ACTIONS = 9
#: the fields of an observation the tables hold a row of, a job type
TABLE_KEYS = OBS_PAD_KEYS + ("node_split", "edge_split", "graph_features")


def _tables(rng, sizes, pad):
    """Observation tables as `build_obs_tables` stacks them: real rows
    first, zeros after, one row a job type."""
    n_pad, e_pad = pad
    ot = {"node_features": np.zeros((len(sizes), n_pad, 5), np.float32),
          "edge_features": np.zeros((len(sizes), e_pad, 2), np.float32),
          "edges_src": np.zeros((len(sizes), e_pad), np.int32),
          "edges_dst": np.zeros((len(sizes), e_pad), np.int32),
          "node_split": np.array([[n] for n, _ in sizes], np.int32),
          "edge_split": np.array([[e] for _, e in sizes], np.int32),
          "graph_features": rng.uniform(
              0, 1, (len(sizes), 17 + N_ACTIONS)).astype(np.float32),
          "with_prices": False}
    for i, (n, e) in enumerate(sizes):
        ot["node_features"][i, :n] = rng.uniform(0.1, 1, (n, 5))
        ot["edge_features"][i, :e] = rng.uniform(0.1, 1, (e, 2))
        ot["edges_src"][i, :e] = rng.integers(0, n, e)
        ot["edges_dst"][i, :e] = rng.integers(0, n, e)
    return ot


def _row(ot, i):
    return {k: ot[k][i] for k in TABLE_KEYS}


# ============================================================== the rule
@pytest.mark.parametrize("cell", sorted(CELLS))
def test_each_cell_lands_on_its_rung(cell):
    sizes, pad, rung = CELLS[cell]
    ot = _tables(np.random.default_rng(len(cell)), sizes, pad)
    fitted = fit_obs_tables(ot)
    assert obs_pads(fitted) == rung
    if rung == pad:
        # no rung under the pad holds the graph: the SAME arrays, so
        # the same program and the same trajectories as without the fit
        assert fitted is ot
    else:
        assert all(fitted[k] is v for k, v in ot.items()
                   if k not in OBS_PAD_KEYS)
        for i in range(len(sizes)):
            want = pad_obs_to(_row(ot, i), *rung)
            for key in OBS_PAD_KEYS:
                assert fitted[key][i].dtype == want[key].dtype
                assert np.array_equal(fitted[key][i], want[key]), key


@pytest.mark.parametrize("seed", range(8))
def test_the_rung_holds_the_largest_graph_under_the_pad(seed):
    rng = np.random.default_rng(seed)
    pad = (int(rng.integers(8, 700)), int(rng.integers(8, 1200)))
    sizes = [(int(rng.integers(1, pad[0] + 1)),
              int(rng.integers(0, pad[1] + 1)))
             for _ in range(int(rng.integers(1, 6)))]
    if seed % 2:
        # small graphs under a wide pad: the deepest rungs
        sizes = [(max(n // 5, 1), e // 5) for n, e in sizes]
    ot = _tables(rng, sizes, pad)
    fitted = fit_obs_tables(ot)
    n, e = obs_pads(fitted)
    n_max = max(s[0] for s in sizes)
    e_max = max(s[1] for s in sizes)
    assert n_max <= n <= pad[0] and e_max <= e <= pad[1]
    ladder = default_buckets(*pad)
    assert (n, e) in ladder
    assert not any(bn >= n_max and be >= e_max
                   for bn, be in ladder if (bn, be) < (n, e))
    for key in OBS_PAD_KEYS:
        assert fitted[key].shape[1] == (n if key == "node_features"
                                        else e)
    for i, (gn, ge) in enumerate(sizes):
        # every real row kept, every real endpoint inside the node rung
        assert np.array_equal(fitted["node_features"][i, :gn],
                              ot["node_features"][i, :gn])
        assert np.array_equal(fitted["edge_features"][i, :ge],
                              ot["edge_features"][i, :ge])
        for key in ("edges_src", "edges_dst"):
            assert np.array_equal(fitted[key][i, :ge], ot[key][i, :ge])
            assert (fitted[key][i] < n).all()
    assert fitted["node_split"] is ot["node_split"]
    assert fitted["edge_split"] is ot["edge_split"]


def test_the_ladder_is_the_servers(monkeypatch):
    """One halving rule in the package: the fit asks
    `serve/bucketing.py:default_buckets` with the tables' pads."""
    from ddls_tpu.serve import bucketing

    asked = []

    def ladder(max_nodes, max_edges):
        asked.append((max_nodes, max_edges))
        return [(33, 40), (150, 512)]

    monkeypatch.setattr(bucketing, "default_buckets", ladder)
    ot = _tables(np.random.default_rng(0), SYNTHETIC, (150, 512))
    assert obs_pads(fit_obs_tables(ot)) == (33, 40)
    assert asked == [(150, 512)]


@pytest.fixture(scope="module")
def dataset(tmp_path_factory):
    from ddls_tpu.graphs.synthetic import generate_pipedream_txt_files

    d = str(tmp_path_factory.mktemp("obs_fit_jobs"))
    # two job types of 18 x 19 and 16 x 15 nodes x edges: under a
    # 64 x 128 pad the rung (32, 64); under 32 x 64 no rung
    generate_pipedream_txt_files(d, n_cnn=1, n_translation=1, seed=9)
    return d


def _wide_pad_config(dataset):
    cfg = _env_config(dataset, horizon=6e2)
    cfg["pad_obs_kwargs"] = {"max_nodes": 64, "max_edges": 128}
    return cfg


def test_kernel_obs_on_fitted_tables_is_the_host_encode_repadded(dataset):
    """`_kernel_obs` reading fitted tables == the host encoder's
    observation of the live env, re-padded to the rung with
    `pad_obs_to`: every field, bit for bit (x64, as the episode parity
    drivers compare them)."""
    from ddls_tpu.envs import RampJobPartitioningEnvironment
    from ddls_tpu.sim.jax_env import (_kernel_obs, build_episode_tables,
                                      build_obs_tables)

    env = RampJobPartitioningEnvironment(**_wide_pad_config(dataset))
    obs = env.reset(seed=3)
    et = build_episode_tables(env)
    configured = build_obs_tables(env, et)
    assert obs_pads(configured) == (64, 128)
    ot = fit_obs_tables(configured)
    assert obs_pads(ot) == (32, 64)
    checked = set()
    done = False
    with jax.enable_x64(True):
        while not done and len(checked) < 12:
            job = next(iter(env.cluster.job_queue.jobs.values()))
            jtype = et.types.index(job.details["model"])
            kobs = _kernel_obs(
                ot, et, jnp.int32(jtype),
                jnp.float64(job.max_acceptable_jct_frac),
                jnp.float64(job.num_training_steps),
                jnp.int32(len(env.cluster.mounted_workers)),
                jnp.int32(len(env.cluster.jobs_running)))
            want = pad_obs_to(obs, 32, 64)
            assert set(kobs) == set(want)
            for key, b in want.items():
                a, b = np.asarray(kobs[key]), np.asarray(b)
                assert a.shape == b.shape, key
                assert np.array_equal(a.astype(b.dtype), b), key
            checked.add((jtype, len(env.cluster.mounted_workers)))
            obs, _, done, _ = env.step(
                int(np.flatnonzero(obs["action_mask"])[-1]))
    assert len({j for j, _ in checked}) == len(et.types)
    assert any(occupied for _, occupied in checked)


# ============================================================ equivalence
def _force_form(monkeypatch, form):
    monkeypatch.setattr(segment_ops, "aggregate_form",
                        lambda platform, n_nodes, n_edges: form)


def _obs_batch(rng, sizes, pad, garbage):
    """A batch of observations off synthetic tables, with ``garbage`` on
    every padded edge row's features (the masks must drop them) and
    padded edges pointing at real nodes."""
    ot = _tables(rng, sizes, pad)
    for i, (n, e) in enumerate(sizes):
        ot["edge_features"][i, e:] = garbage
        ot["edges_src"][i, e:] = rng.integers(0, n, pad[1] - e)
        ot["edges_dst"][i, e:] = rng.integers(0, n, pad[1] - e)
    mask = np.ones((len(sizes), N_ACTIONS), np.int32)
    mask[:, 5] = 0
    extra = {"action_set": np.tile(np.arange(N_ACTIONS, dtype=np.int32),
                                   (len(sizes), 1)),
             "action_mask": mask}

    def batch(tables):
        return jax.tree.map(jnp.asarray, {
            **{k: tables[k] for k in TABLE_KEYS}, **extra})

    return batch(ot), batch(fit_obs_tables(ot))


@pytest.fixture(scope="module")
def model_params():
    model = GNNPolicy(n_actions=N_ACTIONS)
    ot = _tables(np.random.default_rng(2), [(5, 6)], (8, 12))
    obs = {**{k: ot[k][0] for k in TABLE_KEYS},
           "action_set": np.arange(N_ACTIONS, dtype=np.int32),
           "action_mask": np.ones(N_ACTIONS, np.int32)}
    return model, model.init(jax.random.PRNGKey(0),
                             jax.tree.map(jnp.asarray, obs))


@pytest.mark.parametrize("form", ["segment", "dense"])
@pytest.mark.parametrize("garbage", [1e4, np.inf])
def test_fitted_policy_is_full_pad_policy(model_params, form, garbage,
                                          monkeypatch):
    """`flat_batched` logits, values and every parameter's gradient on
    fitted observations against the full 150 x 512 pad, garbage on the
    padded edge rows of both. An inf there is dropped from every sum of
    the forward; a Dense's backward multiplies it by the row's zero
    cotangent, so the gradients are compared under the finite garbage."""
    model, params = model_params
    _force_form(monkeypatch, form)
    full, fitted = _obs_batch(np.random.default_rng(5), SYNTHETIC,
                              (150, 512), garbage)
    assert fitted["node_features"].shape[1:] == (38, 5)
    assert fitted["edge_features"].shape[1:] == (128, 2)

    def run(obs):
        def loss(p):
            logits, values = batched_policy_apply(model, p, obs)
            logp = jax.nn.log_softmax(logits)
            return jnp.sum(logp[:, 3]) + jnp.sum(values ** 2), (
                logits, values)
        return jax.jit(jax.value_and_grad(loss, has_aux=True))(params)

    (_, out_full), grad_full = run(full)
    (_, out_fit), grad_fit = run(fitted)
    for a, b in zip(out_full, out_fit, strict=True):
        assert np.isfinite(np.asarray(a)).all()
        np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-6)
    if np.isfinite(garbage):
        assert jax.tree.structure(grad_fit) == jax.tree.structure(params)
        for a, b in zip(jax.tree.leaves(grad_full),
                        jax.tree.leaves(grad_fit), strict=True):
            assert np.isfinite(np.asarray(a)).all()
            np.testing.assert_allclose(a, b, atol=1e-6, rtol=1e-5)


def _two_epochs(loop):
    """Two fused epochs off fixed keys: the drained episode trace of
    each and the parameters after."""
    rngs = (jax.random.PRNGKey(1), jax.random.PRNGKey(2))
    state, traces = loop.state, []
    for _ in range(2):
        state, rngs, _, ep = loop.fused.fused_epoch(state, rngs)
        traces.append(jax.device_get(ep))
    return traces, jax.device_get(state.params)


@pytest.mark.parametrize("form", ["segment", "dense"])
def test_fused_epoch_with_fitted_tables_is_the_unfitted_epoch(
        dataset, form, monkeypatch):
    """The same actions and the same episode trace, update after
    update, whether the tables carry the rung or the configured pad."""
    from ddls_tpu.rl.fused import EPISODE_TRACE_KEYS

    _force_form(monkeypatch, form)
    runs = {}
    for fit in (True, False):
        if not fit:
            monkeypatch.setattr(jax_env, "fit_obs_tables", lambda ot: ot)
        loop = _make_fused_loop(dataset,
                                env_config=_wide_pad_config(dataset))
        try:
            runs[fit] = (obs_pads(loop.fused.ot), *_two_epochs(loop))
        finally:
            loop.close()
    assert runs[True][0] == (32, 64) and runs[False][0] == (64, 128)
    for with_fit, without in zip(runs[True][1], runs[False][1]):
        assert set(with_fit) == set(EPISODE_TRACE_KEYS)
        assert with_fit["la_trips"].sum() > 0
        for key in EPISODE_TRACE_KEYS:
            assert np.array_equal(with_fit[key], without[key]), key
    for a, b in zip(jax.tree.leaves(runs[True][2]),
                    jax.tree.leaves(runs[False][2]), strict=True):
        np.testing.assert_allclose(a, b, atol=1e-5, rtol=1e-4)


def test_a_bank_that_fits_no_rung_lowers_the_same_program(dataset,
                                                          monkeypatch):
    """18 x 19 under 32 x 64 fits no rung: the tables are the
    configured ones and the lowered epoch program is the same text with
    and without the fit — the large cells' case."""
    texts = []
    for fit in (True, False):
        if not fit:
            monkeypatch.setattr(jax_env, "fit_obs_tables", lambda ot: ot)
        loop = _make_fused_loop(dataset)
        try:
            assert obs_pads(loop.fused.ot) == (32, 64)
            texts.append(loop.fused.lower(loop.state).as_text())
        finally:
            loop.close()
    assert texts[0] == texts[1]


def test_startup_gauges_say_what_the_tables_carry(dataset):
    """The four pad gauges in the `[startup]` line's registry, and the
    incidence gauge at the carried pads (minibatch 8)."""
    from ddls_tpu.sim.jax_env import OBS_PAD_GAUGES
    from ddls_tpu.telemetry import startup

    loop = _make_fused_loop(dataset, env_config=_wide_pad_config(dataset))
    try:
        gauges = [startup.registry().gauge(name).value
                  for name in OBS_PAD_GAUGES]
        assert gauges == [32, 64, 64, 128]
        assert startup.registry().gauge(
            "gnn.aggregate.incidence_elems").value == 8 * 32 * 64
        assert all(f'"{name}"' in startup.report()
                   for name in OBS_PAD_GAUGES)
    finally:
        loop.close()
