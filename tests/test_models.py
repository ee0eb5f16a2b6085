"""L6 tests: segment ops + GNN policy (forward shapes, masking, padding
invariance, jit/vmap)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from ddls_tpu.models import GNN, GNNPolicy, batched_policy_apply
from ddls_tpu.ops import masked_mean, masked_segment_mean, masked_segment_sum

N_ACTIONS = 9
MAX_NODES = 12
MAX_EDGES = (MAX_NODES * (MAX_NODES - 1)) // 2


def _rand_obs(rng, n=5, m=6, max_nodes=MAX_NODES, max_edges=MAX_EDGES):
    node_features = np.zeros((max_nodes, 5), np.float32)
    node_features[:n] = rng.uniform(0, 1, (n, 5))
    edge_features = np.zeros((max_edges, 2), np.float32)
    edge_features[:m] = rng.uniform(0, 1, (m, 2))
    src = np.zeros(max_edges, np.int32)
    dst = np.zeros(max_edges, np.int32)
    src[:m] = rng.integers(0, n, m)
    dst[:m] = rng.integers(0, n, m)
    mask = np.ones(N_ACTIONS, np.int32)
    mask[5] = 0
    return {
        "action_set": np.arange(N_ACTIONS, dtype=np.int32),
        "action_mask": mask,
        "node_features": node_features,
        "edge_features": edge_features,
        "graph_features": rng.uniform(0, 1, (17 + N_ACTIONS,)).astype(
            np.float32),
        "edges_src": src,
        "edges_dst": dst,
        "node_split": np.array([n], np.int32),
        "edge_split": np.array([m], np.int32),
    }


class TestSegmentOps:
    def test_masked_segment_sum(self):
        data = jnp.array([[1.0], [2.0], [4.0], [100.0]])
        seg = jnp.array([0, 0, 1, 0])
        mask = jnp.array([True, True, True, False])
        out = masked_segment_sum(data, seg, mask, 3)
        np.testing.assert_allclose(out, [[3.0], [4.0], [0.0]])

    def test_masked_segment_mean_with_self(self):
        data = jnp.array([[2.0], [4.0]])
        seg = jnp.array([0, 0])
        mask = jnp.array([True, True])
        extra = jnp.array([[6.0], [5.0]])
        out = masked_segment_mean(data, seg, mask, 2, extra=extra)
        # node 0: mean(6, 2, 4) = 4; node 1: mean(5) = 5 (no in-edges)
        np.testing.assert_allclose(out, [[4.0], [5.0]])

    def test_masked_mean(self):
        data = jnp.array([[1.0, 2.0], [3.0, 4.0], [99.0, 99.0]])
        mask = jnp.array([True, True, False])
        np.testing.assert_allclose(masked_mean(data, mask), [2.0, 3.0])


class TestGNN:
    def test_forward_shape_and_padding_mask(self):
        rng = np.random.default_rng(0)
        obs = _rand_obs(rng, n=4, m=5)
        model = GNN()
        params = model.init(
            jax.random.PRNGKey(0),
            jnp.asarray(obs["node_features"]),
            jnp.asarray(obs["edge_features"]),
            jnp.asarray(obs["edges_src"]), jnp.asarray(obs["edges_dst"]),
            jnp.arange(MAX_NODES) < 4, jnp.arange(MAX_EDGES) < 5)
        out = model.apply(params,
                          jnp.asarray(obs["node_features"]),
                          jnp.asarray(obs["edge_features"]),
                          jnp.asarray(obs["edges_src"]),
                          jnp.asarray(obs["edges_dst"]),
                          jnp.arange(MAX_NODES) < 4,
                          jnp.arange(MAX_EDGES) < 5)
        assert out.shape == (MAX_NODES, 16)
        # padded nodes produce exactly zero embeddings
        np.testing.assert_allclose(out[4:], 0.0)

    def test_padding_invariance(self):
        """Growing the pad region must not change real-node embeddings."""
        rng = np.random.default_rng(1)
        small = _rand_obs(rng, n=4, m=5, max_nodes=8, max_edges=10)
        model = GNN()
        args_small = (jnp.asarray(small["node_features"]),
                      jnp.asarray(small["edge_features"]),
                      jnp.asarray(small["edges_src"]),
                      jnp.asarray(small["edges_dst"]),
                      jnp.arange(8) < 4, jnp.arange(10) < 5)
        params = model.init(jax.random.PRNGKey(0), *args_small)
        out_small = model.apply(params, *args_small)

        big = {k: np.copy(v) for k, v in small.items()}
        big["node_features"] = np.zeros((20, 5), np.float32)
        big["node_features"][:8] = small["node_features"]
        big["edge_features"] = np.zeros((40, 2), np.float32)
        big["edge_features"][:10] = small["edge_features"]
        for k in ("edges_src", "edges_dst"):
            arr = np.zeros(40, np.int32)
            arr[:10] = small[k]
            big[k] = arr
        out_big = model.apply(params,
                              jnp.asarray(big["node_features"]),
                              jnp.asarray(big["edge_features"]),
                              jnp.asarray(big["edges_src"]),
                              jnp.asarray(big["edges_dst"]),
                              jnp.arange(20) < 4, jnp.arange(40) < 5)
        np.testing.assert_allclose(out_small[:4], out_big[:4], atol=1e-5)


class TestGNNPolicy:
    @pytest.fixture(scope="class")
    def model_params(self):
        rng = np.random.default_rng(2)
        obs = _rand_obs(rng)
        model = GNNPolicy(n_actions=N_ACTIONS)
        params = model.init(jax.random.PRNGKey(0),
                            jax.tree.map(jnp.asarray, obs))
        return model, params

    def test_forward_shapes(self, model_params):
        model, params = model_params
        obs = _rand_obs(np.random.default_rng(3))
        logits, value = model.apply(params, jax.tree.map(jnp.asarray, obs))
        assert logits.shape == (N_ACTIONS,)
        assert value.shape == ()

    def test_action_masking(self, model_params):
        model, params = model_params
        obs = _rand_obs(np.random.default_rng(4))
        logits, _ = model.apply(params, jax.tree.map(jnp.asarray, obs))
        assert logits[5] <= jnp.finfo(jnp.float32).min / 2
        probs = jax.nn.softmax(logits)
        assert probs[5] == 0.0
        assert np.isfinite(np.asarray(logits[np.asarray(
            obs["action_mask"], bool)])).all()

    def test_batched_apply_jit(self, model_params):
        model, params = model_params
        rng = np.random.default_rng(5)
        batch = [_rand_obs(rng, n=int(rng.integers(2, 8))) for _ in range(4)]
        stacked = {k: jnp.stack([jnp.asarray(o[k]) for o in batch])
                   for k in batch[0]}
        fn = jax.jit(lambda p, o: batched_policy_apply(model, p, o))
        logits, values = fn(params, stacked)
        assert logits.shape == (4, N_ACTIONS)
        assert values.shape == (4,)
        # batching must agree with per-sample application (loose tolerance:
        # jit+vmap lowers the segment ops differently, reassociating f32 sums)
        solo_logits, solo_value = model.apply(
            params, jax.tree.map(jnp.asarray, batch[2]))
        np.testing.assert_allclose(logits[2], solo_logits, atol=5e-3)
        np.testing.assert_allclose(values[2], solo_value, atol=5e-3)

    def test_flat_batched_matches_vmapped(self, model_params):
        """batched_policy_apply runs the flat-rows forward (here, on a
        CPU, with the aggregation's index form over one flattened
        mega-graph); it computes the same sums as vmapping the
        single-sample __call__ (every parameterised op is row-wise;
        segment sums keep per-node edge order), so outputs agree to f32
        reassociation tolerance — XLA
        may tile the row-wise matmuls differently per shape, so exact
        bitwise equality only holds at some shapes. Masked (-inf) entries
        must agree exactly."""
        from ddls_tpu.models.policy import vmapped_policy_apply

        model, params = model_params
        rng = np.random.default_rng(7)
        batch = [_rand_obs(rng, n=int(rng.integers(2, 8))) for _ in range(6)]
        stacked = {k: jnp.stack([jnp.asarray(o[k]) for o in batch])
                   for k in batch[0]}
        lo_f, va_f = jax.jit(
            lambda p, o: batched_policy_apply(model, p, o))(params, stacked)
        lo_v, va_v = jax.jit(
            lambda p, o: vmapped_policy_apply(model, p, o))(params, stacked)
        assert bool(jnp.all(jnp.isfinite(lo_f) == jnp.isfinite(lo_v)))
        np.testing.assert_allclose(
            np.where(np.isfinite(lo_f), lo_f, 0.0),
            np.where(np.isfinite(lo_v), lo_v, 0.0), atol=1e-5)
        np.testing.assert_allclose(va_f, va_v, atol=1e-5)

    def test_grads_flow(self, model_params):
        model, params = model_params
        obs = jax.tree.map(jnp.asarray, _rand_obs(np.random.default_rng(6)))

        def loss(p):
            logits, value = model.apply(p, obs)
            return jnp.sum(jax.nn.log_softmax(logits)[0]) + value ** 2

        grads = jax.grad(loss)(params)
        leaves = jax.tree.leaves(grads)
        assert all(np.isfinite(np.asarray(g)).all() for g in leaves)
        assert any(np.abs(np.asarray(g)).sum() > 0 for g in leaves)


# ----------------------------------------------------------------------
# The aggregation's two lowerings (ops/segment.py): the index form the
# CPU runs and the per-graph incidence contractions the TPU runs compute
# the same sums. The rule picks the index form here, so the dense
# functions are called directly, and whole-model tests steer the rule
# (`_force_form`) — nothing of the program configures it.
from ddls_tpu.ops import segment as segment_ops


def _graphs(rng, fills, n_pad, e_pad, no_in_edge=None):
    """A batch of padded graphs, one per ``(n_real, m_real)`` of
    ``fills``; padded edges point at node 0 (the observation's pad);
    ``no_in_edge`` names a real node that no edge may enter."""
    B = len(fills)
    src = np.zeros((B, e_pad), np.int32)
    dst = np.zeros((B, e_pad), np.int32)
    for b, (n, m) in enumerate(fills):
        if n and m:
            src[b, :m] = rng.integers(0, n, m)
            into = [v for v in range(n) if v != no_in_edge] or [0]
            dst[b, :m] = rng.choice(into, m)
    n_real = np.array([f[0] for f in fills], np.int32)
    m_real = np.array([f[1] for f in fills], np.int32)
    return {"edges_src": src, "edges_dst": dst,
            "node_split": n_real[:, None], "edge_split": m_real[:, None],
            "node_mask": np.arange(n_pad) < n_real[:, None],
            "edge_mask": np.arange(e_pad) < m_real[:, None]}


#: id -> (fills [(real nodes, real edges)], node pad, edge pad)
AGGREGATE_CASES = {
    "ragged": ([(5, 9), (2, 1), (8, 20), (3, 7)], 8, 20),
    "one_graph_full": ([(6, 15)], 6, 15),
    "no_real_edge": ([(4, 0), (5, 6)], 7, 10),
    "fully_padded_sample": ([(0, 0), (6, 11), (0, 0)], 6, 12),
    "pads_point_at_node_0": ([(7, 2), (7, 3)], 9, 30),
    "wide_pad": ([(3, 4), (12, 40), (1, 0)], 40, 64),
    "shipped_150x512": ([(26, 45), (150, 512), (9, 8), (31, 60)], 150,
                        512),
}


def _obs_batch(rng, fills, n_pad, e_pad, no_in_edge=None):
    g = _graphs(rng, fills, n_pad, e_pad, no_in_edge)
    B = len(fills)
    nf = rng.uniform(0, 1, (B, n_pad, 5)).astype(np.float32)
    ef = rng.uniform(0, 1, (B, e_pad, 2)).astype(np.float32)
    mask = np.ones((B, N_ACTIONS), np.int32)
    mask[:, 5] = 0
    return jax.tree.map(jnp.asarray, {
        "action_set": np.tile(np.arange(N_ACTIONS, dtype=np.int32), (B, 1)),
        "action_mask": mask,
        "node_features": nf * g["node_mask"][..., None],
        "edge_features": ef * g["edge_mask"][..., None],
        "graph_features": rng.uniform(
            0, 1, (B, 17 + N_ACTIONS)).astype(np.float32),
        "edges_src": g["edges_src"], "edges_dst": g["edges_dst"],
        "node_split": g["node_split"], "edge_split": g["edge_split"]})


def _force_form(monkeypatch, form):
    monkeypatch.setattr(segment_ops, "aggregate_form",
                        lambda platform, n_nodes, n_edges: form)


def _assert_close(a, b, atol=1e-5):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b), strict=True):
        x, y = np.asarray(x), np.asarray(y)
        assert (np.isfinite(x) == np.isfinite(y)).all()
        np.testing.assert_allclose(np.where(np.isfinite(x), x, 0.0),
                                   np.where(np.isfinite(y), y, 0.0),
                                   atol=atol, rtol=1e-5)


class TestAggregateForms:
    @pytest.mark.parametrize("platform,n_nodes,n_edges,form", [
        ("tpu", 150, 512, "dense"), ("tpu", 300, 512, "dense"),
        ("tpu", 250, 512, "dense"), ("tpu", 12, 66, "dense"),
        ("tpu", 8192, 512, "dense"), ("tpu", 8193, 512, "segment"),
        ("tpu", 2048, 4096, "segment"),
        ("cpu", 150, 512, "segment"), ("cpu", 2, 1, "segment"),
        ("gpu", 150, 512, "segment")])
    def test_the_rule_is_platform_and_pad(self, platform, n_nodes,
                                          n_edges, form):
        assert segment_ops.aggregate_form(platform, n_nodes,
                                          n_edges) == form
        assert (n_nodes * n_edges <= segment_ops.DENSE_MAX_CELLS) == (
            segment_ops.aggregate_form("tpu", n_nodes, n_edges) == "dense")

    def test_this_backend_runs_the_rules_form(self, monkeypatch):
        """`edge_aggregator` asks the rule with the backend's platform
        and the static pad, and builds what it answers."""
        g = _graphs(np.random.default_rng(0), [(4, 5)], 6, 8)
        args = (jnp.asarray(g["edges_src"][0]),
                jnp.asarray(g["edges_dst"][0]),
                jnp.asarray(g["edge_mask"][0]), 6)
        x = jnp.ones((6, 3))
        asked = []

        def rule(platform, n_nodes, n_edges):
            asked.append((platform, n_nodes, n_edges))
            return "dense"

        def traced():   # a fresh function: a trace is cached by it
            return str(jax.make_jaxpr(
                lambda x: segment_ops.edge_aggregator(*args).gather_src(x)
            )(x))

        assert "gather" in traced()              # a CPU: the index form
        monkeypatch.setattr(segment_ops, "aggregate_form", rule)
        assert "gather" not in traced()
        assert asked == [(jax.default_backend(), 6, 8)]

    @pytest.mark.parametrize("case,no_in_edge", [
        (c, n) for c in sorted(AGGREGATE_CASES)
        for n in ((None,) if c in ("shipped_150x512", "wide_pad")
                  else (None, 1))])
    def test_dense_is_segment_values_and_gradients(self, case, no_in_edge):
        """Both operations of a round, dense against segment, on a
        batch: the values (a padded edge's gathered row is the one
        difference the contract allows, and the mean drops it) and the
        gradients to every float input."""
        fills, n_pad, e_pad = AGGREGATE_CASES[case]
        rng = np.random.default_rng(len(case))
        g = _graphs(rng, fills, n_pad, e_pad, no_in_edge)
        B = len(fills)
        graph = (jnp.asarray(g["edges_src"]), jnp.asarray(g["edges_dst"]),
                 jnp.asarray(g["edge_mask"]), n_pad)
        real_edge = jnp.asarray(g["edge_mask"]).reshape(-1)[:, None]
        x = jnp.asarray(rng.normal(size=(B * n_pad, 4)), jnp.float32)
        data = jnp.asarray(rng.normal(size=(B * e_pad, 6)), jnp.float32)
        extra = jnp.asarray(rng.normal(size=(B * n_pad, 6)), jnp.float32)
        w = jnp.asarray(rng.normal(size=(B * e_pad, 4)), jnp.float32)
        v = jnp.asarray(rng.normal(size=(B * n_pad, 6)), jnp.float32)

        def both(build):
            def f(x, data, extra):
                agg = build(*graph)
                rows = agg.gather_src(x) * real_edge
                mean = agg.mean_to_dst(data, extra)
                alone = agg.mean_to_dst(data)
                return (jnp.sum(rows * w) + jnp.sum(mean * v)
                        + jnp.sum(alone * v), (rows, mean, alone))
            return jax.jit(jax.value_and_grad(f, argnums=(0, 1, 2),
                                              has_aux=True))(x, data, extra)

        seg = both(segment_ops.segment_aggregator)
        den = both(segment_ops.dense_aggregator)
        _assert_close(seg, den)
        mean, alone = np.asarray(den[0][1][1]), np.asarray(den[0][1][2])
        if no_in_edge is not None:
            for b, (n, m) in enumerate(fills):
                if n > no_in_edge:   # an empty mailbox: the self-message
                    row = b * n_pad + no_in_edge
                    np.testing.assert_array_equal(mean[row], extra[row])
                    np.testing.assert_array_equal(alone[row], 0.0)
        for b, (n, m) in enumerate(fills):
            if not m:               # no real edge: nothing arrives
                rows = slice(b * n_pad, (b + 1) * n_pad)
                np.testing.assert_array_equal(mean[rows], extra[rows])

    def test_one_graph_without_a_batch_axis(self):
        fills, n_pad, e_pad = AGGREGATE_CASES["ragged"]
        rng = np.random.default_rng(3)
        g = _graphs(rng, fills, n_pad, e_pad)
        x = jnp.asarray(rng.normal(size=(n_pad, 4)), jnp.float32)
        data = jnp.asarray(rng.normal(size=(e_pad, 6)), jnp.float32)
        for b in range(len(fills)):
            graph = (jnp.asarray(g["edges_src"][b]),
                     jnp.asarray(g["edges_dst"][b]),
                     jnp.asarray(g["edge_mask"][b]), n_pad)
            seg = segment_ops.segment_aggregator(*graph)
            den = segment_ops.dense_aggregator(*graph)
            real = g["edge_mask"][b][:, None]
            _assert_close(seg.gather_src(x) * real, den.gather_src(x) * real)
            _assert_close(seg.mean_to_dst(data, x @ jnp.ones((4, 6))),
                          den.mean_to_dst(data, x @ jnp.ones((4, 6))))

    def test_a_padded_edges_value_never_enters_a_sum(self):
        """inf on a padded edge row: 0 x inf would be nan in a bare
        contraction; both forms drop the row before they sum."""
        g = _graphs(np.random.default_rng(5), [(3, 2)], 4, 6)
        graph = (jnp.asarray(g["edges_src"]), jnp.asarray(g["edges_dst"]),
                 jnp.asarray(g["edge_mask"]), 4)
        data = jnp.where(jnp.asarray(g["edge_mask"]).reshape(-1)[:, None],
                         1.0, jnp.inf) * jnp.ones((6, 2))
        for build in (segment_ops.segment_aggregator,
                      segment_ops.dense_aggregator):
            out = build(*graph).mean_to_dst(data)
            assert np.isfinite(np.asarray(out)).all()


class TestPolicyUnderTheDenseForm:
    @pytest.fixture(scope="class")
    def model_params(self):
        model = GNNPolicy(n_actions=N_ACTIONS)
        obs = _rand_obs(np.random.default_rng(2))
        params = model.init(jax.random.PRNGKey(0),
                            jax.tree.map(jnp.asarray, obs))
        return model, params

    @pytest.mark.parametrize("case", sorted(AGGREGATE_CASES))
    def test_dense_policy_is_segment_policy(self, model_params, case,
                                            monkeypatch):
        """Logits, values and every parameter's gradient of one batch,
        dense against segment (the parameters do not depend on a pad)."""
        model, params = model_params
        fills, n_pad, e_pad = AGGREGATE_CASES[case]
        obs = _obs_batch(np.random.default_rng(len(case) + 1), fills,
                         n_pad, e_pad, no_in_edge=2)

        def run():
            def loss(p):
                logits, values = batched_policy_apply(model, p, obs)
                logp = jax.nn.log_softmax(logits)
                return jnp.sum(logp[:, 3]) + jnp.sum(values ** 2), (
                    logits, values)
            # a fresh jit each form: the rule is read when a trace runs
            return jax.jit(jax.value_and_grad(loss, has_aux=True))(params)

        seg = run()
        _force_form(monkeypatch, "dense")
        den = run()
        _assert_close(seg, den)
        assert jax.tree.structure(seg[1]) == jax.tree.structure(params)

    def test_flat_batched_matches_vmapped_under_the_dense_form(
            self, model_params, monkeypatch):
        from ddls_tpu.models.policy import vmapped_policy_apply

        model, params = model_params
        _force_form(monkeypatch, "dense")
        rng = np.random.default_rng(7)
        batch = [_rand_obs(rng, n=int(rng.integers(2, 8))) for _ in range(6)]
        stacked = {k: jnp.stack([jnp.asarray(o[k]) for o in batch])
                   for k in batch[0]}
        flat = jax.jit(lambda p, o: batched_policy_apply(model, p, o))
        assert "scatter" not in str(jax.make_jaxpr(flat)(params, stacked))
        lo_f, va_f = flat(params, stacked)
        lo_v, va_v = jax.jit(
            lambda p, o: vmapped_policy_apply(model, p, o))(params, stacked)
        _assert_close((lo_f, va_f), (lo_v, va_v), atol=1e-5)

    @pytest.mark.parametrize("form", ["segment", "dense"])
    def test_the_parameter_tree_does_not_know_the_form(self, form,
                                                       monkeypatch):
        """No parameter is involved: the canonical checkpoint family's
        paths, whichever form initialises the model."""
        from ddls_tpu.parallel.partition import (CANONICAL_PARAM_PATHS,
                                                 tree_paths)

        _force_form(monkeypatch, form)
        model = GNNPolicy(n_actions=17)
        obs = _rand_obs(np.random.default_rng(2))
        obs["action_mask"] = np.ones(17, np.int32)
        obs["graph_features"] = np.zeros(34, np.float32)
        params = model.init(jax.random.PRNGKey(0),
                            jax.tree.map(jnp.asarray, obs))
        assert tuple(sorted(tree_paths(params["params"]))) == tuple(
            sorted(CANONICAL_PARAM_PATHS))

    def test_dp_sharded_update_compiles_dense_on_four_devices(
            self, monkeypatch):
        """The dp-sharded PPO update with the dense form, compiled for a
        4-device CPU mesh: the batch axis stays leading through the
        contractions, so the partitioner adds no collective over the
        incidence and the program holds no scatter."""
        from ddls_tpu.parallel import make_mesh
        from ddls_tpu.rl import PPOConfig, PPOLearner

        _force_form(monkeypatch, "dense")
        model = GNNPolicy(n_actions=N_ACTIONS, fcnet_hiddens=(16,))
        fills = [(5, 9), (2, 1), (8, 20), (3, 7)] * 2
        obs = _obs_batch(np.random.default_rng(11), fills, 8, 20)
        params = model.init(jax.random.PRNGKey(0),
                            jax.tree.map(lambda x: x[0], obs))
        learner = PPOLearner(
            lambda p, o: batched_policy_apply(model, p, o),
            PPOConfig(num_sgd_iter=1, sgd_minibatch_size=4), make_mesh(4))
        state = learner.init_state(params)
        T, B = 1, len(fills)
        rng = np.random.default_rng(12)
        traj = {"obs": jax.tree.map(lambda x: np.asarray(x)[None], obs),
                "actions": rng.integers(0, 5, (T, B)).astype(np.int32),
                "logp": np.log(np.full((T, B), 0.2, np.float32)),
                "values": rng.normal(size=(T, B)).astype(np.float32),
                "rewards": rng.normal(size=(T, B)).astype(np.float32),
                "dones": np.zeros((T, B), bool)}
        straj, slv = learner.shard_traj(
            traj, rng.normal(size=B).astype(np.float32))
        key = jax.random.PRNGKey(1)
        hlo = learner._jit_train_step.lower(
            state, straj, slv, key).compile().as_text().splitlines()
        in_gnn = [ln for ln in hlo if "/gnn/round_" in ln]
        assert any(" dot(" in ln or "dot_general" in ln for ln in in_gnn)
        assert not [ln for ln in in_gnn
                    if "scatter" in ln or " gather(" in ln
                    or "all-gather" in ln or "all-to-all" in ln]
        new_state, metrics = learner.train_step(state, straj, slv, key)
        assert np.isfinite(float(metrics["total_loss"]))
        assert int(new_state.step) == 2


# ----------------------------------------------------------------------
# What the TPU's own compiler makes of the dense form, without a chip:
# the compiler is installed here and compiles for a DESCRIBED v5e. The
# topology is described inside a fixture (never at import: one process
# holds libtpu, and an xdist worker that cannot get it skips).
@pytest.fixture(scope="module")
def v5e_chip():
    import os

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # no compiler here, or another process holds it
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_compile_cache():
    """A compile for a described chip is written to the persistent
    cache and can never be read back: keep it out."""
    from jax.experimental.compilation_cache import compilation_cache

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def test_the_v5e_compiles_the_update_without_an_indexed_op(
        v5e_chip, no_compile_cache, monkeypatch):
    """The forward + backward of the policy on the small cells' update
    minibatch (128 x 150 x 512), in the form the rule picks for a TPU,
    through the TPU's compiler: no scatter and no gather left in the
    GNN's scope (the index form compiles to 20 scatter and 4 gather
    instructions there), the aggregation is dots."""
    B, N, E = 128, 150, 512
    assert segment_ops.aggregate_form("tpu", N, E) == "dense"
    monkeypatch.setattr(
        segment_ops, "aggregate_form",
        lambda platform, n_nodes, n_edges, rule=segment_ops.aggregate_form:
        rule("tpu", n_nodes, n_edges))

    def spec(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=v5e_chip)

    obs = {"node_features": spec((B, N, 5), np.float32),
           "edge_features": spec((B, E, 2), np.float32),
           "edges_src": spec((B, E), np.int32),
           "edges_dst": spec((B, E), np.int32),
           "graph_features": spec((B, 34), np.float32),
           "action_mask": spec((B, 17), np.int32),
           "node_split": spec((B, 1), np.int32),
           "edge_split": spec((B, 1), np.int32)}
    model = GNNPolicy(n_actions=17)
    params = jax.eval_shape(lambda: model.init(
        jax.random.PRNGKey(0),
        {k: jnp.zeros(v.shape[1:], v.dtype) for k, v in obs.items()}))
    params = jax.tree.map(lambda s: spec(s.shape, s.dtype), params)

    def loss(p, o):
        logits, values = batched_policy_apply(model, p, o)
        return jnp.sum(jax.nn.log_softmax(logits)[:, 3]) + jnp.sum(values ** 2)

    hlo = jax.jit(jax.grad(loss)).lower(params, obs).compile().as_text()
    in_gnn = [ln for ln in hlo.splitlines() if "/gnn/" in ln]
    assert in_gnn and any("...ne,...ef->...nf" in ln for ln in in_gnn)
    assert not [ln for ln in in_gnn
                if "scatter" in ln or " gather(" in ln]
