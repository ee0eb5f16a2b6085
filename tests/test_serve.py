"""Serving subsystem tests (ddls_tpu/serve, ISSUE 1).

The load-bearing pin is BATCHING NEVER CHANGES AN ANSWER: every bucket
runs one fixed-shape XLA program (``flat_batched`` at ``max_batch`` rows,
partial flushes padded with replica rows), and at a fixed program a
request's output rows depend only on its own data — XLA tiles by shape,
not by data — so a request served in a full mixed batch is bit-equal to
the same request served alone. Full bit-equality to the *differently
shaped* single-graph ``__call__`` program is NOT pinnable (XLA retiles
per shape and reassociates f32 sums — the same caveat
tests/test_models.py pins for flat_batched vs vmap); across programs the
pin is masked-pattern equality + 1e-5 closeness + identical argmax
decisions.

Also pinned: deadline flushes of partial batches, saturation/dead-device
degradation to the FixedDegreePacking fallback (answers agree with the
checkpoint-extracted rule; no request is ever dropped), the
``serve_policy.py --selftest`` front end and its dataset pad bounds, and
the server registry's accounting of a mixed request stream.
"""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

import jax

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

N_ACTIONS = 9
BUCKETS = [(8, 12), (16, 28)]
MAX_BATCH = 4


def _rand_obs(rng, n, m, max_nodes, max_edges, mask_valid=(0, 1, 2, 4, 8)):
    node_features = np.zeros((max_nodes, 5), np.float32)
    node_features[:n] = rng.uniform(0, 1, (n, 5))
    edge_features = np.zeros((max_edges, 2), np.float32)
    edge_features[:m] = rng.uniform(0, 1, (m, 2))
    src = np.zeros(max_edges, np.int32)
    dst = np.zeros(max_edges, np.int32)
    src[:m] = rng.integers(0, n, m)
    dst[:m] = rng.integers(0, n, m)
    mask = np.zeros(N_ACTIONS, np.int32)
    mask[list(mask_valid)] = 1
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


@pytest.fixture(scope="module")
def model_params():
    from ddls_tpu.models.policy import GNNPolicy

    model = GNNPolicy(n_actions=N_ACTIONS, out_features_msg=4,
                      out_features_hidden=8, out_features_node=4,
                      out_features_graph=4, fcnet_hiddens=(16,))
    obs = _rand_obs(np.random.default_rng(0), 6, 8, *BUCKETS[-1])
    params = model.init(jax.random.PRNGKey(0),
                        jax.tree_util.tree_map(np.asarray, obs))
    return model, params


def _make_server(model_params, clock=None, **kwargs):
    from ddls_tpu.serve import PolicyServer

    model, params = model_params
    defaults = dict(buckets=BUCKETS, max_batch=MAX_BATCH, deadline_s=0.01)
    defaults.update(kwargs)
    if clock is not None:
        defaults["clock"] = clock
    return PolicyServer(model, params, **defaults)


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


# --------------------------------------------------------------- bucketing
class TestBucketing:
    def test_default_buckets_halving_ladder(self):
        from ddls_tpu.serve import default_buckets

        b = default_buckets(32, 60, n_buckets=3)
        assert b[-1] == (32, 60)
        assert b == sorted(set(b))
        assert len(b) == 3
        # edges default to the fully-connected bound
        assert default_buckets(8)[-1] == (8, 28)

    def test_smallest_fit_and_pad(self):
        from ddls_tpu.serve import BucketOverflowError, ObsBucketer

        bk = ObsBucketer(BUCKETS)
        obs = _rand_obs(np.random.default_rng(1), 5, 6, 20, 40)
        idx, padded = bk.bucket_obs(obs)
        assert idx == 0
        assert padded["node_features"].shape == (8, 5)
        assert padded["edge_features"].shape == (12, 2)
        # real rows untouched, pad rows zero
        np.testing.assert_array_equal(padded["node_features"][:5],
                                      obs["node_features"][:5])
        np.testing.assert_array_equal(padded["node_features"][5:], 0.0)
        np.testing.assert_array_equal(padded["edges_src"][:6],
                                      obs["edges_src"][:6])
        # both dimensions must fit: 5 nodes but 20 edges -> second bucket
        assert bk.bucket_index(5, 20) == 1
        with pytest.raises(BucketOverflowError):
            bk.bucket_index(17, 4)

    def test_repad_is_forward_invariant(self, model_params):
        """pad_obs_to only moves the dead masked region; the single-graph
        forward over the re-padded obs matches the original to padding
        tolerance (the perf_round2 invariant serving relies on)."""
        from ddls_tpu.envs.obs import pad_obs_to

        model, params = model_params
        obs = _rand_obs(np.random.default_rng(2), 6, 9, 20, 40)
        lo_a, va_a = model.apply(params,
                                 jax.tree_util.tree_map(np.asarray, obs))
        re = pad_obs_to(obs, 16, 28)
        lo_b, va_b = model.apply(params,
                                 jax.tree_util.tree_map(np.asarray, re))
        np.testing.assert_allclose(
            np.where(np.isfinite(lo_a), lo_a, 0.0),
            np.where(np.isfinite(lo_b), lo_b, 0.0), atol=1e-5)
        np.testing.assert_allclose(va_a, va_b, atol=1e-5)


# -------------------------------------------------------------- microbatch
class TestMicrobatch:
    def _req(self, rid, bucket, t):
        from ddls_tpu.serve import PendingRequest

        return PendingRequest(request_id=rid, bucket_idx=bucket, obs={},
                              enqueue_time=t)

    def test_full_batch_flushes_immediately(self):
        from ddls_tpu.serve import MicrobatchEngine

        eng = MicrobatchEngine(2, max_batch=3, deadline_s=10.0)
        for i in range(3):
            eng.submit(self._req(i, 0, 0.0))
        batches = eng.due_batches(now=0.0)
        assert len(batches) == 1 and batches[0][0] == 0
        assert [r.request_id for r in batches[0][1]] == [0, 1, 2]
        assert eng.queued() == 0

    def test_deadline_flushes_partial_and_never_mixes_buckets(self):
        from ddls_tpu.serve import MicrobatchEngine

        eng = MicrobatchEngine(2, max_batch=4, deadline_s=0.01)
        eng.submit(self._req(0, 0, 0.0))
        eng.submit(self._req(1, 1, 0.0))
        assert eng.due_batches(now=0.005) == []
        assert eng.next_deadline() == pytest.approx(0.01)
        batches = eng.due_batches(now=0.011)
        assert sorted(b[0] for b in batches) == [0, 1]
        assert all(len(b[1]) == 1 for b in batches)

    def test_force_drains(self):
        from ddls_tpu.serve import MicrobatchEngine

        eng = MicrobatchEngine(1, max_batch=4, deadline_s=100.0)
        eng.submit(self._req(0, 0, 0.0))
        assert eng.due_batches(now=0.0) == []
        assert len(eng.due_batches(now=0.0, force=True)) == 1

    def test_next_deadline_reports_full_batch_due_now(self):
        """A queue already holding a full batch is due immediately:
        next_deadline must report a time not in the future (the head's
        enqueue time), or a caller that sleeps to it would delay a
        flush-on-fill by up to deadline_s — defeating the fill half of
        flush-on-fill-or-deadline."""
        from ddls_tpu.serve import MicrobatchEngine

        eng = MicrobatchEngine(2, max_batch=2, deadline_s=10.0)
        eng.submit(self._req(0, 0, 1.0))
        assert eng.next_deadline() == pytest.approx(11.0)  # partial
        eng.submit(self._req(1, 0, 2.0))                   # now full
        assert eng.next_deadline() == pytest.approx(1.0)   # due already
        eng.due_batches(now=2.0)
        assert eng.next_deadline() is None


# ------------------------------------------------------------ bit-equality
class TestBatchedForwardParity:
    @pytest.mark.parametrize("bucket", list(range(len(BUCKETS))))
    def test_batched_bit_equal_to_unbatched(self, model_params, bucket):
        """THE serving pin (ISSUE 1 acceptance): for every bucket size, a
        request's logits/value from a full mixed batch are bit-equal to
        serving it unbatched through the same program — batching can
        never change an answer."""
        from ddls_tpu.serve import BucketForward

        model, params = model_params
        bn, be = BUCKETS[bucket]
        rng = np.random.default_rng(10 + bucket)
        reqs = [_rand_obs(rng, int(rng.integers(2, bn + 1)),
                          int(rng.integers(1, be + 1)), bn, be)
                for _ in range(MAX_BATCH)]
        bf = BucketForward(model, params, max_batch=MAX_BATCH)
        lo_batch, va_batch = bf.forward(reqs)
        for i, req in enumerate(reqs):
            lo_solo, va_solo = bf.forward([req])
            np.testing.assert_array_equal(lo_batch[i], lo_solo[0])
            np.testing.assert_array_equal(va_batch[i], va_solo[0])

    @pytest.mark.parametrize("bucket", list(range(len(BUCKETS))))
    def test_agrees_with_single_graph_forward(self, model_params, bucket):
        """Across programs (fixed-batch vs the single-graph ``__call__``)
        XLA retiles, so the pin is: identical masked(-inf) pattern,
        1e-5-close finite logits/values, identical argmax decision."""
        model, params = model_params
        from ddls_tpu.serve import BucketForward

        bn, be = BUCKETS[bucket]
        rng = np.random.default_rng(20 + bucket)
        reqs = [_rand_obs(rng, int(rng.integers(2, bn + 1)),
                          int(rng.integers(1, be + 1)), bn, be)
                for _ in range(MAX_BATCH)]
        bf = BucketForward(model, params, max_batch=MAX_BATCH)
        lo_batch, va_batch = bf.forward(reqs)
        for i, req in enumerate(reqs):
            lo_s, va_s = model.apply(
                params, jax.tree_util.tree_map(np.asarray, req))
            lo_s, va_s = np.asarray(lo_s), np.asarray(va_s)
            np.testing.assert_array_equal(np.isfinite(lo_batch[i]),
                                          np.isfinite(lo_s))
            np.testing.assert_allclose(
                np.where(np.isfinite(lo_batch[i]), lo_batch[i], 0.0),
                np.where(np.isfinite(lo_s), lo_s, 0.0), atol=1e-5)
            np.testing.assert_allclose(va_batch[i], va_s, atol=1e-5)
            assert int(np.argmax(lo_batch[i])) == int(np.argmax(lo_s))

    def test_each_bucket_compiles_exactly_once(self, model_params):
        server = _make_server(model_params, clock=_FakeClock())
        rng = np.random.default_rng(3)
        for t in range(10):
            bn, be = BUCKETS[t % 2]
            server.submit(_rand_obs(rng, bn - 1, be - 2, bn, be), now=0.0)
        server.drain(now=0.0)
        assert server.stats.n_compiles == len(BUCKETS)

    def test_server_batched_decisions_match_serve_one(self, model_params):
        rng = np.random.default_rng(4)
        bn, be = BUCKETS[0]
        reqs = [_rand_obs(rng, int(rng.integers(2, bn + 1)),
                          int(rng.integers(1, be + 1)), bn, be)
                for _ in range(MAX_BATCH)]
        clock = _FakeClock()
        server = _make_server(model_params, clock=clock)
        for o in reqs:
            server.submit(o, now=0.0)
        batched = {r.request_id: r.action for r in server.poll(now=0.0)}
        assert len(batched) == MAX_BATCH
        solo_server = _make_server(model_params, clock=_FakeClock())
        for i, o in enumerate(reqs):
            assert solo_server.serve_one(o).action == batched[i]


# ------------------------------------------------------- deadlines/fallback
class TestServerBehaviour:
    def test_deadline_flush_fires_under_partial_batch(self, model_params):
        clock = _FakeClock()
        server = _make_server(model_params, clock=clock, deadline_s=0.01)
        rng = np.random.default_rng(5)
        bn, be = BUCKETS[1]
        for _ in range(MAX_BATCH - 1):
            server.submit(_rand_obs(rng, 10, 14, bn, be), now=0.0)
        assert server.poll(now=0.005) == []          # not due yet
        out = server.poll(now=0.012)                 # deadline expired
        assert len(out) == MAX_BATCH - 1
        assert all(r.source == "policy" and r.batch_fill == MAX_BATCH - 1
                   for r in out)
        assert list(server.stats.occupancies) == [
            pytest.approx((MAX_BATCH - 1) / MAX_BATCH)]
        # latency = deadline wait under the injected clock
        assert all(r.latency_s == pytest.approx(0.012) for r in out)

    def test_saturation_falls_back_without_dropping(self, model_params):
        from ddls_tpu.envs.baselines import FixedDegreePacking

        clock = _FakeClock()
        server = _make_server(model_params, clock=clock, max_queue=4,
                              deadline_s=100.0,
                              fallback=FixedDegreePacking(degree=4))
        rng = np.random.default_rng(6)
        bn, be = BUCKETS[0]
        reqs = [_rand_obs(rng, 5, 6, bn, be) for _ in range(10)]
        ids = [server.submit(o, now=0.0) for o in reqs]
        # the first 4 queued; 5..10 answered immediately from the heuristic
        immediate = server.poll(now=0.0)
        fallback = [r for r in immediate if r.source == "fallback"]
        assert len(fallback) == 6
        assert all(r.reason == "saturated" for r in fallback)
        rule = FixedDegreePacking(degree=4)
        assert all(r.action == rule.compute_action(reqs[r.request_id])
                   for r in fallback)
        # nothing dropped: drain answers the queued remainder
        rest = server.drain(now=0.0)
        answered = {r.request_id for r in immediate} | {
            r.request_id for r in rest}
        assert answered == set(ids)
        assert server.stats.summary()["fallback_rate"] == pytest.approx(0.6)

    def test_dead_backend_degrades_to_heuristic(self, model_params):
        """A dead device backend: the batched forward raising flips
        the server into degraded mode; every request (in-flight and
        later) is answered by FixedDegreePacking at the extracted degree,
        none dropped."""
        from ddls_tpu.envs.baselines import FixedDegreePacking
        from ddls_tpu.serve import DEFAULT_FALLBACK_DEGREE

        def broken_apply(params, obs):
            raise RuntimeError("device backend died")

        clock = _FakeClock()
        server = _make_server(model_params, clock=clock,
                              apply_fn=broken_apply,
                              fallback=FixedDegreePacking(degree=4))
        assert DEFAULT_FALLBACK_DEGREE == 8  # the rule_extraction degree
        rng = np.random.default_rng(7)
        bn, be = BUCKETS[0]
        reqs = [_rand_obs(rng, 5, 6, bn, be) for _ in range(MAX_BATCH + 2)]
        for o in reqs:
            server.submit(o, now=0.0)
        # the latch is LOUD: the heuristic answers what the shipped
        # policy answers, so nothing else would show the difference
        with pytest.warns(UserWarning, match="latching degraded mode"):
            out = server.drain(now=0.0)
        assert len(out) == MAX_BATCH + 2
        assert all(r.source == "fallback" for r in out)
        assert server.degraded
        rule = FixedDegreePacking(degree=4)
        assert all(r.action == rule.compute_action(reqs[r.request_id])
                   for r in out)
        # later submits short-circuit to the heuristic (fallback latency
        # completes at the CLOCK's now — advance it to the submit time)
        clock.t = 1.0
        rid = server.submit(reqs[0], now=1.0)
        out2 = server.poll(now=1.0)
        assert [r.request_id for r in out2] == [rid]
        assert out2[0].reason == "degraded"

    def test_serve_one_matches_id_with_prior_queue(self, model_params):
        """serve_one must return ITS request's response even when the
        forced drain also resolves earlier-queued requests — those stay
        pending for the next poll, none dropped."""
        clock = _FakeClock()
        server = _make_server(model_params, clock=clock, deadline_s=100.0)
        rng = np.random.default_rng(9)
        bn, be = BUCKETS[0]
        first = _rand_obs(rng, 5, 6, bn, be)
        second = _rand_obs(rng, 6, 7, bn, be)
        rid_first = server.submit(first, now=0.0)   # queues (partial batch)
        resp = server.serve_one(second)
        assert resp.request_id != rid_first
        solo = _make_server(model_params, clock=_FakeClock())
        assert resp.action == solo.serve_one(second).action
        # the first request's answer was resolved by the drain and is
        # waiting on the next poll
        rest = server.poll(now=0.0)
        assert [r.request_id for r in rest] == [rid_first]

    def test_oversized_graph_falls_back(self, model_params):
        clock = _FakeClock()
        server = _make_server(model_params, clock=clock)
        big = _rand_obs(np.random.default_rng(8), 20, 24, 24, 30)
        server.submit(big, now=0.0)
        out = server.poll(now=0.0)
        assert len(out) == 1 and out[0].reason == "overflow"

    def test_malformed_obs_rejected_at_submit_not_batch(self, model_params):
        """A bad request errors to ITS caller at submit (missing keys,
        wrong per-row feature width, graph/mask width disagreeing with the
        server's model, action_set the fallback needs absent or ragged)
        and never reaches a batch — co-queued well-formed requests still
        get policy answers."""
        clock = _FakeClock()
        server = _make_server(model_params, clock=clock)
        rng = np.random.default_rng(11)
        bn, be = BUCKETS[0]
        good = _rand_obs(rng, 5, 6, bn, be)
        rid = server.submit(good, now=0.0)

        # every fallback path reads action_set (envs/baselines.py) — a
        # request without it must be rejected up front, not crash poll()
        # the day the backend degrades
        missing = {k: v for k, v in good.items() if k != "action_set"}
        with pytest.raises(ValueError, match="missing"):
            server.submit(missing, now=0.0)

        bad_width = dict(good)
        bad_width["node_features"] = np.zeros((bn, 4), np.float32)
        with pytest.raises(ValueError, match="node_features"):
            server.submit(bad_width, now=0.0)

        bad_graph = dict(good)
        bad_graph["graph_features"] = np.zeros(3, np.float32)
        with pytest.raises(ValueError, match="graph_features"):
            server.submit(bad_graph, now=0.0)

        bad_set = dict(good)
        bad_set["action_set"] = np.arange(3, dtype=np.int32)
        with pytest.raises(ValueError, match="action_set"):
            server.submit(bad_set, now=0.0)

        out = server.drain(now=0.0)
        assert [r.request_id for r in out] == [rid]
        assert out[0].source == "policy"
        # rejected submits are not counted as served requests
        assert server.stats.n_requests == 1

    def test_inconsistent_splits_rejected_at_submit(self, model_params):
        """node_split/edge_split must agree with the rows actually
        present: an inflated split would make the repad zero-fill
        phantom "real" rows (served as a garbage policy answer), a
        negative one silently truncates real rows, and short
        edges_src/edges_dst would index garbage — all data errors owed
        to the submitting caller."""
        clock = _FakeClock()
        server = _make_server(model_params, clock=clock)
        good = _rand_obs(np.random.default_rng(13), 5, 6, *BUCKETS[0])

        inflated = dict(good)
        inflated["node_split"] = np.array(
            [int(np.asarray(good["node_features"]).shape[0]) + 3],
            np.int32)
        with pytest.raises(ValueError, match="node_split"):
            server.submit(inflated, now=0.0)

        negative = dict(good)
        negative["edge_split"] = np.array([-2], np.int32)
        with pytest.raises(ValueError, match="edge_split"):
            server.submit(negative, now=0.0)

        short_src = dict(good)
        short_src["edges_src"] = np.asarray(good["edges_src"])[:2]
        with pytest.raises(ValueError, match="edges_src"):
            server.submit(short_src, now=0.0)

        # a REAL edge endpoint outside this graph's real nodes would
        # escape its slot in the flat-batched mega-graph and scatter
        # into a CO-BATCHED graph's embedding — the one way a request
        # could break "batching never changes an answer"
        n_real = int(np.asarray(good["node_split"]).reshape(-1)[0])
        out_of_range = dict(good)
        dst = np.asarray(good["edges_dst"]).copy()
        dst[0] = n_real  # >= node_split: points past this graph
        out_of_range["edges_dst"] = dst
        with pytest.raises(ValueError, match="edges_dst"):
            server.submit(out_of_range, now=0.0)

        negative_src = dict(good)
        src = np.asarray(good["edges_src"]).copy()
        src[0] = -1
        negative_src["edges_src"] = src
        with pytest.raises(ValueError, match="edges_src"):
            server.submit(negative_src, now=0.0)

        # the well-formed obs still serves; nothing latched
        resp = server.serve_one(good)
        assert resp.source == "policy"
        assert not server.degraded

    def test_checkpoint_graph_feature_dim_probe(self):
        """The startup pairing guard reads the trained graph width off a
        restored param tree (attribute names frozen by the shipped
        checkpoints) and returns None for unrecognised shapes instead of
        raising."""
        from ddls_tpu.serve import checkpoint_graph_feature_dim

        tree = {"params": {"graph_module": {"Dense_0": {
            "kernel": np.zeros((34, 8), np.float32)}}}}
        assert checkpoint_graph_feature_dim(tree) == 34
        assert checkpoint_graph_feature_dim({}) is None
        assert checkpoint_graph_feature_dim({"params": {}}) is None
        assert checkpoint_graph_feature_dim(None) is None

    def test_width_contract_seeded_by_model_not_first_request(
            self, model_params):
        """The action width comes from the model itself and the graph
        width from the constructor where given — a wrong-width FIRST
        request is rejected instead of poisoning the contract (or, worse,
        passing submit and latching degraded when the forward fails on a
        healthy backend). A rejected request commits no pins."""
        clock = _FakeClock()
        good = _rand_obs(np.random.default_rng(12), 5, 6, *BUCKETS[0])
        gdim = int(good["graph_features"].shape[0])
        server = _make_server(model_params, clock=clock,
                              graph_feature_dim=gdim)

        wrong_mask = dict(good)
        wrong_mask["action_mask"] = np.ones(N_ACTIONS + 3, np.int32)
        with pytest.raises(ValueError, match="action_mask"):
            server.submit(wrong_mask, now=0.0)

        wrong_graph = dict(good)
        wrong_graph["graph_features"] = np.zeros(gdim + 9, np.float32)
        with pytest.raises(ValueError, match="graph_features"):
            server.submit(wrong_graph, now=0.0)

        # the correct client still serves; nothing was pinned wrong,
        # nothing latched
        resp = server.serve_one(good)
        assert resp.source == "policy"
        assert not server.degraded


# --------------------------------------------------------------- baselines
def test_adaptive_degree_packing_reads_cluster_arrival_counter():
    """ADVICE r5 item 2: rho comes from the cluster's arrival-demand
    counter (blocked arrivals included), not per-decision accumulation —
    and carries no cross-episode state on that path."""
    from ddls_tpu.envs.baselines import AdaptiveDegreePacking

    class _Stopwatch:
        def __init__(self, t):
            self._t = t

        def time(self):
            return self._t

    class _Topo:
        num_workers = 32
        shape = (4, 4, 2)

    class _Cluster:
        def __init__(self, now, arrived, seq_sum):
            self.stopwatch = _Stopwatch(now)
            self.num_jobs_arrived = arrived
            self.sum_arrived_seq_completion_time = seq_sum
            self.topology = _Topo()

    class _Env:
        def __init__(self, cluster):
            self.cluster = cluster

    class _Job:
        seq_completion_time = 1000.0

    actor = AdaptiveDegreePacking()
    # heavy overload entirely from BLOCKED arrivals: worker-seconds that
    # never reach a decision step still push rho into the heavy tier
    env = _Env(_Cluster(now=100.0, arrived=10, seq_sum=32 * 100.0 * 2.0))
    assert actor._rho(env, _Job()) == pytest.approx(2.0)
    # stateless across calls: same inputs, same estimate (the old
    # accumulator would have doubled it)
    assert actor._rho(env, _Job()) == pytest.approx(2.0)
    # light load
    env2 = _Env(_Cluster(now=100.0, arrived=10, seq_sum=32 * 100.0 * 0.1))
    assert actor._rho(env2, _Job()) == pytest.approx(0.1)
    # warmup guard unchanged
    env3 = _Env(_Cluster(now=0.0, arrived=10, seq_sum=50.0))
    assert np.isnan(actor._rho(env3, _Job()))
    # explicit episode-reset hook exists and clears legacy state
    actor._seq_sum = 123.0
    actor.reset()
    assert actor._seq_sum == 0.0


def test_cluster_accumulates_arrived_seq_completion_time(dataset_dir):
    from ddls_tpu.envs import RampJobPartitioningEnvironment

    env = RampJobPartitioningEnvironment(
        topology_config={"type": "ramp", "kwargs": {
            "num_communication_groups": 2,
            "num_racks_per_communication_group": 2,
            "num_servers_per_rack": 2, "num_channels": 1,
            "total_node_bandwidth": 1.6e12,
            "intra_gpu_propagation_latency": 50e-9,
            "worker_io_latency": 100e-9}},
        node_config={"type_1": {"num_nodes": 8, "workers_config": [
            {"num_workers": 1, "worker": "A100"}]}},
        jobs_config={"path_to_files": dataset_dir,
                     "job_interarrival_time_dist": {
                         "_target_": "ddls_tpu.demands.distributions.Fixed",
                         "val": 100.0},
                     "max_acceptable_job_completion_time_frac_dist": {
                         "_target_":
                             "ddls_tpu.demands.distributions.Uniform",
                         "min_val": 0.5, "max_val": 1.0, "decimals": 2},
                     "replication_factor": 3,
                     "job_sampling_mode": "remove_and_repeat",
                     "num_training_steps": 10},
        max_partitions_per_op=4, min_op_run_time_quantum=0.01,
        reward_function="job_acceptance", max_simulation_run_time=2e3,
        pad_obs_kwargs={"max_nodes": 16, "max_edges": 32})
    obs = env.reset(seed=0)
    c = env.cluster
    assert c.sum_arrived_seq_completion_time > 0.0
    first = c.sum_arrived_seq_completion_time
    assert first == pytest.approx(
        list(c.job_queue.jobs.values())[0].seq_completion_time)
    done, steps = False, 0
    while not done and steps < 6:
        valid = np.flatnonzero(np.asarray(obs["action_mask"]))
        obs, _, done, _ = env.step(int(valid[0]))
        steps += 1
    assert c.sum_arrived_seq_completion_time >= first
    assert c.num_jobs_arrived >= 1
    # reset zeroes the counter with the rest of the cluster
    env.reset(seed=1)
    assert env.cluster.sum_arrived_seq_completion_time == pytest.approx(
        list(env.cluster.job_queue.jobs.values())[0].seq_completion_time)


# ------------------------------------------------------------ front ends
def test_line_assembler_handles_bursts():
    """The stdin pump selects on the raw fd, and select() fires once per
    CHUNK — a burst of N lines arriving in one read must all be handled
    before the loop returns to select (a buffered readline() would
    strand lines 2..N in Python's buffer while select blocks on the
    drained fd: interactive-client deadlock)."""
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        from serve_policy import LineAssembler
    finally:
        sys.path.pop(0)

    la = LineAssembler()
    # one chunk, three complete lines + one partial
    assert la.feed(b'{"id": 1}\n{"id": 2}\n{"id": 3}\n{"id"') == [
        '{"id": 1}', '{"id": 2}', '{"id": 3}']
    # the partial completes across chunks
    assert la.feed(b': 4}\n') == ['{"id": 4}']
    assert la.flush() == []
    # unterminated final line surfaces at EOF flush
    assert la.feed(b'{"id": 5}') == []
    assert la.flush() == ['{"id": 5}']
    assert la.flush() == []


def test_serve_policy_selftest_script():
    """CI satellite: the stdin/JSON driver's --selftest smoke runs on CPU
    and reports ok."""
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "serve_policy.py"),
         "--selftest", "--selftest-requests", "12", "--max-batch", "4",
         "--degree", "8"],
        capture_output=True, text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-2000:]
    payload = json.loads(out.stdout.strip().splitlines()[-1])
    assert payload["selftest"] == "ok"
    assert payload["n_requests"] == 12
    assert payload["n_fallback_saturated"] > 0


def test_policy_server_registry_accounts_for_every_request(model_params):
    """Mixed-size requests through a real server: every request is
    counted once, every flush has one cause, each bucket compiles at
    most once, and the registry's latency histogram IS the summary's
    p50/p99 (same trailing window)."""
    clock = _FakeClock()
    server = _make_server(model_params, clock=clock, deadline_s=0.01)
    rng = np.random.default_rng(11)
    out = []
    for i in range(48):
        bn, be = BUCKETS[int(rng.integers(0, 2))]
        clock.t = i * 0.004
        server.submit(_rand_obs(rng, int(rng.integers(2, bn + 1)),
                                int(rng.integers(1, be + 1)), bn, be),
                      now=clock.t)
        out.extend(server.poll(now=clock.t))
    clock.t += 1.0
    out.extend(server.drain(now=clock.t))
    assert sorted(r.request_id for r in out) == list(range(48))
    assert all(r.source == "policy" for r in out)
    summary = server.stats.summary()
    assert summary["n_requests"] == 48
    assert 0.0 < summary["batch_occupancy"] <= 1.0
    assert summary["fallback_rate"] == 0.0
    assert server.stats.n_compiles <= len(BUCKETS)
    snap = server.stats.registry.snapshot()
    lat = snap["histograms"]["serve.latency_s"]
    assert lat["count"] == 48
    assert lat["p50"] * 1e3 == pytest.approx(summary["p50_latency_ms"])
    assert lat["p99"] * 1e3 == pytest.approx(summary["p99_latency_ms"])
    assert summary["p99_latency_ms"] >= summary["p50_latency_ms"]
    counters = snap["counters"]
    assert counters["serve.requests"] == 48
    assert sum(v for k, v in counters.items()
               if k.startswith("serve.flush_cause.")) == \
        counters["serve.flushes"]
    assert {"fill", "deadline"} <= {
        k.rsplit(".", 1)[1] for k in counters
        if k.startswith("serve.flush_cause.")}


def _serve_policy():
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    try:
        import serve_policy
    finally:
        sys.path.pop(0)
    return serve_policy


def test_dataset_pad_bounds_reads_the_files_every_time(tmp_path):
    """The selftest's pad bounds are a straight read of the dataset: a
    dataset regenerated at the same path with other graph sizes gives
    other bounds (no cache to go stale)."""
    from ddls_tpu.graphs.readers import read_graph_file
    from ddls_tpu.graphs.synthetic import generate_pipedream_txt_files

    sp = _serve_policy()
    d = str(tmp_path / "ds")
    os.makedirs(d)
    generate_pipedream_txt_files(d, n_cnn=1, n_translation=0, seed=0,
                                 min_ops=4, max_ops=6)
    b1 = sp.dataset_pad_bounds(d)
    graphs = [read_graph_file(os.path.join(d, f)) for f in os.listdir(d)]
    assert b1 == {"max_nodes": max(g.n_ops for g in graphs),
                  "max_edges": max(g.n_deps for g in graphs)}
    for f in os.listdir(d):
        os.remove(os.path.join(d, f))
    generate_pipedream_txt_files(d, n_cnn=2, n_translation=1, seed=1,
                                 min_ops=10, max_ops=14)
    b2 = sp.dataset_pad_bounds(d)
    assert b2["max_nodes"] >= 10
    assert b2 != b1


def test_dataset_pad_bounds_rejects_a_dataset_without_graphs(tmp_path):
    """max_nodes=0 would read as "padding disabled" downstream: an empty
    dataset fails at the source."""
    with pytest.raises(FileNotFoundError, match="no graph profiles"):
        _serve_policy().dataset_pad_bounds(str(tmp_path))
