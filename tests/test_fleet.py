"""Serving-fleet tests (ddls_tpu/serve/{fleet,loadgen,autoscale},
ISSUE 8).

The load-bearing pins, in order of importance:

* **Routing never changes an answer**: for every routing policy, fleet
  answers are bit-equal to a single PolicyServer serving the same
  requests — each replica runs the same fixed-shape compiled program
  over the same params, and the PR-1 invariant (batch composition
  cannot change a request's output rows) extends across replicas.
* **Shed before degrade**: with shedding enabled, overload produces
  explicit ``source="shed"`` refusals and the replica's ``saturated``
  heuristic fallback NEVER fires; with shedding disabled the legacy
  saturation fallback is intact. Quota/shed decisions replay
  identically for a seeded trace.
* **Hot-swap no-drop**: drain-then-swap answers every already-admitted
  request with the OLD params as policy answers (no drops, no degraded
  latch), and requests after the swap serve the NEW params.
* **Autoscaler determinism**: decisions are a pure function of
  (config, cooldown state, snapshot) — a JSON-round-tripped snapshot
  sequence replays to identical decisions.
* **Loadgen schema**: seeded traces fingerprint deterministically and
  the validator rejects malformed traces (the ``--selftest`` surface,
  wired into tier-1 here).

All CPU, tier-1.
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


class _FakeClock:
    def __init__(self):
        self.t = 0.0

    def __call__(self):
        return self.t


def _stub_apply(params, obs):
    """Data-independent forward: every request argmaxes to action 0.
    Keeps the admission/lifecycle tests compile-free."""
    import jax.numpy as jnp

    B = obs["node_features"].shape[0]
    return jnp.zeros((B, N_ACTIONS)), jnp.zeros((B,))


@pytest.fixture(scope="module")
def model_params():
    from ddls_tpu.models.policy import GNNPolicy

    model = GNNPolicy(n_actions=N_ACTIONS, out_features_msg=4,
                      out_features_hidden=8, out_features_node=4,
                      out_features_graph=4, fcnet_hiddens=(16,))
    obs = _rand_obs(np.random.default_rng(0), 6, 8, *BUCKETS[-1])
    params = model.init(jax.random.PRNGKey(0),
                        jax.tree_util.tree_map(np.asarray, obs))
    params_b = model.init(jax.random.PRNGKey(1),
                          jax.tree_util.tree_map(np.asarray, obs))
    return model, params, params_b


def _make_fleet(model, params, clock, n_replicas=2, **kwargs):
    from ddls_tpu.serve import build_fleet

    defaults = dict(buckets=BUCKETS, max_batch=MAX_BATCH,
                    deadline_s=0.01)
    defaults.update(kwargs)
    return build_fleet(model, params, n_replicas=n_replicas,
                       clock=clock, **defaults)


def _stub_fleet(clock, n_replicas=2, **kwargs):
    kwargs.setdefault("apply_fn", _stub_apply)
    return _make_fleet(None, {}, clock, n_replicas=n_replicas, **kwargs)


# ------------------------------------------------------------- bucket refit
class TestFitBuckets:
    def test_quantile_ladder_covers_and_is_deterministic(self):
        from ddls_tpu.serve import fit_buckets

        sizes = [(4, 5), (6, 8), (8, 12), (16, 28), (5, 6), (6, 7)]
        specs = fit_buckets(sizes, n_buckets=3)
        assert specs == fit_buckets(list(sizes), n_buckets=3)
        assert specs[-1] == (16, 28)  # top rung covers the observed max
        assert specs == sorted(specs)
        # strictly monotone in BOTH dims (selection needs both to fit)
        for (n0, m0), (n1, m1) in zip(specs, specs[1:]):
            assert n0 < n1 and m0 < m1
        with pytest.raises(ValueError):
            fit_buckets([], n_buckets=3)


# -------------------------------------------------------- routing equality
class TestRoutingBitEquality:
    @pytest.mark.parametrize("routing", ["affinity", "least_loaded",
                                         "round_robin", "hash"])
    def test_fleet_bit_equal_to_single_server(self, model_params,
                                              routing):
        """THE fleet pin (acceptance): whatever the routing policy and
        however requests co-batch on each replica, the fleet's answers
        are bit-equal to one PolicyServer serving the same requests."""
        from ddls_tpu.serve import PolicyServer

        model, params, _ = model_params
        rng = np.random.default_rng(100)
        reqs, tenants = [], []
        for i in range(10):
            bn, be = BUCKETS[i % 2]
            reqs.append(_rand_obs(rng, int(rng.integers(2, bn + 1)),
                                  int(rng.integers(1, be + 1)), bn, be))
            tenants.append(f"tenant-{i % 3}" if i % 2 else None)
        router = _make_fleet(model, params, _FakeClock(), n_replicas=3,
                             routing=routing)
        fids = [router.submit(o, now=0.0, tenant=t)
                for o, t in zip(reqs, tenants)]
        out = {r.request_id: r for r in router.drain(now=0.0)}
        assert sorted(out) == sorted(fids)
        assert all(r.source == "policy" for r in out.values())
        solo = PolicyServer(model, params, buckets=BUCKETS,
                            max_batch=MAX_BATCH, clock=_FakeClock())
        for fid, obs in zip(fids, reqs):
            assert out[fid].action == solo.serve_one(obs).action

    def test_trace_driven_two_replica_fleet_answers_everything(
            self, model_params):
        """The loadgen trace end to end through a REAL two-replica
        fleet: arrivals drive the clock, size ranks pick the graph,
        tenants ride the affinity router — every request is answered
        exactly once by the policy, latencies are measured from the
        SCHEDULED arrival, both replicas serve, and the per-replica
        registries add up to the aggregate."""
        from ddls_tpu.serve import loadgen

        model, params, _ = model_params
        trace = loadgen.generate_trace(n_requests=48, base_rps=400.0,
                                       seed=1, diurnal_period_s=0.4,
                                       burst_period_s=0.2)
        loadgen.validate_trace(trace)
        rng = np.random.default_rng(7)
        pool = sorted(
            (_rand_obs(rng, int(rng.integers(2, bn + 1)),
                       int(rng.integers(1, be + 1)), bn, be)
             for bn, be in BUCKETS * 6),
            key=lambda o: int(o["node_split"][0]))
        clock = _FakeClock()
        router = _make_fleet(model, params, clock, n_replicas=2)
        fids, out = [], []
        for t, frac, tenant in zip(trace["arrival_s"], trace["size_frac"],
                                   trace["tenant"]):
            clock.t = float(t)
            fids.append(router.submit(pool[int(frac * len(pool))],
                                      now=clock.t, tenant=tenant))
            out.extend(router.poll(now=clock.t))
        clock.t = float(trace["arrival_s"][-1]) + 1.0
        out.extend(router.drain(now=clock.t))
        assert sorted(r.request_id for r in out) == sorted(fids)
        assert all(r.source == "policy" for r in out)
        assert {r.replica for r in out} == {0, 1}
        arrived = dict(zip(fids, trace["arrival_s"]))
        assert all(0.0 <= r.latency_s
                   <= clock.t - arrived[r.request_id] + 1e-9 for r in out)
        # the deadline (10 ms) bounds every wait but the final drain's
        assert sum(r.latency_s <= 0.01 + 1e-9 for r in out) >= 40
        snaps = router.registry_snapshots()
        assert {"fleet", "aggregate", "r0", "r1"} <= set(snaps)
        per_replica = [snaps[k]["counters"]["serve.requests"]
                       for k in ("r0", "r1")]
        assert all(n > 0 for n in per_replica)
        assert (snaps["aggregate"]["counters"]["serve.requests"]
                == sum(per_replica) == 48)

    def test_affinity_pins_tenant_to_one_replica(self):
        clock = _FakeClock()
        router = _stub_fleet(clock, n_replicas=3)
        rng = np.random.default_rng(5)
        replicas = set()
        for _ in range(9):
            router.submit(_rand_obs(rng, 5, 6, *BUCKETS[0]), now=0.0,
                          tenant="alice")
            replicas.update(r.replica for r in router.drain(now=0.0))
        assert len(replicas) == 1

    def test_least_loaded_balances_queued_depth(self):
        clock = _FakeClock()
        router = _stub_fleet(clock, n_replicas=3,
                             routing="least_loaded", deadline_s=100.0)
        rng = np.random.default_rng(6)
        for _ in range(9):
            router.submit(_rand_obs(rng, 5, 6, *BUCKETS[0]), now=0.0)
        depths = [rep.server.queued()
                  for rep in router.replica_set.replicas]
        assert max(depths) - min(depths) <= 1
        assert router.drain(now=0.0)  # leave the fleet clean


# ------------------------------------------------------------- quotas/shed
class TestQuotaShed:
    def test_quota_shed_is_deterministic_and_refills(self):
        clock = _FakeClock()
        router = _stub_fleet(clock, n_replicas=2, quota_rps=2.0,
                             quota_burst=2.0, shed_enabled=True)
        rng = np.random.default_rng(7)
        obs = _rand_obs(rng, 5, 6, *BUCKETS[0])
        for _ in range(5):
            router.submit(obs, now=0.0, tenant="t0")
        out = router.drain(now=0.0)
        shed = [r for r in out if r.source == "shed"]
        assert len(shed) == 3  # burst of 2 admitted
        assert all(r.reason == "quota" and r.action is None
                   for r in shed)
        # untenanted traffic is quota-exempt
        fid = router.submit(obs, now=0.0)
        assert any(r.request_id == fid and r.source == "policy"
                   for r in router.drain(now=0.0))
        # tokens refill with (submitted) time: 1 s at 2/s -> 2 tokens
        router.submit(obs, now=1.0, tenant="t0")
        router.submit(obs, now=1.0, tenant="t0")
        third = router.submit(obs, now=1.0, tenant="t0")
        out = router.drain(now=1.0)
        assert [r.source for r in out
                if r.request_id == third] == ["shed"]
        assert sum(1 for r in out if r.source == "policy") == 2

    def test_shed_fires_before_saturated_fallback(self):
        """THE ordering pin (acceptance): shedding replaces the
        replica's `saturated` heuristic fallback — with shed on, the
        fallback counter for `saturated` must stay zero; with shed off
        the legacy fallback path is untouched."""
        clock = _FakeClock()
        rng = np.random.default_rng(8)
        reqs = [_rand_obs(rng, 5, 6, *BUCKETS[0]) for _ in range(8)]

        router = _stub_fleet(clock, n_replicas=1, shed_enabled=True,
                             max_queue=3, deadline_s=100.0)
        for o in reqs:
            router.submit(o, now=0.0)
        out = router.drain(now=0.0)
        shed = [r for r in out if r.source == "shed"]
        assert len(shed) == 5 and all(r.reason == "overload"
                                      for r in shed)
        rep = router.replica_set.replicas[0]
        assert rep.server.stats.fallback_reasons.get("saturated") is None
        assert not any(r.source == "fallback" for r in out)

        legacy = _stub_fleet(clock, n_replicas=1, shed_enabled=False,
                             max_queue=3, deadline_s=100.0)
        for o in reqs:
            legacy.submit(o, now=0.0)
        out = legacy.drain(now=0.0)
        assert not any(r.source == "shed" for r in out)
        saturated = [r for r in out if r.reason == "saturated"]
        assert len(saturated) == 5  # the pre-fleet behaviour, intact

    def test_overload_shed_refunds_quota_token(self):
        """An overload shed must not burn the tenant's admission budget
        (only served requests spend quota — same invariant as the
        data-error refund path)."""
        clock = _FakeClock()
        router = _stub_fleet(clock, n_replicas=1, quota_rps=1e-9,
                             quota_burst=1.0, shed_enabled=True,
                             max_queue=1, deadline_s=100.0)
        rng = np.random.default_rng(16)
        obs = _rand_obs(rng, 5, 6, *BUCKETS[0])
        router.submit(obs, now=0.0)  # saturate the single replica
        fid = router.submit(obs, now=0.0, tenant="t0")
        out = router.poll(now=0.0)
        assert [r.reason for r in out
                if r.request_id == fid] == ["overload"]
        router.drain(now=0.0)  # free the queue
        # with a ~zero refill rate the only way this is admitted is the
        # overload shed having refunded the burst token
        fid2 = router.submit(obs, now=0.0, tenant="t0")
        assert any(r.request_id == fid2 and r.source == "policy"
                   for r in router.drain(now=0.0))

    def test_seeded_trace_replays_to_identical_decisions(self):
        """Quota/shed/routing decisions are pure functions of the
        submitted timestamps: the same seeded trace through two fresh
        fleets produces the identical decision stream."""
        from ddls_tpu.serve import loadgen

        trace = loadgen.generate_trace(n_requests=40, base_rps=50.0,
                                       seed=3, diurnal_period_s=0.4,
                                       burst_period_s=0.2)
        loadgen.validate_trace(trace)
        rng = np.random.default_rng(9)
        obs = _rand_obs(rng, 5, 6, *BUCKETS[0])

        def run():
            clock = _FakeClock()
            router = _stub_fleet(clock, n_replicas=2, quota_rps=20.0,
                                 quota_burst=4.0, shed_enabled=True,
                                 max_queue=4, deadline_s=0.005)
            stream = []
            for t, tenant in zip(trace["arrival_s"], trace["tenant"]):
                clock.t = float(t)
                router.submit(obs, now=float(t), tenant=tenant)
                stream.extend(router.poll(now=float(t)))
            stream.extend(router.drain(now=float(trace["arrival_s"][-1])))
            return [(r.request_id, r.source, r.reason, r.replica,
                     r.action) for r in stream]

        assert run() == run()


# ------------------------------------------------------- live reconfiguration
class TestHotSwapRefit:
    def test_hot_swap_no_drop_no_degrade(self, model_params):
        """Acceptance pin: a swap answers every already-admitted request
        (policy answers under the OLD params — nothing dropped, nothing
        degraded) and later requests serve the NEW params."""
        from ddls_tpu.serve import PolicyServer

        model, params_a, params_b = model_params
        rng = np.random.default_rng(11)
        bn, be = BUCKETS[0]
        reqs = [_rand_obs(rng, int(rng.integers(2, bn + 1)),
                          int(rng.integers(1, be + 1)), bn, be)
                for _ in range(6)]
        router = _make_fleet(model, params_a, _FakeClock(),
                             n_replicas=2, deadline_s=100.0)
        fids = [router.submit(o, now=0.0) for o in reqs]
        assert router.queued() == len(reqs)  # nothing flushed yet
        router.hot_swap(params_b, now=0.0)
        out = {r.request_id: r for r in router.poll(now=0.0)}
        assert sorted(out) == sorted(fids)
        assert all(r.source == "policy" for r in out.values())
        for rep in router.replica_set.replicas:
            assert rep.server.stats.degraded_transitions == 0
            assert not rep.server.degraded and not rep.server.draining
        solo_a = PolicyServer(model, params_a, buckets=BUCKETS,
                              max_batch=MAX_BATCH, clock=_FakeClock())
        for fid, obs in zip(fids, reqs):
            assert out[fid].action == solo_a.serve_one(obs).action
        # post-swap traffic runs the new checkpoint
        solo_b = PolicyServer(model, params_b, buckets=BUCKETS,
                              max_batch=MAX_BATCH, clock=_FakeClock())
        post = _rand_obs(rng, 6, 7, bn, be)
        fid = router.submit(post, now=0.0)
        resp = next(r for r in router.drain(now=0.0)
                    if r.request_id == fid)
        assert resp.action == solo_b.serve_one(post).action

    def test_close_is_drain_aware_and_idempotent(self):
        from ddls_tpu.serve import PolicyServer

        server = PolicyServer(None, {}, buckets=BUCKETS,
                              max_batch=MAX_BATCH, deadline_s=100.0,
                              apply_fn=_stub_apply, clock=_FakeClock())
        rng = np.random.default_rng(12)
        ids = [server.submit(_rand_obs(rng, 5, 6, *BUCKETS[0]), now=0.0)
               for _ in range(2)]
        out = server.close(now=0.0)
        assert sorted(r.request_id for r in out) == sorted(ids)
        assert all(r.source == "policy" for r in out)
        assert server.close(now=0.0) == []  # idempotent
        with pytest.raises(RuntimeError, match="closed"):
            server.submit(_rand_obs(rng, 5, 6, *BUCKETS[0]), now=0.0)

    def test_router_close_latches_like_policy_server(self):
        """Router.close mirrors the PolicyServer contract: idempotent,
        answers everything admitted, and post-close submits RAISE
        instead of being silently recorded as overload sheds."""
        clock = _FakeClock()
        router = _stub_fleet(clock, n_replicas=2, deadline_s=100.0)
        rng = np.random.default_rng(18)
        obs = _rand_obs(rng, 5, 6, *BUCKETS[0])
        fid = router.submit(obs, now=0.0)
        out = router.close(now=0.0)
        assert [r.request_id for r in out] == [fid]
        assert out[0].source == "policy"
        assert router.close(now=0.0) == []  # idempotent
        shed_before = dict(router.registry.counter_items()).get(
            "fleet.shed", 0)
        with pytest.raises(RuntimeError, match="closed"):
            router.submit(obs, now=0.0)
        assert dict(router.registry.counter_items()).get(
            "fleet.shed", 0) == shed_before

    def test_swap_params_drains_under_old_params_first(self,
                                                       model_params):
        from ddls_tpu.serve import PolicyServer

        model, params_a, params_b = model_params
        rng = np.random.default_rng(13)
        obs = _rand_obs(rng, 5, 6, *BUCKETS[0])
        solo_a = PolicyServer(model, params_a, buckets=BUCKETS,
                              max_batch=MAX_BATCH, clock=_FakeClock())
        expected = solo_a.serve_one(obs).action
        server = PolicyServer(model, params_a, buckets=BUCKETS,
                              max_batch=MAX_BATCH, deadline_s=100.0,
                              clock=_FakeClock())
        rid = server.submit(obs, now=0.0)
        server.swap_params(params_b, now=0.0)
        out = server.poll(now=0.0)
        assert [(r.request_id, r.action) for r in out] == [(rid, expected)]

    def test_refit_buckets_from_observed_sizes(self):
        clock = _FakeClock()
        router = _stub_fleet(clock, n_replicas=2, deadline_s=100.0)
        rng = np.random.default_rng(14)
        # the population is small graphs only: the fitted ladder should
        # shrink below the configured (16, 28) top bucket
        fids = [router.submit(_rand_obs(rng, int(rng.integers(3, 7)),
                                        int(rng.integers(3, 9)),
                                        *BUCKETS[0]), now=0.0)
                for _ in range(12)]
        specs = router.refit_buckets(n_buckets=2, now=0.0)
        assert specs[-1][0] <= 8 and specs[-1][1] <= 12
        out = router.poll(now=0.0)  # queued requests answered pre-refit
        assert sorted(r.request_id for r in out) == sorted(fids)
        assert all(r.source == "policy" for r in out)
        for rep in router.replica_set.replicas:
            assert rep.server.bucketer.buckets == specs
        # the new ladder still serves (and overflows past its new top
        # go to the fallback, not a crash)
        fid = router.submit(_rand_obs(rng, 5, 6, *BUCKETS[0]), now=0.0)
        assert any(r.request_id == fid and r.source == "policy"
                   for r in router.drain(now=0.0))


# ----------------------------------------------------------------- autoscale
class TestAutoscale:
    def test_decisions_reproducible_from_counter_snapshots(self):
        """Acceptance pin: decisions replay identically from a fixed
        (JSON round-tripped) snapshot sequence — scaling history is
        reconstructable from a telemetry dump."""
        from ddls_tpu.serve import Autoscaler, AutoscaleConfig

        cfg = AutoscaleConfig(min_replicas=1, max_replicas=3,
                              target_p99_ms=50.0, queue_high=4.0,
                              queue_low=1.0, cooldown=2)
        snaps = [
            {"replicas": 1, "queued_total": 0, "p99_latency_ms": 80.0,
             "batch_occupancy": 0.9},           # p99 breach -> up
            {"replicas": 2, "queued_total": 20, "p99_latency_ms": 20.0,
             "batch_occupancy": 0.9},           # cooldown holds
            {"replicas": 2, "queued_total": 20, "p99_latency_ms": 20.0,
             "batch_occupancy": 0.9},           # cooldown holds
            {"replicas": 2, "queued_total": 20, "p99_latency_ms": 20.0,
             "batch_occupancy": 0.9},           # queue breach -> up
            {"replicas": 3, "queued_total": 30, "p99_latency_ms": 20.0,
             "batch_occupancy": 0.9},           # cooldown
            {"replicas": 3, "queued_total": 0, "p99_latency_ms": 5.0,
             "batch_occupancy": 0.1},           # cooldown
            {"replicas": 3, "queued_total": 0, "p99_latency_ms": 5.0,
             "batch_occupancy": 0.1},           # idle -> down
            {"replicas": 2, "queued_total": 0, "p99_latency_ms": None,
             "batch_occupancy": None},          # cooldown
        ]
        snaps = json.loads(json.dumps(snaps))  # storage round trip

        def run():
            a = Autoscaler(cfg)
            return [tuple(a.decide(s)) for s in snaps]

        first = run()
        assert first == run()
        assert [d[0] for d in first] == [2, 2, 2, 3, 3, 3, 2, 2]
        assert first[0][1] == "up:p99"
        assert first[3][1] == "up:queue"
        assert first[6][1] == "down:idle"
        # out-of-range fleet size snaps back before anything else
        a = Autoscaler(cfg)
        assert a.decide({"replicas": 9, "queued_total": 0}) == (3, "clamp")

    def test_retired_replica_registry_retained_in_aggregate(self):
        """A scale-down must not lose the traffic the retired replica
        served: its final registry snapshot stays in
        ``registry_snapshots()`` and the exact aggregate."""
        clock = _FakeClock()
        router = _stub_fleet(clock, n_replicas=2, routing="round_robin",
                             deadline_s=100.0)
        rng = np.random.default_rng(17)
        obs = _rand_obs(rng, 5, 6, *BUCKETS[0])
        for _ in range(6):
            router.submit(obs, now=0.0)
        router.drain(now=0.0)
        router.scale_to(1, now=0.0)
        snaps = router.registry_snapshots()
        assert "r1" in snaps  # the retired replica's final snapshot
        assert snaps["aggregate"]["counters"]["serve.requests"] == 6

    def test_warm_replica_hook_runs_on_initial_and_scale_up(self):
        """The warm hook runs for the initial fleet and for every
        autoscale-added replica BEFORE it joins the routing set (a
        scale-up never serves its first batches cold)."""
        from ddls_tpu.serve import build_fleet

        warmed = []
        router = build_fleet(None, {}, n_replicas=2,
                             warm_replica=warmed.append,
                             clock=_FakeClock(), buckets=BUCKETS,
                             max_batch=MAX_BATCH, deadline_s=0.01,
                             apply_fn=_stub_apply)
        assert len(warmed) == 2
        router.scale_to(3)
        assert len(warmed) == 3
        assert warmed[2] is router.replica_set.replicas[-1].server

    def test_controller_closes_the_loop_on_real_fleet_counters(self):
        from ddls_tpu.serve import (Autoscaler, AutoscaleConfig,
                                    AutoscaleController)

        clock = _FakeClock()
        router = _stub_fleet(clock, n_replicas=1, deadline_s=100.0,
                             max_queue=64)
        ctl = AutoscaleController(router, Autoscaler(AutoscaleConfig(
            min_replicas=1, max_replicas=2, queue_high=4.0,
            queue_low=1.0, occupancy_low=2.0, target_p99_ms=1e9,
            cooldown=1)))
        rng = np.random.default_rng(15)
        fids = [router.submit(_rand_obs(rng, 5, 6, *BUCKETS[0]), now=0.0)
                for _ in range(8)]
        d = ctl.step(now=0.0)  # queue depth 8 > high watermark -> up
        assert d.target == 2 and d.reason == "up:queue"
        assert len(router.replica_set.replicas) == 2
        out = router.drain(now=0.0)
        assert sorted(r.request_id for r in out) == sorted(fids)
        assert ctl.step(now=0.0).reason == "cooldown"
        d = ctl.step(now=0.0)  # drained + idle -> down, replica retired
        assert d.target == 1 and d.reason == "down:idle"
        assert len(router.replica_set.replicas) == 1
        # scaling history rode the router's private registry
        counters = dict(router.registry.counter_items())
        assert counters["fleet.autoscale.up"] == 1
        assert counters["fleet.autoscale.down"] == 1


# ------------------------------------------------------------------ loadgen
class TestLoadgen:
    def test_fingerprint_determinism_and_validation(self):
        from ddls_tpu.serve import loadgen

        kwargs = dict(n_requests=64, base_rps=100.0, seed=5,
                      diurnal_period_s=0.4, burst_period_s=0.2)
        a = loadgen.generate_trace(**kwargs)
        b = loadgen.generate_trace(**kwargs)
        loadgen.validate_trace(a)
        assert loadgen.trace_fingerprint(a) == loadgen.trace_fingerprint(b)
        c = loadgen.generate_trace(**{**kwargs, "seed": 6})
        assert (loadgen.trace_fingerprint(c)
                != loadgen.trace_fingerprint(a))
        with pytest.raises(ValueError, match="non-decreasing"):
            loadgen.validate_trace(
                dict(a, arrival_s=np.asarray(a["arrival_s"])[::-1]))
        with pytest.raises(ValueError, match="size_frac"):
            loadgen.validate_trace(
                dict(a, size_frac=np.asarray(a["size_frac"]) + 1.0))

    def test_poisson_trace_is_a_function_of_seed_rate_and_n(self):
        """``diurnal_amplitude=0`` + ``burst_factor=1`` is the plain
        Poisson process: the same (seed, rate, n) gives the bit-same
        arrival trace and fingerprint twice, another seed another
        trace, and the arrivals run at the offered rate."""
        from ddls_tpu.serve import loadgen

        def poisson(seed, rate=400.0, n=2000):
            return loadgen.generate_trace(
                n_requests=n, base_rps=rate, seed=seed,
                diurnal_amplitude=0.0, burst_factor=1.0)

        a, b, c = poisson(1), poisson(1), poisson(2)
        loadgen.validate_trace(a)
        np.testing.assert_array_equal(a["arrival_s"], b["arrival_s"])
        np.testing.assert_array_equal(a["size_frac"], b["size_frac"])
        assert list(a["tenant"]) == list(b["tenant"])
        assert loadgen.trace_fingerprint(a) == loadgen.trace_fingerprint(b)
        assert len(loadgen.trace_fingerprint(a)) == 16
        assert not np.array_equal(a["arrival_s"], c["arrival_s"])
        # 2,000 Exp(1/400) gaps: the mean is within 5 sigma / sqrt(n)
        gaps = np.diff(np.concatenate([[0.0], a["arrival_s"]]))
        assert gaps.mean() == pytest.approx(1 / 400.0, rel=5 / 2000 ** 0.5)
        assert (loadgen.generate_trace(n_requests=2000, base_rps=400.0,
                                       seed=1)["arrival_s"][-1]
                != a["arrival_s"][-1])  # the shaped default is another load

    def test_loadgen_selftest_script(self):
        """CI satellite: the trace-schema validator runs as a tier-1
        subprocess (numpy-only — no jax, no TPU probe)."""
        out = subprocess.run(
            [sys.executable, "-m", "ddls_tpu.serve.loadgen",
             "--selftest"],
            capture_output=True, text=True, timeout=300, cwd=REPO)
        assert out.returncode == 0, out.stderr[-2000:]
        payload = json.loads(out.stdout.strip().splitlines()[-1])
        assert payload["selftest"] == "ok"
        assert payload["rejected_malformed"] == 4
