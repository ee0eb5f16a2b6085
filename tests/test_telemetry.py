"""Unified telemetry layer tests (ISSUE 3): span/histogram math under an
injected clock, thread-safety, snapshot/reset semantics, the
disabled-path guard on the env hot loop (no metrics, no per-step
allocations — by counter), probe-outcome events, the JSONL sink +
report script, serve stats on telemetry primitives, and env-worker
counters crossing the process boundary."""
import json
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from ddls_tpu import telemetry

pytestmark = pytest.mark.telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_global_telemetry():
    """Each test starts and ends with the global registry disabled,
    empty, sinkless, and back on the real clock (telemetry is
    process-global state; a leaked injected clock would freeze any later
    `span.elapsed()` loop)."""
    import time

    def clean():
        telemetry.reset()
        telemetry.disable()
        reg = telemetry.registry()
        reg.sink = None
        reg.clock = time.perf_counter

    clean()
    yield
    clean()


# --------------------------------------------------------------- primitives
def test_span_math_under_injected_clock():
    t = {"now": 100.0}
    reg = telemetry.Registry(enabled=True, clock=lambda: t["now"])
    with reg.span("phase") as sp:
        t["now"] += 0.25
    assert sp.duration_s == 0.25
    with reg.span("phase") as sp:
        t["now"] += 0.75
        assert sp.elapsed() == 0.75  # mid-span running clock
    s = reg.span_summaries()["phase"]
    assert s["count"] == 2
    assert s["total_s"] == pytest.approx(1.0)
    assert s["mean_ms"] == pytest.approx(500.0)
    # np.percentile over the window: exact, deterministic
    assert s["p50_ms"] == pytest.approx(500.0)
    assert s["max_ms"] == pytest.approx(750.0)


def test_histogram_buckets_and_window_percentiles():
    h = telemetry.Histogram("lat", buckets=(0.001, 0.01, 0.1))
    samples = (0.0005, 0.005, 0.05, 0.5)
    for v in samples:
        h.observe(v)
    # le-convention fixed buckets + one overflow
    assert h.bucket_counts() == {"0.001": 1, "0.01": 1, "0.1": 1,
                                 "+inf": 1}
    arr = np.asarray(samples, dtype=np.float64)
    for q in (50, 95, 99):
        assert h.percentile(q) == float(np.percentile(arr, q))
    summ = h.summary()
    assert summ["count"] == 4
    assert summ["min"] == 0.0005 and summ["max"] == 0.5


def test_histogram_bucket_only_percentile_fallback():
    h = telemetry.Histogram("x", buckets=(1.0, 2.0, 4.0), window=0)
    for v in [0.5] * 50 + [3.0] * 50:
        h.observe(v)
    p50 = h.percentile(50)
    p99 = h.percentile(99)
    assert 0.5 <= p50 <= 2.0  # inside the buckets bracketing the median
    assert 2.0 <= p99 <= 3.0  # clamped to the observed max


def test_thread_safe_aggregation():
    reg = telemetry.Registry(enabled=True)
    counter = reg.counter("c")
    hist = reg.histogram("h")

    def work():
        for i in range(5000):
            counter.inc()
            hist.observe(0.001 * (i % 7))

    threads = [threading.Thread(target=work) for _ in range(8)]
    for th in threads:
        th.start()
    for th in threads:
        th.join()
    assert counter.value == 8 * 5000
    assert hist.count == 8 * 5000


def test_snapshot_reset_semantics():
    telemetry.enable()
    telemetry.inc("a", 3)
    telemetry.set_gauge("g", 1.5)
    telemetry.observe("h", 0.01)
    with telemetry.span("s"):
        pass
    snap = telemetry.snapshot()
    assert snap["counters"]["a"] == 3
    assert snap["gauges"]["g"] == 1.5
    assert snap["histograms"]["h"]["count"] == 1
    assert snap["spans"]["s"]["count"] == 1
    telemetry.reset()
    assert telemetry.snapshot() == {}
    # registry still enabled after reset: new metrics record fresh
    telemetry.inc("a")
    assert telemetry.snapshot() == {"counters": {"a": 1}}


def test_event_records_counters_by_phase():
    telemetry.enable()
    telemetry.record_event("tpu_probe", phase="attempt", timeout_s=1.0)
    telemetry.record_event("tpu_probe", phase="timeout",
                           wedge_suspected=True)
    c = telemetry.snapshot()["counters"]
    assert c["event.tpu_probe"] == 2
    assert c["event.tpu_probe.attempt"] == 1
    assert c["event.tpu_probe.timeout"] == 1


# ------------------------------------------------------------ disabled path
def test_disabled_api_is_near_noop():
    assert not telemetry.enabled()
    # the span is a shared singleton: zero allocations per call
    assert telemetry.span("x") is telemetry.span("y")
    with telemetry.span("x") as sp:
        pass
    assert sp.elapsed() == 0.0 and sp.duration_s == 0.0
    telemetry.inc("c")
    telemetry.observe("h", 1.0)
    telemetry.set_gauge("g", 2.0)
    telemetry.record_event("k", phase="p")
    assert telemetry.snapshot() == {}


def _tiny_env_kwargs(dataset_dir):
    return dict(
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


def _tiny_env(dataset_dir):
    from ddls_tpu.envs import RampJobPartitioningEnvironment

    return RampJobPartitioningEnvironment(**_tiny_env_kwargs(dataset_dir))


def _step_env(env, n_steps, seed=0):
    obs = env.reset(seed=seed)
    rng = np.random.RandomState(seed)
    for _ in range(n_steps):
        valid = np.flatnonzero(np.asarray(obs["action_mask"]))
        obs, _, done, _ = env.step(int(rng.choice(valid)))
        if done:
            obs = env.reset(seed=seed)
    return obs


def test_env_hot_loop_disabled_guard(dataset_dir, monkeypatch):
    """Acceptance guard: with telemetry disabled the env step loop
    creates NO metrics and performs no per-step telemetry allocations —
    counted by intercepting every metric-creating registry call."""
    reg = telemetry.registry()
    created = {"n": 0}
    for factory in ("counter", "gauge", "histogram", "span"):
        orig = getattr(reg, factory)

        def counting(*a, _orig=orig, **k):
            created["n"] += 1
            return _orig(*a, **k)

        monkeypatch.setattr(reg, factory, counting)

    env = _tiny_env(dataset_dir)
    _step_env(env, 6)
    assert created["n"] == 0
    assert telemetry.snapshot() == {}

    # flipping the switch makes the SAME loop record cache/backend
    # counters (lookahead + partition memo instrumentation is live)
    telemetry.enable()
    _step_env(env, 6, seed=1)
    counters = telemetry.snapshot()["counters"]
    assert any(k.startswith("sim.lookahead_cache.") for k in counters), \
        counters
    assert any(k.startswith("sim.partition_cache.") for k in counters)
    assert any(k.startswith("sim.lookahead.backend.") for k in counters)
    assert created["n"] > 0


def test_fleet_serving_burst_disabled_guard(monkeypatch):
    """ISSUE 8 satellite: the whole fleet stack — Router admission/
    routing/quotas/shedding, loadgen trace generation, hot-swap,
    autoscaler decide + apply — keeps every stat on PRIVATE always-on
    registries and creates ZERO global metrics while telemetry is
    disabled (counted by intercepting the global registry's
    metric-creating calls, like the env hot-loop guard above)."""
    reg = telemetry.registry()
    created = {"n": 0}
    for factory in ("counter", "gauge", "histogram", "span"):
        orig = getattr(reg, factory)

        def counting(*a, _orig=orig, **k):
            created["n"] += 1
            return _orig(*a, **k)

        monkeypatch.setattr(reg, factory, counting)

    import jax.numpy as jnp

    from ddls_tpu.serve import (Autoscaler, AutoscaleConfig,
                                AutoscaleController, build_fleet, loadgen)

    n_actions = 9

    def stub_apply(params, obs):
        b = obs["node_features"].shape[0]
        return jnp.zeros((b, n_actions)), jnp.zeros((b,))

    rng = np.random.RandomState(0)
    obs = {
        "action_set": np.arange(n_actions, dtype=np.int32),
        "action_mask": np.ones(n_actions, np.int32),
        "node_features": rng.uniform(0, 1, (8, 5)).astype(np.float32),
        "edge_features": rng.uniform(0, 1, (12, 2)).astype(np.float32),
        "graph_features": rng.uniform(0, 1, (26,)).astype(np.float32),
        "edges_src": np.zeros(12, np.int32),
        "edges_dst": np.zeros(12, np.int32),
        "node_split": np.array([8], np.int32),
        "edge_split": np.array([12], np.int32),
    }

    class Clock:
        t = 0.0

        def __call__(self):
            return self.t

    assert not telemetry.enabled()
    router = build_fleet(None, {}, n_replicas=2, shed_enabled=True,
                         quota_rps=5.0, clock=Clock(),
                         buckets=[(8, 12)], max_batch=4,
                         deadline_s=0.005, max_queue=8,
                         apply_fn=stub_apply)
    trace = loadgen.generate_trace(n_requests=24, base_rps=100.0,
                                   seed=0, diurnal_period_s=0.12,
                                   burst_period_s=0.06)
    ctl = AutoscaleController(router, Autoscaler(AutoscaleConfig(
        max_replicas=3, cooldown=1)))
    for t, tenant in zip(trace["arrival_s"], trace["tenant"]):
        router.submit(obs, now=float(t), tenant=tenant)
        router.poll(now=float(t))
    ctl.step(now=1.0)
    router.hot_swap({}, now=1.0)
    router.refit_buckets(n_buckets=1, now=1.0)
    router.drain(now=1.0)
    router.summary()
    router.registry_snapshots()
    router.close(now=1.0)

    assert created["n"] == 0
    assert telemetry.snapshot() == {}
    # ...while the PRIVATE registries did record the burst
    assert dict(router.registry.counter_items())["fleet.requests"] == 24


# ------------------------------------------------- profiler annotation
def test_live_span_is_a_ddls_trace_annotation(monkeypatch):
    """One clock: a live span of an annotating registry (the global
    one) is also a ``jax.profiler.TraceAnnotation`` named
    ``ddls.<name>``, entered and left with it — how a profile shows the
    program's spans beside the device's. Disabled telemetry opens none:
    it still hands out the shared NULL_SPAN. An always-on private
    registry (serve's per-request spans) opens none either."""
    import jax

    calls = []

    class FakeAnnotation:
        def __init__(self, name):
            self.name = name

        def __enter__(self):
            calls.append(("enter", self.name))

        def __exit__(self, *exc):
            calls.append(("exit", self.name))

    monkeypatch.setattr(jax.profiler, "TraceAnnotation", FakeAnnotation)
    assert telemetry.TRACE_ANNOTATION_PREFIX == "ddls."
    with telemetry.span("off"):           # disabled: no annotation
        pass
    assert telemetry.span("off") is telemetry.NULL_SPAN and calls == []

    private = telemetry.Registry(enabled=True)
    with private.span("serve.request") as sp:
        pass
    assert calls == [] and sp._annotation is None
    assert private.span_summaries()["serve.request"]["count"] == 1

    reg = telemetry.Registry(enabled=True, annotate_spans=True)
    with reg.span("outer"):
        with reg.span("inner"):
            assert calls == [("enter", "ddls.outer"),
                             ("enter", "ddls.inner")]
    assert calls[2:] == [("exit", "ddls.inner"), ("exit", "ddls.outer")]
    assert reg.span_summaries()["outer"]["count"] == 1

    del calls[:]
    telemetry.enable()
    with telemetry.span("train.collect"):
        pass
    assert calls == [("enter", "ddls.train.collect"),
                     ("exit", "ddls.train.collect")]


def test_span_in_a_process_without_jax_skips_the_annotation(monkeypatch):
    """Env workers never import jax; a span there must not."""
    monkeypatch.delitem(sys.modules, "jax")
    reg = telemetry.Registry(enabled=True, annotate_spans=True)
    with reg.span("worker.step") as sp:
        assert "jax" not in sys.modules
    assert sp._annotation is None
    assert reg.span_summaries()["worker.step"]["count"] == 1


# ----------------------------------------------------------- sink + report
def test_jsonl_sink_and_report_script(tmp_path):
    sink_path = str(tmp_path / "tel.jsonl")
    t = {"now": 0.0}
    telemetry.enable(sink_path=sink_path, clock=lambda: t["now"])
    for dur in (0.01, 0.02, 0.03):
        with telemetry.span("train.collect"):
            t["now"] += dur
    telemetry.record_event("tpu_probe", phase="success",
                           round_trip_ms=116.0)
    telemetry.dump_snapshot(extra={"serve": {"counters": {"x": 1}}})
    records = [json.loads(line)
               for line in open(sink_path).read().splitlines()]
    kinds = [r["type"] for r in records]
    assert kinds.count("span") == 3
    assert kinds.count("event") == 1
    assert kinds[-1] == "snapshot"
    assert records[-1]["data"]["serve"]["counters"]["x"] == 1

    out = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts",
                                      "telemetry_report.py"), sink_path],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    assert "train.collect" in out.stdout
    assert "tpu_probe" in out.stdout
    assert "event.tpu_probe.success" in out.stdout


def test_report_script_missing_file():
    out = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "scripts", "telemetry_report.py"),
         "/nonexistent/tel.jsonl"],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 2


# ------------------------------------------------------------ check script
def test_check_no_bare_timers_clean_tree():
    out = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "scripts", "check_no_bare_timers.py")],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr


def test_check_no_bare_timers_flags_new_pair(tmp_path):
    bad = tmp_path / "hot_module.py"
    bad.write_text("import time\n"
                   "t0 = time.perf_counter()\n"
                   "dt = time.perf_counter() - t0\n")
    out = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "scripts", "check_no_bare_timers.py"),
         "--paths", str(tmp_path)],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 1
    assert "hot_module.py" in out.stdout
    assert "telemetry.span" in out.stdout


# ----------------------------------------------------- serve stats parity
def test_serve_stats_histogram_agrees_with_exact_percentiles():
    from ddls_tpu.serve.server import ServeResponse, ServeStats

    stats = ServeStats()
    rng = np.random.RandomState(0)
    lats = rng.uniform(1e-4, 5e-2, size=200)
    for i, lat in enumerate(lats):
        stats.record_response(ServeResponse(
            request_id=i, action=8,
            source="policy" if i % 3 else "fallback",
            reason="batched" if i % 3 else "saturated",
            bucket_idx=0, latency_s=float(lat)))
    for i in range(10):
        stats.record_flush(fill=(i % 4) + 1, capacity=4,
                           bucket_idx=i % 2,
                           cause="fill" if i % 2 else "deadline")
    s = stats.summary()
    # histogram-derived percentiles == exact np.percentile of the samples
    assert s["p50_latency_ms"] == pytest.approx(
        float(np.percentile(lats, 50)) * 1e3)
    assert s["p99_latency_ms"] == pytest.approx(
        float(np.percentile(lats, 99)) * 1e3)
    assert s["n_requests"] == 0  # record_request not called here
    assert s["n_policy"] + s["n_fallback"] == 200
    assert s["flush_causes"] == {"fill": 5, "deadline": 5}
    occ = stats.per_bucket_occupancy()
    assert set(occ) == {0, 1} and all(0 < v <= 1 for v in occ.values())
    # two ServeStats never share counters (private registries)
    other = ServeStats()
    assert other.n_fallback == 0 and other.summary()["n_flushes"] == 0
    # registry snapshot is the report surface
    snap = stats.registry.snapshot()
    assert snap["histograms"]["serve.latency_s"]["count"] == 200


# ------------------------------------------------- env-worker boundary
def test_env_worker_counters_cross_the_process_boundary(dataset_dir):
    """Host episodes stepped in spawned env workers under
    ``telemetry.enable()``: the workers mirror the parent's switch and
    their sim cache/backend counters ride the close ack into the
    parent's registry (the parent itself stepped no env)."""
    from ddls_tpu.envs import RampJobPartitioningEnvironment
    from ddls_tpu.rl.rollout import ParallelVectorEnv

    telemetry.enable()
    vec = ParallelVectorEnv(RampJobPartitioningEnvironment,
                            _tiny_env_kwargs(dataset_dir), num_envs=2,
                            backend="pipe")
    try:
        obs = vec.reset()
        for _ in range(4):
            actions = [int(np.flatnonzero(np.asarray(o["action_mask"]))[-1])
                       for o in obs]
            obs, _, _ = vec.step(np.asarray(actions))
        before = telemetry.snapshot().get("counters", {})
        assert not any(k.startswith("sim.") for k in before), before
    finally:
        vec.close()
    counters = telemetry.snapshot()["counters"]
    assert any(k.startswith("sim.lookahead_cache.") for k in counters), \
        counters
    assert any(k.startswith("sim.lookahead.backend.") for k in counters)


# =============================== transfer ledger + run ledger (ISSUE 18)
def test_transfer_disabled_guard():
    """Disabled ``telemetry.transfer`` is the shared NullSpan: zero
    metric objects, zero sink records, and ``add()`` swallows any tree
    — the transfer ledger compiles into hot paths for free."""
    assert not telemetry.enabled()
    tr = telemetry.transfer("stage.traj", "h2d")
    assert tr is telemetry.NULL_SPAN
    assert telemetry.transfer("drain.metrics", "d2h") is tr
    with tr as t:
        t.add({"obs": np.zeros(64)})
    assert t.bytes == 0
    reg = telemetry.registry()
    assert not reg._counters and not reg._histograms and not reg._spans
    assert telemetry.snapshot() == {}


def test_transfer_records_bytes_counters_and_sink(tmp_path):
    path = str(tmp_path / "t.jsonl")
    t = {"now": 10.0}
    telemetry.enable(sink_path=path, clock=lambda: t["now"],
                     record_intervals=True)
    with telemetry.transfer("sebulba.params", "l2a") as tr:
        t["now"] += 0.05
        tr.add({"w": np.zeros((4, 4), dtype=np.float32)})   # 64 B
        tr.add([np.zeros(16, dtype=np.float64)])            # 128 B
    assert tr.bytes == 192
    assert tr.duration_s == pytest.approx(0.05)
    snap = telemetry.snapshot()
    assert snap["counters"]["transfer.sebulba.params.calls"] == 1
    assert snap["counters"]["transfer.sebulba.params.bytes"] == 192
    assert snap["counters"]["transfer.l2a.bytes"] == 192
    assert snap["spans"]["transfer.sebulba.params"]["count"] == 1
    # the interval ring carries the transfer like any span (timeline fuel)
    assert any(n == "transfer.sebulba.params"
               for n, _, _ in telemetry.span_intervals())
    telemetry.registry().sink.close()
    recs = [json.loads(line) for line in open(path) if line.strip()]
    tr_recs = [r for r in recs if r.get("type") == "transfer"]
    assert len(tr_recs) == 1
    assert tr_recs[0]["name"] == "sebulba.params"
    assert tr_recs[0]["direction"] == "l2a"
    assert tr_recs[0]["bytes"] == 192
    assert tr_recs[0]["dur_s"] == pytest.approx(0.05)
    # the report script renders the transfer + cross-mesh sections
    out = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "scripts", "telemetry_report.py"), path],
        capture_output=True, text=True, timeout=120)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "transfers (gated ledger" in out.stdout
    assert "sebulba cross-mesh hops" in out.stdout


def test_tree_nbytes_nested_and_without_jax(monkeypatch):
    from ddls_tpu.telemetry import tree_nbytes

    tree = {"a": np.zeros(10, np.float32),
            "b": [np.zeros((2, 2), np.float64),
                  {"c": np.zeros(3, np.int32)}],
            "d": 7}
    want = 40 + 32 + 12  # the int leaf has no nbytes
    assert tree_nbytes(tree) == want
    # container-walk fallback when jax is absent (worker processes that
    # never import it) must agree
    monkeypatch.setitem(sys.modules, "jax", None)
    assert tree_nbytes(tree) == want


# -------------------------------------------------- aggregate_snapshots
def test_aggregate_snapshots_exact_merge():
    from ddls_tpu.telemetry import aggregate_snapshots

    t = {"now": 0.0}
    r1 = telemetry.Registry(enabled=True, clock=lambda: t["now"])
    r2 = telemetry.Registry(enabled=True, clock=lambda: t["now"])
    r1.counter("c").inc(2)
    r2.counter("c").inc(3)
    r2.counter("only2").inc(1)
    r1.gauge("g").set(1.0)
    r2.gauge("g").set(2.5)
    for v in (0.01, 0.02):
        r1.histogram("h").observe(v)
    r2.histogram("h").observe(0.04)
    with r1.span("s"):
        t["now"] += 0.1
    with r2.span("s"):
        t["now"] += 0.3
    merged = aggregate_snapshots([r1.snapshot(), {}, r2.snapshot()])
    assert merged["counters"] == {"c": 5, "only2": 1}
    assert merged["gauges"]["g"] == 3.5
    h = merged["histograms"]["h"]
    assert h["count"] == 3
    assert h["sum"] == pytest.approx(0.07)
    assert h["min"] == 0.01 and h["max"] == 0.04
    # percentiles reconstructed from the merged lifetime buckets
    assert h["p50"] is not None and h["min"] <= h["p50"] <= h["max"]
    s = merged["spans"]["s"]
    assert s["count"] == 2
    assert s["total_s"] == pytest.approx(0.4)
    assert s["mean_ms"] == pytest.approx(200.0)
    # window percentiles cannot merge order-faithfully: dropped
    assert "p50_ms" not in s


def test_aggregate_snapshots_empty_and_partial():
    from ddls_tpu.telemetry import aggregate_snapshots

    assert aggregate_snapshots([]) == {}
    assert aggregate_snapshots([{}, {}]) == {}
    # sections missing entirely (a counters-only registry) merge fine
    merged = aggregate_snapshots([{"counters": {"a": 1}},
                                  {"gauges": {"g": 2.0}}])
    assert merged == {"counters": {"a": 1}, "gauges": {"g": 2.0}}


# ----------------------------------- report robustness on partial sinks
def _run_report(path):
    return subprocess.run(
        [sys.executable,
         os.path.join(REPO, "scripts", "telemetry_report.py"), str(path)],
        capture_output=True, text=True, timeout=120)


def test_report_script_on_sinks_missing_sections(tmp_path):
    """The report renders every sink shape without crashing: events
    only (no ring/flight/snapshot), a fleet-only snapshot, and a
    snapshot whose histograms carry buckets but no window percentiles
    (foreign/merged snapshots)."""
    events_only = tmp_path / "events.jsonl"
    events_only.write_text(
        json.dumps({"type": "event", "kind": "tpu_probe",
                    "phase": "ok", "ts": 1.0}) + "\n")
    out = _run_report(events_only)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "== events ==" in out.stdout

    fleet_only = tmp_path / "fleet.jsonl"
    fleet_only.write_text(json.dumps({
        "type": "snapshot", "ts": 2.0, "data": {"serve": {
            "r0": {"counters": {"serve.requests": 4}},
            "r1": {"counters": {"serve.requests": 6}},
            "aggregate": {"counters": {"serve.requests": 10}}}}}) + "\n")
    out = _run_report(fleet_only)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "serving fleet" in out.stdout

    bucket_only = tmp_path / "buckets.jsonl"
    bucket_only.write_text(json.dumps({
        "type": "snapshot", "ts": 3.0, "data": {"histograms": {
            "h": {"count": 2, "sum": 0.03, "min": 0.01, "max": 0.02,
                  "buckets": {"0.01": 1, "0.025": 1, "+inf": 0}}}}})
        + "\n")
    out = _run_report(bucket_only)
    assert out.returncode == 0, out.stdout + out.stderr
    assert "histograms (last snapshot)" in out.stdout
