"""Episode flight recorder tests (ISSUE 6): the recorder-off hot-path
guard (zero event objects created during env stepping — the same
discipline as test_telemetry's ``test_env_hot_loop_disabled_guard``),
trace capture + JSONL round trip through ``scripts/trace_export.py`` and
``scripts/telemetry_report.py``, cross-backend diffing (seeded host vs
C++ identical; a deliberately perturbed backend pinpointed at its first
divergent event), the worker-process trace merge over the rollout close
ack, and the ``scripts/check_flight_gated.py`` tier-1 guard."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from ddls_tpu.telemetry import flight

pytestmark = pytest.mark.telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_global_flight():
    """Each test starts and ends with the global recorder disabled and
    empty (it is process-global state, like the telemetry registry)."""
    def clean():
        flight.reset()
        flight.disable()
        flight.recorder().detail = False

    clean()
    yield
    clean()


def _tiny_env(dataset_dir, **overrides):
    from ddls_tpu.envs import RampJobPartitioningEnvironment

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
    kwargs.update(overrides)
    return RampJobPartitioningEnvironment(**kwargs)


def _run_episode(env, seed=0, max_decisions=20):
    obs = env.reset(seed=seed)
    rng = np.random.RandomState(seed)
    actions, done = [], False
    while not done and len(actions) < max_decisions:
        valid = np.flatnonzero(np.asarray(obs["action_mask"]))
        action = int(rng.choice(valid))
        obs, _, done, _ = env.step(action)
        actions.append(action)
    return actions


# ------------------------------------------------------------ off guard
def test_recorder_disabled_guard(dataset_dir, monkeypatch):
    """Acceptance guard: with the recorder disabled, env stepping calls
    the emit path zero times — no event objects, no payload dicts."""
    calls = {"n": 0}
    orig = flight.FlightRecorder.emit

    def counting(self, *a, **k):
        calls["n"] += 1
        return orig(self, *a, **k)

    monkeypatch.setattr(flight.FlightRecorder, "emit", counting)
    monkeypatch.setattr(flight, "emit",
                        lambda *a, **k: counting(flight.recorder(),
                                                 *a, **k))

    env = _tiny_env(dataset_dir)
    _run_episode(env, seed=0, max_decisions=4)
    assert calls["n"] == 0
    assert flight.events() == []

    # flipping the switch makes the SAME loop emit the full vocabulary
    flight.enable()
    _run_episode(env, seed=1, max_decisions=6)
    assert calls["n"] > 0
    kinds = {e["kind"] for e in flight.events()}
    assert {"job_arrived", "action_decided", "tick"} <= kinds, kinds
    # this seed places at least one job: the full placement chain fires
    assert {"partitioned", "placed", "mounted", "lookahead"} <= kinds, \
        kinds


def test_recorder_event_order_and_summary(dataset_dir):
    flight.enable()
    env = _tiny_env(dataset_dir)
    _run_episode(env, seed=3, max_decisions=8)
    events = flight.events()
    seqs = [e["seq"] for e in events]
    assert seqs == sorted(seqs) and len(set(seqs)) == len(seqs)
    summ = flight.summarize(events)
    assert summ["n_events"] == len(events)
    decided = summ["by_kind"]["action_decided"]
    assert decided == 8 or env.cluster.is_done()
    # every decided job has a lifecycle row with an arrival
    for ji, row in summ["jobs"].items():
        if "decided" in row:
            assert "arrived" in row, (ji, row)


def test_detail_events_only_with_detail_enabled(dataset_dir):
    flight.enable(detail=False)
    env = _tiny_env(dataset_dir)
    _run_episode(env, seed=3, max_decisions=6)
    assert not any(e["kind"] in flight.DETAIL_KINDS
                   for e in flight.events())
    flight.reset()
    flight.enable(detail=True)
    # fresh cluster (fresh lookahead cache), HOST engine — detail events
    # exist only where the host engine ticks the lookahead itself
    env2 = _tiny_env(dataset_dir, use_native_lookahead=False)
    _run_episode(env2, seed=3, max_decisions=6)
    detail = [e for e in flight.events()
              if e["kind"] in flight.DETAIL_KINDS]
    assert detail, "no op/flow completion detail from the host engine"
    assert all("lt" in e and "job_idx" in e for e in detail)


# ------------------------------------------------- round trip + export
def test_jsonl_roundtrip_export_and_report(dataset_dir, tmp_path):
    flight.enable()
    env = _tiny_env(dataset_dir)
    _run_episode(env, seed=3, max_decisions=8)
    events = flight.drain()
    path = str(tmp_path / "trace.jsonl")
    n = flight.save_jsonl(path, events)
    assert n == len(events)
    loaded = flight.load_jsonl(path)
    assert loaded == events

    # trace_export.py: Chrome-trace JSON with slices + markers
    out_json = str(tmp_path / "trace.perfetto.json")
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "trace_export.py"),
         path, "-o", out_json],
        capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr
    trace = json.load(open(out_json))
    phases = [e.get("ph") for e in trace["traceEvents"]]
    assert "X" in phases and "i" in phases and "M" in phases
    assert trace["otherData"]["n_flight_events"] == len(events)

    # telemetry_report.py: the flight-trace summary section
    res = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "scripts", "telemetry_report.py"), path],
        capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr
    assert "flight trace" in res.stdout
    assert "action_decided" in res.stdout
    assert "blocked by cause" in res.stdout


def test_export_rejects_empty_input(tmp_path):
    empty = tmp_path / "empty.jsonl"
    empty.write_text("")
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "trace_export.py"),
         str(empty)],
        capture_output=True, text=True, timeout=300)
    assert res.returncode == 2


# ------------------------------------------------------- backend diffing
def test_host_vs_native_trace_identical(dataset_dir):
    """Acceptance: a seeded canonical-RAMP episode produces bit-identical
    flight traces on the host and C++ lookahead backends."""
    from ddls_tpu.native import native_available

    if not native_available():
        pytest.skip("C++ lookahead engine unavailable")

    traces = {}
    for backend in ("host", "native"):
        flight.reset()
        flight.enable()
        env = _tiny_env(dataset_dir,
                        use_native_lookahead=(backend == "native"))
        _run_episode(env, seed=7, max_decisions=10)
        traces[backend] = flight.drain()
    a = flight.comparable_events(traces["host"])
    b = flight.comparable_events(traces["native"])
    assert len(a) > 20
    div = flight.first_divergence(a, b)
    assert div is None, flight.format_divergence(div, "host", "native")
    # the context field the diff ignores really did differ: the engines
    # are distinguishable in the raw traces
    assert {e.get("backend") for e in traces["host"]
            if e["kind"] == "lookahead"} <= {"host", "cache"}
    assert "native" in {e.get("backend") for e in traces["native"]
                        if e["kind"] == "lookahead"}


def test_perturbed_backend_first_divergent_event(dataset_dir, tmp_path):
    """Acceptance: a deliberately perturbed lookahead backend is
    pinpointed at its first divergent event — kind, sim-time, payload
    diff — in-process and through scripts/trace_diff.py files mode."""
    flight.enable()
    env_a = _tiny_env(dataset_dir, use_native_lookahead=False)
    actions = _run_episode(env_a, seed=7, max_decisions=10)
    trace_a = flight.drain()

    flight.reset()
    flight.enable()
    env_b = _tiny_env(dataset_dir, use_native_lookahead=False)
    orig = env_b.cluster._run_lookahead

    def perturbed(job):
        jct, comm, comp, busy = orig(job)
        return jct * 1.0001, comm, comp, busy  # the injected bug

    env_b.cluster._run_lookahead = perturbed
    obs = env_b.reset(seed=7)
    for action in actions:
        try:
            obs, _, done, _ = env_b.step(action)
        except ValueError:
            break  # mask diverged post-perturbation
        if done:
            break
    trace_b = flight.drain()

    a = flight.comparable_events(trace_a)
    b = flight.comparable_events(trace_b)
    div = flight.first_divergence(a, b)
    assert div is not None
    assert div["a"]["kind"] == "lookahead"
    assert "jct" in [f[0] for f in div["fields"]]
    text = flight.format_divergence(div, "host", "perturbed")
    assert "lookahead" in text and "jct" in text and "t=" in text

    # the script names the same event from the saved files
    pa, pb = str(tmp_path / "a.jsonl"), str(tmp_path / "b.jsonl")
    flight.save_jsonl(pa, trace_a)
    flight.save_jsonl(pb, trace_b)
    res = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "trace_diff.py"),
         "files", pa, pb],
        capture_output=True, text=True, timeout=300)
    assert res.returncode == 1, res.stdout + res.stderr
    assert "first divergence" in res.stdout
    assert "lookahead" in res.stdout and "jct" in res.stdout


def test_summarize_separates_envs_and_episode_generations():
    """Merged worker traces and auto-reset episodes reuse job_idx; the
    lifecycle table must not conflate them (labels carry the env tag and
    an episode generation bumped on each re-arrival)."""
    evts = [
        {"seq": 0, "kind": "job_arrived", "t": 0.0, "job_idx": 0,
         "env": 0},
        {"seq": 1, "kind": "job_blocked", "t": 1.0, "job_idx": 0,
         "env": 0, "cause": "not_handled"},
        # same idx, other worker
        {"seq": 0, "kind": "job_arrived", "t": 0.0, "job_idx": 0,
         "env": 1},
        {"seq": 1, "kind": "job_completed", "t": 5.0, "job_idx": 0,
         "env": 1, "jct": 5.0},
        # same idx again on env 0: a new episode's job 0
        {"seq": 2, "kind": "job_arrived", "t": 0.0, "job_idx": 0,
         "env": 0},
    ]
    jobs = flight.summarize(evts)["jobs"]
    assert set(jobs) == {"e0:j0", "e1:j0", "e0:j0#1"}
    assert "blocked" in jobs["e0:j0"]
    assert "completed" in jobs["e1:j0"]
    assert jobs["e0:j0#1"] == {"arrived": 0.0, "model": None}
    # single-env single-episode traces keep plain numeric labels
    plain = flight.summarize([
        {"seq": 0, "kind": "job_arrived", "t": 0.0, "job_idx": 3}])
    assert set(plain["jobs"]) == {"3"}


def test_first_divergence_length_and_rtol():
    a = [{"kind": "tick", "t": 1.0, "dt": 0.5}]
    assert flight.first_divergence(a, list(a)) is None
    div = flight.first_divergence(a, [])
    assert div["reason"] == "length" and div["index"] == 0
    b = [{"kind": "tick", "t": 1.0, "dt": 0.5 + 1e-12}]
    assert flight.first_divergence(a, b) is not None
    assert flight.first_divergence(a, b, rtol=1e-9) is None


# --------------------------------------------------- worker trace merge
def test_worker_traces_merge_on_close(dataset_dir):
    """Subprocess env workers mirror the parent's recorder switch and
    their traces ride the close ack into the parent, env-tagged."""
    from ddls_tpu.envs import RampJobPartitioningEnvironment
    from ddls_tpu.rl.rollout import ParallelVectorEnv

    flight.enable()
    env_kwargs = dict(
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
                "val": 1000.0},
            "max_acceptable_job_completion_time_frac_dist": {
                "_target_": "ddls_tpu.demands.distributions.Uniform",
                "min_val": 0.1, "max_val": 1.0, "decimals": 2},
            "replication_factor": 5,
            "job_sampling_mode": "remove_and_repeat",
            "num_training_steps": 50},
        max_partitions_per_op=8, min_op_run_time_quantum=0.01,
        reward_function="job_acceptance",
        reward_function_kwargs={"fail_reward": -1, "success_reward": 1},
        max_simulation_run_time=2e4,
        pad_obs_kwargs={"max_nodes": 64, "max_edges": 256})
    vec = ParallelVectorEnv(RampJobPartitioningEnvironment, env_kwargs,
                            num_envs=2, backend="pipe")
    try:
        vec.reset()
        for _ in range(3):
            vec.step(np.zeros(2, dtype=np.int64))
    finally:
        vec.close()
    events = flight.events()
    assert events, "no worker events merged on close"
    assert {e.get("env") for e in events} == {0, 1}
    assert {"job_arrived", "action_decided"} <= {e["kind"]
                                                 for e in events}


# ------------------------------------------------------ tier-1 guards
def test_check_flight_gated_clean_tree():
    out = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "scripts", "check_flight_gated.py")],
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 0, out.stdout + out.stderr


def test_check_flight_gated_flags_violations(tmp_path):
    bad = tmp_path / "hot_module.py"
    bad.write_text(
        "from ddls_tpu.telemetry import flight as _flight\n"
        "def step(t):\n"
        "    _flight.emit('tick', t=t)\n"          # ungated
        "    if _flight.enabled():\n"
        "        _flight.emit('ok', t=t)\n"         # gated: fine
        "    _flight.enable()\n")                   # switch: forbidden
    out = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "scripts", "check_flight_gated.py"),
         "--paths", str(tmp_path)],
        capture_output=True, text=True, timeout=300)
    assert out.returncode == 1
    assert "hot_module.py:3" in out.stdout
    assert "hot_module.py:6" in out.stdout
    assert "hot_module.py:5" not in out.stdout
    assert "enabled" in out.stdout  # the fix pointer
