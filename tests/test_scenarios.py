"""Scenario subsystem (ISSUE 16): fingerprinted specs, deterministic
failure schedules, the loadgen arrival bridge, and the backend-
conformance harness.

Tier-1 scope: the fast conformance legs (host_native episodes, golden
stats, lint) run IN-process; the full five-leg run (x64 jax/jitted
parity) is the ``slow``-marked subprocess test + the manual
``python scripts/conformance.py`` acceptance run.
"""
import copy
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from ddls_tpu.scenarios import (REGISTRY, ScenarioError, ScenarioSpec,
                                canonical_spec, failures_spec, get_spec,
                                multi_channel_spec, resolve_failure_windows,
                                spec_fingerprint, validate_spec)
from ddls_tpu.scenarios.failures import inflate_duration

pytestmark = pytest.mark.scenario

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


# ------------------------------------------------------------ spec basics
def test_fingerprint_roundtrip():
    for factory in REGISTRY.values():
        spec = factory()
        validate_spec(spec)
        again = ScenarioSpec.from_json(spec.to_json())
        assert again == spec
        assert spec_fingerprint(again) == spec_fingerprint(spec)


def test_fingerprint_sensitive_to_every_value():
    base = spec_fingerprint(canonical_spec())
    edited = canonical_spec()
    edited.topology["kwargs"]["num_channels"] = 2
    assert spec_fingerprint(edited) != base
    edited = canonical_spec()
    edited.seed = 1
    assert spec_fingerprint(edited) != base


def test_registry_names_and_file_resolution(tmp_path):
    assert sorted(REGISTRY) == ["canonical", "failures", "multi_channel"]
    spec = failures_spec()
    path = tmp_path / "spec.json"
    path.write_text(spec.to_json())
    assert get_spec(str(path)) == spec
    with pytest.raises(ScenarioError, match="unknown scenario"):
        get_spec("no_such_scenario")


def test_from_json_rejects_unknown_fields():
    data = json.loads(canonical_spec().to_json())
    data["surprise"] = 1
    with pytest.raises(ScenarioError, match="unknown ScenarioSpec"):
        ScenarioSpec.from_json(json.dumps(data))


@pytest.mark.parametrize("mutate, match", [
    (lambda s: s.arrival.update(kind="bursty"), "arrival.kind"),
    (lambda s: s.sla.update(kind="exotic"), "sla.kind"),
    (lambda s: setattr(s, "job_sampling_mode", "remove_twice"),
     "job_sampling_mode"),
    (lambda s: s.device_speeds.update({"0-0-0": 0.0}), "must be > 0"),
    (lambda s: s.failures.update({"n_preempt": 1, "surprise": 2}),
     "unknown failures keys"),
    (lambda s: s.failures.update(
        {"windows": [{"kind": "meteor", "resource": 0,
                      "t0": 1.0, "t1": 2.0}]}), "window kind"),
    (lambda s: s.failures.update(
        {"windows": [{"kind": "worker_preempt", "resource": 0,
                      "t0": 5.0, "t1": 2.0}]}), "t0 < t1"),
])
def test_validator_rejections(mutate, match):
    spec = canonical_spec()
    mutate(spec)
    with pytest.raises(ScenarioError, match=match):
        validate_spec(spec)


# ------------------------------------------------------- failure schedule
def test_failure_schedule_bit_reproducible():
    spec = failures_spec()
    a = resolve_failure_windows(spec, n_servers=8, n_channels=28)
    b = resolve_failure_windows(copy.deepcopy(spec), n_servers=8,
                                n_channels=28)
    assert a == b  # exact, including every float bit
    assert len(a) == 4
    for w, nxt in zip(a, a[1:]):
        assert w["t1"] <= nxt["t0"]  # globally non-overlapping
    # any spec edit re-keys the schedule (the rng seed includes the
    # fingerprint)
    rekeyed = failures_spec()
    rekeyed.seed = 2
    assert resolve_failure_windows(rekeyed, 8, 28) != a


def test_explicit_overlapping_windows_rejected():
    spec = canonical_spec()
    spec.failures = {"windows": [
        {"kind": "worker_preempt", "resource": 0, "t0": 10.0, "t1": 50.0},
        {"kind": "channel_straggle", "resource": 1, "t0": 40.0,
         "t1": 80.0, "slowdown": 2.0}]}
    with pytest.raises(ScenarioError, match="non-overlapping"):
        resolve_failure_windows(spec, 8, 28)


# ------------------------------------------------------- loadgen arrivals
def test_loadgen_interarrival_deterministic():
    from ddls_tpu.demands.distributions import LoadgenInterarrival

    kw = dict(n_requests=64, base_rps=1.0, seed=7, time_scale=600.0)
    a, b = LoadgenInterarrival(**kw), LoadgenInterarrival(**kw)
    assert a.trace_fingerprint == b.trace_fingerprint
    ga = [a.sample() for _ in range(130)]  # cycles past n_requests
    gb = [b.sample() for _ in range(130)]
    assert ga == gb
    assert all(g >= 0.0 for g in ga)
    assert LoadgenInterarrival(**{**kw, "seed": 8}).trace_fingerprint \
        != a.trace_fingerprint


# ------------------------------------------------------ inflation kernels
def test_inflate_duration_hand_computed():
    t0 = np.asarray([10.0]); t1 = np.asarray([20.0])
    # full preemption (rate 0): work stops for the overlap, resumes after
    rate = np.asarray([0.0])
    assert inflate_duration(0.0, 15.0, 1.0, t0, t1, rate,
                            [True]) == pytest.approx(25.0)
    # window misses the op entirely: nominal
    assert inflate_duration(0.0, 5.0, 1.0, t0, t1, rate, [True]) == 5.0
    # not-affected resource: nominal
    assert inflate_duration(0.0, 15.0, 1.0, t0, t1, rate, [False]) == 15.0
    # straggler at rate 0.5: remaining work inside the window takes 2x;
    # 10s of work left at t=10, window capacity 10*0.5=5 -> 5s spill
    rate = np.asarray([0.5])
    assert inflate_duration(0.0, 20.0, 1.0, t0, t1, rate,
                            [True]) == pytest.approx(25.0)
    # slow device (r0=0.5) doubles everything before windows apply
    assert inflate_duration(0.0, 4.0, 0.5, t0[:0], t1[:0], rate[:0],
                            []) == pytest.approx(8.0)


def test_inflate_duration_host_vs_jax_agree():
    import jax.numpy as jnp

    from ddls_tpu.scenarios.failures import inflate_duration_jax

    rng = np.random.default_rng(3)
    t0 = np.sort(rng.uniform(0.0, 100.0, 4))
    t1 = t0 + rng.uniform(1.0, 10.0, 4)
    rate = np.asarray([0.0, 0.5, 0.25, 0.0])
    for _ in range(25):
        t_start = float(rng.uniform(0.0, 90.0))
        nominal = float(rng.uniform(0.1, 50.0))
        r0 = float(rng.choice([0.5, 0.8, 1.0, 1.25]))
        affects = [bool(b) for b in rng.integers(0, 2, 4)]
        host = inflate_duration(t_start, nominal, r0, t0, t1, rate,
                                affects)
        dev = inflate_duration_jax(
            jnp.asarray(t_start), jnp.asarray(nominal), jnp.asarray(r0),
            jnp.asarray(t0), jnp.asarray(t1), jnp.asarray(rate),
            [jnp.asarray(b) for b in affects])
        # f32 under the test mesh (no x64): compare at f32 resolution
        assert float(dev) == pytest.approx(host, rel=1e-5)


# ------------------------------------------------- episodes + conformance
def _run_failure_episode(max_decisions=40):
    from ddls_tpu.scenarios.conformance import (build_env,
                                                run_recorded_episode)

    env = build_env(failures_spec(), "host")
    events, actions = run_recorded_episode(env, seed=0,
                                           max_decisions=max_decisions)
    return events, actions


def test_failure_events_deterministic_and_adjusted():
    events_a, actions_a = _run_failure_episode()
    events_b, actions_b = _run_failure_episode()
    assert actions_a == actions_b
    fails_a = [e for e in events_a
               if e["kind"] in ("worker_preempted", "channel_degraded")]
    fails_b = [e for e in events_b
               if e["kind"] in ("worker_preempted", "channel_degraded")]
    assert fails_a and fails_a == fails_b
    # emitted t IS the window's t0 — the pure-(seed, spec) schedule
    spec = failures_spec()
    windows = resolve_failure_windows(spec, 8, 28)
    by_t0 = {w["t0"]: w for w in windows}
    for e in fails_a:
        w = by_t0[e["t0"]]
        assert e["t"] == w["t0"] and e["t1"] == w["t1"]
        assert e["rate"] == w["rate"]


def test_conformance_fast_legs_green_on_all_registry_specs():
    """host_native (bit-exact episodes), golden stats, and the lint
    backend-surface rule — in-process; the jitted leg needs x64 and
    rides the CLI tests below (the canonical spec in tier-1, the whole
    registry slow-marked)."""
    from ddls_tpu.native import native_available
    from ddls_tpu.scenarios.conformance import run_conformance

    for name in sorted(REGISTRY):
        report = run_conformance(get_spec(name), seed=0, max_decisions=30,
                                 legs=("host_native", "golden", "lint"))
        assert report["ok"], report
        statuses = {l["leg"]: l["status"] for l in report["legs"]}
        assert statuses["golden"] == "ok"
        assert statuses["lint"] == "ok"
        if native_available():
            assert statuses["host_native"] == "ok", report


def test_canonical_spec_matches_golden_stats():
    from ddls_tpu.scenarios.conformance import golden_stats_leg

    leg = golden_stats_leg(canonical_spec())
    assert leg["status"] == "ok", leg.get("mismatches")


def test_multi_channel_spec_excludes_jitted_leg_with_reason():
    from ddls_tpu.scenarios.conformance import _jitted_supported

    ok, reason = _jitted_supported(multi_channel_spec())
    assert not ok and "single-channel" in reason
    assert _jitted_supported(canonical_spec()) == (True, None)


def test_conformance_canonical_every_leg_green():
    """The canonical spec through EVERY default leg, as a user runs it:
    scripts/conformance.py in its own process (it pins x64, which the
    host_jitted leg needs), exit 0, each leg of DEFAULT_LEGS reported
    once and ok, skipped or unavailable."""
    from ddls_tpu.scenarios.conformance import DEFAULT_LEGS

    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "conformance.py"),
         "--spec", "canonical", "--json", "--max-decisions", "30"],
        capture_output=True, text=True, timeout=300, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["ok"] and [r["spec"]["name"] for r in doc["specs"]] == [
        "canonical"]
    legs = doc["specs"][0]["legs"]
    assert [leg["leg"] for leg in legs] == list(DEFAULT_LEGS)
    for leg in legs:
        assert leg["status"] in ("ok", "skipped", "unavailable"), leg
        # x64 is on in that process and the canonical fabric is the
        # dense one: the jitted leg really ran
        assert leg["leg"] != "host_jitted" or leg["status"] == "ok", leg


@pytest.mark.slow
def test_conformance_cli_full_legs():
    """The acceptance run: scripts/conformance.py (which pins x64 in its
    own process) exits 0 across the whole registry with every leg ok or
    skipped-with-reason."""
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "scripts", "conformance.py"),
         "--json", "--max-decisions", "120"],
        capture_output=True, text=True, timeout=2400, cwd=REPO)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    doc = json.loads(proc.stdout)
    assert doc["ok"]
    for rep in doc["specs"]:
        for leg in rep["legs"]:
            assert leg["status"] in ("ok", "skipped", "unavailable"), leg


# ----------------------------------------- training-loop scenario plumbing
_LOOP_ENV_CLS = ("ddls_tpu.envs.partitioning_env."
                 "RampJobPartitioningEnvironment")
_LOOP_TINY_MODEL = {"fcnet_hiddens": [16],
                    "custom_model_config": {"out_features_msg": 4,
                                            "out_features_hidden": 8,
                                            "out_features_node": 4,
                                            "out_features_graph": 4}}


def _loop_overrides(dataset_dir):
    """Tiny-workload env_config overrides: each key REPLACES the spec's
    top-level key wholesale (the loops.py merge contract — never a deep
    merge)."""
    return dict(
        jobs_config={
            "path_to_files": dataset_dir,
            "job_interarrival_time_dist": {
                "_target_": "ddls_tpu.demands.distributions.Fixed",
                "val": 100.0},
            "replication_factor": 4,
            "job_sampling_mode": "remove_and_repeat",
            "num_training_steps": 2},
        max_partitions_per_op=4,
        max_simulation_run_time=5e4,
        pad_obs_kwargs={"max_nodes": 32, "max_edges": 64})


def _scenario_loop(scenario, dataset_dir):
    from ddls_tpu.train import make_epoch_loop

    return make_epoch_loop(
        "ppo",
        path_to_env_cls=_LOOP_ENV_CLS,
        env_config=_loop_overrides(dataset_dir),
        model=_LOOP_TINY_MODEL,
        algo_config={"train_batch_size": 4, "sgd_minibatch_size": 2,
                     "num_sgd_iter": 1, "num_workers": 2},
        num_envs=2, rollout_length=2, n_devices=1,
        use_parallel_envs=False, evaluation_interval=None, seed=0,
        loop_mode="pipelined", scenario=scenario)


def test_epoch_loop_canonical_scenario_is_byte_identical(dataset_dir):
    """ISSUE 20 satellite: make_epoch_loop(scenario=...) resolves the
    spec into env construction kwargs with explicit env_config keys
    replacing spec keys wholesale, and records the fingerprint. The
    canonical spec builds runtime=None, so the resulting env_config is
    EXACTLY the hand-built dict — no scenario_runtime key, byte-
    identical env path."""
    from ddls_tpu.scenarios import env_kwargs

    spec = canonical_spec()
    loop = _scenario_loop("canonical", dataset_dir)
    try:
        expected = dict(env_kwargs(spec))
        expected.update(_loop_overrides(dataset_dir))
        assert loop.env_config == expected
        assert "scenario_runtime" not in loop.env_config
        assert loop.scenario_fingerprint == spec_fingerprint(spec)
    finally:
        loop.close()


def test_epoch_loop_failure_scenario_carries_runtime(dataset_dir):
    """A failure spec's resolved ScenarioRuntime rides env_config into
    every constructed env (cluster.scenario_runtime), keyed by the spec
    fingerprint; a spec instance is accepted as well as a name."""
    spec = failures_spec()
    loop = _scenario_loop(spec, dataset_dir)
    try:
        rt = loop.env_config["scenario_runtime"]
        assert rt is not None
        assert rt.fingerprint == spec_fingerprint(spec)
        env = loop.vec_env.envs[0]
        assert env.cluster.scenario_runtime is rt
        assert loop.scenario_fingerprint == spec_fingerprint(spec)
    finally:
        loop.close()
