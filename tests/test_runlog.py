"""Run ledger + unified timeline tests (ISSUE 18).

Covers the RunLedger lifecycle (manifest/result/snapshot files, the
save→swap→restore of global telemetry state), the Perfetto timeline
builder over synthetic run dirs (span slices, transfer flow arrows,
ring lifecycle async slices, counter tracks), the end-to-end acceptance
path — ledger-enabled pipelined AND sebulba training runs merged into
one trace.
"""
import json
import os
import subprocess
import sys
import time

import pytest

from ddls_tpu import telemetry

pytestmark = pytest.mark.telemetry

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)

from test_fused import ENV_CLS, _TINY_MODEL, _env_config  # noqa: E402


@pytest.fixture(autouse=True)
def _clean_global_telemetry():
    def clean():
        telemetry.reset()
        telemetry.disable()
        reg = telemetry.registry()
        reg.sink = None
        reg.clock = time.perf_counter
        reg.record_intervals = False

    clean()
    yield
    clean()


@pytest.fixture(scope="module")
def runlog_dataset(tmp_path_factory):
    from ddls_tpu.graphs.synthetic import generate_pipedream_txt_files

    d = str(tmp_path_factory.mktemp("runlog_jobs"))
    generate_pipedream_txt_files(d, n_cnn=1, n_translation=1, seed=9)
    return d


# ------------------------------------------------------------ RunLedger
def test_run_ledger_roundtrip(tmp_path):
    from ddls_tpu.telemetry.runlog import RunLedger, load_run_dir

    run_dir = tmp_path / "run"
    ledger = RunLedger(str(run_dir), kind="conformance",
                       argv=["conformance.py", "--spec", "canonical"],
                       config={"num_envs": 4},
                       scenario_fingerprint="abc123")
    assert not telemetry.enabled()
    ledger.open()
    # open() flipped the global registry on with the run-dir sink
    assert telemetry.enabled()
    assert telemetry.registry().sink is not None
    with telemetry.span("bench.run"):
        pass
    with telemetry.transfer("stage.traj", "h2d") as tr:
        tr.add({"x": b""})
    ledger.update_config({"warmed": True})
    ledger.record_result({"metric": "env_steps_per_sec", "value": 42.0})
    ledger.finalize(blocks={"ring": {"stalls": 0}})
    # finalize() restored the prior (disabled, sinkless) state
    assert not telemetry.enabled()
    assert telemetry.registry().sink is None

    run = load_run_dir(str(run_dir))
    man = run["manifest"]
    assert man["kind"] == "conformance"
    assert man["argv"] == ["conformance.py", "--spec", "canonical"]
    assert man["config"]["num_envs"] == 4
    assert man["config"]["warmed"] is True  # update_config rewrote it
    assert man["scenario_fingerprint"] == "abc123"
    assert {"unix", "perf"} <= set(man["clock"])
    assert man["process"] == {"index": 0, "count": 1}
    assert "devices" in man and "git" in man and "host" in man
    assert run["results"] == [{"metric": "env_steps_per_sec",
                               "value": 42.0}]
    snap = run["snapshot"]
    assert snap["blocks"]["ring"] == {"stalls": 0}
    assert snap["snapshot"]["spans"]["bench.run"]["count"] == 1
    assert snap["snapshot"]["counters"]["transfer.stage.traj.calls"] == 1
    # sink records made it to disk (span + transfer at least)
    types = {r.get("type") for r in run["records"]}
    assert {"span", "transfer"} <= types


def test_run_ledger_preserves_active_sink(tmp_path):
    """A ledger opened inside an existing telemetry window must put
    the PRIOR sink back on finalize, not leave its own."""
    from ddls_tpu.telemetry import JsonlSink
    from ddls_tpu.telemetry.runlog import RunLedger

    prior_path = str(tmp_path / "prior.jsonl")
    telemetry.enable(sink_path=prior_path)
    prior_sink = telemetry.registry().sink
    ledger = RunLedger(str(tmp_path / "run"), kind="test").open()
    assert telemetry.registry().sink is not prior_sink
    ledger.finalize()
    assert telemetry.registry().sink is prior_sink
    assert telemetry.enabled()  # prior state was enabled
    prior_sink.close()
    assert isinstance(prior_sink, JsonlSink)


def test_load_run_dir_tolerates_partial(tmp_path):
    from ddls_tpu.telemetry.runlog import load_run_dir

    d = tmp_path / "partial"
    d.mkdir()
    # torn sink line + no manifest/snapshot/result
    (d / "telemetry.jsonl").write_text(
        json.dumps({"type": "span", "name": "s", "dur_s": 0.1,
                    "ts": 5.0}) + "\n{torn")
    run = load_run_dir(str(d))
    # missing pieces stay ABSENT (not empty) — consumers .get() them
    assert "manifest" not in run and "results" not in run
    assert [r["name"] for r in run["records"]] == ["s"]


# ----------------------------------------------------- timeline builder
def _synthetic_run(tmp_path, name="runA", kind="train:pipelined"):
    """A run dir written through the real ledger, with one of every
    record family the timeline renders."""
    from ddls_tpu.telemetry.runlog import RunLedger

    ledger = RunLedger(str(tmp_path / name), kind=kind).open()
    with telemetry.span("train.collect"):
        time.sleep(0.002)
    with telemetry.transfer("sebulba.params", "l2a") as tr:
        tr.add({"w": memoryview(bytes(64))})
    telemetry.record_event("ring_segment", phase="lease", segment=0,
                           generation=1)
    telemetry.record_event("ring_segment", phase="publish", segment=0,
                           generation=1)
    telemetry.record_event("ring_segment", phase="release", segment=0,
                           generation=1)
    telemetry.record_event("ring_segment", phase="stall", segment=None,
                           occupied=3)
    telemetry.record_event("memo_counters", hits=30, misses=10, evicts=1)
    telemetry.record_event("params_age", value=2)
    ledger.finalize()
    return str(tmp_path / name)


def test_timeline_renders_every_track_family(tmp_path):
    from ddls_tpu.telemetry.timeline import write_timeline

    runs = [_synthetic_run(tmp_path, "runA", "train:pipelined"),
            _synthetic_run(tmp_path, "runB", "train:sebulba")]
    out = tmp_path / "timeline.json"
    doc = write_timeline(runs, str(out))
    assert out.exists()
    ev = doc["traceEvents"]
    # two processes, labelled kind:dirname
    procs = {e["args"]["name"] for e in ev
             if e.get("ph") == "M" and e["name"] == "process_name"}
    assert procs == {"train:pipelined:runA", "train:sebulba:runB"}
    # span duration slice
    spans = [e for e in ev if e.get("ph") == "X"
             and e["name"] == "train.collect"]
    assert len(spans) == 2 and all(e["dur"] >= 2e3 for e in spans)
    # transfer slice with bytes + flow arrows to the destination track
    hops = [e for e in ev if e.get("ph") == "X"
            and e["name"] == "sebulba.params"]
    assert len(hops) == 2
    assert all(e["args"]["bytes"] == 64 for e in hops)
    assert any(e.get("ph") == "s" and e.get("cat") == "transfer"
               for e in ev)
    assert any(e.get("ph") == "f" and e.get("cat") == "transfer"
               for e in ev)
    # ring lifecycle async pair + publish instant + flagged stall
    assert any(e.get("ph") == "b" and e.get("cat") == "ring" for e in ev)
    assert any(e.get("ph") == "e" and e.get("cat") == "ring" for e in ev)
    assert any(e.get("ph") == "i" and e["name"] == "RING STALL"
               for e in ev)
    # counter tracks
    memo = [e for e in ev if e.get("ph") == "C"
            and e["name"] == "memo hit rate"]
    assert memo and memo[0]["args"]["hit_rate"] == 0.75
    assert any(e.get("ph") == "C" and e["name"] == "params_age_updates"
               for e in ev)
    # all timestamps share the non-negative global origin
    assert all(e.get("ts", 0) >= 0 for e in ev)
    # otherData carries run manifest correlation keys
    assert [r["pid"] for r in doc["otherData"]["runs"]] == [1, 2]
    assert doc["otherData"]["runs"][0]["memo_counters"]["hits"] == 30


def test_timeline_cli_and_report_delegation(tmp_path):
    run = _synthetic_run(tmp_path, "runC")
    out1 = tmp_path / "t1.json"
    rc = subprocess.run(
        [sys.executable, "-m", "ddls_tpu.telemetry.timeline", run,
         "-o", str(out1)],
        capture_output=True, text=True, timeout=120, cwd=REPO)
    assert rc.returncode == 0, rc.stdout + rc.stderr
    assert json.load(open(out1))["traceEvents"]
    out2 = tmp_path / "t2.json"
    rc = subprocess.run(
        [sys.executable,
         os.path.join(REPO, "scripts", "telemetry_report.py"),
         "--timeline", run, "-o", str(out2)],
        capture_output=True, text=True, timeout=120)
    assert rc.returncode == 0, rc.stdout + rc.stderr
    assert json.load(open(out2))["traceEvents"]


# ------------------------------------- end-to-end: train runs → timeline
def _make_loop(dataset_dir, loop_mode, ledger, **kw):
    from ddls_tpu.train import make_epoch_loop

    defaults = dict(
        path_to_env_cls=ENV_CLS,
        env_config=_env_config(dataset_dir, horizon=6e2),
        model=_TINY_MODEL,
        algo_config={"train_batch_size": 16, "sgd_minibatch_size": 8,
                     "num_sgd_iter": 2, "num_workers": 8,
                     "device_collector": True},
        num_envs=8, rollout_length=2, n_devices=8,
        use_parallel_envs=False, evaluation_interval=None, seed=0,
        loop_mode=loop_mode, metrics_sync_interval=1,
        run_ledger=ledger)
    if loop_mode == "sebulba":
        defaults["sebulba_config"] = {"actor_devices": 4}
    defaults.update(kw)
    return make_epoch_loop("ppo", **defaults)


def test_end_to_end_train_ledgers_to_one_timeline(tmp_path,
                                                  runlog_dataset):
    """THE acceptance path: a ledger-enabled pipelined run and a
    ledger-enabled sebulba run, merged by one command into one Perfetto
    trace with span tracks, ring lifecycle slices, and cross-mesh hops
    carrying byte sizes."""
    from ddls_tpu.telemetry.runlog import RunLedger, load_run_dir
    from ddls_tpu.telemetry.timeline import write_timeline

    run_dirs = []
    for mode in ("pipelined", "sebulba"):
        run_dir = str(tmp_path / f"run_{mode}")
        loop = _make_loop(runlog_dataset, mode,
                          RunLedger(run_dir, kind=f"train:{mode}"))
        if mode == "sebulba":
            assert loop.loop_mode == "sebulba", \
                "split must not have fallen back"
        try:
            for _ in range(3):
                loop.run()
        finally:
            loop.close()
        run_dirs.append(run_dir)
        # ledger restored the disabled default between runs
        assert not telemetry.enabled()
        man = load_run_dir(run_dir)["manifest"]
        assert man["config"]["loop_mode"] == mode
        assert man["config"]["algo"] == "ppo"
        blocks = load_run_dir(run_dir)["snapshot"]["blocks"]
        assert blocks["train"]["epochs"] == 3

    doc = write_timeline(run_dirs, str(tmp_path / "timeline.json"))
    ev = doc["traceEvents"]
    by_pid_names = {}
    for e in ev:
        if e.get("ph") == "X":
            by_pid_names.setdefault(e["pid"], set()).add(e["name"])
    # both runs contributed span tracks from the training loop
    assert len(by_pid_names) == 2
    for names in by_pid_names.values():
        assert "train.collect" in names
    # the sebulba run's cross-mesh hops carry real byte sizes
    hops = [e for e in ev if e.get("ph") == "X"
            and e["name"] in ("sebulba.params", "stage.traj")
            and (e.get("args") or {}).get("bytes")]
    assert hops, "no cross-mesh hop slices with bytes in the trace"
    assert all(e["args"]["bytes"] > 0 for e in hops)
    directions = {e["args"]["direction"] for e in hops}
    assert "l2a" in directions and "a2l" in directions
    # the sebulba device-mode ring left lease→release lifecycles
    assert any(e.get("ph") == "b" and e.get("cat") == "ring" for e in ev)
    assert any(e.get("ph") == "e" and e.get("cat") == "ring" for e in ev)
    # flow arrows pair up (every dispatch has an arrival)
    s_ids = {e["id"] for e in ev if e.get("ph") == "s"}
    f_ids = {e["id"] for e in ev if e.get("ph") == "f"}
    assert s_ids and s_ids == f_ids


def test_pipelined_transfer_free_pin_survives_ledger(runlog_dataset,
                                                     tmp_path):
    """The ledger compiles into the loop but stays a no-op unless its
    run is enabled: with NO ledger and telemetry off, the steady-state
    pipelined epoch stays transfer-free under jax.transfer_guard (the
    ISSUE 18 hot-path contract; mirrors test_train_pipeline's pin with
    the new instrumentation in place)."""
    import jax

    # the canonical pin's shape (test_train_pipeline): host collection,
    # sync interval beyond the run so no drain fires inside the guard
    loop = _make_loop(
        runlog_dataset, "pipelined", None,
        algo_config={"train_batch_size": 16, "sgd_minibatch_size": 8,
                     "num_sgd_iter": 2, "num_workers": 8},
        metrics_sync_interval=1000)
    try:
        loop.run()  # warm epoch: compiles + first-use constant transfers
        with jax.transfer_guard("disallow"):
            loop.run()
    finally:
        loop.close()
