"""``run.py`` itself: it refuses a CPU backend, it is useless without
the program beside it, and each path runs end to end at a tiny
test-local size with the accelerator check patched HERE (the harness
has no option for it), printing the contract's one-line object last."""
import json
import os
import shutil
import subprocess
import sys

import pytest

import bench_tiny
from benchmarks import harness, run
from benchmarks.paths import serve, train

REPO = bench_tiny.REPO


@pytest.fixture()
def restore_process_state():
    """A run changes process-wide settings a fresh process would not
    mind: the compile cache's threshold, global telemetry, argv-free
    environment. Put them back for the tests that follow."""
    import jax

    from ddls_tpu import telemetry

    threshold = jax.config.jax_persistent_cache_min_compile_time_secs
    env = os.environ.get("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS")
    intervals = telemetry.registry().record_intervals
    yield
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      threshold)
    if env is None:
        os.environ.pop("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", None)
    else:
        os.environ["JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS"] = env
    telemetry.disable()
    telemetry.reset()
    telemetry.registry().record_intervals = intervals


@pytest.fixture()
def tiny_tree(tmp_path, monkeypatch, restore_process_state):
    root = bench_tiny.build_tree(str(tmp_path / "tree"))
    real_load = harness.load_cell
    monkeypatch.setattr(harness, "load_cell",
                        lambda name: real_load(name, root=root))
    monkeypatch.setattr(harness, "require_chips", lambda chips: None)
    monkeypatch.setattr(harness, "peaks_for", lambda kind: {
        "bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11})
    monkeypatch.setattr(harness, "OUT_DIR", str(tmp_path / "out"))
    return root


def _result(capsys, argv):
    assert run.main(argv) == 0
    lines = capsys.readouterr().out.strip().splitlines()
    return json.loads(lines[-1]), lines[:-1]


def _argv(cell, trace):
    return ["--workload", cell, "--seed", "3", "--seconds", "2",
            "--trace", str(trace)]


def _check_line(result, traced):
    wanted = {"correct", "attempted", "failed", "metrics", "device"}
    assert set(result) - ({"breakdown"} if traced else set()) == wanted
    assert result["correct"] is True
    assert result["failed"] == 0 and result["attempted"] > 0
    assert set(result["device"]) >= {"platform", "kind", "count",
                                     "memory_peak_bytes"}
    for value in result["metrics"].values():
        assert set(value) == {"value", "unit"}
        assert isinstance(value["value"], float)


def test_refuses_a_cpu_backend(capsys, restore_process_state):
    with pytest.raises(SystemExit) as exc:
        run.main(_argv("ramp32_dev.train_fused", 0))
    assert "accelerator" in str(exc.value)
    out = capsys.readouterr().out
    assert '"correct"' not in out


def test_refuses_fewer_chips_than_the_cell_asks(monkeypatch):
    import jax

    class Chip:
        platform, device_kind = "tpu", "TPU v5 lite"

    monkeypatch.setattr(jax, "devices", lambda: [Chip()])
    harness.require_chips(1)
    with pytest.raises(SystemExit, match="needs 4 chips"):
        harness.require_chips(4)


def test_peak_is_the_allocators_peak_plus_the_programs_scratch():
    stats = [{"peak_bytes_in_use": 100}, {"peak_bytes_in_use": 300}, {}]
    assert harness.device_facts(stats)["memory_peak_bytes"] == 300
    assert harness.device_facts(stats, 50)["memory_peak_bytes"] == 350


def test_host_collection_has_no_single_program_to_read_scratch_of():
    class Loop:
        fused = None

    assert train.program_scratch_bytes(Loop()) == 0


def test_useless_without_the_program(tmp_path):
    """BENCHMARK.json and the files under ``paths`` alone: no result,
    exit code not 0."""
    shutil.copy(os.path.join(REPO, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(REPO, "benchmarks"),
                    tmp_path / "benchmarks",
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    out = subprocess.run(
        [sys.executable, "benchmarks/run.py", "--workload",
         "ramp32_dev.train_fused", "--seed", "0", "--seconds", "1",
         "--trace", "0"], cwd=tmp_path, capture_output=True, text=True,
        timeout=120, env={**os.environ, "PYTHONPATH": ""})
    assert out.returncode != 0
    assert '"correct"' not in out.stdout


def test_unknown_workload_names_the_known_ones():
    with pytest.raises(SystemExit, match="ramp32_dev.train_fused"):
        harness.load_cell("no.such.cell")


def test_peaks_table_knows_the_v5e_and_nothing_it_was_not_told():
    assert harness.peaks_for("TPU v5 lite")["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError, match="no published peaks"):
        harness.peaks_for("TPU v9")


def test_config_that_drifts_from_its_file_is_an_error():
    cfg = {"env_config": {"pad_obs_kwargs": {"max_nodes": 150}},
           "nodes": [{"n": 1}]}
    harness.check_expectations(cfg, {
        "env_config.pad_obs_kwargs.max_nodes": 150, "nodes.0.n": 1})
    with pytest.raises(SystemExit, match="max_nodes"):
        harness.check_expectations(
            cfg, {"env_config.pad_obs_kwargs.max_nodes": 128})


def test_steps_per_s_statistics():
    epochs = [{"env_steps": 256, "start": t, "seconds": s}
              for t, s in ((0.0, 1.0), (1.0, 2.0), (3.0, 4.0))]
    assert train.steps_per_s(epochs, "ratio") == pytest.approx(768 / 7)
    assert train.steps_per_s(epochs, "median_epoch_rate") == 128.0
    # a 5 s window holds two whole epochs and half of the third; the
    # share is continuous where the plain ratio jumps by an epoch
    assert train.steps_per_s(epochs, "window_share",
                             (0.0, 5.0)) == pytest.approx(640 / 5)
    assert train.steps_per_s(epochs, "window_share",
                             (0.0, 7.0)) == pytest.approx(768 / 7)
    assert train.steps_per_s(epochs, "window_share", (0.0, 6.999)) \
        == pytest.approx(768 / 7, rel=1e-3)
    with pytest.raises(ValueError):
        train.steps_per_s(epochs, "mean")


# --------------------------------------------- rehearsals, end to end
def test_host_collected_path_traced(tiny_tree, capsys):
    result, notes = _result(capsys, _argv("tiny.host", 1))
    _check_line(result, traced=True)
    metrics = result["metrics"]
    # spans, counters and the compile meter read on any backend; the
    # device-trace metrics find no device plane on the CPU and are left
    # out rather than faked
    assert {"compile_s", "compiles_in_window", "epoch_wall_p50_s",
            "collect_wall_s"} <= set(metrics)
    assert metrics["compiles_in_window"]["value"] == 0.0
    assert "update_device_s" not in metrics
    assert "busy_s" not in result["device"]
    assert any(n.startswith("[bench] fidelity") and '"ok": true' in n
               for n in notes)


def test_fused_path_end_to_end(tiny_tree, capsys, tmp_path):
    result, notes = _result(capsys, _argv("tiny.fused", 0))
    _check_line(result, traced=False)
    assert set(result["metrics"]) == {"train_env_steps_per_s", "setup_s"}
    assert result["metrics"]["train_env_steps_per_s"]["value"] > 0
    # the CPU's allocator reports no peak: what is left is the scratch
    # of the epoch program, read from its memory analysis
    assert result["device"]["memory_peak_bytes"] > 0
    assert any(n.startswith("[bench] memory") and '"compiles": 0' in n
               for n in notes), "reading the scratch compiled a program"
    # the jitted-episode replay left its verdict for later runs
    marker = tmp_path / "out" / "fidelity_tiny.fused_cpu.json"
    assert json.loads(marker.read_text())["ok"] is True


def test_serve_path_end_to_end(tiny_tree, capsys):
    result, notes = _result(capsys, _argv("tiny.serve", 0))
    _check_line(result, traced=False)
    assert set(result["metrics"]) == {"serve_decisions_per_s",
                                      "serve_p99_ms", "setup_s"}
    # every request answered by the policy: decisions/s IS the rate
    assert result["metrics"]["serve_decisions_per_s"]["value"] == 100.0
    assert result["attempted"] == 200


def test_summarise_counts_only_policy_answers():
    import numpy as np

    obs = {"action_mask": np.array([1, 1, 0])}
    code = serve.SOURCES.index
    out = {"source": np.array([code("policy"), code("fallback:saturated"),
                               code("unanswered"), code("policy")]),
           "action": np.array([0, 1, -1, 2]),
           "latency": np.array([0.002, 0.001, np.nan, 0.004]),
           "arrival_s": np.array([0.1, 0.6, 1.1, 1.6]),
           "sized": [obs] * 4, "duplicates": 0}
    summary = serve.summarise(out, seconds=2.0, percentile=99.0)
    assert summary["attempted"] == 4 and summary["failed"] == 2
    assert summary["decisions_per_s"] == 1.0
    assert summary["decisions_per_s_whole_window"] == 1.0
    assert summary["sources"] == {"policy": 2, "fallback:saturated": 1,
                                  "unanswered": 1}
    assert summary["max_ms"] == pytest.approx(4.0)
    assert summary["pq_ms"] == pytest.approx(4.0)
    assert summary["actions_in_mask"] is False   # action 2 is masked
    assert summary["answered_once"] is True


def test_medians_over_slices_shrug_off_one_stalled_slice():
    """Five slices of 100 requests at 100/s; a stall in the third
    delays 30 requests to 150 ms and sheds 10 to the fallback. The
    whole-window p99 is the stall; the median of the slices is not."""
    import numpy as np

    n = 500
    arrival = (np.arange(n) + 0.5) / 100.0
    latency = np.full(n, 0.005)
    source = np.full(n, serve.SOURCES.index("policy"))
    latency[200:230] = 0.150
    source[230:240] = serve.SOURCES.index("fallback:saturated")
    out = {"source": source, "action": np.zeros(n, dtype=int),
           "latency": latency, "arrival_s": arrival,
           "sized": [{"action_mask": np.array([1])}] * n, "duplicates": 0}
    summary = serve.summarise(out, seconds=5.0, percentile=99.0,
                              subwindows=5, rate_rps=100.0)
    assert summary["failed"] == 10
    assert summary["pq_ms"] == pytest.approx(150.0)
    assert summary["slice_pq_ms"] == [pytest.approx(x) for x in (
        5.0, 5.0, 150.0, 5.0, 5.0)]
    assert summary["pq_ms_median_of_slices"] == pytest.approx(5.0)
    assert summary["slice_policy_share"] == [1.0, 1.0, 0.9, 1.0, 1.0]
    assert summary["decisions_per_s"] == 100.0
    assert summary["decisions_per_s_whole_window"] == 98.0


def test_source_code_names_how_a_request_ended():
    from ddls_tpu.serve.server import ServeResponse

    def resp(source, reason):
        return ServeResponse(0, 0, source, reason, None, 0.0)

    names = [serve.SOURCES[serve.source_code(resp(*sr))] for sr in (
        ("policy", "batched"), ("fallback", "saturated"),
        ("fallback", "degraded"), ("fallback", "brand-new"),
        ("shed", "quota"))]
    assert names == ["policy", "fallback:saturated", "fallback:degraded",
                     "fallback:other", "shed"]


def test_gc_watch_times_collections_by_generation():
    import gc

    with harness.GcWatch() as watch:
        gc.collect()
    summary = watch.summary()
    assert summary["gen2"]["n"] == 1 and summary["gen2"]["max_ms"] > 0
    gc.collect()
    assert watch.summary()["gen2"]["n"] == 1   # unhooked on exit
