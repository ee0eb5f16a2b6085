"""``run.py`` itself: it refuses a CPU backend, it is useless without
the program beside it, and each path runs end to end at a tiny
test-local size with the accelerator check patched HERE (the harness
has no option for it), printing the contract's one-line object last."""
import json
import math
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
    assert set(result) - {"compared"} \
        - ({"breakdown"} if traced else set()) == wanted
    if "compared" in result:     # each number beside its limit, last
        assert list(result)[-1] == "compared"
        for pair in result["compared"].values():
            assert set(pair) == {"value", "limit"}
            assert pair["value"] <= pair["limit"]
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


# ------------------------------------------ the measured set of epochs
SET = (30, 90)          # trinity's measure_epochs
WINDOW = (0.0, 30.0)


def _warming(n=150, lanes=16, cold=0.42, warm=0.17, tau=25.0):
    """``n`` epochs back to back that shorten as a memo warms, shaped
    like ``trinity``'s (16 lanes; ~140 in 30 s)."""
    epochs, t = [], 0.0
    for i in range(n):
        seconds = warm + (cold - warm) * math.exp(-i / tau)
        epochs.append({"env_steps": lanes, "start": t,
                       "seconds": seconds, "cpu_s": 0.005})
        t += seconds
    return epochs


def _stalled(epochs, index, stall=2.5):
    """The same epochs with the host held for ``stall`` s in one."""
    out = [dict(e) for e in epochs]
    out[index]["seconds"] += stall
    for later in out[index + 1:]:
        later["start"] += stall
    return out


def _set_rate(epochs, measure_epochs=SET):
    return train.steps_per_s(train.measured_set(epochs, measure_epochs),
                             "median_epoch_rate")


@pytest.mark.parametrize("index", [0, 10, 29, 30, 31, 60, 88, 89, 90, 120,
                                   149])
def test_a_stall_anywhere_moves_the_sets_median_by_a_rank_at_most(index):
    """2.5 s of a held host in one epoch, before, inside or after the
    set: the median over the set's indices moves by nothing or by one
    rank (< 0.5 %), where the window's own rate (all the work inside it
    over all of its time, which `train_env_steps_per_s` stays) loses
    the stall's share of the window and its warmest epochs: > 2 %. The
    stall is counted where its epoch started inside the window."""
    clean = _warming()
    stalled = _stalled(clean, index)
    in_set = SET[0] <= index < SET[1]
    moved = abs(_set_rate(stalled) / _set_rate(clean) - 1)
    assert moved < 0.005 and (in_set or moved == 0)
    share = [train.steps_per_s(e, "window_share", WINDOW)
             for e in (clean, stalled)]
    in_window = clean[index]["start"] < WINDOW[1]
    if in_window:
        assert share[1] < 0.98 * share[0]
    else:
        assert share[1] == share[0]
    assert train.long_epochs(clean, WINDOW) == []
    assert train.long_epochs(stalled, WINDOW) \
        == ([index] if in_window else [])
    facts = train.window_facts(stalled, *WINDOW, SET)
    assert [s["index"] for s in facts["long_epochs"]] \
        == train.long_epochs(stalled, WINDOW)
    assert harness.read_layer_metric("long_epochs_in_window",
                                     {"window": facts}) == in_window


@pytest.mark.parametrize("held", [90, 91, 140, 150])
def test_the_set_is_read_by_index_whatever_the_window_held_beyond(held):
    epochs = _warming()
    assert train.measured_set(epochs[:held], SET) == epochs[30:90]
    assert _set_rate(epochs[:held]) == _set_rate(epochs)
    # a faster window holds more, warmer epochs: its own rate rises
    # with what it holds, the set's reading does not
    facts = train.window_facts(epochs[:held], 0.0, 30.0, SET)
    assert facts["set"]["median_epoch_rate"] == _set_rate(epochs)
    assert facts["measure_epochs"] == [30, 90]
    assert facts["set"]["wall_s"] == pytest.approx(
        sum(e["seconds"] for e in epochs[30:90]))
    assert facts["set"]["ended_s"] == pytest.approx(epochs[90]["start"])


@pytest.mark.parametrize("held", [1, 30, 89])
def test_a_set_the_window_did_not_complete_reads_nothing(held):
    epochs = _warming()[:held]
    assert train.measured_set(epochs, SET) is None
    facts = train.window_facts(epochs, 0.0, 30.0, SET)
    assert facts["set"] is None
    ctx = {"window": facts}
    assert harness.read_layer_metric("warm_epoch_rate_p50", ctx) is None
    assert harness.read_layer_metric("warm_set_env_steps_per_s",
                                     ctx) is None
    assert harness.read_layer_metric("long_epochs_in_window", ctx) == 0.0


@pytest.mark.parametrize("bad", [(5, 5), (9, 3), (-1, 4)])
def test_measure_epochs_that_name_no_set_are_an_error(bad):
    with pytest.raises(ValueError):
        train.measured_set(_warming(), bad)


def test_long_epochs_pass_three_medians_and_a_whole_second():
    """`olmoe`'s cold first epoch at PR 46 was 8 x its warm median and
    under a second; a 1.2 s epoch among 0.5 s ones is under three
    medians: both bars have to be passed, and the median is the
    window's own epochs'. What passes both is counted whether it is the
    program's (a cold memo, the same index in every run) or the host's:
    the note lists index, seconds and CPU seconds of each."""
    def back_to_back(seconds):
        epochs, t = [], 0.0
        for s in seconds:
            epochs.append({"env_steps": 32, "start": t, "seconds": s,
                           "cpu_s": 0.01})
            t += s
        return epochs

    cold_head = back_to_back([0.88] + [0.107] * 200)
    assert train.long_epochs(cold_head, WINDOW) == []
    slowish = back_to_back([0.5] * 20 + [1.2] + [0.5] * 20)
    assert train.long_epochs(slowish, WINDOW) == []
    held = back_to_back([0.107] * 50 + [1.9, 0.107, 2.9] + [0.107] * 50)
    assert train.long_epochs(held, WINDOW) == [50, 52]
    facts = train.window_facts(held, 0.0, 30.0, None)
    assert "set" not in facts
    assert [(e["index"], e["seconds"], e["cpu_s"])
            for e in facts["long_epochs"]] \
        == [(50, 1.9, 0.01), (52, 2.9, 0.01)]
    assert facts["long_epochs"][0]["began_s"] == pytest.approx(5.35)
    ctx = {"window": facts}
    assert harness.read_layer_metric("long_epochs_in_window", ctx) == 2.0
    for name in ("warm_epoch_rate_p50", "warm_set_env_steps_per_s"):
        assert harness.read_layer_metric(name, ctx) is None
        assert harness.read_layer_metric(name, {}) is None


def test_a_median_in_the_gap_between_two_modes_moves_with_one_hiccup():
    """`trinity`'s epochs fall into modes (all-hit 0.093 s, part-miss
    0.2-0.3 s; my chip runs, PR 47) and its set's median stands in a
    gap (0.217 | 0.247 s): ONE hiccup of 0.1 s inside the set moves the
    median by a whole gap, the set's plain ratio by the hiccup's share
    of the set. Neither is the steadier everywhere, so both are
    read."""
    seconds = [0.093] * 12 + [0.21] * 17 + [0.217, 0.247] \
        + [0.26] * 17 + [0.3] * 12
    epochs, t = [], 0.0
    for s in seconds:
        epochs.append({"env_steps": 16, "start": t, "seconds": s,
                       "cpu_s": 0.0})
        t += s
    hiccup = _stalled(epochs, 15, stall=0.1)    # 0.21 -> 0.31
    whole = (0, len(seconds))
    facts = [train.window_facts(e, 0.0, 30.0, whole)["set"]
             for e in (epochs, hiccup)]
    median = facts[1]["median_epoch_rate"] / facts[0]["median_epoch_rate"]
    ratio = facts[1]["ratio_steps_per_s"] / facts[0]["ratio_steps_per_s"]
    assert median < 0.97 and 0.99 < ratio < 1.0
    assert harness.read_layer_metric(
        "warm_set_env_steps_per_s",
        {"window": {"set": facts[0]}}) == pytest.approx(
            16 * len(seconds) / sum(seconds))


class _FakeClock:
    """``time`` for `measure_window`: every epoch takes ``epoch_s``."""

    def __init__(self):
        self.now = 100.0

    def perf_counter(self):
        return self.now

    def process_time(self):
        return 0.0


@pytest.mark.parametrize("measure_epochs, ran", [
    (None, 10),          # no set: epochs while one starts inside 10 s
    ([2, 8], 10),        # a set complete inside the window adds nothing
    ([2, 13], 13),       # the window goes on for the set ...
    ([2, 15], 15),
    ([2, 16], 15),       # ... to 1.5 x --seconds and no further
    ([2, 40], 15)])
def test_window_goes_on_for_an_incomplete_set_to_half_again(
        monkeypatch, measure_epochs, ran):
    clock = _FakeClock()
    monkeypatch.setattr(train, "time", clock)

    def one_second_epoch(loop, rec):
        start = clock.now
        clock.now += 1.0
        return {"start": start, "seconds": 1.0, "cpu_s": 0.0,
                "env_steps": 16, "loss": 0.0}

    monkeypatch.setattr(train, "run_epoch", one_second_epoch)
    epochs, raised, t_window = train.measure_window(
        None, harness.Recorder(), 10.0, 1, measure_epochs)
    assert (len(epochs), raised, t_window) == (ran, 0, 100.0)
    # what the window's own rate reads does not change with the overrun
    assert train.steps_per_s(epochs, "window_share", (t_window, 10.0)) \
        == pytest.approx(16.0)
    if measure_epochs:
        complete = measure_epochs[1] <= ran
        assert (train.measured_set(epochs, measure_epochs) is not None) \
            == complete


def _name_a_set(tiny_tree, measure_epochs):
    """The tiny fused mix with a set of epochs named, as the listed
    cells' mixes name theirs (the other tiny presets' windows, 2 s on a
    busy CPU, name none: nothing is read and nothing is refused)."""
    path = os.path.join(tiny_tree, "benchmarks", "traffic",
                        "tiny_fused.json")
    mix = json.load(open(path))
    mix["measure_epochs"] = measure_epochs
    json.dump(mix, open(path, "w"))


def test_traced_run_whose_set_stays_incomplete_fails_with_the_reason(
        tiny_tree, capsys):
    """The traced run is where the set is read. k1 = 10 ** 6 epochs do
    not fit into 1.5 x 2 s: no result line, the reason names k1 and the
    epochs run. The untraced run of the same mix keeps its result: what
    it reports does not read the set."""
    _name_a_set(tiny_tree, [1, 10 ** 6])
    with pytest.raises(SystemExit) as failed:
        run.main(_argv("tiny.fused", 1))
    reason = str(failed.value)
    assert "measure_epochs [1, 1000000]" in reason
    assert "k1 = 1000000" in reason and "epochs in" in reason
    lines = capsys.readouterr().out.strip().splitlines()
    assert not lines[-1].startswith("{")
    noted, = [json.loads(l[len("[bench] epochs: "):]) for l in lines
              if l.startswith("[bench] epochs: ")]
    assert noted["set"] is None and noted["window_s"] < 2 * 1.5 + 2
    result, _ = _result(capsys, _argv("tiny.fused", 0))
    _check_line(result, traced=False)


def test_fused_path_traced_reads_the_set_and_counts_no_stall(
        tiny_tree, capsys):
    _name_a_set(tiny_tree, [1, 4])
    result, notes = _result(capsys, _argv("tiny.fused", 1))
    _check_line(result, traced=True)
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    noted, = [json.loads(n[len("[bench] epochs: "):]) for n in notes
              if n.startswith("[bench] epochs: ")]
    assert noted["measure_epochs"] == [1, 4]
    rates = sorted(16 / s for s in noted["seconds"][1:4])
    assert metrics["warm_epoch_rate_p50"] == pytest.approx(rates[1]) \
        == noted["set"]["median_epoch_rate"]
    assert metrics["long_epochs_in_window"] == 0.0 == len(noted["long_epochs"])
    assert 0 < noted["in_window"] <= len(noted["seconds"]) >= 4
    assert noted["window_share"] > 0
    assert metrics["warm_set_env_steps_per_s"] == pytest.approx(
        48 / sum(noted["seconds"][1:4])) \
        == noted["set"]["ratio_steps_per_s"]
    assert len(noted["cpu_s"]) == len(noted["seconds"])


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
