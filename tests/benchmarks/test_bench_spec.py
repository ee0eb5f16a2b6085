"""BENCHMARK.json against the contract it has to meet, and the proof
that the harness is driven by data: a new cell, configuration, mix or
data-sourced per-layer metric is new files plus one entry."""
import importlib
import json
import os
import re

import pytest

import bench_tiny
from benchmarks import harness

REPO = bench_tiny.REPO
BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
FILE_NAME = re.compile(r"^[A-Za-z0-9_.\-/]+$")
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
METRIC_SOURCES = ("device_trace", "program_span", "program_counter",
                  "host_clock")
ALL_METRICS = BENCH["end_to_end"] + BENCH["per_layer"]


def test_top_level_keys_and_limits():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert 1 <= BENCH["run_seconds"] <= 51
    assert 1 <= len(BENCH["paths"]) <= 16
    assert len(BENCH["command"]) <= 32
    assert 2 <= len(BENCH["workloads"]) <= 24
    assert 1 <= len(BENCH["configs"]) <= 24
    assert 1 <= len(BENCH["end_to_end"]) <= 16
    assert 1 <= len(BENCH["per_layer"]) <= 128
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) < 65536
    # the full check of 24 cells has to fit into 43,200 s
    runs = 2 + 14 * 24
    assert (runs * (BENCH["run_seconds"] + 60) + 24 * 180 + 1200
            <= 43200)


def test_command_names_only_files_under_paths():
    assert BENCH["command"][1].startswith(BENCH["paths"][0] + "/")
    for word in BENCH["command"]:
        assert not word.startswith("/") and ".." not in word
        assert 1 <= len(word) <= 200


@pytest.mark.parametrize("entry", BENCH["configs"] + BENCH["workloads"]
                         + ALL_METRICS, ids=lambda e: e["name"])
def test_names_units_and_keys_are_legal(entry):
    assert NAME.match(entry["name"]), entry["name"]
    for key in ("config", "traffic", "moves"):
        if key in entry:
            assert NAME.match(entry[key]), entry[key]
    texts = ["why", "layer"] + ([] if entry in ALL_METRICS else ["source"])
    for key in texts:
        if key in entry:
            text = entry[key]
            assert 1 <= len(text) <= 200, (key, len(text))
            assert "\n" not in text and "\t" not in text
    if entry in ALL_METRICS:
        assert UNIT.match(entry["unit"]), entry["unit"]
        assert entry["better"] in ("lower", "higher")
        assert entry["source"] in METRIC_SOURCES
        allowed = {"name", "unit", "better", "source", "workloads"}
        allowed |= ({"bound"} if entry in BENCH["end_to_end"]
                    else {"layer", "moves"})
        assert set(entry) <= allowed, set(entry) - allowed


def test_entry_keys_are_exactly_the_contracts():
    for cfg in BENCH["configs"]:
        assert set(cfg) == {"name", "source", "file", "reduced", "why"}
        assert len(cfg["reduced"]) <= 16
        assert all(NAME.match(k) for k in cfg["reduced"])
    for cell in BENCH["workloads"]:
        assert set(cell) == {"name", "config", "traffic", "chips", "why"}
        assert cell["chips"] in (1, 4)


def test_no_name_twice_and_each_pair_once():
    for group in (BENCH["configs"], BENCH["workloads"], ALL_METRICS):
        names = [e["name"] for e in group]
        assert len(names) == len(set(names)), names
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(pairs) == len(set(pairs))
    files = [c["file"] for c in BENCH["configs"]]
    assert len(files) == len(set(files))


def test_four_chip_cells_are_at_most_a_quarter_or_one():
    four = [w for w in BENCH["workloads"] if w["chips"] == 4]
    assert len(four) <= max(1, len(BENCH["workloads"]) // 4)


def test_end_to_end_bounds_and_sources():
    names = {m["name"] for m in BENCH["end_to_end"]}
    assert "setup_s" in names
    for metric in BENCH["end_to_end"]:
        assert 0.01 <= metric["bound"] <= 0.1, metric
        assert metric["source"] in ("host_clock", "device_trace")
    for metric in BENCH["per_layer"]:
        assert metric["moves"] in names, metric
        for cell in metric.get("workloads", ()):
            assert cell in {w["name"] for w in BENCH["workloads"]}


def test_same_layer_is_spelt_the_same():
    layers = {m["layer"] for m in BENCH["per_layer"]}
    assert len({l.lower() for l in layers}) == len(layers)
    perf = open(os.path.join(REPO, "PERF.md")).read()
    for layer in layers:
        assert layer in perf, f"PERF.md's list of layers lacks {layer!r}"


def test_files_under_paths_have_legal_names():
    for path in BENCH["paths"]:
        assert FILE_NAME.match(path) and len(path) <= 200
        for base, dirs, files in os.walk(os.path.join(REPO, path)):
            dirs[:] = [d for d in dirs if d not in ("out", "__pycache__")]
            for name in files:
                rel = os.path.relpath(os.path.join(base, name), REPO)
                assert FILE_NAME.match(rel), rel


@pytest.mark.parametrize("cell_entry", BENCH["workloads"],
                         ids=lambda w: w["name"])
def test_every_cell_resolves_to_files_that_exist(cell_entry):
    cell = harness.load_cell(cell_entry["name"])
    assert cell.config["name"] == cell.config_name
    assert cell.traffic["name"] == cell.traffic_name
    path = harness.load_path(cell.path)
    assert callable(path.run)
    reported = {m["name"] for m in cell.end_to_end}
    assert "setup_s" in reported and len(reported) >= 2
    assert cell.per_layer, "a cell reports at least one per-layer metric"
    for metric in cell.per_layer:
        assert metric["moves"] in reported
        spec = harness.read_json(os.path.join(
            harness.BENCH_DIR, "layer_metrics", metric["name"] + ".json"))
        assert spec["layer"] == metric["layer"]
        assert spec["unit"] == metric["unit"]
        assert spec["moves"] == metric["moves"]
        reader = importlib.import_module(
            f"benchmarks.sources.{spec['source']['kind']}")
        assert callable(reader.read)
    config_entry = next(c for c in BENCH["configs"]
                        if c["name"] == cell.config_name)
    assert config_entry["source"] == cell.config["source"]
    assert set(config_entry["reduced"]) == set(cell.config["reduced"])


TRAIN_CELLS = [w["name"] for w in BENCH["workloads"]
               if harness.load_cell(w["name"]).path == "train"]


@pytest.mark.parametrize("cell_name", TRAIN_CELLS)
def test_training_cell_names_the_set_of_epochs_its_steadier_reading_reads(
        cell_name):
    """`train_env_steps_per_s` stays what it was (`window_share`: all
    the work inside the window over all of its time); beside it every
    training cell's mix names a fixed set of at least 50 of the
    window's epochs, behind the cold head, for `warm_epoch_rate_p50`,
    says what it was set from, and lists both new metrics. Nothing
    reads a `program_spans` key, so no mix carries one."""
    cell = harness.load_cell(cell_name)
    mix = cell.traffic
    assert mix["statistic"] == "window_share" and mix["warmup_epochs"] == 1
    k0, k1 = mix["measure_epochs"]
    assert isinstance(k0, int) and isinstance(k1, int)
    assert 0 < k0 < k1 and k1 - k0 >= 50
    assert "my chip runs, PR 47" in mix["why_measure_epochs"]
    assert "program_spans" not in mix
    reported = {m["name"] for m in cell.per_layer}
    assert {"warm_epoch_rate_p50", "warm_set_env_steps_per_s",
            "long_epochs_in_window"} <= reported


@pytest.mark.parametrize("name, fact, better", [
    ("warm_epoch_rate_p50", "set.median_epoch_rate", "higher"),
    ("warm_set_env_steps_per_s", "set.ratio_steps_per_s", "higher"),
    ("long_epochs_in_window", "long_epochs", "lower")])
def test_window_fact_metrics_are_data_beside_a_reader_that_exists(
        name, fact, better):
    entry, = [m for m in BENCH["per_layer"] if m["name"] == name]
    spec = harness.read_json(os.path.join(
        harness.BENCH_DIR, "layer_metrics", name + ".json"))
    assert spec["source"]["kind"] == "window_fact"
    assert spec["source"]["fact"] == fact
    assert (entry["layer"], entry["moves"], entry["source"],
            entry["better"]) == ("epoch loop", "train_env_steps_per_s",
                                 "host_clock", better)
    assert entry["workloads"][:len(TRAIN_CELLS)] == TRAIN_CELLS
    reader = importlib.import_module("benchmarks.sources.window_fact")
    window = {"set": {"median_epoch_rate": 77.5,
                      "ratio_steps_per_s": 73.0},
              "long_epochs": [{"index": 3}, {"index": 9}]}
    assert reader.read(spec["source"], {"window": window}) \
        == {"warm_epoch_rate_p50": 77.5, "warm_set_env_steps_per_s": 73.0,
            "long_epochs_in_window": 2}[name]
    assert reader.read(spec["source"], {}) is None
    assert reader.read(spec["source"], {"window": {"set": None}}) is None


@pytest.mark.parametrize("mix", sorted(
    name[:-len(".json")] for name in os.listdir(
        os.path.join(harness.BENCH_DIR, "traffic"))))
def test_no_mix_carries_a_key_nothing_reads(mix):
    assert "program_spans" not in harness.read_json(os.path.join(
        harness.BENCH_DIR, "traffic", mix + ".json"))


@pytest.mark.parametrize("config, traffic", [
    ("pacml_ramp32_dev", "train_fused_8x32"),
    ("pacml_ramp32_dev", "train_host_8x32"),
    ("pacml_ramp32_load32", "train_fused_8x32"),
    ("pacml_ramp32_load32", "serve_poisson_p80")])
def test_mixes_the_floor_keeps_out_still_resolve(config, traffic):
    """The first benchmark's mixes stay beside the listed one (their
    cells hold 7-162 MB and the driver's floor is 4 GiB, PERF.md): the
    files name a path that exists and the configuration they ran on."""
    cell = bench_tiny.unlisted_cell(config, traffic)
    assert cell.traffic["name"] == traffic
    assert cell.config["name"] == config
    assert callable(harness.load_path(cell.path).run)
    listed = {w["traffic"] for w in BENCH["workloads"]}
    assert traffic not in listed


def test_every_config_is_used_and_states_its_cuts():
    used = {w["config"] for w in BENCH["workloads"]}
    for cfg in BENCH["configs"]:
        assert cfg["name"] in used
        body = harness.read_json(os.path.join(REPO, cfg["file"]))
        assert "assumed" in body and "reduced" in body and "source" in body
        # no width is ever cut
        assert not any(re.search(r"(_dim|_rank|hidden|features|width)",
                                 key) for key in cfg["reduced"])


def test_serve_rate_is_four_fifths_of_the_measured_knee():
    mix = harness.read_json(os.path.join(
        harness.BENCH_DIR, "traffic", "serve_poisson_p80.json"))
    assert isinstance(mix["rate_rps"], (int, float))
    assert mix["rate_rps"] == pytest.approx(0.8 * mix["knee_rps"])


# ---------------------------------------------------- driven by data
def test_additions_need_only_new_files_and_one_entry(tmp_path):
    """A fifth cell on a third configuration under a new mix, with a
    new per-layer metric of a known kind: four new data files and four
    entries, no edit to a file that is there."""
    root = bench_tiny.build_tree(str(tmp_path))
    bench_dir = os.path.join(root, "benchmarks")
    before = {}
    for base, _, files in os.walk(bench_dir):
        for name in files:
            path = os.path.join(base, name)
            before[path] = open(path, "rb").read()

    config = bench_tiny.tiny_config("third_config")
    mix = dict(bench_tiny.tiny_traffic()["tiny_host"], name="new_mix",
               warmup_epochs=3)
    metric = {"name": "host_sync_wall_s", "layer": "epoch loop",
              "unit": "s", "moves": "train_env_steps_per_s", "scale": 1,
              "source": {"kind": "span", "origin": "bench",
                         "name": "host_sync", "stat": "median"}}
    for rel, body in (("configs/third_config.json", config),
                      ("traffic/new_mix.json", mix),
                      ("layer_metrics/host_sync_wall_s.json", metric)):
        with open(os.path.join(bench_dir, rel), "w") as fh:
            json.dump(body, fh)
    bench_path = os.path.join(root, "BENCHMARK.json")
    bench = json.load(open(bench_path))
    bench["configs"].append({
        "name": "third_config", "source": "test-local", "reduced": [],
        "file": "benchmarks/configs/third_config.json", "why": "new"})
    bench["workloads"].append({
        "name": "fifth.cell", "config": "third_config",
        "traffic": "new_mix", "chips": 1, "why": "new"})
    # the contract has a metric that exists only in some cells list
    # them, so the new cell's name also joins the lists of the metrics
    # it reports
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if metric["name"] == "train_env_steps_per_s":
            metric["workloads"].append("fifth.cell")
    bench["per_layer"].append({
        "name": "host_sync_wall_s", "unit": "s", "better": "lower",
        "source": "host_clock", "layer": "epoch loop",
        "moves": "train_env_steps_per_s", "workloads": ["fifth.cell"]})
    json.dump(bench, open(bench_path, "w"))

    cell = harness.load_cell("fifth.cell", root=root)
    assert cell.traffic["warmup_epochs"] == 3
    assert cell.config["name"] == "third_config"
    assert {"host_sync_wall_s", "epoch_wall_p50_s", "compile_s"} <= {
        m["name"] for m in cell.per_layer}
    value = harness.read_layer_metric(
        "host_sync_wall_s",
        {"spans": {"bench": {"host_sync": [0.1, 0.3, 0.2]}}},
        root=bench_dir)
    assert value == pytest.approx(0.2)
    for path, body in before.items():
        assert open(path, "rb").read() == body, f"{path} was edited"
