"""The cell ``mimo_ramp32.train_fused``: its files resolve and agree with
the composed tree and with the architecture file, it lists the two
per-layer metrics this PR adds beside everything the older cells report,
the older cells report what they reported, and a tiny STATED preset of
the same job source (layers F W W; D E E; hidden 64, 4 of 8 experts)
runs the training path end to end on the CPU with the new metrics in its
traced line — which a synthetic-job run reads too, where its counters
exist."""
import json
import os

import pytest

import bench_tiny
from benchmarks import harness
from benchmarks.paths import train
from test_bench_run import (_argv, _check_line, _result,  # noqa: F401
                            restore_process_state, tiny_tree)

REPO = bench_tiny.REPO
CELL = "mimo_ramp32.train_fused"
OLD_CELLS = ("ramp32_dev.train_fused", "ramp32_load32.train_fused",
             "olmoe_ramp32.train_fused", "glm5_ramp32.train_fused")
NEW_METRICS = ("decision_accept_share_longest", "job_quadratic_time_share")
ARCH_FILE = "ddls_tpu/graphs/arch_configs/mimo_v2_flash.json"
#: what every cell of the parent's benchmark reported, in its order
PARENT_PER_LAYER = (
    "compile_s", "compiles_in_window", "epoch_wall_p50_s", "memo_hit_rate",
    "fused_epoch_device_s", "device_idle_share", "peak_hbm_bytes",
    "program_scratch_bytes", "lookahead_device_s", "placement_device_s",
    "pricing_device_s", "memo_probe_device_s", "advance_device_s",
    "fused_forward_device_s", "fused_update_device_s",
    "fused_unscoped_device_share", "lookahead_lockstep_trips",
    "lookahead_lockstep_efficiency", "lookahead_trip_device_ms",
    "setup_job_banks_s", "setup_trace_lower_s", "setup_first_epoch_s",
    "device_idle_unattributed_share", "setup_build_run_s",
    "setup_before_build_s", "lookahead_block_fill", "setup_job_graphs_s",
    "setup_device_tables_s", "lookahead_block_fill_decided",
    "obs_node_fill", "lookahead_minor_fill", "decision_accept_share",
    "cluster_occupied_share", "mask_placeable_share")
BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))


def test_cell_is_80_lanes_of_the_mimo_queue():
    cell = harness.load_cell(CELL)
    assert (cell.chips, cell.config_name, cell.traffic_name, cell.path) \
        == (1, "mimo_v2_flash_share_ramp32", "train_fused_mimo", "train")
    mix = cell.traffic
    lanes = mix["epoch"]["lanes"]
    assert lanes in (80, 96)         # the issue's two packed-form sizes
    assert mix["epoch"] == {"lanes": lanes, "steps": 1, "env_steps": lanes}
    assert f"epoch_loop.fused_config={{lanes: {lanes}, segment_len: 1}}" \
        in mix["overrides"]
    assert f"epoch_loop.num_envs={lanes}" in mix["overrides"]
    assert "epoch_loop.updates_per_epoch=1" in mix["overrides"]
    assert mix["fidelity"] == {**mix["fidelity"], "kind": "jitted_episode",
                               "decisions": 48, "rtol": 1e-4}
    assert (mix["warmup_epochs"], mix["statistic"], mix["trace_epochs"],
            mix["train_seed"]) == (1, "window_share", 1, 0)
    # the steadier reading beside it: a fixed set of the window's epochs
    k0, k1 = mix["measure_epochs"]
    assert 0 < k0 < k1 and k1 - k0 >= 50 and mix["why_measure_epochs"]
    assert "program_spans" not in mix
    assert cell.config["composed_from"]["overrides"] == [
        "env_config=env_mimo_32"]
    assert cell.config["train_batch_size"] == lanes
    assert {m["name"] for m in cell.end_to_end} == {
        "train_env_steps_per_s", "setup_s"}


def test_published_is_the_architecture_file_and_the_cut_is_listed():
    """The widths are pinned twice: the architecture file the program
    reads and the ``published`` block (the catalog row's keys) are the
    same numbers; the top level differs from them in the ``reduced`` keys
    alone, and BENCHMARK.json lists exactly those."""
    arch = json.load(open(os.path.join(REPO, ARCH_FILE)))
    config = harness.load_cell(CELL).config
    assert arch["source_url"] == config["source"]
    assert arch["training_state"] == {"resident_bytes_per_parameter": 16,
                                      "synced_bytes_per_parameter": 2}
    published = dict(config["published"])
    assert published.pop("train_batch_size") == 4000
    assert published == arch["config"]
    differs = {k for k, v in arch["config"].items() if config[k] != v}
    assert differs == {"n_routed_experts"}
    # the depth cut is a key of its own: the model keeps its 48 layers
    # and both 48-entry lists, this pipeline stage holds the first
    # num_layers of them
    assert (config["num_hidden_layers"], config["num_layers"],
            config["n_routed_experts"]) == (48, 1 + 6, 64)
    assert len(config["hybrid_layer_pattern"]) == 48 \
        == len(config["moe_layer_freq"])
    differs.add("num_layers")
    entry = _entry("configs", "mimo_v2_flash_share_ramp32")
    assert set(entry["reduced"]) == differs | {"train_batch_size"} \
        == set(config["reduced"])
    assert entry["source"] == arch["source_url"]
    for field in ("deployment", "assumed", "reduced", "published"):
        assert config[field], field
    # every departure is written down: no MTP module, no q/k norm, the
    # sink's form, the rotary width
    assert {"mtp", "q_k_norm", "sink", "rotary_width", "router",
            "sequence_lengths"} <= set(config["assumed"])


def test_catalog_numbers_sit_at_the_top_level_under_the_same_keys():
    """What the driver compares: every number of the catalog row's
    ``config`` at the file's top level, equal unless listed in
    ``reduced``; lists and nulls copied whole."""
    arch = json.load(open(os.path.join(REPO, ARCH_FILE)))["config"]
    config = harness.load_cell(CELL).config
    for key, value in arch.items():
        assert key in config, key
        if key != "n_routed_experts":
            assert config[key] == value, key
    assert config["head_dim"] == 192 and config["v_head_dim"] == 128
    assert (config["num_key_value_heads"],
            config["swa_num_key_value_heads"]) == (4, 8)
    assert config["n_shared_experts"] is None


@pytest.mark.parametrize("metric", [
    *NEW_METRICS, "compile_s", "compiles_in_window", "memo_hit_rate",
    "lookahead_lockstep_efficiency", "lookahead_block_fill_decided",
    "obs_node_fill", "advance_device_s", "lookahead_device_s",
    "placement_device_s", "pricing_device_s", "fused_update_device_s",
    "decision_accept_share", "cluster_occupied_share",
    "mask_placeable_share", "setup_job_graphs_s"])
def test_cell_reports_the_metric(metric):
    assert metric in {m["name"] for m in harness.load_cell(CELL).per_layer}


def _entry(kind, name):
    """BENCHMARK.json's entry of that name, wherever it stands."""
    entry, = [e for e in BENCH[kind] if e["name"] == name]
    return entry


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_metrics_are_data_of_reader_kinds_that_exist(metric):
    """Each is a ratio of two telemetry counters: no benchmark code is
    added, and the count of drained traces cancels. They are in
    BENCHMARK.json with the cells that list them, each of which reports
    the metric moved."""
    spec = harness.read_json(os.path.join(
        harness.BENCH_DIR, "layer_metrics", metric + ".json"))
    entry = _entry("per_layer", metric)
    assert CELL in entry["workloads"]
    assert (entry["layer"], entry["unit"], entry["moves"]) \
        == (spec["layer"], spec["unit"], spec["moves"])
    for cell in entry["workloads"]:
        assert spec["moves"] in {m["name"]
                                 for m in harness.load_cell(cell).end_to_end}
    assert spec["source"]["kind"] == "metric_ratio"
    assert spec["layer"] == ("job graphs" if metric.startswith("job_")
                             else "device collection")
    for part in (spec["source"]["num"], spec["source"]["den"]):
        source = harness.read_json(os.path.join(
            harness.BENCH_DIR, "layer_metrics", part + ".json"))["source"]
        assert source["kind"] == "telemetry_counter" and source["per_epoch"]


def test_old_cells_report_what_they_reported():
    """This PR appends, and leaves room for the next to: every old
    cell's per-layer list starts with the parent's names, the new
    cell's holds those and the two new metrics, and the new cell's name
    joined the lists the old cells are on. Entries are found by name,
    never by position."""
    for cell in OLD_CELLS:
        names = [m["name"] for m in harness.load_cell(cell).per_layer]
        assert names[:len(PARENT_PER_LAYER)] == list(PARENT_PER_LAYER)
    names = [m["name"] for m in harness.load_cell(CELL).per_layer]
    assert set(PARENT_PER_LAYER) | set(NEW_METRICS) <= set(names)
    assert len(set(names)) == len(names)
    for metric in [_entry("end_to_end", "train_env_steps_per_s"),
                   *(_entry("per_layer", n) for n in PARENT_PER_LAYER)]:
        assert metric["workloads"][:4] == list(OLD_CELLS)
        assert CELL in metric["workloads"]
    assert _entry("workloads", CELL)["config"] \
        == "mimo_v2_flash_share_ramp32"
    assert _entry("configs", "mimo_v2_flash_share_ramp32")["file"] \
        == "benchmarks/configs/mimo_v2_flash_share_ramp32.json"


def test_composed_tree_is_what_the_configuration_file_expects(tmp_path):
    """``compose`` checks ``expect``; beyond it, the kernel pads the
    file describes are what the tables of that tree are built to."""
    cell = harness.load_cell(CELL)
    cfg = train.compose(cell, 0, str(tmp_path))
    jobs = cfg["env_config"]["jobs_config"]
    assert "synthetic" not in jobs and jobs["path_to_files"] is None
    assert jobs["architecture"]["config"] == ARCH_FILE
    assert cfg["epoch_loop"]["loop_mode"] == "fused"
    pads = cell.config["pads"]
    assert (pads["max_nodes"], pads["max_edges"]) == (150, 256)
    # 114 original ops x 16; (165 edges + 57 backward cliques) x 16^2
    assert pads["kernel_ops"] == 114 * 16
    assert pads["kernel_blocks"] == 165 + 57
    assert pads["kernel_deps"] == pads["kernel_blocks"] * 16 ** 2
    # a lane's 128-key memo, as the traffic file states it
    assert 128 * (pads["kernel_ops"] + pads["kernel_deps"]) * 4 \
        == pytest.approx(30.0e6, rel=2e-3)


# ------------------------------------------------ the tiny preset, run
TINY_ARCH = {"model_type": "tinymimo", "hidden_size": 64,
             "num_attention_heads": 4, "num_key_value_heads": 1,
             "head_dim": 24, "v_head_dim": 16,
             "swa_num_attention_heads": 4, "swa_num_key_value_heads": 2,
             "swa_head_dim": 24, "swa_v_head_dim": 16,
             "partial_rotary_factor": 0.334, "attention_value_scale": 0.707,
             "sliding_window": 16, "hybrid_layer_pattern": [0, 1, 1, 0],
             "moe_layer_freq": [0, 1, 1, 1],
             "add_swa_attention_sink_bias": True,
             "add_full_attention_sink_bias": False,
             "intermediate_size": 128, "moe_intermediate_size": 32,
             "n_routed_experts": 8, "n_shared_experts": None,
             "num_experts_per_tok": 2, "num_hidden_layers": 4,
             "scoring_func": "sigmoid", "vocab_size": 256}


def _add_cell(tiny_tree, name, config, mix):
    bench_path = os.path.join(tiny_tree, "BENCHMARK.json")
    bench = json.load(open(bench_path))
    for kind, body in (("configs", config), ("traffic", mix)):
        with open(os.path.join(tiny_tree, "benchmarks", kind,
                               body["name"] + ".json"), "w") as fh:
            json.dump(body, fh)
    bench["configs"].append({
        "name": config["name"], "source": "test-local", "reduced": [],
        "file": f"benchmarks/configs/{config['name']}.json", "why": "tiny"})
    bench["workloads"].append({
        "name": name, "config": config["name"], "traffic": mix["name"],
        "chips": 1, "why": "tiny"})
    for metric in bench["end_to_end"]:
        if metric["name"] == "train_env_steps_per_s":
            metric["workloads"].append(name)
    json.dump(bench, open(bench_path, "w"))


def test_tiny_mimo_preset_runs_the_training_path_traced(
        tiny_tree, capsys, tmp_path):
    arch_file = tmp_path / "tinymimo.json"
    arch_file.write_text(json.dumps({
        "source_url": "test-local", "config": TINY_ARCH,
        "training_state": {"resident_bytes_per_parameter": 16,
                           "synced_bytes_per_parameter": 2}}))
    config = bench_tiny.tiny_config("tiny_mimo", overrides=[
        "env_config=env_mimo_32", *bench_tiny.TINY_OVERRIDES[1:],
        f"env_config.jobs_config.architecture.config={arch_file}",
        "env_config.jobs_config.architecture.layers="
        "{leading_dense: 1, following: 2}",
        "env_config.jobs_config.architecture.experts_held=4",
        # the second shape's one full core is 83 % of its forward pass
        "env_config.jobs_config.architecture.shapes="
        "[{seq_len: 32, micro_batch: 4096}, {seq_len: 16384, micro_batch: 512}]",
        "env_config.jobs_config.job_interarrival_time_dist.val=0.01",
        "env_config.jobs_config.max_acceptable_job_completion_time_frac_dist="
        "{_target_: ddls_tpu.demands.distributions.Fixed, val: 0.95}",
        "env_config.max_simulation_run_time=1.0",
        "env_config.max_partitions_per_op=4",
        # at hidden 64 the real fabric buys no time by partitioning
        # (tests/test_arch_graphs.py)
        "env_config.topology_config.kwargs.total_node_bandwidth=1.6e14",
        "env_config.pad_obs_kwargs={max_nodes: 50, max_edges: 128}"])
    config["expect"] = {"env_config.min_op_run_time_quantum": 1e-5,
                        "env_config.max_partitions_per_op": 4}
    mix = dict(bench_tiny.tiny_traffic()["tiny_fused"], name="tiny_mimo")
    _add_cell(tiny_tree, "tiny.mimo", config, mix)

    result, notes = _result(capsys, _argv("tiny.mimo", 1))
    _check_line(result, traced=True)
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW_METRICS) <= set(metrics), sorted(metrics)
    # the bank's mean of 4.5 % (32 tokens a sequence) and 83.5 %
    assert metrics["job_quadratic_time_share"] == pytest.approx(
        100 * (0.0450 + 0.8347) / 2, abs=0.01)
    assert metrics["job_models"] == 2.0
    # the 16,384-token shape is the longest: its decisions are a part of
    # all decisions, and no more of them are accepted than offered
    assert 0 < metrics["decisions_offered_longest"] \
        < metrics["decisions_offered"] == 16.0
    assert 0 <= metrics["decisions_accepted_longest"] \
        <= metrics["decisions_offered_longest"]
    assert metrics["decisions_accepted_longest"] \
        <= metrics["decisions_accepted"]
    assert 0 <= metrics["decision_accept_share_longest"] <= 100
    assert 0 < metrics["decision_accept_share"] <= 100
    # 50 real nodes (25 forward ops mirrored) under the 50-node pad
    assert metrics["obs_node_fill"] == pytest.approx(100.0)
    assert metrics["compiles_in_window"] == 0.0
    assert any(n.startswith("[bench] fidelity") and '"ok": true' in n
               for n in notes)
    startup_line, = [n for n in notes if n.startswith("[startup] ")]
    gauges = json.loads(startup_line[len("[startup] "):])
    for model in ("tinymimo_s32_b4096", "tinymimo_s16384_b512"):
        assert gauges[f"graphs.arch.forward_ops.{model}"] == 25
        assert gauges[f"graphs.arch.layers_full.{model}"] == 1
        assert gauges[f"graphs.arch.layers_window.{model}"] == 2
    assert gauges["graphs.arch.quadratic_time_share.tinymimo_s16384_b512"] \
        == pytest.approx(0.8347, abs=1e-4)


def test_synthetic_jobs_count_the_longest_type_and_state_no_share(
        tiny_tree, capsys):
    """The old cells' kind of run (synthetic chains, no architecture):
    the longest type's decisions are counted there too, and the
    quadratic share, which only an architecture's op names state, is
    left out of the line rather than read as 0."""
    from ddls_tpu.telemetry import startup

    # the start-up registry is the process's: an architecture run before
    # this one in the same worker left its gauges there
    startup.registry().reset()
    result, _ = _result(capsys, _argv("tiny.fused", 1))
    _check_line(result, traced=True)
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert "job_quadratic_time_share" not in metrics
    assert 0 < metrics["decisions_offered_longest"] \
        <= metrics["decisions_offered"]
    assert 0 <= metrics["decision_accept_share_longest"] <= 100
