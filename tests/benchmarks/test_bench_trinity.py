"""The cell ``trinity_ramp32.train_fused``: its files resolve and agree
with the composed tree and with the architecture file, NOTHING but the
batch is reduced, it lists the per-layer metric this PR adds beside
everything the older cells report, the older cells report what they
reported, and a tiny STATED preset of the same job source (whole: S S S
F; D E E E with a shared expert; hidden 64) runs the training path end
to end on the CPU with the new metric, counter and gauges in its traced
line and `[startup]` line."""
import json
import os

import pytest

import bench_tiny
from benchmarks import harness
from benchmarks.paths import train
from test_bench_mimo import PARENT_PER_LAYER, _add_cell
from test_bench_run import (_argv, _check_line, _result,  # noqa: F401
                            restore_process_state, tiny_tree)

REPO = bench_tiny.REPO
CELL = "trinity_ramp32.train_fused"
OLD_CELLS = ("ramp32_dev.train_fused", "ramp32_load32.train_fused",
             "olmoe_ramp32.train_fused", "glm5_ramp32.train_fused",
             "mimo_ramp32.train_fused")
NEW_METRIC = "decision_blocked_placement_share"
#: listed for `mimo_ramp32.train_fused` alone in the parent's benchmark
MIMO_ONLY = ("decision_accept_share_longest", "job_quadratic_time_share",
             "epoch_device_wait_p50_s", "epoch_host_p50_ms",
             "epoch_observer_p50_ms", "device_idle_observer_share")
ARCH_FILE = "ddls_tpu/graphs/arch_configs/trinity_mini.json"
BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))


def _entry(kind, name):
    """BENCHMARK.json's entry of that name, wherever it stands."""
    entry, = [e for e in BENCH[kind] if e["name"] == name]
    return entry


def test_cell_is_16_lanes_of_the_trinity_queue():
    cell = harness.load_cell(CELL)
    assert (cell.chips, cell.config_name, cell.traffic_name, cell.path) \
        == (1, "trinity_mini_whole_ramp32", "train_fused_trinity", "train")
    mix = cell.traffic
    lanes = mix["epoch"]["lanes"]
    assert lanes in (16, 24)         # the issue's two packed-form sizes
    assert mix["epoch"] == {"lanes": lanes, "steps": 1, "env_steps": lanes}
    assert f"epoch_loop.fused_config={{lanes: {lanes}, segment_len: 1}}" \
        in mix["overrides"]
    assert f"epoch_loop.num_envs={lanes}" in mix["overrides"]
    assert "epoch_loop.updates_per_epoch=1" in mix["overrides"]
    assert mix["fidelity"]["kind"] == "jitted_episode"
    assert mix["fidelity"]["decisions"] in (24, 48)
    assert mix["fidelity"]["rtol"] == 1e-4
    assert mix["fidelity"]["why_decisions"] and mix["fidelity"]["why_rtol"]
    assert (mix["warmup_epochs"], mix["statistic"], mix["trace_epochs"],
            mix["train_seed"]) == (1, "window_share", 1, 0)
    # the steadier reading beside it: a fixed set of the window's epochs
    k0, k1 = mix["measure_epochs"]
    assert 0 < k0 < k1 and k1 - k0 >= 50 and mix["why_measure_epochs"]
    assert "program_spans" not in mix
    assert cell.config["composed_from"]["overrides"] == [
        "env_config=env_trinity_32"]
    assert cell.config["train_batch_size"] == lanes
    assert {m["name"] for m in cell.end_to_end} == {
        "train_env_steps_per_s", "setup_s"}


def test_published_is_the_architecture_file_and_only_the_batch_is_reduced():
    """The widths are pinned twice: the architecture file the program
    reads and the ``published`` block (the catalog row's keys) are the
    same numbers, and so is the top level: depth, experts and vocabulary
    are WHOLE, the file says so in words, and ``reduced`` is
    ``train_batch_size`` alone, here and in BENCHMARK.json."""
    arch = json.load(open(os.path.join(REPO, ARCH_FILE)))
    config = harness.load_cell(CELL).config
    assert arch["source_url"] == config["source"]
    assert arch["training_state"] == {"resident_bytes_per_parameter": 16,
                                      "synced_bytes_per_parameter": 2}
    assert "modeling" not in arch        # nothing written from memory
    published = dict(config["published"])
    assert published.pop("train_batch_size") == 4000
    assert published == arch["config"]
    assert {k for k, v in arch["config"].items() if config[k] != v} == set()
    entry = _entry("configs", "trinity_mini_whole_ramp32")
    assert set(entry["reduced"]) == {"train_batch_size"} \
        == set(config["reduced"])
    assert entry["source"] == arch["source_url"]
    words = config["reduced"]["train_batch_size"]
    for whole in ("DEPTH is whole", "EXPERTS are whole",
                  "VOCABULARY is whole"):
        assert whole in words
    assert "whole" in config["deployment"]
    for field in ("deployment", "assumed", "reduced", "published"):
        assert config[field], field
    # every departure and every check is written down
    assert {"left_out", "router", "fork", "op_graph", "ragged_rows",
            "placeable_on_an_empty_cluster", "sequence_lengths",
            "arrivals"} <= set(config["assumed"])
    assert "25.855 B" in config["assumed"]["left_out"]


def test_catalog_numbers_sit_at_the_top_level_under_the_same_keys():
    """What the driver compares: every number of the catalog row's
    ``config`` at the file's top level, equal; lists and nulls copied
    whole."""
    arch = json.load(open(os.path.join(REPO, ARCH_FILE)))["config"]
    config = harness.load_cell(CELL).config
    for key, value in arch.items():
        assert config[key] == value, key
    assert (config["num_hidden_layers"], config["num_experts"],
            config["num_dense_layers"], config["num_shared_experts"],
            config["vocab_size"], config["max_position_embeddings"]) \
        == (32, 128, 2, 1, 200192, 131072)
    assert (config["hidden_size"], config["head_dim"],
            config["intermediate_size"], config["moe_intermediate_size"],
            config["num_experts_per_tok"], config["sliding_window"]) \
        == (2048, 128, 6144, 1024, 8, 2048)
    assert config["layer_types"] == (["sliding_attention"] * 3
                                     + ["full_attention"]) * 8


@pytest.mark.parametrize("metric", [
    NEW_METRIC, *MIMO_ONLY, "compile_s", "compiles_in_window",
    "memo_hit_rate", "lookahead_lockstep_efficiency",
    "lookahead_block_fill_decided", "obs_node_fill", "advance_device_s",
    "lookahead_device_s", "placement_device_s", "pricing_device_s",
    "fused_update_device_s", "decision_accept_share",
    "cluster_occupied_share", "mask_placeable_share", "setup_job_graphs_s",
    "setup_device_tables_s", "peak_hbm_bytes", "program_scratch_bytes"])
def test_cell_reports_the_metric(metric):
    assert metric in {m["name"] for m in harness.load_cell(CELL).per_layer}


def test_new_metric_is_data_of_reader_kinds_that_exist():
    """A ratio of two telemetry counters: no benchmark code is added,
    and the count of drained traces cancels. It is in BENCHMARK.json for
    the new cell first (no older cell lists it; a later one may join
    behind), which reports the metric moved."""
    spec = harness.read_json(os.path.join(
        harness.BENCH_DIR, "layer_metrics", NEW_METRIC + ".json"))
    entry = _entry("per_layer", NEW_METRIC)
    assert entry["workloads"][0] == CELL and entry["better"] == "lower"
    assert not set(OLD_CELLS) & set(entry["workloads"])
    assert (entry["layer"], entry["unit"], entry["moves"]) \
        == (spec["layer"], spec["unit"], spec["moves"]) \
        == ("device collection", "%", "train_env_steps_per_s")
    assert spec["source"] == {"kind": "metric_ratio",
                              "num": "decisions_blocked_placement",
                              "den": "decisions_offered"}
    for part in (spec["source"]["num"], spec["source"]["den"]):
        source = harness.read_json(os.path.join(
            harness.BENCH_DIR, "layer_metrics", part + ".json"))["source"]
        assert source["kind"] == "telemetry_counter" and source["per_epoch"]
    num = harness.read_json(os.path.join(
        harness.BENCH_DIR, "layer_metrics",
        "decisions_blocked_placement.json"))
    assert num["source"]["counter"] == "env.decisions.blocked_placement"


def test_old_cells_report_what_they_reported():
    """This PR appended, and leaves room for the next to: every old
    cell's per-layer list starts with what the parent's was (`mimo`'s 34
    shared names, then its own six), the new cell's holds all of
    `mimo`'s and then the new metric, and its name joined each list
    `mimo`'s is on, behind it. Entries are found by name, never by
    position, and nothing is pinned as the benchmark's last."""
    mimo = [m["name"] for m in
            harness.load_cell("mimo_ramp32.train_fused").per_layer]
    assert mimo[:34] == list(PARENT_PER_LAYER)
    assert mimo[34:40] == list(MIMO_ONLY) and NEW_METRIC not in mimo
    for cell in OLD_CELLS[:4]:
        names = [m["name"] for m in harness.load_cell(cell).per_layer]
        assert names[:34] == mimo[:34]
        assert not {NEW_METRIC, *MIMO_ONLY} & set(names)
    names = [m["name"] for m in harness.load_cell(CELL).per_layer]
    assert names[:41] == mimo[:40] + [NEW_METRIC]
    assert set(mimo) <= set(names) and len(set(names)) == len(names)
    joined = [_entry("end_to_end", "train_env_steps_per_s"),
              *(_entry("per_layer", n) for n in mimo[:40])]
    assert len(joined) == 1 + 34 + 6
    for metric in joined:
        cells = metric["workloads"]
        old = cells[:cells.index(CELL)]
        assert old == list(OLD_CELLS) or old == list(OLD_CELLS[-1:])
    assert _entry("per_layer", NEW_METRIC)["workloads"][0] == CELL
    assert [w["name"] for w in BENCH["workloads"]][:6] \
        == [*OLD_CELLS, CELL]
    listed = [m["name"] for m in BENCH["per_layer"]]
    assert listed.index(NEW_METRIC) == 1 + max(listed.index(n)
                                               for n in mimo[:40])
    assert _entry("workloads", CELL)["config"] \
        == "trinity_mini_whole_ramp32"
    assert _entry("configs", "trinity_mini_whole_ramp32")["file"] \
        == "benchmarks/configs/trinity_mini_whole_ramp32.json"
    assert [c["name"] for c in BENCH["configs"]].index(
        "trinity_mini_whole_ramp32") == 5


def test_composed_tree_is_what_the_configuration_file_expects(tmp_path):
    """``compose`` checks ``expect``; beyond it, the kernel pads the
    file describes are what the tables of that tree are built to: the
    fourth pad class."""
    cell = harness.load_cell(CELL)
    cfg = train.compose(cell, 0, str(tmp_path))
    jobs = cfg["env_config"]["jobs_config"]
    assert "synthetic" not in jobs and jobs["path_to_files"] is None
    assert set(jobs["architecture"]) == {"config", "shapes"}   # no cut
    assert jobs["architecture"]["config"] == ARCH_FILE
    assert cfg["epoch_loop"]["loop_mode"] == "fused"
    pads = cell.config["pads"]
    assert (pads["max_nodes"], pads["max_edges"]) == (600, 1024)
    # 570 original ops x 16; (877 edges + 285 backward cliques) x 16^2
    assert pads["kernel_ops"] == 570 * 16 == 9120
    assert pads["kernel_blocks"] == 877 + 285 == 1162
    assert pads["kernel_deps"] == pads["kernel_blocks"] * 16 ** 2 == 297472
    assert pads["kernel_fwd_ops"] == 285
    # a lane's 128-key memo, as the traffic file states it
    memo = 128 * (pads["kernel_ops"] + pads["kernel_deps"]) * 4
    assert memo == 156_975_104 and round(memo / 1e6) == 157
    # the obs pads keep the GNN's contraction form (ops/segment.py)
    from ddls_tpu.ops.segment import DENSE_MAX_CELLS

    assert pads["max_nodes"] * pads["max_edges"] == 614400 \
        <= DENSE_MAX_CELLS


# ------------------------------------------------ the tiny preset, run
TINY_ARCH = {"model_type": "tinyafmoe", "hidden_size": 64,
             "num_attention_heads": 4, "num_key_value_heads": 1,
             "head_dim": 16, "sliding_window": 16,
             "layer_types": ["sliding_attention"] * 3 + ["full_attention"],
             "global_attn_every_n_layers": 4, "num_dense_layers": 1,
             "intermediate_size": 128, "moe_intermediate_size": 32,
             "num_experts": 8, "num_shared_experts": 1,
             "num_experts_per_tok": 2, "num_hidden_layers": 4,
             "score_func": "sigmoid", "route_norm": True,
             "route_scale": 2.826, "vocab_size": 256}


def test_tiny_trinity_preset_runs_the_training_path_traced(
        tiny_tree, capsys, tmp_path):
    arch_file = tmp_path / "tinyafmoe.json"
    arch_file.write_text(json.dumps({
        "source_url": "test-local", "config": TINY_ARCH,
        "training_state": {"resident_bytes_per_parameter": 16,
                           "synced_bytes_per_parameter": 2}}))
    config = bench_tiny.tiny_config("tiny_trinity", overrides=[
        "env_config=env_trinity_32", *bench_tiny.TINY_OVERRIDES[1:],
        f"env_config.jobs_config.architecture.config={arch_file}",
        # the second shape's ops are 9-50 us: ragged rows; whole model
        "env_config.jobs_config.architecture.shapes="
        "[{seq_len: 32, micro_batch: 524288}, {seq_len: 32, micro_batch: 4096}]",
        "env_config.jobs_config.job_interarrival_time_dist.val=0.01",
        "env_config.jobs_config.max_acceptable_job_completion_time_frac_dist="
        "{_target_: ddls_tpu.demands.distributions.Fixed, val: 0.95}",
        "env_config.max_simulation_run_time=1.0",
        "env_config.max_partitions_per_op=4",
        # at hidden 64 the real fabric buys no time by partitioning
        # (tests/test_arch_graphs.py)
        "env_config.topology_config.kwargs.total_node_bandwidth=1.6e14",
        "env_config.pad_obs_kwargs={max_nodes: 100, max_edges: 192}"])
    config["expect"] = {"env_config.min_op_run_time_quantum": 1e-5,
                        "env_config.max_partitions_per_op": 4}
    mix = dict(bench_tiny.tiny_traffic()["tiny_fused"], name="tiny_trinity")
    _add_cell(tiny_tree, "tiny.trinity", config, mix)

    result, notes = _result(capsys, _argv("tiny.trinity", 1))
    _check_line(result, traced=True)
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert NEW_METRIC in metrics, sorted(metrics)
    # blocked-by-placement decisions are a part of the decisions that
    # were not accepted
    assert metrics["decisions_offered"] == 16.0
    assert 0 <= metrics["decisions_blocked_placement"] \
        <= metrics["decisions_offered"] - metrics["decisions_accepted"]
    assert metrics[NEW_METRIC] == pytest.approx(
        100 * metrics["decisions_blocked_placement"] / 16.0)
    assert 0 < metrics["decision_accept_share"] <= 100
    # 72 real nodes (36 forward ops mirrored) under the 100-node pad
    assert metrics["obs_node_fill"] == pytest.approx(72.0)
    assert metrics["job_models"] == 2.0
    assert metrics["compiles_in_window"] == 0.0
    assert any(n.startswith("[bench] fidelity") and '"ok": true' in n
               for n in notes)
    startup_line, = [n for n in notes if n.startswith("[startup] ")]
    gauges = json.loads(startup_line[len("[startup] "):])
    # 16 of the short shape's 36 forward ops are under 4 quanta of 10 us
    for model, ragged in (("tinyafmoe_s32_b524288", 0),
                          ("tinyafmoe_s32_b4096", 16)):
        assert gauges[f"graphs.arch.forward_ops.{model}"] == 36
        assert gauges[f"graphs.arch.layers_full.{model}"] == 1
        assert gauges[f"graphs.arch.layers_window.{model}"] == 3
        assert gauges[f"graphs.arch.shared_expert_layers.{model}"] == 3
        assert gauges[f"graphs.arch.ragged_ops.{model}"] == ragged


def test_synthetic_jobs_count_placement_blocks_and_state_no_ragged_gauge(
        tiny_tree, capsys):
    """The old cells' kind of run (synthetic chains, no architecture):
    the counter is counted there too (every fused run drains the cause
    trace), and the `graphs.arch.*` gauges, which only an architecture
    job source sets, are absent from the `[startup]` line."""
    from ddls_tpu.telemetry import startup

    # the start-up registry is the process's: an architecture run before
    # this one in the same worker left its gauges there
    startup.registry().reset()
    result, notes = _result(capsys, _argv("tiny.fused", 1))
    _check_line(result, traced=True)
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert 0 <= metrics[NEW_METRIC] <= 100
    assert metrics["decisions_blocked_placement"] \
        <= metrics["decisions_offered"] - metrics["decisions_accepted"]
    startup_line, = [n for n in notes if n.startswith("[startup] ")]
    assert "graphs.arch." not in startup_line
