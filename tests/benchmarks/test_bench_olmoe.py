"""The cell ``olmoe_ramp32.train_fused``: its files resolve and agree
with the composed tree, it lists the per-layer metrics that read what
the architecture job source added to the program, and a tiny preset of
the same job source (2 layers, hidden 64, 4 experts) runs the training
path end to end on the CPU with those metrics in its traced line."""
import json
import os

import pytest

import bench_tiny
from benchmarks import harness, run
from benchmarks.paths import train
from test_bench_run import (_argv, _check_line, _result,  # noqa: F401
                            restore_process_state, tiny_tree)

REPO = bench_tiny.REPO
CELL = "olmoe_ramp32.train_fused"
OLD_CELLS = ("ramp32_dev.train_fused", "ramp32_load32.train_fused")
NEW_METRICS = ("setup_job_graphs_s", "setup_device_tables_s",
               "lookahead_block_fill_decided", "obs_node_fill")


def test_cell_is_32_lanes_of_the_olmoe_queue():
    cell = harness.load_cell(CELL)
    assert (cell.chips, cell.config_name, cell.traffic_name, cell.path) \
        == (1, "olmoe_1b7b_ramp32", "train_fused_olmoe", "train")
    mix = cell.traffic
    lanes = mix["epoch"]["lanes"]
    assert lanes >= 32 and lanes % 8 == 0
    assert mix["epoch"] == {"lanes": lanes, "steps": 1, "env_steps": lanes}
    assert f"epoch_loop.fused_config={{lanes: {lanes}, segment_len: 1}}" \
        in mix["overrides"]
    assert f"epoch_loop.num_envs={lanes}" in mix["overrides"]
    assert mix["fidelity"]["kind"] == "jitted_episode"
    assert mix["fidelity"]["decisions"] >= 32
    assert mix["fidelity"]["rtol"] == 1e-4
    assert (mix["warmup_epochs"], mix["statistic"], mix["trace_epochs"],
            mix["train_seed"]) == (1, "window_share", 1, 0)
    # the steadier reading beside it: a fixed set of the window's epochs
    k0, k1 = mix["measure_epochs"]
    assert 0 < k0 < k1 and k1 - k0 >= 50 and mix["why_measure_epochs"]
    assert "program_spans" not in mix
    assert cell.config["composed_from"]["overrides"] == [
        "env_config=env_olmoe32"]
    assert cell.config["train_batch_size"] == lanes
    assert set(cell.config["reduced"]) <= {"train_batch_size", "num_layers"}


@pytest.mark.parametrize("metric", [
    *NEW_METRICS, "lookahead_block_fill", "lookahead_lockstep_trips",
    "lookahead_trip_device_ms", "fused_epoch_device_s",
    "lookahead_device_s", "placement_device_s", "pricing_device_s",
    "memo_probe_device_s", "advance_device_s", "fused_forward_device_s",
    "fused_update_device_s", "fused_unscoped_device_share"])
def test_cell_reports_the_metric(metric):
    assert metric in {m["name"] for m in harness.load_cell(CELL).per_layer}


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_metrics_are_read_in_the_old_cells_too(metric):
    """They read spans and counters every fused run leaves, so the two
    synthetic-job cells report them beside the new one."""
    for cell in OLD_CELLS:
        assert metric in {m["name"]
                          for m in harness.load_cell(cell).per_layer}


def test_composed_tree_is_what_the_configuration_file_expects(tmp_path):
    """``compose`` checks ``expect``; beyond it, the kernel pads the
    file describes are what the tables of that tree are built to."""
    cell = harness.load_cell(CELL)
    cfg = train.compose(cell, 0, str(tmp_path))
    jobs = cfg["env_config"]["jobs_config"]
    assert "synthetic" not in jobs and jobs["path_to_files"] is None
    assert cfg["epoch_loop"]["loop_mode"] == "fused"
    pads = cell.config["pads"]
    assert (pads["max_nodes"], pads["max_edges"]) == (300, 512)
    # 262 original ops x 16; (389 edges + 131 backward cliques) x 16^2
    assert pads["kernel_ops"] == 262 * 16
    assert pads["kernel_blocks"] == 389 + 131
    assert pads["kernel_deps"] == pads["kernel_blocks"] * 16 ** 2


# ------------------------------------------------ the tiny preset, run
TINY_ARCH = {"model_type": "tinymoe", "hidden_size": 64,
             "num_attention_heads": 4, "num_key_value_heads": 4,
             "intermediate_size": 32, "num_experts": 4,
             "num_experts_per_tok": 2, "num_hidden_layers": 2,
             "vocab_size": 256}


def test_tiny_preset_runs_the_training_path_traced(tiny_tree, capsys,
                                                   tmp_path):
    arch_file = tmp_path / "tinymoe.json"
    arch_file.write_text(json.dumps({"source_url": "test-local",
                                     "config": TINY_ARCH}))
    config = bench_tiny.tiny_config("tiny_olmoe", overrides=[
        "env_config=env_olmoe32", *bench_tiny.TINY_OVERRIDES[1:],
        f"env_config.jobs_config.architecture.config={arch_file}",
        "env_config.jobs_config.architecture.shapes="
        "[{seq_len: 32, micro_batch: 4096}, {seq_len: 32, micro_batch: 65536}]",
        "env_config.jobs_config.job_interarrival_time_dist.val=0.01",
        "env_config.max_simulation_run_time=1.0",
        "env_config.max_partitions_per_op=4",
        "env_config.pad_obs_kwargs={max_nodes: 50, max_edges: 64}"])
    config["expect"] = {"env_config.min_op_run_time_quantum": 1e-5,
                        "env_config.max_partitions_per_op": 4}
    bench_path = os.path.join(tiny_tree, "BENCHMARK.json")
    bench = json.load(open(bench_path))
    with open(os.path.join(tiny_tree, "benchmarks", "configs",
                           "tiny_olmoe.json"), "w") as fh:
        json.dump(config, fh)
    mix = dict(bench_tiny.tiny_traffic()["tiny_fused"], name="tiny_olmoe")
    with open(os.path.join(tiny_tree, "benchmarks", "traffic",
                           "tiny_olmoe.json"), "w") as fh:
        json.dump(mix, fh)
    bench["configs"].append({
        "name": "tiny_olmoe", "source": "test-local", "reduced": [],
        "file": "benchmarks/configs/tiny_olmoe.json", "why": "tiny"})
    bench["workloads"].append({
        "name": "tiny.olmoe", "config": "tiny_olmoe",
        "traffic": "tiny_olmoe", "chips": 1, "why": "tiny"})
    for metric in bench["end_to_end"]:
        if metric["name"] == "train_env_steps_per_s":
            metric["workloads"].append("tiny.olmoe")
    json.dump(bench, open(bench_path, "w"))

    result, notes = _result(capsys, _argv("tiny.olmoe", 1))
    _check_line(result, traced=True)
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW_METRICS) <= set(metrics), sorted(metrics)
    assert 0 < metrics["setup_job_graphs_s"] < metrics["setup_build_run_s"]
    assert metrics["setup_device_tables_s"] > 0
    # 38 real nodes under the 50-node pad, whatever the job
    assert metrics["obs_node_fill"] == pytest.approx(100 * 38 / 50)
    assert 0 < metrics["lookahead_block_fill_decided"] \
        <= metrics["lookahead_block_fill"] < 100
    assert metrics["compiles_in_window"] == 0.0
    assert any(n.startswith("[bench] fidelity") and '"ok": true' in n
               for n in notes)
    startup_line, = [n for n in notes if n.startswith("[startup] ")]
    gauges = json.loads(startup_line[len("[startup] "):])
    assert gauges["graphs.arch.forward_ops.tinymoe_s32_b4096"] == 19
    assert gauges["graphs.arch.edges.tinymoe_s32_b65536"] == 53
