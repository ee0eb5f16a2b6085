"""The cell ``glm5_ramp32.train_fused``: its files resolve and agree with
the composed tree and with the architecture file, it and the three older
cells list the per-layer metrics that read what a decision met, and a
tiny STATED preset of the same job source (1 dense + 1 expert layer +
MTP, hidden 64, 4 of 8 experts) runs the training path end to end on the
CPU with those metrics in its traced line."""
import json
import os

import pytest

import bench_tiny
from benchmarks import harness
from benchmarks.paths import train
from test_bench_run import (_argv, _check_line, _result,  # noqa: F401
                            restore_process_state, tiny_tree)

REPO = bench_tiny.REPO
CELL = "glm5_ramp32.train_fused"
OLD_CELLS = ("ramp32_dev.train_fused", "ramp32_load32.train_fused",
             "olmoe_ramp32.train_fused")
NEW_METRICS = ("decision_accept_share", "cluster_occupied_share",
               "mask_placeable_share")
ARCH_FILE = "ddls_tpu/graphs/arch_configs/glm_5.json"


def test_cell_is_32_lanes_of_the_glm5_queue():
    cell = harness.load_cell(CELL)
    assert (cell.chips, cell.config_name, cell.traffic_name, cell.path) \
        == (1, "glm5_share_ramp32", "train_fused_glm5", "train")
    mix = cell.traffic
    lanes = mix["epoch"]["lanes"]
    assert lanes in (32, 48)         # the issue's two packed-form sizes
    assert mix["epoch"] == {"lanes": lanes, "steps": 1, "env_steps": lanes}
    assert f"epoch_loop.fused_config={{lanes: {lanes}, segment_len: 1}}" \
        in mix["overrides"]
    assert f"epoch_loop.num_envs={lanes}" in mix["overrides"]
    assert "epoch_loop.updates_per_epoch=1" in mix["overrides"]
    assert mix["fidelity"]["kind"] == "jitted_episode"
    assert mix["fidelity"]["decisions"] in (32, 48)
    assert mix["fidelity"]["rtol"] == 1e-4
    assert (mix["warmup_epochs"], mix["statistic"], mix["trace_epochs"],
            mix["train_seed"]) == (1, "window_share", 1, 0)
    # the steadier reading beside it: a fixed set of the window's epochs
    k0, k1 = mix["measure_epochs"]
    assert 0 < k0 < k1 and k1 - k0 >= 50 and mix["why_measure_epochs"]
    assert "program_spans" not in mix
    assert cell.config["composed_from"]["overrides"] == [
        "env_config=env_glm5_32"]
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
    # the depth cut is a key of its own: the model keeps its 78 layers,
    # this pipeline stage holds num_layers of them (+ the MTP module)
    assert (config["num_hidden_layers"], config["num_layers"],
            config["n_routed_experts"]) == (78, 3 + 4, 64)
    differs.add("num_layers")
    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    entry = next(c for c in bench["configs"]
                 if c["name"] == "glm5_share_ramp32")
    assert set(entry["reduced"]) == differs | {"train_batch_size"} \
        == set(config["reduced"])
    assert entry["source"] == arch["source_url"]
    for field in ("deployment", "assumed", "reduced", "published"):
        assert config[field], field


@pytest.mark.parametrize("metric", [
    *NEW_METRICS, "compile_s", "compiles_in_window", "memo_hit_rate",
    "lookahead_lockstep_efficiency", "lookahead_block_fill_decided",
    "obs_node_fill", "advance_device_s", "lookahead_device_s",
    "placement_device_s", "pricing_device_s", "fused_update_device_s"])
def test_cell_reports_the_metric(metric):
    assert metric in {m["name"] for m in harness.load_cell(CELL).per_layer}


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_metrics_are_read_in_the_old_cells_too(metric):
    """They read counters every fused run drains, so all four cells
    report them (near 0 on ``olmoe``: the finding they make visible)."""
    for cell in OLD_CELLS:
        assert metric in {m["name"]
                          for m in harness.load_cell(cell).per_layer}
    spec = harness.read_json(os.path.join(
        harness.BENCH_DIR, "layer_metrics", metric + ".json"))
    assert spec["source"]["kind"] == "metric_ratio"
    for part in (spec["source"]["num"], spec["source"]["den"]):
        assert harness.read_json(os.path.join(
            harness.BENCH_DIR, "layer_metrics", part + ".json")
        )["source"]["kind"] == "telemetry_counter"


def test_old_cells_report_what_they_reported():
    """This PR appended: the parent's per-layer list of every old cell
    (31 names) leads today's, the three new follow, and what later PRs
    listed comes behind: nothing is pinned as the last."""
    new = set(NEW_METRICS)
    for cell in (*OLD_CELLS, CELL):
        names = [m["name"] for m in harness.load_cell(cell).per_layer]
        assert names[31:34] == list(NEW_METRICS)
        assert not new & set(names[:31]) and len(set(names)) == len(names)
        assert names[:34] == [
            m["name"] for m in harness.load_cell(CELL).per_layer][:34]


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
    assert (pads["max_nodes"], pads["max_edges"]) == (250, 512)
    # 218 original ops x 16; (347 edges + 109 backward cliques) x 16^2
    assert pads["kernel_ops"] == 218 * 16
    assert pads["kernel_blocks"] == 347 + 109
    assert pads["kernel_deps"] == pads["kernel_blocks"] * 16 ** 2


# ------------------------------------------------ the tiny preset, run
TINY_ARCH = {"model_type": "tinyglm", "hidden_size": 64,
             "num_attention_heads": 4, "q_lora_rank": 32,
             "kv_lora_rank": 16, "qk_nope_head_dim": 12,
             "qk_rope_head_dim": 4, "v_head_dim": 16, "index_n_heads": 2,
             "index_head_dim": 8, "index_topk": 16,
             "intermediate_size": 128, "moe_intermediate_size": 32,
             "n_routed_experts": 8, "n_shared_experts": 1,
             "num_experts_per_tok": 2, "first_k_dense_replace": 1,
             "num_hidden_layers": 3, "num_nextn_predict_layers": 1,
             "scoring_func": "sigmoid", "vocab_size": 256}


def test_tiny_stated_preset_runs_the_training_path_traced(
        tiny_tree, capsys, tmp_path):
    arch_file = tmp_path / "tinyglm.json"
    arch_file.write_text(json.dumps({
        "source_url": "test-local", "config": TINY_ARCH,
        "training_state": {"resident_bytes_per_parameter": 16,
                           "synced_bytes_per_parameter": 2}}))
    config = bench_tiny.tiny_config("tiny_glm5", overrides=[
        "env_config=env_glm5_32", *bench_tiny.TINY_OVERRIDES[1:],
        f"env_config.jobs_config.architecture.config={arch_file}",
        "env_config.jobs_config.architecture.layers="
        "{leading_dense: 1, following: 1}",
        "env_config.jobs_config.architecture.experts_held=4",
        "env_config.jobs_config.architecture.shapes="
        "[{seq_len: 32, micro_batch: 4096}, {seq_len: 32, micro_batch: 524288}]",
        "env_config.jobs_config.job_interarrival_time_dist.val=0.01",
        # a tiny job gains little by partitioning: only an SLA of the
        # whole sequential time lets some through
        "env_config.jobs_config.max_acceptable_job_completion_time_frac_dist="
        "{_target_: ddls_tpu.demands.distributions.Fixed, val: 1.0}",
        "env_config.max_simulation_run_time=1.0",
        "env_config.max_partitions_per_op=4",
        # at hidden 64 every op is memory-bound and the real fabric
        # buys no time by partitioning (tests/test_arch_graphs.py)
        "env_config.topology_config.kwargs.total_node_bandwidth=1.6e14",
        "env_config.pad_obs_kwargs={max_nodes: 100, max_edges: 192}"])
    config["expect"] = {"env_config.min_op_run_time_quantum": 1e-5,
                        "env_config.max_partitions_per_op": 4}
    bench_path = os.path.join(tiny_tree, "BENCHMARK.json")
    bench = json.load(open(bench_path))
    with open(os.path.join(tiny_tree, "benchmarks", "configs",
                           "tiny_glm5.json"), "w") as fh:
        json.dump(config, fh)
    mix = dict(bench_tiny.tiny_traffic()["tiny_fused"], name="tiny_glm5")
    with open(os.path.join(tiny_tree, "benchmarks", "traffic",
                           "tiny_glm5.json"), "w") as fh:
        json.dump(mix, fh)
    bench["configs"].append({
        "name": "tiny_glm5", "source": "test-local", "reduced": [],
        "file": "benchmarks/configs/tiny_glm5.json", "why": "tiny"})
    bench["workloads"].append({
        "name": "tiny.glm5", "config": "tiny_glm5",
        "traffic": "tiny_glm5", "chips": 1, "why": "tiny"})
    for metric in bench["end_to_end"]:
        if metric["name"] == "train_env_steps_per_s":
            metric["workloads"].append("tiny.glm5")
    json.dump(bench, open(bench_path, "w"))

    result, notes = _result(capsys, _argv("tiny.glm5", 1))
    _check_line(result, traced=True)
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW_METRICS) <= set(metrics), sorted(metrics)
    # jobs get in, so decisions meet clusters that hold jobs
    assert 0 < metrics["decision_accept_share"] < 100
    assert 0 < metrics["cluster_occupied_share"] < 100
    # 2 shapes x the actions {1, 2, 4} the mask offers on an empty
    # cluster; the allocator places every one at this size
    assert metrics["mask_rows_offered"] == 6.0
    assert metrics["mask_placeable_share"] == 100.0
    assert metrics["decisions_offered"] == 16.0      # 8 lanes x 2 steps
    assert metrics["cluster_servers"] == 16.0 * 32
    # 90 real nodes (45 forward ops mirrored) under the 100-node pad
    assert metrics["obs_node_fill"] == pytest.approx(90.0)
    assert metrics["compiles_in_window"] == 0.0
    assert any(n.startswith("[bench] fidelity") and '"ok": true' in n
               for n in notes)
    startup_line, = [n for n in notes if n.startswith("[startup] ")]
    gauges = json.loads(startup_line[len("[startup] "):])
    assert gauges["graphs.arch.forward_ops.tinyglm_s32_b4096"] == 45
    assert gauges["env.mask.rows_offered"] == 6
    assert gauges["env.mask.rows_placeable"] == 6
    for model in ("tinyglm_s32_b4096", "tinyglm_s32_b524288"):
        # stated: deps carry activations, syncs the 2 B gradients
        resident = gauges[f"graphs.arch.resident_bytes.{model}"]
        assert gauges[f"graphs.arch.sync_bytes_max.{model}"] \
            < gauges[f"graphs.arch.payload_bytes_max.{model}"] < resident
