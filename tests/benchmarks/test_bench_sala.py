"""The cell ``sala_ramp32.train_fused``: its files resolve and agree
with the composed tree and with the architecture file, NOTHING but the
batch is reduced, it lists the two per-layer metrics this PR adds beside
everything ``trinity_ramp32.train_fused`` reports, the older cells
report what they reported, what this PR added to ``BENCHMARK.json`` was
appended behind what was there (and leaves room for the next), and a
tiny STATED preset of the same job source (whole and dense: mixers A L L
A, hidden 64, a 62-op and a 70-op graph of one model) runs the training
path end to end on the CPU with the new metrics, counters and gauges in
its traced line and `[startup]` line."""
import json
import os

import pytest

import bench_tiny
from bench_history import benchmark_as_of
from benchmarks import harness
from benchmarks.paths import train
from test_bench_mimo import _add_cell
from test_bench_run import (_argv, _check_line, _result,  # noqa: F401
                            restore_process_state, tiny_tree)

REPO = bench_tiny.REPO
CELL = "sala_ramp32.train_fused"
PARENT_LAST = "trinity_ramp32.train_fused"
OLD_CELLS = ("ramp32_dev.train_fused", "ramp32_load32.train_fused",
             "olmoe_ramp32.train_fused", "glm5_ramp32.train_fused",
             "mimo_ramp32.train_fused", PARENT_LAST)
CONFIG = "minicpm_sala_whole_ramp32"
NEW_METRICS = ("decision_accept_share_ragged",
               "decision_ragged_offered_share")
#: their parts: read by the ratios, listed for no cell
PARTS = {"decisions_offered_ragged": "env.decisions.offered_ragged",
         "decisions_accepted_ragged": "env.decisions.accepted_ragged"}
ARCH_FILE = "ddls_tpu/graphs/arch_configs/minicpm_sala.json"
BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))


def parent_of(bench: dict) -> dict:
    """The parent's benchmark: the later cells taken away, and the
    metrics that stand behind this PR's own two (PRs only append)."""
    parent = benchmark_as_of(bench, PARENT_LAST)
    parent["per_layer"] = parent["per_layer"][
        :[m["name"] for m in bench["per_layer"]].index(NEW_METRICS[0])]
    return parent


PARENT = parent_of(BENCH)


def _entry(kind, name, bench=BENCH):
    """BENCHMARK.json's entry of that name, wherever it stands."""
    entry, = [e for e in bench[kind] if e["name"] == name]
    return entry


def test_cell_is_16_lanes_of_the_sala_queue():
    cell = harness.load_cell(CELL)
    assert (cell.chips, cell.config_name, cell.traffic_name, cell.path) \
        == (1, CONFIG, "train_fused_sala", "train")
    mix = cell.traffic
    lanes = mix["epoch"]["lanes"]
    assert lanes in (16, 24)         # the issue's two packed-form sizes
    assert mix["epoch"] == {"lanes": lanes, "steps": 1, "env_steps": lanes}
    assert f"epoch_loop.fused_config={{lanes: {lanes}, segment_len: 1}}" \
        in mix["overrides"]
    assert f"epoch_loop.num_envs={lanes}" in mix["overrides"]
    assert "epoch_loop.updates_per_epoch=1" in mix["overrides"]
    assert "epoch_loop.loop_mode=fused" in mix["overrides"]
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
    # but for the lanes, the words and the measured set of epochs (a
    # window holds as many as its own epochs are long), the mix is
    # trinity's
    other = harness.load_cell(PARENT_LAST).traffic
    same = set(mix) - {"name", "what", "why_this_shape", "fidelity",
                       "overrides", "epoch", "measure_epochs",
                       "why_measure_epochs"}
    assert {k: mix[k] for k in same} == {k: other[k] for k in same}
    assert cell.config["composed_from"]["overrides"] == [
        "env_config=env_sala_32"]
    assert cell.config["train_batch_size"] == lanes
    assert {m["name"] for m in cell.end_to_end} == {
        "train_env_steps_per_s", "setup_s"}
    entry = _entry("workloads", CELL)
    assert entry["chips"] == 1 and len(entry["why"]) <= 200


def test_published_is_the_architecture_file_and_only_the_batch_is_reduced():
    """The widths are pinned twice: the architecture file the program
    reads and the ``published`` block (the catalog row's keys) are the
    same numbers, and so is the top level: depth and vocabulary are
    WHOLE, the model is dense, the file says so in words, and
    ``reduced`` is ``train_batch_size`` alone, here and in
    BENCHMARK.json. What the row does not give is under ``assumed``,
    each size with its source, and in the architecture file's
    ``modeling`` block — never in the builder."""
    arch = json.load(open(os.path.join(REPO, ARCH_FILE)))
    config = harness.load_cell(CELL).config
    assert arch["source_url"] == config["source"]
    assert arch["training_state"] == {"resident_bytes_per_parameter": 16,
                                      "synced_bytes_per_parameter": 2}
    published = dict(config["published"])
    assert published.pop("train_batch_size") == 4000
    assert published == arch["config"]
    assert {k for k, v in arch["config"].items() if config[k] != v} == set()
    entry = _entry("configs", CONFIG)
    assert set(entry["reduced"]) == {"train_batch_size"} \
        == set(config["reduced"])
    assert entry["source"] == arch["source_url"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    words = config["reduced"]["train_batch_size"]
    for whole in ("DEPTH is whole", "VOCABULARY is whole", "DENSE",
                  "9.477 B", "NOT queued"):
        assert whole in words
    assert "whole" in config["deployment"]
    for field in ("deployment", "assumed", "reduced", "published"):
        assert config[field], field
    # every departure and every check is written down
    assumed = config["assumed"]
    assert {"sparse_config", "lightning_chunk_size", "key_readings",
            "op_graph", "chunk_states", "layout", "ragged_rows",
            "placeable_on_an_empty_cluster", "sequence_lengths",
            "arrivals"} <= set(assumed)
    # the eight assumed sizes: stated with their source, and the
    # numbers are the modeling block's
    modeling = arch["modeling"]
    assert set(modeling) == {"sparse_config", "lightning_chunk_size"}
    assert modeling["sparse_config"] == {
        "kernel_size": 32, "kernel_stride": 16, "block_size": 64,
        "topk": 64, "window_size": 2048, "init_blocks": 1,
        "dense_len": 8192}
    for key, value in modeling["sparse_config"].items():
        assert f"{key} {value}" in assumed["sparse_config"], key
    assert "2506.07900" in assumed["sparse_config"]
    assert modeling["lightning_chunk_size"] == 256
    assert "256" in assumed["lightning_chunk_size"] \
        and "2401.04658" in assumed["lightning_chunk_size"]
    assert not set(modeling) & set(arch["config"])
    assert "LEFT OUT" in assumed["chunk_states"]


def test_catalog_numbers_sit_at_the_top_level_under_the_same_keys():
    """What the driver compares: every number of the catalog row's
    ``config`` at the file's top level, equal; lists copied whole."""
    arch = json.load(open(os.path.join(REPO, ARCH_FILE)))["config"]
    config = harness.load_cell(CELL).config
    for key, value in arch.items():
        assert config[key] == value, key
    assert (config["num_hidden_layers"], config["vocab_size"],
            config["max_position_embeddings"]) == (32, 73448, 524288)
    assert (config["hidden_size"], config["head_dim"],
            config["intermediate_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["lightning_nh"],
            config["lightning_nkv"], config["lightning_head_dim"]) \
        == (4096, 128, 16384, 32, 2, 32, 32, 128)
    assert (config["mixer_types"].count("lightning-attn"),
            config["mixer_types"].count("minicpm4")) == (24, 8)
    assert not {"num_experts", "n_routed_experts",
                "num_experts_per_tok"} & set(config)      # dense


def test_cell_reports_every_metric_trinitys_does_and_the_two_new():
    """Trinity's 41 of the parent's benchmark lead, the two new follow,
    and whatever a later PR listed for both cells comes behind: nothing
    is pinned as the last."""
    names = [m["name"] for m in harness.load_cell(CELL).per_layer]
    trinity = [m["name"] for m in harness.load_cell(PARENT_LAST).per_layer]
    assert trinity[:41] == [m["name"] for m in PARENT["per_layer"]
                            if PARENT_LAST in m["workloads"]]
    assert names[:43] == trinity[:41] + list(NEW_METRICS)
    assert set(trinity) <= set(names) and len(set(names)) == len(names)


@pytest.mark.parametrize("metric", [
    *NEW_METRICS, "decision_blocked_placement_share",
    "decision_accept_share_longest", "job_quadratic_time_share",
    "epoch_device_wait_p50_s", "epoch_host_p50_ms",
    "epoch_observer_p50_ms", "device_idle_observer_share", "compile_s",
    "compiles_in_window", "memo_hit_rate", "lookahead_lockstep_efficiency",
    "lookahead_block_fill_decided", "obs_node_fill", "lookahead_device_s",
    "placement_device_s", "decision_accept_share",
    "cluster_occupied_share", "mask_placeable_share", "peak_hbm_bytes"])
def test_cell_reports_the_metric(metric):
    assert metric in {m["name"] for m in harness.load_cell(CELL).per_layer}


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_metrics_are_data_of_reader_kinds_that_exist(metric):
    """Ratios of telemetry counters, like `decision_accept_share_longest`:
    no benchmark code is added, and the count of drained traces
    cancels. They are in BENCHMARK.json for the new cell alone, which
    reports the metric moved; their parts are listed for no cell."""
    spec = harness.read_json(os.path.join(
        harness.BENCH_DIR, "layer_metrics", metric + ".json"))
    entry = _entry("per_layer", metric)
    assert entry["workloads"][0] == CELL
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert entry["source"] == "program_counter"
    assert (entry["layer"], entry["unit"], entry["moves"]) \
        == (spec["layer"], spec["unit"], spec["moves"]) \
        == ("device collection", "%", "train_env_steps_per_s")
    assert spec["scale"] == 100
    assert spec["source"] == {
        "decision_accept_share_ragged": {
            "kind": "metric_ratio", "num": "decisions_accepted_ragged",
            "den": "decisions_offered_ragged"},
        "decision_ragged_offered_share": {
            "kind": "metric_ratio", "num": "decisions_offered_ragged",
            "den": "decisions_offered"}}[metric]
    kinds = {f[:-3] for f in os.listdir(os.path.join(harness.BENCH_DIR,
                                                     "sources"))}
    for part in (spec["source"]["num"], spec["source"]["den"]):
        source = harness.read_json(os.path.join(
            harness.BENCH_DIR, "layer_metrics", part + ".json"))["source"]
        assert source["kind"] == "telemetry_counter" and source["per_epoch"]
        assert source["kind"] in kinds and spec["source"]["kind"] in kinds
        if part in PARTS:
            assert source["counter"] == PARTS[part]
            assert part not in {m["name"] for m in BENCH["per_layer"]}
    # everything this PR put under benchmarks/ is data
    added = [f"layer_metrics/{n}.json" for n in (*NEW_METRICS, *PARTS)] \
        + [f"configs/{CONFIG}.json", "traffic/train_fused_sala.json"]
    for path in added:
        assert os.path.exists(os.path.join(harness.BENCH_DIR, path)), path


def test_this_pr_appended_and_old_cells_report_what_they_reported():
    """Taking the new cell away gives the parent's benchmark entry for
    entry: its configurations, cells and metrics are a prefix of
    today's, in their order, with bounds and `run_seconds` untouched;
    the new cell joined every list that names trinity's, behind it. A
    later PR's entries may follow: nothing here is pinned as the last."""
    for key in ("command", "paths", "run_seconds"):
        assert PARENT[key] == BENCH[key]
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        assert [e["name"] for e in BENCH[kind]][:len(PARENT[kind])] \
            == [e["name"] for e in PARENT[kind]], kind
    assert [w["name"] for w in PARENT["workloads"]] == list(OLD_CELLS)
    assert [w["name"] for w in BENCH["workloads"]][:7] == [*OLD_CELLS, CELL]
    assert [c["name"] for c in BENCH["configs"]][6] == CONFIG
    assert [m["name"] for m in BENCH["per_layer"]][
        len(PARENT["per_layer"]):][:2] == list(NEW_METRICS)
    joined = 0
    for kind in ("end_to_end", "per_layer"):
        for old in PARENT[kind]:
            new = _entry(kind, old["name"])
            assert {k: v for k, v in new.items() if k != "workloads"} \
                == {k: v for k, v in old.items() if k != "workloads"}
            if "workloads" not in old:
                assert "workloads" not in new
                continue
            cells = new["workloads"]
            assert cells[:len(old["workloads"])] == old["workloads"]
            if PARENT_LAST in old["workloads"]:
                assert cells[len(old["workloads"])] == CELL
                joined += 1
            else:
                assert CELL not in cells
    assert joined == 1 + 41      # train_env_steps_per_s and 41 per-layer
    for entry in (*PARENT["configs"], *PARENT["workloads"]):
        kind = "configs" if "file" in entry else "workloads"
        assert _entry(kind, entry["name"]) == entry
    # so every old cell reports what it reported, and then what later
    # PRs listed for it
    for cell in OLD_CELLS:
        names = [m["name"] for m in harness.load_cell(cell).per_layer]
        reported = [m["name"] for m in PARENT["per_layer"]
                    if cell in m["workloads"]]
        assert names[:len(reported)] == reported
        assert not set(NEW_METRICS) & set(names)


def test_composed_tree_is_what_the_configuration_file_expects(tmp_path):
    """``compose`` checks ``expect``; beyond it, the kernel pads the
    file describes are what the tables of that tree are built to: the
    fifth pad class, sized by the LARGER of the model's two graphs."""
    cell = harness.load_cell(CELL)
    cfg = train.compose(cell, 0, str(tmp_path))
    jobs = cfg["env_config"]["jobs_config"]
    assert "synthetic" not in jobs and jobs["path_to_files"] is None
    assert set(jobs["architecture"]) == {"config", "shapes"}   # no cut
    assert jobs["architecture"]["config"] == ARCH_FILE
    assert [(s["seq_len"], s["micro_batch"])
            for s in jobs["architecture"]["shapes"]] \
        == [(4096, 1), (8192, 4), (32768, 1), (131072, 1)]
    assert cfg["epoch_loop"]["loop_mode"] == "fused"
    assert cfg["env_config"]["jobs_config"][
        "job_interarrival_time_dist"]["val"] == 18
    assert cfg["env_config"]["max_simulation_run_time"] == 7200
    pads = cell.config["pads"]
    assert (pads["max_nodes"], pads["max_edges"]) == (500, 768)
    # 486 original ops x 16; (709 edges + 243 backward cliques) x 16^2
    assert pads["kernel_ops"] == 486 * 16 == 7776
    assert pads["kernel_blocks"] == 709 + 243 == 952
    assert pads["kernel_deps"] == pads["kernel_blocks"] * 16 ** 2 == 243712
    assert pads["kernel_fwd_ops"] == 243
    # between olmoe's and trinity's: the fifth pad class
    slots = {name: harness.load_cell(name + "_ramp32.train_fused").config[
        "pads"]["kernel_deps"] for name in ("olmoe", "trinity")}
    assert slots["olmoe"] < pads["kernel_deps"] < slots["trinity"]
    # a lane's 128-key memo, as the traffic file states it
    memo = 128 * (pads["kernel_ops"] + pads["kernel_deps"]) * 4
    assert memo == 128_761_856 and round(memo / 1e6, 1) == 128.8
    assert "128.8 MB" in cell.traffic["what"]
    # the obs pads keep the GNN's contraction form (ops/segment.py)
    from ddls_tpu.ops.segment import DENSE_MAX_CELLS

    assert pads["max_nodes"] * pads["max_edges"] == 384000 \
        <= DENSE_MAX_CELLS


# ------------------------------------------------ the tiny preset, run
TINY_ARCH = {"model_type": "tinysala", "hidden_size": 64,
             "num_hidden_layers": 4, "intermediate_size": 128,
             "vocab_size": 256,
             "mixer_types": ["minicpm4", "lightning-attn",
                             "lightning-attn", "minicpm4"],
             "num_attention_heads": 4, "num_key_value_heads": 1,
             "head_dim": 16, "attn_use_rope": False,
             "attn_use_output_gate": True,
             "lightning_nh": 4, "lightning_nkv": 4,
             "lightning_head_dim": 16, "lightning_use_rope": True,
             "lightning_scale": "1/sqrt(d)", "qk_norm": True,
             "use_output_gate": True, "use_output_norm": True,
             "scale_emb": 12, "scale_depth": 1.4, "dim_model_base": 32}
TINY_MODELING = {
    "sparse_config": {"kernel_size": 8, "kernel_stride": 4, "block_size": 8,
                      "topk": 2, "window_size": 16, "init_blocks": 1,
                      "dense_len": 64},
    "lightning_chunk_size": 16}


def test_tiny_sala_preset_runs_the_training_path_traced(
        tiny_tree, capsys, tmp_path):
    arch_file = tmp_path / "tinysala.json"
    arch_file.write_text(json.dumps({
        "source_url": "test-local", "config": TINY_ARCH,
        "modeling": TINY_MODELING,
        "training_state": {"resident_bytes_per_parameter": 16,
                           "synced_bytes_per_parameter": 2}}))
    config = bench_tiny.tiny_config("tiny_sala", overrides=[
        "env_config=env_sala_32", *bench_tiny.TINY_OVERRIDES[1:],
        f"env_config.jobs_config.architecture.config={arch_file}",
        # a dense-path shape whose ops are 17-50 us (ragged rows at
        # degree 4) and a sparse-path one: two graph sizes in one bank
        "env_config.jobs_config.architecture.shapes="
        "[{seq_len: 32, micro_batch: 4096}, {seq_len: 128, micro_batch: 131072}]",
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
    mix = dict(bench_tiny.tiny_traffic()["tiny_fused"], name="tiny_sala")
    _add_cell(tiny_tree, "tiny.sala", config, mix)

    result, notes = _result(capsys, _argv("tiny.sala", 1))
    _check_line(result, traced=True)
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW_METRICS) | set(PARTS) <= set(metrics), sorted(metrics)
    # decisions on a ragged row are a part of the decisions, and no more
    # of them are accepted than offered or than accepted in all
    assert metrics["decisions_offered"] == 16.0
    assert 0 < metrics["decisions_offered_ragged"] < 16.0
    assert 0 <= metrics["decisions_accepted_ragged"] \
        <= min(metrics["decisions_offered_ragged"],
               metrics["decisions_accepted"])
    assert metrics["decision_accept_share_ragged"] == pytest.approx(
        100 * metrics["decisions_accepted_ragged"]
        / metrics["decisions_offered_ragged"])
    assert metrics["decision_ragged_offered_share"] == pytest.approx(
        100 * metrics["decisions_offered_ragged"] / 16.0)
    assert 0 < metrics["decision_accept_share"] <= 100
    # the larger graph is 70 nodes (35 forward ops mirrored), the smaller
    # 62, under the 100-node pad: the fill lies between
    assert 62.0 <= metrics["obs_node_fill"] <= 70.0
    assert metrics["job_models"] == 2.0
    assert metrics["compiles_in_window"] == 0.0
    assert any(n.startswith("[bench] fidelity") and '"ok": true' in n
               for n in notes)
    startup_line, = [n for n in notes if n.startswith("[startup] ")]
    gauges = json.loads(startup_line[len("[startup] "):])
    # per SHAPE where one model builds two graphs; 14 of the short
    # shape's 31 forward ops are under 4 quanta of 10 us
    for model, forward, edges, sparse, ragged in (
            ("tinysala_s32_b4096", 31, 85, 0, 14),
            ("tinysala_s128_b131072", 35, 101, 2, 0)):
        assert gauges[f"graphs.arch.forward_ops.{model}"] == forward
        assert gauges[f"graphs.arch.edges.{model}"] == edges
        assert gauges[f"graphs.arch.layers_linear.{model}"] == 2
        assert gauges[f"graphs.arch.layers_block_sparse.{model}"] == sparse
        assert gauges[f"graphs.arch.layers_full.{model}"] == 2 - sparse
        assert 0 < gauges[f"graphs.arch.linear_time_share.{model}"] < 0.2
        assert gauges[f"graphs.arch.ragged_ops.{model}"] == ragged


def test_synthetic_jobs_count_ragged_rows_and_read_no_ragged_share(
        tiny_tree, capsys):
    """The old cells' kind of run (synthetic chains): the two counters
    are counted in every fused run — `decision_ragged_offered_share` is
    read there too — and where no ragged row was chosen the accept
    share's denominator is 0 and the metric is left out of the line."""
    from ddls_tpu.telemetry import startup

    startup.registry().reset()
    result, notes = _result(capsys, _argv("tiny.fused", 1))
    _check_line(result, traced=True)
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert 0 <= metrics["decisions_accepted_ragged"] \
        <= metrics["decisions_offered_ragged"] <= 16.0
    assert metrics["decision_ragged_offered_share"] == pytest.approx(
        100 * metrics["decisions_offered_ragged"] / 16.0)
    assert ("decision_accept_share_ragged" in metrics) \
        == (metrics["decisions_offered_ragged"] > 0)
    startup_line, = [n for n in notes if n.startswith("[startup] ")]
    assert "graphs.arch." not in startup_line
