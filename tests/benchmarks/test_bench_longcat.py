"""The cell ``longcat_ramp32.train_fused``: its files resolve and agree
with the composed tree and with the architecture file, ``reduced`` is
the depth, the experts held and the batch, it lists the three per-layer
metrics this PR adds beside everything ``sala_ramp32.train_fused``
reports but the two ``_ragged`` shares, the older cells report what they
reported, what this PR added to ``BENCHMARK.json`` was appended behind
what was there (and leaves room for the next: the pins of every older
PR, and this one's, hold with a cell appended behind THIS one), and a
tiny STATED preset of the same job source (2 double layers, hidden 64, 8
FFN + 4 zero-compute experts, 3 a token, 4 held) runs the training path
end to end on the CPU with the new metrics, counter and gauges in its
traced line and `[startup]` line."""
import copy
import json
import os

import pytest

import bench_tiny
import test_bench_room
from bench_history import benchmark_as_of
from benchmarks import harness
from benchmarks.paths import train
from test_bench_mimo import _add_cell
from test_bench_run import (_argv, _check_line, _result,  # noqa: F401
                            restore_process_state, tiny_tree)

REPO = bench_tiny.REPO
CELL = "longcat_ramp32.train_fused"
PARENT_LAST = "sala_ramp32.train_fused"
OLD_CELLS = ("ramp32_dev.train_fused", "ramp32_load32.train_fused",
             "olmoe_ramp32.train_fused", "glm5_ramp32.train_fused",
             "mimo_ramp32.train_fused", "trinity_ramp32.train_fused",
             PARENT_LAST)
CONFIG = "longcat_flash_omni_share_ramp32"
NEW_METRICS = ("job_branch_time_share", "job_zero_routed_share",
               "lookahead_trips_per_op")
#: sala's two that this cell does not join: no ragged row is mounted
NOT_JOINED = ("decision_accept_share_ragged",
              "decision_ragged_offered_share")
#: the new metrics' new parts: read by the ratios, listed for no cell
PARTS = {"job_branch_time_shares": "graphs.arch.branch_time_shares",
         "job_zero_routed_shares": "graphs.arch.zero_routed_shares",
         "lookahead_ops_decided": "sim.lookahead.ops_decided"}
ARCH_FILE = "ddls_tpu/graphs/arch_configs/longcat_flash_omni.json"
BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))


def parent_of(bench: dict) -> dict:
    """The parent's benchmark: the later cells taken away, and the
    metrics that stand behind this PR's own three (PRs only append)."""
    parent = benchmark_as_of(bench, PARENT_LAST)
    parent["per_layer"] = parent["per_layer"][
        :[m["name"] for m in bench["per_layer"]].index(NEW_METRICS[0])]
    return parent


PARENT = parent_of(BENCH)


def _entry(kind, name, bench=None):
    """BENCHMARK.json's entry of that name, wherever it stands."""
    entry, = [e for e in (bench or BENCH)[kind] if e["name"] == name]
    return entry


def test_cell_is_48_lanes_of_the_longcat_queue():
    cell = harness.load_cell(CELL)
    assert (cell.chips, cell.config_name, cell.traffic_name, cell.path) \
        == (1, CONFIG, "train_fused_longcat", "train")
    mix = cell.traffic
    lanes = mix["epoch"]["lanes"]
    assert lanes in (48, 64)         # the issue's two packed-form sizes
    assert mix["epoch"] == {"lanes": lanes, "steps": 1, "env_steps": lanes}
    assert f"epoch_loop.fused_config={{lanes: {lanes}, segment_len: 1}}" \
        in mix["overrides"]
    assert f"epoch_loop.num_envs={lanes}" in mix["overrides"]
    assert "epoch_loop.updates_per_epoch=1" in mix["overrides"]
    assert "epoch_loop.loop_mode=fused" in mix["overrides"]
    assert mix["fidelity"]["kind"] == "jitted_episode"
    assert mix["fidelity"]["decisions"] == 48
    assert mix["fidelity"]["rtol"] == 1e-4
    assert mix["fidelity"]["why_decisions"] and mix["fidelity"]["why_rtol"]
    assert (mix["warmup_epochs"], mix["statistic"], mix["trace_epochs"],
            mix["train_seed"]) == (1, "window_share", 1, 0)
    k0, k1 = mix["measure_epochs"]
    assert 0 < k0 < k1 and k1 - k0 >= 50
    assert "my chip runs, PR 48" in mix["why_measure_epochs"]
    assert "program_spans" not in mix
    # but for the lanes, the words and the measured set of epochs, the
    # mix is glm5's (the other 48-lane cell)
    other = harness.load_cell("glm5_ramp32.train_fused").traffic
    same = set(mix) - {"name", "what", "why_this_shape", "fidelity",
                       "overrides", "epoch", "measure_epochs",
                       "why_measure_epochs"}
    assert {k: mix[k] for k in same} == {k: other[k] for k in same}
    assert cell.config["composed_from"]["overrides"] == [
        "env_config=env_longcat_32"]
    assert cell.config["train_batch_size"] == lanes
    assert {m["name"] for m in cell.end_to_end} == {
        "train_env_steps_per_s", "setup_s"}
    entry = _entry("workloads", CELL)
    assert entry["chips"] == 1 and len(entry["why"]) <= 200


def test_published_is_the_architecture_file_and_three_keys_are_reduced():
    """The widths are pinned twice: the architecture file the program
    reads and the ``published`` block (the catalog row's keys) are the
    same numbers; the top level differs from them in the two cuts
    alone, each under the catalog's own key, with its arithmetic in
    ``reduced``; no width is among them. What the row does not give is
    under ``assumed`` and in the architecture file's ``modeling`` block
    — never in the builder."""
    arch = json.load(open(os.path.join(REPO, ARCH_FILE)))
    config = harness.load_cell(CELL).config
    assert arch["source_url"] == config["source"]
    assert arch["training_state"] == {"resident_bytes_per_parameter": 16,
                                      "synced_bytes_per_parameter": 2}
    published = dict(config["published"])
    assert published.pop("train_batch_size") == 4000
    assert published == arch["config"]
    assert {k: config[k] for k, v in arch["config"].items()
            if config[k] != v} == {"num_layers": 4, "n_routed_experts": 128}
    entry = _entry("configs", CONFIG)
    assert set(entry["reduced"]) == set(config["reduced"]) == {
        "num_layers", "n_routed_experts", "train_batch_size"}
    for key in entry["reduced"]:    # no width: the contract's words
        assert not key.endswith(("_dim", "_rank")) and "hidden" not in key
    assert entry["source"] == arch["source_url"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert len(entry["why"]) <= 200
    for words, said in (("num_layers", ("560,664,980,480", "8.97 TB",
                                        "following: 4")),
                        ("n_routed_experts", ("23,493,469,184", "375.9 GB",
                                              "WHOLE on every pod",
                                              "experts_held = 128")),
                        ("train_batch_size", ("48.1 MB",))):
        for phrase in said:
            assert phrase in config["reduced"][words], (words, phrase)
    for field in ("deployment", "assumed", "reduced", "published",
                  "guarantees"):
        assert config[field], field
    assumed = config["assumed"]
    assert {"layout", "encoders", "modeling", "op_graph", "routing",
            "ragged_rows", "sequence_lengths", "arrivals"} <= set(assumed)
    assert "LEFT OUT" in assumed["encoders"]
    assert "balanced over the router's 768 outputs" in assumed["routing"]
    # the modeling block: three entries, each named in `assumed`, none a
    # key of the public config
    modeling = arch["modeling"]
    assert modeling == {"model_type": "longcat_flash",
                        "shortcut_sub_blocks": 2,
                        "e_score_correction_bias": True}
    for key in modeling:
        assert key in assumed["modeling"] and key in arch["modeling_why"]
    assert not set(modeling) & set(arch["config"])
    assert "LEFT OUT" in arch["what"]


def test_catalog_numbers_sit_at_the_top_level_under_the_same_keys():
    """What the driver compares: every number of the catalog row's
    ``config`` at the file's top level under the same key, equal but
    for the two listed in ``reduced``."""
    arch = json.load(open(os.path.join(REPO, ARCH_FILE)))["config"]
    config = harness.load_cell(CELL).config
    for key, value in arch.items():
        if key not in config["reduced"]:
            assert config[key] == value, key
    assert (config["hidden_size"], config["ffn_hidden_size"],
            config["expert_ffn_hidden_size"], config["num_attention_heads"],
            config["kv_lora_rank"], config["q_lora_rank"],
            config["qk_rope_head_dim"], config["qk_nope_head_dim"],
            config["v_head_dim"], config["moe_topk"],
            config["zero_expert_num"], config["vocab_size"]) \
        == (6144, 12288, 2048, 64, 512, 1536, 64, 128, 128, 12, 256,
            131072)
    assert not {"num_hidden_layers", "intermediate_size",
                "num_experts_per_tok", "model_type", "index_topk"} \
        & set(config)                   # the third family of key names


def test_cell_reports_every_metric_salas_does_but_the_ragged_two():
    """Sala's 48 of the parent's benchmark lead, less the two shares of
    MOUNTED ragged rows (this queue mounts none), the three new follow,
    and whatever a later PR lists comes behind: nothing is pinned as
    the last."""
    names = [m["name"] for m in harness.load_cell(CELL).per_layer]
    sala = [m["name"] for m in PARENT["per_layer"]
            if PARENT_LAST in m["workloads"]]
    assert len(sala) == 48 and set(NOT_JOINED) <= set(sala)
    joined = [n for n in sala if n not in NOT_JOINED]
    assert names[:49] == joined + list(NEW_METRICS)
    assert len(set(names)) == len(names)


@pytest.mark.parametrize("metric", [
    *NEW_METRICS, "decision_blocked_placement_share",
    "decision_accept_share_longest", "job_quadratic_time_share",
    "epoch_device_wait_p50_s", "epoch_host_p50_ms",
    "epoch_observer_p50_ms", "device_idle_observer_share", "compile_s",
    "compiles_in_window", "memo_hit_rate", "lookahead_lockstep_efficiency",
    "lookahead_block_fill_decided", "obs_node_fill", "lookahead_device_s",
    "placement_device_s", "decision_accept_share",
    "cluster_occupied_share", "mask_placeable_share", "peak_hbm_bytes",
    "lookahead_narrow_trip_share", "lookahead_narrowest_trip_share",
    "warm_epoch_rate_p50", "warm_set_env_steps_per_s",
    "long_epochs_in_window"])
def test_cell_reports_the_metric(metric):
    assert metric in {m["name"] for m in harness.load_cell(CELL).per_layer}


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_metrics_are_data_of_reader_kinds_that_exist(metric):
    """Ratios of telemetry counters, like `job_quadratic_time_share`: no
    benchmark code is added. They are in BENCHMARK.json for the new cell
    alone; their new parts are listed for no cell; the two descriptors'
    `what` says that they describe the queue."""
    spec = harness.read_json(os.path.join(
        harness.BENCH_DIR, "layer_metrics", metric + ".json"))
    entry = _entry("per_layer", metric)
    assert entry["workloads"][0] == CELL
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert entry["source"] == "program_counter"
    layer, unit, scale, source = {
        "job_branch_time_share": ("job graphs", "%", 100, {
            "kind": "metric_ratio", "num": "job_branch_time_shares",
            "den": "job_models"}),
        "job_zero_routed_share": ("job graphs", "%", 100, {
            "kind": "metric_ratio", "num": "job_zero_routed_shares",
            "den": "job_models"}),
        "lookahead_trips_per_op": ("device collection", "count", 1, {
            "kind": "metric_ratio", "num": "lookahead_lane_trips",
            "den": "lookahead_ops_decided"})}[metric]
    assert (entry["layer"], entry["unit"], entry["moves"]) \
        == (spec["layer"], spec["unit"], spec["moves"]) \
        == (layer, unit, "train_env_steps_per_s")
    assert (spec["scale"], spec["source"]) == (scale, source)
    if metric.startswith("job_"):
        assert "A DESCRIPTOR of the queue, not a lever" in spec["what"]
    kinds = {f[:-3] for f in os.listdir(os.path.join(harness.BENCH_DIR,
                                                     "sources"))}
    for part in (source["num"], source["den"]):
        reader = harness.read_json(os.path.join(
            harness.BENCH_DIR, "layer_metrics", part + ".json"))["source"]
        assert reader["kind"] == "telemetry_counter" in kinds
        assert bool(reader.get("per_epoch")) == metric.startswith("job_")
        if part in PARTS:
            assert reader["counter"] == PARTS[part]
            assert part not in {m["name"] for m in BENCH["per_layer"]}
    # everything this PR put under benchmarks/ is data
    added = [f"layer_metrics/{n}.json" for n in (*NEW_METRICS, *PARTS)] \
        + [f"configs/{CONFIG}.json", "traffic/train_fused_longcat.json"]
    for path in added:
        assert os.path.exists(os.path.join(harness.BENCH_DIR, path)), path


def test_this_pr_appended_and_old_cells_report_what_they_reported(
        bench=None):
    """Taking the new cell away gives the parent's benchmark entry for
    entry: its configurations, cells and metrics are a prefix of
    today's, in their order, with bounds and `run_seconds` untouched;
    the new cell joined every list that names sala's, behind it, but the
    two `_ragged` shares. A later PR's entries may follow: nothing here
    is pinned as the last."""
    bench = bench or BENCH
    parent = parent_of(bench)
    for key in ("command", "paths", "run_seconds"):
        assert parent[key] == bench[key]
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        assert [e["name"] for e in bench[kind]][:len(parent[kind])] \
            == [e["name"] for e in parent[kind]], kind
    assert [w["name"] for w in parent["workloads"]] == list(OLD_CELLS)
    assert [w["name"] for w in bench["workloads"]][:8] == [*OLD_CELLS, CELL]
    assert [c["name"] for c in bench["configs"]][7] == CONFIG
    assert [m["name"] for m in bench["per_layer"]][
        len(parent["per_layer"]):][:3] == list(NEW_METRICS)
    joined = 0
    for kind in ("end_to_end", "per_layer"):
        for old in parent[kind]:
            new = _entry(kind, old["name"], bench)
            assert {k: v for k, v in new.items() if k != "workloads"} \
                == {k: v for k, v in old.items() if k != "workloads"}
            if "workloads" not in old:
                assert "workloads" not in new
                continue
            cells = new["workloads"]
            assert cells[:len(old["workloads"])] == old["workloads"]
            if PARENT_LAST in old["workloads"] \
                    and old["name"] not in NOT_JOINED:
                assert cells[len(old["workloads"])] == CELL
                joined += 1
            else:
                assert CELL not in cells
    assert joined == 1 + 46      # train_env_steps_per_s and 46 per-layer
    for entry in (*parent["configs"], *parent["workloads"]):
        kind = "configs" if "file" in entry else "workloads"
        assert _entry(kind, entry["name"], bench) == entry
    # so every old cell reports what it reported, and then what later
    # PRs listed for it
    for cell in OLD_CELLS:
        names = [m["name"] for m in bench["per_layer"]
                 if cell in m.get("workloads", [cell])]
        reported = [m["name"] for m in parent["per_layer"]
                    if cell in m["workloads"]]
        assert names[:len(reported)] == reported
        assert not set(NEW_METRICS) & set(names)


# --------------------------------- room for the next cell, behind THIS
NEXT = "next_ramp32.train_fused"


def appended_behind_this(bench: dict) -> dict:
    """``bench`` as the NEXT `model_config` PR would leave it
    (`test_bench_room.appended`, one cell on): a configuration, a cell
    standing on this cell's files, its name behind this cell's on every
    list that has it, and one per-layer metric for it alone."""
    out = copy.deepcopy(bench)
    out["configs"].append(dict(_entry("configs", CONFIG, out),
                               name="next_share_ramp32"))
    out["workloads"].append(dict(_entry("workloads", CELL, out), name=NEXT,
                                 config="next_share_ramp32"))
    for metric in out["end_to_end"] + out["per_layer"]:
        if CELL in metric.get("workloads", ()):
            metric["workloads"].append(NEXT)
    out["per_layer"].append({
        "name": "next_cells_own_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "device collection",
        "moves": "train_env_steps_per_s", "workloads": [NEXT]})
    return out


@pytest.fixture()
def benchmark_with_a_cell_behind_this(monkeypatch):
    """`test_bench_room`'s fixture with the next cell behind THIS one:
    every module's copy of the benchmark, and the one `load_cell` reads
    from the repo."""
    import test_bench_epoch_anatomy
    import test_bench_glm5
    import test_bench_mimo
    import test_bench_narrow
    import test_bench_narrowest
    import test_bench_sala
    import test_bench_trinity

    listed = os.path.join(harness.REPO, "BENCHMARK.json")
    read_json = harness.read_json
    monkeypatch.setattr(
        harness, "read_json",
        lambda path: appended_behind_this(read_json(path))
        if os.path.abspath(path) == listed else read_json(path))
    later = appended_behind_this(BENCH)
    for module in (test_bench_glm5, test_bench_mimo, test_bench_trinity,
                   test_bench_sala, test_bench_narrow,
                   test_bench_narrowest, test_bench_epoch_anatomy):
        if hasattr(module, "BENCH"):
            monkeypatch.setattr(module, "BENCH", later)
    monkeypatch.setattr(test_bench_sala, "PARENT",
                        test_bench_sala.parent_of(later))
    return later


def test_the_cell_behind_this_resolves_and_reports_what_this_does(
        benchmark_with_a_cell_behind_this):
    cell = harness.load_cell(NEXT)
    names = [m["name"] for m in cell.per_layer]
    assert names[:-1] == [m["name"]
                          for m in harness.load_cell(CELL).per_layer]
    assert names[-1] == "next_cells_own_share"
    assert [w["name"] for w in benchmark_with_a_cell_behind_this[
        "workloads"]][-3:] == [PARENT_LAST, CELL, NEXT]
    # and this PR's own pins hold on it
    test_this_pr_appended_and_old_cells_report_what_they_reported(
        benchmark_with_a_cell_behind_this)
    test_cell_reports_every_metric_salas_does_but_the_ragged_two()


#: `test_bench_room`'s pins of the older PRs that run with no shim of
#: ``tests/conftest.py`` when driven from another module, on the
#: benchmark with THIS cell in it and the next one behind
ROOM_PINS = [pin for pin in test_bench_room.PINS
             if pin[2] in ((), (PARENT_LAST,), ("sala_ramp32",))]


@pytest.mark.parametrize(
    "module, name, args", ROOM_PINS,
    ids=[f"{m.__name__[len('test_bench_'):]}.{n[len('test_'):][:48]}"
         for m, n, a in ROOM_PINS])
def test_an_older_prs_pins_hold_with_a_cell_behind_this(
        benchmark_with_a_cell_behind_this, module, name, args):
    getattr(module, name)(*args)


def test_composed_tree_is_what_the_configuration_file_expects(tmp_path):
    """``compose`` checks ``expect``; beyond it, the kernel pads the
    file describes are what the tables of that tree are built to: the
    sixth pad class, between mimo's and glm5's."""
    cell = harness.load_cell(CELL)
    cfg = train.compose(cell, 0, str(tmp_path))
    jobs = cfg["env_config"]["jobs_config"]
    assert "synthetic" not in jobs and jobs["path_to_files"] is None
    assert jobs["architecture"]["config"] == ARCH_FILE
    assert jobs["architecture"]["layers"] == {"leading_dense": 0,
                                              "following": 4}
    assert jobs["architecture"]["experts_held"] == 128
    assert [(s["seq_len"], s["micro_batch"])
            for s in jobs["architecture"]["shapes"]] \
        == [(8192, 1), (8192, 4), (32768, 1), (131072, 1)]
    assert cfg["epoch_loop"]["loop_mode"] == "fused"
    assert jobs["job_interarrival_time_dist"]["val"] == 21
    assert cfg["env_config"]["max_simulation_run_time"] == 8400
    pads = cell.config["pads"]
    assert (pads["max_nodes"], pads["max_edges"]) == (200, 512)
    # 174 original ops x 16; (269 edges + 87 backward cliques) x 16^2
    assert pads["kernel_ops"] == 174 * 16 == 2784
    assert pads["kernel_blocks"] == 269 + 87 == 356
    assert pads["kernel_deps"] == pads["kernel_blocks"] * 16 ** 2 == 91136
    assert pads["kernel_fwd_ops"] == 87
    slots = {name: harness.load_cell(name + "_ramp32.train_fused").config[
        "pads"]["kernel_deps"] for name in ("mimo", "glm5")}
    assert slots["mimo"] < pads["kernel_deps"] < slots["glm5"]
    # a lane's 128-key memo, as the traffic file states it
    memo = 128 * (pads["kernel_ops"] + pads["kernel_deps"]) * 4
    assert memo == 48_087_040 and round(memo / 1e6, 1) == 48.1
    assert "48.1 MB" in cell.traffic["what"]
    from ddls_tpu.ops.segment import DENSE_MAX_CELLS

    assert pads["max_nodes"] * pads["max_edges"] == 102400 \
        <= DENSE_MAX_CELLS


# ------------------------------------------------ the tiny preset, run
TINY_ARCH = {"hidden_size": 64, "num_attention_heads": 4,
             "q_lora_rank": 32, "kv_lora_rank": 16,
             "qk_nope_head_dim": 12, "qk_rope_head_dim": 4,
             "v_head_dim": 16, "mla_scale_q_lora": True,
             "mla_scale_kv_lora": True, "ffn_hidden_size": 128,
             "expert_ffn_hidden_size": 32, "n_routed_experts": 8,
             "zero_expert_num": 4, "zero_expert_type": "identity",
             "moe_topk": 3, "routed_scaling_factor": 6, "num_layers": 2,
             "vocab_size": 256}
TINY_MODELING = {"model_type": "tinylongcat", "shortcut_sub_blocks": 2,
                 "e_score_correction_bias": True}


def test_tiny_longcat_preset_runs_the_training_path_traced(
        tiny_tree, capsys, tmp_path):
    arch_file = tmp_path / "tinylongcat.json"
    arch_file.write_text(json.dumps({
        "source_url": "test-local", "config": TINY_ARCH,
        "modeling": TINY_MODELING,
        "training_state": {"resident_bytes_per_parameter": 16,
                           "synced_bytes_per_parameter": 2}}))
    config = bench_tiny.tiny_config("tiny_longcat", overrides=[
        "env_config=env_longcat_32", *bench_tiny.TINY_OVERRIDES[1:],
        f"env_config.jobs_config.architecture.config={arch_file}",
        "env_config.jobs_config.architecture.layers="
        "{leading_dense: 0, following: 2}",
        "env_config.jobs_config.architecture.experts_held=4",
        "env_config.jobs_config.architecture.shapes="
        "[{seq_len: 32, micro_batch: 4096}, {seq_len: 32, micro_batch: 131072}]",
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
    mix = dict(bench_tiny.tiny_traffic()["tiny_fused"], name="tiny_longcat")
    _add_cell(tiny_tree, "tiny.longcat", config, mix)

    result, notes = _result(capsys, _argv("tiny.longcat", 1))
    _check_line(result, traced=True)
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW_METRICS) | set(PARTS) <= set(metrics), sorted(metrics)
    startup_line, = [n for n in notes if n.startswith("[startup] ")]
    gauges = json.loads(startup_line[len("[startup] "):])
    models = ("tinylongcat_s32_b4096", "tinylongcat_s32_b131072")
    for model in models:
        assert gauges[f"graphs.arch.forward_ops.{model}"] == 45
        assert gauges[f"graphs.arch.edges.{model}"] == 137
        assert gauges[f"graphs.arch.layers_latent.{model}"] == 4
        assert gauges[f"graphs.arch.shortcut_branches.{model}"] == 2
        assert gauges[f"graphs.arch.zero_experts.{model}"] == 4
        assert gauges[f"graphs.arch.zero_routed_share.{model}"] == 4 / 12
        assert 0.05 < gauges[f"graphs.arch.branch_time_share.{model}"] < 0.5
    # the descriptors are the bank's means of the gauges: the count of
    # drained traces cancels
    assert metrics["job_models"] == 2.0
    assert metrics["job_zero_routed_share"] == pytest.approx(100 / 3)
    assert metrics["job_branch_time_share"] == pytest.approx(100 * sum(
        gauges[f"graphs.arch.branch_time_share.{m}"] for m in models) / 2)
    # every decided job has 90 ops (45 forward, mirrored): the counter
    # is that times the decisions whose lookahead ran
    assert metrics["lookahead_ops_decided"] % 90 == 0
    assert 0 < metrics["lookahead_ops_decided"] <= 90 * 16
    assert metrics["lookahead_trips_per_op"] == pytest.approx(
        metrics["lookahead_lane_trips"] / metrics["lookahead_ops_decided"])
    assert 1.0 < metrics["lookahead_trips_per_op"] < 60.0
    assert metrics["obs_node_fill"] == pytest.approx(90.0)
    assert metrics["compiles_in_window"] == 0.0
    assert any(n.startswith("[bench] fidelity") and '"ok": true' in n
               for n in notes)


def test_synthetic_jobs_read_trips_per_op_and_no_arch_descriptor(
        tiny_tree, capsys):
    """The old cells' kind of run (synthetic chains): the new counter is
    counted in every fused run, so `lookahead_trips_per_op` is read
    there too; no architecture built the jobs, so the two descriptors
    find nothing and are left out of the line."""
    from ddls_tpu.telemetry import startup

    startup.registry().reset()
    result, notes = _result(capsys, _argv("tiny.fused", 1))
    _check_line(result, traced=True)
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert metrics["lookahead_trips_per_op"] == pytest.approx(
        metrics["lookahead_lane_trips"] / metrics["lookahead_ops_decided"])
    assert not {"job_branch_time_share", "job_zero_routed_share"} \
        & set(metrics)
