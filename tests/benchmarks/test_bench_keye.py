"""The cell ``keye_ramp32.train_fused``: its files resolve and agree with
the composed tree and with the architecture file, ``reduced`` is the
depth (as ``num_layers``) and the batch, it lists the two per-layer metrics this PR adds
beside everything ``longcat_ramp32.train_fused`` reports, the older
cells report what they reported, what this PR added to
``BENCHMARK.json`` was appended behind what was there — and leaves room
for the next: the pins of every older PR (``test_bench_room.PINS``),
``test_bench_longcat``'s own and this module's hold with a cell appended
behind the LAST cell, found by POSITION, so the next `model_config` PR
needs no shim of ``tests/conftest.py`` for this module — and a tiny
STATED preset of the same job source (2 layers, hidden 64, 4 q / 2 kv
heads, an indexer whose top-16 is under the 32-token sequence, 8
experts) runs the training path end to end on the CPU with the new
metrics and gauges in its traced line and `[startup]` line."""
import copy
import json
import os

import pytest

import bench_tiny
import test_bench_longcat
import test_bench_room
from bench_history import benchmark_as_of
from benchmarks import harness
from benchmarks.paths import train
from test_bench_mimo import _add_cell
from test_bench_run import (_argv, _check_line, _result,  # noqa: F401
                            restore_process_state, tiny_tree)

REPO = bench_tiny.REPO
CELL = "keye_ramp32.train_fused"
PARENT_LAST = "longcat_ramp32.train_fused"
OLD_CELLS = (*test_bench_longcat.OLD_CELLS, PARENT_LAST)
CONFIG = "keye_vl2_30b_a3b_stage_ramp32"
NEW_METRICS = ("job_index_time_share", "job_attended_keys_share")
#: the new metrics' new parts: read by the ratios, listed for no cell
PARTS = {"job_index_time_shares": "graphs.arch.index_time_shares",
         "job_attended_keys_shares": "graphs.arch.attended_keys_shares"}
ARCH_FILE = "ddls_tpu/graphs/arch_configs/keye_vl_2_30b_a3b.json"
BENCH = json.load(open(os.path.join(REPO, "BENCHMARK.json")))


def parent_of(bench: dict) -> dict:
    """The parent's benchmark: the later cells taken away, and the
    metrics that stand behind this PR's own two (PRs only append)."""
    parent = benchmark_as_of(bench, PARENT_LAST)
    parent["per_layer"] = parent["per_layer"][
        :[m["name"] for m in bench["per_layer"]].index(NEW_METRICS[0])]
    return parent


PARENT = parent_of(BENCH)


def _entry(kind, name, bench=None):
    """BENCHMARK.json's entry of that name, wherever it stands."""
    entry, = [e for e in (bench or BENCH)[kind] if e["name"] == name]
    return entry


def test_cell_is_lanes_of_the_keye_queue():
    cell = harness.load_cell(CELL)
    assert (cell.chips, cell.config_name, cell.traffic_name, cell.path) \
        == (1, CONFIG, "train_fused_keye", "train")
    mix = cell.traffic
    lanes = mix["epoch"]["lanes"]
    assert lanes in (24, 16)         # the issue's two packed-form sizes
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
    assert "my chip runs, PR 47" in mix["why_measure_epochs"]
    assert "my chip runs, PR 52" in mix["why_measure_epochs"]
    assert "program_spans" not in mix
    # but for the lanes, the words and the measured set of epochs, the
    # mix is sala's (the other cell at these pads)
    other = harness.load_cell("sala_ramp32.train_fused").traffic
    same = set(mix) - {"name", "what", "why_this_shape", "fidelity",
                       "overrides", "epoch", "measure_epochs",
                       "why_measure_epochs"}
    assert {k: mix[k] for k in same} == {k: other[k] for k in same}
    assert cell.config["composed_from"]["overrides"] == [
        "env_config=env_keye_32"]
    assert cell.config["train_batch_size"] == lanes
    assert f"{lanes} lanes x 1 step" in cell.config["reduced"][
        "train_batch_size"]
    assert {m["name"] for m in cell.end_to_end} == {
        "train_env_steps_per_s", "setup_s"}
    entry = _entry("workloads", CELL)
    assert entry["chips"] == 1 and len(entry["why"]) <= 200
    assert f"{lanes} lanes x 1 step" in entry["why"]


def test_published_is_the_architecture_file_and_two_keys_are_reduced():
    """The widths are pinned twice: the architecture file the program
    reads and the ``published`` block (the catalog row's keys) are the
    same numbers; the ONE cut stands beside them as ``num_layers``, with
    its arithmetic and the measured reason in ``reduced``; no width is
    among them. What the row does not give
    is under ``assumed`` — there is no modeling block."""
    arch = json.load(open(os.path.join(REPO, ARCH_FILE)))
    config = harness.load_cell(CELL).config
    assert arch["source_url"] == config["source"]
    assert arch["training_state"] == {"resident_bytes_per_parameter": 16,
                                      "synced_bytes_per_parameter": 2}
    assert "modeling" not in arch
    published = dict(config["published"])
    assert published.pop("train_batch_size") == 4000
    assert published == arch["config"]
    # every catalog key keeps its published value at the top level; the
    # depth cut is `num_layers` (glm5's and mimo's precedent: the
    # accepted `test_bench_spec.py` refuses a reduced key with "hidden")
    assert {k: config[k] for k, v in arch["config"].items()
            if config[k] != v} == {}
    assert (config["num_hidden_layers"], config["num_layers"]) == (48, 24)
    assert "num_layers" not in arch["config"]
    entry = _entry("configs", CONFIG)
    assert set(entry["reduced"]) == set(config["reduced"]) == {
        "num_layers", "train_batch_size"}
    for key in entry["reduced"]:    # no width: the contract's words
        assert not key.endswith(("_dim", "_rank")) and "hidden_size" != key
    assert entry["source"] == arch["source_url"]
    assert entry["file"] == f"benchmarks/configs/{CONFIG}.json"
    assert len(entry["why"]) <= 200
    for words, said in (("num_layers", (
            "30,640,641,024", "490.3 GB", "15,631,486,464", "250.1 GB",
            "RUN'S TIME LIMIT", "483 forward ops", "of the 360 s",
            "following: 24", "num_hidden_layers 48 -> 24 HELD")),
            ("train_batch_size", ("130.9 MB",))):
        for phrase in said:
            assert phrase in config["reduced"][words], (words, phrase)
    for field in ("deployment", "assumed", "reduced", "published",
                  "guarantees"):
        assert config[field], field
    assumed = config["assumed"]
    assert {"layout", "tower", "indexer", "chunk_sizes", "qk_norm",
            "position_streams", "sliding_window", "op_graph", "routing",
            "costs", "ragged_rows", "sequence_lengths", "arrivals",
            "all_to_all"} <= set(assumed)
    assert "LEFT OUT" in assumed["tower"] and "LEFT OUT" in arch["what"]
    assert "WHOLE 64" in assumed["indexer"]
    assert "NOT counted" in assumed["qk_norm"]
    assert "balanced" in assumed["routing"]
    assert "no FLOP and no once-read byte" in assumed["chunk_sizes"]


def test_catalog_numbers_sit_at_the_top_level_under_the_same_keys():
    """What the driver compares: every number of the catalog row's
    ``config`` at the file's top level under the same key, equal, every
    one (the cut is ``num_layers``, no key of the row); nested groups
    copied whole."""
    arch = json.load(open(os.path.join(REPO, ARCH_FILE)))["config"]
    config = harness.load_cell(CELL).config
    for key, value in arch.items():
        assert config[key] == value, key
    assert (config["hidden_size"], config["intermediate_size"],
            config["moe_intermediate_size"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["num_experts"], config["num_local_experts"],
            config["num_experts_per_tok"], config["vocab_size"]) \
        == (2048, 6144, 768, 32, 4, 128, 128, 128, 8, 151936)
    assert config["sa_config"] == arch["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
        "q_chunk_size": 512, "topk": 2048}
    assert config["rope_scaling"]["mrope_section"] == [16, 24, 24]
    # the fourth family of key names: nothing of GLM-5's flat ones
    assert not {"index_topk", "index_n_heads", "kv_lora_rank",
                "n_routed_experts", "first_k_dense_replace",
                "experts_held"} & set(config)


def test_cell_reports_every_metric_longcats_does_and_the_two_new():
    """Longcat's 58 of the parent's benchmark lead (the two `_ragged`
    shares are not among them: this queue mounts no ragged row either),
    the two new follow, and whatever a later PR lists comes behind:
    nothing is pinned as the last."""
    names = [m["name"] for m in harness.load_cell(CELL).per_layer]
    longcat = [m["name"] for m in PARENT["per_layer"]
               if PARENT_LAST in m["workloads"]]
    assert len(longcat) == 58
    assert not set(test_bench_longcat.NOT_JOINED) & set(names)
    assert names[:60] == longcat + list(NEW_METRICS)
    assert len(set(names)) == len(names)


@pytest.mark.parametrize("metric", [
    *NEW_METRICS, "job_quadratic_time_share", "job_branch_time_share",
    "job_zero_routed_share", "lookahead_trips_per_op",
    "decision_blocked_placement_share", "decision_accept_share_longest",
    "epoch_device_wait_p50_s", "epoch_host_p50_ms", "compile_s",
    "compiles_in_window", "memo_hit_rate", "lookahead_lockstep_efficiency",
    "lookahead_block_fill_decided", "obs_node_fill", "lookahead_device_s",
    "placement_device_s", "decision_accept_share",
    "cluster_occupied_share", "mask_placeable_share", "peak_hbm_bytes",
    "program_scratch_bytes", "lookahead_narrow_trip_share",
    "lookahead_narrowest_trip_share", "warm_epoch_rate_p50",
    "warm_set_env_steps_per_s", "long_epochs_in_window",
    "decision_glue_device_s", "lookahead_stage_device_s",
    "update_grad_device_s", "setup_job_graphs_s", "setup_device_tables_s"])
def test_cell_reports_the_metric(metric):
    assert metric in {m["name"] for m in harness.load_cell(CELL).per_layer}


@pytest.mark.parametrize("metric", NEW_METRICS)
def test_new_metrics_are_data_of_reader_kinds_that_exist(metric):
    """Ratios of telemetry counters over `job_models`, like
    `job_quadratic_time_share`: no benchmark code is added and no new
    reader. They are in BENCHMARK.json for the new cell alone; their new
    parts are listed for no cell; each `what` says that it describes the
    queue."""
    spec = harness.read_json(os.path.join(
        harness.BENCH_DIR, "layer_metrics", metric + ".json"))
    entry = _entry("per_layer", metric)
    assert entry["workloads"][0] == CELL
    assert set(entry) == {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert entry["source"] == "program_counter"
    assert entry["better"] == _entry(
        "per_layer", "job_quadratic_time_share")["better"] == "lower"
    assert (entry["layer"], entry["unit"], entry["moves"]) \
        == (spec["layer"], spec["unit"], spec["moves"]) \
        == ("job graphs", "%", "train_env_steps_per_s")
    assert spec["scale"] == 100
    assert spec["source"] == {"kind": "metric_ratio", "num": metric + "s",
                              "den": "job_models"}
    assert "A DESCRIPTOR of the queue, not a lever" in spec["what"]
    kinds = {f[:-3] for f in os.listdir(os.path.join(harness.BENCH_DIR,
                                                     "sources"))}
    for part in (metric + "s", "job_models"):
        reader = harness.read_json(os.path.join(
            harness.BENCH_DIR, "layer_metrics", part + ".json"))["source"]
        assert reader["kind"] == "telemetry_counter" in kinds
        assert reader["per_epoch"] is True
        if part in PARTS:
            assert reader["counter"] == PARTS[part]
            assert part not in {m["name"] for m in BENCH["per_layer"]}
    # the counters are the program's: `BANK_GAUGES`, counted once a
    # drained epoch
    from ddls_tpu.demands.jobs_generator import BANK_GAUGES

    assert set(PARTS.values()) <= set(BANK_GAUGES)
    # everything this PR put under benchmarks/ is data
    added = [f"layer_metrics/{n}.json" for n in (*NEW_METRICS, *PARTS)] \
        + [f"configs/{CONFIG}.json", "traffic/train_fused_keye.json"]
    for path in added:
        assert os.path.exists(os.path.join(harness.BENCH_DIR, path)), path


def test_this_pr_appended_and_old_cells_report_what_they_reported(
        bench=None):
    """Taking the new cell away gives the parent's benchmark entry for
    entry: its configurations, cells and metrics are a prefix of
    today's, in their order, with bounds and `run_seconds` untouched;
    the new cell joined every list that names longcat's, behind it. A
    later PR's entries may follow: nothing here is pinned as the
    last."""
    bench = bench or BENCH
    parent = parent_of(bench)
    for key in ("command", "paths", "run_seconds"):
        assert parent[key] == bench[key]
    for kind in ("configs", "workloads", "end_to_end", "per_layer"):
        assert [e["name"] for e in bench[kind]][:len(parent[kind])] \
            == [e["name"] for e in parent[kind]], kind
    assert [w["name"] for w in parent["workloads"]] == list(OLD_CELLS)
    assert [w["name"] for w in bench["workloads"]][:9] == [*OLD_CELLS, CELL]
    assert [c["name"] for c in bench["configs"]][8] == CONFIG
    assert [m["name"] for m in bench["per_layer"]][
        len(parent["per_layer"]):][:2] == list(NEW_METRICS)
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
            if PARENT_LAST in old["workloads"]:
                assert cells[len(old["workloads"])] == CELL
                joined += 1
            else:
                assert CELL not in cells
    assert joined == 1 + 58      # train_env_steps_per_s and 58 per-layer
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


# ------------------- room for the next cell, behind the LAST by position
NEXT = test_bench_room.NEXT


def appended_behind_the_last(bench: dict) -> dict:
    """``bench`` as the NEXT `model_config` PR would leave it
    (`test_bench_room.appended`, found by POSITION): a configuration, a
    cell standing on the last cell's files, its name behind the last
    cell's on every list that has it, and one per-layer metric for it
    alone."""
    out = copy.deepcopy(bench)
    last = out["workloads"][-1]
    out["configs"].append(dict(_entry("configs", last["config"], out),
                               name="next_stage_ramp32"))
    out["workloads"].append(dict(last, name=NEXT,
                                 config="next_stage_ramp32"))
    for metric in out["end_to_end"] + out["per_layer"]:
        if last["name"] in metric.get("workloads", ()):
            metric["workloads"].append(NEXT)
    out["per_layer"].append({
        "name": "next_cells_own_share", "unit": "%", "better": "higher",
        "source": "program_counter", "layer": "device collection",
        "moves": "train_env_steps_per_s", "workloads": [NEXT]})
    return out


@pytest.fixture()
def benchmark_with_a_cell_behind_the_last(monkeypatch):
    """`test_bench_room`'s fixture with the next cell behind the LAST
    one: every module's copy of the benchmark, and the one `load_cell`
    reads from the repo."""
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
        lambda path: appended_behind_the_last(read_json(path))
        if os.path.abspath(path) == listed else read_json(path))
    later = appended_behind_the_last(BENCH)
    for module in (test_bench_glm5, test_bench_mimo, test_bench_trinity,
                   test_bench_sala, test_bench_narrow,
                   test_bench_narrowest, test_bench_epoch_anatomy,
                   test_bench_longcat):
        if hasattr(module, "BENCH"):
            monkeypatch.setattr(module, "BENCH", later)
    monkeypatch.setattr(test_bench_sala, "PARENT",
                        test_bench_sala.parent_of(later))
    monkeypatch.setattr(test_bench_longcat, "PARENT",
                        test_bench_longcat.parent_of(later))
    return later


def test_the_cell_behind_the_last_resolves_and_reports_what_the_last_does(
        benchmark_with_a_cell_behind_the_last):
    later = benchmark_with_a_cell_behind_the_last
    last = BENCH["workloads"][-1]["name"]       # by position, no name
    cell = harness.load_cell(NEXT)
    names = [m["name"] for m in cell.per_layer]
    assert names[:-1] == [m["name"]
                          for m in harness.load_cell(last).per_layer]
    assert names[-1] == "next_cells_own_share"
    assert [w["name"] for w in later["workloads"]][-2:] == [last, NEXT]
    assert len(later["workloads"]) == len(BENCH["workloads"]) + 1
    # this PR's own pins hold on it, and PR 48's
    test_this_pr_appended_and_old_cells_report_what_they_reported(later)
    test_cell_reports_every_metric_longcats_does_and_the_two_new()
    test_bench_longcat \
        .test_this_pr_appended_and_old_cells_report_what_they_reported(
            later)
    test_bench_longcat \
        .test_cell_reports_every_metric_salas_does_but_the_ragged_two()


#: `test_bench_room`'s pins of the older PRs — all of them but the one
#: that names ITS appended cell as sala's successor — on the benchmark
#: AS IT IS with the next cell behind the last: driven from here no shim
#: of ``tests/conftest.py`` applies
ROOM_PINS = [pin for pin in test_bench_room.PINS
             if pin[2] != (test_bench_room.NEXT,)]
ROOM_PIN_IDS = [f"{m.__name__[len('test_bench_'):]}.{n[len('test_'):][:48]}"
                + ("." + a[0].split(".")[0] if a else "")
                for m, n, a in ROOM_PINS]


@pytest.mark.parametrize("module, name, args", ROOM_PINS, ids=ROOM_PIN_IDS)
def test_an_older_prs_pins_hold_with_a_cell_behind_the_last(
        benchmark_with_a_cell_behind_the_last, module, name, args):
    getattr(module, name)(*args)


@pytest.mark.parametrize("module, name, args", ROOM_PINS, ids=ROOM_PIN_IDS)
def test_the_same_pins_hold_on_the_benchmark_as_it_is(module, name, args):
    getattr(module, name)(*args)


def test_the_shim_hands_each_pinned_module_its_own_benchmark(request):
    """``tests/conftest.py``: ONE shim, a map from the modules that pin
    a cell as the LAST to that cell (the room test, longcat's, and PR
    50's scope-tree test, whose `== [cells[-1]]` no appended cell can
    meet); this module is not in it."""
    conftest, = [
        plugin for plugin in request.config.pluginmanager.get_plugins()
        if getattr(plugin, "__file__", None)
        == os.path.join(REPO, "tests", "conftest.py")]
    assert conftest.KNOWS_THE_BENCHMARK_AS_OF == {
        "test_bench_room": "sala_ramp32.train_fused",
        "test_bench_longcat": PARENT_LAST,
        "test_bench_scope_tree": PARENT_LAST}
    assert __name__ not in conftest.KNOWS_THE_BENCHMARK_AS_OF
    for module, last in conftest.KNOWS_THE_BENCHMARK_AS_OF.items():
        known = benchmark_as_of(BENCH, last)
        assert known["workloads"][-1]["name"] == last
        assert CELL not in [w["name"] for w in known["workloads"]]
        assert not set(NEW_METRICS) & {m["name"]
                                       for m in known["per_layer"]}


def test_composed_tree_is_what_the_configuration_file_expects(tmp_path):
    """``compose`` checks ``expect``; beyond it, the kernel pads the
    file describes are what the tables of that tree are built to: the
    seventh pad class, sala's to within 2 %."""
    cell = harness.load_cell(CELL)
    cfg = train.compose(cell, 0, str(tmp_path))
    jobs = cfg["env_config"]["jobs_config"]
    assert "synthetic" not in jobs and jobs["path_to_files"] is None
    assert jobs["architecture"]["config"] == ARCH_FILE
    assert jobs["architecture"]["layers"] == {"leading_dense": 0,
                                              "following": 24}
    assert "experts_held" not in jobs["architecture"]
    assert [(s["seq_len"], s["micro_batch"])
            for s in jobs["architecture"]["shapes"]] \
        == [(8192, 4), (32768, 1), (65536, 1), (131072, 1)]
    assert cfg["epoch_loop"]["loop_mode"] == "fused"
    assert jobs["job_interarrival_time_dist"]["val"] == 7.9
    assert cfg["env_config"]["max_simulation_run_time"] == 3160
    pads = cell.config["pads"]
    assert (pads["max_nodes"], pads["max_edges"]) == (500, 768)
    # 486 original ops x 16; (725 edges + 243 backward cliques) x 16^2
    assert pads["kernel_ops"] == 486 * 16 == 7776
    assert pads["kernel_blocks"] == 725 + 243 == 968
    assert pads["kernel_deps"] == pads["kernel_blocks"] * 16 ** 2 == 247808
    assert pads["kernel_fwd_ops"] == 243
    sala = harness.load_cell("sala_ramp32.train_fused").config["pads"]
    assert pads["kernel_ops"] == sala["kernel_ops"]
    assert 1.0 < pads["kernel_deps"] / sala["kernel_deps"] < 1.02
    assert (pads["max_nodes"], pads["max_edges"]) \
        == (sala["max_nodes"], sala["max_edges"])
    # a lane's 128-key memo, as the traffic file states it
    memo = 128 * (pads["kernel_ops"] + pads["kernel_deps"]) * 4
    assert memo == 130_859_008 and round(memo / 1e6, 1) == 130.9
    assert "130.9 MB" in cell.traffic["what"]


# ------------------------------------------------ the tiny preset, run
TINY_ARCH = {"model_type": "tinykeye", "hidden_size": 64,
             "num_attention_heads": 4, "num_key_value_heads": 2,
             "head_dim": 16, "intermediate_size": 128,
             "moe_intermediate_size": 32, "num_experts": 8,
             "num_local_experts": 8, "num_experts_per_tok": 2,
             "norm_topk_prob": True, "decoder_sparse_step": 1,
             "mlp_only_layers": [], "num_hidden_layers": 2,
             "rope_scaling": {"mrope_section": [2, 3, 3]},
             "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2,
                           "indexer_num_kv_heads": 1, "kv_chunk_size": 8,
                           "q_chunk_size": 8, "topk": 16},
             "sliding_window": None, "use_sliding_window": False,
             "max_window_layers": 2, "vocab_size": 256}


def test_tiny_keye_preset_runs_the_training_path_traced(
        tiny_tree, capsys, tmp_path):
    arch_file = tmp_path / "tinykeye.json"
    arch_file.write_text(json.dumps({
        "source_url": "test-local", "config": TINY_ARCH,
        "training_state": {"resident_bytes_per_parameter": 16,
                           "synced_bytes_per_parameter": 2}}))
    config = bench_tiny.tiny_config("tiny_keye", overrides=[
        "env_config=env_keye_32", *bench_tiny.TINY_OVERRIDES[1:],
        f"env_config.jobs_config.architecture.config={arch_file}",
        "env_config.jobs_config.architecture.layers="
        "{leading_dense: 0, following: 2}",
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
        "env_config.pad_obs_kwargs={max_nodes: 50, max_edges: 128}"])
    config["expect"] = {"env_config.min_op_run_time_quantum": 1e-5,
                        "env_config.max_partitions_per_op": 4}
    mix = dict(bench_tiny.tiny_traffic()["tiny_fused"], name="tiny_keye")
    _add_cell(tiny_tree, "tiny.keye", config, mix)

    result, notes = _result(capsys, _argv("tiny.keye", 1))
    _check_line(result, traced=True)
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert set(NEW_METRICS) | set(PARTS) <= set(metrics), sorted(metrics)
    startup_line, = [n for n in notes if n.startswith("[startup] ")]
    gauges = json.loads(startup_line[len("[startup] "):])
    models = ("tinykeye_s32_b4096", "tinykeye_s32_b131072")
    keys = (16 * 17 / 2 + 16 * 16) / (32 * 33 / 2)
    for model in models:
        assert gauges[f"graphs.arch.forward_ops.{model}"] == 23
        assert gauges[f"graphs.arch.edges.{model}"] == 65
        assert gauges[f"graphs.arch.layers_indexed.{model}"] == 2
        assert gauges[f"graphs.arch.layers_full.{model}"] == 0
        assert gauges[f"graphs.arch.position_streams.{model}"] == 3
        assert gauges[f"graphs.arch.attended_keys_share.{model}"] == keys
        assert 0.01 < gauges[f"graphs.arch.index_time_share.{model}"] < 0.5
        assert 0 < gauges[
            f"graphs.arch.sparse_core_time_share.{model}"] < 0.5
        assert gauges[f"graphs.arch.branch_time_share.{model}"] > 0
    # the descriptors are the bank's means of the gauges: the count of
    # drained traces cancels
    assert metrics["job_models"] == 2.0
    assert metrics["job_attended_keys_share"] == pytest.approx(100 * keys)
    assert metrics["job_index_time_share"] == pytest.approx(100 * sum(
        gauges[f"graphs.arch.index_time_share.{m}"] for m in models) / 2)
    assert metrics["job_zero_routed_share"] == 0.0
    assert metrics["compiles_in_window"] == 0.0
    assert any(n.startswith("[bench] fidelity") and '"ok": true' in n
               for n in notes)


def test_synthetic_jobs_read_no_indexer_descriptor(tiny_tree, capsys):
    """The old cells' kind of run (synthetic chains): no architecture
    built the jobs, so the two descriptors find nothing and are left out
    of the line — as on a parent program, which has no such gauge."""
    from ddls_tpu.telemetry import startup

    startup.registry().reset()
    result, notes = _result(capsys, _argv("tiny.fused", 1))
    _check_line(result, traced=True)
    assert not (set(NEW_METRICS) | set(PARTS)) & set(result["metrics"])
