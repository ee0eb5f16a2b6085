"""A tiny benchmark tree for the CPU rehearsals: the real harness files
beside a test-local BENCHMARK.json, two tiny configurations and three
tiny traffic mixes (``env_small``, 8-wide GNN, epochs of 16 steps).
Only sizes differ from the real cells; nothing here is a new option of
the harness."""
import json
import os
import shutil

REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

TINY_OVERRIDES = [
    "env_config=env_small",
    "algo.algo_config.num_sgd_iter=2",
    "algo.algo_config.sgd_minibatch_size=8",
    "algo.algo_config.train_batch_size=16",
    "model.custom_model_config.out_features_msg=4",
    "model.custom_model_config.out_features_hidden=8",
    "model.custom_model_config.out_features_node=4",
    "model.custom_model_config.out_features_graph=4"]
COMMON = ["eval_config.evaluation_interval=null",
          "epoch_loop.metrics_sync_interval=1"]
TINY_MODEL = {"in_features_node": 5, "in_features_edge": 2,
              "graph_features": 18, "out_features_msg": 4,
              "out_features_hidden": 8, "out_features_node": 4,
              "out_features_graph": 4, "num_rounds": 2,
              "aggregator_type": "mean", "fcnet_hiddens": [17]}


def tiny_config(name: str, checkpoint=None, overrides=()) -> dict:
    return {
        "name": name, "source": "test-local",
        "composed_from": {
            "config_path": "scripts/ramp_job_partitioning_configs",
            "config_name": "rllib_config",
            "overrides": list(overrides)},
        "pads": {"max_nodes": 150, "max_edges": 512, "n_actions": 9,
                 "max_partitions_per_op": 8},
        "model": TINY_MODEL,
        "ppo": {"num_sgd_iter": 2, "sgd_minibatch_size": 8},
        "checkpoint": checkpoint,
        "expect": {"env_config.max_partitions_per_op":
                   8 if "env_config=env_small" in overrides else 16}}


def tiny_traffic() -> dict:
    epoch = {"lanes": 8, "steps": 2, "env_steps": 16}
    return {
        "tiny_fused": {
            "name": "tiny_fused", "path": "train",
            "overrides": COMMON + [
                "epoch_loop.loop_mode=fused",
                "epoch_loop.updates_per_epoch=1",
                "epoch_loop.fused_config={lanes: 8, segment_len: 2}",
                "epoch_loop.num_envs=8", "epoch_loop.rollout_length=2"],
            "epoch": epoch, "warmup_epochs": 1, "train_seed": 0,
            "statistic": "window_share", "trace_epochs": 1,
            "fidelity": {"kind": "jitted_episode", "decisions": 6,
                         "rtol": 1e-4}},
        "tiny_host": {
            "name": "tiny_host", "path": "train",
            "overrides": COMMON + [
                "epoch_loop.loop_mode=pipelined",
                "epoch_loop.use_parallel_envs=false",
                "epoch_loop.num_envs=8", "epoch_loop.rollout_length=2"],
            "epoch": epoch, "warmup_epochs": 1,
            "statistic": "median_epoch_rate", "trace_epochs": 2,
            "fidelity": {"kind": "native_engine", "decisions": 6,
                         "rtol": 0.0}},
        "tiny_serve": {
            "name": "tiny_serve", "path": "serve", "rate_rps": 100.0,
            "arrivals": {"diurnal_amplitude": 0.0, "burst_factor": 1.0,
                         "size_tail_alpha": 1.5, "n_tenants": 4},
            "pool": {"observations": 12},
            "server": {"max_batch": 8, "deadline_s": 0.005,
                       "max_queue": 64},
            "percentile": 99.0, "subwindows": 2, "drain_timeout_s": 2.0,
            "trace_seconds": 1.0,
            "reference": {"sample": 4, "logit_atol": 1e-3}},
    }


def _with_x4(mixes: dict) -> dict:
    """The fused mix again under its own name, for the four-device
    cell (a pair of configuration and mix appears once)."""
    mixes["tiny_fused_x4"] = dict(mixes["tiny_fused"], name="tiny_fused_x4")
    return mixes


def build_tree(root: str) -> str:
    """Copy the benchmark's files into ``root`` and give it a tiny
    BENCHMARK.json of all three paths (the real one lists cells of the
    fused training path only: the driver's memory floor refuses the
    others, PERF.md); returns ``root``. Per-layer metrics and sources
    are the real ones."""
    bench = os.path.join(root, "benchmarks")
    shutil.copytree(os.path.join(REPO, "benchmarks"), bench,
                    ignore=shutil.ignore_patterns("out", "__pycache__"))
    real = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    configs = {
        "tiny_train": tiny_config("tiny_train", overrides=TINY_OVERRIDES),
        "tiny_serve": tiny_config(
            "tiny_serve", checkpoint="checkpoints/ppo_device_trained",
            overrides=["env_config=env_load32"])}
    for name, cfg in configs.items():
        with open(os.path.join(bench, "configs", name + ".json"),
                  "w") as fh:
            json.dump(cfg, fh)
    for name, mix in _with_x4(tiny_traffic()).items():
        with open(os.path.join(bench, "traffic", name + ".json"),
                  "w") as fh:
            json.dump(mix, fh)
    cells = [("tiny.fused", "tiny_train", "tiny_fused"),
             ("tiny.host", "tiny_train", "tiny_host"),
             ("tiny.serve", "tiny_serve", "tiny_serve"),
             ("tiny.fused_x4", "tiny_train", "tiny_fused_x4")]

    train = [c for c, _, t in cells if t != "tiny_serve"]
    end_to_end = [
        {"name": "train_env_steps_per_s", "unit": "env_steps/s",
         "better": "higher", "bound": 0.01, "source": "host_clock",
         "workloads": train},
        {"name": "serve_decisions_per_s", "unit": "decisions/s",
         "better": "higher", "bound": 0.01, "source": "host_clock",
         "workloads": ["tiny.serve"]},
        {"name": "serve_p99_ms", "unit": "ms", "better": "lower",
         "bound": 0.05, "source": "host_clock",
         "workloads": ["tiny.serve"]},
        {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
         "source": "host_clock"}]
    # every per-layer metric file the benchmark has, the paths the real
    # BENCHMARK.json lists no cell for among them: a cell reports the
    # ones that move a metric of its own and whose reader finds
    # something to read
    per_layer = []
    for name in sorted(os.listdir(os.path.join(bench, "layer_metrics"))):
        spec = json.load(open(os.path.join(bench, "layer_metrics", name)))
        per_layer.append({
            "name": spec["name"], "unit": spec["unit"], "better": "lower",
            "source": "host_clock", "layer": spec["layer"],
            "moves": spec["moves"]})

    tiny = dict(
        real, run_seconds=2,
        configs=[{"name": n, "source": "test-local",
                  "file": f"benchmarks/configs/{n}.json", "reduced": [],
                  "why": "tiny"} for n in configs],
        workloads=[{"name": c, "config": k, "traffic": t,
                    "chips": 4 if c.endswith("_x4") else 1,
                    "why": "tiny"} for c, k, t in cells],
        end_to_end=end_to_end, per_layer=per_layer)
    with open(os.path.join(root, "BENCHMARK.json"), "w") as fh:
        json.dump(tiny, fh)
    return root


def unlisted_cell(config: str, traffic: str):
    """A cell of the real configuration and mix files that the real
    BENCHMARK.json does not list, for the readers that take their
    shapes from a cell."""
    from benchmarks import harness

    bench = os.path.join(REPO, "benchmarks")
    return harness.Cell(
        name=f"{config}.{traffic}", chips=1, config_name=config,
        config=harness.read_json(
            os.path.join(bench, "configs", config + ".json")),
        traffic_name=traffic,
        traffic=harness.read_json(
            os.path.join(bench, "traffic", traffic + ".json")),
        end_to_end=[], per_layer=[], run_seconds=30)
