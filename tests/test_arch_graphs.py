"""Architecture config -> job graph (``ddls_tpu/graphs/arch.py``): the
builder's totals against a closed form written HERE from the config's
keys, the profile through the normal reader, and a tiny preset stepped
in-kernel against the host oracle (x64 to 1e-9 in a subprocess, f32 at
rtol 1e-4 in-process, at the full configuration's time scales)."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from ddls_tpu.graphs import arch
from ddls_tpu.graphs.readers import _parse_pipedream_txt, read_graph_file

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OLMOE_FILE = "ddls_tpu/graphs/arch_configs/olmoe_1b_7b_0125.json"

#: 2 layers, hidden 64, 4 experts, 2 per token, sequence 32
TINY = {"model_type": "tinymoe", "hidden_size": 64,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "intermediate_size": 32, "num_experts": 4,
        "num_experts_per_tok": 2, "num_hidden_layers": 2,
        "vocab_size": 256}
#: micro-batches that put the tiny preset's op times where the full
#: configuration's are: 4,096 sequences -> ops of 9-42 us (splits of 2,
#: 4 and 6 under the 10 us quantum: a ragged row), 2**21 -> ops of
#: 2-21 ms and steps of 0.74 s
TINY_SHAPES = [{"seq_len": 32, "micro_batch": 4096},
               {"seq_len": 32, "micro_batch": 2 ** 21}]
QUANTUM = 10e-6


@pytest.fixture(scope="module")
def olmoe():
    return arch.load_arch_config(OLMOE_FILE)


# ------------------------------------------------- (a) the closed form
def _closed_form(cfg, seq_len):
    """Parameters, active parameters a token and forward FLOPs a token
    of a decoder with q/k-normed MHA and routed SwiGLU experts, from the
    config's keys alone."""
    H, L, V = (cfg["hidden_size"], cfg["num_hidden_layers"],
               cfg["vocab_size"])
    heads = cfg["num_attention_heads"]
    d = H // heads
    q, kv = heads * d, cfg["num_key_value_heads"] * d
    E, k, I = (cfg["num_experts"], cfg["num_experts_per_tok"],
               cfg["intermediate_size"])
    attn = H * (q + 2 * kv) + (q + kv) + q * H        # qkv, q/k norms, out
    expert = 3 * H * I
    layer_total = 2 * H + attn + H * E + E * expert   # two norms, router
    layer_active = 2 * H + attn + H * E + k * expert
    total = V * H + L * layer_total + H + H * V
    active = V * H + L * layer_active + H + H * V
    # 2 a multiply-accumulate of every active matrix (the embedding is a
    # lookup; norms' weights are elementwise), the causal half of the
    # S x S scores twice (QK^T, PV) ...
    matmul = 2 * (L * (H * (q + 2 * kv) + q * H + H * E + k * expert)
                  + H * V)
    scores = L * heads * seq_len * 2 * d
    # ... and arch.py's elementwise terms: norms 4, q/k norm + RoPE 7,
    # softmax 5 a score / logit, residual adds 1, silu*up 4, combine 2
    elementwise = (L * (2 * 4 * H + 7 * (q + kv)
                        + 2.5 * heads * seq_len + 2 * H + 5 * E
                        + 4 * k * I + 2 * k * H)
                   + 4 * H + 5 * V)
    return total, active, matmul + scores + elementwise


@pytest.mark.parametrize("quantity", ["parameters", "active_parameters",
                                      "forward_flops_per_token"])
def test_totals_equal_the_closed_form(olmoe, tmp_path, quantity):
    seq_len, micro_batch = 4096, 2
    total, active, flops = _closed_form(olmoe, seq_len)
    if quantity == "parameters":
        path, = arch.write_profiles(
            str(tmp_path), olmoe,
            [{"seq_len": seq_len, "micro_batch": micro_batch}])
        nodes, _ = _parse_pipedream_txt(path)
        stated = sum(n["parameter"] for n in nodes.values())
        assert stated == total * arch.PARAM_BYTES
        assert total == pytest.approx(6.92e9, rel=2e-3)
    elif quantity == "active_parameters":
        assert active == pytest.approx(1.28e9, rel=5e-3)
        # what the experts a token does NOT visit hold
        idle = (olmoe["num_experts"] - olmoe["num_experts_per_tok"]) \
            * 3 * olmoe["hidden_size"] * olmoe["intermediate_size"] \
            * olmoe["num_hidden_layers"]
        costs = arch.op_costs(olmoe, seq_len, micro_batch)
        assert sum(c["params"] for c in costs) - idle == active
    else:
        costs = arch.op_costs(olmoe, seq_len, micro_batch)
        per_token = sum(c["flops"] for c in costs) / (seq_len * micro_batch)
        assert per_token == pytest.approx(flops, rel=1e-12)
        assert flops == pytest.approx(2.63e9, rel=2e-3)


def test_full_size_graph_has_the_published_shape(olmoe, tmp_path):
    assert (olmoe["hidden_size"], olmoe["num_attention_heads"],
            olmoe["num_experts"], olmoe["intermediate_size"],
            olmoe["num_experts_per_tok"], olmoe["vocab_size"],
            olmoe["num_hidden_layers"], olmoe["max_position_embeddings"]
            ) == (2048, 16, 64, 1024, 8, 50304, 16, 4096)
    path, = arch.write_profiles(str(tmp_path), olmoe,
                                [{"seq_len": 4096, "micro_batch": 1}])
    assert os.path.basename(path) == "olmoe_s4096_b1.txt"
    graph = read_graph_file(path)
    assert graph.meta["model"] == "olmoe_s4096_b1"
    assert (len(graph.forward_op_ids()), graph.n_ops, graph.n_deps) \
        == (131, 262, 389)
    nodes, edges = _parse_pipedream_txt(path)
    kinds = [n["op_type"] for n in nodes.values()]
    assert kinds[0] == "Embedding" and kinds[-2:] == ["FinalNorm",
                                                      "LMHeadLoss"]
    assert kinds[1:-2] == list(arch.LAYER_OPS) * 16
    assert len(edges) == 130 + 4 * 16
    # a 17 us norm is written as 17 us, not rounded to 0.000017
    norm = nodes["2"]["forward"]
    assert norm == arch.forward_time(arch.op_costs(olmoe, 4096, 1)[1])
    assert 16e-6 < norm < 17e-6 and nodes["2"]["backward"] == 2 * norm
    # experts: 64 x 3 x 2048 x 1024 weights, T x 8 x 2048 outputs
    experts = nodes["8"]
    assert experts["parameter"] == 64 * 3 * 2048 * 1024 * arch.PARAM_BYTES
    assert experts["activation"] == 4096 * 8 * 2048 * arch.ACT_BYTES


def test_parameter_size_is_the_training_state_of_a_parameter(olmoe, tmp_path):
    """16 B a parameter, as the cell was specified (bf16 weight and
    gradient, fp32 master, two Adam moments), priced on the simulator's
    own worker: a job's state is 110.7 GB and fits no single A100."""
    from ddls_tpu.hardware.devices import A100
    from ddls_tpu.sim import comm_model

    assert arch.PARAM_BYTES == 2 + 2 + 4 + 4 + 4
    path, = arch.write_profiles(str(tmp_path), olmoe,
                                [{"seq_len": 4096, "micro_batch": 1}])
    nodes, _ = _parse_pipedream_txt(path)
    state = sum(n["parameter"] for n in nodes.values())
    assert state == pytest.approx(110.7e9, rel=1e-3)
    assert state > A100.memory_capacity
    # one worker model: the profile's times and a collective's parallel
    # add are priced with the same peaks
    assert comm_model.parallel_add_time(1e6, 4) == comm_model.parallel_add_time(
        1e6, 4, mem_frequency=A100.memory_bandwidth,
        peak_flops=A100.peak_flops)
    matmul = arch.op_costs(olmoe, 4096, 1)[2]          # QKVProj, layer 0
    assert arch.forward_time(matmul) == matmul["flops"] / A100.peak_flops


def test_committed_architecture_is_what_the_benchmark_says_is_published():
    """The widths are pinned twice: the architecture file the program
    reads and the ``published`` block of the benchmark's configuration
    file (the catalog row's keys) must be the same numbers."""
    config = json.load(open(os.path.join(REPO, OLMOE_FILE)))
    bench = json.load(open(os.path.join(
        REPO, "benchmarks/configs/olmoe_1b7b_ramp32.json")))
    assert config["source_url"] == bench["source"]
    published = dict(bench["published"])
    assert published.pop("train_batch_size") == 4000
    assert published == config["config"]
    # the driver compares the numbers at the file's top level
    for key, value in config["config"].items():
        assert bench[key] == value, key


# ------------------------------------------- (c) determinism, identity
def test_profile_is_deterministic_and_named_by_shape(tmp_path):
    a = arch.write_profiles(str(tmp_path / "a"), TINY, TINY_SHAPES)
    b = arch.write_profiles(str(tmp_path / "b"), TINY, TINY_SHAPES)
    assert [os.path.basename(p) for p in a] == [
        "tinymoe_s32_b4096.txt", "tinymoe_s32_b2097152.txt"]
    for pa, pb in zip(a, b):
        assert open(pa, "rb").read() == open(pb, "rb").read()
    with pytest.raises(ValueError, match="repeat"):
        arch.write_profiles(str(tmp_path / "c"), TINY, TINY_SHAPES[:1] * 2)


@pytest.mark.parametrize("change", ["seq_len", "micro_batch", "one_more",
                                    "config"])
def test_dataset_id_changes_with_any_shape(change):
    base = arch.dataset_id(TINY, TINY_SHAPES)
    assert base == arch.dataset_id(dict(TINY), [dict(s) for s in TINY_SHAPES])
    shapes, config = [dict(s) for s in TINY_SHAPES], dict(TINY)
    if change == "one_more":
        shapes.append({"seq_len": 64, "micro_batch": 1})
    elif change == "config":
        config["num_experts_per_tok"] = 1
    else:
        shapes[1][change] += 1
    assert arch.dataset_id(config, shapes) != base


# ------------------------------------------------ through the generator
def _tiny_arch_file(directory) -> str:
    path = os.path.join(str(directory), "tinymoe.json")
    with open(path, "w") as fh:
        json.dump({"source_url": "test-local", "config": TINY}, fh)
    return path


def _tiny_env(arch_file, **over):
    from ddls_tpu.envs import RampJobPartitioningEnvironment

    kwargs = dict(
        topology_config={"type": "ramp", "kwargs": {
            "num_communication_groups": 4,
            "num_racks_per_communication_group": 4,
            "num_servers_per_rack": 2, "num_channels": 1,
            # at hidden 64 every op is memory-bound, and on the real
            # 1.6e12 fabric partitioning buys no time (JCT 1-3 x the
            # sequential time at every degree): a 100 x faster fabric
            # lets jobs through, so that the cluster loads and blocks
            "total_node_bandwidth": 1.6e14,
            "intra_gpu_propagation_latency": 50e-9,
            "worker_io_latency": 100e-9}},
        node_config={"type_1": {"num_nodes": 32, "workers_config": [
            {"num_workers": 1, "worker": "A100"}]}},
        jobs_config={
            "architecture": {"config": arch_file, "shapes": TINY_SHAPES},
            "job_interarrival_time_dist": {
                "_target_": "ddls_tpu.demands.distributions.Fixed",
                "val": 0.3},
            "max_acceptable_job_completion_time_frac_dist": {
                "_target_": "ddls_tpu.demands.distributions.Uniform",
                "min_val": 0.1, "max_val": 1.0, "decimals": 2},
            "replication_factor": 10,
            "job_sampling_mode": "remove_and_repeat",
            "shuffle_files": True, "num_training_steps": 20},
        max_partitions_per_op=16, min_op_run_time_quantum=QUANTUM,
        reward_function="job_acceptance", max_simulation_run_time=12.0,
        pad_obs_kwargs={"max_nodes": 50, "max_edges": 64},
        use_native_lookahead=False)
    kwargs.update(over)
    return RampJobPartitioningEnvironment(**kwargs)


def test_generator_takes_an_architecture_like_any_other_source(tmp_path):
    from ddls_tpu.demands.jobs_generator import JobsGenerator
    from ddls_tpu.telemetry import startup

    jobs = dict(
        architecture={"config": _tiny_arch_file(tmp_path),
                      "shapes": TINY_SHAPES},
        job_interarrival_time_dist={
            "_target_": "ddls_tpu.demands.distributions.Fixed", "val": 1.0},
        replication_factor=3, num_training_steps=20)
    startup.registry().reset()
    gen = JobsGenerator(**jobs)
    models = sorted({p.details["model"] for p in gen.sampler.prototypes})
    assert models == ["tinymoe_s32_b2097152", "tinymoe_s32_b4096"]
    assert len(gen.sampler.prototypes) == 6
    # 1 + 2 x 8 + 2 forward ops, 18 + 2 x 4 forward edges mirrored + join
    assert {p.graph.n_ops for p in gen.sampler.prototypes} == {38}
    assert {p.graph.n_deps for p in gen.sampler.prototypes} == {53}
    assert gen.workload_fingerprint[0] == arch.dataset_id(TINY, TINY_SHAPES)
    assert JobsGenerator(**jobs).workload_fingerprint \
        == gen.workload_fingerprint          # another temp dir, same id
    assert startup.gauges() == {
        f"graphs.arch.{what}.{m}": n for m in models
        for what, n in (("forward_ops", 19), ("edges", 53))}
    assert [n for n, _, _ in startup.registry().span_intervals()] \
        == ["startup.job_graphs"] * 2
    report = json.loads(startup.report()[len("[startup] "):])
    assert report["graphs.arch.forward_ops.tinymoe_s32_b4096"] == 19
    assert report["job_graphs"] >= 0
    startup.registry().reset()
    with pytest.raises(ValueError, match="architecture"):
        JobsGenerator(job_interarrival_time_dist=jobs[
            "job_interarrival_time_dist"])


def test_env_yaml_states_what_its_comments_derive(olmoe):
    """env_olmoe32.yaml's arrival gap, horizon and pads are derived
    from the builder's graph, as its comments say."""
    import math

    from ddls_tpu.config import load_config

    cfg = load_config(
        os.path.join(REPO, "scripts/ramp_job_partitioning_configs"),
        "rllib_config", ["env_config=env_olmoe32"])["env_config"]
    jobs = cfg["jobs_config"]
    assert jobs["architecture"]["config"] == OLMOE_FILE
    shapes = jobs["architecture"]["shapes"]
    assert [(s["seq_len"], s["micro_batch"]) for s in shapes] == [
        (olmoe["max_position_embeddings"], b) for b in (1, 2, 4, 8)]
    steps = jobs["num_training_steps"]
    lengths = [steps * (1 + arch.BACKWARD_OVER_FORWARD) * sum(
        arch.forward_time(c) for c in arch.op_costs(olmoe, **s))
        for s in shapes]
    gap = np.mean(lengths) / 25
    two_figures = round(gap, 1 - int(math.floor(math.log10(gap))))
    assert jobs["job_interarrival_time_dist"]["val"] == two_figures == 0.76
    assert cfg["max_simulation_run_time"] == pytest.approx(400 * two_figures)
    assert cfg["min_op_run_time_quantum"] == QUANTUM
    n_ops, n_deps = 262, 389
    assert cfg["pad_obs_kwargs"] == {"max_nodes": 50 * -(-n_ops // 50),
                                     "max_edges": 256 * -(-n_deps // 256)}


# -------------------------------------------------- (d) a ragged row
@pytest.fixture(scope="module")
def tiny_block_build(tmp_path_factory):
    from test_jax_lookahead import _BlockBuild

    arch_file = _tiny_arch_file(tmp_path_factory.mktemp("tiny_arch"))
    return _BlockBuild(_tiny_env(arch_file))


def test_tiny_preset_partitions_into_ragged_rows(tiny_block_build):
    et = tiny_block_build.et
    assert et.degrees == [1, 2, 4, 6, 8, 10, 12, 14, 16]
    assert et.types == ["tinymoe_s32_b2097152", "tinymoe_s32_b4096"]
    assert 0 < et.pads.n_deps_used < et.pads.n_deps
    assert et.pads.n_fwd == 19 and et.pads.n_orig == 38
    split = np.asarray(et.tables["f_split"])
    ragged = tiny_block_build.row("tinymoe_s32_b4096", 16)
    assert sorted(set(split[ragged].tolist())) == [2, 4, 6]
    even = tiny_block_build.row("tinymoe_s32_b2097152", 16)
    assert set(split[even].tolist()) == {16}
    for degree in (1, 2, 8, 16):
        row = tiny_block_build.row("tinymoe_s32_b2097152", degree)
        assert set(split[row].tolist()) == {degree}
        assert int(et.tables["n_ops"][row]) == 38 * degree


@pytest.mark.parametrize("model,degree", [
    ("tinymoe_s32_b4096", 16), ("tinymoe_s32_b4096", 4),
    ("tinymoe_s32_b2097152", 8)])
def test_block_lookahead_is_flat_lookahead_on_a_ragged_row(
        tiny_block_build, model, degree):
    """PR 24's test, on rows whose blocks are 2x4, 4x6, 6x2 ...: the
    block-form lookahead equals the flat form on all six outputs."""
    from test_jax_lookahead import _assert_same_bits

    build = tiny_block_build
    cfg = build.row(model, degree)
    args, blocks, placed = build.arguments(cfg, build.states[0])
    want = build.flat(args, blocks)
    _assert_same_bits(build.block(args, blocks), want, (model, degree))
    assert bool(placed) and bool(want[4]) and int(want[5]) > 0


# ---------------------------- (b) the in-kernel episode vs the oracle
EPISODE_DRIVER = r"""
import json, sys
sys.path[:0] = [{repo!r}, {tests!r}, {benchmarks!r}]
import jax
assert jax.config.read("jax_enable_x64") == {x64}
import test_arch_graphs as t
from benchmarks.reference import first_mismatch
from ddls_tpu.scenarios.conformance import (
    decision_events, jitted_decision_events, run_recorded_episode)

env = t._tiny_env({arch_file!r})
events, actions = run_recorded_episode(env, {seed}, max_decisions=24)
host = decision_events(events)
kernel = jitted_decision_events(env, events, actions)
print(json.dumps({{
    "decisions": len(actions), "degrees": sorted(set(actions)),
    "accepted": sum(e["accepted"] for e in host),
    "causes": sorted({{str(e["cause"]) for e in host}}),
    "max_jct": max(e["jct"] for e in host),
    "mismatch": first_mismatch(host, kernel, {rtol})}}, default=str))
"""


@pytest.mark.parametrize("x64,rtol", [(True, 1e-9), (False, 1e-4)],
                         ids=["x64_1e-9", "f32_1e-4"])
def test_in_kernel_episode_replays_the_host_oracle(tmp_path, x64, rtol):
    """A seeded action sequence on the tiny preset (10 us quantum, ops
    of 9 us to 21 ms, steps of up to 0.74 s, jobs of up to 15 s): the
    jitted episode agrees with the float64 Python oracle on accepted
    and cause exactly, on times to 1e-9 under x64 and to 1e-4 in
    float32. Each in its own process: x64 is process-global."""
    driver = EPISODE_DRIVER.format(
        repo=REPO, tests=os.path.join(REPO, "tests"),
        benchmarks=os.path.join(REPO, "tests", "benchmarks"),
        arch_file=_tiny_arch_file(tmp_path), seed=12, x64=x64, rtol=rtol)
    out = subprocess.run(
        [sys.executable, "-c", driver], capture_output=True, text=True,
        timeout=900, env={**os.environ, "JAX_PLATFORMS": "cpu",
                          "JAX_ENABLE_X64": "1" if x64 else "0"})
    assert out.returncode == 0, out.stderr[-3000:]
    verdict = json.loads(out.stdout.strip().splitlines()[-1])
    assert verdict["mismatch"] is None, verdict
    assert verdict["decisions"] == 24
    # the sequence shows something: accepted and blocked jobs, an SLA
    # rejection among them, small and large degrees, second-long JCTs
    assert 0 < verdict["accepted"] < 24, verdict
    assert "max_acceptable_job_completion_time_exceeded" \
        in verdict["causes"], verdict
    assert {1, 16} & set(verdict["degrees"]) and verdict["max_jct"] > 1.0
