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

from ddls_tpu.demands.jobs_generator import BANK_GAUGES
from ddls_tpu.graphs import arch
from ddls_tpu.graphs.readers import _parse_pipedream_txt, read_graph_file

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
OLMOE_FILE = "ddls_tpu/graphs/arch_configs/olmoe_1b_7b_0125.json"

#: 2 layers, hidden 64, 4 experts, 2 per token, sequence 32
TINY = {"model_type": "tinymoe", "hidden_size": 64,
        "num_attention_heads": 4, "num_key_value_heads": 4,
        "use_qk_norm": True, "intermediate_size": 32, "num_experts": 4,
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
    gauges = startup.gauges()
    assert gauges.pop(BANK_GAUGES[1]) == 2
    shares = {k: v for k, v in gauges.items() if "_share" in k}
    assert {k: v for k, v in gauges.items()
            if "_bytes" not in k and k not in shares} == {
        f"graphs.arch.{what}.{m}": n for m in models
        for what, n in (("forward_ops", 19), ("edges", 53),
                        ("layers_full", 2), ("layers_window", 0),
                        ("layers_linear", 0), ("layers_block_sparse", 0),
                        ("layers_latent", 0), ("layers_indexed", 0),
                        ("shortcut_branches", 0), ("zero_experts", 0),
                        ("position_streams", 1),
                        ("shared_expert_layers", 0))}
    assert sorted(shares) == sorted(
        [BANK_GAUGES[0], *BANK_GAUGES[2:]]
        + [f"graphs.arch.{kind}_share.{m}" for m in models
           for kind in ("quadratic_time", "linear_time", "branch_time",
                        "zero_routed", "index_time", "sparse_core_time",
                        "attended_keys")])
    # a chain with shortcut edges alone: nothing runs beside it, no
    # router output is a zero-compute expert, and with no indexer every
    # key of a full core is read
    assert [gauges[name] for name in BANK_GAUGES[2:]] == [0, 0, 0, 2]
    # an unstated family: what a dep or a sync edge is sized by is the
    # largest op's whole memory cost
    for m in models:
        graph = next(p.graph for p in gen.sampler.prototypes
                     if p.details["model"] == m)
        biggest = max(graph.memory_cost(o) for o in graph.op_ids)
        assert gauges[f"graphs.arch.resident_bytes.{m}"] == sum(
            graph.memory_cost(o) for o in graph.op_ids)
        assert gauges[f"graphs.arch.payload_bytes_max.{m}"] == biggest
        assert gauges[f"graphs.arch.sync_bytes_max.{m}"] == biggest
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


# =========================================================== glm_moe_dsa
GLM_FILE = "ddls_tpu/graphs/arch_configs/glm_5.json"
#: the deployment's cut (env_glm5_32.yaml): 3 dense + 4 expert layers +
#: the MTP module, 64 of the 256 routed experts
GLM_CUT = {"layers": {"leading_dense": 3, "following": 4},
           "experts_held": 64}
#: 1 dense + 2 expert layers + MTP, hidden 64, 8 experts (2 a token, 1
#: shared), index top-16
TINY_GLM = {"model_type": "tinyglm", "hidden_size": 64,
            "num_attention_heads": 4, "q_lora_rank": 32,
            "kv_lora_rank": 16, "qk_nope_head_dim": 12,
            "qk_rope_head_dim": 4, "v_head_dim": 16, "index_n_heads": 2,
            "index_head_dim": 8, "index_topk": 16,
            "intermediate_size": 128, "moe_intermediate_size": 32,
            "n_routed_experts": 8, "n_shared_experts": 1,
            "num_experts_per_tok": 2, "first_k_dense_replace": 1,
            "num_hidden_layers": 3, "num_nextn_predict_layers": 1,
            "scoring_func": "sigmoid", "vocab_size": 256}
STATE = {"resident_bytes_per_parameter": 16,
         "synced_bytes_per_parameter": 2}
OLMOE_SHA256 = {
    1: "e37d732fdece58a9690ddecfee7e1b2c59e8a469047e40e1633973723963d263",
    2: "89ce01d98f6416e189da20c491a8b1cbe80ceb430a9aa3a3d0ae5dde06915a0a",
    4: "8aba20ad93284818802a2662483d29cafe48784ba07276e1c2de6260229824e0",
    8: "433fef7186272ee4ca9bbf18c6d416d744267f3ef95029eb6958b15ebb96f305"}


@pytest.fixture(scope="module")
def glm():
    return arch.load_arch_config(GLM_FILE)


@pytest.mark.parametrize("stated", ["modeling_block", "config_key",
                                    "nowhere"])
def test_qk_norm_is_counted_where_a_key_states_it(olmoe, stated):
    """OLMoE's ``config.json`` has no key for its q/k RMSNorm: the
    architecture file's ``modeling`` block states ``use_qk_norm`` and
    `builder_config` lays it over the config. The builder counts the
    norm (a weight and 4 FLOPs an element of a token's q and k) by that
    key alone, wherever it was stated."""
    published = arch.load_arch_file(OLMOE_FILE)["config"]
    assert "use_qk_norm" not in published and olmoe["use_qk_norm"] is True
    config = {"modeling_block": olmoe,
              "config_key": {**published, "use_qk_norm": True},
              "nowhere": published}[stated]
    T, H = 4096, published["hidden_size"]
    qk = 2 * H                       # 16 q heads and 16 k heads of 128
    proj = next(c for c in arch.op_costs(config, T, 1)
                if c["op_type"] == "QKVProj")
    normed = stated != "nowhere"
    assert proj["params"] == H * 3 * H + normed * qk
    # x W_qkv, RoPE 3 an element of q and k, the norm 4
    assert proj["flops"] == 2 * T * H * 3 * H + (3 + 4 * normed) * T * qk


@pytest.mark.parametrize("micro_batch", [1, 2, 4, 8])
def test_olmoe_profiles_are_text_equal_to_the_parents(olmoe, micro_batch):
    """The builder was rewritten around layer kinds; what it writes for
    the family the benchmark already runs is pinned to PR 29's bytes."""
    import hashlib

    text = arch.profile_text(olmoe, 4096, micro_batch)
    assert "sync_size" not in text
    assert hashlib.sha256(text.encode()).hexdigest() \
        == OLMOE_SHA256[micro_batch]


@pytest.mark.parametrize("case", ["tiny_s8", "tiny_s32", "tiny_cut",
                                  "glm5_8k_cut", "glm5_64k_cut"])
def test_op_costs_equal_the_plain_count_op_by_op(glm, case):
    """`tests/plain_arch_counts.py` is written from the equations and
    imports nothing of the builder: parameters, FLOPs and output
    elements agree on every op, below and above the indexer's top-k."""
    from plain_arch_counts import plain_counts

    config, seq_len, micro_batch, cut, plain_cut = {
        "tiny_s8": (TINY_GLM, 8, 3, {}, {}),
        "tiny_s32": (TINY_GLM, 32, 2, {}, {}),
        "tiny_cut": (TINY_GLM, 32, 4,
                     {"layers": {"leading_dense": 1, "following": 1},
                      "experts_held": 2},
                     {"leading_dense": 1, "following": 1, "held": 2}),
        "glm5_8k_cut": (glm, 8192, 4, GLM_CUT,
                        {"leading_dense": 3, "following": 4, "held": 64}),
        "glm5_64k_cut": (glm, 65536, 1, GLM_CUT,
                         {"leading_dense": 3, "following": 4, "held": 64}),
    }[case]
    built = arch.op_costs(config, seq_len, micro_batch, **cut)
    plain = plain_counts(config, seq_len, micro_batch, **plain_cut)
    assert [o["op_type"] for o in built] == [p[0] for p in plain]
    for i, (o, (kind, params, flops, out)) in enumerate(zip(built, plain)):
        assert o["params"] == params, (i, kind)
        assert o["flops"] == pytest.approx(flops, rel=1e-12), (i, kind)
        assert o["out_elems"] == pytest.approx(out, rel=1e-12), (i, kind)


@pytest.mark.parametrize("quantity", ["parameters", "active_parameters"])
def test_full_depth_totals_are_the_published_model(glm, quantity):
    """All 78 layers and 256 experts: 743.9 B parameters with the MTP
    module apart (the family's published 744 B), ~40 B active a token."""
    whole = arch.op_costs({**glm, "num_nextn_predict_layers": 0}, 4096, 1)
    total = sum(o["params"] for o in whole)
    if quantity == "parameters":
        assert len(whole) == 1 + 3 * 11 + 75 * 14 + 2
        assert total == pytest.approx(743.9e9, rel=1e-4)
        with_mtp = sum(o["params"] for o in arch.op_costs(glm, 4096, 1))
        # one more expert layer and the 2H x H projection
        assert with_mtp - total == pytest.approx(9.877e9 + 0.0755e9,
                                                 rel=1e-3)
    else:
        idle = (glm["n_routed_experts"] - glm["num_experts_per_tok"]) \
            * 3 * glm["hidden_size"] * glm["moe_intermediate_size"] * 75
        assert total - idle == pytest.approx(40e9, rel=0.06)


@pytest.mark.parametrize("seq_len,keys", [
    (2048, 2048 * 2049 // 2),
    (2049, 2048 * 2049 // 2 + 2048),
    (65536, 2048 * 2049 // 2 + (65536 - 2048) * 2048)])
def test_sparse_core_reads_min_t_topk_keys_a_query(glm, seq_len, keys):
    assert arch.attended_keys(seq_len, glm["index_topk"]) == keys
    core = next(o for o in arch.op_costs(glm, seq_len, 1, **GLM_CUT)
                if o["op_type"] == "SparseAttnCore")
    heads = glm["num_attention_heads"]
    assert core["flops"] == keys * heads * (
        2 * glm["qk_head_dim"] + 2 * glm["v_head_dim"] + 5)
    # the indexer stays S^2: it passes the core as the sequence grows
    index = next(o for o in arch.op_costs(glm, seq_len, 1, **GLM_CUT)
                 if o["op_type"] == "IndexScoreTopK")
    assert index["flops"] == seq_len ** 2 / 2 * 32 * (2 * 128 + 2)
    assert (index["flops"] > core["flops"]) == (seq_len > 16384)


@pytest.mark.parametrize("case", ["tiny", "glm5"])
def test_the_four_shares_add_up_to_the_uncut_layer(glm, case):
    """Four pods hold a quarter of the routed experts each: their expert
    groups' FLOPs and parameters sum to the uncut layer's, and every
    other op — what each pod computes alike, the shared expert among
    it — is the uncut layer's own, counted once."""
    config, seq_len, held = {"tiny": (TINY_GLM, 32, 2),
                             "glm5": (glm, 8192, 64)}[case]
    layers = {"leading_dense": 0, "following": 1}
    uncut = arch.op_costs(config, seq_len, 2, layers=layers)
    share = arch.op_costs(config, seq_len, 2, layers=layers,
                          experts_held=held)
    assert [o["op_type"] for o in share] == [o["op_type"] for o in uncut]
    assert 4 * held == config["n_routed_experts"]
    for a, b in zip(share, uncut):
        if a["op_type"] == "Experts":
            for key in ("flops", "params", "out_elems"):
                assert 4 * a[key] == b[key], key
        elif a["op_type"] == "CombineResidual":
            # the weighted sum runs over this pod's pairs; the shared
            # expert's add and the residual are whole
            T = seq_len * 2
            k, H = config["num_experts_per_tok"], config["hidden_size"]
            assert a["flops"] == 2 * T * k * H / 4 + 2 * T * H
            assert b["flops"] == 2 * T * k * H + 2 * T * H
        else:
            assert a == b, a["op_type"]


def test_the_cut_keeps_every_layer_kind_and_no_dangling_edge(glm, tmp_path):
    path, = arch.write_profiles(
        str(tmp_path), glm, [{"seq_len": 8192, "micro_batch": 1}],
        GLM_CUT["layers"], GLM_CUT["experts_held"], STATE)
    assert os.path.basename(path) == "glm_moe_dsa_s8192_b1.txt"
    nodes, edges = _parse_pipedream_txt(path)
    kinds = [n["op_type"] for n in nodes.values()]
    attention = ["InputNorm", "QAProj", "QBProj", "KVAProj", "KVBProj",
                 "IndexerProj", "IndexScoreTopK", "SparseAttnCore",
                 "OutProjResidual", "PostAttnNorm"]
    dense = attention + ["DenseMLPResidual"]
    expert = attention + ["Router", "SharedExpert", "Experts",
                          "CombineResidual"]
    assert kinds == (["Embedding"] + dense * 3 + expert * 4
                     + ["MTPHiddenNorm", "MTPEmbedNorm", "MTPProj"] + expert
                     + ["FinalNorm", "LMHeadLoss"])
    assert len(kinds) == 109 and len(edges) == 173
    # every op but the embedding consumes something, every op but the
    # loss is consumed; the head sees the main stream and the MTP's
    ids = set(nodes)
    assert {v for _, v in edges} == ids - {"1"}
    assert {u for u, _ in edges} == ids - {"109"}
    final = str(kinds.index("FinalNorm") + 1)
    assert sorted(int(u) for u, v in edges if v == final) == [90, 107]
    # true data dependencies: c_q feeds W_qb and the indexer, x three
    # projections, k_r (with c_kv) the core
    first = 2                                   # layer 0's InputNorm
    at = {name: first + i for i, name in enumerate(attention)}
    consumers = lambda u: sorted(int(v) for s, v in edges if int(s) == u)
    assert consumers(at["QAProj"]) == [at["QBProj"], at["IndexerProj"]]
    assert consumers(at["InputNorm"]) == [at["QAProj"], at["KVAProj"],
                                          at["IndexerProj"]]
    assert consumers(at["KVAProj"]) == [at["KVBProj"],
                                        at["SparseAttnCore"]]
    graph = read_graph_file(path)
    assert (len(graph.forward_op_ids()), graph.n_ops, graph.n_deps) \
        == (109, 218, 347)
    assert sum(n["parameter"] for n in nodes.values()) \
        == pytest.approx(261.3e9, rel=1e-3)


def test_glm_env_yaml_states_what_its_comments_derive(glm):
    """env_glm5_32.yaml: the cut and the shapes as data, the arrival gap
    and horizon derived from the builder's graph, env_olmoe32's pads
    rule, and nothing else changed from env_olmoe32."""
    import math

    from ddls_tpu.config import load_config

    def env(name):
        return load_config(
            os.path.join(REPO, "scripts/ramp_job_partitioning_configs"),
            "rllib_config", [f"env_config={name}"])["env_config"]

    cfg, base = env("env_glm5_32"), env("env_olmoe32")
    jobs = cfg["jobs_config"]
    family = jobs["architecture"]
    assert family["config"] == GLM_FILE
    assert {k: family[k] for k in GLM_CUT} == GLM_CUT
    shapes = family["shapes"]
    assert [(s["seq_len"], s["micro_batch"]) for s in shapes] == [
        (8192, 1), (8192, 4), (32768, 1), (65536, 1)]
    steps = jobs["num_training_steps"]
    lengths = [steps * (1 + arch.BACKWARD_OVER_FORWARD) * sum(
        arch.forward_time(c) for c in arch.op_costs(glm, **s, **GLM_CUT))
        for s in shapes]
    gap = np.mean(lengths) / 25
    two_figures = round(gap, 1 - int(math.floor(math.log10(gap))))
    assert jobs["job_interarrival_time_dist"]["val"] == two_figures == 7.5
    assert cfg["max_simulation_run_time"] == pytest.approx(400 * two_figures)
    assert cfg["pad_obs_kwargs"] == {"max_nodes": 50 * -(-218 // 50),
                                     "max_edges": 256 * -(-347 // 256)}
    # the rest is env_olmoe32's
    changed = {"jobs_config", "max_simulation_run_time", "pad_obs_kwargs"}
    assert {k: v for k, v in cfg.items() if k not in changed} \
        == {k: v for k, v in base.items() if k not in changed}
    for key in set(jobs) - {"architecture", "job_interarrival_time_dist"}:
        assert jobs[key] == base["jobs_config"][key], key


# ------------------------------------------------------ the three sizes
def _partition_digest(graph, splits):
    """sha256 over every op's memory and every dep's size of the graph
    partitioned with forward op i split ``splits[i % len(splits)]``
    ways."""
    import hashlib

    from ddls_tpu.sim.partition import partition_graph

    action = {str(int(op)): splits[i % len(splits)]
              for i, op in enumerate(graph.forward_op_ids())}
    part = partition_graph(graph, action)
    h = hashlib.sha256()
    for op in part.op_ids:
        h.update(f"{op}:{part.memory_cost(op).hex()};".encode())
    for u, v in part.edge_ids:
        h.update(f"{u}>{v}:{part.edge_size(u, v).hex()};".encode())
    return part.n_ops, part.n_deps, h.hexdigest()


@pytest.mark.parametrize("family", ["synthetic_chains", "olmoe"])
def test_unstated_graphs_partition_bit_equal_to_the_parents(
        olmoe, tmp_path, family):
    """A profile that states only activation_size and parameter_size is
    sized as the reference sizes it: digests taken at PR 29's tree."""
    from ddls_tpu.graphs.synthetic import generate_pipedream_txt_files

    if family == "olmoe":
        path, = arch.write_profiles(
            str(tmp_path), olmoe, [{"seq_len": 4096, "micro_batch": 2}])
        got = [_partition_digest(read_graph_file(path), (4, 2, 1, 8))]
        want = [(974, 7139, "ff58e4521c3f5654c037506005be76216fbcf3cda"
                 "4e3af6097c5d62ee252bba0")]
    else:
        paths = generate_pipedream_txt_files(
            str(tmp_path), n_cnn=1, n_translation=1, seed=3, min_ops=5,
            max_ops=7)
        got = [_partition_digest(read_graph_file(p), (1, 2, 4, 2, 6))
               for p in sorted(paths)]
        want = [(36, 140, "d1fbd463b605dfab68d2da9465e698e1e22676aae5349"
                 "e77f2567f7e43eb5919"),
                (32, 119, "003d5dc661c465ae96dccd9b2e51c1cd29a7de4b9b201"
                 "b4e3a604001bedda1c4")]
    assert got == want
    graph = read_graph_file(path if family == "olmoe" else sorted(paths)[0])
    assert all(graph.stated_payload(o) is None
               and graph.stated_sync(o) is None for o in graph.op_ids)


def _stated_tiny_graph(directory):
    path, = arch.write_profiles(
        str(directory), TINY_GLM, [{"seq_len": 32, "micro_batch": 4096}],
        training_state=STATE)
    return path, read_graph_file(path)


def test_a_stated_job_occupies_parameter_state_and_two_activations(
        tmp_path):
    path, graph = _stated_tiny_graph(tmp_path)
    nodes, _ = _parse_pipedream_txt(path)
    params = sum(o["params"] for o in arch.op_costs(TINY_GLM, 32, 4096))
    activations = sum(n["activation"] for n in nodes.values())
    assert sum(n["parameter"] for n in nodes.values()) == params * 16
    assert sum(n["sync"] for n in nodes.values()) == params * 2
    assert sum(graph.memory_cost(o) for o in graph.op_ids) \
        == params * 16 + 2 * activations
    n = len(nodes)
    for op, vals in nodes.items():
        mirror = str(2 * n - (int(op) - 1))
        assert graph.memory_cost(op) == vals["activation"] \
            + vals["parameter"]
        assert graph.memory_cost(mirror) == vals["activation"]
        for node in (op, mirror):
            assert graph.payload(node) == vals["activation"]
            assert graph.sync_size(node) == vals["sync"]


@pytest.mark.parametrize("degree", [2, 4])
def test_stated_deps_carry_activations_and_syncs_carry_gradients(
        tmp_path, degree):
    """`partition_graph` on a stated graph: every dep is its producer's
    activation divided by each split it crosses, every clique edge the
    op's bf16 gradient / n; ops hold memory / n."""
    from ddls_tpu.sim.partition import partition_graph

    path, graph = _stated_tiny_graph(tmp_path)
    nodes, _ = _parse_pipedream_txt(path)
    n_fwd = len(nodes)
    # every other forward op split, the rest whole
    split = {op: degree if int(op) % 2 else 1 for op in nodes}
    part = partition_graph(graph, dict(split))

    def original(sub):              # "12b" -> ("12", forward op "5")
        op = sub.rstrip("abcdefghijklmnop")
        fwd = op if int(op) <= n_fwd else str(2 * n_fwd - (int(op) - 1))
        return op, fwd

    cliques = deps = 0
    for u, v in part.edge_ids:
        (ou, fu), (ov, fv) = original(u), original(v)
        size = part.edge_size(u, v)
        if ou == ov:                # two sub-ops of one backward op
            assert int(ou) > n_fwd and u != v
            assert size == nodes[fu]["sync"] / split[fu]
            cliques += 1
        else:
            assert size == nodes[fu]["activation"] / split[fu] / split[fv]
            deps += 1
    assert cliques == sum(degree * (degree - 1)
                          for s in split.values() if s > 1)
    assert deps > 0
    for sub in part.op_ids:
        op, fwd = original(sub)
        assert part.memory_cost(sub) == graph.memory_cost(op) / split[fwd]


def _tiny_glm_arch_file(directory) -> str:
    path = os.path.join(str(directory), "tinyglm.json")
    with open(path, "w") as fh:
        json.dump({"source_url": "test-local", "training_state": STATE,
                   "config": TINY_GLM}, fh)
    return path


#: ops of 8 us - 6 ms and steps of ~0.2 s at the first, a ragged row at
#: the second
TINY_GLM_SHAPES = [{"seq_len": 32, "micro_batch": 2 ** 19},
                   {"seq_len": 32, "micro_batch": 4096}]


def _tiny_glm_env(arch_file, **over):
    jobs = dict(
        architecture={"config": arch_file, "shapes": TINY_GLM_SHAPES,
                      "layers": {"leading_dense": 1, "following": 1},
                      "experts_held": 4},
        job_interarrival_time_dist={
            "_target_": "ddls_tpu.demands.distributions.Fixed", "val": 0.4},
        max_acceptable_job_completion_time_frac_dist={
            "_target_": "ddls_tpu.demands.distributions.Uniform",
            "min_val": 0.1, "max_val": 1.0, "decimals": 2},
        replication_factor=10, job_sampling_mode="remove_and_repeat",
        shuffle_files=True, num_training_steps=20)
    # degrees to 8: the kernel's pads, and its compile, are a quarter
    # of the degree-16 ones
    over.setdefault("max_partitions_per_op", 8)
    return _tiny_env(arch_file, jobs_config=jobs,
                     max_simulation_run_time=16.0,
                     pad_obs_kwargs={"max_nodes": 100, "max_edges": 192},
                     **over)


@pytest.mark.parametrize("x64,rtol", [(True, 1e-9), (False, 1e-4)],
                         ids=["x64_1e-9", "f32_1e-4"])
def test_stated_job_in_kernel_replays_the_host_oracle(tmp_path, x64, rtol):
    """A tiny STATED glm_moe_dsa job family (cut to 1 dense + 1 expert
    layer + MTP, 4 of 8 experts) through reader -> mirror -> Job -> the
    jitted episode kernel against the float64 Python oracle: accepted
    and cause exactly, JCT to the tolerance; zero-size sync edges (ops
    without parameters) included."""
    driver = EPISODE_DRIVER.replace(
        "t._tiny_env(", "t._tiny_glm_env(").format(
        repo=REPO, tests=os.path.join(REPO, "tests"),
        benchmarks=os.path.join(REPO, "tests", "benchmarks"),
        arch_file=_tiny_glm_arch_file(tmp_path), seed=5, x64=x64, rtol=rtol)
    out = subprocess.run(
        [sys.executable, "-c", driver], capture_output=True, text=True,
        timeout=900, env={**os.environ, "JAX_PLATFORMS": "cpu",
                          "JAX_ENABLE_X64": "1" if x64 else "0"})
    assert out.returncode == 0, out.stderr[-3000:]
    verdict = json.loads(out.stdout.strip().splitlines()[-1])
    assert verdict["mismatch"] is None, verdict
    assert verdict["decisions"] == 24
    assert 0 < verdict["accepted"] < 24, verdict
    assert len(verdict["causes"]) >= 3, verdict


def test_conformance_host_native_leg_on_a_stated_spec(tmp_path):
    """`scripts/conformance.py --spec <file> --legs host_native`: the
    C++ engine steps a stated job family bit-exactly with the host."""
    from ddls_tpu.scenarios import get_spec
    from ddls_tpu.scenarios.conformance import run_conformance
    from ddls_tpu.scenarios.spec import ScenarioSpec

    env = _tiny_glm_env(_tiny_glm_arch_file(tmp_path))
    spec_file = tmp_path / "stated_spec.json"
    spec_file.write_text(ScenarioSpec(
        name="stated_tinyglm",
        topology=env.cluster.topology_config,
        node_config={"type_1": {"num_nodes": 32, "workers_config": [
            {"num_workers": 1, "worker": "A100"}]}},
        jobs={"architecture": {
            "config": _tiny_glm_arch_file(tmp_path),
            "shapes": TINY_GLM_SHAPES,
            "layers": {"leading_dense": 1, "following": 1},
            "experts_held": 4}},
        arrival={"kind": "fixed", "interarrival": 0.4},
        num_training_steps=20, max_partitions_per_op=8,
        min_op_run_time_quantum=QUANTUM, sim_seconds=16.0,
        pad_obs={"max_nodes": 100, "max_edges": 192}).to_json())
    report = run_conformance(get_spec(str(spec_file)), seed=3,
                             max_decisions=30, legs=["host_native"])
    leg, = report["legs"]
    assert leg["status"] == "ok", leg
    assert leg["rtol"] == 0.0 and leg["decisions"] == 30


# ========================================================= mimo_v2_flash
MIMO_FILE = "ddls_tpu/graphs/arch_configs/mimo_v2_flash.json"
#: the deployment's cut (env_mimo_32.yaml): layers 0-6 of the published
#: lists, 64 of the 256 routed experts
MIMO_CUT = {"layers": {"leading_dense": 1, "following": 6},
            "experts_held": 64}
MIMO_SHAPES = [(8192, 4), (32768, 1), (65536, 1), (262144, 1)]
#: 4 layers (attention F W W F; feed-forward D E E E), hidden 64, q/k
#: heads of 24 beside v heads of 16, 1 kv head on full layers and 2 on
#: window layers, window 16, 8 experts (2 a token, none shared)
TINY_MIMO = {"model_type": "tinymimo", "hidden_size": 64,
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
GLM_SHA256 = {
    (8192, 1):
        "a35b7d33627fa75c547574150e7600ff3e45eb34ecba07455c1f6225f2930f84",
    (8192, 4):
        "f7f69f829bc6fb32d96501e42f1de8055f4f1d5d604cc91c95a2f4fba79c6892",
    (32768, 1):
        "796597f437747b3801d9899db80e46b8f708a258cd7ec0e771eb1fa2f1ad75a4",
    (65536, 1):
        "9fb3efb29c5d2176570e6986500d4af38838d39d6c8097928826af318f4ad07e"}


@pytest.fixture(scope="module")
def mimo():
    return arch.load_arch_config(MIMO_FILE)


@pytest.mark.parametrize("shape", sorted(GLM_SHA256))
def test_glm_profiles_are_text_equal_to_the_parents(shape):
    """`_full_attention` was generalised under both older families: what
    the builder writes for GLM-5's four stated shapes is pinned to PR
    31's bytes (OLMoE's four: `test_olmoe_profiles_are_text_equal_...`)."""
    import hashlib

    family = arch.load_arch_file(GLM_FILE)
    text = arch.profile_text(family["config"], *shape, GLM_CUT["layers"],
                             GLM_CUT["experts_held"],
                             family["training_state"])
    assert hashlib.sha256(text.encode()).hexdigest() == GLM_SHA256[shape]


@pytest.mark.parametrize("case", ["tiny_s8", "tiny_s200", "tiny_cut",
                                  "mimo_8k_cut", "mimo_256k_cut",
                                  "mimo_whole"])
def test_mimo_op_costs_equal_the_plain_count_op_by_op(mimo, case):
    """`plain_counts_mimo` is written from the equations and imports
    nothing of the builder: parameters, FLOPs and output elements agree
    on every op, below and above the window, with both kinds of each
    per-layer list, split head sizes and per-kind kv heads."""
    from plain_arch_counts import plain_counts_mimo

    config, seq_len, micro_batch, cut, plain_cut = {
        "tiny_s8": (TINY_MIMO, 8, 3, {}, {}),
        "tiny_s200": (TINY_MIMO, 200, 2, {}, {}),
        "tiny_cut": (TINY_MIMO, 32, 4,
                     {"layers": {"leading_dense": 1, "following": 2},
                      "experts_held": 2}, {"layers": 3, "held": 2}),
        "mimo_8k_cut": (mimo, 8192, 4, MIMO_CUT, {"layers": 7, "held": 64}),
        "mimo_256k_cut": (mimo, 262144, 1, MIMO_CUT,
                          {"layers": 7, "held": 64}),
        "mimo_whole": (mimo, 4096, 1, {}, {}),
    }[case]
    built = arch.op_costs(config, seq_len, micro_batch, **cut)
    plain = plain_counts_mimo(config, seq_len, micro_batch, **plain_cut)
    assert [o["op_type"] for o in built] == [p[0] for p in plain]
    for i, (o, (kind, params, flops, out)) in enumerate(zip(built, plain)):
        assert o["params"] == params, (i, kind)
        assert o["flops"] == pytest.approx(flops, rel=1e-12), (i, kind)
        assert o["out_elems"] == pytest.approx(out, rel=1e-12), (i, kind)


@pytest.mark.parametrize("quantity", ["parameters", "active_parameters"])
def test_mimo_full_depth_totals_are_the_published_model(mimo, quantity):
    """All 48 layers and 256 experts: 308.8 B parameters (published
    309 B), ~15.4 B active a token (published A15 B)."""
    whole = arch.op_costs(mimo, 4096, 1)
    total = sum(o["params"] for o in whole)
    if quantity == "parameters":
        # embedding, a 6-op dense layer, 47 8-op expert layers, norm, head
        assert len(whole) == 1 + 6 + 47 * 8 + 2
        assert total == pytest.approx(308.8e9, rel=1e-4)
        kinds = [o["op_type"] for o in whole]
        assert (kinds.count("AttnCore"), kinds.count("WindowAttnCore")) \
            == (9, 39)
        assert (kinds.count("DenseMLPResidual"), kinds.count("Experts")) \
            == (1, 47)
    else:
        idle = (mimo["n_routed_experts"] - mimo["num_experts_per_tok"]) \
            * 3 * mimo["hidden_size"] * mimo["moe_intermediate_size"] * 47
        assert total - idle == pytest.approx(15.4e9, rel=5e-3)


@pytest.mark.parametrize("seq_len", [100, 128, 129, 262144])
def test_window_core_reads_min_t_w_keys_a_query(mimo, seq_len):
    """At S <= w the window is the causal triangle; above it a query
    sees w keys: the closed form against the sum itself."""
    w = mimo["sliding_window"]
    keys = sum(min(t, w) for t in range(1, seq_len + 1))
    assert arch.attended_keys(seq_len, w) == keys
    assert arch.attended_keys(seq_len, seq_len) \
        == seq_len * (seq_len + 1) // 2
    costs = arch.op_costs(mimo, seq_len, 1, **MIMO_CUT)
    window = next(o for o in costs if o["op_type"] == "WindowAttnCore")
    full = next(o for o in costs if o["op_type"] == "AttnCore")
    per_pair = 64 * (2 * 192 + 2 * 128 + 5)
    assert window["flops"] == keys * per_pair + seq_len * 64
    assert window["params"] == 64                 # a sink logit a head
    assert full["flops"] == seq_len * (seq_len + 1) // 2 * per_pair
    assert full["params"] == 0                    # no sink on full layers
    assert (window["flops"] < full["flops"]) == (seq_len > w)


def test_kv_heads_differ_by_layer_kind_and_out_projections_agree(mimo):
    """A window layer's QKVProj holds 8 kv heads and a full layer's 4,
    q and k at 192 and v at 128 a head; both out-projections are 64 x
    128 = 8192 -> 4096."""
    costs = arch.op_costs(mimo, 8192, 1, **MIMO_CUT)
    kinds = [o["op_type"] for o in costs]
    full_core, window_core = kinds.index("AttnCore"), kinds.index(
        "WindowAttnCore")
    H = 4096
    assert costs[full_core - 1]["op_type"] == "QKVProj"
    assert costs[full_core - 1]["params"] == H * (64 * 192 + 4 * 192
                                                  + 4 * 128)
    assert costs[window_core - 1]["params"] == H * (64 * 192 + 8 * 192
                                                    + 8 * 128)
    for core in (full_core, window_core):
        out = costs[core + 1]
        assert out["op_type"] == "OutProjResidual"
        assert out["params"] == 8192 * H
        assert costs[core]["out_elems"] == 8192 * 8192    # T x n x d_v
    # RoPE on round(0.334 x 192) = 64 of each q and k head, v scaled
    T = 8192
    assert costs[full_core - 1]["flops"] == 2 * T * H * (
        64 * 192 + 4 * 192 + 4 * 128) + 3 * T * 68 * 64 + T * 4 * 128


@pytest.mark.parametrize("case", ["tiny", "mimo"])
def test_the_four_mimo_shares_add_up_to_the_uncut_layer(mimo, case):
    """Four pods hold a quarter of the routed experts each: their expert
    groups' FLOPs and parameters sum to the uncut layer's, and every
    other op — what each pod computes alike; there is no shared expert —
    is the uncut layer's own, counted once."""
    config, seq_len, held = {"tiny": (TINY_MIMO, 32, 2),
                             "mimo": (mimo, 8192, 64)}[case]
    layers = {"leading_dense": 1, "following": 1}
    uncut = arch.op_costs(config, seq_len, 2, layers=layers)
    share = arch.op_costs(config, seq_len, 2, layers=layers,
                          experts_held=held)
    assert [o["op_type"] for o in share] == [o["op_type"] for o in uncut]
    assert 4 * held == config["n_routed_experts"]
    assert "SharedExpert" not in {o["op_type"] for o in uncut}
    seen = set()
    for a, b in zip(share, uncut):
        seen.add(a["op_type"])
        if a["op_type"] == "Experts":
            for key in ("flops", "params", "out_elems"):
                assert 4 * a[key] == b[key], key
        elif a["op_type"] == "CombineResidual":
            T = seq_len * 2
            k, H = config["num_experts_per_tok"], config["hidden_size"]
            assert a["flops"] == 2 * T * k * H / 4 + T * H
            assert b["flops"] == 2 * T * k * H + T * H
        else:
            assert a == b, a["op_type"]
    assert {"Experts", "CombineResidual", "Router", "WindowAttnCore",
            "AttnCore", "DenseMLPResidual"} <= seen


def test_the_mimo_cut_keeps_the_lists_order_and_no_dangling_edge(
        mimo, tmp_path):
    path, = arch.write_profiles(
        str(tmp_path), mimo, [{"seq_len": 8192, "micro_batch": 4}],
        MIMO_CUT["layers"], MIMO_CUT["experts_held"], STATE)
    assert os.path.basename(path) == "mimo_v2_flash_s8192_b4.txt"
    nodes, edges = _parse_pipedream_txt(path)
    kinds = [n["op_type"] for n in nodes.values()]

    def layer(core, feed_forward):
        return ["InputNorm", "QKVProj", core, "OutProjResidual",
                "PostAttnNorm", *feed_forward]

    expert = ["Router", "Experts", "CombineResidual"]
    F, W = "AttnCore", "WindowAttnCore"
    # layers 0-6 of the published lists: F W W W W F W; D E E E E E E
    assert mimo["hybrid_layer_pattern"][:7] == [0, 1, 1, 1, 1, 0, 1]
    assert mimo["moe_layer_freq"][:7] == [0, 1, 1, 1, 1, 1, 1]
    assert kinds == (["Embedding"] + layer(F, ["DenseMLPResidual"])
                     + sum((layer(core, expert)
                            for core in (W, W, W, W, F, W)), [])
                     + ["FinalNorm", "LMHeadLoss"])
    assert len(kinds) == 57 and len(edges) == 82
    ids = set(nodes)
    assert {v for _, v in edges} == ids - {"1"}
    assert {u for u, _ in edges} == ids - {"57"}
    graph = read_graph_file(path)
    assert (len(graph.forward_op_ids()), graph.n_ops, graph.n_deps) \
        == (57, 114, 165)
    assert sum(n["parameter"] for n in nodes.values()) \
        == pytest.approx(188.3e9, rel=1e-3)
    # a stated graph: parameters x 16 B + 2 x activations
    assert sum(graph.memory_cost(o) for o in graph.op_ids) \
        == pytest.approx(251.7e9, rel=1e-3)


@pytest.mark.parametrize("case", ["default", "one_period", "dense_count",
                                  "too_deep", "scalar_freq"])
def test_a_cut_takes_the_lists_first_layers_or_is_refused(mimo, glm, case):
    """With per-layer lists the cut says only HOW MANY layers: the
    default is the lists' own leading zeros and length, and a cut whose
    kinds would depart from the lists is not expressible."""
    if case == "default":
        assert arch.resolve_cut(mimo) == {
            "leading_dense": 1, "following": 47, "experts_held": 256}
    elif case == "one_period":
        assert arch.resolve_cut(mimo, **MIMO_CUT) == {
            "leading_dense": 1, "following": 6, "experts_held": 64}
    elif case == "dense_count":
        for layers in ({"leading_dense": 0, "following": 4},
                       {"leading_dense": 2, "following": 4}):
            with pytest.raises(ValueError, match="moe_layer_freq"):
                arch.resolve_cut(mimo, layers)
    elif case == "too_deep":
        with pytest.raises(ValueError, match="moe_layer_freq"):
            arch.resolve_cut(mimo, {"leading_dense": 1, "following": 48})
    else:
        # a scalar moe_layer_freq reads first_k_dense_replace, as before
        assert glm["moe_layer_freq"] == 1
        assert arch.resolve_cut(glm)["leading_dense"] == 3
        assert arch.resolve_cut(glm, {"leading_dense": 0, "following": 1}
                                )["following"] == 1


def _arch_gauges(arch_file, shapes, **cut):
    """Every start-up gauge `jobs_generator` sets for a family."""
    from ddls_tpu.demands.jobs_generator import JobsGenerator
    from ddls_tpu.telemetry import startup

    startup.registry().reset()
    JobsGenerator(
        architecture={"config": arch_file, "shapes": [
            {"seq_len": s, "micro_batch": b} for s, b in shapes], **cut},
        job_interarrival_time_dist={
            "_target_": "ddls_tpu.demands.distributions.Fixed", "val": 1.0},
        replication_factor=1, num_training_steps=20)
    gauges = startup.gauges()
    startup.registry().reset()
    return gauges


def _kind_gauges(arch_file, shapes, **cut):
    """The layer-kind gauges `jobs_generator` sets for a family, per
    model name: ``{"layers_full", "layers_window",
    "quadratic_time_share"}``."""
    kinds = {}
    for name, value in _arch_gauges(arch_file, shapes, **cut).items():
        what, _, model = name[len("graphs.arch."):].partition(".")
        if what in ("layers_full", "layers_window", "quadratic_time_share",
                    "shared_expert_layers"):
            kinds.setdefault(model, {})[what] = value
    return kinds


@pytest.mark.parametrize("family", ["olmoe", "glm", "mimo"])
def test_generator_counts_layers_and_the_quadratic_share(family):
    """What `jobs_generator` sets as start-up gauges from the profile's
    own op names and times: layers by the kind of their core, and the
    share of a degree-1 forward pass in ops whose FLOPs grow as S^2
    (full cores; GLM-5's index score)."""
    if family == "olmoe":
        kinds = _kind_gauges(OLMOE_FILE, [(4096, 1)])
        assert kinds["olmoe_s4096_b1"]["layers_full"] == 16
        assert kinds["olmoe_s4096_b1"]["layers_window"] == 0
        assert 0.05 < kinds["olmoe_s4096_b1"]["quadratic_time_share"] < 0.2
    elif family == "glm":
        kinds = _kind_gauges(GLM_FILE, [(8192, 1), (65536, 1)], **GLM_CUT)
        for k in kinds.values():      # latent-sparse: neither kind
            assert (k["layers_full"], k["layers_window"]) == (0, 0)
        assert kinds["glm_moe_dsa_s8192_b1"]["quadratic_time_share"] \
            < kinds["glm_moe_dsa_s65536_b1"]["quadratic_time_share"] < 0.5
    else:
        kinds = _kind_gauges(MIMO_FILE, MIMO_SHAPES, **MIMO_CUT)
        shares = []
        for (s, b) in MIMO_SHAPES:
            k = kinds[f"mimo_v2_flash_s{s}_b{b}"]
            assert (k["layers_full"], k["layers_window"]) == (2, 5)
            shares.append(k["quadratic_time_share"])
        # the two full cores: 8.5 % of an 8k x 4 step, 75 % of a 256k one
        assert shares == pytest.approx([0.0853, 0.2717, 0.4273, 0.7490],
                                       abs=1e-4)


def test_mimo_env_yaml_states_what_its_comments_derive(mimo):
    """env_mimo_32.yaml: the cut and the shapes as data, the arrival gap
    and horizon derived from the builder's graph, env_olmoe32's pads
    rule, and nothing else changed from env_glm5_32."""
    import math

    from ddls_tpu.config import load_config

    def env(name):
        return load_config(
            os.path.join(REPO, "scripts/ramp_job_partitioning_configs"),
            "rllib_config", [f"env_config={name}"])["env_config"]

    cfg, base = env("env_mimo_32"), env("env_glm5_32")
    jobs = cfg["jobs_config"]
    family = jobs["architecture"]
    assert family["config"] == MIMO_FILE
    assert {k: family[k] for k in MIMO_CUT} == MIMO_CUT
    shapes = family["shapes"]
    assert [(s["seq_len"], s["micro_batch"]) for s in shapes] == MIMO_SHAPES
    assert shapes[-1]["seq_len"] == mimo["max_position_embeddings"]
    steps = jobs["num_training_steps"]
    lengths = [steps * (1 + arch.BACKWARD_OVER_FORWARD) * sum(
        arch.forward_time(c) for c in arch.op_costs(mimo, **s, **MIMO_CUT))
        for s in shapes]
    assert lengths == pytest.approx([59.960, 75.306, 191.527, 1748.057],
                                    abs=1e-3)
    gap = np.mean(lengths) / 25
    two_figures = round(gap, 1 - int(math.floor(math.log10(gap))))
    assert jobs["job_interarrival_time_dist"]["val"] == two_figures == 21
    assert cfg["max_simulation_run_time"] == pytest.approx(400 * two_figures)
    assert cfg["pad_obs_kwargs"] == {"max_nodes": 50 * -(-114 // 50),
                                     "max_edges": 256 * -(-165 // 256)}
    # the shortest op at degree 1 is over 16 quanta: no ragged row
    shortest = min(arch.forward_time(c) for s in shapes
                   for c in arch.op_costs(mimo, **s, **MIMO_CUT))
    assert shortest == pytest.approx(268.4e-6, rel=1e-3)
    assert shortest > 16 * cfg["min_op_run_time_quantum"]
    # the rest is env_glm5_32's
    changed = {"jobs_config", "max_simulation_run_time", "pad_obs_kwargs"}
    assert {k: v for k, v in cfg.items() if k not in changed} \
        == {k: v for k, v in base.items() if k not in changed}
    for key in set(jobs) - {"architecture", "job_interarrival_time_dist"}:
        assert jobs[key] == base["jobs_config"][key], key


def _tiny_mimo_arch_file(directory) -> str:
    path = os.path.join(str(directory), "tinymimo.json")
    with open(path, "w") as fh:
        json.dump({"source_url": "test-local", "training_state": STATE,
                   "config": TINY_MIMO}, fh)
    return path


#: steps of ~0.2 s with every op memory-bound (the full core 4.5 % of
#: the pass), and of ~0.65 s at 16,384 tokens a sequence, where the one
#: full core is 83 % of the pass: a 0.18 s op beside 0.6 ms ones
TINY_MIMO_SHAPES = {
    "short": [{"seq_len": 32, "micro_batch": 2 ** 19},
              {"seq_len": 32, "micro_batch": 4096}],
    "long": [{"seq_len": 16384, "micro_batch": 512},
             {"seq_len": 32, "micro_batch": 2 ** 19}]}


def _tiny_mimo_env(arch_file, shapes="short", **over):
    jobs = dict(
        architecture={"config": arch_file,
                      "shapes": TINY_MIMO_SHAPES[shapes],
                      "layers": {"leading_dense": 1, "following": 2},
                      "experts_held": 4},
        job_interarrival_time_dist={
            "_target_": "ddls_tpu.demands.distributions.Fixed", "val": 0.4},
        max_acceptable_job_completion_time_frac_dist={
            "_target_": "ddls_tpu.demands.distributions.Uniform",
            "min_val": 0.1, "max_val": 1.0, "decimals": 2},
        replication_factor=10, job_sampling_mode="remove_and_repeat",
        shuffle_files=True, num_training_steps=20)
    over.setdefault("max_partitions_per_op", 8)
    return _tiny_env(arch_file, jobs_config=jobs,
                     max_simulation_run_time=16.0,
                     pad_obs_kwargs={"max_nodes": 50, "max_edges": 128},
                     **over)


@pytest.mark.parametrize("shapes,x64,rtol", [
    ("short", True, 1e-9), ("short", False, 1e-4), ("long", False, 1e-4)],
    ids=["short_x64_1e-9", "short_f32_1e-4", "long_f32_1e-4"])
def test_mimo_job_in_kernel_replays_the_host_oracle(tmp_path, shapes, x64,
                                                    rtol):
    """A tiny STATED mimo_v2_flash job family (layers F W W; D E E; 4 of
    8 experts) through reader -> mirror -> Job -> the jitted episode
    kernel against the float64 Python oracle: accepted and cause
    exactly, JCT to the tolerance; ``long`` puts 83 % of a step into the
    one full core (a 0.18 s op beside 0.6 ms ones)."""
    if shapes == "long":
        long = TINY_MIMO_SHAPES["long"][0]
        share = _kind_gauges(
            _tiny_mimo_arch_file(tmp_path),
            [(long["seq_len"], long["micro_batch"])],
            layers={"leading_dense": 1, "following": 2}, experts_held=4)
        assert share["tinymimo_s16384_b512"]["quadratic_time_share"] > 0.5
    driver = EPISODE_DRIVER.replace(
        "t._tiny_env({arch_file!r})",
        f"t._tiny_mimo_env({{arch_file!r}}, {shapes!r})").format(
        repo=REPO, tests=os.path.join(REPO, "tests"),
        benchmarks=os.path.join(REPO, "tests", "benchmarks"),
        arch_file=_tiny_mimo_arch_file(tmp_path), seed=5, x64=x64,
        rtol=rtol)
    out = subprocess.run(
        [sys.executable, "-c", driver], capture_output=True, text=True,
        timeout=900, env={**os.environ, "JAX_PLATFORMS": "cpu",
                          "JAX_ENABLE_X64": "1" if x64 else "0"})
    assert out.returncode == 0, out.stderr[-3000:]
    verdict = json.loads(out.stdout.strip().splitlines()[-1])
    assert verdict["mismatch"] is None, verdict
    assert verdict["decisions"] == 24
    assert 0 < verdict["accepted"] < 24, verdict
    assert len(verdict["causes"]) >= 2, verdict


def test_generator_sets_the_layer_kind_gauges(tmp_path):
    """`graphs.arch.layers_full` / `layers_window` /
    `quadratic_time_share` per model and the bank's mean share, beside
    the older `graphs.arch.*` gauges, in the `[startup]` line."""
    from ddls_tpu.demands.jobs_generator import JobsGenerator
    from ddls_tpu.telemetry import startup

    startup.registry().reset()
    JobsGenerator(
        architecture={"config": _tiny_mimo_arch_file(tmp_path),
                      "shapes": TINY_MIMO_SHAPES["long"],
                      "layers": {"leading_dense": 1, "following": 2},
                      "experts_held": 4},
        job_interarrival_time_dist={
            "_target_": "ddls_tpu.demands.distributions.Fixed", "val": 1.0},
        replication_factor=2, num_training_steps=20)
    gauges = startup.gauges()
    models = ("tinymimo_s16384_b512", "tinymimo_s32_b524288")
    for m in models:
        assert gauges[f"graphs.arch.layers_full.{m}"] == 1
        assert gauges[f"graphs.arch.layers_window.{m}"] == 2
        assert gauges[f"graphs.arch.forward_ops.{m}"] == 25
    shares = [gauges[f"graphs.arch.quadratic_time_share.{m}"]
              for m in models]
    assert shares == pytest.approx([0.8347, 0.0450], abs=1e-4)
    # the bank's mean as a ratio of two gauges, as the benchmark reads it
    assert [gauges[name] for name in BANK_GAUGES[:2]] == [sum(shares), 2]
    report = json.loads(startup.report()[len("[startup] "):])
    assert [report[name] for name in BANK_GAUGES[:2]] == [sum(shares), 2]
    startup.registry().reset()


def test_decisions_on_the_longest_job_type_are_counted():
    """`record_decisions` reduces the drained ``jtype`` / ``cause``
    traces into `env.decisions.offered_longest` / `accepted_longest`
    (the decisions on the type with the largest degree-1 step time) and
    `env.decisions.blocked_placement` (those that failed
    ``op_placement``) and `env.decisions.offered_ragged` /
    `accepted_ragged` (those whose chosen (type, degree) row has a
    forward op split fewer ways than the degree); a decision is
    accepted where its cause is ``CAUSE_ACCEPTED``."""
    import types

    from ddls_tpu import telemetry
    from ddls_tpu.rl.fused import record_decisions
    from ddls_tpu.sim import jax_env

    A, N, P, S = (jax_env.CAUSE_ACCEPTED, jax_env.CAUSE_NOT_HANDLED,
                  jax_env.CAUSE_OP_PLACEMENT, jax_env.CAUSE_SLA)
    # three job types x degrees (1, 2, 4): type 1's rows above degree 1
    # and type 2's top row are ragged
    et = types.SimpleNamespace(
        n_srv=32, max_action=4, degrees=[1, 2, 4],
        row_ragged=np.array([0, 0, 0, 0, 3, 3, 0, 0, 5]))
    ot = {"orig_seq_sum": np.array([3.0, 87.4, 9.6])}
    trace = {"jtype": np.array([[1, 0, 1], [2, 1, 0]]),
             "action": np.array([[2, 4, 4], [4, 1, 0]]),
             "cause": np.array([[A, A, P], [S, A, A]]),
             "n_occupied": np.array([[0, 4, 8], [8, 8, 12]])}
    was = telemetry.enabled()
    telemetry.enable()
    telemetry.reset()
    try:
        record_decisions(trace, et, ot)
        counters = dict(telemetry.snapshot()["counters"])
        telemetry.reset()
        record_decisions({**trace, "cause": np.array([[P, N, P],
                                                      [P, S, N]])}, et, ot)
        blocked = dict(telemetry.snapshot()["counters"])
    finally:
        telemetry.reset()
        if not was:
            telemetry.disable()
    assert counters["env.decisions.offered"] == 6
    assert counters["env.decisions.accepted"] == 4
    assert counters["env.decisions.blocked_placement"] == 1
    assert counters["env.decisions.offered_longest"] == 3
    assert counters["env.decisions.accepted_longest"] == 2
    # ragged rows chosen: (1, 2) accepted, (1, 4) placement-blocked,
    # (2, 4) over its limit; (1, 1) is even and action 0 chose no row
    assert counters["env.decisions.offered_ragged"] == 3
    assert counters["env.decisions.accepted_ragged"] == 1
    assert counters["env.cluster.servers"] == 6 * 32
    assert (blocked["env.decisions.accepted"],
            blocked["env.decisions.blocked_placement"]) == (0, 3)


# ================================================================= afmoe
TRINITY_FILE = "ddls_tpu/graphs/arch_configs/trinity_mini.json"
TRINITY_SHAPES = [(8192, 4), (32768, 1), (65536, 1), (131072, 1)]
#: 4 layers (attention S S S F by ``layer_types``; feed-forward D E E E by
#: ``num_dense_layers``), hidden 64, 4 q / 1 kv heads of 16, window 16,
#: 8 experts (2 a token) beside 1 shared, under afmoe's key names
TINY_TRINITY = {"model_type": "tinyafmoe", "hidden_size": 64,
                "num_attention_heads": 4, "num_key_value_heads": 1,
                "head_dim": 16, "sliding_window": 16,
                "layer_types": ["sliding_attention"] * 3
                + ["full_attention"],
                "global_attn_every_n_layers": 4, "num_dense_layers": 1,
                "intermediate_size": 128, "moe_intermediate_size": 32,
                "num_experts": 8, "num_shared_experts": 1,
                "num_experts_per_tok": 2, "num_hidden_layers": 4,
                "score_func": "sigmoid", "route_norm": True,
                "route_scale": 2.826, "vocab_size": 256}


@pytest.fixture(scope="module")
def trinity():
    return arch.load_arch_config(TRINITY_FILE)


def _tiny_trinity_arch_file(directory) -> str:
    path = os.path.join(str(directory), "tinyafmoe.json")
    with open(path, "w") as fh:
        json.dump({"source_url": "test-local", "training_state": STATE,
                   "config": TINY_TRINITY}, fh)
    return path


@pytest.mark.parametrize("case", ["tiny_s8", "tiny_s200", "trinity_8k",
                                  "trinity_128k"])
def test_trinity_op_costs_equal_the_plain_count_op_by_op(trinity, case):
    """`plain_counts_trinity` is written from the equations and imports
    nothing of the builder: op names, parameters, FLOPs, output
    elements, bytes moved and the edge set agree, below and above the
    window, at two tiny and two real shapes — nothing is cut."""
    from plain_arch_counts import plain_counts_trinity

    config, seq_len, micro_batch = {
        "tiny_s8": (TINY_TRINITY, 8, 3), "tiny_s200": (TINY_TRINITY, 200, 2),
        "trinity_8k": (trinity, 8192, 4),
        "trinity_128k": (trinity, 131072, 1)}[case]
    built = arch.build_graph(config, seq_len, micro_batch)
    plain, edges = plain_counts_trinity(config, seq_len, micro_batch)
    assert [o["op_type"] for o in built.ops] == [p[0] for p in plain]
    for i, (o, (kind, params, flops, out, nbytes)) in enumerate(
            zip(built.ops, plain)):
        assert o["params"] == params, (i, kind)
        assert o["flops"] == pytest.approx(flops, rel=1e-12), (i, kind)
        assert o["out_elems"] == out, (i, kind)
        assert o["bytes"] == pytest.approx(nbytes, rel=1e-12), (i, kind)
    assert set(built.edges) == edges and len(built.edges) == len(edges)


@pytest.mark.parametrize("quantity", ["parameters", "active_parameters"])
def test_trinity_whole_is_the_published_model(trinity, quantity):
    """All 32 layers, 128 experts and the whole vocabulary: 25.855 B
    parameters from the config's keys alone (published 26 B), 3.2 B
    active a token (published A3B)."""
    whole = arch.op_costs(trinity, 4096, 1)
    total = sum(o["params"] for o in whole)
    assert arch.resolve_cut(trinity) == {
        "leading_dense": 2, "following": 30, "experts_held": 128}
    if quantity == "parameters":
        # embedding, two 6-op dense layers, 30 9-op expert layers, norm,
        # head
        assert len(whole) == 1 + 2 * 6 + 30 * 9 + 2 == 285
        assert total == 25_855_399_680
        assert total == pytest.approx(25.855e9, rel=2e-5)
    else:
        idle = (trinity["num_experts"] - trinity["num_experts_per_tok"]) \
            * 3 * trinity["hidden_size"] * trinity["moe_intermediate_size"] \
            * 30
        assert total - idle == pytest.approx(3.2e9, rel=5e-3)


def test_layer_types_give_the_kinds_in_order_and_no_dangling_edge(
        trinity, tmp_path):
    """24 `WindowAttnCore` + 8 `AttnCore` in S S S F order, 2
    `DenseMLPResidual` then 30 x (`Router`, `SharedExpert`, `Experts`,
    `CombineResidual`); every op but the first has a producer and every
    op but the last a consumer; a stated graph of 586.0 GB."""
    family = arch.load_arch_file(TRINITY_FILE)
    assert family["training_state"] == STATE and "modeling" not in family
    path, = arch.write_profiles(
        str(tmp_path), trinity, [{"seq_len": 8192, "micro_batch": 4}],
        training_state=STATE)
    assert os.path.basename(path) == "afmoe_s8192_b4.txt"
    nodes, edges = _parse_pipedream_txt(path)
    kinds = [n["op_type"] for n in nodes.values()]

    def layer(core, feed_forward):
        return ["InputNorm", "QKVProj", core, "OutProjResidual",
                "PostAttnNorm", *feed_forward]

    expert = ["Router", "SharedExpert", "Experts", "CombineResidual"]
    cores = ["WindowAttnCore", "WindowAttnCore", "WindowAttnCore",
             "AttnCore"] * 8
    assert [k for k in kinds if k.endswith("AttnCore")] == cores
    assert kinds == (["Embedding"]
                     + sum((layer(core, ["DenseMLPResidual"] if i < 2
                                  else expert)
                            for i, core in enumerate(cores)), [])
                     + ["FinalNorm", "LMHeadLoss"])
    assert len(kinds) == 285 and len(edges) == 438
    ids = set(nodes)
    assert {v for _, v in edges} == ids - {"1"}
    assert {u for u, _ in edges} == ids - {"285"}
    graph = read_graph_file(path)
    assert (len(graph.forward_op_ids()), graph.n_ops, graph.n_deps) \
        == (285, 570, 877)
    assert sum(n["parameter"] for n in nodes.values()) \
        == pytest.approx(413.7e9, rel=1e-4)
    assert sum(graph.memory_cost(o) for o in graph.op_ids) \
        == pytest.approx(586.0e9, rel=1e-4)
    # no q/k norm, sink, value scale or partial rotary: none is stated
    proj = next(o for o in arch.op_costs(trinity, 8192, 4)
                if o["op_type"] == "QKVProj")
    T, H, width = 32768, 2048, 32 * 128 + 2 * 4 * 128
    assert proj["params"] == H * width
    assert proj["flops"] == 2 * T * H * width + 3 * T * 36 * 128


@pytest.mark.parametrize("seq_len", [2047, 2048, 2049, 131072])
def test_trinity_window_core_against_brute_force(trinity, seq_len):
    """A sliding layer's query reads min(t, 2048) keys, a full layer's
    all t: the closed form against the sum itself, at the window's edge
    and at the longest context."""
    w = trinity["sliding_window"]
    assert w == 2048
    keys = sum(min(t, w) for t in range(1, seq_len + 1))
    assert arch.attended_keys(seq_len, w) == keys
    costs = arch.op_costs(trinity, seq_len, 1)
    window = next(o for o in costs if o["op_type"] == "WindowAttnCore")
    full = next(o for o in costs if o["op_type"] == "AttnCore")
    per_pair = 32 * (4 * 128 + 5)
    assert window["flops"] == keys * per_pair and window["params"] == 0
    assert full["flops"] == seq_len * (seq_len + 1) // 2 * per_pair
    assert (window["flops"] < full["flops"]) == (seq_len > w)


@pytest.mark.parametrize("case", [
    "unknown_string", "every_n_mismatch", "dense_count", "too_deep",
    "prefix", "mtp"])
def test_afmoe_keys_are_checked_against_each_other(trinity, case):
    """`layer_types` is the per-layer list: an unknown string, a list
    `global_attn_every_n_layers` contradicts, and a cut that departs
    from the list or from `num_dense_layers` are refused."""
    if case == "unknown_string":
        types = ["sliding_attention", "linear_attention"] * 16
        with pytest.raises(ValueError, match="linear_attention"):
            arch.op_costs({**trinity, "layer_types": types}, 64, 1)
    elif case == "every_n_mismatch":
        for every in (3, 8):
            with pytest.raises(ValueError,
                               match="global_attn_every_n_layers"):
                arch.op_costs({**trinity,
                               "global_attn_every_n_layers": every}, 64, 1)
        # without the key the list stands alone
        config = {k: v for k, v in trinity.items()
                  if k != "global_attn_every_n_layers"}
        assert arch.op_costs(config, 64, 1) == arch.op_costs(trinity, 64, 1)
    elif case == "dense_count":
        for layers in ({"leading_dense": 0, "following": 8},
                       {"leading_dense": 3, "following": 5}):
            with pytest.raises(ValueError, match="layer_types"):
                arch.resolve_cut(trinity, layers)
    elif case == "too_deep":
        with pytest.raises(ValueError, match="layer_types"):
            arch.resolve_cut(trinity, {"leading_dense": 2, "following": 31})
    elif case == "prefix":
        # the stack's first layers, in the list's order: one whole period
        cut = {"leading_dense": 2, "following": 2}
        assert arch.resolve_cut(trinity, cut)["following"] == 2
        kinds = [o["op_type"]
                 for o in arch.op_costs(trinity, 64, 1, layers=cut)]
        assert [k for k in kinds if k.endswith("AttnCore")] == [
            "WindowAttnCore"] * 3 + ["AttnCore"]
        assert (kinds.count("DenseMLPResidual"), kinds.count("Experts")) \
            == (2, 2)
    else:
        with pytest.raises(ValueError, match="multi-token-prediction"):
            arch.op_costs({**trinity, "num_nextn_predict_layers": 1}, 64, 1)


@pytest.mark.parametrize("family", ["trinity", "olmoe", "olmoe_published",
                                    "mimo"])
def test_only_a_stated_half_square_drops_the_diagonal(trinity, olmoe, mimo,
                                                      family):
    """The one fork in `_gqa_attention` is keyed on what OLMoE's
    architecture file states (`modeling.causal_core_count`), not on the
    absence of `v_head_dim`: Trinity has one head size too and counts
    the diagonal, as MiMo does; OLMoE's published keys alone would."""
    S = 4096
    published = arch.load_arch_file(OLMOE_FILE)["config"]
    assert olmoe["causal_core_count"] == "half_square"
    assert "causal_core_count" not in published
    for config in (trinity, published):
        assert "v_head_dim" not in config
    config, heads, per_pair = {
        "trinity": (trinity, 32, 4 * 128 + 5),
        "olmoe": (olmoe, 16, 4 * 128 + 5),
        "olmoe_published": (published, 16, 4 * 128 + 5),
        "mimo": (mimo, 64, 2 * 192 + 2 * 128 + 5)}[family]
    core = next(o for o in arch.op_costs(config, S, 1)
                if o["op_type"] == "AttnCore")
    keys = S * S / 2 if family == "olmoe" else S * (S + 1) // 2
    assert core["flops"] == keys * heads * per_pair


def test_router_weight_flops_follow_route_norm_and_route_scale(trinity):
    """Under `score_func` the sigmoid router's per-selected-expert work
    is what the config states: 2 for `route_norm`, 1 for `route_scale`;
    `scoring_func` configs (GLM-5, MiMo: pinned) count all three."""
    T, H, E, k = 64, 2048, 128, 8
    base = 2 * T * H * E + 5 * T * E

    def router(config):
        return next(o for o in arch.op_costs(config, T, 1)
                    if o["op_type"] == "Router")

    assert router(trinity)["flops"] == base + 3 * T * k
    assert router(trinity)["params"] == H * E + E
    assert router({**trinity, "route_norm": False})["flops"] \
        == base + T * k
    assert router({**trinity, "route_scale": None})["flops"] \
        == base + 2 * T * k
    renamed = {k_: v for k_, v in trinity.items()
               if k_ not in ("score_func", "route_norm", "route_scale")}
    assert router({**renamed, "scoring_func": "sigmoid"})["flops"] \
        == base + 3 * T * k
    # neither name: the softmax router, no selection bias
    assert router(renamed)["params"] == H * E


TRINITY_SHA256 = {
    (8192, 4):
        "31cd86399c1e6a53f4099c6ece8596e3f027e10174e5e6bd24d795588a7cb8f0",
    (32768, 1):
        "d09d8fc5fd9cf136f4bcdb1070ba8745438b3ec3a82b7ccc8fc433257993156e",
    (65536, 1):
        "e4cf6a995a44a5ab0756d6db6c1c4375e6794bc8fb5a4da14414dcc4f5e45dad",
    (131072, 1):
        "5d15e437b33d882df9a99d771193362b292b7eee7c2e2fab2b10c599b089107a"}


@pytest.mark.parametrize("shape", TRINITY_SHAPES)
def test_trinity_profiles_are_pinned(shape):
    """The four profiles of `trinity_ramp32.train_fused`, byte for
    byte: a later change to a shared count shows here."""
    import hashlib

    family = arch.load_arch_file(TRINITY_FILE)
    text = arch.profile_text(arch.builder_config(family), *shape,
                             training_state=family["training_state"])
    assert hashlib.sha256(text.encode()).hexdigest() \
        == TRINITY_SHA256[shape]


def test_trinity_env_yaml_states_what_its_comments_derive(trinity):
    """env_trinity_32.yaml: NO cut, the shapes as data, the arrival gap
    and horizon derived from the builder's graph, env_olmoe32's pads
    rule, ragged rows in the two 32,768-token shapes, and nothing else
    changed from env_mimo_32."""
    import math

    from ddls_tpu.config import load_config

    def env(name):
        return load_config(
            os.path.join(REPO, "scripts/ramp_job_partitioning_configs"),
            "rllib_config", [f"env_config={name}"])["env_config"]

    cfg, base = env("env_trinity_32"), env("env_mimo_32")
    jobs = cfg["jobs_config"]
    family = jobs["architecture"]
    assert set(family) == {"config", "shapes"}      # whole: no cut keys
    assert family["config"] == TRINITY_FILE
    shapes = family["shapes"]
    assert [(s["seq_len"], s["micro_batch"]) for s in shapes] \
        == TRINITY_SHAPES
    assert shapes[-1]["seq_len"] == trinity["max_position_embeddings"]
    steps = jobs["num_training_steps"]
    lengths = [steps * (1 + arch.BACKWARD_OVER_FORWARD) * sum(
        arch.forward_time(c) for c in arch.op_costs(trinity, **s))
        for s in shapes]
    assert lengths == pytest.approx([105.435, 131.184, 328.342, 919.429],
                                    abs=1e-3)
    gap = np.mean(lengths) / 25
    two_figures = round(gap, 1 - int(math.floor(math.log10(gap))))
    assert jobs["job_interarrival_time_dist"]["val"] == two_figures == 15
    assert cfg["max_simulation_run_time"] == pytest.approx(400 * two_figures)
    assert cfg["pad_obs_kwargs"] == {"max_nodes": 50 * -(-570 // 50),
                                     "max_edges": 256 * -(-877 // 256)}
    # 13 quanta: the norms, routers and embedding of a 32,768-token step
    # split 14 ways at degree 16; every op of the longer shapes is over
    # 16 quanta
    quantum, top = cfg["min_op_run_time_quantum"], cfg[
        "max_partitions_per_op"]
    ragged = [sorted(o["op_type"] for o in arch.op_costs(trinity, **s)
                     if arch.forward_time(o) < top * quantum)
              for s in shapes]
    assert ragged[0] == ragged[1] == sorted(
        ["InputNorm"] * 32 + ["PostAttnNorm"] * 32 + ["Router"] * 30
        + ["Embedding", "FinalNorm"])
    assert ragged[2] == ragged[3] == []
    assert {int(arch.forward_time(o) / quantum)
            for o in arch.op_costs(trinity, **shapes[1])
            if arch.forward_time(o) < top * quantum} == {13}
    # a job's memory: parameter state + 2 x activations
    state = 16 * sum(o["params"] for o in arch.op_costs(trinity, 64, 1))
    jobs_gb = [(state + 2 * arch.ACT_BYTES * sum(
        o["out_elems"] for o in arch.op_costs(trinity, **s))) / 1e9
        for s in shapes]
    assert jobs_gb == pytest.approx([586.0, 586.0, 758.3, 1103.0], abs=0.05)
    # the rest is env_mimo_32's
    changed = {"jobs_config", "max_simulation_run_time", "pad_obs_kwargs"}
    assert {k: v for k, v in cfg.items() if k not in changed} \
        == {k: v for k, v in base.items() if k not in changed}
    for key in set(jobs) - {"architecture", "job_interarrival_time_dist"}:
        assert jobs[key] == base["jobs_config"][key], key


#: steps of ~0.32 s with every op over 8 quanta, and a shape whose ops
#: are 9-50 us: ragged rows at every degree above 1
TINY_TRINITY_SHAPES = [{"seq_len": 32, "micro_batch": 2 ** 19},
                       {"seq_len": 32, "micro_batch": 4096}]


def _tiny_trinity_env(arch_file, **over):
    """The whole tiny model (no cut) on env_trinity_32's cluster."""
    jobs = dict(
        architecture={"config": arch_file, "shapes": TINY_TRINITY_SHAPES},
        job_interarrival_time_dist={
            "_target_": "ddls_tpu.demands.distributions.Fixed", "val": 0.4},
        max_acceptable_job_completion_time_frac_dist={
            "_target_": "ddls_tpu.demands.distributions.Uniform",
            "min_val": 0.1, "max_val": 1.0, "decimals": 2},
        replication_factor=10, job_sampling_mode="remove_and_repeat",
        shuffle_files=True, num_training_steps=20)
    over.setdefault("max_partitions_per_op", 8)
    return _tiny_env(arch_file, jobs_config=jobs,
                     max_simulation_run_time=16.0,
                     pad_obs_kwargs={"max_nodes": 100, "max_edges": 192},
                     **over)


#: beside the verdict, the accepted decisions that ran a RAGGED row (the
#: 4,096-sequence shape above degree 1) while an earlier job still ran
TRINITY_DRIVER = EPISODE_DRIVER.replace(
    "t._tiny_env(", "t._tiny_trinity_env(").replace(
    'print(json.dumps({{', '''model = {{e["job_idx"]: e["model"] for e in events
         if e["kind"] == "job_arrived"}}
ends, ragged_loaded = [], 0
for e in host:
    if e["accepted"]:
        ragged_loaded += (model[e["job_idx"]].endswith("_b4096")
                          and e["degree"] > 1
                          and any(end > e["t"] for end in ends))
        ends.append(e["t"] + e["jct"])
print(json.dumps({{
    "ragged_loaded": int(ragged_loaded),''')


@pytest.mark.parametrize("x64,rtol", [(True, 1e-9), (False, 1e-4)],
                         ids=["x64_1e-9", "f32_1e-4"])
def test_trinity_job_in_kernel_replays_the_host_oracle(tmp_path, x64, rtol):
    """A tiny STATED afmoe job family, WHOLE (attention S S S F;
    feed-forward D E E E with a shared expert), through reader ->
    mirror -> Job -> the jitted episode kernel against the float64
    Python oracle: accepted and cause exactly, JCT to the tolerance,
    with a ragged row accepted on a cluster that holds a running job."""
    from ddls_tpu.sim.jax_env import build_partition_action

    arch_file = _tiny_trinity_arch_file(tmp_path)
    path = arch.write_profiles(str(tmp_path / "p"), TINY_TRINITY,
                               TINY_TRINITY_SHAPES[1:],
                               training_state=STATE)[0]
    splits = set(build_partition_action(read_graph_file(path), QUANTUM,
                                        8).values())
    assert len(splits) > 1 and max(splits) < 8, splits      # ragged
    driver = TRINITY_DRIVER.format(
        repo=REPO, tests=os.path.join(REPO, "tests"),
        benchmarks=os.path.join(REPO, "tests", "benchmarks"),
        arch_file=arch_file, seed=5, x64=x64, rtol=rtol)
    out = subprocess.run(
        [sys.executable, "-c", driver], capture_output=True, text=True,
        timeout=900, env={**os.environ, "JAX_PLATFORMS": "cpu",
                          "JAX_ENABLE_X64": "1" if x64 else "0"})
    assert out.returncode == 0, out.stderr[-3000:]
    verdict = json.loads(out.stdout.strip().splitlines()[-1])
    assert verdict["mismatch"] is None, verdict
    assert verdict["decisions"] == 24
    assert 0 < verdict["accepted"] < 24, verdict
    assert verdict["ragged_loaded"] >= 1, verdict
    assert len(verdict["causes"]) >= 2, verdict


def test_generator_and_tables_set_the_trinity_gauges(tmp_path):
    """`graphs.arch.shared_expert_layers.<model>` from the profile's op
    names (`jobs_generator`), and `graphs.arch.ragged_ops.<model>` from
    the top-degree row's splits (`sim/jax_env.py:ragged_forward_ops`,
    set by `train/loops.py:_device_tables` beside them)."""
    from ddls_tpu.sim.jax_env import (build_episode_tables,
                                      ragged_forward_ops)
    from ddls_tpu.telemetry import startup

    startup.registry().reset()
    env = _tiny_trinity_env(_tiny_trinity_arch_file(tmp_path))
    env.reset(seed=0)
    gauges = startup.gauges()
    models = ("tinyafmoe_s32_b4096", "tinyafmoe_s32_b524288")
    for m in models:
        assert gauges[f"graphs.arch.shared_expert_layers.{m}"] == 3
        assert gauges[f"graphs.arch.layers_full.{m}"] == 1
        assert gauges[f"graphs.arch.layers_window.{m}"] == 3
        assert gauges[f"graphs.arch.forward_ops.{m}"] == 36
    et = build_episode_tables(env)
    # every op of the short shape is under 8 quanta, none of the long
    assert ragged_forward_ops(et) == {models[0]: 36, models[1]: 0}
    startup.registry().reset()
    # GLM-5 has a shared expert a layer too, MiMo none
    glm = _kind_gauges(GLM_FILE, [(8192, 1)], **GLM_CUT)
    assert glm["glm_moe_dsa_s8192_b1"]["shared_expert_layers"] == 4 + 1
    mimo = _kind_gauges(MIMO_FILE, MIMO_SHAPES[:1], **MIMO_CUT)
    assert mimo["mimo_v2_flash_s8192_b4"]["shared_expert_layers"] == 0


def test_stacked_tables_are_built_once_a_workload(tmp_path, monkeypatch):
    """`build_episode_tables` keeps the last workload's stacked tables:
    a second env of the same job source, degrees, quantum and topology
    (the fidelity replay's, after the training loop's) reuses them, and
    another quantum or another job source builds anew."""
    from ddls_tpu.sim import jax_env

    arch_file = _tiny_trinity_arch_file(tmp_path)
    calls = []
    real = jax_env.config_tables_for
    monkeypatch.setattr(
        jax_env, "config_tables_for",
        lambda graph, degree, quantum: calls.append(degree)
        or real(graph, degree, quantum))

    def tables(**over):
        env = _tiny_trinity_env(arch_file, **over)
        env.reset(seed=0)
        return jax_env.build_episode_tables(env)

    jax_env._STACKED_TABLES.clear()
    first = tables()
    rows = len(first.types) * len(first.degrees)
    assert len(calls) == rows == 2 * 5
    again = tables()
    assert len(calls) == rows                       # nothing rebuilt
    assert again.pads == first.pads
    for name, value in first.tables.items():
        assert np.array_equal(np.asarray(value),
                              np.asarray(again.tables[name])), name
    tables(min_op_run_time_quantum=2 * QUANTUM)     # another quantum
    assert len(calls) == 2 * rows
    tables(max_partitions_per_op=4)                 # other degrees
    assert len(calls) == 2 * rows + 2 * 3
    env = _tiny_env(_tiny_arch_file(tmp_path))      # another job source
    env.reset(seed=0)
    other = jax_env.build_episode_tables(env)
    assert other.types != first.types
    assert len(calls) == 2 * rows + 2 * 3 + 2 * 9


# ========================================================== minicpm_sala
SALA_FILE = "ddls_tpu/graphs/arch_configs/minicpm_sala.json"
SALA_SHAPES = [(4096, 1), (8192, 4), (32768, 1), (131072, 1)]
#: 4 layers (mixers A L L A by ``mixer_types``), hidden 64, DENSE (no
#: expert key): softmax GQA at 4 q / 1 kv heads of 16 without RoPE, full
#: up to 64 tokens and block-sparse beyond; Lightning at 4 / 4 heads of
#: 16 in chunks of 16; every key MiniCPM-SALA's config states, under its
#: own name; the sizes the config leaves unsaid in a ``modeling`` block
TINY_SALA = {"model_type": "tinysala", "hidden_size": 64,
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
             "scale_emb": 12, "scale_depth": 1.4, "dim_model_base": 32,
             "tie_word_embeddings": False}
TINY_SALA_MODELING = {
    "sparse_config": {"kernel_size": 8, "kernel_stride": 4, "block_size": 8,
                      "topk": 2, "window_size": 16, "init_blocks": 1,
                      "dense_len": 64},
    "lightning_chunk_size": 16}
TINY_SALA_BUILT = {**TINY_SALA, **TINY_SALA_MODELING}


@pytest.fixture(scope="module")
def sala():
    return arch.load_arch_config(SALA_FILE)


def _tiny_sala_arch_file(directory) -> str:
    path = os.path.join(str(directory), "tinysala.json")
    with open(path, "w") as fh:
        json.dump({"source_url": "test-local", "training_state": STATE,
                   "modeling": TINY_SALA_MODELING, "config": TINY_SALA}, fh)
    return path


@pytest.mark.parametrize("case", ["tiny_s8", "tiny_s64", "tiny_s203",
                                  "sala_4k", "sala_8k", "sala_32k",
                                  "sala_128k"])
def test_sala_op_costs_equal_the_plain_count_op_by_op(sala, case):
    """`plain_counts_sala` is written from ISSUE 39's equations and
    imports nothing of the builder: op names, parameters, FLOPs, output
    elements, bytes moved and the edge set agree on both sides of
    ``dense_len`` (two graphs of one model), with whole and partial
    chunks, at three tiny shapes and the cell's four — nothing is cut."""
    from plain_arch_counts import plain_counts_sala

    config, seq_len, micro_batch = {
        "tiny_s8": (TINY_SALA_BUILT, 8, 3),
        "tiny_s64": (TINY_SALA_BUILT, 64, 2),
        "tiny_s203": (TINY_SALA_BUILT, 203, 2),
        "sala_4k": (sala, 4096, 1), "sala_8k": (sala, 8192, 4),
        "sala_32k": (sala, 32768, 1),
        "sala_128k": (sala, 131072, 1)}[case]
    built = arch.build_graph(config, seq_len, micro_batch)
    plain, edges = plain_counts_sala(config, seq_len, micro_batch)
    assert [o["op_type"] for o in built.ops] == [p[0] for p in plain]
    for i, (o, (kind, params, flops, out, nbytes)) in enumerate(
            zip(built.ops, plain)):
        assert o["params"] == params, (i, kind)
        assert o["flops"] == pytest.approx(flops, rel=1e-12), (i, kind)
        assert o["out_elems"] == out, (i, kind)
        assert o["bytes"] == pytest.approx(nbytes, rel=1e-12), (i, kind)
    assert set(built.edges) == edges and len(built.edges) == len(edges)
    sparse = seq_len > config["sparse_config"]["dense_len"]
    layers = len(config["mixer_types"])
    softmax = config["mixer_types"].count("minicpm4")
    assert len(built.ops) == 1 + 7 * layers + 2 * softmax * sparse + 2


@pytest.mark.parametrize("seq_len,sparse", [(8192, False), (8193, True)])
def test_sala_graph_switches_at_dense_len(sala, tmp_path, seq_len, sparse):
    """Up to ``sparse_config.dense_len`` tokens a `minicpm4` layer runs
    the full causal core every other family counts; one token more and
    it is `KCompress`, `BlockScoreTopK` and `BlockSparseAttnCore`: 227
    forward ops and 322 edges, or 243 and 354. Every op but the first
    has a producer and every op but the last a consumer."""
    family = arch.load_arch_file(SALA_FILE)
    assert family["modeling"]["sparse_config"]["dense_len"] == 8192
    path, = arch.write_profiles(
        str(tmp_path), sala, [{"seq_len": seq_len, "micro_batch": 1}],
        training_state=family["training_state"])
    assert os.path.basename(path) == f"minicpm_sala_s{seq_len}_b1.txt"
    nodes, edges = _parse_pipedream_txt(path)
    kinds = [n["op_type"] for n in nodes.values()]
    core = ["KCompress", "BlockScoreTopK", "BlockSparseAttnCore"] \
        if sparse else ["AttnCore"]

    def layer(mixer):
        return ["InputNorm", "QKVProj", "GateProj",
                *(["LinearAttnCore"] if mixer == "lightning-attn"
                  else core),
                "OutProjResidual", "PostAttnNorm", "DenseMLPResidual"]

    assert kinds == (["Embedding"]
                     + sum((layer(m) for m in sala["mixer_types"]), [])
                     + ["FinalNorm", "LMHeadLoss"])
    assert kinds.count("LinearAttnCore") == 24
    assert (len(kinds), len(edges)) == ((243, 354) if sparse
                                        else (227, 322))
    ids = set(nodes)
    assert {v for _, v in edges} == ids - {"1"}
    assert {u for u, _ in edges} == ids - {str(len(kinds))}
    graph = read_graph_file(path)
    assert (len(graph.forward_op_ids()), graph.n_ops, graph.n_deps) \
        == ((243, 486, 709) if sparse else (227, 454, 645))
    # the quadratic ops of either graph: 8 cores or 8 block scores
    assert sum(k in arch.QUADRATIC_OPS for k in kinds) == 8
    # a sparse query reads 64 blocks of 64, the window and a first block
    if sparse:
        per_pair = 32 * (4 * 128 + 5)
        sparse_core = next(o for o in arch.op_costs(sala, seq_len, 1)
                           if o["op_type"] == "BlockSparseAttnCore")
        assert sparse_core["flops"] == per_pair * sum(
            min(t, 64 * 64 + 2048 + 64) for t in range(1, seq_len + 1))


@pytest.mark.parametrize("chunk", [64, 256])
def test_linear_core_is_exactly_linear_in_the_sequence(sala, chunk):
    """`LinearAttnCore` holds no parameter and its FLOPs and bytes are
    exactly linear in S at a fixed chunk (whole chunks), where a full
    core's grow as S (S + 1) / 2; the chunk is a shape of the scan: a
    larger one pays more intra-chunk pairs a token."""
    config = {**sala, "lightning_chunk_size": chunk}

    def core(seq_len, kind="LinearAttnCore", cfg=config):
        return next(o for o in arch.op_costs(cfg, seq_len, 1)
                    if o["op_type"] == kind)

    base = core(1024)
    assert base["params"] == 0
    for times in (2, 8, 128):
        longer = core(1024 * times)
        assert longer["flops"] == times * base["flops"]
        assert longer["bytes"] == times * base["bytes"]
        assert longer["out_elems"] == times * base["out_elems"]
    # per token and head: (C + 1) / 2 pairs of 4 d + 1, 4 d^2 + 4 d, and
    # the state's 2 d^2 a chunk
    n, d = 32, 128
    assert base["flops"] == 1024 * n * (
        (chunk + 1) * (4 * d + 1) / 2 + 4 * d * d + 4 * d
        + 2 * d * d / chunk)
    assert core(4096, cfg={**sala, "lightning_chunk_size": 2 * chunk})[
        "flops"] > core(4096)["flops"]
    full = core(8192, "AttnCore")["flops"] / core(4096, "AttnCore")["flops"]
    assert full == pytest.approx(4.0, rel=1e-3)


def test_sala_whole_is_the_published_model(sala):
    """All 32 layers and the whole untied vocabulary: 9.477 B
    parameters from the config's keys (published 9B), of which the q/k
    and output norms' weights are 0.26 M; no expert, so the cut is 32
    dense layers and nothing follows."""
    assert arch.resolve_cut(sala) == {
        "leading_dense": 32, "following": 0, "experts_held": 0}
    whole = arch.op_costs(sala, 4096, 1)
    total = sum(o["params"] for o in whole)
    H, I, V, nd = 4096, 16384, 73448, 32 * 128
    matrices = 2 * V * H + 32 * 3 * H * I \
        + 24 * (H * 3 * nd + 2 * H * nd) \
        + 8 * (H * (nd + 2 * 2 * 128) + 2 * H * nd)
    norms = (2 * 32 + 1) * H + 24 * (2 * nd + nd) + 8 * (nd + 2 * 128)
    assert total == matrices + norms == 9_477_429_248
    assert matrices == pytest.approx(9.477e9, rel=1e-4) and norms < 1e6
    # the training state of a job, and with 2 x activations the job
    assert 16 * total == pytest.approx(151.6e9, rel=1e-3)


@pytest.mark.parametrize("case", [
    "experts_held", "following", "unknown_mixer", "too_deep", "prefix",
    "mtp", "old_families_untouched"])
def test_dense_and_mixer_keys_are_checked(sala, trinity, case):
    """A config with no expert key is dense throughout: stating
    `experts_held` or a layer to follow the dense ones is refused;
    `mixer_types` is the per-layer list: an unknown string and a cut
    deeper than the list are refused."""
    if case == "experts_held":
        with pytest.raises(ValueError, match="experts_held"):
            arch.resolve_cut(sala, experts_held=8)
        with pytest.raises(ValueError, match="experts_held"):
            arch.op_costs(sala, 64, 1, experts_held=1)
        assert arch.resolve_cut(trinity, experts_held=8)[
            "experts_held"] == 8
    elif case == "following":
        with pytest.raises(ValueError, match="no expert key"):
            arch.resolve_cut(sala, {"leading_dense": 2, "following": 2})
    elif case == "unknown_mixer":
        types = ["minicpm4", "mamba2"] * 16
        with pytest.raises(ValueError, match="mamba2"):
            arch.op_costs({**sala, "mixer_types": types}, 64, 1)
    elif case == "too_deep":
        with pytest.raises(ValueError, match="mixer_types"):
            arch.resolve_cut(sala, {"leading_dense": 33, "following": 0})
    elif case == "prefix":
        # the stack's first layers, in the list's order
        kinds = [o["op_type"] for o in arch.op_costs(
            sala, 64, 1, layers={"leading_dense": 10, "following": 0})]
        assert [k for k in kinds if k.endswith("AttnCore")] == \
            ["AttnCore"] + ["LinearAttnCore"] * 8 + ["AttnCore"]
        assert kinds.count("DenseMLPResidual") == 10
    elif case == "mtp":
        with pytest.raises(ValueError, match="multi-token-prediction"):
            arch.op_costs({**sala, "num_nextn_predict_layers": 1}, 64, 1)
    else:
        # no old family has a gate, a mixer list, a sparse_config or a
        # muP scalar: none of the new ops is in its graph
        kinds = {o["op_type"] for o in arch.op_costs(trinity, 16384, 1)}
        assert not kinds & {"GateProj", "LinearAttnCore", "KCompress",
                            "BlockScoreTopK", "BlockSparseAttnCore"}
        assert arch.linear_layers(trinity) is None


@pytest.mark.parametrize("key,op,delta", [
    ("use_output_gate", "GateProj", None),
    ("attn_use_output_gate", "GateProj", None),
    ("use_output_norm", "OutProjResidual", "lightning_norm"),
    ("qk_norm", "QKVProj", "qk_norm"),
    ("attn_use_rope", "QKVProj", "attn_rope"),
    ("lightning_use_rope", "QKVProj", "lightning_rope"),
    ("scale_emb", "Embedding", "T*H"),
    ("scale_depth", "DenseMLPResidual", "T*H"),
    ("dim_model_base", "LMHeadLoss", "T*H")])
def test_each_part_is_counted_where_a_key_states_it(sala, key, op, delta):
    """An output gate, an output norm, q/k norms, RoPE and the muP
    scalars are counted where MiniCPM-SALA's keys state them and not
    where a key says no: the prefixed key speaks for its mixer, the
    unprefixed `use_output_*` for the Lightning mixer."""
    T, H, nd = 64, 4096, 32 * 128
    flipped = {**sala, key: not sala[key]} \
        if isinstance(sala[key], bool) \
        else {k: v for k, v in sala.items() if k != key}

    def ops(config):
        return arch.op_costs(config, T, 1)

    def total(config, field="flops"):
        return sum(o[field] for o in ops(config) if o["op_type"] == op)

    stated, other = ops(sala), ops(flipped)
    if delta is None:
        # the mixer's gate key: its layers lose GateProj, the gate's 3
        # an element of o and the edge; the other mixer keeps its gate
        gated = {"use_output_gate": 24, "attn_use_output_gate": 8}[key]
        assert sum(o["op_type"] == "GateProj" for o in stated) == 32
        assert sum(o["op_type"] == "GateProj" for o in other) == 32 - gated
        assert sum(o["flops"] for o in stated) \
            - sum(o["flops"] for o in other) \
            == gated * (2 * T * H * nd + 3 * T * nd)
        assert sum(o["params"] for o in stated) \
            - sum(o["params"] for o in other) == gated * H * nd
    elif delta == "lightning_norm":
        assert total(sala) - total(flipped) == 24 * 4 * T * nd
        assert total(sala, "params") - total(flipped, "params") == 24 * nd
    elif delta == "qk_norm":
        width = 24 * 2 * nd + 8 * (nd + 2 * 128)
        assert total(sala) - total(flipped) == 4 * T * width
        assert total(sala, "params") - total(flipped, "params") == width
    elif delta == "attn_rope":
        # stated false: turning it on adds RoPE to the 8 softmax layers
        assert sala[key] is False
        assert total(flipped) - total(sala) == 8 * 3 * T * (32 + 2) * 128
    elif delta == "lightning_rope":
        assert total(sala) - total(flipped) == 24 * 3 * T * 64 * 128
    else:
        count = {"Embedding": 1, "DenseMLPResidual": 32, "LMHeadLoss": 1}[op]
        assert total(sala) - total(flipped) == count * T * H
        if key == "scale_depth":
            # the attention branch's too
            assert sum(o["flops"] for o in stated) \
                - sum(o["flops"] for o in other) == 2 * 32 * T * H


SALA_SHA256 = {
    (4096, 1):
        "a88ead2af4d63170bdcc4d0f8d4a13f68aa886a495622fab0e0dd14f0428bb70",
    (8192, 4):
        "61371ad7db96903573e122f432749f01a2f3ded8e42bff7a03984b71f6fe8ffd",
    (32768, 1):
        "c9fd769c42aec16eb94c29f4a09f48defeca29cd9c67862d165768aa91470b38",
    (131072, 1):
        "0409af227c8303ff4aa16d322df817c9a63306bceee0029864138cfdf0c0ab2e"}


@pytest.mark.parametrize("shape", SALA_SHAPES)
def test_sala_profiles_are_pinned(shape):
    """The four profiles of `sala_ramp32.train_fused`, byte for byte: a
    later change to a shared count shows here."""
    import hashlib

    family = arch.load_arch_file(SALA_FILE)
    text = arch.profile_text(arch.builder_config(family), *shape,
                             training_state=family["training_state"])
    assert hashlib.sha256(text.encode()).hexdigest() == SALA_SHA256[shape]


def test_sala_env_yaml_states_what_its_comments_derive(sala):
    """env_sala_32.yaml: NO cut, the shapes as data, the arrival gap and
    horizon derived from the builder's graph, env_olmoe32's pads rule
    over the LARGER of the two graphs, which ops make which rows
    ragged, and nothing else changed from env_trinity_32."""
    import math

    from ddls_tpu.agents.partitioners import sip_ml_num_partitions
    from ddls_tpu.config import load_config

    def env(name):
        return load_config(
            os.path.join(REPO, "scripts/ramp_job_partitioning_configs"),
            "rllib_config", [f"env_config={name}"])["env_config"]

    cfg, base = env("env_sala_32"), env("env_trinity_32")
    jobs = cfg["jobs_config"]
    family = jobs["architecture"]
    assert set(family) == {"config", "shapes"}      # whole: no cut keys
    assert family["config"] == SALA_FILE
    shapes = family["shapes"]
    assert [(s["seq_len"], s["micro_batch"]) for s in shapes] == SALA_SHAPES
    # the two longest contexts the model declares fit no 16 servers
    assert sala["max_position_embeddings"] == 524288
    steps = jobs["num_training_steps"]
    costs = [arch.op_costs(sala, **s) for s in shapes]
    lengths = [steps * (1 + arch.BACKWARD_OVER_FORWARD)
               * sum(arch.forward_time(c) for c in ops) for ops in costs]
    assert lengths == pytest.approx([35.541, 288.427, 292.514, 1186.004],
                                    abs=1e-3)
    # 4 x the tokens cost 4.05 x the time: nothing S^2 is over 3 %
    assert lengths[3] / lengths[2] == pytest.approx(4.055, abs=1e-3)
    gap = np.mean(lengths) / 25
    two_figures = round(gap, 1 - int(math.floor(math.log10(gap))))
    assert jobs["job_interarrival_time_dist"]["val"] == two_figures == 18
    assert cfg["max_simulation_run_time"] == pytest.approx(400 * two_figures)
    # two graph sizes in one bank: the pads hold the larger
    assert [len(ops) for ops in costs] == [227, 227, 243, 243]
    assert cfg["pad_obs_kwargs"] == {"max_nodes": 50 * -(-486 // 50),
                                     "max_edges": 256 * -(-709 // 256)}
    # ragged rows: the ops the SiP-ML rule splits fewer ways than 16
    quantum, top = cfg["min_op_run_time_quantum"], cfg[
        "max_partitions_per_op"]
    ragged = [sorted((o["op_type"], sip_ml_num_partitions(
        arch.forward_time(o), quantum, top)) for o in ops
        if sip_ml_num_partitions(arch.forward_time(o), quantum, top) < top)
        for ops in costs]
    assert ragged[0] == sorted(
        [("InputNorm", 4)] * 32 + [("PostAttnNorm", 4)] * 32
        + [("Embedding", 4), ("FinalNorm", 4)]
        + [("LinearAttnCore", 14)] * 24)
    assert ragged[1] == []
    assert ragged[2] == [("KCompress", 2)] * 8
    assert ragged[3] == [("KCompress", 4)] * 8
    # a job's memory: parameter state + 2 x activations
    state = 16 * sum(o["params"] for o in costs[0])
    jobs_gb = [(state + 2 * arch.ACT_BYTES
                * sum(o["out_elems"] for o in ops)) / 1e9 for ops in costs]
    assert jobs_gb == pytest.approx([171.3, 308.9, 309.1, 781.3], abs=0.05)
    # the rest is env_trinity_32's
    changed = {"jobs_config", "max_simulation_run_time", "pad_obs_kwargs"}
    assert {k: v for k, v in cfg.items() if k not in changed} \
        == {k: v for k, v in base.items() if k not in changed}
    for key in set(jobs) - {"architecture", "job_interarrival_time_dist"}:
        assert jobs[key] == base["jobs_config"][key], key


#: a dense-path shape (S <= the tiny dense_len of 64: 31 forward ops)
#: whose ops are 17-50 us — ragged rows at degrees 4, 6 and 8 — and a
#: sparse-path shape (35 forward ops) with steps of 0.33 s and every op
#: over 8 quanta: two graph sizes in one bank
TINY_SALA_SHAPES = [{"seq_len": 32, "micro_batch": 4096},
                    {"seq_len": 128, "micro_batch": 2 ** 17}]


def _tiny_sala_env(arch_file, **over):
    """The whole tiny model (no cut) on env_sala_32's cluster."""
    jobs = dict(
        architecture={"config": arch_file, "shapes": TINY_SALA_SHAPES},
        job_interarrival_time_dist={
            "_target_": "ddls_tpu.demands.distributions.Fixed", "val": 0.4},
        max_acceptable_job_completion_time_frac_dist={
            "_target_": "ddls_tpu.demands.distributions.Uniform",
            "min_val": 0.1, "max_val": 1.0, "decimals": 2},
        replication_factor=10, job_sampling_mode="remove_and_repeat",
        shuffle_files=True, num_training_steps=20)
    over.setdefault("max_partitions_per_op", 8)
    return _tiny_env(arch_file, jobs_config=jobs,
                     max_simulation_run_time=16.0,
                     pad_obs_kwargs={"max_nodes": 100, "max_edges": 192},
                     **over)


#: beside the verdict, the accepted decisions that ran a RAGGED row (the
#: 4,096-sequence shape above degree 2) while an earlier job still ran
SALA_DRIVER = TRINITY_DRIVER.replace(
    "t._tiny_trinity_env(", "t._tiny_sala_env(").replace(
    'and e["degree"] > 1', 'and e["degree"] > 2')


def test_tiny_sala_runs_the_host_env_with_a_ragged_row_mounted(tmp_path):
    """A tiny STATED dense family, WHOLE (mixers A L L A), through
    reader -> mirror -> `Job` -> the host env: one bank holds a 62-op
    and a 70-op graph of ONE model, and a ragged row is accepted on a
    cluster that holds a running job. `record_decisions` counts it from
    the decisions' (job type, action, cause) alone:
    `env.decisions.accepted_ragged` is 1 for that decision, and over
    the episode what the partitioner's own splits say."""
    from ddls_tpu import telemetry
    from ddls_tpu.rl.fused import record_decisions
    from ddls_tpu.scenarios.conformance import (decision_events,
                                                run_recorded_episode)
    from ddls_tpu.sim.jax_env import (CAUSE_ACCEPTED, CAUSE_STR_TO_CODE,
                                      build_episode_tables,
                                      build_partition_action)

    env = _tiny_sala_env(_tiny_sala_arch_file(tmp_path))
    events, actions = run_recorded_episode(env, 7, max_decisions=24)
    graphs = {p.details["model"]: p.graph
              for p in env.cluster.jobs_generator.sampler.prototypes}
    assert {m: (g.n_ops, g.n_deps) for m, g in graphs.items()} == {
        "tinysala_s32_b4096": (62, 85), "tinysala_s128_b131072": (70, 101)}
    kinds = {m: set(g.meta["op_types"].values()) for m, g in graphs.items()}
    assert "AttnCore" in kinds["tinysala_s32_b4096"]
    assert {"KCompress", "BlockScoreTopK", "BlockSparseAttnCore"} \
        <= kinds["tinysala_s128_b131072"]
    for kind in kinds.values():
        assert {"LinearAttnCore", "GateProj", "DenseMLPResidual"} <= kind
        assert not kind & {"Router", "Experts"}
    model = {e["job_idx"]: e["model"] for e in events
             if e["kind"] == "job_arrived"}
    host = decision_events(events)
    assert len(host) == len(actions) == 24

    def ragged(e):
        splits = set(build_partition_action(
            graphs[model[e["job_idx"]]], QUANTUM, e["degree"]).values())
        return e["degree"] > 0 and len(splits) > 1

    ends, mounted_ragged = [], []
    for i, e in enumerate(host):
        if e["accepted"]:
            if ragged(e) and any(end > e["t"] for end in ends):
                mounted_ragged.append(i)
            ends.append(e["t"] + e["jct"])
    assert mounted_ragged, [(model[e["job_idx"]], e["degree"], e["accepted"])
                           for e in host]

    et = build_episode_tables(env)
    ot = {"orig_seq_sum": np.array(
        [float(graphs[m].finalize()["compute"].sum()) for m in et.types])}

    def counted(indices):
        trace = {
            "jtype": np.array([et.types.index(model[host[i]["job_idx"]])
                               for i in indices]),
            "action": np.array([host[i]["degree"] for i in indices]),
            "cause": np.array([CAUSE_ACCEPTED if host[i]["accepted"]
                               else CAUSE_STR_TO_CODE[host[i]["cause"]]
                               for i in indices]),
            "n_occupied": np.zeros(len(indices), np.int64)}
        was = telemetry.enabled()
        telemetry.enable()
        telemetry.reset()
        try:
            record_decisions(trace, et, ot)
            return dict(telemetry.snapshot()["counters"])
        finally:
            telemetry.reset()
            if not was:
                telemetry.disable()

    one = counted(mounted_ragged[:1])
    assert (one["env.decisions.offered_ragged"],
            one["env.decisions.accepted_ragged"]) == (1, 1)
    whole = counted(range(len(host)))
    assert whole["env.decisions.offered"] == 24
    assert whole["env.decisions.offered_ragged"] \
        == sum(ragged(e) for e in host)
    assert whole["env.decisions.accepted_ragged"] \
        == sum(ragged(e) and e["accepted"] for e in host) >= 1
    assert whole["env.decisions.accepted"] \
        == sum(e["accepted"] for e in host)


@pytest.mark.parametrize("x64,rtol", [(True, 1e-9), (False, 1e-4)],
                         ids=["x64_1e-9", "f32_1e-4"])
def test_sala_job_in_kernel_replays_the_host_oracle(tmp_path, x64, rtol):
    """The same tiny family through the jitted episode kernel against
    the float64 Python oracle — two graph sizes of one model under one
    pad class: accepted and cause exactly, JCT to the tolerance, with a
    ragged row accepted on a cluster that holds a running job."""
    driver = SALA_DRIVER.format(
        repo=REPO, tests=os.path.join(REPO, "tests"),
        benchmarks=os.path.join(REPO, "tests", "benchmarks"),
        arch_file=_tiny_sala_arch_file(tmp_path), seed=7, x64=x64,
        rtol=rtol)
    out = subprocess.run(
        [sys.executable, "-c", driver], capture_output=True, text=True,
        timeout=900, env={**os.environ, "JAX_PLATFORMS": "cpu",
                          "JAX_ENABLE_X64": "1" if x64 else "0"})
    assert out.returncode == 0, out.stderr[-3000:]
    verdict = json.loads(out.stdout.strip().splitlines()[-1])
    assert verdict["mismatch"] is None, verdict
    assert verdict["decisions"] == 24
    assert 0 < verdict["accepted"] < 24, verdict
    assert verdict["ragged_loaded"] >= 1, verdict
    assert len(verdict["causes"]) >= 2, verdict


def test_generator_and_tables_set_the_sala_gauges(tmp_path):
    """`graphs.arch.layers_linear` / `layers_block_sparse` /
    `linear_time_share.<model>` from the profile's op names and times,
    and `forward_ops` / `edges` / `ragged_ops` per SHAPE where one
    model builds two graphs; the old families read 0 linear layers."""
    from ddls_tpu.sim.jax_env import (build_episode_tables,
                                      ragged_forward_ops)
    from ddls_tpu.telemetry import startup

    startup.registry().reset()
    env = _tiny_sala_env(_tiny_sala_arch_file(tmp_path))
    env.reset(seed=0)
    gauges = startup.gauges()
    short, long = "tinysala_s32_b4096", "tinysala_s128_b131072"
    for m, forward, edges, sparse in ((short, 31, 85, 0), (long, 35, 101, 2)):
        assert gauges[f"graphs.arch.forward_ops.{m}"] == forward
        assert gauges[f"graphs.arch.edges.{m}"] == edges
        assert gauges[f"graphs.arch.layers_linear.{m}"] == 2
        assert gauges[f"graphs.arch.layers_block_sparse.{m}"] == sparse
        assert gauges[f"graphs.arch.layers_full.{m}"] == 2 - sparse
        assert gauges[f"graphs.arch.layers_window.{m}"] == 0
        assert gauges[f"graphs.arch.shared_expert_layers.{m}"] == 0
        assert 0 < gauges[f"graphs.arch.linear_time_share.{m}"] < 0.2
        # the quadratic ops: two full cores, or two block scores
        assert 0 < gauges[f"graphs.arch.quadratic_time_share.{m}"] < 0.2
    et = build_episode_tables(env)
    # the short shape's top row: 31 forward ops under 8 quanta, none of
    # the long one's; by row, degrees (1, 2, 4, 6, 8) a type
    assert ragged_forward_ops(et) == {long: 0, short: 31}
    assert et.row_ragged.tolist() == [0, 0, 0, 0, 0, 0, 0, 14, 24, 31]
    startup.registry().reset()
    # no old family has a linear or a block-sparse layer
    report = _arch_gauges(TRINITY_FILE, TRINITY_SHAPES[:1])
    assert report["graphs.arch.layers_linear.afmoe_s8192_b4"] == 0
    assert report["graphs.arch.layers_block_sparse.afmoe_s8192_b4"] == 0
    assert report["graphs.arch.linear_time_share.afmoe_s8192_b4"] == 0
    # the cell's own shapes: 24 linear layers; 8 full cores up to 8,192
    # tokens, 8 block-sparse ones beyond
    report = _arch_gauges(SALA_FILE, SALA_SHAPES)
    for (s, b), sparse, share in zip(SALA_SHAPES, (0, 0, 8, 8),
                                     (0.0054, 0.0053, 0.0052, 0.0052)):
        m = f"minicpm_sala_s{s}_b{b}"
        assert report[f"graphs.arch.layers_linear.{m}"] == 24
        assert report[f"graphs.arch.layers_block_sparse.{m}"] == sparse
        assert report[f"graphs.arch.layers_full.{m}"] == 8 - sparse
        assert report[f"graphs.arch.forward_ops.{m}"] == 227 + 2 * sparse
        assert report[f"graphs.arch.edges.{m}"] == 645 + 8 * sparse
        assert report[f"graphs.arch.linear_time_share.{m}"] \
            == pytest.approx(share, abs=1e-4)
    shares = [report["graphs.arch.quadratic_time_share."
                     f"minicpm_sala_s{s}_b{b}"] for s, b in SALA_SHAPES]
    assert shares == pytest.approx([0.0144, 0.0284, 0.0035, 0.0140],
                                   abs=1e-4)


# ========================================================= longcat_flash
LONGCAT_FILE = "ddls_tpu/graphs/arch_configs/longcat_flash_omni.json"
#: the deployment's cut (env_longcat_32.yaml): 4 of the 28 double layers,
#: 128 of the 512 FFN experts
LONGCAT_CUT = {"layers": {"leading_dense": 0, "following": 4},
               "experts_held": 128}
LONGCAT_SHAPES = [(8192, 1), (8192, 4), (32768, 1), (131072, 1)]
#: 2 double layers, hidden 64, 8 FFN + 4 zero-compute experts (3 a
#: token): every key LongCat-Flash's config states, under its own name —
#: the THIRD family of key names (``num_layers``, ``ffn_hidden_size``,
#: ``expert_ffn_hidden_size``, ``moe_topk``; no ``model_type``) — and
#: what it leaves unsaid in a ``modeling`` block
TINY_LONGCAT = {"hidden_size": 64, "num_attention_heads": 4,
                "q_lora_rank": 32, "kv_lora_rank": 16,
                "qk_nope_head_dim": 12, "qk_rope_head_dim": 4,
                "v_head_dim": 16, "mla_scale_q_lora": True,
                "mla_scale_kv_lora": True, "ffn_hidden_size": 128,
                "expert_ffn_hidden_size": 32, "n_routed_experts": 8,
                "zero_expert_num": 4, "zero_expert_type": "identity",
                "moe_topk": 3, "routed_scaling_factor": 6,
                "num_layers": 2, "vocab_size": 256}
TINY_LONGCAT_MODELING = {"model_type": "tinylongcat",
                         "shortcut_sub_blocks": 2,
                         "e_score_correction_bias": True}
TINY_LONGCAT_BUILT = {**TINY_LONGCAT, **TINY_LONGCAT_MODELING}
#: the ops of one double layer, in profile order
LONGCAT_SUB_BLOCK = ["InputNorm", "QAProj", "QBProj", "KVAProj", "KVBProj",
                     "LatentAttnCore", "OutProjResidual", "PostAttnNorm"]
LONGCAT_LAYER = (LONGCAT_SUB_BLOCK + ["Router", "Experts",
                                      "DenseMLPResidual"]
                 + LONGCAT_SUB_BLOCK + ["DenseMLPResidual",
                                        "ShortcutCombineResidual"])


@pytest.fixture(scope="module")
def longcat():
    return arch.load_arch_config(LONGCAT_FILE)


def _tiny_longcat_arch_file(directory) -> str:
    path = os.path.join(str(directory), "tinylongcat.json")
    with open(path, "w") as fh:
        json.dump({"source_url": "test-local", "training_state": STATE,
                   "modeling": TINY_LONGCAT_MODELING,
                   "config": TINY_LONGCAT}, fh)
    return path


@pytest.mark.parametrize("case", ["tiny_s8", "tiny_cut", "longcat_8k",
                                  "longcat_8k_x4", "longcat_32k",
                                  "longcat_128k"])
def test_longcat_op_costs_equal_the_plain_count_op_by_op(longcat, case):
    """`plain_counts_longcat` is written from ISSUE 48's equations and
    imports nothing of the builder: op names, parameters, FLOPs, output
    elements, bytes moved and the edge set agree at two tiny shapes
    (whole, and a cut whose pairs are no whole number) and the cell's
    four."""
    from plain_arch_counts import plain_counts_longcat

    cut = (4, 128)
    config, seq_len, micro_batch, (layers, held) = {
        "tiny_s8": (TINY_LONGCAT_BUILT, 8, 3, (None, None)),
        "tiny_cut": (TINY_LONGCAT_BUILT, 37, 5, (1, 5)),
        "longcat_8k": (longcat, 8192, 1, cut),
        "longcat_8k_x4": (longcat, 8192, 4, cut),
        "longcat_32k": (longcat, 32768, 1, cut),
        "longcat_128k": (longcat, 131072, 1, cut)}[case]
    built = arch.build_graph(
        config, seq_len, micro_batch,
        None if layers is None else {"leading_dense": 0,
                                     "following": layers}, held)
    plain, edges = plain_counts_longcat(config, seq_len, micro_batch,
                                        layers, held)
    assert [o["op_type"] for o in built.ops] == [p[0] for p in plain]
    for i, (o, (kind, params, flops, out, nbytes)) in enumerate(
            zip(built.ops, plain)):
        assert o["params"] == params, (i, kind)
        assert o["flops"] == pytest.approx(flops, rel=1e-12), (i, kind)
        assert o["out_elems"] == pytest.approx(out, rel=1e-12), (i, kind)
        assert o["bytes"] == pytest.approx(nbytes, rel=1e-12), (i, kind)
    assert set(built.edges) == edges and len(built.edges) == len(edges)
    depth = config["num_layers"] if layers is None else layers
    # 21 ops and 33 edges a double layer: an edge for every true data
    # dependency and no other
    assert [o["op_type"] for o in built.ops] == (
        ["Embedding"] + LONGCAT_LAYER * depth + ["FinalNorm", "LMHeadLoss"])
    assert len(built.ops) == 1 + 21 * depth + 2
    assert len(built.edges) == 33 * depth + 2


@pytest.mark.parametrize("quantity", ["whole", "as_cut"])
def test_longcat_whole_and_cut_are_the_published_model(longcat, quantity):
    """Whole, the language model counts 560.66 B parameters (published
    560B; the encoders and the codec decoder have no key and are left
    out) and 27 B active a token; the stage the cell queues is 23.49 B:
    87 forward ops, 134 forward edges."""
    if quantity == "whole":
        ops = arch.op_costs(longcat, 8192, 1)
        assert len(ops) == 1 + 21 * 28 + 2
        assert sum(o["params"] for o in ops) == 560_664_980_480
        # a token's own parameters: everything but the experts it does
        # not reach — 12 x 512 / 768 = 8 FFN experts a layer on average
        expert = 3 * 6144 * 2048
        active = sum(o["params"] for o in ops
                     if o["op_type"] != "Experts") + 28 * 8 * expert
        assert 26e9 < active - 2 * 131072 * 6144 + 131072 * 6144 < 28e9
        assert arch.resolve_cut(longcat) == {
            "leading_dense": 0, "following": 28, "experts_held": 512}
    else:
        graph = arch.build_graph(longcat, 8192, 1, **LONGCAT_CUT)
        assert sum(o["params"] for o in graph.ops) == 23_493_469_184
        assert (len(graph.ops), len(graph.edges)) == (87, 134)
        # one attention 90.57 M, one dense FFN 226.49 M, the router
        # 4.72 M, an expert 37.75 M
        layer = graph.ops[1:22]
        assert sum(o["params"] for o in layer[:7]) - 6144 \
            == 90_572_800 == 6144 * 1536 + 1536 + 1536 * 64 * 192 \
            + 6144 * 576 + 512 + 512 * 64 * 256 + 64 * 128 * 6144
        assert layer[10]["params"] == 3 * 6144 * 12288 == 226_492_416
        assert layer[8]["params"] == 6144 * 768 + 768 == 4_719_360
        assert layer[9]["params"] == 128 * 37_748_736


@pytest.mark.parametrize("case", [
    "zero_expert_num_absent", "mla_scale_q_false", "mla_scale_kv_false",
    "routed_scaling_factor_absent", "score_bias_unstated",
    "index_topk_present", "no_shortcut"])
def test_each_longcat_key_is_counted_where_it_is_stated(glm, case):
    """Take a key away and exactly its count goes: the router narrows to
    E and the pairs are today's; a scalar is 1 FLOP an element of the
    latent it scales; with ``index_topk`` the SAME q/kv projections
    feed GLM-5's indexer and sparse core; without ``shortcut_sub_blocks``
    the same keys build the one-chain expert layer."""
    S, B = 32, 2
    T, H = S * B, 64
    base = TINY_LONGCAT_BUILT
    ops = {o["op_type"]: o for o in arch.op_costs(base, S, B)}
    drop = lambda *keys: {k: v for k, v in base.items() if k not in keys}

    def changed(config):
        got = {o["op_type"]: o for o in arch.op_costs(config, S, B)}
        return got, sorted(k for k in got if got[k] != ops.get(k))

    if case == "zero_expert_num_absent":
        got, differ = changed(drop("zero_expert_num", "zero_expert_type"))
        # (the combine's bytes tie at these sizes: k Z / (E + Z) = 1, so
        # the identity pairs' x'_0 is as many elements as the FFN pairs
        # they displace; its edge below does not)
        assert differ == ["Experts", "Router"]
        E, k = 8, 3
        assert got["Router"]["params"] == H * E + E
        assert got["Router"]["flops"] == 2 * T * H * E + 5 * T * E + T * k
        pairs = T * k                     # all 8 held: today's count
        assert got["Experts"]["out_elems"] == pairs * H
        assert ops["Experts"]["out_elems"] == pairs * H * 8 / 12
        assert got["ShortcutCombineResidual"]["flops"] \
            == ops["ShortcutCombineResidual"]["flops"] \
            == 2 * pairs * H + T * H
        # no identity pair reads x'_0: one edge and T H elements fewer
        edges = lambda c: len(arch.build_graph(c, S, B).edges)
        assert edges(base) - edges(drop("zero_expert_num")) == 2
        with pytest.raises(ValueError, match="zero_expert_type"):
            arch.op_costs({**base, "zero_expert_type": "copy"}, S, B)
    elif case.startswith("mla_scale"):
        key, op, rank = {"mla_scale_q_false": ("mla_scale_q_lora",
                                               "QAProj", 32),
                         "mla_scale_kv_false": ("mla_scale_kv_lora",
                                                "KVAProj", 16)}[case]
        got, differ = changed({**base, key: False})
        assert differ == [op]
        assert ops[op]["flops"] - got[op]["flops"] == T * rank
        assert {k: v for k, v in got[op].items() if k != "flops"} \
            == {k: v for k, v in ops[op].items() if k != "flops"}
    elif case == "routed_scaling_factor_absent":
        got, differ = changed(drop("routed_scaling_factor"))
        assert differ == ["Router"]
        assert ops["Router"]["flops"] - got["Router"]["flops"] == T * 3
    elif case == "score_bias_unstated":
        got, differ = changed(drop("e_score_correction_bias"))
        assert differ == ["Router"]
        assert ops["Router"]["params"] - got["Router"]["params"] == 12
        assert ops["Router"]["bytes"] - got["Router"]["bytes"] == 2 * 12
    elif case == "index_topk_present":
        index = {k: TINY_GLM[k] for k in ("index_n_heads",
                                          "index_head_dim", "index_topk")}
        types = [o["op_type"] for o in arch.op_costs({**base, **index},
                                                     S, B)]
        assert "LatentAttnCore" not in types
        assert types[1:10] == [
            "InputNorm", "QAProj", "QBProj", "KVAProj", "KVBProj",
            "IndexerProj", "IndexScoreTopK", "SparseAttnCore",
            "OutProjResidual"]
        # and GLM-5's own config, which states no scale, pays none
        glm_ops = {o["op_type"]: o for o in arch.op_costs(
            glm, 8192, 1, **GLM_CUT)}
        T5, rq = 8192, glm["q_lora_rank"]
        assert glm_ops["QAProj"]["flops"] \
            == 2 * T5 * 6144 * rq + 4 * T5 * rq
    else:
        graph = arch.build_graph(drop("shortcut_sub_blocks"), S, B)
        types = [o["op_type"] for o in graph.ops]
        assert types[1:12] == LONGCAT_SUB_BLOCK + [
            "Router", "Experts", "CombineResidual"]
        assert len(types) == 1 + 11 * 2 + 2
        # the zero-compute pairs read the block's input here too
        combine = types.index("CombineResidual") + 1
        assert (types.index("PostAttnNorm") + 1, combine) in graph.edges


@pytest.mark.parametrize("case", ["depth", "dense_width", "expert_width",
                                  "experts_per_token", "disagree",
                                  "dense_layer_leads"])
def test_either_family_of_key_names_is_read_and_a_disagreement_refused(
        longcat, glm, case):
    """Depth, widths and experts a token under `num_hidden_layers` /
    `num_layers`, `intermediate_size` / `ffn_hidden_size`,
    `moe_intermediate_size` / `expert_ffn_hidden_size`,
    `num_experts_per_tok` / `moe_topk`: one reader each; a config that
    states both names and disagrees is refused, one that agrees is
    not."""
    reader, old, new = {
        "depth": (arch.stack_depth, "num_hidden_layers", "num_layers"),
        "dense_width": (arch.dense_width, "intermediate_size",
                        "ffn_hidden_size"),
        "expert_width": (arch.expert_width, "moe_intermediate_size",
                         "expert_ffn_hidden_size"),
        "experts_per_token": (arch.experts_per_token,
                              "num_experts_per_tok", "moe_topk"),
    }.get(case, (None, None, None))
    if reader is not None:
        assert old in glm and old not in longcat
        assert new in longcat and new not in glm
        assert reader(glm) == glm[old] and reader(longcat) == longcat[new]
        assert reader({**longcat, old: longcat[new]}) == longcat[new]
        with pytest.raises(ValueError, match="disagree"):
            reader({**longcat, old: longcat[new] + 1})
    elif case == "disagree":
        with pytest.raises(ValueError, match="disagree"):
            arch.build_graph({**TINY_LONGCAT_BUILT,
                              "num_hidden_layers": 4}, 8, 1)
        with pytest.raises(KeyError, match="num_layers"):
            arch.stack_depth({"hidden_size": 64})
        # no size is defaulted in the builder
        for key in ("ffn_hidden_size", "q_lora_rank", "moe_topk",
                    "vocab_size"):
            config = {k: v for k, v in TINY_LONGCAT_BUILT.items()
                      if k != key}
            if key == "moe_topk":     # 0 experts a token: no pair at all
                experts = [o for o in arch.op_costs(config, 8, 1)
                           if o["op_type"] == "Experts"]
                assert all(o["flops"] == 0 for o in experts)
            else:
                with pytest.raises(KeyError):
                    arch.build_graph(config, 8, 1)
    else:
        with pytest.raises(ValueError, match="shortcut_sub_blocks"):
            arch.resolve_cut(TINY_LONGCAT_BUILT,
                             {"leading_dense": 1, "following": 1})
        assert arch.model_name(TINY_LONGCAT_BUILT, 8, 1) \
            == "tinylongcat_s8_b1"
        assert arch.model_name(longcat, 8192, 4) == "longcat_flash_s8192_b4"
        assert "model_type" not in arch.load_arch_file(
            LONGCAT_FILE)["config"]


@pytest.mark.parametrize("seq_len", [1, 7, 64])
def test_latent_core_reads_t_keys_a_query_and_grows_as_s_squared(seq_len):
    """The indexer-less latent core is FULL causal: query t reads its t
    keys (brute force), so it is one of `QUADRATIC_OPS`."""
    core = next(o for o in arch.op_costs(TINY_LONGCAT_BUILT, seq_len, 3)
                if o["op_type"] == "LatentAttnCore")
    keys = sum(t for t in range(1, seq_len + 1))
    assert core["flops"] == 3 * keys * 4 * (2 * 16 + 2 * 16 + 5)
    assert "LatentAttnCore" in arch.QUADRATIC_OPS
    assert "SparseAttnCore" not in arch.QUADRATIC_OPS


LONGCAT_SHA256 = {
    (8192, 1):
        "3857fd1ba59deb417a50111c5462edfcc3211ea2255b44a46f6b0b1ff8f6f339",
    (8192, 4):
        "4382bc323282cdb7b78c9a227e46ad15d16b3c11317534d2ae83ec98d5707597",
    (32768, 1):
        "2fca134457fd734740121c5989fafb14ab893a85c9e41d5e24b4164efc26371e",
    (131072, 1):
        "55dda6a86e039ed9418120d74238d0b1e9419cebda5c8b68e46a6d5d09d7d0f0"}


@pytest.mark.parametrize("shape", LONGCAT_SHAPES)
def test_longcat_profiles_are_pinned(shape):
    """The four profiles of `longcat_ramp32.train_fused`, byte for byte:
    a later change to a shared count shows here."""
    import hashlib

    family = arch.load_arch_file(LONGCAT_FILE)
    text = arch.profile_text(arch.builder_config(family), *shape,
                             LONGCAT_CUT["layers"],
                             LONGCAT_CUT["experts_held"],
                             family["training_state"])
    assert hashlib.sha256(text.encode()).hexdigest() \
        == LONGCAT_SHA256[shape]


def test_longcat_env_yaml_states_what_its_comments_derive(longcat):
    """env_longcat_32.yaml: the cut and the shapes as data, the arrival
    gap and horizon derived from the builder's graph, env_olmoe32's pads
    rule, which row is ragged, and nothing else changed from
    env_mimo_32."""
    import math

    from ddls_tpu.agents.partitioners import sip_ml_num_partitions
    from ddls_tpu.config import load_config

    def env(name):
        return load_config(
            os.path.join(REPO, "scripts/ramp_job_partitioning_configs"),
            "rllib_config", [f"env_config={name}"])["env_config"]

    cfg, base = env("env_longcat_32"), env("env_mimo_32")
    jobs = cfg["jobs_config"]
    family = jobs["architecture"]
    assert family["config"] == LONGCAT_FILE
    assert {k: family[k] for k in LONGCAT_CUT} == LONGCAT_CUT
    shapes = family["shapes"]
    assert [(s["seq_len"], s["micro_batch"]) for s in shapes] \
        == LONGCAT_SHAPES
    assert longcat["max_position_embeddings"] == 131072
    steps = jobs["num_training_steps"]
    costs = [arch.op_costs(longcat, **s, **LONGCAT_CUT) for s in shapes]
    forward = [sum(arch.forward_time(c) for c in ops) for ops in costs]
    assert forward == pytest.approx([0.550, 2.199, 3.222, 29.25], abs=5e-3)
    lengths = [steps * (1 + arch.BACKWARD_OVER_FORWARD) * f
               for f in forward]
    assert lengths == pytest.approx([32.986, 131.944, 193.316, 1755.211],
                                    abs=1e-3)
    gap = np.mean(lengths) / 25
    two_figures = round(gap, 1 - int(math.floor(math.log10(gap))))
    assert jobs["job_interarrival_time_dist"]["val"] == two_figures == 21
    assert cfg["max_simulation_run_time"] == pytest.approx(400 * two_figures)
    assert [len(ops) for ops in costs] == [87] * 4
    n_ops, n_deps = 174, 269
    assert cfg["pad_obs_kwargs"] == {"max_nodes": 50 * -(-n_ops // 50),
                                     "max_edges": 256 * -(-n_deps // 256)}
    # what the header says of a forward pass: the eight latent cores and
    # the branch (Router + Experts + the combine)
    def share(ops, kinds):
        return sum(arch.forward_time(o) for o in ops
                   if o["op_type"] in kinds) / sum(
            arch.forward_time(o) for o in ops)

    assert [share(ops, ("LatentAttnCore",)) for ops in costs] \
        == pytest.approx([0.155, 0.155, 0.423, 0.746], abs=5e-4)
    branch = ("Router", "Experts", "ShortcutCombineResidual")
    assert [share(ops, branch) for ops in costs] \
        == pytest.approx([0.0754, 0.0754, 0.0515, 0.0227], abs=5e-5)
    whole = arch.op_costs(longcat, 8192, 1, LONGCAT_CUT["layers"])
    assert share(whole, branch) == pytest.approx(0.236, abs=5e-4)
    # the one ragged row: the 8,192-token shape's norms and embedding at
    # 11 quanta, rounded up to an even split
    quantum, top = cfg["min_op_run_time_quantum"], cfg[
        "max_partitions_per_op"]
    ragged = [sorted({(o["op_type"], sip_ml_num_partitions(
        arch.forward_time(o), quantum, top)) for o in ops
        if sip_ml_num_partitions(arch.forward_time(o), quantum, top) < top})
        for ops in costs]
    assert ragged == [[("Embedding", 12), ("FinalNorm", 12),
                       ("InputNorm", 12), ("PostAttnNorm", 12)], [], [], []]
    assert min(arch.forward_time(o) for o in costs[0]) \
        == pytest.approx(100.7e-6, abs=1e-7)
    # a job's memory: parameter state + 2 x activations
    state = 16 * sum(o["params"] for o in costs[0])
    assert state == pytest.approx(375.9e9, abs=5e7)
    jobs_gb = [(state + 2 * arch.ACT_BYTES
                * sum(o["out_elems"] for o in ops)) / 1e9 for ops in costs]
    assert jobs_gb == pytest.approx([399.7, 471.0, 471.0, 756.3], abs=0.05)
    # the rest is env_mimo_32's
    changed = {"jobs_config", "pad_obs_kwargs"}
    assert {k: v for k, v in cfg.items() if k not in changed} \
        == {k: v for k, v in base.items() if k not in changed}
    for key in set(jobs) - {"architecture"}:
        assert jobs[key] == base["jobs_config"][key], key


@pytest.mark.parametrize("case", ["tiny", "longcat"])
def test_the_four_longcat_shares_add_up_to_the_uncut_layer(longcat, case):
    """Four pods hold a quarter of the FFN experts each: their expert
    groups' FLOPs, parameters and outputs sum to the uncut layer's; the
    identity pairs are WHOLE on every pod (a token's own device adds
    them) and, like both attentions, both FFNs and the router, are the
    uncut layer's own, counted once."""
    config, seq_len, held = {"tiny": (TINY_LONGCAT_BUILT, 32, 2),
                             "longcat": (longcat, 8192, 128)}[case]
    layers = {"leading_dense": 0, "following": 1}
    uncut = arch.op_costs(config, seq_len, 2, layers=layers)
    share = arch.op_costs(config, seq_len, 2, layers=layers,
                          experts_held=held)
    assert [o["op_type"] for o in share] == [o["op_type"] for o in uncut]
    assert 4 * held == config["n_routed_experts"]
    T, H = seq_len * 2, config["hidden_size"]
    k, E, Z = (config["moe_topk"], config["n_routed_experts"],
               config["zero_expert_num"])
    once = []
    for a, b in zip(share, uncut):
        if a["op_type"] == "Experts":
            for key in ("flops", "params", "out_elems"):
                assert 4 * a[key] == b[key], key
            assert b["out_elems"] == T * k * E / (E + Z) * H
        elif a["op_type"] == "ShortcutCombineResidual":
            # the weighted sum runs over this pod's FFN pairs and ALL
            # identity pairs; the residual is whole
            ffn, zero = 2 * T * k * E / (E + Z) * H, 2 * T * k * Z / (
                E + Z) * H
            assert a["flops"] == ffn / 4 + zero + T * H
            assert b["flops"] == ffn + zero + T * H
            assert ffn + zero == 2 * T * k * H     # every routed pair
        else:
            assert a == b, a["op_type"]
            once.append(a["op_type"])
    assert (once.count("LatentAttnCore"), once.count("DenseMLPResidual"),
            once.count("Router")) == (2, 2, 1)


@pytest.mark.parametrize("family", ["olmoe", "glm", "longcat", "tiny"])
def test_branch_time_share_is_what_runs_beside_the_longest_path(
        tmp_path, family):
    """`graphs.arch.branch_time_share.<model>` from the graph's edges
    and times alone: 0 on OLMoE's chain (its extra edges are shortcuts),
    GLM-5's known fan-outs and no more (the shorter low-rank path, the
    indexer's projections, the shared expert, an MTP norm), and on the
    double layer the expert branch on top of the shorter low-rank
    path."""
    def gauges(arch_file, shapes, **cut):
        return {name[len("graphs.arch.branch_time_share."):]: value
                for name, value in _arch_gauges(arch_file, shapes,
                                                **cut).items()
                if name.startswith("graphs.arch.branch_time_share.")}

    def named(config, shape, cut, beside):
        ops = arch.op_costs(config, *shape, **cut)
        return sum(arch.forward_time(o) for o in ops
                   if o["op_type"] in beside) / sum(
            arch.forward_time(o) for o in ops)

    if family == "olmoe":
        assert gauges(OLMOE_FILE, [(4096, 1)]) == {"olmoe_s4096_b1": 0.0}
    elif family == "glm":
        got = gauges(GLM_FILE, [(8192, 1)], **GLM_CUT)[
            "glm_moe_dsa_s8192_b1"]
        config = arch.load_arch_config(GLM_FILE)
        # at 8k the kv path and the indexer are the shorter arms, the
        # shared expert runs beside Router -> Experts, one MTP norm
        # beside the other
        want = named(config, (8192, 1), GLM_CUT,
                     ("KVAProj", "KVBProj", "IndexerProj",
                      "IndexScoreTopK", "SharedExpert"))
        assert want < got < want + 0.001 and got == pytest.approx(
            0.1037, abs=1e-4)
    elif family == "longcat":
        got = gauges(LONGCAT_FILE, LONGCAT_SHAPES, **LONGCAT_CUT)
        config = arch.load_arch_config(LONGCAT_FILE)
        for shape in LONGCAT_SHAPES:
            # Router -> Experts beside nine ops, and the kv path beside
            # the longer q path; the combine is ON the path
            want = named(config, shape, LONGCAT_CUT,
                         ("Router", "Experts", "KVAProj", "KVBProj"))
            model = "longcat_flash_s%d_b%d" % shape
            assert got[model] == pytest.approx(want, rel=1e-12), model
        assert [got["longcat_flash_s%d_b%d" % s] for s in LONGCAT_SHAPES] \
            == pytest.approx([0.0954, 0.0954, 0.0652, 0.0287], abs=1e-4)
    else:
        got = gauges(_tiny_longcat_arch_file(tmp_path), [(32, 4096)],
                     experts_held=4)
        assert 0.05 < got["tinylongcat_s32_b4096"] < 0.5


def test_generator_sets_the_longcat_gauges(tmp_path):
    """`graphs.arch.{layers_latent,shortcut_branches,zero_experts,
    branch_time_share,zero_routed_share}.<model>` and the bank's sums
    beside `graphs.arch.models`, in the `[startup]` line."""
    gauges = _arch_gauges(_tiny_longcat_arch_file(tmp_path),
                          [(32, 4096), (32, 2 ** 19)], experts_held=4)
    models = ("tinylongcat_s32_b4096", "tinylongcat_s32_b524288")
    for m in models:
        assert gauges[f"graphs.arch.forward_ops.{m}"] == 45
        assert gauges[f"graphs.arch.edges.{m}"] == 2 * 68 + 1
        assert gauges[f"graphs.arch.layers_latent.{m}"] == 4
        assert gauges[f"graphs.arch.layers_full.{m}"] == 0
        assert gauges[f"graphs.arch.shortcut_branches.{m}"] == 2
        assert gauges[f"graphs.arch.zero_experts.{m}"] == 4
        assert gauges[f"graphs.arch.zero_routed_share.{m}"] == 4 / 12
        assert gauges[f"graphs.arch.quadratic_time_share.{m}"] > 0
    shares = [gauges[f"graphs.arch.branch_time_share.{m}"] for m in models]
    assert all(0.05 < s < 0.5 for s in shares)
    assert [gauges[name] for name in BANK_GAUGES[1:4]] == [
        2, sum(shares), 2 * 4 / 12]
    assert arch.zero_routed_share(arch.load_arch_config(LONGCAT_FILE)) \
        == 256 / 768
    assert arch.zero_routed_share(TINY_GLM) == 0.0


# =============================================================== KeyeVL2
KEYE_FILE = "ddls_tpu/graphs/arch_configs/keye_vl_2_30b_a3b.json"
#: the deployment's cut (env_keye_32.yaml): 24 of the 48 layers, all 128
#: experts (no ``experts_held``)
KEYE_CUT = {"layers": {"leading_dense": 0, "following": 24}}
KEYE_SHAPES = [(8192, 4), (32768, 1), (65536, 1), (131072, 1)]
#: 2 layers, hidden 64, 4 q / 2 kv heads of 16 (M-RoPE: 2 + 3 + 3 = 8
#: pairs), an indexer of 2 heads of 8 over ONE key head whose top-16 is
#: under the tiny sequence of 32 (both regimes of ``attended_keys`` in
#: one graph), 8 experts (2 a token, renormalised), no shared expert,
#: under KeyeVL2's key names
TINY_KEYE = {"model_type": "tinykeye", "hidden_size": 64,
             "num_attention_heads": 4, "num_key_value_heads": 2,
             "head_dim": 16, "intermediate_size": 128,
             "moe_intermediate_size": 32, "num_experts": 8,
             "num_local_experts": 8, "num_experts_per_tok": 2,
             "norm_topk_prob": True, "decoder_sparse_step": 1,
             "mlp_only_layers": [], "num_hidden_layers": 2,
             "rope_scaling": {"mrope_section": [2, 3, 3],
                              "rope_type": "default", "type": "default"},
             "sa_config": {"indexer_head_dim": 8, "indexer_num_heads": 2,
                           "indexer_num_kv_heads": 1, "kv_chunk_size": 8,
                           "q_chunk_size": 8, "topk": 16},
             "sliding_window": None, "use_sliding_window": False,
             "max_window_layers": 2, "vocab_size": 256}
#: the ops of one layer, in profile order
KEYE_LAYER = ["InputNorm", "QKVProj", "IndexerProj", "IndexScoreTopK",
              "SparseAttnCore", "OutProjResidual", "PostAttnNorm",
              "Router", "Experts", "CombineResidual"]


@pytest.fixture(scope="module")
def keye():
    return arch.load_arch_config(KEYE_FILE)


def _tiny_keye_arch_file(directory) -> str:
    path = os.path.join(str(directory), "tinykeye.json")
    with open(path, "w") as fh:
        json.dump({"source_url": "test-local", "training_state": STATE,
                   "config": TINY_KEYE}, fh)
    return path


def _assert_equals_plain(built, plain, edges):
    assert [o["op_type"] for o in built.ops] == [p[0] for p in plain]
    for i, (o, (kind, params, flops, out, nbytes)) in enumerate(
            zip(built.ops, plain)):
        assert o["params"] == params, (i, kind)
        assert o["flops"] == pytest.approx(flops, rel=1e-12), (i, kind)
        assert o["out_elems"] == pytest.approx(out, rel=1e-12), (i, kind)
        assert o["bytes"] == pytest.approx(nbytes, rel=1e-12), (i, kind)
    assert set(built.edges) == edges and len(built.edges) == len(edges)


@pytest.mark.parametrize("case", ["tiny_s8", "tiny_s37", "keye_8k_x4",
                                  "keye_32k", "keye_64k", "keye_128k"])
def test_keye_op_costs_equal_the_plain_count_op_by_op(keye, case):
    """`plain_counts_keye` is written from ISSUE 52's equations and
    imports nothing of the builder: op names, parameters, FLOPs, output
    elements, bytes moved and the edge set agree at two tiny shapes (a
    sequence under the indexer's top-k and one over it) and the cell's
    four."""
    from plain_arch_counts import plain_counts_keye

    config, seq_len, micro_batch, layers = {
        "tiny_s8": (TINY_KEYE, 8, 3, None),
        "tiny_s37": (TINY_KEYE, 37, 5, 1),
        "keye_8k_x4": (keye, 8192, 4, 24),
        "keye_32k": (keye, 32768, 1, 24),
        "keye_64k": (keye, 65536, 1, 24),
        "keye_128k": (keye, 131072, 1, 24)}[case]
    built = arch.build_graph(
        config, seq_len, micro_batch,
        None if layers is None else {"leading_dense": 0,
                                     "following": layers})
    _assert_equals_plain(built, *plain_counts_keye(config, seq_len,
                                                   micro_batch, layers))
    depth = config["num_hidden_layers"] if layers is None else layers
    # 10 ops and 15 edges a layer: an edge for every true data
    # dependency and no other
    assert [o["op_type"] for o in built.ops] == (
        ["Embedding"] + KEYE_LAYER * depth + ["FinalNorm", "LMHeadLoss"])
    assert len(built.edges) == 15 * depth + 2
    first = [(u - 1, v - 1) for u, v in built.edges if v <= 11]
    names = ["Embedding"] + KEYE_LAYER
    assert sorted((names[u], names[v]) for u, v in first) == sorted([
        ("Embedding", "InputNorm"), ("InputNorm", "QKVProj"),
        ("InputNorm", "IndexerProj"), ("IndexerProj", "IndexScoreTopK"),
        ("QKVProj", "SparseAttnCore"), ("IndexScoreTopK", "SparseAttnCore"),
        ("SparseAttnCore", "OutProjResidual"),
        ("Embedding", "OutProjResidual"),
        ("OutProjResidual", "PostAttnNorm"), ("PostAttnNorm", "Router"),
        ("Router", "Experts"), ("PostAttnNorm", "Experts"),
        ("Experts", "CombineResidual"),
        ("OutProjResidual", "CombineResidual"),
        ("Router", "CombineResidual")])


@pytest.mark.parametrize("quantity", ["whole", "stage"])
def test_keye_whole_and_stage_are_the_published_model(keye, quantity):
    """Whole, the language model counts 30.64 B parameters (published
    30B; the vision tower has no key and is left out) and 3.46 B
    active a token (3.15 B beside the embedding table: A3B); the stage the cell queues is 15.63 B: 243 forward
    ops, 362 forward edges."""
    if quantity == "whole":
        ops = arch.op_costs(keye, 8192, 1)
        assert len(ops) == 1 + 10 * 48 + 2 == 483
        assert sum(o["params"] for o in ops) == 30_640_641_024 \
            == 48 * 625_381_440 + 2 * 311_164_928 + 2048
        expert = 3 * 2048 * 768
        active = sum(o["params"] for o in ops
                     if o["op_type"] != "Experts") + 48 * 8 * expert
        # 3.46 B with the embedding table, 3.15 B beside it (A3B)
        assert active == 3_461_551_104
        assert 3.1e9 < active - 151936 * 2048 < 3.2e9
        assert arch.resolve_cut(keye) == {
            "leading_dense": 0, "following": 48, "experts_held": 128}
        assert arch.routed_layers(keye) == [1] * 48
    else:
        graph = arch.build_graph(keye, 8192, 4, **KEYE_CUT)
        assert sum(o["params"] for o in graph.ops) == 15_631_486_464
        assert (len(graph.ops), len(graph.edges)) == (243, 362)
        layer = {o["op_type"]: o["params"] for o in graph.ops[1:11]}
        assert layer == {
            "InputNorm": 2048, "QKVProj": 10_485_760,
            "IndexerProj": 2_260_992 + 64, "IndexScoreTopK": 0,
            "SparseAttnCore": 0, "OutProjResidual": 8_388_608,
            "PostAttnNorm": 2048, "Router": 262_144,
            "Experts": 603_979_776, "CombineResidual": 0}
        assert sum(layer.values()) == 625_381_440
        # the model name is the config's own model_type, nothing else is
        assert arch.model_name(keye, 8192, 4) == "KeyeVL2_s8192_b4"
        renamed = arch.build_graph({**keye, "model_type": "anything"},
                                   8192, 4, **KEYE_CUT)
        assert renamed.ops == graph.ops and renamed.edges == graph.edges
        assert "modeling" not in arch.load_arch_file(KEYE_FILE)


def test_glm5_and_keye_take_the_indexer_from_one_function(glm, keye):
    """`IndexerProj`, `IndexScoreTopK` and `SparseAttnCore` are added in
    ONE place, `build_graph`'s `_indexer`, which both attention families
    call; at equal indexer sizes the score op is the same op whatever
    the family (it reads nothing of the q/k/v path), the projections
    differ by where the index queries come from (the q latent, the
    normed stream) and the core by its family's head arithmetic."""
    import inspect

    source = inspect.getsource(arch.build_graph)
    for op in ("IndexerProj", "IndexScoreTopK", "SparseAttnCore"):
        assert source.count(f'"{op}"') == 1, op
    body = source[source.index("def _indexer("):source.index(
        "def _out_proj(")]
    assert all(f'"{op}"' in body for op in (
        "IndexerProj", "IndexScoreTopK", "SparseAttnCore"))
    assert source.count("_indexer(") == 3       # the def and two calls
    S, B = 32, 3
    T, H = S * B, 64
    by = lambda c: {o["op_type"]: o for o in arch.op_costs(c, S, B)}
    tiny_glm, tiny_keye = by(TINY_GLM), by(TINY_KEYE)
    assert tiny_glm["IndexScoreTopK"] == tiny_keye["IndexScoreTopK"]
    ni, di, rq, dr = 2, 8, 32, 4
    # q^I from the 32-wide q latent (RoPE on 4 of 8) | from x (all 8)
    assert tiny_glm["IndexerProj"]["params"] \
        == rq * ni * di + H * di + H * ni + di
    assert tiny_keye["IndexerProj"]["params"] \
        == H * ni * di + H * di + H * ni + di
    assert tiny_glm["IndexerProj"]["flops"] - 3 * T * (ni + 1) * dr \
        - 2 * T * rq * ni * di \
        == tiny_keye["IndexerProj"]["flops"] - 3 * T * (ni + 1) * di \
        - 2 * T * H * ni * di
    keys = sum(min(t, 16) for t in range(1, S + 1))
    assert tiny_glm["SparseAttnCore"]["flops"] \
        == B * keys * 4 * (2 * 16 + 2 * 16 + 5) \
        == tiny_keye["SparseAttnCore"]["flops"]
    # at full size: one reader a size, both families of names
    assert (arch.index_heads(glm), arch.index_head_dim(glm),
            arch.index_topk(glm)) == (32, 128, 2048)
    assert (arch.index_heads(keye), arch.index_head_dim(keye),
            arch.index_topk(keye)) == (16, 64, 2048)
    assert arch.index_topk(TINY) == 0

    def edges(config, op):
        """Op types the first layer's ``op`` reads."""
        graph = arch.build_graph(config, S, B)
        return {graph.ops[u - 1]["op_type"] for u, v in graph.edges
                if graph.ops[v - 1]["op_type"] == op and v <= 12}

    assert edges(TINY_GLM, "IndexerProj") == {"QAProj", "InputNorm"}
    assert edges(TINY_KEYE, "IndexerProj") == {"InputNorm"}
    assert edges(TINY_KEYE, "SparseAttnCore") == {"QKVProj",
                                                  "IndexScoreTopK"}


@pytest.mark.parametrize("seq_len", [1, 7, 16, 17, 64])
def test_gqa_sparse_core_reads_min_t_topk_keys_a_query(seq_len):
    """Under the top-k the learned-sparse core is the full causal count
    (no second graph is needed), over it a query reads top-k keys."""
    ops = {o["op_type"]: o for o in arch.op_costs(TINY_KEYE, seq_len, 3)}
    keys = sum(min(t, 16) for t in range(1, seq_len + 1))
    assert ops["SparseAttnCore"]["flops"] == 3 * keys * 4 * (4 * 16 + 5)
    assert ops["IndexScoreTopK"]["out_elems"] == 3 * seq_len * min(
        seq_len, 16)
    full = {**TINY_KEYE}
    del full["sa_config"]
    core = next(o for o in arch.op_costs(full, seq_len, 3)
                if o["op_type"] == "AttnCore")
    assert (core["flops"] == ops["SparseAttnCore"]["flops"]) \
        == (seq_len <= 16)
    assert "IndexScoreTopK" in arch.QUADRATIC_OPS
    assert "SparseAttnCore" not in arch.QUADRATIC_OPS


def _keye_with(**over):
    """TINY_KEYE with top-level keys replaced (None: dropped) and, under
    ``sa``, entries of its ``sa_config``."""
    sa = over.pop("sa", None)
    config = {**TINY_KEYE, **over}
    if sa is not None:
        config["sa_config"] = {**TINY_KEYE["sa_config"], **sa}
    return {k: v for k, v in config.items() if v is not None}


@pytest.mark.parametrize("case, over, match", [
    ("heads_stated_twice_unequal", {"index_n_heads": 3}, "disagree"),
    ("head_dim_stated_twice_unequal", {"index_head_dim": 4}, "disagree"),
    ("topk_stated_twice_unequal", {"index_topk": 8}, "disagree"),
    ("two_index_key_heads", {"sa": {"indexer_num_kv_heads": 2}},
     "indexer_num_kv_heads"),
    ("q_chunk_zero", {"sa": {"q_chunk_size": 0}}, "q_chunk_size"),
    ("kv_chunk_negative", {"sa": {"kv_chunk_size": -8}}, "kv_chunk_size"),
    ("mrope_sections_do_not_sum", {"rope_scaling": {
        "mrope_section": [2, 3, 4]}}, "mrope_section"),
    ("beside_sparse_config", {"sparse_config": {"dense_len": 8}},
     "sparse_config"),
    ("on_a_window_layer", {
        "use_sliding_window": True, "sliding_window": 8,
        "layer_types": ["sliding_attention", "full_attention"]},
     "sliding-window layer"),
    ("beside_a_sink_logit", {"add_full_attention_sink_bias": True},
     "sink logit"),
    ("sliding_true_without_a_list", {"use_sliding_window": True,
                                     "sliding_window": 8},
     "use_sliding_window"),
    ("sliding_false_beside_window_layers", {
        "sliding_window": 8,
        "layer_types": ["sliding_attention", "full_attention"]},
     "use_sliding_window"),
    ("expert_counts_unequal", {"num_local_experts": 4}, "disagree"),
    ("sparse_step_zero", {"decoder_sparse_step": 0},
     "decoder_sparse_step"),
    ("freq_list_disagrees", {"moe_layer_freq": [0, 1]}, "disagree"),
])
def test_what_keye_states_and_cannot_be_built_is_refused(case, over, match):
    """A stated key that cannot be built is REFUSED, never ignored: a
    size stated under both families of names and unequal, more than one
    index key head, a tile that is not positive, M-RoPE sections that do
    not sum to half the head, an indexer beside a `sparse_config`, on a
    window layer or beside a sink logit, `use_sliding_window` against
    the per-layer list, two expert counts, two statements of which
    layers route."""
    arch.build_graph(TINY_KEYE, 8, 1)         # the base builds
    with pytest.raises(ValueError, match=match):
        arch.build_graph(_keye_with(**over), 8, 1)


def test_keye_keys_that_agree_or_say_nothing_build_the_same_graph(keye):
    """Stated twice and EQUAL is not refused; `use_sliding_window:
    false` builds no window layer whatever `sliding_window` /
    `max_window_layers` hold; the tiles move no count; `model_type`
    names the job and nothing else; M-RoPE's three streams cost RoPE's
    FLOPs."""
    base = arch.build_graph(TINY_KEYE, 32, 2)
    same = [_keye_with(index_n_heads=2, index_head_dim=8, index_topk=16),
            _keye_with(sliding_window=4, max_window_layers=0),
            _keye_with(sa={"q_chunk_size": 512, "kv_chunk_size": 1}),
            _keye_with(rope_scaling=None),
            _keye_with(moe_layer_freq=[1, 1]),
            _keye_with(mlp_only_layers=None),
            _keye_with(num_local_experts=None),
            _keye_with(model_type="another")]
    for config in same:
        got = arch.build_graph(config, 32, 2)
        assert got.ops == base.ops and got.edges == base.edges
    assert "WindowAttnCore" not in {o["op_type"] for o in base.ops}
    assert arch.position_streams(keye) == 3
    assert arch.position_streams(TINY_KEYE) == 3
    assert arch.position_streams(TINY_GLM) == 1
    assert keye["rope_scaling"]["mrope_section"] == [16, 24, 24] \
        and sum(keye["rope_scaling"]["mrope_section"]) == 128 // 2
    assert arch.window_layers(keye) is None
    # the flat keys alone (GLM-5's names) under grouped-query attention
    flat = _keye_with(sa_config=None, index_n_heads=2, index_head_dim=8,
                      index_topk=16)
    got = arch.build_graph(flat, 32, 2)
    assert got.ops == base.ops and got.edges == base.edges


@pytest.mark.parametrize("case", ["step_2_only_0", "step_1_only_1",
                                  "cut_departs"])
def test_sparse_step_and_mlp_only_layers_say_which_layers_route(case):
    """Layer i routes iff i is not in `mlp_only_layers` and (i + 1) mod
    `decoder_sparse_step` is 0; the others are dense SwiGLU at
    `intermediate_size`, through the per-layer path a `moe_layer_freq`
    list takes (the cut keeps the stack's first layers)."""
    from plain_arch_counts import plain_counts_keye

    dense = KEYE_LAYER[:7] + ["DenseMLPResidual"]
    if case == "step_2_only_0":
        config = _keye_with(num_hidden_layers=4, decoder_sparse_step=2,
                            mlp_only_layers=[0], max_window_layers=4)
        assert arch.routed_layers(config) == [0, 1, 0, 1]
        assert arch.resolve_cut(config) == {
            "leading_dense": 1, "following": 3, "experts_held": 8}
        built = arch.build_graph(config, 32, 2)
        assert [o["op_type"] for o in built.ops] == (
            ["Embedding"] + dense + KEYE_LAYER + dense + KEYE_LAYER
            + ["FinalNorm", "LMHeadLoss"])
        _assert_equals_plain(built, *plain_counts_keye(config, 32, 2))
        mlp = next(o for o in built.ops
                   if o["op_type"] == "DenseMLPResidual")
        assert mlp["params"] == 3 * 64 * 128
        # a stage of the first two layers: the dense one and one that
        # routes
        stage = arch.build_graph(config, 32, 2, {"leading_dense": 1,
                                                 "following": 1})
        assert [o["op_type"] for o in stage.ops] == (
            ["Embedding"] + dense + KEYE_LAYER + ["FinalNorm",
                                                  "LMHeadLoss"])
    elif case == "step_1_only_1":
        config = _keye_with(num_hidden_layers=3, mlp_only_layers=[1])
        assert arch.routed_layers(config) == [1, 0, 1]
        built = arch.build_graph(config, 32, 2)
        assert [o["op_type"] for o in built.ops] == (
            ["Embedding"] + KEYE_LAYER + dense + KEYE_LAYER
            + ["FinalNorm", "LMHeadLoss"])
        _assert_equals_plain(built, *plain_counts_keye(config, 32, 2))
    else:
        config = _keye_with(num_hidden_layers=4, decoder_sparse_step=2)
        with pytest.raises(ValueError, match="decoder_sparse_step"):
            arch.build_graph(config, 8, 1, {"leading_dense": 0,
                                            "following": 2})
        with pytest.raises(ValueError, match="decoder_sparse_step"):
            arch.resolve_cut(config, {"leading_dense": 1, "following": 4})
        # the older families state neither key: their lists are theirs
        assert arch.routed_layers(TINY_GLM) is None
        assert arch.routed_layers(TINY_MIMO) == TINY_MIMO["moe_layer_freq"]


@pytest.mark.parametrize("family", ["keye", "olmoe_false", "longcat_absent",
                                    "sigmoid"])
def test_norm_topk_prob_is_two_flops_a_selected_weight_on_softmax(
        olmoe, longcat, family):
    """`norm_topk_prob: true` on a SOFTMAX router: the sum and the
    divide of p_sel / sum p_sel, 2 FLOPs a selected weight. OLMoE states
    `false`, LongCat nothing; the sigmoid routers (GLM-5, MiMo state it
    `true`) count their three a weight as before."""
    S, B = 32, 2
    T, H = S * B, 64
    router = lambda c: next(o for o in arch.op_costs(c, S, B)
                            if o["op_type"] == "Router")
    if family == "keye":
        with_norm, without = (router(TINY_KEYE),
                              router(_keye_with(norm_topk_prob=False)))
        assert with_norm["flops"] - without["flops"] == 2 * T * 2
        assert with_norm["flops"] == 2 * T * H * 8 + 5 * T * 8 + 2 * T * 2
        assert {k: v for k, v in with_norm.items() if k != "flops"} \
            == {k: v for k, v in without.items() if k != "flops"}
    elif family == "olmoe_false":
        assert olmoe["norm_topk_prob"] is False
        assert router(TINY) == router({**TINY, "norm_topk_prob": False})
        assert router({**TINY, "norm_topk_prob": True})["flops"] \
            - router(TINY)["flops"] == 2 * T * TINY["num_experts_per_tok"]
    elif family == "longcat_absent":
        assert "norm_topk_prob" not in longcat
        assert router(TINY_LONGCAT_BUILT)["flops"] \
            == 2 * T * H * 12 + 5 * T * 12 + T * 3
    else:
        assert router({**TINY_GLM, "norm_topk_prob": True}) \
            == router(TINY_GLM)


#: the deployments of the six older families (their env yamls state the
#: cut and the shapes as data) and the sha256 of each profile as the
#: parent of PR 52 wrote it
OLDER_PROFILES_SHA256 = {
    ("env_olmoe32", 4096, 1):
        "e37d732fdece58a9690ddecfee7e1b2c59e8a469047e40e1633973723963d263",
    ("env_olmoe32", 4096, 2):
        "89ce01d98f6416e189da20c491a8b1cbe80ceb430a9aa3a3d0ae5dde06915a0a",
    ("env_olmoe32", 4096, 4):
        "8aba20ad93284818802a2662483d29cafe48784ba07276e1c2de6260229824e0",
    ("env_olmoe32", 4096, 8):
        "433fef7186272ee4ca9bbf18c6d416d744267f3ef95029eb6958b15ebb96f305",
    ("env_glm5_32", 8192, 1):
        "a35b7d33627fa75c547574150e7600ff3e45eb34ecba07455c1f6225f2930f84",
    ("env_glm5_32", 8192, 4):
        "f7f69f829bc6fb32d96501e42f1de8055f4f1d5d604cc91c95a2f4fba79c6892",
    ("env_glm5_32", 32768, 1):
        "796597f437747b3801d9899db80e46b8f708a258cd7ec0e771eb1fa2f1ad75a4",
    ("env_glm5_32", 65536, 1):
        "9fb3efb29c5d2176570e6986500d4af38838d39d6c8097928826af318f4ad07e",
    ("env_mimo_32", 8192, 4):
        "b8d04ab4c6bdb8a0cee1f8b0dc568eb067f161cc2d92560676b05370df00b410",
    ("env_mimo_32", 32768, 1):
        "f11dd41b33879babe8018511e6d9735567bdff77629b317381a42b74f506752d",
    ("env_mimo_32", 65536, 1):
        "c7302b5ab73fdfe49a12919929bca036dcacfe2401ef2b24bb11bac50873ca8a",
    ("env_mimo_32", 262144, 1):
        "b898a48b56d5be2a35a2c33152ea6085ba377108ff04bc675161daf6f9899931",
    ("env_trinity_32", 8192, 4):
        "31cd86399c1e6a53f4099c6ece8596e3f027e10174e5e6bd24d795588a7cb8f0",
    ("env_trinity_32", 32768, 1):
        "d09d8fc5fd9cf136f4bcdb1070ba8745438b3ec3a82b7ccc8fc433257993156e",
    ("env_trinity_32", 65536, 1):
        "e4cf6a995a44a5ab0756d6db6c1c4375e6794bc8fb5a4da14414dcc4f5e45dad",
    ("env_trinity_32", 131072, 1):
        "5d15e437b33d882df9a99d771193362b292b7eee7c2e2fab2b10c599b089107a",
    ("env_sala_32", 4096, 1):
        "a88ead2af4d63170bdcc4d0f8d4a13f68aa886a495622fab0e0dd14f0428bb70",
    ("env_sala_32", 8192, 4):
        "61371ad7db96903573e122f432749f01a2f3ded8e42bff7a03984b71f6fe8ffd",
    ("env_sala_32", 32768, 1):
        "c9fd769c42aec16eb94c29f4a09f48defeca29cd9c67862d165768aa91470b38",
    ("env_sala_32", 131072, 1):
        "0409af227c8303ff4aa16d322df817c9a63306bceee0029864138cfdf0c0ab2e",
    ("env_longcat_32", 8192, 1):
        "3857fd1ba59deb417a50111c5462edfcc3211ea2255b44a46f6b0b1ff8f6f339",
    ("env_longcat_32", 8192, 4):
        "4382bc323282cdb7b78c9a227e46ad15d16b3c11317534d2ae83ec98d5707597",
    ("env_longcat_32", 32768, 1):
        "2fca134457fd734740121c5989fafb14ab893a85c9e41d5e24b4164efc26371e",
    ("env_longcat_32", 131072, 1):
        "55dda6a86e039ed9418120d74238d0b1e9419cebda5c8b68e46a6d5d09d7d0f0"}
KEYE_SHA256 = {
    (8192, 4):
        "d2cde2c3262c5efa833d957c441c3f8fa5f60a678b8bba8d3df18f9b832679e4",
    (32768, 1):
        "e830d12a783ed57d078436ca520fb01e0882019be131a73c448728d461ffc7e4",
    (65536, 1):
        "9e35a97e5010fa409e0991fc9ff36d771075e4bb986b7b8d1a47173495c47b56",
    (131072, 1):
        "3ecf51aea096d6d7af644ec8470e8568abb4662919ac25fe35fe5110337c25e0"}


def _env_config(name):
    from ddls_tpu.config import load_config

    return load_config(
        os.path.join(REPO, "scripts/ramp_job_partitioning_configs"),
        "rllib_config", [f"env_config={name}"])["env_config"]


def _deployment_profile(env_name, seq_len, micro_batch) -> str:
    """The profile `jobs_generator` writes for one shape of a
    deployment, from its env yaml's own `jobs_config.architecture`."""
    import hashlib

    family = _env_config(env_name)["jobs_config"]["architecture"]
    arch_file = arch.load_arch_file(family["config"])
    text = arch.profile_text(
        arch.builder_config(arch_file), seq_len, micro_batch,
        family.get("layers"), family.get("experts_held"),
        arch_file.get("training_state"))
    return hashlib.sha256(text.encode()).hexdigest()


@pytest.mark.parametrize(
    "deployment", sorted(OLDER_PROFILES_SHA256),
    ids=["%s_s%d_b%d" % d for d in sorted(OLDER_PROFILES_SHA256)])
def test_the_24_older_profiles_are_byte_equal_to_the_parents(deployment):
    """PR 52 moved the indexer out of `_latent_attention`, read the
    indexer's sizes, the expert count and which layers route through new
    readers and counted `norm_topk_prob` on the softmax router: every
    profile of the six older families' deployments is the parent's,
    byte for byte."""
    env_name, seq_len, micro_batch = deployment
    shapes = _env_config(env_name)["jobs_config"]["architecture"]["shapes"]
    assert {"seq_len": seq_len, "micro_batch": micro_batch} in shapes
    assert len(shapes) == 4
    assert _deployment_profile(*deployment) \
        == OLDER_PROFILES_SHA256[deployment]


@pytest.mark.parametrize("shape", KEYE_SHAPES)
def test_keye_profiles_are_pinned(shape):
    """The four profiles of `keye_ramp32.train_fused`, byte for byte: a
    later change to a shared count shows here."""
    assert _deployment_profile("env_keye_32", *shape) == KEYE_SHA256[shape]


def test_keye_env_yaml_states_what_its_comments_derive(keye):
    """env_keye_32.yaml: the cut and the shapes as data, the arrival gap
    and horizon derived from the builder's graph, env_olmoe32's pads
    rule, which rows are ragged, and nothing else changed from
    env_trinity_32."""
    import math

    from ddls_tpu.agents.partitioners import sip_ml_num_partitions

    cfg, base = _env_config("env_keye_32"), _env_config("env_trinity_32")
    jobs = cfg["jobs_config"]
    family = jobs["architecture"]
    assert family["config"] == KEYE_FILE
    assert family["layers"] == KEYE_CUT["layers"]
    assert "experts_held" not in family
    shapes = family["shapes"]
    assert [(s["seq_len"], s["micro_batch"]) for s in shapes] == KEYE_SHAPES
    assert keye["max_position_embeddings"] == 262144
    steps = jobs["num_training_steps"]
    costs = [arch.op_costs(keye, **s, **KEYE_CUT) for s in shapes]
    forward = [sum(arch.forward_time(c) for c in ops) for ops in costs]
    assert forward == pytest.approx([1.1267, 1.3005, 3.0197, 7.6950],
                                    abs=5e-4)
    lengths = [steps * (1 + arch.BACKWARD_OVER_FORWARD) * f
               for f in forward]
    assert lengths == pytest.approx([67.599, 78.029, 181.181, 461.702],
                                    abs=1e-3)
    gap = np.mean(lengths) / 25
    two_figures = round(gap, 1 - int(math.floor(math.log10(gap))))
    assert jobs["job_interarrival_time_dist"]["val"] == two_figures == 7.9
    assert cfg["max_simulation_run_time"] == pytest.approx(400 * two_figures)
    assert [len(ops) for ops in costs] == [243] * 4
    n_ops, n_deps = 486, 725
    assert cfg["pad_obs_kwargs"] == {"max_nodes": 50 * -(-n_ops // 50),
                                     "max_edges": 256 * -(-n_deps // 256)}

    # what the header says of a forward pass: the indexer and the cores
    def share(ops, kinds):
        return sum(arch.forward_time(o) for o in ops
                   if o["op_type"] in kinds) / sum(
            arch.forward_time(o) for o in ops)

    assert [share(ops, ("IndexerProj", "IndexScoreTopK")) for ops in costs] \
        == pytest.approx([0.0700, 0.1796, 0.2912, 0.4429], abs=5e-5)
    assert [share(ops, ("SparseAttnCore",)) for ops in costs] \
        == pytest.approx([0.1592, 0.1527, 0.1336, 0.1057], abs=5e-5)
    assert [arch.attended_keys(s["seq_len"], 2048)
            / arch.attended_keys(s["seq_len"], s["seq_len"])
            for s in shapes] == pytest.approx(
        [0.4375, 0.1211, 0.0615, 0.0310], abs=5e-5)
    # with full cores the longest job would be about four times as long
    full = {k: v for k, v in keye.items() if k != "sa_config"}
    assert sum(arch.forward_time(c) for c in arch.op_costs(
        full, 131072, 1, **KEYE_CUT)) / forward[3] \
        == pytest.approx(3.9, abs=0.1)
    # the ragged rows: the norms, routers and embedding of a
    # 32,768-token step at 13 quanta
    quantum, top = cfg["min_op_run_time_quantum"], cfg[
        "max_partitions_per_op"]
    ragged = [sorted({(o["op_type"], sip_ml_num_partitions(
        arch.forward_time(o), quantum, top)) for o in ops
        if sip_ml_num_partitions(arch.forward_time(o), quantum, top) < top})
        for ops in costs]
    thirteen = [("Embedding", 14), ("FinalNorm", 14), ("InputNorm", 14),
                ("PostAttnNorm", 14), ("Router", 14)]
    assert ragged == [thirteen, thirteen, [], []]
    assert sum(sip_ml_num_partitions(arch.forward_time(o), quantum, top)
               < top for o in costs[0]) == 74
    # a job's memory: parameter state + 2 x activations
    state = 16 * sum(o["params"] for o in costs[0])
    assert state == pytest.approx(250.1e9, abs=5e7)
    jobs_gb = [(state + 2 * arch.ACT_BYTES
                * sum(o["out_elems"] for o in ops)) / 1e9 for ops in costs]
    assert jobs_gb == pytest.approx([386.8, 386.8, 523.5, 797.0], abs=0.05)
    assert [math.ceil(gb / 80) for gb in jobs_gb] == [5, 5, 7, 10]
    longest = arch.op_costs(keye, 262144, 1, **KEYE_CUT)
    assert (state + 2 * arch.ACT_BYTES * sum(
        o["out_elems"] for o in longest)) / 1e9 > 16 * 80
    # the rest is env_trinity_32's
    changed = {"jobs_config", "pad_obs_kwargs", "max_simulation_run_time"}
    assert {k: v for k, v in cfg.items() if k not in changed} \
        == {k: v for k, v in base.items() if k not in changed}
    for key in set(jobs) - {"architecture", "job_interarrival_time_dist"}:
        assert jobs[key] == base["jobs_config"][key], key


def test_committed_keye_file_is_the_catalog_rows_config():
    """The architecture file holds the row's `config` verbatim (the
    benchmark's configuration file repeats it under `published`), the
    family's training state and no modeling block."""
    family = arch.load_arch_file(KEYE_FILE)
    assert set(family) == {"name", "source_url", "what", "training_state",
                           "config"}
    assert family["training_state"] == STATE
    assert "LEFT OUT" in family["what"]
    config = family["config"]
    assert (config["model_type"], config["hidden_size"],
            config["num_hidden_layers"], config["num_attention_heads"],
            config["num_key_value_heads"], config["head_dim"],
            config["num_experts"], config["num_local_experts"],
            config["num_experts_per_tok"], config["moe_intermediate_size"],
            config["intermediate_size"], config["vocab_size"]) \
        == ("KeyeVL2", 2048, 48, 32, 4, 128, 128, 128, 8, 768, 6144, 151936)
    assert config["sa_config"] == {
        "indexer_head_dim": 64, "indexer_num_heads": 16,
        "indexer_num_kv_heads": 1, "kv_chunk_size": 512,
        "q_chunk_size": 512, "topk": 2048}
    assert (config["decoder_sparse_step"], config["mlp_only_layers"],
            config["norm_topk_prob"], config["use_sliding_window"],
            config["sliding_window"], config["max_window_layers"]) \
        == (1, [], True, False, None, 48)
    bench = json.load(open(os.path.join(
        REPO, "benchmarks/configs/keye_vl2_30b_a3b_stage_ramp32.json")))
    published = dict(bench["published"])
    assert published.pop("train_batch_size") == 4000
    assert published == config


@pytest.mark.parametrize("family", ["tiny", "glm", "keye", "olmoe"])
def test_generator_sets_the_indexer_gauges(tmp_path, family):
    """`graphs.arch.{layers_indexed,index_time_share,
    sparse_core_time_share,attended_keys_share,position_streams}.<model>`
    and the bank's two new sums beside `graphs.arch.models`:
    `layers_indexed` counts ops named `IndexScoreTopK`, so GLM-5's
    graphs read it too."""
    def of(gauges, what):
        return {name[len(f"graphs.arch.{what}."):]: value
                for name, value in gauges.items()
                if name.startswith(f"graphs.arch.{what}.")}

    if family == "tiny":
        gauges = _arch_gauges(_tiny_keye_arch_file(tmp_path),
                              [(32, 4096), (8, 2 ** 19)])
        models = ("tinykeye_s32_b4096", "tinykeye_s8_b524288")
        assert of(gauges, "layers_indexed") == dict.fromkeys(models, 2)
        assert of(gauges, "position_streams") == dict.fromkeys(models, 3)
        assert of(gauges, "layers_full") == dict.fromkeys(models, 0)
        keys = of(gauges, "attended_keys_share")
        assert keys == {models[0]: arch.attended_keys(32, 16)
                        / arch.attended_keys(32, 32), models[1]: 1.0}
        assert keys[models[0]] == (16 * 17 / 2 + 16 * 16) / (32 * 33 / 2)
        index = of(gauges, "index_time_share")
        assert all(0.01 < index[m] < 0.5 for m in models)
        assert all(0.0 < v < 0.5
                   for v in of(gauges, "sparse_core_time_share").values())
        # QKVProj runs beside the indexer's two ops (or they beside it)
        assert all(v > 0 for v in of(gauges, "branch_time_share").values())
        assert [gauges[name] for name in BANK_GAUGES[4:]] == [
            sum(index.values()), sum(keys.values())]
        assert gauges[BANK_GAUGES[1]] == 2
    elif family == "glm":
        gauges = _arch_gauges(GLM_FILE, [(8192, 1), (65536, 1)], **GLM_CUT)
        # 3 + 4 layers and the MTP module's
        assert of(gauges, "layers_indexed") == {
            "glm_moe_dsa_s8192_b1": 8, "glm_moe_dsa_s65536_b1": 8}
        assert of(gauges, "position_streams") == {
            "glm_moe_dsa_s8192_b1": 1, "glm_moe_dsa_s65536_b1": 1}
        assert of(gauges, "attended_keys_share")[
            "glm_moe_dsa_s65536_b1"] == pytest.approx(0.0615, abs=5e-5)
        assert 0 < of(gauges, "index_time_share")[
            "glm_moe_dsa_s8192_b1"] < of(gauges, "index_time_share")[
            "glm_moe_dsa_s65536_b1"] < 1
    elif family == "keye":
        gauges = _arch_gauges(KEYE_FILE, KEYE_SHAPES, **KEYE_CUT)
        models = ["KeyeVL2_s%d_b%d" % s for s in KEYE_SHAPES]
        index, keys = (of(gauges, "index_time_share"),
                       of(gauges, "attended_keys_share"))
        assert [index[m] for m in models] == pytest.approx(
            [0.0700, 0.1796, 0.2912, 0.4429], abs=5e-5)
        assert [keys[m] for m in models] == pytest.approx(
            [0.4375, 0.1211, 0.0615, 0.0310], abs=5e-5)
        assert of(gauges, "layers_indexed") == dict.fromkeys(models, 24)
        assert of(gauges, "position_streams") == dict.fromkeys(models, 3)
        assert of(gauges, "forward_ops") == dict.fromkeys(models, 243)
        assert of(gauges, "edges") == dict.fromkeys(models, 725)
        # the bank's means, what the two new benchmark metrics read
        assert gauges[BANK_GAUGES[4]] / gauges[BANK_GAUGES[1]] \
            == pytest.approx(0.2459, abs=5e-5)
        assert gauges[BANK_GAUGES[5]] / gauges[BANK_GAUGES[1]] \
            == pytest.approx(0.1628, abs=5e-5)
        # at long S the indexer's two ops ARE the longest path and
        # QKVProj runs beside them
        beside = of(gauges, "branch_time_share")
        assert all(0.01 < beside[m] < 0.2 for m in models)
    else:
        gauges = _arch_gauges(OLMOE_FILE, [(4096, 1)])
        assert of(gauges, "layers_indexed") == {"olmoe_s4096_b1": 0}
        assert of(gauges, "index_time_share") == {"olmoe_s4096_b1": 0.0}
        assert of(gauges, "attended_keys_share") == {"olmoe_s4096_b1": 1.0}
        assert [gauges[name] for name in BANK_GAUGES[4:]] == [0.0, 1.0]
