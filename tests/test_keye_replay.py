"""A learned-sparse indexer under GROUPED-QUERY heads through the device
modules unchanged in form (ISSUE 52, step 5): the tiny Keye preset of
``tests/test_arch_graphs.py`` — 2 layers whose IndexerProj ->
IndexScoreTopK run beside QKVProj (both read the input norm), an index
top-k under the tiny sequence so that both regimes of ``attended_keys``
are in the graph, 8 experts on every layer and no shared one — through
reader -> mirror -> ``Job`` -> host env -> the jitted episode kernel.
Two ops of one job are ready on one worker at once in every layer, so
``srpt_op_scheduler``'s order (the host oracle) against ``select_ops``
(the kernel) decides event times: twelve seeds at x64 1e-9 and f32 1e-4,
each precision in its own process (x64 is process-global), and three
rows bit-equal to the flat lookahead reference."""
import json
import os
import subprocess
import sys

import numpy as np
import pytest

from test_arch_graphs import (KEYE_LAYER, REPO, _tiny_env,
                              _tiny_keye_arch_file)

SEEDS = tuple(range(12))
#: steps of ~0.2 s with every op over 8 quanta (every row even), and a
#: shape whose ops are 9-50 us: ragged rows at every degree above 1
TINY_KEYE_SHAPES = [{"seq_len": 32, "micro_batch": 2 ** 19},
                       {"seq_len": 32, "micro_batch": 4096}]
#: forward ops of the tiny preset by where they sit in the layer
#: (1-based profile ids; op 1 is the embedding)
OP_KIND = {1 + 10 * layer + i + 1: ("indexer" if kind in ("IndexerProj",
                                                         "IndexScoreTopK")
                                    else "qkv" if kind == "QKVProj"
                                    else "other")
           for layer in range(2) for i, kind in enumerate(KEYE_LAYER)}


def _tiny_keye_env(arch_file, **over):
    """The whole tiny model (both layers, all 8 experts) on
    env_keye_32's cluster."""
    jobs = dict(
        architecture={"config": arch_file, "shapes": TINY_KEYE_SHAPES},
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


def test_op_kinds_name_the_indexer_and_the_projection_beside_it():
    kinds = [OP_KIND[op] for op in sorted(OP_KIND)]
    assert len(kinds) == 20 and kinds.count("indexer") == 4
    assert kinds.count("qkv") == 2
    assert [KEYE_LAYER[i] for i in range(10)
            if OP_KIND[2 + i] == "indexer"] == ["IndexerProj",
                                               "IndexScoreTopK"]


#: the twelve seeds in ONE process (the kernel compiles once a job type
#: and degree); beside each verdict, from the PLACEMENT the host
#: committed (`RampClusterEnvironment._place_ops`): the accepted jobs
#: partitioned >= 4 ways in which a sub-op of an indexer op (IndexerProj,
#: IndexScoreTopK) and a sub-op of QKVProj sit on one worker
REPLAY_DRIVER = r"""
import json, sys
sys.path[:0] = [{repo!r}, {tests!r}, {benchmarks!r}]
import jax
assert jax.config.read("jax_enable_x64") == {x64}
import test_keye_replay as t
from benchmarks.reference import first_mismatch
from ddls_tpu.scenarios.conformance import (
    decision_events, jitted_decision_events, run_recorded_episode)
from ddls_tpu.sim.cluster import RampClusterEnvironment

placements = {{}}
place_ops = RampClusterEnvironment._place_ops


def recording(self, op_placement):
    placements.update({{job: dict(ops)
                       for job, ops in op_placement.action.items()}})
    return place_ops(self, op_placement)


RampClusterEnvironment._place_ops = recording
env = t._tiny_keye_env({arch_file!r})
out = []
for seed in {seeds!r}:
    placements.clear()
    events, actions = run_recorded_episode(env, seed, max_decisions=24)
    host = decision_events(events)
    job_id = {{e["job_idx"]: e["job_id"] for e in events
              if e["kind"] == "job_arrived"}}
    shared = 0
    for e in host:
        if not (e["accepted"] and e["degree"] >= 4):
            continue
        workers = {{"indexer": set(), "qkv": set()}}
        for sub_op, worker in placements[job_id[e["job_idx"]]].items():
            kind = t.OP_KIND.get(int(sub_op[:-1]))
            if kind in workers:
                workers[kind].add(worker)
        shared += bool(workers["indexer"] & workers["qkv"])
    kernel = jitted_decision_events(env, events, actions)
    out.append({{
        "seed": seed, "decisions": len(actions),
        "accepted": sum(e["accepted"] for e in host),
        "causes": sorted({{str(e["cause"]) for e in host}}),
        "shared_worker_jobs": shared,
        "mismatch": first_mismatch(host, kernel, {rtol})}})
print(json.dumps(out, default=str))
"""


@pytest.mark.parametrize("x64,rtol", [(True, 1e-9), (False, 1e-4)],
                         ids=["x64_1e-9", "f32_1e-4"])
def test_keye_job_in_kernel_replays_the_host_oracle_on_twelve_seeds(
        tmp_path, x64, rtol):
    """A tiny STATED KeyeVL2 job family — grouped-query attention behind
    a learned-sparse indexer that runs beside QKVProj, an expert layer
    with no shared expert on every layer — against the float64 Python
    oracle: accepted and cause exactly, JCT to the tolerance, on TWELVE
    seeds (PR 36 found a fault since PR 13 on the twelfth seed of a new
    graph shape), with accepted jobs partitioned >= 4 ways whose indexer
    ops and QKVProj shared a worker in every seed's replay."""
    driver = REPLAY_DRIVER.format(
        repo=REPO, tests=os.path.join(REPO, "tests"),
        benchmarks=os.path.join(REPO, "tests", "benchmarks"),
        arch_file=_tiny_keye_arch_file(tmp_path), seeds=SEEDS, x64=x64,
        rtol=rtol)
    out = subprocess.run(
        [sys.executable, "-c", driver], capture_output=True, text=True,
        timeout=1200, env={**os.environ, "JAX_PLATFORMS": "cpu",
                           "JAX_ENABLE_X64": "1" if x64 else "0"})
    assert out.returncode == 0, out.stderr[-3000:]
    verdicts = json.loads(out.stdout.strip().splitlines()[-1])
    assert [v["seed"] for v in verdicts] == list(SEEDS)
    for v in verdicts:
        assert v["mismatch"] is None, v
        assert v["decisions"] == 24 and 0 < v["accepted"] < 24, v
        assert v["shared_worker_jobs"] >= 1, v
    assert sum(v["accepted"] for v in verdicts) >= 30
    assert {c for v in verdicts for c in v["causes"]} >= {
        "None", "max_acceptable_job_completion_time_exceeded"}


@pytest.fixture(scope="module")
def keye_block_build(tmp_path_factory):
    from test_jax_lookahead import _BlockBuild

    arch_file = _tiny_keye_arch_file(tmp_path_factory.mktemp("tiny_keye"))
    return _BlockBuild(_tiny_keye_env(arch_file))


def test_tiny_keye_tables_carry_the_indexer_beside_the_projection(
        keye_block_build):
    """The tables the kernel runs on: 23 forward ops a job, 46 with the
    mirror; the indexer's sub-ops and QKVProj's on the same servers in
    the placement the kernel's own allocator makes."""
    build = keye_block_build
    et = build.et
    assert et.types == ["tinykeye_s32_b4096", "tinykeye_s32_b524288"]
    assert et.pads.n_fwd == 23 and et.pads.n_orig == 46
    row = build.row("tinykeye_s32_b524288", 4)
    split = np.asarray(et.tables["f_split"])[row]
    assert set(split.tolist()) == {4}
    args, _, placed = build.arguments(row, build.states[0])
    assert bool(placed)
    worker = np.asarray(args[2]).reshape(et.pads.n_orig, et.pads.max_split)
    servers = {kind: set() for kind in ("indexer", "qkv")}
    for op, kind in OP_KIND.items():
        if kind in servers:
            servers[kind] |= set(worker[op - 1][worker[op - 1] >= 0].tolist())
    assert len(servers["indexer"]) == 4
    assert servers["indexer"] == servers["qkv"]


@pytest.mark.parametrize("model,degree", [
    ("tinykeye_s32_b524288", 4), ("tinykeye_s32_b524288", 8),
    ("tinykeye_s32_b4096", 6)])
def test_block_lookahead_is_flat_lookahead_on_an_indexed_row(
        keye_block_build, model, degree):
    """The block-form lookahead equals the flat reference
    (`tests/flat_lookahead.py`) on all six outputs, bit for bit, on rows
    of a job partitioned 4, 8 and (ragged) up to 6 ways whose indexer
    and QKVProj share every worker."""
    from test_jax_lookahead import _assert_same_bits

    build = keye_block_build
    cfg = build.row(model, degree)
    args, blocks, placed = build.arguments(cfg, build.states[0])
    want = build.flat(args, blocks)
    _assert_same_bits(build.block(args, blocks), want, (model, degree))
    assert bool(placed) and bool(want[4]) and int(want[5]) > 0
