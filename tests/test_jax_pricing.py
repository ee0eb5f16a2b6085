"""jax_price_and_score vs the host pricing/scheduling pipeline: for every
job placed during a real episode, the kernel's dep run times, flow mask,
channel assignment, and SRPT lookahead scores must match the host's
(assign_dep_run_times + SRPT schedulers + build_native_lookahead_arrays).

The full-precision comparison runs in a subprocess with JAX_ENABLE_X64=1
(x64 is a process-global jax flag; the main pytest process stays f32), the
way tests/test_distributed.py isolates its gloo processes."""
import os
import subprocess
import sys

DRIVER = r"""
import numpy as np
import jax
import jax.numpy as jnp

assert jax.config.read("jax_enable_x64"), "driver needs JAX_ENABLE_X64=1"

import tempfile
from ddls_tpu.graphs.synthetic import generate_pipedream_txt_files
from ddls_tpu.envs import RampJobPartitioningEnvironment
from ddls_tpu.sim.jax_lookahead import build_native_lookahead_arrays
from ddls_tpu.sim.jax_env import (build_shape_tables, config_tables_for,
                                  jax_price_and_score, stack_config_tables,
                                  table_slots)

d = tempfile.mkdtemp(prefix="jax_pricing_")
generate_pipedream_txt_files(d, n_cnn=2, n_translation=1, seed=3)
env = RampJobPartitioningEnvironment(
    topology_config={"type": "ramp", "kwargs": {
        "num_communication_groups": 4,
        "num_racks_per_communication_group": 4,
        "num_servers_per_rack": 2, "num_channels": 1,
        "total_node_bandwidth": 1.6e12,
        "intra_gpu_propagation_latency": 50e-9,
        "worker_io_latency": 100e-9}},
    node_config={"type_1": {"num_nodes": 32, "workers_config": [
        {"num_workers": 1, "worker": "A100"}]}},
    jobs_config={"path_to_files": d,
        "job_interarrival_time_dist": {
            "_target_": "ddls_tpu.demands.distributions.Fixed", "val": 50.0},
        "max_acceptable_job_completion_time_frac_dist": {
            "_target_": "ddls_tpu.demands.distributions.Uniform",
            "min_val": 0.3, "max_val": 1.0, "decimals": 2},
        "replication_factor": 12, "job_sampling_mode": "remove_and_repeat",
        "num_training_steps": 20},
    max_partitions_per_op=8, min_op_run_time_quantum=0.01,
    reward_function="job_acceptance", max_simulation_run_time=1.5e4,
    pad_obs_kwargs={"max_nodes": 150, "max_edges": 512})
obs = env.reset(seed=11)

topo = env.cluster.topology
records = []
rng = np.random.RandomState(2)
for _ in range(40):
    job = next(iter(env.cluster.job_queue.jobs.values()))
    valid = np.nonzero(np.asarray(obs["action_mask"]))[0]
    prefer = [a for a in valid if a > 0]
    action = int(rng.choice(prefer)) if prefer else 0
    obs, reward, done, info = env.step(action)
    ji = env.cluster.job_id_to_job_idx[job.job_id]
    if action > 0 and ji in env.cluster.jobs_running:
        placed = env.cluster.jobs_running[ji]
        native = build_native_lookahead_arrays(env.cluster, placed)
        payload = env.cluster.job_dep_arrays[ji]
        records.append({
            "model": placed.details["model"],
            "graph": job.graph,              # original profile graph
            "degree": action,
            "sc": env.cluster.job_server_codes[ji].copy(),
            "times": placed.dep_init_run_time_arr.copy(),
            "chan": payload.chan.copy(),
            "op_score": native.op_score.copy(),
            "dep_score": native.dep_score.copy(),
            "is_flow": native.dep_is_flow.copy(),
        })
    if done:
        break

assert len(records) >= 6, f"only {len(records)} placements recorded"

ramp_shape = topo.shape
st = build_shape_tables(ramp_shape, 8)
keys, cfgs = [], []
for r in records:
    key = (r["model"], r["degree"])
    if key not in keys:
        keys.append(key)
        cfgs.append(config_tables_for(r["graph"], r["degree"], 0.01))
tables, pads = stack_config_tables(cfgs, st)
jt = {k: jnp.asarray(v) for k, v in tables.items()}
pair_channel = jnp.asarray(topo.dense_tables()["pair_channel"])
comm = {"x": topo.num_communication_groups,
        "rate": topo.channel_bandwidth,
        "prop": topo.intra_gpu_propagation_latency,
        "io": topo.worker_io_latency}
fn = jax.jit(lambda sc, cfg: jax_price_and_score(
    sc, cfg, jt, st, pads, comm, pair_channel))

# the tables are in block order (stack_config_tables): the kernel takes
# and returns per-op / per-dep arrays by SLOT, the host by finalize()
# index -- un-permute, then compare as before
checked = tied = tie_break_matters = 0
for r in records:
    cfg = keys.index((r["model"], r["degree"]))
    ops, deps = table_slots(cfgs[cfg], pads.max_split)
    n = len(r["sc"])
    m = len(r["times"])
    assert len(ops) == n and len(deps) == m
    sc = np.full(pads.n_ops, -1, np.int64)
    sc[ops] = r["sc"]
    times, is_flow, chan, op_score, dep_score, finite_ok = (
        np.asarray(x) for x in fn(jnp.asarray(sc), cfg))
    assert finite_ok
    np.testing.assert_allclose(times[deps], r["times"], rtol=1e-12, atol=0,
        err_msg=f"dep times mismatch {r['model']} deg {r['degree']}")
    assert (np.delete(times, deps) == 0).all()
    assert (is_flow[deps] == r["is_flow"]).all(), "flow mask mismatch"
    assert not np.delete(is_flow, deps).any()
    assert (chan[deps] == r["chan"]).all(), "channel assignment mismatch"
    np.testing.assert_allclose(op_score[ops], r["op_score"], rtol=0, atol=0,
        err_msg=f"op_score mismatch {r['model']} deg {r['degree']}")
    np.testing.assert_allclose(dep_score[deps], r["dep_score"], rtol=0,
        atol=0,
        err_msg=f"dep_score mismatch {r['model']} deg {r['degree']}")
    checked += 1
    # SRPT breaks priced-cost ties in HOST edge order: a record with
    # tied flow costs whose block order differs from the edge order is
    # what pins `dep_edge` (a sort on slot position would mis-rank it)
    flow_t = r["times"][r["is_flow"]]
    if len(np.unique(flow_t)) < len(flow_t):
        tied += 1
        raw = np.where(np.asarray(tables["dep_valid"][cfg]), -times, np.inf)
        by_slot = np.lexsort((np.arange(len(raw)), raw))
        by_edge = np.lexsort((np.asarray(tables["dep_edge"][cfg]), raw))
        pos = np.empty(len(raw), np.int64)
        pos[by_slot] = np.arange(len(raw))
        pos_e = np.empty(len(raw), np.int64)
        pos_e[by_edge] = np.arange(len(raw))
        tie_break_matters += int((pos[deps][r["is_flow"]]
                                  != pos_e[deps][r["is_flow"]]).any())
assert tied >= 1, "no record with tied priced costs"
assert tie_break_matters >= 1, "no record where the tie-break order shows"
print(f"PRICING_PARITY_OK checked={checked} tied={tied} "
      f"tie_break_matters={tie_break_matters}")
"""


def test_pricing_and_scores_match_host_x64():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_ENABLE_X64"] = "1"
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    res = subprocess.run([sys.executable, "-c", DRIVER], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, (res.stdout[-4000:], res.stderr[-4000:])
    assert "PRICING_PARITY_OK" in res.stdout, res.stdout[-2000:]


# ---------------------------------------------------------------------------
# The config tables' block order (stack_config_tables): a permutation of
# each row's ops and deps onto (o, k) / (b, i, j) slots.
# ---------------------------------------------------------------------------
import glob

import numpy as np
import pytest

_TABLE_DEGREES = [1, 2, 4, 8, 16]
_TABLE_FILES = ["cnn_0", "cnn_1", "translation_0"]


@pytest.fixture(scope="module")
def table_build(dataset_dir):
    from ddls_tpu.graphs.readers import read_graph_file
    from ddls_tpu.sim.jax_env import (build_shape_tables, config_tables_for,
                                      stack_config_tables)

    files = sorted(glob.glob(os.path.join(dataset_dir, "*.txt")))
    assert [os.path.basename(f)[:-4] for f in files] == _TABLE_FILES
    # quantum 0.25: at degree 16 ops split 6..16 ways (uneven blocks)
    cfgs = [config_tables_for(read_graph_file(f), d, 0.25)
            for f in files for d in _TABLE_DEGREES]
    tables, pads = stack_config_tables(cfgs, build_shape_tables((2, 2, 4), 16))
    return cfgs, tables, pads


@pytest.mark.parametrize("row", range(len(_TABLE_FILES) * len(_TABLE_DEGREES)),
                         ids=[f"{f}-{d}" for f in _TABLE_FILES
                              for d in _TABLE_DEGREES])
def test_block_order_is_a_permutation_of_the_row(table_build, row):
    from ddls_tpu.sim.jax_env import table_slots

    cfgs, tables, pads = table_build
    c = cfgs[row]
    S, B, No = pads.max_split, pads.n_blocks, pads.n_orig
    assert pads.n_ops == No * S and pads.n_deps == B * S * S
    ops, deps = table_slots(c, S)
    n, m = c["n_ops"], c["n_deps"]
    # every real op and every real edge sits on exactly one slot, and
    # the valid masks mark those slots and no other
    assert len(set(ops.tolist())) == n and len(set(deps.tolist())) == m
    assert sorted(np.nonzero(tables["op_valid"][row])[0]) == sorted(ops)
    assert sorted(np.nonzero(tables["dep_valid"][row])[0]) == sorted(deps)
    assert (tables["dep_edge"][row][deps] == np.arange(m)).all()
    assert (np.delete(tables["dep_edge"][row], deps) == pads.n_deps).all()
    # un-permuted, the per-slot values are the host's
    assert (tables["op_compute"][row][ops] == c["op_compute"]).all()
    assert (tables["dep_size"][row][deps] == c["dep_size"]).all()
    assert (tables["dep_mutual"][row][deps] == c["dep_mutual"]).all()
    assert (tables["dep_sorted_rank"][row][deps]
            == c["dep_sorted_rank"]).all()
    assert (tables["dep_src"][row][deps] == ops[c["dep_src"]]).all()
    assert (tables["dep_dst"][row][deps] == ops[c["dep_dst"]]).all()
    # a dep IS (block b, source shard i, destination shard j): its
    # endpoints follow from the block's two original ops
    e = np.sort(deps)
    b, i, j = e // (S * S), (e // S) % S, e % S
    assert (tables["dep_src"][row][e] == tables["blk_src"][row][b] * S + i
            ).all()
    assert (tables["dep_dst"][row][e] == tables["blk_dst"][row][b] * S + j
            ).all()
    n_blk = len(c["blk_src"])
    assert (tables["blk_src"][row][n_blk:] == -1).all()
    assert set(b.tolist()) == set(range(n_blk))
    # index-valued tables point at slots: a group's edge joins its u, v
    for edges, u, v, ok in zip(tables["grp_edges"][row],
                               tables["grp_u"][row], tables["grp_v"][row],
                               tables["grp_edge_valid"][row]):
        assert (tables["dep_src"][row][edges[ok]] == u[ok]).all()
        assert (tables["dep_dst"][row][edges[ok]] == v[ok]).all()
    o2o = tables["o2o_edges"][row][tables["o2o_valid"][row]]
    assert tables["dep_valid"][row][o2o].all()
    sync = tables["sync_edges"][row][tables["sync_valid"][row]]
    assert tables["dep_mutual"][row][sync[sync >= 0]].all()


def test_benchmark_row_is_52_blocks_of_256():
    """The degree-16 pads' arithmetic, counted from the tables: the
    largest row of the shipped env_dev dataset (cnn_1 at degree 16) is
    37 original edges + 15 backward cliques = 52 blocks, 37 x 256 + 15
    x 240 = 13,072 deps on 52 x 256 = 13,312 slots."""
    import tempfile

    from ddls_tpu.graphs.readers import read_graph_file
    from ddls_tpu.graphs.synthetic import generate_pipedream_txt_files
    from ddls_tpu.sim.jax_env import (build_shape_tables, config_tables_for,
                                      stack_config_tables)

    d = tempfile.mkdtemp(prefix="bench_row_")
    generate_pipedream_txt_files(d, n_cnn=3, n_translation=2, seed=0,
                                 min_ops=8, max_ops=16)
    graph = read_graph_file(os.path.join(d, "cnn_1.txt"))
    c = config_tables_for(graph, 16, 0.01)
    tables, pads = stack_config_tables(
        [c], build_shape_tables((4, 4, 2), 16))
    assert (c["n_orig"], len(graph.edge_ids)) == (30, 37)
    cliques = int((c["blk_src"] == c["blk_dst"]).sum())
    assert (len(c["blk_src"]), cliques) == (52, 15)
    assert c["n_deps"] == 37 * 256 + 15 * 240 == 13072
    assert (pads.n_ops, pads.n_deps) == (480, 13312)
    assert int(tables["dep_valid"].sum()) == 13072
