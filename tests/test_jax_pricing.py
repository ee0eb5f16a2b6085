"""jax_price_and_score vs the host pricing/scheduling pipeline: for every
job placed during a real episode, the kernel's dep run times, flow mask,
channel assignment, and SRPT lookahead scores must match the host's
(assign_dep_run_times + SRPT schedulers + build_native_lookahead_arrays)
-- and, bit for bit, the flat forms it had before it priced by block
(tests/flat_pricing.py), in x64 (the same subprocess) and in f32.

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
from ddls_tpu.native.arrays import build_native_lookahead_arrays
from ddls_tpu.sim.jax_env import (build_shape_tables, config_tables_for,
                                  table_slots)
import flat_pricing

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
pair_channel = np.asarray(topo.dense_tables()["pair_channel"])
comm = {"x": topo.num_communication_groups,
        "rate": topo.channel_bandwidth,
        "prop": topo.intra_gpu_propagation_latency,
        "io": topo.worker_io_latency}
forms = flat_pricing.Forms(cfgs, st, comm, pair_channel)
tables, pads = forms.tables, forms.pads
free = jnp.full((forms.n_chan,), -1, jnp.int32)

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
    (times, is_flow, op_score, dep_score, finite_ok, ok_chan, chan_mask,
     srv_mask) = (np.asarray(x) for x in forms.block(jnp.asarray(sc), cfg,
                                                     free))
    assert finite_ok and ok_chan
    np.testing.assert_allclose(times[deps], r["times"], rtol=1e-12, atol=0,
        err_msg=f"dep times mismatch {r['model']} deg {r['degree']}")
    assert (np.delete(times, deps) == 0).all()
    assert (is_flow[deps] == r["is_flow"]).all(), "flow mask mismatch"
    assert not np.delete(is_flow, deps).any()
    # the host's per-dep channel, as the set of channels the job's flows
    # ride (each the direct link of its (source, destination) servers)
    assert (r["chan"][~r["is_flow"]] == -1).all()
    src_dst = np.argwhere(np.isin(pair_channel, r["chan"][r["is_flow"]]))
    assert set(map(tuple, src_dst)) == set(zip(
        r["sc"][cfgs[cfg]["dep_src"]][r["is_flow"]],
        r["sc"][cfgs[cfg]["dep_dst"]][r["is_flow"]]))
    assert (np.nonzero(chan_mask)[0]
            == np.unique(r["chan"][r["is_flow"]])).all(), "channel mismatch"
    assert (np.nonzero(srv_mask)[0] == np.unique(r["sc"])).all()
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

# ... and the block forms against the flat ones, in f64: the recorded
# placements, then every row under the placements of flat_pricing
# (unplaced ops, asymmetric groups, same-server pairs, taken channels),
# unbatched and under a 2-lane vmap
rng = np.random.RandomState(5)
for r in records:
    cfg = keys.index((r["model"], r["degree"]))
    sc = np.full(pads.n_ops, -1, np.int32)
    sc[table_slots(cfgs[cfg], pads.max_split)[0]] = r["sc"]
    args = (jnp.asarray(sc), cfg, jnp.asarray(forms.occupancy(rng)))
    flat_pricing.assert_same_bits(forms.block(*args), forms.flat(*args),
                                  ("recorded", cfg))
seen = [flat_pricing.check_row(forms, cfg, rng) for cfg in range(len(cfgs))]
seen = {k: sum(s[k] for s in seen) for k in seen[0]}
assert all(seen.values()), seen
flat_pricing.check_lanes(forms, 2, rng)
assert forms.jt["dep_size"].dtype == np.float64
print(f"PRICING_PARITY_OK checked={checked} tied={tied} "
      f"tie_break_matters={tie_break_matters} block_vs_flat={seen}")
"""


def test_pricing_and_scores_match_host_x64():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_ENABLE_X64"] = "1"
    tests = os.path.dirname(os.path.abspath(__file__))
    env["PYTHONPATH"] = os.pathsep.join([os.path.dirname(tests), tests])
    res = subprocess.run([sys.executable, "-c", DRIVER], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, (res.stdout[-4000:], res.stderr[-4000:])
    assert "PRICING_PARITY_OK" in res.stdout, res.stdout[-2000:]


# ---------------------------------------------------------------------------
# The config tables' block order (stack_config_tables): a permutation of
# each row's ops and deps onto (o, k) / (b, i, j) slots.
# ---------------------------------------------------------------------------
import functools
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
    from ddls_tpu.sim import jax_env as je
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
    # a dep IS (block b, source shard i, destination shard j): its
    # endpoints follow from the block's two original ops
    b, i, j = deps // (S * S), (deps // S) % S, deps % S
    assert (ops[c["dep_src"]] == tables["blk_src"][row][b] * S + i).all()
    assert (ops[c["dep_dst"]] == tables["blk_dst"][row][b] * S + j).all()
    n_blk = len(c["blk_src"])
    assert (tables["blk_src"][row][n_blk:] == -1).all()
    assert set(b.tolist()) == set(range(n_blk))
    # ... and is priced the way its block is: the grouping's edge lists
    # (index-valued, in host order) land on blocks of their own kind
    kind, grp = tables["blk_kind"][row], tables["blk_grp"][row]
    for gi, g in enumerate(c["groups"]):
        assert (kind[b[g["edges"]]] == je.BLK_CANDIDATE).all()
        assert (grp[b[g["edges"]]] == gi).all()
    assert tables["grp_valid"][row].sum() == len(c["groups"])
    for g in c["sync"]:
        assert (kind[b[g["edges"]]] == je.BLK_SYNC).all()
        assert (tables["blk_msg"][row][b[g["edges"]]] == g["msg"]).all()
        assert c["dep_mutual"][g["edges"]].all()
    assert (kind[b[c["o2o_edges"]]] == je.BLK_O2O).all()
    assert (kind[n_blk:] == 0).all() and (kind[:n_blk] > 0).all()
    assert (grp[kind != je.BLK_CANDIDATE] == -1).all()


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


# ---------------------------------------------------------------------------
# Pricing by block (broadcast, reduction and one-hots over the servers)
# against the flat forms it replaced (tests/flat_pricing.py: one gather
# or scatter per dep): the same bits on every output, in f32 here and in
# f64 in the subprocess above.
# ---------------------------------------------------------------------------

_COMM = {"x": 2, "rate": 0.8e12, "prop": 50e-9, "io": 100e-9}
_ROWS = [(f, d) for f in _TABLE_FILES for d in _TABLE_DEGREES]


@pytest.fixture(scope="module")
def forms(table_build):
    import flat_pricing

    from ddls_tpu.sim.jax_env import build_shape_tables

    cfgs, _, _ = table_build
    return flat_pricing.Forms(
        cfgs, build_shape_tables((2, 2, 4), 16), _COMM,
        flat_pricing.complete_pair_channel(16))


@pytest.mark.parametrize("row", range(len(_ROWS)),
                         ids=[f"{f}-{d}" for f, d in _ROWS])
def test_block_pricing_is_flat_pricing(forms, row):
    """Every (model, degree) row under five placements — the
    allocator's own, random, two servers, some ops unplaced (-1), one
    server — with free and with partly taken channels: `times`,
    `is_flow`, `op_score`, `dep_score`, `finite_ok` and `eval_cfg`'s
    `ok_chan` / `chan_mask` / `srv_mask`, with ``==``."""
    import flat_pricing

    seen = flat_pricing.check_row(forms, row, np.random.RandomState(row))
    assert seen["chan_free"] >= 5
    if _ROWS[row][1] > 1:
        # flows exist, their costs tie (the `dep_edge` tie-break), a
        # taken channel blocks, the allocator's placement passes the
        # symmetry test and a random one falls back to one-to-one
        assert all(seen.values()), seen


@pytest.mark.parametrize("n_lanes", [2, 32, 130])
def test_block_pricing_is_flat_pricing_under_vmap(forms, n_lanes):
    """Lanes of different rows, placements and occupancies: under 128
    lanes and over, a multiple of nothing."""
    import flat_pricing

    flat_pricing.check_lanes(forms, n_lanes, np.random.RandomState(n_lanes))


@pytest.fixture(scope="module")
def small_env_kernels(dataset_dir):
    """`_episode_kernels` of a 16-server env over the module's dataset
    (degrees 1, 2, 4, 6, 8), a one-job bank, and a cluster state in
    which two servers and a fifth of the channels are another job's."""
    import flat_pricing
    import jax.numpy as jnp

    from ddls_tpu.envs import RampJobPartitioningEnvironment
    from ddls_tpu.sim import jax_env as je

    env = RampJobPartitioningEnvironment(
        topology_config={"type": "ramp", "kwargs": {
            "num_communication_groups": 2,
            "num_racks_per_communication_group": 2,
            "num_servers_per_rack": 4, "num_channels": 1,
            "total_node_bandwidth": 1.6e12,
            "intra_gpu_propagation_latency": 50e-9,
            "worker_io_latency": 100e-9}},
        node_config={"type_1": {"num_nodes": 16, "workers_config": [
            {"num_workers": 1, "worker": "A100"}]}},
        jobs_config={"path_to_files": dataset_dir,
            "job_interarrival_time_dist": {
                "_target_": "ddls_tpu.demands.distributions.Fixed",
                "val": 100.0},
            "replication_factor": 2,
            "job_sampling_mode": "remove_and_repeat",
            "num_training_steps": 3},
        max_partitions_per_op=8, reward_function="job_acceptance",
        max_simulation_run_time=1e5,
        pad_obs_kwargs={"max_nodes": 150, "max_edges": 512})
    env.reset(seed=0)
    et = je.build_episode_tables(env, quantum=0.25)
    k = je._episode_kernels(et)
    bank = {key: jnp.asarray(v) for key, v in je.build_job_bank(et, [
        {"model": et.types[-1], "num_training_steps": 3, "sla_frac": 1.0,
         "time_arrived": 0.0}]).items()}
    carry = list(k.init_state(bank)[0])
    rng = np.random.RandomState(9)
    carry[2] = jnp.asarray(np.where(np.arange(et.n_srv) % 8 == 1, 0, -1),
                           jnp.int32)                    # srv_job
    carry[3] = jnp.asarray(np.where(rng.rand(et.n_chan) < 0.2, 0, -1),
                           jnp.int32)                    # chan_occ
    return et, k, bank, tuple(carry)


@pytest.mark.parametrize("cluster", ["taken", "free"])
def test_eval_cfg_masks_are_the_flat_forms(small_env_kernels, cluster):
    """`eval_cfg` itself, on a cluster with taken servers and channels
    and on an empty one: its `ok_chan` / `chan_mask` / `srv_mask` are
    the flat forms' over the flat pricing of the allocator's own
    placement, column by column, and `price_all`'s cfg vmap (each
    column's rows read inside it) is the unbatched call's bits."""
    import flat_pricing
    import jax
    import jax.numpy as jnp

    from ddls_tpu.sim import jax_env as je

    et, k, bank, carry = small_env_kernels
    if cluster == "free":
        carry = k.init_state(bank)[0]
    row = jnp.int32(0)
    n_deg = len(et.degrees)
    cfg0 = int(bank["type"][0]) * n_deg

    def flat_eval(cfg):
        ots, _, _ = je.jax_allocate_job(
            carry[1], carry[2] < 0, je.config_rows(et.tables, cfg), et.st,
            et.pads)
        dep_src, dep_dst = flat_pricing.block_endpoint_slots(
            et.tables["blk_src"][cfg], et.tables["blk_dst"][cfg],
            et.pads.max_split)
        scp = jnp.clip(ots, 0)
        src, dst = scp[dep_src], scp[dep_dst]
        is_flow = (et.tables["dep_valid"][cfg]
                   & (et.tables["dep_size"][cfg] > 0) & (src != dst))
        chan = jnp.where(is_flow, et.pair_channel[src, dst], -1)
        return flat_pricing.flat_masks(
            ots, et.tables["op_valid"][cfg], is_flow, chan, carry[3],
            et.n_srv, et.n_chan)

    block = jax.jit(lambda cfg: k.eval_cfg(
        bank, carry, row, cfg, je.config_rows(et.tables, cfg))[0])
    flat = jax.jit(flat_eval)
    blocked, evs = 0, []
    for col in range(n_deg):
        ev = block(jnp.int32(cfg0 + col))
        for name, want in zip(("ok_chan", "chan_mask", "srv_mask"),
                              flat(jnp.int32(cfg0 + col))):
            got, want = np.asarray(ev[name]), np.asarray(want)
            assert got.dtype == want.dtype and (got == want).all(), \
                (col, name)
        blocked += int(not bool(ev["ok_chan"]))
        evs.append(ev)
    if cluster == "taken":
        assert 0 < blocked < n_deg, "a taken channel blocks some columns"
    else:
        assert blocked == 0
    placeable, jct = jax.jit(lambda: k.price_all(bank, carry, row))()
    assert (np.asarray(placeable) == np.array(
        [bool(e["ok_place"] & e["ok_chan"] & e["engine_ok"])
         for e in evs])).all()
    assert (np.asarray(jct) == np.array(
        [np.asarray(e["jct"]) for e in evs])).all()


# ---------------------------------------------------------------------------
# The engagement pins: no equation of the traced `eval_cfg` indexes per
# dep (the test behind the start-up gauge `sim.price.dep_indexed_ops`),
# and every block of every row is priced one way.
# ---------------------------------------------------------------------------

def test_eval_cfg_indexes_no_dep(small_env_kernels, forms):
    """`price_dep_indexed_ops` — the gauge — reads 0 on the package's
    `eval_cfg`; the same walk over the flat forms finds the gathers and
    scatters that were half of a small-job epoch."""
    import jax
    import jax.numpy as jnp

    from ddls_tpu.sim import jax_env as je

    et = small_env_kernels[0]
    assert je.price_dep_indexed_ops(et) == 0
    n = forms.pads.n_blocks * forms.pads.max_split
    args = (jnp.zeros((forms.pads.n_ops,), jnp.int32), jnp.int32(0),
            jnp.full((forms.n_chan,), -1, jnp.int32))
    assert je.dep_indexed_ops(
        jax.make_jaxpr(forms.block_fn)(*args).jaxpr, n) == []
    found = je.dep_indexed_ops(jax.make_jaxpr(forms.flat_fn)(*args).jaxpr, n)
    assert sorted(set(found)) == ["gather", "scatter", "scatter-max"]
    assert len(found) >= 12, found


@functools.lru_cache(maxsize=None)
def _olmoe_rows():
    from ddls_tpu.graphs import arch
    from ddls_tpu.graphs.readers import read_graph_file

    cfg = arch.load_arch_config(os.path.join(
        os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        "ddls_tpu/graphs/arch_configs/olmoe_1b_7b_0125.json"))
    import tempfile
    out = tempfile.mkdtemp(prefix="olmoe_rows_")
    shapes = [{"seq_len": 4096, "micro_batch": b} for b in (1, 8)]
    return [read_graph_file(p) for p in arch.write_profiles(out, cfg, shapes)]


@pytest.mark.parametrize("micro_batch,degree", [(1, 2), (1, 16), (8, 2),
                                                (8, 16)])
def test_olmoe_blocks_are_priced_one_way(micro_batch, degree):
    """OLMoE-1B-7B rows (262 ops, 520 blocks; the fwd -> bwd join edge
    is the one `claim` gives to the first group that asks): every
    block has ONE (kind, group, sync message) — `_block_pricing` raises
    otherwise — and every dep is covered."""
    from ddls_tpu.sim.jax_env import (BLK_CANDIDATE, BLK_SYNC,
                                      _block_pricing, config_tables_for)

    graph = _olmoe_rows()[0 if micro_batch == 1 else 1]
    c = config_tables_for(graph, degree, 10e-6)
    kind, grp, msg = _block_pricing(c)
    assert len(kind) == len(c["blk_src"]) == 520 and (kind > 0).all()
    assert (kind == BLK_SYNC).sum() == 131           # the backward cliques
    assert sorted(set(grp[kind == BLK_CANDIDATE])) == list(
        range(len(c["groups"])))
    assert (msg[kind == BLK_SYNC] > 0).all() and not msg[kind != BLK_SYNC].any()


def test_a_block_split_between_groups_raises(table_build):
    """A hand-made row: move one edge of a candidate group into the
    next group (or one sync pair's message off its clique's) and the
    table build refuses the row."""
    import copy

    from ddls_tpu.sim.jax_env import (build_shape_tables,
                                      stack_config_tables)

    cfgs, _, _ = table_build
    row = _ROWS.index(("cnn_0", 4))
    st = build_shape_tables((2, 2, 4), 16)
    stack_config_tables([cfgs[row]], st)             # the real row builds
    split = copy.deepcopy(cfgs[row])
    a, b = split["groups"][0], split["groups"][1]
    b["edges"] = np.append(b["edges"], a["edges"][-1])
    a["edges"] = a["edges"][:-1]
    with pytest.raises(ValueError, match="split between collective groups"):
        stack_config_tables([split], st)
    off = copy.deepcopy(cfgs[row])
    off["sync"][0]["msg"] *= 2
    with pytest.raises(ValueError, match="split between collective groups"):
        stack_config_tables([off], st)
    orphan = copy.deepcopy(cfgs[row])
    orphan["o2o_edges"] = orphan["o2o_edges"][:0]
    orphan["groups"][0]["edges"] = orphan["groups"][0]["edges"][:-1]
    with pytest.raises(ValueError, match="in no collective group"):
        stack_config_tables([orphan], st)
