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
                                  jax_price_and_score, stack_config_tables)

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

checked = 0
for r in records:
    cfg = keys.index((r["model"], r["degree"]))
    n = len(r["sc"])
    m = len(r["times"])
    sc = np.full(pads.n_ops, -1, np.int64)
    sc[:n] = r["sc"]
    times, is_flow, chan, op_score, dep_score, finite_ok = (
        np.asarray(x) for x in fn(jnp.asarray(sc), cfg))
    assert finite_ok
    np.testing.assert_allclose(times[:m], r["times"], rtol=1e-12, atol=0,
        err_msg=f"dep times mismatch {r['model']} deg {r['degree']}")
    assert (times[m:] == 0).all()
    assert (is_flow[:m] == r["is_flow"]).all(), "flow mask mismatch"
    assert (chan[:m] == r["chan"]).all(), "channel assignment mismatch"
    np.testing.assert_allclose(op_score[:n], r["op_score"], rtol=0, atol=0,
        err_msg=f"op_score mismatch {r['model']} deg {r['degree']}")
    np.testing.assert_allclose(dep_score[:m], r["dep_score"], rtol=0,
        atol=0,
        err_msg=f"dep_score mismatch {r['model']} deg {r['degree']}")
    checked += 1
print(f"PRICING_PARITY_OK checked={checked}")
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
