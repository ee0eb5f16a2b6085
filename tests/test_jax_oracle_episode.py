"""The OracleJCT heuristic running entirely in-kernel: candidate pricing,
the oracle's selection rule, decision, and event clock in one jitted
dispatch — replayed against the host OracleJCT driving the real env with
host candidate pricing. Every action, reward, and counter must match.

x64 subprocess (process-global flag), as the other episode-parity
tests."""
import os
import subprocess
import sys

DRIVER = r"""
import numpy as np
import jax
import jax.numpy as jnp

assert jax.config.read("jax_enable_x64")

import tempfile
from ddls_tpu.graphs.synthetic import generate_pipedream_txt_files
from ddls_tpu.envs import RampJobPartitioningEnvironment
from ddls_tpu.envs.baselines import OracleJCT
from ddls_tpu.sim.jax_env import (build_episode_tables, build_job_bank,
                                  build_obs_tables,
                                  make_oracle_episode_fn)

d = tempfile.mkdtemp(prefix="jax_oracle_ep_")
generate_pipedream_txt_files(d, n_cnn=2, n_translation=1, seed=5)
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
            "_target_": "ddls_tpu.demands.distributions.Fixed",
            "val": 45.0},
        "max_acceptable_job_completion_time_frac_dist": {
            "_target_": "ddls_tpu.demands.distributions.Uniform",
            "min_val": 0.1, "max_val": 1.0, "decimals": 2},
        "replication_factor": 30, "job_sampling_mode": "remove_and_repeat",
        "num_training_steps": 20},
    max_partitions_per_op=8, min_op_run_time_quantum=0.01,
    reward_function="job_acceptance", max_simulation_run_time=4e3,
    pad_obs_kwargs={"max_nodes": 150, "max_edges": 512},
    candidate_pricing="native")

# ---- host episode: OracleJCT with host candidate pricing
obs = env.reset(seed=31)
actor = OracleJCT()
arrivals, actions, rewards = [], [], []
seen = set()
done = False
while not done:
    job = next(iter(env.cluster.job_queue.jobs.values()))
    ji = env.cluster.job_id_to_job_idx[job.job_id]
    if ji not in seen:
        seen.add(ji)
        arrivals.append({"model": job.details["model"],
                         "num_training_steps": job.num_training_steps,
                         "sla_frac": job.max_acceptable_jct_frac,
                         "time_arrived": job.details["time_arrived"]})
    action = int(actor.compute_action(obs, job_to_place=job, env=env))
    actions.append(action)
    obs, reward, done, info = env.step(action)
    rewards.append(reward)
n_arrived = env.cluster.num_jobs_arrived
for ji in range(len(arrivals), n_arrived):
    j = (env.cluster.jobs_running.get(ji)
         or env.cluster.jobs_completed.get(ji)
         or env.cluster.jobs_blocked.get(ji)
         or env.cluster.job_queue.jobs.get(env.cluster.job_idx_to_job_id[ji]))
    j = j.original_job if j.original_job is not j else j
    arrivals.append({"model": j.details["model"],
                     "num_training_steps": j.num_training_steps,
                     "sla_frac": j.max_acceptable_jct_frac,
                     "time_arrived": j.details["time_arrived"]})
host_ret = float(np.sum(rewards))
print(f"host oracle: {len(actions)} decisions, return {host_ret}")

# ---- in-kernel oracle on the same bank
et = build_episode_tables(env)
ot = build_obs_tables(env, et)
bank = {k: jnp.asarray(v) for k, v in build_job_bank(et, arrivals).items()}
fn = make_oracle_episode_fn(et, ot)
out = fn(bank)
a_tr, r_tr, acc_tr, cause_tr, jct_tr, t_tr, has_tr = (
    np.asarray(x) for x in out["trace"])
live = has_tr.nonzero()[0]
assert len(live) == len(actions), (len(live), len(actions))
mismatch = np.nonzero(a_tr[live] != np.array(actions))[0]
if len(mismatch):
    i = mismatch[0]
    print(f"FIRST MISMATCH at decision {i}: host {actions[i]} "
          f"kernel {a_tr[live][i]}")
assert len(mismatch) == 0, f"{len(mismatch)} action mismatches"
assert np.allclose(r_tr[live], np.array(rewards))
assert abs(float(out["ret"]) - host_ret) < 1e-9
print(f"ORACLE_EPISODE_PARITY_OK decisions={len(actions)} ret={host_ret}")
"""


def test_oracle_episode_parity_x64():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_ENABLE_X64"] = "1"
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    res = subprocess.run([sys.executable, "-c", DRIVER], env=env,
                         capture_output=True, text=True, timeout=1800)
    assert res.returncode == 0, (res.stdout[-4000:], res.stderr[-4000:])
    assert "ORACLE_EPISODE_PARITY_OK" in res.stdout, res.stdout[-2000:]
