"""Full-episode parity: the jitted canonical-RAMP episode
(sim/jax_env.py make_episode_fn) replays a host episode's action sequence
and must reproduce every decision — reward, acceptance, blocked cause,
decision time, lookahead JCT — plus the final counters.

Runs under JAX_ENABLE_X64=1 in a subprocess (process-global flag), the
same isolation pattern as tests/test_jax_pricing.py."""
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
from ddls_tpu.sim.jax_env import (build_episode_tables, build_job_bank,
                                  make_episode_fn, CAUSE_ACCEPTED,
                                  CAUSE_NOT_HANDLED, CAUSE_OP_PLACEMENT,
                                  CAUSE_DEP_PLACEMENT, CAUSE_SLA)

d = tempfile.mkdtemp(prefix="jax_episode_")
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
            "_target_": "ddls_tpu.demands.distributions.Fixed", "val": 40.0},
        "max_acceptable_job_completion_time_frac_dist": {
            "_target_": "ddls_tpu.demands.distributions.Uniform",
            "min_val": 0.1, "max_val": 1.0, "decimals": 2},
        "replication_factor": 40, "job_sampling_mode": "remove_and_repeat",
        "num_training_steps": 20},
    max_partitions_per_op=8, min_op_run_time_quantum=0.01,
    reward_function="job_acceptance", max_simulation_run_time=5e3,
    pad_obs_kwargs={"max_nodes": 150, "max_edges": 512})

CAUSE_BY_STR = {
    "not_handled": CAUSE_NOT_HANDLED,
    "op_partition": CAUSE_OP_PLACEMENT,   # never expected here
    "op_placement": CAUSE_OP_PLACEMENT,
    "dep_placement": CAUSE_DEP_PLACEMENT,
    "max_acceptable_job_completion_time_exceeded": CAUSE_SLA,
    "job_queue_full": -99,                # cannot occur in this MDP
}

# ---- host episode with a mixed action policy, recording everything
obs = env.reset(seed=17)
rng = np.random.RandomState(23)
arrivals = []   # one record per arrived job, in arrival order
decisions = []  # (action, reward, accepted, cause_code, t, jct)
seen_idx = set()

def record_arrival(job):
    arrivals.append({"model": job.details["model"],
                     "num_training_steps": job.num_training_steps,
                     "sla_frac": job.max_acceptable_jct_frac,
                     "time_arrived": job.details["time_arrived"]})

done = False
while not done:
    job = next(iter(env.cluster.job_queue.jobs.values()))
    ji = env.cluster.job_id_to_job_idx[job.job_id]
    if ji not in seen_idx:
        assert ji == len(arrivals), (ji, len(arrivals))
        seen_idx.add(ji)
        record_arrival(job)
    t_dec = env.cluster.stopwatch.time()
    valid = np.nonzero(np.asarray(obs["action_mask"]))[0]
    # mix: mostly aggressive degrees (exercises placement failures +
    # SLA blocks), some zeros (not_handled), some moderate
    r = rng.rand()
    if r < 0.15:
        action = 0
    elif r < 0.55:
        action = int(valid[-1])
    else:
        action = int(rng.choice(valid))
    n_causes_before = len(env.cluster.episode_stats[
        "jobs_blocked_cause_of_unsuccessful_handling"])
    obs, reward, done, info = env.step(action)
    accepted = ji in env.cluster.jobs_running or ji in env.cluster.jobs_completed
    if accepted:
        pj = (env.cluster.jobs_running.get(ji)
              or env.cluster.jobs_completed.get(ji))
        jct = pj.details["lookahead_job_completion_time"]
        cause = CAUSE_ACCEPTED
    else:
        jct = 0.0
        # the decided job's cause is the FIRST one appended this step
        # (episode finalisation may append later simulation_ended entries)
        causes = env.cluster.episode_stats[
            "jobs_blocked_cause_of_unsuccessful_handling"]
        cause = CAUSE_BY_STR[causes[n_causes_before]]
    decisions.append((action, reward, accepted, cause, t_dec, jct))

# jobs that arrived but were never decided (episode ended) are not in
# `arrivals` via the decision loop only if queued at done; record all
# remaining arrivals the cluster saw so the bank covers them
n_arrived = env.cluster.num_jobs_arrived
host = {
    "accepted": int(sum(1 for d in decisions if d[2])),
    "blocked": int(sum(1 for d in decisions if not d[2])),
    "completed": int(len(env.cluster.jobs_completed)),
    "ret": float(sum(d[1] for d in decisions)),
}
print(f"host episode: {len(decisions)} decisions, {n_arrived} arrivals, "
      f"accepted {host['accepted']} blocked {host['blocked']} "
      f"completed {host['completed']}")

# bank needs EVERY arrival (the last one may still sit in the queue)
for ji in range(len(arrivals), n_arrived):
    j = (env.cluster.jobs_running.get(ji) or env.cluster.jobs_completed.get(ji)
         or env.cluster.jobs_blocked.get(ji)
         or env.cluster.job_queue.jobs.get(env.cluster.job_idx_to_job_id[ji]))
    assert j is not None, f"arrival {ji} untracked"
    record_arrival(j.original_job if j.original_job is not j else j)

# ---- jitted replay
et = build_episode_tables(env)
bank = build_job_bank(et, arrivals)
episode_fn = make_episode_fn(et)
actions = jnp.asarray([d[0] for d in decisions], jnp.int32)
out = episode_fn({k: jnp.asarray(v) for k, v in bank.items()}, actions)
reward_tr, accept_tr, cause_tr, jct_tr, t_tr, has_job_tr = (
    np.asarray(x) for x in out["trace"])

assert has_job_tr.all(), "replay ran out of queued jobs before the host did"
n_bad = 0
for i, (action, reward, accepted, cause, t_dec, jct) in enumerate(decisions):
    ok = (bool(accept_tr[i]) == accepted and int(cause_tr[i]) == cause
          and reward_tr[i] == reward
          and abs(t_tr[i] - t_dec) <= 1e-9 * max(t_dec, 1.0)
          and (not accepted or abs(jct_tr[i] - jct) <= 1e-9 * jct))
    if not ok:
        n_bad += 1
        if n_bad <= 5:
            print(f"DECISION {i} action {action}: host "
                  f"(acc={accepted}, cause={cause}, r={reward}, "
                  f"t={t_dec}, jct={jct}) vs kernel "
                  f"(acc={bool(accept_tr[i])}, cause={int(cause_tr[i])}, "
                  f"r={reward_tr[i]}, t={t_tr[i]}, jct={jct_tr[i]})")
assert n_bad == 0, f"{n_bad} of {len(decisions)} decisions diverged"
assert int(out["accepted"]) == host["accepted"]
assert int(out["blocked"]) == host["blocked"]
assert int(out["completed"]) == host["completed"]
assert abs(float(out["ret"]) - host["ret"]) < 1e-9

# ---- episode-record parity vs the host cluster's finalised stats:
# arrivals (the device collectors' rate denominator) and num_jobs_blocked
# INCLUDING the host finalisation that blocks jobs still running at
# simulation end (cluster.py:1010-1013)
er = env.cluster.episode_stats
assert int(out["arrived"]) == n_arrived == er["num_jobs_arrived"], (
    int(out["arrived"]), n_arrived, er["num_jobs_arrived"])
assert int(out["blocked_total"]) == er["num_jobs_blocked"], (
    int(out["blocked_total"]), int(out["blocked"]), er["num_jobs_blocked"])
still = int(out["blocked_total"]) - int(out["blocked"])
print(f"EPISODE_PARITY_OK decisions={len(decisions)} "
      f"still_running_at_end={still}")
"""


def test_full_episode_parity_x64():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["JAX_ENABLE_X64"] = "1"
    env["PYTHONPATH"] = os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))
    res = subprocess.run([sys.executable, "-c", DRIVER], env=env,
                         capture_output=True, text=True, timeout=1800)
    assert res.returncode == 0, (res.stdout[-4000:], res.stderr[-4000:])
    assert "EPISODE_PARITY_OK" in res.stdout, res.stdout[-2000:]
