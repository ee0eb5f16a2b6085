"""Round-12 A/B measurements (ISSUE 17, docs/perf_round12.md).

Modes:
  memo     — the wide-probe acceptance A/B: the vmap8 replay episode
             kernel at the CANONICAL degree (max_partitions_per_op=16,
             different banks per lane — the bench vmap8 shape) timed
             memo-ON vs memo-OFF. The outputs are bit-identical (the
             parity contract), so the ratio of walls IS the decision-
             rate ratio; lane-summed {hits, misses, evicts, hit_rate}
             ride the memo-on line, fetched once from the episode
             outputs.
  sebulba  — Sebulba vs pipelined(device-collector) vs fused
             env-steps/s on an 8-virtual-device CPU mesh (forced via
             XLA host_platform_device_count below), interleaved rounds
             for load control (the bench.py --loop-mode both
             discipline). CAVEAT printed into the JSON: virtual CPU
             devices timeshare the same cores, so the actor/learner
             overlap the split exists for CANNOT show here — this line
             pins the dispatch/queue overhead floor; the win case is
             real multi-chip silicon (the bench TPU is 1 chip and
             cannot split either).

One JSON line per measurement, bench.py-style.
"""
import json
import os
import sys
import time

# an 8-device virtual mesh for the sebulba mode, set BEFORE any jax
# backend initialisation (harmless for the memo mode's vmap8)
os.environ.setdefault("XLA_FLAGS",
                      "--xla_force_host_platform_device_count=8")

import numpy as np  # noqa: E402

from _eval_common import _ROOT  # noqa: E402

sys.path.insert(0, _ROOT)
from bench import _make_dataset, make_env_kwargs  # noqa: E402


def _force_cpu():
    import jax

    jax.config.update("jax_platforms", "cpu")
    return jax


def mode_memo(policy_shaped=False):
    jax = _force_cpu()
    import jax.numpy as jnp

    from ddls_tpu.envs import RampJobPartitioningEnvironment
    from ddls_tpu.sim.jax_env import (build_episode_tables,
                                      build_job_bank, make_episode_fn)

    kwargs = make_env_kwargs(_make_dataset())
    kwargs["jobs_config"]["job_interarrival_time_dist"]["val"] = 50.0
    kwargs["jobs_config"]["num_training_steps"] = 20
    kwargs["max_simulation_run_time"] = 2e4  # canonical degree 16 kept
    env = RampJobPartitioningEnvironment(**kwargs)
    env.reset(seed=0)
    et = build_episode_tables(env)

    rng = np.random.RandomState(0)
    J, W = 420, 8
    # policy_shaped = the LEARNED policy's action stream: the shipped
    # checkpoints ARE FixedDegreePacking(d=8) at canonical scale
    # (docs/results_round5/rule_extraction.md), so the realistic caller
    # replays one degree and runs past the ~300-step memo transient.
    # The random stream (degrees drawn from the whole action space every
    # step) is the adversarial key-space bound; its D is trimmed because
    # the memo-OFF arm pays the full ~107 ms/decision degree-16 kernel
    # on every lane (docs/perf_round8).
    D = 400 if policy_shaped else 150

    def mk_bank(seed):
        r = np.random.RandomState(seed)
        recs = [{"model": et.types[int(r.randint(0, len(et.types)))],
                 "num_training_steps": 20,
                 "sla_frac": round(float(r.uniform(0.1, 1.0)), 2),
                 "time_arrived": 50.0 * i} for i in range(J)]
        return {k: jnp.asarray(v)
                for k, v in build_job_bank(et, recs).items()}

    if policy_shaped:
        actions = jnp.full((D,), 8, jnp.int32)
    else:
        actions = jnp.asarray(rng.choice([0, 1, 2, 4, 8, 16], size=D),
                              jnp.int32)
    bb = {k: jnp.stack([b[k] for b in (mk_bank(s) for s in range(W))])
          for k in mk_bank(0)}
    aa = jnp.broadcast_to(actions, (W, D))

    results = {}
    for arm, memo_cfg in (("memo_on", "auto"), ("memo_off", None)):
        fn = (make_episode_fn(et) if memo_cfg == "auto"
              else make_episode_fn(et, memo_cfg=None))
        vfn = jax.jit(jax.vmap(fn, in_axes=(0, 0)))
        t0 = time.perf_counter()
        out = jax.block_until_ready(vfn(bb, aa))
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = jax.block_until_ready(vfn(bb, aa))
        dt = time.perf_counter() - t0
        dec = int(np.asarray(out["trace"][5]).sum())
        results[arm] = {"wall_s": round(dt, 2),
                        "compile_s": round(compile_s, 1),
                        "decisions": dec,
                        "aggregate_dec_per_s": round(dec / dt, 2)}
        if memo_cfg == "auto":
            h = int(np.asarray(out["memo_hits"]).sum())
            m = int(np.asarray(out["memo_misses"]).sum())
            results[arm]["memo"] = {
                "hits": h, "misses": m,
                "evicts": int(np.asarray(out["memo_evicts"]).sum()),
                "hit_rate": round(h / (h + m), 4) if h + m else 0.0}
        # parity spot check: the timed arms must agree bit-for-bit
        results.setdefault("_trace5", np.asarray(out["trace"][5]))
        assert np.array_equal(results["_trace5"],
                              np.asarray(out["trace"][5]))
    trace5 = results.pop("_trace5")
    del trace5
    print(json.dumps({
        "mode": "memo_ab", "platform": jax.devices()[0].platform,
        "actions": "fixed_degree_8" if policy_shaped else "random",
        "width": W, "max_degree": 16, "decisions_per_lane": D,
        "memo_on": results["memo_on"], "memo_off": results["memo_off"],
        "speedup": round(results["memo_on"]["aggregate_dec_per_s"]
                         / results["memo_off"]["aggregate_dec_per_s"],
                         2),
    }), flush=True)


def mode_sebulba():
    jax = _force_cpu()
    assert len(jax.devices()) == 8, (
        "sebulba A/B needs the 8-virtual-device CPU mesh — run with "
        "XLA_FLAGS=--xla_force_host_platform_device_count=8")
    from ddls_tpu.train import make_epoch_loop

    B, T = 8, 32
    kwargs = make_env_kwargs(_make_dataset(), max_degree=2)
    # the --ab-degree 2 regime (docs/perf_round8.md): tiny pads so the
    # comparison measures the LOOPS, not the padded kernel
    kwargs["jobs_config"]["job_interarrival_time_dist"]["val"] = 50.0
    kwargs["jobs_config"]["num_training_steps"] = 20
    kwargs["max_simulation_run_time"] = 2e4
    model = {"fcnet_hiddens": [64],
             "custom_model_config": {"out_features_msg": 8,
                                     "out_features_hidden": 16,
                                     "out_features_node": 8,
                                     "out_features_graph": 8}}

    def make_loop(mode):
        lk = dict(
            path_to_env_cls="ddls_tpu.envs.partitioning_env."
                            "RampJobPartitioningEnvironment",
            env_config=kwargs, model=model,
            algo_config={"train_batch_size": B * T,
                         "sgd_minibatch_size": B * T,
                         "num_sgd_iter": 1, "num_workers": B,
                         "device_collector": True},
            num_envs=B, rollout_length=T, n_devices=8,
            use_parallel_envs=False, evaluation_interval=None, seed=0,
            metrics_sync_interval=1_000_000)
        if mode == "sebulba":
            lk["sebulba_config"] = {"actor_devices": 4}
        if mode == "fused":
            lk["fused_config"] = {"lanes": B, "segment_len": T}
        return make_epoch_loop("ppo", loop_mode=mode, **lk)

    modes = ["sebulba", "pipelined", "fused"]
    loops = {m: make_loop(m) for m in modes}
    for m, loop in loops.items():
        assert loop.loop_mode == m, (m, loop.loop_mode)

    def settle(loop):
        jax.block_until_ready(loop.state.params)

    for loop in loops.values():  # warm: compile + alias probes
        for _ in range(3):
            loop.run()
        settle(loop)

    rounds, k_epochs = 6, 3
    acc = {m: {"steps": 0, "wall": 0.0, "rates": []} for m in modes}
    for r in range(rounds):
        order = modes if r % 2 else list(reversed(modes))
        for m in order:
            loop = loops[m]
            t0 = time.perf_counter()
            steps = 0
            for _ in range(k_epochs):
                steps += loop.run()["env_steps_this_iter"]
            settle(loop)
            dt = time.perf_counter() - t0
            acc[m]["steps"] += steps
            acc[m]["wall"] += dt
            acc[m]["rates"].append(round(steps / dt, 2))
    out = {"mode": "sebulba_ab", "platform": "cpu",
           "devices": 8, "virtual_devices": True,
           "caveat": ("8 virtual CPU devices timeshare one socket: the "
                      "actor/learner overlap cannot show here — this "
                      "measures the split's dispatch/queue overhead "
                      "floor; the win case is real multi-chip silicon"),
           "num_envs": B, "rollout_length": T, "max_degree": 2,
           "rounds": rounds, "epochs_per_round": k_epochs}
    for m in modes:
        out[m] = {"env_steps_per_sec":
                  round(acc[m]["steps"] / acc[m]["wall"], 2),
                  "per_round": acc[m]["rates"]}
    ring = loops["sebulba"].ring_stats()
    out["sebulba"]["ring"] = {k: ring[k] for k in
                              ("segments", "leases", "stalls",
                               "publishes", "releases")}
    memo = loops["sebulba"].collector.memo_counters()
    memo["hit_rate"] = round(memo["hit_rate"], 4)
    out["sebulba"]["memo"] = memo
    for loop in loops.values():
        loop.close()
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    if sys.argv[1] == "memo":
        mode_memo(policy_shaped="--policy-shaped" in sys.argv[2:])
    else:
        {"sebulba": mode_sebulba}[sys.argv[1]]()
