"""Round-5 jitted-env measurements (VERDICT r4 items 8 + 9).

Modes:
  width   — vmap-width scaling of the replay episode kernel with the
            SAME bank replicated across lanes (round-4's table used
            different banks per lane, confounding lockstep cost with
            worst-lane trip-count variance), widths {1,2,4,8,16}.
  degree  — the canonical action space is degree 16
            (env_dev.yaml max_partitions_per_op: 16) but most jitted-env
            evidence is degree-8 pads; measure compile time + throughput
            of all three kernels (replay episode, policy episode,
            PPO segment) at degree 8 vs 16, with the product-size GNN.

Runs on the backend jax picks.
Prints one JSON line per measurement.
"""
import json
import sys
import time

import numpy as np

from _eval_common import _ROOT  # noqa: F401

sys.path.insert(0, _ROOT)
from bench import _make_dataset, make_env_kwargs  # noqa: E402


def build(max_degree: int):
    import jax.numpy as jnp

    from ddls_tpu.envs import RampJobPartitioningEnvironment
    from ddls_tpu.sim.jax_env import build_episode_tables, build_job_bank

    kwargs = make_env_kwargs(_make_dataset())
    kwargs["jobs_config"]["job_interarrival_time_dist"]["val"] = 50.0
    kwargs["jobs_config"]["num_training_steps"] = 20
    kwargs["max_simulation_run_time"] = 2e4
    kwargs["max_partitions_per_op"] = max_degree
    env = RampJobPartitioningEnvironment(**kwargs)
    env.reset(seed=0)
    et = build_episode_tables(env)

    def mk_bank(seed, J=420):
        r = np.random.RandomState(seed)
        recs = [{"model": et.types[int(r.randint(0, len(et.types)))],
                 "num_training_steps": 20,
                 "sla_frac": round(float(r.uniform(0.1, 1.0)), 2),
                 "time_arrived": 50.0 * i} for i in range(J)]
        return {k: jnp.asarray(v)
                for k, v in build_job_bank(et, recs).items()}

    return env, et, mk_bank


def mode_width():
    import jax
    import jax.numpy as jnp

    from ddls_tpu.sim.jax_env import make_episode_fn

    env, et, mk_bank = build(8)
    # memo off ON PURPOSE: this experiment measures the PLAIN kernel's
    # width scaling — with the wide probe (sim/jax_memo.py, round 12)
    # the memo would serve most lookaheads and the curve would measure
    # cache behaviour instead of the compute being scaled
    episode_fn = make_episode_fn(et, memo_cfg=None)
    rng = np.random.RandomState(0)
    D = 400
    actions = jnp.asarray(rng.choice([0, 1, 2, 4, 8], size=D), jnp.int32)
    bank = mk_bank(0)
    for w in (1, 2, 4, 8, 16):
        vfn = jax.jit(jax.vmap(episode_fn, in_axes=(0, 0)))
        bb = {k: jnp.stack([v] * w) for k, v in bank.items()}
        aa = jnp.broadcast_to(actions, (w, D))
        t0 = time.perf_counter()
        jax.block_until_ready(vfn(bb, aa))
        compile_s = time.perf_counter() - t0
        t0 = time.perf_counter()
        vout = jax.block_until_ready(vfn(bb, aa))
        dt = time.perf_counter() - t0
        vdec = int(np.asarray(vout["trace"][5]).sum())
        print(json.dumps({
            "mode": "width", "platform": jax.devices()[0].platform,
            "width": w, "identical_banks": True,
            "aggregate_dec_per_s": round(vdec / dt, 2),
            "per_lane_dec_per_s": round(vdec / dt / w, 2),
            "compile_s": round(compile_s, 1),
        }), flush=True)


def mode_degree():
    import jax
    import jax.numpy as jnp

    from ddls_tpu.models.policy import GNNPolicy
    from ddls_tpu.sim.jax_env import (build_obs_tables, make_episode_fn,
                                      make_policy_episode_fn,
                                      make_segment_fn, segment_init)

    rng0 = np.random.RandomState(0)
    for deg in (8, 16):
        env, et, mk_bank = build(deg)
        ot = build_obs_tables(env, et)
        bank = mk_bank(0)
        bank1 = mk_bank(1)
        D = 400
        degrees = [d for d in (0, 1, 2, 4, 8, 16) if d <= deg]
        actions = jnp.asarray(rng0.choice(degrees, size=D), jnp.int32)

        # replay kernel
        fn = make_episode_fn(et)
        t0 = time.perf_counter()
        jax.block_until_ready(fn(bank, actions))
        c = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = jax.block_until_ready(fn(bank1, actions))
        dt = time.perf_counter() - t0
        ndec = int(np.asarray(out["trace"][5]).sum())
        print(json.dumps({
            "mode": "degree", "kernel": "replay", "max_degree": deg,
            "platform": jax.devices()[0].platform,
            "pads": {"ops": et.pads.n_ops, "deps": et.pads.n_deps},
            "compile_s": round(c, 1),
            "dec_per_s": round(ndec / dt, 2)}), flush=True)

        # policy episode kernel (product-size GNN)
        model = GNNPolicy(n_actions=deg + 1)
        obs = env.reset(seed=0)
        params = model.init(jax.random.PRNGKey(0),
                            jax.tree_util.tree_map(jnp.asarray, obs))
        pfn = make_policy_episode_fn(et, ot, model)
        t0 = time.perf_counter()
        jax.block_until_ready(pfn(bank, params, jax.random.PRNGKey(1)))
        c = time.perf_counter() - t0
        t0 = time.perf_counter()
        out = jax.block_until_ready(pfn(bank1, params,
                                        jax.random.PRNGKey(2)))
        dt = time.perf_counter() - t0
        ndec = int(np.asarray(out["trace"][-1]).sum())
        print(json.dumps({
            "mode": "degree", "kernel": "policy_episode",
            "max_degree": deg, "compile_s": round(c, 1),
            "dec_per_s": round(ndec / dt, 2)}), flush=True)

        # segment kernel at the product collection shape (2 x 128)
        seg = make_segment_fn(et, ot, model, 128)
        vseg = jax.jit(jax.vmap(seg, in_axes=(0, None, 0, 0)))
        banks = {k: jnp.stack([bank[k], bank1[k]])
                 for k in bank}
        state = jax.vmap(lambda b: segment_init(et, b))(banks)
        rngs = jax.random.split(jax.random.PRNGKey(3), 2)
        t0 = time.perf_counter()
        state2, trace, _ = jax.block_until_ready(
            vseg(banks, params, state, rngs))
        c = time.perf_counter() - t0
        t0 = time.perf_counter()
        jax.block_until_ready(vseg(banks, params, state2, rngs))
        dt = time.perf_counter() - t0
        print(json.dumps({
            "mode": "degree", "kernel": "segment_2x128",
            "max_degree": deg, "compile_s": round(c, 1),
            "steps_per_s": round(2 * 128 / dt, 2)}), flush=True)


if __name__ == "__main__":
    {"width": mode_width, "degree": mode_degree}[sys.argv[1]]()
