"""Profile one PPO epoch at the CPU bench shape (VERDICT r4 item 2).

Breaks the epoch into the four phases the verdict asks for — obs
encode/stack, batched sampling dispatch, env stepping, jitted update —
by wall clock, and cProfiles the collect phase to find the top sinks
inside it. Writes a breakdown table to stdout.

Run: JAX_PLATFORMS=cpu python scripts/experiments/profile_ppo_loop.py
"""
from __future__ import annotations

import cProfile
import io
import pstats
import sys
import time

import numpy as np

sys.path.insert(0, ".")
import bench  # noqa: E402


def main() -> None:
    import jax

    from ddls_tpu.models.policy import GNNPolicy, batched_policy_apply
    from ddls_tpu.parallel.mesh import make_mesh
    from ddls_tpu.rl.ppo import PPOConfig, PPOLearner
    from ddls_tpu.rl import rollout as rollout_mod
    from ddls_tpu.rl.rollout import RolloutCollector, stack_obs

    num_envs, rollout_length, num_sgd_iter = 4, 16, 10

    model = GNNPolicy(n_actions=17)
    vec = bench._make_vec_env(bench._make_dataset(), num_envs)
    vec.reset()
    single = jax.tree_util.tree_map(np.asarray, vec.obs[0])
    params = model.init(jax.random.PRNGKey(0), single)
    mesh = make_mesh(len(jax.devices()))
    batch = num_envs * rollout_length
    cfg = PPOConfig(num_sgd_iter=num_sgd_iter,
                    sgd_minibatch_size=min(128, batch),
                    train_batch_size=batch)
    learner = PPOLearner(lambda p, o: batched_policy_apply(model, p, o),
                         cfg, mesh)
    state = learner.init_state(params)
    collector = RolloutCollector(vec, learner, rollout_length)

    # instrument phases by monkeypatching the collector's collaborators
    phase = {"stack": 0.0, "sample": 0.0, "env": 0.0}

    orig_stack = rollout_mod.stack_obs

    def timed_stack(obs_list):
        t0 = time.perf_counter()
        out = orig_stack(obs_list)
        phase["stack"] += time.perf_counter() - t0
        return out

    orig_sample = learner.sample_actions

    def timed_sample(params, obs, rng):
        t0 = time.perf_counter()
        out = orig_sample(params, obs, rng)
        out = jax.block_until_ready(out)
        phase["sample"] += time.perf_counter() - t0
        return out

    orig_step = vec.step

    def timed_step(actions):
        t0 = time.perf_counter()
        out = orig_step(actions)
        phase["env"] += time.perf_counter() - t0
        return out

    rollout_mod.stack_obs = timed_stack
    learner.sample_actions = timed_sample
    vec.step = timed_step

    rng = jax.random.PRNGKey(1)

    def one_epoch(state, rng, timings):
        t0 = time.perf_counter()
        out = collector.collect(state.params, rng)
        timings["collect"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        straj, slv = learner.shard_traj(out["traj"], out["last_values"])
        timings["shard"] = time.perf_counter() - t0
        t0 = time.perf_counter()
        state, metrics = learner.train_step(state, straj, slv, rng)
        jax.block_until_ready(metrics["total_loss"])
        timings["update"] = time.perf_counter() - t0
        return state, out

    # warmup (compiles)
    rng, sub = jax.random.split(rng)
    t0 = time.perf_counter()
    state, _ = one_epoch(state, sub, {})
    print(f"warmup epoch (incl. compile): {time.perf_counter()-t0:.2f}s",
          flush=True)

    # timed epochs with phase attribution
    n_epochs = 3
    for k in phase:
        phase[k] = 0.0
    timings_sum = {"collect": 0.0, "shard": 0.0, "update": 0.0}
    t_all = time.perf_counter()
    for _ in range(n_epochs):
        rng, sub = jax.random.split(rng)
        timings = {}
        state, out = one_epoch(state, sub, timings)
        for k in timings_sum:
            timings_sum[k] += timings[k]
    total = time.perf_counter() - t_all
    steps = n_epochs * num_envs * rollout_length

    print(f"\n=== {n_epochs} epochs, {steps} env-steps, "
          f"{total:.2f}s total -> {steps/total:.1f} env-steps/s ===")
    print(f"{'phase':<22}{'sec':>8}{'%':>7}")
    for k, v in timings_sum.items():
        print(f"{k:<22}{v:>8.2f}{100*v/total:>6.1f}%")
    print("-- inside collect --")
    for k, v in phase.items():
        print(f"  {k:<20}{v:>8.2f}{100*v/total:>6.1f}%")
    other = timings_sum["collect"] - sum(phase.values())
    print(f"  {'other(buf/rng/np)':<20}{other:>8.2f}{100*other/total:>6.1f}%")

    # cProfile one collect to see inside env stepping + stack
    rollout_mod.stack_obs = orig_stack
    learner.sample_actions = orig_sample
    vec.step = orig_step
    rng, sub = jax.random.split(rng)
    pr = cProfile.Profile()
    pr.enable()
    collector.collect(state.params, sub)
    pr.disable()
    s = io.StringIO()
    ps = pstats.Stats(pr, stream=s).sort_stats("cumulative")
    ps.print_stats(45)
    print("\n=== cProfile of one collect ===")
    print(s.getvalue())

    vec.close()


if __name__ == "__main__":
    main()
