"""Evolution strategies trained ENTIRELY on device via jitted episodes.

The §5.8 end-state for one algorithm family: the fitness of every
population member is a full environment episode run inside jit
(`sim/jax_env.py:make_policy_episode_fn` — placement, pricing, lookahead,
event clock, observation, policy forward, sampling all in one `lax.scan`),
vmapped over the antithetic population. One device dispatch evaluates the
whole generation; the ES gradient estimate and parameter update
(`rl/es.py:ESLearner`) are jitted too, so a training generation never
touches a host simulator: population-parallel episodes per dispatch
instead of one device round trip per host-driven decision.

The host keeps only the outer generation loop and job-bank sampling
(workload arrivals are data, not computation).
"""
from __future__ import annotations

from typing import Callable, Dict

import numpy as np


def make_generation_fn(episode_fn: Callable, learner):
    """(state, stacked_params, eps, bank, rng) -> (new_state, fitness).

    ``episode_fn`` from `make_policy_episode_fn`; ``stacked_params``/
    ``eps`` from `ESLearner.perturb`. Every population member rolls one
    full episode on the SAME job bank, and each antithetic pair shares
    one action-sampling key, so within-pair fitness differences are pure
    policy effects (common random numbers)."""
    import jax

    def generation(state, stacked_params, eps, bank, rng):
        import jax.numpy as jnp

        pop = jax.tree_util.tree_leaves(stacked_params)[0].shape[0]
        # common random numbers WITHIN each antithetic pair: the +eps and
        # -eps members share one action-sampling key (perturb stacks
        # [plus, minus], es.py:110-117), so their fitness difference is a
        # pure policy effect, not sampling noise
        half_rngs = jax.random.split(rng, pop // 2)
        rngs = jnp.concatenate([half_rngs, half_rngs])
        out = jax.vmap(episode_fn, in_axes=(None, 0, 0))(
            bank, stacked_params, rngs)
        fitness = out["ret"]
        new_state, metrics = learner.update(state, eps, fitness)
        return new_state, fitness

    return jax.jit(generation)


def train_es_on_device(et, ot, model, learner, params,
                       sample_bank: Callable[[int], Dict],
                       n_generations: int,
                       seed: int = 0,
                       verbose: bool = False):
    """Outer ES loop: everything inside a generation is one jitted
    program. Returns (final_params, history)."""
    import jax

    from ddls_tpu.sim.jax_env import make_policy_episode_fn

    # wide memo ON (the make_policy_episode_fn default): the generation
    # vmaps the episode over the population and the batched probe masks
    # hit lanes out of the lookahead while_loop — every population
    # member carries its own table and hits its cache (ISSUE 17)
    episode_fn = make_policy_episode_fn(et, ot, model)
    generation_fn = make_generation_fn(episode_fn, learner)
    state = learner.init_state(params)
    rng = jax.random.PRNGKey(seed)
    history = []
    for g in range(n_generations):
        rng, r_perturb, r_run = jax.random.split(rng, 3)
        stacked, eps = learner.perturb(state.params, r_perturb)
        bank = sample_bank(g)
        state, fitness = generation_fn(state, stacked, eps, bank, r_run)
        fit = np.asarray(fitness)
        history.append({"generation": g, "fitness_mean": float(fit.mean()),
                        "fitness_max": float(fit.max()),
                        "fitness_min": float(fit.min())})
        if verbose:
            print(f"generation {g}: fitness mean {fit.mean():.2f} "
                  f"max {fit.max():.2f}", flush=True)
    return state.params, history
