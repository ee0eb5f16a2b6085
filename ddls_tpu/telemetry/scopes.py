"""The ``jax.named_scope`` names of the device program's layers — ONE
home, constants only, so a rename is one edit and a test can compare
this list with what the benchmark's ``layer_metrics/*.json`` match.

A scope is metadata on the traced ops (the HLO ``op_name`` path, which a
TPU profile carries per instruction as the ``tf_op`` stat): it costs
nothing at run time and names the same code in every program that
traces it (episode kernels, device collector, Sebulba, the fused epoch,
the standalone ``jit__train_step``). Under ``vmap``/``jvp``/``transpose``
the name stack wraps a scope (``vmap(sim_lookahead)``), so readers match
a scope as a path segment, wrapped or not.
"""

#: placement scan (`sim/jax_env.py:jax_allocate_job`)
SIM_ALLOCATE = "sim_allocate"
#: dep pricing + SRPT scores (`sim/jax_env.py:jax_price_and_score`)
SIM_PRICE = "sim_price"
#: memo key, hash, residual compare, select and insert
#: (`sim/jax_memo.py:memo_lookahead` minus the lookahead it wraps)
SIM_MEMO_PROBE = "sim_memo_probe"
#: the lookahead tick engine's ``lax.while_loop`` (`sim/jax_lookahead.py`)
SIM_LOOKAHEAD = "sim_lookahead"
#: the event-clock ``while`` between decisions (`_episode_kernels.advance`)
SIM_ADVANCE = "sim_advance"
#: in-kernel observation rebuild (`_kernel_obs` and its field gathers)
ENV_OBS = "env_obs"
#: the policy forward that ACTS (segment/episode step, bootstrap values)
POLICY_FORWARD = "policy_forward"
#: the PPO learner's update (`rl/ppo.py:PPOLearner._train_step`)
PPO_UPDATE = "ppo_update"

ALL = (SIM_ALLOCATE, SIM_PRICE, SIM_MEMO_PROBE, SIM_LOOKAHEAD, SIM_ADVANCE,
       ENV_OBS, POLICY_FORWARD, PPO_UPDATE)
