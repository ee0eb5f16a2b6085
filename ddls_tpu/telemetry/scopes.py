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
#: memo key, hash, residual compare and the select of stored over
#: computed (`sim/jax_memo.py:memo_probe` minus the lookahead it wraps),
#: and the row writes of `memo_commit`, which stands outside every
#: ``lax.cond``
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

# The scopes that ENCLOSE others. ``ALL`` are leaves around eight calls;
# what runs between them (a ``cond`` and what ``vmap`` makes of it, a
# scan's step, the staging around a loop) gets its name from one of
# these, and is read as the enclosing scope's SELF time: its operations
# less those of its children (`benchmarks/reduce/scope_tree.py`).

#: the body of `sim/jax_env.py:make_segment_fn`'s ``segment``: sampling
#: and log-prob, counters, the in-kernel reset's selects, the trace row,
#: the scan's carries
SIM_SEGMENT = "sim_segment"
#: all of `_episode_kernels.decision`, its ``lax.cond`` included (what
#: ``vmap`` emits for a ``cond`` is bound under the call's name stack):
#: the selects over the branch outputs, `placement_masks`, the verdict,
#: the scenario adjustment, the commit of slots and channels
SIM_DECIDE = "sim_decide"
#: the whole of `sim/jax_lookahead.py:jax_lookahead` on the block path:
#: `to_servers`, the stage gathers, `endpoint_onehots`, `_results`, the
#: scatter back — everything around the tick loops, which keep
#: ``SIM_LOOKAHEAD``
SIM_LOOKAHEAD_CALL = "sim_lookahead_call"
#: GAE, ``to_rows``, the per-epoch permutation and the minibatch gather
PPO_SHUFFLE = "ppo_shuffle"
#: ``value_and_grad(ppo_loss)`` of one minibatch
PPO_GRAD = "ppo_grad"
#: ``tx.update`` + ``apply_updates`` of one minibatch, and the KL
#: coefficient
PPO_APPLY = "ppo_apply"

#: the tree's root: the jitted program itself, which has no scope name
ROOT = None

#: THE tree, stated once: parent -> the scopes traced directly under it.
#: A name may stand under two parents (the policy acts inside the
#: segment and bootstraps outside it; the memo is probed inside the
#: decision and committed beside it). A node's self time is what runs
#: under its name and under none of its children's; a new scope goes in
#: here WITH a committed metric that reads it, or not in at all
#: (`tests/benchmarks/test_bench_scope_tree.py` holds the metric files
#: to this dict).
TREE = {
    ROOT: (SIM_SEGMENT, ENV_OBS, POLICY_FORWARD, PPO_UPDATE),
    SIM_SEGMENT: (ENV_OBS, POLICY_FORWARD, SIM_DECIDE, SIM_MEMO_PROBE,
                  SIM_ADVANCE),
    SIM_DECIDE: (SIM_ALLOCATE, SIM_PRICE, SIM_MEMO_PROBE,
                 SIM_LOOKAHEAD_CALL),
    SIM_LOOKAHEAD_CALL: (SIM_LOOKAHEAD,),
    PPO_UPDATE: (PPO_SHUFFLE, PPO_GRAD, PPO_APPLY),
}
