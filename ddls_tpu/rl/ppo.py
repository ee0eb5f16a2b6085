"""Pure-JAX PPO learner, sharded over a device mesh.

This replaces the reference's RLlib ``PPOTrainer`` (SURVEY.md §2.7,
ddls/loops/rllib_epoch_loop.py:81): same algorithm — GAE, clipped surrogate
with adaptive-KL penalty, clipped value loss, entropy bonus, minibatched SGD
epochs — but as a single jitted SPMD program. The trajectory batch is sharded
over the mesh's ``dp`` axis and parameters are replicated, so XLA emits the
gradient all-reduce over ICI from the sharding annotations (the TPU-native
equivalent of RLlib's learner/worker gradient sync).

Tuned defaults follow the reference's PPO hyperparameters
(scripts/ramp_job_partitioning_configs/algo/ppo.yaml via BASELINE.md): lr
2.785e-4, gamma 0.997, clip 0.18, entropy 0.003, train batch 4000, SGD
minibatch 128, 50 SGD iters.

Everything under ``train_step`` is traced once: the SGD-epoch and minibatch
loops are ``lax.scan``s, so the whole update is one XLA computation per
compile — no per-minibatch dispatch from Python.
"""
from __future__ import annotations

import dataclasses
from functools import partial
from typing import Any, Callable, Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import optax
from flax import struct
from jax.sharding import NamedSharding, PartitionSpec as P

from ddls_tpu.parallel.mesh import (place_state_tree,
                                    replicated_sharding, shard_batch)
from ddls_tpu.telemetry import scopes


def traj_donate_argnums(state_argnum: int, *traj_argnums: int):
    """Donation plan for a jitted train step: on accelerator backends the
    state AND the staged trajectory/last_values buffers (shard_traj's
    device_put) are donated — the batch is consumed exactly once, so the
    staging copy of the largest arrays in the loop (the [T, B, ...] obs)
    disappears instead of outliving the update, and the state updates in
    place. Callers must treat shard_traj output as moved-from after
    train_step there.

    On CPU donation is DISABLED entirely (round 6, measured in
    docs/perf_round6.md): XLA:CPU cannot alias the staged batch into the
    update's outputs anyway ('donated buffers were not usable'), and —
    the load-bearing part — a donated jitted call EXECUTES INLINE on the
    dispatching thread instead of dispatching asynchronously, which
    serialises the update against all host work and defeats the
    pipelined loop's overlap. Bit-identical numerics either way.
    """
    import jax

    if jax.default_backend() == "cpu":
        return ()
    return (state_argnum,) + tuple(traj_argnums)


@dataclasses.dataclass
class PPOConfig:
    lr: float = 2.785e-4
    gamma: float = 0.997
    gae_lambda: float = 1.0
    clip_param: float = 0.18
    vf_clip_param: float = 10.0
    vf_loss_coeff: float = 1.0
    entropy_coeff: float = 0.003
    kl_coeff: float = 0.2
    kl_target: float = 0.01
    num_sgd_iter: int = 50
    sgd_minibatch_size: int = 128
    # consumed by the epoch loop, which sizes rollouts so that
    # rollout_length x num_envs == train_batch_size (the learner itself
    # takes whatever [T, B] batch it is handed)
    train_batch_size: int = 4000
    grad_clip: Optional[float] = None
    normalize_advantages: bool = True


class TrainState(struct.PyTreeNode):
    params: Any
    opt_state: Any
    kl_coeff: jnp.ndarray
    step: jnp.ndarray

    @classmethod
    def create(cls, params, tx, kl_coeff: float):
        return cls(params=params, opt_state=tx.init(params),
                   kl_coeff=jnp.asarray(kl_coeff, jnp.float32),
                   step=jnp.zeros((), jnp.int32))


def categorical_entropy(logits: jnp.ndarray) -> jnp.ndarray:
    """Entropy of softmax(logits); safe for -inf-masked logits."""
    logp = jax.nn.log_softmax(logits, axis=-1)
    p = jnp.exp(logp)
    return -jnp.sum(jnp.where(p > 0, p * logp, 0.0), axis=-1)


def compute_gae(rewards: jnp.ndarray, values: jnp.ndarray,
                dones: jnp.ndarray, last_values: jnp.ndarray,
                gamma: float, lam: float
                ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Generalised advantage estimation over [T, B] arrays.

    ``dones[t]`` marks that the episode ended at step t (no bootstrap
    across it). Returns (advantages, value_targets), both [T, B].
    """
    next_values = jnp.concatenate([values[1:], last_values[None]], axis=0)
    not_done = 1.0 - dones.astype(jnp.float32)
    deltas = rewards + gamma * next_values * not_done - values

    def scan_fn(carry, x):
        delta, nd = x
        adv = delta + gamma * lam * nd * carry
        return adv, adv

    _, advs = jax.lax.scan(scan_fn, jnp.zeros_like(last_values),
                           (deltas, not_done), reverse=True)
    return advs, advs + values


def ppo_loss(params, apply_fn: Callable, batch: Dict[str, jnp.ndarray],
             kl_coeff: jnp.ndarray, cfg: PPOConfig):
    """Clipped-surrogate PPO loss with KL penalty on one minibatch.

    ``batch``: obs (dict of [N, ...]), actions [N], old_logp [N],
    old_values [N], advantages [N], value_targets [N].
    """
    logits, values = apply_fn(params, batch["obs"])
    # invalid actions arrive already finfo.min-masked in the logits
    # (GNNPolicy), so the softmax family here needs no extra masking
    logp_all = jax.nn.log_softmax(logits, axis=-1)
    logp = jnp.take_along_axis(
        logp_all, batch["actions"][:, None].astype(jnp.int32),
        axis=-1)[:, 0]

    ratio = jnp.exp(logp - batch["old_logp"])
    advs = batch["advantages"]
    surr = jnp.minimum(
        ratio * advs,
        jnp.clip(ratio, 1.0 - cfg.clip_param, 1.0 + cfg.clip_param) * advs)
    policy_loss = -jnp.mean(surr)

    # sample-estimated KL(old || new), as RLlib's PPO uses for its
    # adaptive penalty
    kl = jnp.mean(batch["old_logp"] - logp)

    vf_err = (values - batch["value_targets"]) ** 2
    vf_clipped = batch["old_values"] + jnp.clip(
        values - batch["old_values"], -cfg.vf_clip_param, cfg.vf_clip_param)
    vf_err_clipped = (vf_clipped - batch["value_targets"]) ** 2
    vf_loss = 0.5 * jnp.mean(jnp.maximum(vf_err, vf_err_clipped))

    entropy = jnp.mean(categorical_entropy(logits))

    total = (policy_loss + kl_coeff * kl + cfg.vf_loss_coeff * vf_loss
             - cfg.entropy_coeff * entropy)
    metrics = {"policy_loss": policy_loss, "vf_loss": vf_loss, "kl": kl,
               "entropy": entropy, "total_loss": total,
               "clip_frac": jnp.mean(
                   (jnp.abs(ratio - 1.0) > cfg.clip_param).astype(
                       jnp.float32))}
    return total, metrics


class PPOLearner:
    """Owns the optimiser + jitted, mesh-sharded ``train_step``.

    ``apply_fn(params, obs) -> (logits [N, A], values [N])`` must accept a
    dict of batched observation arrays (see
    ``ddls_tpu.models.policy.batched_policy_apply``).
    """

    def __init__(self, apply_fn: Callable, cfg: PPOConfig, mesh,
                 shard_params_axis: str | None = None,
                 param_sharding: str = "replicated"):
        self.apply_fn = apply_fn
        self.cfg = cfg
        self.mesh = mesh
        # optional tensor parallelism: name a second mesh axis (e.g. "mp")
        # and eligible dense kernels are sharded over their output-feature
        # dim (parallel/mesh.py mp_tree_shardings); XLA emits the tp
        # collectives from the annotations. None = replicate (the default
        # 1-D dp plan; the policy net is small enough that dp alone is
        # usually right — SURVEY §2.10 MP row)
        self.shard_params_axis = shard_params_axis
        # declarative layout from the partition-rule table
        # (parallel/partition.py): "replicated" keeps today's exact
        # sharding objects (bit-identical jit programs); "fsdp"/"tp"
        # assign PartitionSpecs by regex over param-tree paths
        from ddls_tpu.parallel import partition as _partition

        _partition.validate_layout(param_sharding)
        if param_sharding != "replicated":
            if shard_params_axis is not None:
                raise ValueError(
                    "pass either param_sharding or the legacy "
                    "shard_params_axis, not both")
            _partition.validate_mesh_for_layout(mesh, param_sharding)
        self.param_sharding = param_sharding
        self._partition = _partition
        chain = []
        if cfg.grad_clip is not None:
            chain.append(optax.clip_by_global_norm(cfg.grad_clip))
        chain.append(optax.adam(cfg.lr))
        self.tx = optax.chain(*chain)

        self._replicated = replicated_sharding(mesh)
        self._batch_time = NamedSharding(mesh, P(None, "dp"))
        self._batch_only = NamedSharding(mesh, P("dp"))
        self._jit_train_step = None  # built per state layout in init_state
        self._jit_cache = {}  # state-layout key -> compiled jit wrapper
        self._jit_sample = jax.jit(self._sample_actions)

    def _state_shardings(self, state):
        """Sharding tree for a TrainState: replicated, rule-table sharded
        (partition.state_shardings — regex over paths, so params and their
        adam moments get identical specs via suffix matching), or
        tp-sharded by the legacy shape-based rule."""
        if self.param_sharding != "replicated":
            return self._partition.state_shardings(
                self.mesh, state, self.param_sharding)
        if self.shard_params_axis is None:
            return self._replicated
        from ddls_tpu.parallel.mesh import mp_tree_shardings

        return mp_tree_shardings(self.mesh, state,
                                 axis_name=self.shard_params_axis)

    # ------------------------------------------------------------- state
    def init_state(self, params) -> TrainState:
        # copy params: train_step donates its input state, and device_put
        # alone can alias the caller's arrays (which donation would delete)
        params = jax.tree_util.tree_map(jnp.copy, params)
        state = TrainState.create(params, self.tx, self.cfg.kl_coeff)
        shardings = self._state_shardings(state)
        # memoise the jit wrapper per state layout: a fresh jax.jit object
        # has an empty executable cache, so rebuilding it on every
        # init_state would recompile the scanned SGD update even when the
        # layout is unchanged (e.g. re-initialising params between trials)
        key = (jax.tree_util.tree_structure(state),
               tuple(str(getattr(s, "spec", s)) for s in
                     jax.tree_util.tree_leaves(shardings)))
        if key not in self._jit_cache:
            self._jit_cache[key] = jax.jit(
                self._train_step,
                in_shardings=(shardings, self._batch_time,
                              self._batch_only, self._replicated),
                out_shardings=(shardings, self._replicated),
                donate_argnums=traj_donate_argnums(0, 1, 2))
        self._jit_train_step = self._jit_cache[key]
        # multi-host-safe placement: device_put onto a global sharding
        # would run jax's per-leaf assert_equal broadcasts (gloo-
        # colliding under process skew); the state is process-identical
        # by the multi-host seed rules, so each process contributes its
        # copy collective-free (parallel/mesh.py:place_state_tree)
        return place_state_tree(state, shardings)

    # ------------------------------------------------------------ acting
    def _sample_actions(self, params, obs, rng):
        logits, values = self.apply_fn(params, obs)
        actions = jax.random.categorical(rng, logits, axis=-1)
        logp = jnp.take_along_axis(
            jax.nn.log_softmax(logits, axis=-1), actions[:, None],
            axis=-1)[:, 0]
        return actions, logp, values

    def sample_actions(self, params, obs, rng):
        """Batched action sampling: dict of [B, ...] -> (actions [B],
        logp [B], values [B])."""
        return self._jit_sample(params, obs, rng)

    # ----------------------------------------------------------- update
    def _minibatch_step(self, state, mb):
        with jax.named_scope(scopes.PPO_GRAD):
            grad_fn = jax.value_and_grad(ppo_loss, has_aux=True)
            (_, metrics), grads = grad_fn(state.params, self.apply_fn, mb,
                                          state.kl_coeff, self.cfg)
        with jax.named_scope(scopes.PPO_APPLY):
            updates, opt_state = self.tx.update(grads, state.opt_state,
                                                state.params)
            params = optax.apply_updates(state.params, updates)
        state = state.replace(params=params, opt_state=opt_state,
                              step=state.step + 1)
        return state, metrics

    @jax.named_scope(scopes.PPO_UPDATE)
    def _train_step(self, state: TrainState, traj: Dict[str, jnp.ndarray],
                    last_values: jnp.ndarray, rng: jnp.ndarray):
        """One PPO update on a [T, B] trajectory batch.

        GAE -> flatten to [N] -> num_sgd_iter epochs of shuffled
        minibatches (both loops are lax.scans). N must be divisible by
        sgd_minibatch_size x 1; the trailing remainder of each shuffled
        epoch is dropped, as in standard JAX PPO implementations.
        """
        cfg = self.cfg
        T, B = traj["rewards"].shape
        n = T * B
        D = self.mesh.shape["dp"]  # static; B % D enforced by shard_batch
        n_loc = n // D

        # [T, B, ...] -> [D, n_loc, ...] with the D axis sharded over dp.
        # Transpose-then-reshape only relabels the sharded B axis (B ->
        # (D, B/D)), so this flattening needs no cross-device movement.
        def to_rows(x):
            x = jnp.swapaxes(x, 0, 1)  # [B, T, ...]
            return x.reshape((D, n_loc) + x.shape[2:])

        with jax.named_scope(scopes.PPO_SHUFFLE):
            advs, targets = compute_gae(traj["rewards"], traj["values"],
                                        traj["dones"], last_values,
                                        cfg.gamma, cfg.gae_lambda)
            if cfg.normalize_advantages:
                advs = (advs - advs.mean()) / (advs.std() + 1e-8)
            flat = {
                "obs": jax.tree_util.tree_map(to_rows, traj["obs"]),
                "actions": to_rows(traj["actions"]),
                "old_logp": to_rows(traj["logp"]),
                "old_values": to_rows(traj["values"]),
                "advantages": to_rows(advs),
                "value_targets": to_rows(targets),
            }
        # each minibatch takes mb_loc samples from every device's shard, so
        # shuffling happens per shard (a batched local gather) rather than
        # as a global permutation that would all-gather the whole batch
        # across ICI every SGD epoch; with per-epoch reshuffles this
        # stratified scheme is statistically equivalent minibatch SGD
        mb_loc = max(min(cfg.sgd_minibatch_size, n) // D, 1)
        num_mb = n_loc // mb_loc

        def epoch(state, erng):
            def shuffle(x):
                # drop the remainder of each shard so the minibatch grid is
                # exact (num_mb * mb_loc <= n_loc)
                x = jax.vmap(lambda row, p: row[p[:num_mb * mb_loc]])(x, perms)
                x = x.reshape((D, num_mb, mb_loc) + x.shape[2:])
                x = jnp.swapaxes(x, 0, 1)  # [num_mb, D, mb_loc, ...]
                return x.reshape((num_mb, D * mb_loc) + x.shape[3:])

            with jax.named_scope(scopes.PPO_SHUFFLE):
                perms = jax.vmap(
                    lambda k: jax.random.permutation(k, n_loc))(
                        jax.random.split(erng, D))
                mbs = jax.tree_util.tree_map(shuffle, flat)
            state, ms = jax.lax.scan(self._minibatch_step, state, mbs)
            # mean over the epoch's minibatches, so the KL driving the
            # adaptive coefficient is a batch-wide estimate (as in RLlib),
            # not one arbitrary minibatch
            return state, jax.tree_util.tree_map(jnp.mean, ms)

        with jax.named_scope(scopes.PPO_SHUFFLE):
            epoch_rngs = jax.random.split(rng, cfg.num_sgd_iter)
        state, metrics_per_epoch = jax.lax.scan(epoch, state, epoch_rngs)
        metrics = jax.tree_util.tree_map(lambda m: m[-1], metrics_per_epoch)

        # RLlib-style adaptive KL coefficient update
        kl = metrics["kl"]
        with jax.named_scope(scopes.PPO_APPLY):
            kl_coeff = jnp.where(
                kl > 2.0 * cfg.kl_target, state.kl_coeff * 1.5,
                jnp.where(kl < 0.5 * cfg.kl_target, state.kl_coeff * 0.5,
                          state.kl_coeff))
        state = state.replace(kl_coeff=kl_coeff)
        metrics["kl_coeff"] = kl_coeff
        return state, metrics

    def train_step(self, state: TrainState, traj: Dict[str, jnp.ndarray],
                   last_values, rng):
        """Jitted sharded update. ``traj`` leaves are [T, B, ...] with the
        B axis sharded over the mesh's dp axis (see shard_traj)."""
        if self._jit_train_step is None:
            raise RuntimeError("call init_state() before train_step(): the "
                               "update is compiled for the state's layout")
        return self._jit_train_step(state, traj, last_values, rng)

    def shard_traj(self, traj: Dict[str, Any], last_values):
        """Place a host trajectory on the mesh: [T, B, ...] leaves sharded
        over B; last_values [B] sharded over its only axis."""
        traj = shard_batch(self.mesh, traj, batch_axis=1)
        last_values = shard_batch(self.mesh, last_values, batch_axis=0)
        return traj, last_values
