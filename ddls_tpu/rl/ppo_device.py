"""PPO with ON-DEVICE rollout collection.

The collection half of PPO-on-device (§5.8): fixed-length [T, B]
segments are produced by `sim/jax_env.py:make_segment_fn` — the entire
environment (placement, pricing, lookahead, event clock, observation,
policy forward, sampling) runs inside one jitted scan per env, vmapped
over B job banks, with episodes resetting in-kernel. The host
reconstructs the exact observations from the compact trace
(`rebuild_obs_batch` — bit-equal to what the kernel's policy forward
saw) and feeds the EXISTING `PPOLearner.shard_traj`/`train_step`.

This replaces T×B host→device round trips per collect with ONE
dispatch.
"""
from __future__ import annotations

from typing import Dict

import numpy as np

from ddls_tpu.sim.jax_memo import MemoCounters


class DevicePPOCollector(MemoCounters):
    """Drop-in counterpart of `rl/rollout.py:RolloutCollector` whose envs
    live on device. ``banks`` is a dict of stacked job-bank arrays with a
    leading B axis (same shapes per bank).

    With ``mesh`` (a 1-D+ ``jax.sharding.Mesh`` with a ``dp`` axis), the
    lane axis is SHARDED over the mesh's dp devices: each device runs its
    own lanes' episodes inside the one jitted dispatch (the vmapped scan
    is embarrassingly parallel over lanes, so XLA partitions it with no
    collectives). This is the pod collection shape — the update already
    shards its batch over the same mesh, so without it a multi-chip
    slice would collect on one chip and update on all. Requires
    ``num_envs`` divisible by the dp axis size.

    ``params_shardings`` (optional, mesh mode only) is the sharding tree
    the learner keeps its params in (``parallel/partition.py`` — fsdp/tp
    layouts); the collector's jitted forwards declare it as the params
    in_sharding so sharded params enter the in-scan forward as-is (XLA
    inserts the layout's gathers INSIDE the program) instead of being
    implicitly replicated at dispatch. Default keeps today's replicated
    in_sharding — bit-identical programs.

    ``memo_cfg`` wires the in-kernel lookahead memo (sim/jax_memo.py):
    ``"auto"`` (default) enables it at EVERY lane count — the batched
    probe masks hit lanes out of the lookahead while_loop, so the
    vmapped lanes hit their own per-lane tables too (ISSUE 17). Memo
    hit/miss counters ride the per-collect trace and
    ``memo_counters()`` exposes the cumulative totals summed over lanes
    (drain boundaries only)."""

    def __init__(self, et, ot, model, banks: Dict, rollout_length: int,
                 mesh=None, memo_cfg="auto", params_shardings=None):
        import jax
        import jax.numpy as jnp

        from ddls_tpu.rl.ppo import traj_donate_argnums
        from ddls_tpu.sim.jax_env import (make_segment_fn, segment_init,
                                          vmap_segment_fn)
        from ddls_tpu.sim.jax_memo import resolve_memo_cfg

        self.et, self.ot, self.model = et, ot, model
        self.rollout_length = rollout_length
        self.num_envs = int(jax.tree_util.tree_leaves(banks)[0].shape[0])
        self.mesh = mesh
        self.memo_cfg = resolve_memo_cfg(memo_cfg, self.num_envs)
        segment = make_segment_fn(et, ot, model, rollout_length,
                                  memo_cfg=self.memo_cfg)
        lane_segment = vmap_segment_fn(segment, self.num_envs)
        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            if self.num_envs % mesh.shape["dp"] != 0:
                raise ValueError(
                    f"num_envs {self.num_envs} must divide over the "
                    f"mesh dp axis ({mesh.shape['dp']})")
            lane = NamedSharding(mesh, P("dp"))
            repl = NamedSharding(mesh, P())
            # fsdp/tp params enter with the learner's layout declared, so
            # dispatch never implicitly replicates them (the gathers live
            # inside the compiled program instead)
            p_sh = repl if params_shardings is None else params_shardings
            banks = jax.device_put(banks, lane)
            # rngs/state arrive as host (or mismatched) arrays; jit's
            # explicit in_shardings reshards them on dispatch. The env
            # state (argnum 2) is donated on accelerator backends: each
            # collect replaces it with the returned state, so the old
            # buffers can back the new ones in place instead of doubling
            # the per-lane sim state (CPU donation disabled — it forces
            # inline execution of the jitted call, ppo.traj_donate_argnums)
            self._vseg = jax.jit(
                lane_segment,
                in_shardings=(lane, p_sh, lane, lane),
                out_shardings=(lane, lane, lane),
                donate_argnums=traj_donate_argnums(2))
        else:
            if params_shardings is not None:
                raise ValueError(
                    "params_shardings requires a mesh: the sharded-params "
                    "layouts only exist on a device mesh")
            self._vseg = jax.jit(lane_segment,
                                 donate_argnums=traj_donate_argnums(2))
        self.banks = banks
        # jitted bootstrap-value forward: one compiled dispatch per
        # collect instead of an eager op-by-op chain — and the SAME
        # compiled math as the fused epoch's in-scan bootstrap
        # (rl/fused.py), whose x64 parity pin requires the two paths to
        # round identically. Two ingredients of that bit-equality:
        # jitted not eager (eager fuses nothing and differs at the last
        # f32 ulp), and the same PARTITIONING — under a mesh the fused
        # bootstrap consumes lane-sharded obs, so the standalone one
        # must shard its batch axis identically or the partitioned
        # segment-sum accumulation order diverges
        from ddls_tpu.models.policy import batched_policy_apply

        if mesh is not None:
            from jax.sharding import NamedSharding, PartitionSpec as P

            self._jit_apply = jax.jit(
                lambda p, o: batched_policy_apply(model, p, o),
                in_shardings=(p_sh if params_shardings is not None
                              else NamedSharding(mesh, P()),
                              NamedSharding(mesh, P("dp"))))
        else:
            self._jit_apply = jax.jit(
                lambda p, o: batched_policy_apply(model, p, o))
        # per-env initial state from each env's OWN bank (arrival clocks
        # differ across banks)
        self._state = jax.vmap(
            lambda b: segment_init(et, b, self.memo_cfg))(banks)
        if mesh is not None:
            # same jit cache key as the state each collect returns (jax
            # keys on the mesh an input's sharding names — rl/fused.py)
            self._state = jax.device_put(self._state, lane)
        # per-lane decision count of the in-flight episode (episodes span
        # segment boundaries; the kernel's counters reset in-kernel at
        # done, so length is tracked here)
        self._ep_len = np.zeros(self.num_envs, np.int64)

    def collect(self, params, rng) -> Dict:
        """One [T, B] segment batch; returns the PPOLearner traj dict
        plus bootstrap values."""
        import jax

        from ddls_tpu.sim.jax_env import rebuild_obs_batch

        rngs = jax.random.split(rng, self.num_envs)
        self._state, trace, next_fields = self._vseg(
            self.banks, params, self._state, rngs)
        trace = {k: np.asarray(v) for k, v in trace.items()}
        # kernel trace is [B, T]; the learner wants [T, B]
        trace = {k: np.swapaxes(v, 0, 1) for k, v in trace.items()}
        obs = rebuild_obs_batch(self.et, self.ot, trace)
        traj = {
            "obs": obs,
            "actions": trace["action"].astype(np.int32),
            "logp": trace["logp"].astype(np.float32),
            "values": trace["value"].astype(np.float32),
            "rewards": trace["reward"].astype(np.float32),
            "dones": trace["done"].astype(bool),
        }
        next_obs = rebuild_obs_batch(self.et, self.ot, {
            k: np.asarray(v) for k, v in next_fields.items()})
        next_obs = {k: np.asarray(v) for k, v in next_obs.items()}
        if self.mesh is not None and jax.process_count() > 1:
            # multi-process jax rejects numpy inputs against the jit's
            # non-trivial (dp-sharded) in_shardings even on this fully-
            # addressable LOCAL mesh — stage explicitly first (device_put
            # to a local sharding is collective-free; same program, same
            # bits as the single-process path)
            from jax.sharding import NamedSharding, PartitionSpec as P

            next_obs = jax.device_put(
                next_obs, NamedSharding(self.mesh, P("dp")))
        _, last_values = self._jit_apply(params, next_obs)
        return {"traj": traj,
                "last_values": np.asarray(last_values, np.float32),
                "env_steps": self.rollout_length * self.num_envs,
                "episodes": self._harvest_episodes(trace)}

    def _harvest_episodes(self, trace) -> list:
        """Episode records at done boundaries, from the traced in-kernel
        counters — the device counterpart of
        `rollout.py:harvest_episode_record`, using the HOST denominators:
        ``acceptance_rate`` = completed/arrived and ``blocking_rate`` =
        blocked/arrived where arrived counts every job that entered the
        queue, decided or not (cluster.py:1020-1023; the kernel traces
        the arrival pointer as ``ep_arrived``), so device- and
        host-collected runs log comparable rates."""
        episodes = []
        done = trace["done"]  # [T, B] after the caller's swap
        T, B = done.shape
        for t in range(T):
            self._ep_len += 1
            for b in np.nonzero(done[t])[0]:
                blk = int(trace["ep_blocked"][t, b])
                com = int(trace["ep_completed"][t, b])
                arr = int(trace["ep_arrived"][t, b])
                episodes.append({
                    "env_index": int(b),
                    "episode_return": float(trace["ep_return"][t, b]),
                    "episode_length": int(self._ep_len[b]),
                    "num_jobs_arrived": arr,
                    "num_jobs_completed": com,
                    "num_jobs_blocked": blk,
                    "acceptance_rate": com / arr if arr else 0.0,
                    "blocking_rate": blk / arr if arr else 0.0,
                })
                self._ep_len[b] = 0
        return episodes
