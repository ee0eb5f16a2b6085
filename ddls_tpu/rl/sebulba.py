"""Sebulba actor/learner device split over the trajectory ring.

The second Podracer architecture (arXiv 2104.06272; Anakin — the fused
single-program loop — landed in rl/fused.py): actor lanes are PINNED to
a sub-mesh of the local devices and the learner update to the
complement, so collection and update run on DISJOINT silicon and can
overlap instead of time-slicing one program. The actor half is the
fused driver's in-kernel collection (``make_segment_fn(trace_obs=True)``
+ the jitted bootstrap forward, one dispatch per segment, nothing
leaves the device); the learner half is the UNCHANGED standalone
``train_step`` jitted over the learner sub-mesh.

The actor→learner queue is a DEVICE-MODE trajectory ring
(``rl/ring.py``, slab-less segments): each collect leases a segment,
publishes it, and the existing two-phase token protocol releases it —
phase 1's token is the trajectory ``device_put`` onto the learner
sub-mesh (ready exactly when the device-to-device transfer completes;
with no host views the alias verdict is trivially "copied"), phase 2's
unconditional update-output token covers donating backends deleting
the staged buffers at dispatch. Lease backpressure bounds the in-flight
batches to the ring size, and depth-K staleness accounting
(``params_age_updates``, IMPALA's ``clip_rho_fraction`` gauge) rides
along unchanged from the round-10 ring.

Steady-state epochs are TRANSFER-FREE under
``jax.transfer_guard("disallow")``: every cross-mesh hop — params
learner→actor, per-lane rngs, trajectory actor→learner — is an
EXPLICIT ``device_put`` (the defining traffic of the split), episode
counters stay device-resident until the fused-style drain boundaries,
and the trace-obs trajectory never visits the host (the
``DevicePPOCollector`` host hop is exactly what this driver removes).

Bit-exactness vs the sequential device-collector path holds at MATCHED
partitioning (same actor mesh for collection, same learner mesh for the
update — the bootstrap forward's partitioned segment-sum accumulation
order depends on the dp width, rl/ppo_device.py): the parity driver in
tests/test_sebulba.py pins depth-0 PPO params bitwise.

Single-process only (the split partitions LOCAL devices); DQN/ES reject
loudly in train/loops.py — the same device-collection contract as the
fused loop.
"""
from __future__ import annotations

from typing import Dict, Optional

import numpy as np

from ddls_tpu import telemetry
from ddls_tpu.rl.fused import EPISODE_TRACE_KEYS
from ddls_tpu.rl.ring import TrajRing
from ddls_tpu.sim.jax_memo import MemoCounters
from ddls_tpu.telemetry import scopes


def split_meshes(actor_devices: Optional[int] = None, devices=None):
    """Partition the local devices into the actor sub-mesh and the
    learner complement: actor = first ``actor_devices`` devices
    (default: half), learner = the rest. Raises ``ValueError`` when the
    split is infeasible (< 2 devices, or an explicit count leaving
    either side empty) — callers decide whether that is a loud fallback
    (auto sizing) or a config error (explicit sizing)."""
    import jax

    from ddls_tpu.parallel.mesh import make_mesh

    devs = list(devices) if devices is not None else jax.local_devices()
    if len(devs) < 2:
        raise ValueError(
            f"sebulba needs >= 2 local devices to split (got "
            f"{len(devs)}): actor lanes and the learner update must "
            "live on disjoint sub-meshes")
    a = len(devs) // 2 if actor_devices is None else int(actor_devices)
    if not 1 <= a <= len(devs) - 1:
        raise ValueError(
            f"sebulba actor_devices={a} must leave both sub-meshes "
            f"non-empty over {len(devs)} local devices")
    return (make_mesh(devices=devs[:a]), make_mesh(devices=devs[a:]))


class SebulbaCollector(MemoCounters):
    """Actor-side collector of the Sebulba split: ``collect(params,
    rng)`` runs one [T, B] segment batch entirely on the ACTOR sub-mesh
    and returns DEVICE trajectories for the learner to ``shard_traj``
    onto its own sub-mesh (the explicit device-to-device staging hop).

    Duck-types ``DevicePPOCollector``'s out dict, plus the ring keys
    the epoch loop's two-phase token protocol consumes
    (``ring``/``ring_segment``/``ring_generation`` — rl/rollout.py's
    shm contract) and ``ep_pending`` (the [B, T] device episode-counter
    trace, drained fused-style at sync boundaries instead of per
    collect — ``out["episodes"]`` is always empty here).

    ``memo_cfg`` follows the device-collector contract: ``"auto"``
    enables the in-kernel lookahead memo at every lane count (the
    round-12 batched probe — sim/jax_memo.py).

    ``param_layout`` names the LEARNER's partition-rule layout
    (``parallel/partition.py``); the learner→actor hop always lands
    replicated on the actor sub-mesh, so a sharded layout makes that
    ``device_put`` a gather-to-actor-layout — the transfer-ledger name
    carries the resolved layout (``sebulba.params[gather-from-fsdp]``)
    so cross-mesh byte counts stay attributable per layout."""

    def __init__(self, et, ot, model, banks: Dict, rollout_length: int,
                 actor_mesh, ring_segments: int = 2, memo_cfg="auto",
                 param_layout: str = "replicated"):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ddls_tpu.models.policy import batched_policy_apply
        from ddls_tpu.rl.ppo import traj_donate_argnums
        from ddls_tpu.sim.jax_env import (_kernel_obs, make_segment_fn,
                                          segment_init, vmap_segment_fn)
        from ddls_tpu.sim.jax_memo import resolve_memo_cfg

        self.et, self.ot, self.model = et, ot, model
        self.rollout_length = int(rollout_length)
        self.num_envs = int(jax.tree_util.tree_leaves(banks)[0].shape[0])
        self.mesh = actor_mesh
        self.param_layout = str(param_layout)
        # layout-attributed transfer name (telemetry_report groups the
        # ledger by name, so the gather shows up as its own row)
        self._params_hop_name = (
            "sebulba.params" if self.param_layout == "replicated"
            else f"sebulba.params[gather-from-{self.param_layout}]")
        self.memo_cfg = resolve_memo_cfg(memo_cfg, self.num_envs)
        B, T = self.num_envs, self.rollout_length
        if B % actor_mesh.shape["dp"] != 0:
            raise ValueError(
                f"num_envs {B} must divide over the actor sub-mesh dp "
                f"axis ({actor_mesh.shape['dp']})")
        self._lane = NamedSharding(actor_mesh, P("dp"))
        self._repl = NamedSharding(actor_mesh, P())
        batch_time = NamedSharding(actor_mesh, P(None, "dp"))
        batch_only = self._lane
        self.banks = jax.device_put(banks, self._lane)
        # placed on the lane sharding: the same jit cache key as the
        # state each collect returns (jax keys on the mesh an input's
        # sharding names — rl/fused.py)
        self._state = jax.device_put(jax.vmap(
            lambda b: segment_init(et, b, self.memo_cfg))(self.banks),
            self._lane)
        self._ep_len = np.zeros(B, np.int64)

        segment = make_segment_fn(et, ot, model, T, trace_obs=True,
                                  memo_cfg=self.memo_cfg,
                                  trace_trips=True)
        lane_segment = vmap_segment_fn(segment, B)

        def actor_round(bb, params, sim_state, lane_rngs):
            """One segment + its bootstrap forward, ONE dispatch on the
            actor sub-mesh. Mirrors rl/fused.py's one_round collection
            half exactly (trace_obs trajectory, same f64-then-f32
            casts, same jitted dp-sharded bootstrap) — the two
            ingredients of the x64 bit-parity with the sequential
            device-collector path (rl/ppo_device.py)."""
            sim_state, trace, next_fields = lane_segment(
                bb, params, sim_state, lane_rngs)

            def tb(x):
                return jnp.swapaxes(x, 0, 1)

            traj = {
                "obs": {k: tb(v) for k, v in trace["obs"].items()},
                "actions": tb(trace["action"]).astype(jnp.int32),
                "logp": tb(trace["logp"]).astype(jnp.float32),
                "values": tb(trace["value"]).astype(jnp.float32),
                "rewards": tb(trace["reward"]).astype(jnp.float32),
                "dones": tb(trace["done"]),
            }
            traj = jax.lax.with_sharding_constraint(
                traj, jax.tree_util.tree_map(lambda _: batch_time, traj))
            next_obs = jax.vmap(lambda j, f, s, o, r: _kernel_obs(
                ot, et, j, f, s, o, r))(
                next_fields["jtype"], next_fields["frac"],
                next_fields["steps"], next_fields["n_occupied"],
                next_fields["n_running"])
            with jax.named_scope(scopes.POLICY_FORWARD):
                _, last_values = batched_policy_apply(model, params,
                                                      next_obs)
            last_values = jax.lax.with_sharding_constraint(
                last_values.astype(jnp.float32), batch_only)
            ep = {k: trace[k] for k in EPISODE_TRACE_KEYS}
            return sim_state, traj, last_values, ep

        self._actor = jax.jit(
            actor_round,
            in_shardings=(self._lane, self._repl, self._lane, self._lane),
            donate_argnums=traj_donate_argnums(2))
        # the actor→learner queue: slab-less ledger segments, one per
        # in-flight device batch (lease backpressure + the two-phase
        # release-token protocol — rl/ring.py device mode)
        self.ring = TrajRing(None, rows=T + 1, num_envs=B,
                             segments=ring_segments)

    def collect(self, params, rng) -> Dict:
        """One [T, B] segment batch on the actor sub-mesh. ``params``
        arrive committed to the LEARNER sub-mesh; the replicating
        ``device_put`` here is the explicit learner→actor hop (a real
        copy — the device sets are disjoint — so learner-side donation
        can never delete the actor's params)."""
        import jax

        seg = self.ring.lease()
        # transfer-ledger wraps (gated; NULL_SPAN + no-op add when
        # telemetry is off) around the EXISTING explicit hops — byte
        # attribution is .nbytes metadata only, transfer-guard safe
        with telemetry.transfer(self._params_hop_name, "l2a") as tr:
            params = jax.device_put(params, self._repl)
            tr.add(params)
        with telemetry.transfer("sebulba.rngs", "h2d") as tr:
            lane_rngs = jax.device_put(
                jax.random.split(rng, self.num_envs), self._lane)
            tr.add(lane_rngs)
        self._state, traj, last_values, ep = self._actor(
            self.banks, params, self._state, lane_rngs)
        self.ring.publish(seg)
        return {"traj": traj,
                "last_values": last_values,
                "env_steps": self.rollout_length * self.num_envs,
                "episodes": [],
                "ep_pending": ep,
                "ring": self.ring,
                "ring_segment": seg,
                "ring_generation": seg.generation}

    def harvest_episodes(self, ep_trace) -> list:
        """Episode records from a FETCHED [B, T] episode-counter trace
        (the drain boundary hands host numpy arrays) — the same
        records, in the same (t, b) order and with the same host
        denominators, as ``DevicePPOCollector._harvest_episodes`` emits
        for the matching collect."""
        episodes = []
        done = np.asarray(ep_trace["done"])  # [B, T]
        B, T = done.shape
        for t in range(T):
            self._ep_len += 1
            for b in np.nonzero(done[:, t])[0]:
                blk = int(ep_trace["ep_blocked"][b, t])
                com = int(ep_trace["ep_completed"][b, t])
                arr = int(ep_trace["ep_arrived"][b, t])
                episodes.append({
                    "env_index": int(b),
                    "episode_return": float(ep_trace["ep_return"][b, t]),
                    "episode_length": int(self._ep_len[b]),
                    "num_jobs_arrived": arr,
                    "num_jobs_completed": com,
                    "num_jobs_blocked": blk,
                    "acceptance_rate": com / arr if arr else 0.0,
                    "blocking_rate": blk / arr if arr else 0.0,
                })
                self._ep_len[b] = 0
        return episodes

    def close(self) -> None:
        self.ring.close()
