"""Vectorised rollout collection.

Replaces RLlib's Ray rollout workers (SURVEY.md §3.1): instead of N worker
processes each owning an environment and a policy copy, one host process
steps B environment instances, stacks their padded observations into [B, ...]
arrays, and samples all B actions in a single jitted device call
(``PPOLearner.sample_actions``). The simulator itself runs per-step on the
host (its per-job heuristic placer is sequential/combinatorial — SURVEY.md
§7.4.2); the device sees only fixed-shape batched tensors.

Environments auto-reset on episode end; completed-episode returns/lengths and
the cluster's episode stats are harvested for logging, mirroring what RLlib's
callbacks collect (ddls/environments/ramp_cluster/utils.py:25-73).
"""
from __future__ import annotations

import multiprocessing as mp
import time
from typing import Any, Callable, Dict, List, Optional, Tuple

import jax
import numpy as np

from ddls_tpu import telemetry
from ddls_tpu.telemetry import flight
from ddls_tpu.utils.runtime import jax_process_state, pin_cpu_platform

OBS_KEYS = ("node_features", "edge_features", "graph_features",
            "edges_src", "edges_dst", "node_split", "edge_split",
            "action_mask")


def stack_obs(obs_list: List[Dict[str, np.ndarray]]) -> Dict[str, np.ndarray]:
    return {k: np.stack([np.asarray(o[k]) for o in obs_list])
            for k in OBS_KEYS}


def harvest_episode_record(env, env_index: int, episode_return: float,
                           episode_length: int) -> Dict[str, Any]:
    """Episode summary + the cluster's episode stats, mirroring what RLlib's
    callbacks collect (ddls/environments/ramp_cluster/utils.py:25-73)."""
    record = {"env_index": env_index,
              "episode_return": float(episode_return),
              "episode_length": int(episode_length)}
    cluster = getattr(env, "cluster", None)
    if cluster is not None and getattr(cluster, "episode_stats", None):
        stats = cluster.episode_stats
        for key in ("num_jobs_arrived", "num_jobs_completed",
                    "num_jobs_blocked", "blocking_rate",
                    "acceptance_rate"):
            if key in stats:
                record[key] = stats[key]
        for key in ("job_completion_time",
                    "job_completion_time_speedup"):
            vals = stats.get(key)
            if vals:
                record[f"mean_{key}"] = float(np.mean(vals))
    return record


class VectorEnv:
    """B independent environment instances with auto-reset."""

    def __init__(self, env_fns: List[Callable[[], Any]],
                 seeds: Optional[List[int]] = None):
        self.envs = [fn() for fn in env_fns]
        self.num_envs = len(self.envs)
        self.seeds = seeds or list(range(self.num_envs))
        self.episode_returns = np.zeros(self.num_envs)
        self.episode_lengths = np.zeros(self.num_envs, dtype=np.int64)
        self.completed_episodes: List[Dict[str, Any]] = []
        self._stacked_bufs: Optional[Dict[str, np.ndarray]] = None

    def stacked_obs(self) -> Dict[str, np.ndarray]:
        """The current obs list as one [B, ...] batch, assembled into a
        REUSED preallocated buffer (values bit-identical to
        ``stack_obs(self.obs)``; contents valid until the next
        ``stacked_obs()`` call — every current consumer copies or stages
        the batch before stepping again). The single-process half of the
        per-step obs copy tax: one allocation per run instead of one per
        step. (In-process envs have no stepping to overlap the stacking
        with — see ParallelVectorEnv for the prefetched/shm variants.)"""
        arrays = {k: [np.asarray(o[k]) for o in self.obs]
                  for k in OBS_KEYS}
        bufs = self._stacked_bufs
        if bufs is None or any(
                bufs[k].shape != (self.num_envs,) + arrays[k][0].shape
                or bufs[k].dtype != arrays[k][0].dtype for k in OBS_KEYS):
            bufs = {k: np.empty((self.num_envs,) + arrays[k][0].shape,
                                arrays[k][0].dtype) for k in OBS_KEYS}
            self._stacked_bufs = bufs
        for k in OBS_KEYS:
            np.stack(arrays[k], out=bufs[k])
        if telemetry.enabled():
            telemetry.inc("rollout.obs.bytes_stack",
                          sum(b.nbytes for b in bufs.values()))
        return bufs

    def reset(self) -> List[Dict[str, np.ndarray]]:
        self.obs = [env.reset(seed=self.seeds[i])
                    for i, env in enumerate(self.envs)]
        self.episode_returns[:] = 0.0
        self.episode_lengths[:] = 0
        return self.obs

    def step(self, actions: np.ndarray):
        return self.step_subset(range(self.num_envs), actions)

    def step_subset(self, indices, actions: np.ndarray):
        """Step only ``envs[i] for i in indices`` with ``actions`` (same
        length as ``indices``); returns (obs list for the subset, rewards,
        dones). Used by the pipelined collector to overlap device sampling
        of one env group with host stepping of the other."""
        indices = list(indices)
        rewards = np.zeros(len(indices), dtype=np.float32)
        dones = np.zeros(len(indices), dtype=bool)
        for k, i in enumerate(indices):
            env = self.envs[i]
            obs, reward, done, _ = env.step(int(actions[k]))
            rewards[k] = reward
            dones[k] = done
            self.episode_returns[i] += reward
            self.episode_lengths[i] += 1
            if done:
                self._harvest_episode(i, env)
                # fresh seed per episode so workload sampling differs
                self.seeds[i] += self.num_envs
                obs = env.reset(seed=self.seeds[i])
                self.episode_returns[i] = 0.0
                self.episode_lengths[i] = 0
            self.obs[i] = obs
        return [self.obs[i] for i in indices], rewards, dones

    def _harvest_episode(self, i: int, env) -> None:
        self.completed_episodes.append(harvest_episode_record(
            env, i, self.episode_returns[i], self.episode_lengths[i]))

    def drain_completed_episodes(self) -> List[Dict[str, Any]]:
        out, self.completed_episodes = self.completed_episodes, []
        return out

    def restart_episodes(self) -> List[Dict[str, np.ndarray]]:
        """Abandon every in-progress episode and start fresh ones on
        advanced per-env seeds. Completed-episode records are kept; the
        abandoned partial returns/lengths are dropped — used after an
        off-policy interlude (e.g. an ES eval window) so foreign-policy
        steps can never leak into training episode stats."""
        for i in range(self.num_envs):
            self.seeds[i] += self.num_envs
        self.obs = [env.reset(seed=self.seeds[i])
                    for i, env in enumerate(self.envs)]
        self.episode_returns[:] = 0.0
        self.episode_lengths[:] = 0
        return self.obs

    def close(self) -> None:
        pass


def _parallel_env_worker(conn, env_builder, env_kwargs: Dict[str, Any],
                         env_index: int, seed: int, seed_stride: int,
                         telemetry_enabled: bool = False,
                         flight_state: Optional[tuple] = None) -> None:
    """Subprocess body: owns one env, steps it on command, auto-resets.

    ``env_builder`` is a picklable callable (class or factory) receiving
    ``**env_kwargs`` — the process-parallel replacement for RLlib's Ray
    rollout workers, each of which builds its own env from the env_config
    (SURVEY.md §3.1 process-boundary note).

    ``telemetry_enabled`` mirrors the parent's telemetry switch into this
    process (spawned workers start with the global registry disabled);
    the worker's counters — the sim-layer cache hit/miss counts live
    HERE, not in the parent — ride back on the "closed" ack and are
    merged into the parent registry by ``ParallelVectorEnv.close``.
    ``flight_state`` (enabled, detail) mirrors the flight recorder the
    same way: the simulator's event trace is emitted in THIS process,
    drained on the close ack, and merged into the parent recorder tagged
    with this worker's env index.

    Shared-memory protocol (the ``shm`` backend): on ``shm_open`` the
    worker maps the parent's slabs (rl/shm.py); step commands then carry
    ``(action, dest_row)`` and the observation is written in place into
    this worker's ``[dest_row, env_index]`` slice via the masked-pad
    ``envs.obs.write_obs_into`` — the pipe reply shrinks to the
    (reward, done, record) control payload, which doubles as the ready
    flag the parent waits on before reading the slice. ``ring_open``
    upgrades the mapping to a trajectory ring (rl/ring.py): K segment
    attachments, and ``dest_row`` becomes ``(segment, row)`` — segment
    ownership (who may be written when) is entirely parent-side; the
    worker just writes where the step command points.
    """
    attachment = None
    ring_attachment = None  # set on ring_open (rl/ring.py segments)
    writer = None  # set with the attachment on shm_open/ring_open
    try:
        # the parent may hold an accelerator, which belongs to ONE
        # process: anything in here that reaches for jax (the opt-in
        # jax lookahead / candidate pricing, a toolchain-less host's
        # jax fallback) must land on the CPU backend
        pin_cpu_platform()
        if telemetry_enabled:
            telemetry.enable()
        if flight_state is not None and flight_state[0]:
            flight.enable(detail=bool(flight_state[1]))
        env = env_builder(**env_kwargs)
        episode_return, episode_length = 0.0, 0
        while True:
            cmd, payload = conn.recv()
            if cmd == "reset":
                # seedless reset replays the current seed (same semantics
                # as the serial VectorEnv); "restart" advances it
                seed = payload if payload is not None else seed
                obs = env.reset(seed=seed)
                episode_return, episode_length = 0.0, 0
                conn.send(("obs", obs))
            elif cmd == "restart":
                # abandon the in-progress episode for a fresh workload
                seed += seed_stride
                obs = env.reset(seed=seed)
                episode_return, episode_length = 0.0, 0
                conn.send(("obs", obs))
            elif cmd == "shm_open":
                from ddls_tpu.envs.obs import ObsWriter
                from ddls_tpu.rl.shm import SlabAttachment

                if attachment is not None:
                    attachment.close()
                attachment = SlabAttachment(payload)
                writer = ObsWriter(
                    attachment.views["node_features"].shape[2],
                    attachment.views["edge_features"].shape[2])
                conn.send(("ok", None))
            elif cmd == "ring_open":
                from ddls_tpu.envs.obs import ObsWriter
                from ddls_tpu.rl.shm import RingAttachment

                if ring_attachment is not None:
                    ring_attachment.close()
                if attachment is not None:
                    # retire the pre-ring slab mapping (the parent
                    # unlinks it at first lease; keeping the mmap would
                    # pin the memory for the worker's lifetime) — and a
                    # stale bare-row dest after ring install now fails
                    # loudly instead of writing a retired slab
                    attachment.close()
                    attachment = None
                ring_attachment = RingAttachment(payload)
                v0 = ring_attachment.views_for(0)
                writer = ObsWriter(v0["node_features"].shape[2],
                                   v0["edge_features"].shape[2])
                conn.send(("ok", None))
            elif cmd == "step":
                if isinstance(payload, tuple):
                    action, dest_row = payload
                else:
                    action, dest_row = payload, None
                obs, reward, done, _ = env.step(int(action))
                episode_return += reward
                episode_length += 1
                record = None
                if done:
                    record = harvest_episode_record(
                        env, env_index, episode_return, episode_length)
                    seed += seed_stride
                    obs = env.reset(seed=seed)
                    episode_return, episode_length = 0.0, 0
                if isinstance(dest_row, tuple):
                    seg, row = dest_row
                    writer.write(obs, {k: v[row, env_index]
                                       for k, v in
                                       ring_attachment.views_for(
                                           seg).items()})
                    conn.send(("step", (float(reward), bool(done), record)))
                elif attachment is not None and dest_row is not None:
                    writer.write(obs, {k: v[dest_row, env_index]
                                       for k, v in
                                       attachment.views.items()})
                    conn.send(("step", (float(reward), bool(done), record)))
                else:
                    conn.send(("step",
                               (obs, float(reward), bool(done), record)))
            elif cmd == "close":
                # telemetry: counters only (cross-process histogram merge
                # is lossy, and the sim layer records nothing but
                # counters); flight: the full event trace, merged
                # parent-side with this worker's env-index tag
                counters = telemetry.snapshot().get("counters") or None
                trace = flight.drain() if flight.enabled() else None
                # what this process asked jax for, which backends it
                # opened, and which lookahead engine stepped the env
                state = jax_process_state()
                state["native_lookahead"] = getattr(
                    getattr(env, "cluster", None),
                    "use_native_lookahead", None)
                conn.send(("closed", {"counters": counters,
                                      "flight": trace,
                                      "process": state}))
                return
    except KeyboardInterrupt:
        pass
    except Exception as e:  # surface worker crashes to the parent
        import traceback
        conn.send(("error", f"{e}\n{traceback.format_exc()}"))
    finally:
        if attachment is not None:
            attachment.close()
        if ring_attachment is not None:
            ring_attachment.close()


class _LazyObsList:
    """Sequence facade over a shm-backend env's per-env obs dicts: the
    ``step()`` return value materialises slab copies only if someone
    actually indexes/iterates it (the PPO/IMPALA hot paths ignore the
    obs return entirely — paying B copies per step there would undo the
    zero-copy win)."""

    def __init__(self, env):
        self._env = env

    def __len__(self):
        return self._env.num_envs

    def __getitem__(self, i):
        return self._env.obs[i]

    def __iter__(self):
        return iter(self._env.obs)


class ParallelVectorEnv:
    """B environment instances stepped in B subprocesses.

    Same interface as ``VectorEnv``. Env construction arguments must be
    picklable (builder callable + kwargs dict), since workers are spawned
    fresh — which also keeps the TPU runtime out of the children (only the
    parent process touches jax).

    ``backend`` selects the obs transport:

    * ``"pipe"`` (default — the seed's exact semantics): workers pickle
      the full padded obs over the control pipe every step;
    * ``"shm"``: workers write each obs once, in place, into per-field
      shared-memory slabs (rl/shm.py) and the pipe carries only the
      (reward, done, record) ready flag. ``stacked_obs()`` then returns
      VIEWS of the slab (valid until the next ``step``/``reset``;
      ``.obs`` materialises per-env copies on access), and
      ``ensure_traj_rows(T + 1)`` grows the slabs so the deferred-fetch
      collector's trajectory is the slab itself — the worker's write IS
      the traj-buffer write. Bit-identical outputs to ``pipe`` (obs,
      rewards, dones, episode-record content and order) for the same
      seeds — pinned by tests/test_shm.py;
    * ``"auto"``: ``shm`` where POSIX shared memory is usable, else
      ``pipe``.
    """

    def __init__(self, env_builder: Callable[..., Any],
                 env_kwargs: Dict[str, Any], num_envs: int,
                 seeds: Optional[List[int]] = None,
                 start_method: str = "spawn",
                 backend: str = "pipe"):
        from ddls_tpu.rl.shm import shm_available

        if backend == "auto":
            backend = "shm" if shm_available() else "pipe"
        if backend not in ("pipe", "shm"):
            raise ValueError(f"backend must be 'pipe', 'shm' or 'auto', "
                             f"got {backend!r}")
        if backend == "shm" and not shm_available():
            import warnings

            warnings.warn("POSIX shared memory unavailable; "
                          "ParallelVectorEnv falling back to the pipe "
                          "backend")
            backend = "pipe"
        self.backend = backend
        self.num_envs = num_envs
        self.seeds = seeds or list(range(num_envs))
        # opt-in (the pipelined collector sets it): full-batch step()
        # receives worker replies OUT OF ORDER as they finish and writes
        # each obs row straight into a stacked [B, ...] batch, so the
        # next sample's input assembles while slower workers still step
        # — the stacking cost rides inside the env wall instead of after
        # it. Off by default so the sequential loop keeps the seed's
        # exact cost profile for load-controlled comparisons. (The shm
        # backend subsumes it: stacked_obs IS the slab.)
        self.prefetch_stacked = False
        self._stacked_cache: Optional[Dict[str, np.ndarray]] = None
        self._stacked_bufs: Optional[Dict[str, np.ndarray]] = None
        # shm-backend state: slabs are allocated lazily at the first
        # reset (field shapes come from a real obs), row 0 holds the
        # current obs until ensure_traj_rows grows the slab — or
        # ensure_traj_ring replaces it with a K-segment trajectory ring
        # (rl/ring.py), after which _slabs tracks the ACTIVE segment's
        # slab set and _active_seg its ring index (None = single slab)
        self._slabs = None
        self._ring = None
        self._active_seg = None
        self._field_specs = None
        self._cur_row = 0
        self._obs_list: List[Dict[str, np.ndarray]] = []
        self._obs_cache: Optional[List[Dict[str, np.ndarray]]] = None
        self._extra_obs: Optional[List[Dict[str, np.ndarray]]] = None
        self._obs_nbytes = 0
        # bounded step wait: a wedged worker raises instead of hanging
        # collection forever (a DEAD worker is detected immediately via
        # pipe EOF, no timeout needed)
        self.step_timeout_s = 300.0
        self._closed = False
        ctx = mp.get_context(start_method)
        self._conns = []
        self._procs = []
        for i in range(num_envs):
            parent, child = ctx.Pipe()
            proc = ctx.Process(
                target=_parallel_env_worker,
                args=(child, env_builder, env_kwargs, i, self.seeds[i],
                      num_envs, telemetry.enabled(),
                      (flight.enabled(), flight.detail_enabled())),
                daemon=True)
            proc.start()
            child.close()
            self._conns.append(parent)
            self._procs.append(proc)
        self.completed_episodes: List[Dict[str, Any]] = []
        self._first_reset = True
        # each worker's jax_process_state() + lookahead engine, reported
        # on its close ack: the proof that no child opened the parent's
        # accelerator
        self.worker_states: List[Optional[Dict[str, Any]]] = \
            [None] * num_envs

    # ------------------------------------------------------------- obs views
    @property
    def obs(self) -> List[Dict[str, np.ndarray]]:
        """Per-env obs dicts. Pipe backend: the worker-sent dicts. Shm
        backend: copies materialised from the slab on access (cached
        until the next step) plus the reset-time non-slab fields
        (``action_set`` — episode-constant by the encode contract); the
        copies stay valid across later steps, so replay-style consumers
        (the DQN loop's ``prev_obs``) are safe."""
        if self._slabs is None:
            return self._obs_list
        if self._obs_cache is None:
            row = self._cur_row
            views = self._slabs.views
            extra = self._extra_obs or [{}] * self.num_envs
            self._obs_cache = [
                {**extra[i],
                 **{k: np.array(views[k][row, i]) for k in OBS_KEYS}}
                for i in range(self.num_envs)]
        return self._obs_cache

    @obs.setter
    def obs(self, value) -> None:
        self._obs_list = list(value)
        self._obs_cache = self._obs_list if self._slabs is not None else None

    def _send(self, i: int, msg) -> None:
        """Guarded dispatch: a worker that died before this command
        surfaces as a clear error instead of an unhandled
        BrokenPipeError (the kill-a-worker hardening path)."""
        try:
            self._conns[i].send(msg)
        except (BrokenPipeError, OSError):
            exitcode = self._procs[i].exitcode
            self.close()
            raise RuntimeError(
                f"env worker {i} died (exitcode {exitcode}) — cannot "
                f"dispatch {msg[0]!r}") from None

    def _recv(self, conn) -> Tuple[str, Any]:
        i = self._conns.index(conn)
        if not conn.poll(self.step_timeout_s):
            self.close()
            raise RuntimeError(
                f"env worker {i} did not reply within "
                f"{self.step_timeout_s:.0f}s (wedged worker?)")
        try:
            kind, payload = conn.recv()
        except (EOFError, ConnectionResetError, OSError):
            exitcode = self._procs[i].exitcode
            self.close()
            raise RuntimeError(
                f"env worker {i} died (exitcode {exitcode}) — pipe "
                f"closed before its reply") from None
        if kind == "error":
            self.close()
            raise RuntimeError(f"env worker failed:\n{payload}")
        return kind, payload

    def _drain_step_replies(self, on_reply) -> None:
        """One step reply per worker, consumed OUT OF ORDER as workers
        finish, under the bounded ``step_timeout_s`` deadline —
        ``on_reply(i, payload)`` handles each. The single drain loop
        shared by the shm and pipe-prefetch step paths, so the
        dead-worker (pipe EOF) and wedged-worker (deadline) handling
        can never diverge between transports."""
        from multiprocessing import connection as mp_connection

        remaining = {conn: i for i, conn in enumerate(self._conns)}
        deadline = time.monotonic() + self.step_timeout_s
        while remaining:
            ready = mp_connection.wait(
                list(remaining), timeout=max(deadline - time.monotonic(),
                                             0.0))
            if not ready:
                stuck = sorted(remaining.values())
                self.close()
                raise RuntimeError(
                    f"env workers {stuck} did not reply within "
                    f"{self.step_timeout_s:.0f}s (wedged worker?)")
            for conn in ready:
                i = remaining.pop(conn)
                try:
                    kind, payload = conn.recv()
                except (EOFError, ConnectionResetError, OSError):
                    exitcode = self._procs[i].exitcode
                    self.close()
                    raise RuntimeError(
                        f"env worker {i} died mid-step (exitcode "
                        f"{exitcode})") from None
                if kind == "error":
                    self.close()
                    raise RuntimeError(f"env worker failed:\n{payload}")
                on_reply(i, payload)

    # ---------------------------------------------------------- shm plumbing
    def _setup_slabs(self, obs: List[Dict[str, np.ndarray]]) -> None:
        """First-reset slab allocation: field shapes/dtypes come from the
        first worker's obs (all workers must agree — i.e. the env pads to
        fixed bounds); on any failure the env falls back to pipe
        permanently rather than crash training."""
        from ddls_tpu.rl import shm as shm_mod

        try:
            fields = shm_mod.obs_field_specs(obs[0], OBS_KEYS)
            for j, o in enumerate(obs[1:], start=1):
                other = shm_mod.obs_field_specs(o, OBS_KEYS)
                if other != fields:
                    raise ValueError(
                        f"env {j} obs shapes {other} differ from env 0's "
                        f"{fields} (shm needs fixed pad bounds)")
            slabs = shm_mod.SlabSet(fields, rows=1, num_envs=self.num_envs)
        except Exception as e:
            import warnings

            warnings.warn(f"shm backend unusable for this env ({e}); "
                          "falling back to pipe")
            self.backend = "pipe"
            return
        self._field_specs = fields
        self._install_slabs(slabs)
        # non-slab obs fields (action_set) are episode-constant; captured
        # at reset and reattached to materialised obs copies
        self._extra_obs = [{k: np.asarray(v) for k, v in o.items()
                            if k not in OBS_KEYS} for o in obs]

    def _install_slabs(self, slabs) -> None:
        """Broadcast the slab spec and wait for every worker's attach ack
        (after which step replies stop carrying obs payloads)."""
        spec = slabs.spec()
        for i in range(self.num_envs):
            self._send(i, ("shm_open", spec))
        for conn in self._conns:
            self._recv(conn)
        self._slabs = slabs
        self._cur_row = 0
        self._obs_nbytes = slabs.obs_nbytes

    def _guard_ring_write(self, what: str) -> None:
        """Loud ledger guard shared by the parent-side write paths
        (reset/restart row-0 writes, full-batch stepping): writing the
        active segment while it is PUBLISHED would corrupt a batch the
        learner may still be reading. Ready release tokens are swept
        first, so a segment whose consumer already finished never
        false-positives."""
        if self._ring is None or self._active_seg is None:
            return
        self._ring.sweep()  # release anything whose token is ready
        seg = self._ring.segments[self._active_seg]
        if seg.state == "published":
            raise RuntimeError(
                f"{what} would write ring segment {seg.index}, which is "
                "PUBLISHED (owned by the learner until its release "
                "token fires) — settle the in-flight update (or release "
                "the segment) first")

    def _write_row0(self, obs: List[Dict[str, np.ndarray]]) -> None:
        self._guard_ring_write("reset/restart row-0 write")
        views = self._slabs.views
        for k in OBS_KEYS:
            for i in range(self.num_envs):
                views[k][0, i] = obs[i][k]
        self._cur_row = 0

    def ensure_traj_rows(self, rows: int) -> bool:
        """Grow the obs slabs to ``[rows, B, ...]`` so a [T, B] collector
        can treat rows ``[0:T]`` as its trajectory buffer (row t = the obs
        BEFORE step t; the final row = the bootstrap obs). Returns True
        when the slab-trajectory contract is in force. No-op (False) on
        the pipe backend."""
        if self._slabs is None:
            return False
        if self._ring is not None:
            # a ring-backed env must stay on the ring: the single-slab
            # contract would treat the ACTIVE ring segment as a private
            # slab and rewrite rows the ledger may have handed to the
            # learner (a silent fallback is exactly what the ring's
            # loud-violation contract forbids)
            raise RuntimeError(
                "ensure_traj_rows on a ring-backed env — this env's "
                "trajectory transport is the ring (ensure_traj_ring); "
                "build a separate vec env for single-slab collection")
        if self._slabs.rows >= rows:
            return True
        current = self.obs  # materialise from the OLD slab first
        old = self._slabs
        try:
            from ddls_tpu.rl.shm import SlabSet

            slabs = SlabSet(self._field_specs, rows=rows,
                            num_envs=self.num_envs)
        except Exception as e:
            import warnings

            warnings.warn(f"could not grow shm slabs to {rows} rows "
                          f"({e}); keeping per-step slab")
            return False
        self._install_slabs(slabs)
        self._write_row0(current)
        self._obs_cache = current
        old.close()
        return True

    def rebase_row0(self) -> None:
        """Move the current obs to slab row 0 (one [B, ...] copy per
        field, once per segment) so the next T steps write rows 1..T."""
        if self._slabs is None or self._cur_row == 0:
            return
        views = self._slabs.views
        for k in OBS_KEYS:
            views[k][0] = views[k][self._cur_row]
        self._cur_row = 0
        self._obs_cache = None

    # ------------------------------------------------------ trajectory ring
    @property
    def traj_ring(self):
        """The installed trajectory ring (rl/ring.py), or None."""
        return self._ring

    def ensure_traj_ring(self, rows: int, segments: int):
        """Install (or return) a ``segments``-way trajectory ring of
        ``[rows, B, ...]`` slabs (rl/ring.py) — the multi-segment
        generalisation of ``ensure_traj_rows``. Returns the ring, or
        None on the pipe backend / allocation failure (callers fall
        back to the single-slab path). Idempotent while the requested
        shape fits the installed ring."""
        if self._slabs is None:
            return None
        if self._ring is not None:
            if (self._ring.rows >= rows
                    and len(self._ring.segments) >= segments):
                return self._ring
            # a silent fallback here would route collection onto the
            # single-slab path while the active slab is still a ring
            # segment the learner may own — ledger-violating writes,
            # exactly what the contract promises can't happen. Loud by
            # design (as ring-lease timeouts are).
            raise RuntimeError(
                f"trajectory ring shape change mid-run: installed "
                f"[{self._ring.rows} rows x "
                f"{len(self._ring.segments)} segments], requested "
                f"[{rows} x {segments}] — build a fresh vec env for a "
                "different rollout length or pipeline depth")
        try:
            from ddls_tpu.rl.ring import TrajRing

            ring = TrajRing(self._field_specs, rows=rows,
                            num_envs=self.num_envs, segments=segments)
        except Exception as e:
            import warnings

            warnings.warn(f"could not allocate a {segments}-segment "
                          f"trajectory ring ({e}); keeping the single "
                          "slab")
            return None
        specs = ring.specs()
        for i in range(self.num_envs):
            self._send(i, ("ring_open", specs))
        for conn in self._conns:
            self._recv(conn)
        self._ring = ring
        return ring

    def begin_ring_segment(self, segment) -> None:
        """Point collection at a freshly-leased ring segment: the
        current obs (the previous segment's bootstrap row — or the
        pre-ring slab's current row on the first lease) is copied into
        the new segment's row 0, the one [B, ...]-per-field copy that
        ``rebase_row0`` pays on the single slab. The previous segment
        is only READ here, which every ledger state permits."""
        prev, prev_row = self._slabs, self._cur_row
        views = segment.views
        if prev is not segment.slabs or prev_row != 0:
            for k in OBS_KEYS:
                views[k][0] = prev.views[k][prev_row]
        if self._active_seg is None and prev is not segment.slabs:
            # first lease: the pre-ring current-obs slab is retired (its
            # unlink frees the name now; workers' live mappings die with
            # them — they will only ever be pointed at ring segments)
            prev.close()
        self._slabs = segment.slabs
        self._active_seg = segment.index
        self._cur_row = 0
        self._obs_cache = None
        self._stacked_cache = None

    def traj_obs_views(self, T: int) -> Dict[str, np.ndarray]:
        """Slab rows [0:T] as the trajectory obs — zero-copy views, valid
        until the next ``rebase_row0``/``reset`` overwrites row 0 (i.e.
        until the next collect segment begins)."""
        return {k: self._slabs.views[k][:T] for k in OBS_KEYS}

    def reset(self) -> List[Dict[str, np.ndarray]]:
        # seeds live worker-side (advanced on every auto-reset); only the
        # first reset pins them, later resets continue each worker's sequence
        payload = self.seeds if self._first_reset else [None] * self.num_envs
        self._first_reset = False
        self._stacked_cache = None
        for i, seed in enumerate(payload):
            self._send(i, ("reset", seed))
        obs = [self._recv(conn)[1] for conn in self._conns]
        self.obs = obs
        if self.backend == "shm" and self._slabs is None:
            self._setup_slabs(obs)
        if self._slabs is not None:
            self._write_row0(obs)
            self._obs_cache = obs
        if not self._obs_nbytes:
            # per-env obs bytes (the unit of the bytes-copied counters),
            # valid for both transports once shapes are known
            self._obs_nbytes = sum(int(np.asarray(obs[0][k]).nbytes)
                                   for k in OBS_KEYS)
        return self.obs

    def stacked_obs(self) -> Dict[str, np.ndarray]:
        """The current obs as one [B, ...] batch. Shm backend: VIEWS of
        the slab row the workers wrote in place — no copy at all (valid
        until the next ``step``/``reset``). Pipe backend with
        ``prefetch_stacked``: the batch was already assembled inside the
        previous ``step()`` as worker replies arrived (bit-identical to
        ``stack_obs(self.obs)``, measured earlier)."""
        if self._slabs is not None:
            row = self._cur_row
            return {k: self._slabs.views[k][row] for k in OBS_KEYS}
        if self._stacked_cache is not None:
            return self._stacked_cache
        stacked = stack_obs(self.obs)
        if telemetry.enabled():
            telemetry.inc("rollout.obs.bytes_stack",
                          sum(v.nbytes for v in stacked.values()))
        return stacked

    def step(self, actions: np.ndarray):
        if self._slabs is not None:
            return self._step_shm(actions)
        if self.prefetch_stacked:
            return self._step_prefetch(actions)
        return self.step_subset(range(self.num_envs), actions)

    def _step_shm(self, actions: np.ndarray):
        """Full-batch step over the slab transport: obs rows are written
        worker-side (each write is the ONLY materialisation of that obs),
        replies carry (reward, done, record) and arrive out of order —
        the reply is the per-worker ready flag; episode records flush in
        env-index order, matching the pipe paths bit-for-bit."""
        if self._ring is not None and self._active_seg is None:
            # workers retired their pre-ring slab mapping at ring_open;
            # stepping before the first begin_ring_segment would write
            # nowhere the parent reads — surface it, loudly
            raise RuntimeError(
                "trajectory ring installed but no segment is active — "
                "lease a segment and call begin_ring_segment() before "
                "stepping")
        # stepping outside the lease cycle (a direct vec.step() between
        # collects) must not rewrite a learner-owned segment either
        self._guard_ring_write("step")
        R = self._slabs.rows
        dest = self._cur_row if R == 1 else min(self._cur_row + 1, R - 1)
        payload_dest = (dest if self._active_seg is None
                        else (self._active_seg, dest))
        for i in range(self.num_envs):
            self._send(i, ("step", (int(actions[i]), payload_dest)))
        B = self.num_envs
        rewards = np.zeros(B, dtype=np.float32)
        dones = np.zeros(B, dtype=bool)
        records: Dict[int, dict] = {}

        def on_reply(i, payload):
            reward, done, record = payload
            rewards[i] = reward
            dones[i] = done
            if record is not None:
                records[i] = record

        self._drain_step_replies(on_reply)
        self._cur_row = dest
        self._obs_cache = None
        self.completed_episodes.extend(records[i] for i in sorted(records))
        if telemetry.enabled():
            telemetry.inc("rollout.obs.bytes_slab", self._obs_nbytes * B)
        return _LazyObsList(self), rewards, dones

    def _step_prefetch(self, actions: np.ndarray):
        """Full-batch step with out-of-order reply handling: each worker's
        obs row lands in a fresh stacked batch the moment it arrives, so
        stacking overlaps the stragglers' env stepping. Outputs (obs,
        rewards, dones, episode-record order) are bit-identical to the
        in-order path — records are flushed in env-index order."""
        for i in range(self.num_envs):
            self._send(i, ("step", int(actions[i])))
        B = self.num_envs
        rewards = np.zeros(B, dtype=np.float32)
        dones = np.zeros(B, dtype=bool)
        records: Dict[int, dict] = {}
        state = {"stacked": None}

        def on_reply(i, payload):
            obs, reward, done, record = payload
            self.obs[i] = obs
            stacked = state["stacked"]
            if stacked is None:
                # reuse the previous step's assembly buffers (valid-
                # until-next-step contract, same as stacked_obs)
                stacked = self._stacked_bufs
                if stacked is None or any(
                        stacked[k].shape[1:] != np.asarray(obs[k]).shape
                        or stacked[k].dtype != np.asarray(obs[k]).dtype
                        for k in OBS_KEYS):
                    stacked = {
                        k: np.empty((B,) + np.asarray(obs[k]).shape,
                                    np.asarray(obs[k]).dtype)
                        for k in OBS_KEYS}
                self._stacked_bufs = state["stacked"] = stacked
            for k in OBS_KEYS:
                stacked[k][i] = obs[k]
            rewards[i] = reward
            dones[i] = done
            if record is not None:
                records[i] = record

        self._drain_step_replies(on_reply)
        self.completed_episodes.extend(
            records[i] for i in sorted(records))
        self._stacked_cache = state["stacked"]
        if telemetry.enabled():
            telemetry.inc("rollout.obs.bytes_pipe", self._obs_nbytes * B)
            telemetry.inc("rollout.obs.bytes_stack", self._obs_nbytes * B)
        return list(self.obs), rewards, dones

    def step_subset(self, indices, actions: np.ndarray):
        """Step only the workers in ``indices``; see VectorEnv.step_subset.
        On the shm backend a partial subset rides the pipe (obs payload)
        and the parent refreshes the CURRENT slab row in place — subset
        stepping is the split-batch pipelined collector's path, which
        never runs under the slab-trajectory contract."""
        indices = list(indices)
        self._stacked_cache = None
        for k, i in enumerate(indices):
            self._send(i, ("step", int(actions[k])))
        rewards = np.zeros(len(indices), dtype=np.float32)
        dones = np.zeros(len(indices), dtype=bool)
        for k, i in enumerate(indices):
            _, (obs, reward, done, record) = self._recv(self._conns[i])
            if self._slabs is not None:
                views = self._slabs.views
                for key in OBS_KEYS:
                    views[key][self._cur_row, i] = obs[key]
                self._obs_cache = None
            else:
                self.obs[i] = obs
            rewards[k] = reward
            dones[k] = done
            if record is not None:
                self.completed_episodes.append(record)
        if telemetry.enabled():
            telemetry.inc("rollout.obs.bytes_pipe",
                          self._obs_nbytes * len(indices))
        return [self.obs[i] for i in indices], rewards, dones

    def drain_completed_episodes(self) -> List[Dict[str, Any]]:
        out, self.completed_episodes = self.completed_episodes, []
        return out

    def restart_episodes(self) -> List[Dict[str, np.ndarray]]:
        """See VectorEnv.restart_episodes: workers advance their own seeds
        on the dedicated restart command and drop partial accumulators."""
        if self._first_reset:
            return self.reset()
        self._stacked_cache = None
        for i in range(self.num_envs):
            self._send(i, ("restart", None))
        obs = [self._recv(conn)[1] for conn in self._conns]
        self.obs = obs
        if self._slabs is not None:
            self._write_row0(obs)
            self._obs_cache = obs
        return self.obs

    def close(self) -> None:
        """Idempotent shutdown: close acks drained under one shared
        deadline, workers join-escalated (join -> terminate -> kill) so a
        wedged worker can never hang teardown, and the shm slabs are
        unlinked last (their finalizer covers paths that never reach
        here)."""
        if self._closed:
            return
        self._closed = True
        for conn in self._conns:
            try:
                conn.send(("close", None))
            except (BrokenPipeError, OSError):
                pass
        # drain to each worker's "closed" ack (stale step replies may sit
        # ahead of it when closing after a worker error) and merge the
        # worker's telemetry counters into this process's registry. One
        # SHARED 2 s deadline across all conns: a wedged worker must not
        # serially cost 2 s per env on the failure-path teardown (the
        # join/terminate below still reaps it). With the flight recorder
        # on, the ack carries each worker's full event trace — give the
        # drain real room so a long run's traces are not silently cut
        # off mid-merge by the teardown budget
        deadline = time.monotonic() + (30.0 if flight.enabled() else 2.0)
        for i, conn in enumerate(self._conns):
            try:
                while True:
                    remaining = deadline - time.monotonic()
                    if remaining <= 0 or not conn.poll(remaining):
                        break
                    kind, payload = conn.recv()
                    if kind == "closed":
                        payload = payload or {}
                        counters = payload.get("counters")
                        if counters and telemetry.enabled():
                            for name, value in counters.items():
                                telemetry.inc(name, int(value))
                        trace = payload.get("flight")
                        if trace and flight.enabled():
                            flight.extend(trace, env_index=i)
                        self.worker_states[i] = payload.get("process")
                        break
            except (EOFError, BrokenPipeError, OSError):
                pass
        for proc in self._procs:
            proc.join(timeout=5)
            if proc.is_alive():
                proc.terminate()
                proc.join(timeout=2)
            if proc.is_alive():  # terminate ignored (blocked in syscall)
                proc.kill()
                proc.join(timeout=1)
        if self._ring is not None:
            if self._active_seg is None and self._slabs is not None:
                # ring installed but never leased: the pre-ring slab
                # was not yet retired by begin_ring_segment — unlink it
                # here (the parent-unlinks-on-close contract)
                self._slabs.close()
            # unlink every ring segment (after the first lease,
            # self._slabs is one of them)
            self._ring.close()
            self._ring = None
            self._slabs = None
        if self._slabs is not None:
            self._slabs.close()
            self._slabs = None


class RolloutCollector:
    """Collects [T, B] trajectory batches for the PPO learner.

    With ``pipeline=True`` (default for an even batch of >= 2 envs) the envs
    are split into two groups and collection interleaves them: while the host
    steps group A's simulators, the device is already computing group B's
    action batch (jax dispatch is asynchronous), so the per-step device
    round-trip is hidden behind env
    stepping instead of serialised with it.
    """

    def __init__(self, vec_env: VectorEnv, learner, rollout_length: int,
                 pipeline: Optional[bool] = None,
                 deferred_fetch: bool = False,
                 ring_segments: Optional[int] = None):
        self.vec_env = vec_env
        self.learner = learner
        self.rollout_length = rollout_length
        B = vec_env.num_envs
        # trajectory-ring sizing (rl/ring.py): on a shm vec env the
        # deferred collector leases one [T+1, B, ...] segment per
        # collect instead of rewriting the single slab, which deletes
        # the per-segment bulk defensive copy (the PR 4 aliasing
        # hazard is handled by segment ownership: a leased segment is
        # not rewritten until its release token reports the staged
        # batch consumed). None resolves to the double-buffer minimum
        # (2) for deferred fetch; 0 forces the legacy single slab +
        # bulk copy; the depth-K pipelined loop passes depth + 2.
        if ring_segments is None:
            ring_segments = 2 if deferred_fetch else 0
        self.ring_segments = int(ring_segments)
        # deferred_fetch (the pipelined loop mode, train/loops.py): one
        # jitted program per step (rng split folded in), actions are the
        # ONLY per-step device fetch (logp/values stay device futures,
        # drained in one device_get at segment end), obs rows are copied
        # into preallocated [T, B, ...] traj buffers while the forward
        # is in flight, and every transfer is explicit
        # (device_put/device_get — pinned by the transfer-guard test).
        # Bit-identical outputs to the plain path; only the
        # dispatch/fetch schedule changes.
        self.deferred_fetch = bool(deferred_fetch)
        self._jit_step_fn = None
        # explicit staging target for the stacked obs: the learner's
        # replicated mesh sharding (where its params live), so the jitted
        # sample needs no implicit device-to-device reshard — a bare
        # device_put would commit to ONE device and trip the
        # transfer-guard pin (and a real reshard) on multi-device meshes.
        # MULTI-PROCESS: never — each process's obs are ITS OWN shard of
        # the collection, and a device_put onto the global mesh would
        # fabricate a "replicated" global array from process-divergent
        # data (mismatched collectives downstream: gloo size errors).
        # There the batch rides into the jit as host arrays, exactly as
        # the pre-round-6 collector did.
        self._obs_sharding = (getattr(learner, "_replicated", None)
                              if jax.process_count() == 1 else None)
        if self.deferred_fetch:
            pipeline = False  # deferred path has its own schedule
            if getattr(vec_env, "prefetch_stacked", None) is False:
                vec_env.prefetch_stacked = True
        if pipeline is None and (B < 2 or B % 2
                                 or jax.default_backend() == "cpu"):
            # overlap only exists when sampling runs on an accelerator; on a
            # CPU backend the device IS the host, and two half-batch calls
            # just double the sampling overhead
            pipeline = False
        # pipeline=None: decide adaptively after timing the first collect.
        # Per step, pipelined cost ~ 2*max(sample, env/2) vs non-pipelined
        # sample + env, so splitting wins exactly when sampling is cheaper
        # than env stepping — with a high-latency device and fast host
        # envs, pipelining *doubles* the dominant round-trip count.
        self.pipeline = pipeline
        self._needs_reset = True

    def _step_program(self):
        """One jitted program per rollout step: rng split + sampling fused,
        so the host dispatches once instead of paying a separate
        ~ms-scale ``jax.random.split`` dispatch per step. The split tree
        is IDENTICAL to the plain path's host-side
        ``rng, step_rng = split(rng)`` followed by sampling with
        ``step_rng`` — same bits out."""
        if self._jit_step_fn is None:
            sample = self.learner._sample_actions

            def step_fn(params, obs, rng):
                rng, step_rng = jax.random.split(rng)
                actions, logp, values = sample(params, obs, step_rng)
                return rng, actions, logp, values

            self._jit_step_fn = jax.jit(step_fn)
        return self._jit_step_fn

    def _collect_deferred(self, params, rng) -> Dict[str, Any]:
        """Deferred-fetch collection (see __init__); [T, B] outputs
        bit-identical to the plain path.

        On a shm-backend vec env the workers' in-place writes ARE the
        trajectory buffer (row t = the obs before step t, row T = the
        bootstrap obs). With ``ring_segments >= 2`` (the default for
        deferred fetch) each collect leases one segment of a
        K-segment trajectory ring (rl/ring.py) and returns ZERO-COPY
        views of its rows: segment ownership — a published segment is
        not rewritten until its release token reports the staged batch
        consumed — replaces the bulk defensive copy the single slab
        needed. That copy was a correctness requirement there: jax's
        CPU client ZERO-COPY ALIASES page-aligned host buffers (shm
        mmaps are page-aligned) when a device_put/jit input needs no
        layout change — measured on a 1-device mesh — so single-slab
        views staged into the async update would be silently rewritten
        by the next segment's worker writes (``ring_segments=0`` keeps
        that legacy path: slab + bulk copy). The per-step sample inputs
        stay views on every path because each step's
        ``device_get(actions)`` completes the forward before any row it
        read is rewritten."""
        T, B = self.rollout_length, self.vec_env.num_envs
        step_fn = self._step_program()
        ring = segment = None
        if self.ring_segments >= 2:
            ensure_ring = getattr(self.vec_env, "ensure_traj_ring", None)
            if ensure_ring is not None:
                ring = ensure_ring(T + 1, self.ring_segments)
        if ring is not None:
            # lease the next free segment (counts a stall + blocks on
            # the oldest published segment's release token when the
            # learner is behind); its row 0 receives the bootstrap obs
            segment = ring.lease()
            self.vec_env.begin_ring_segment(segment)
            use_slab = True
        else:
            ensure = getattr(self.vec_env, "ensure_traj_rows", None)
            use_slab = bool(ensure is not None and ensure(T + 1))
            if use_slab:
                # carry the previous segment's bootstrap obs into row 0
                self.vec_env.rebase_row0()
        if self._obs_sharding is not None:
            # the epoch's incoming key was split outside the mesh; place
            # it next to the params explicitly (after step 0 the key is
            # step_fn's own replicated output and stays put)
            rng = jax.device_put(rng, self._obs_sharding)
        act_buf = np.zeros((T, B), dtype=np.int32)
        rew_buf = np.zeros((T, B), dtype=np.float32)
        done_buf = np.zeros((T, B), dtype=bool)
        traj_obs: Optional[Dict[str, np.ndarray]] = None
        logp_refs: List[Any] = [None] * T
        val_refs: List[Any] = [None] * T
        for t in range(T):
            batched = self.vec_env.stacked_obs()
            staged = (jax.device_put(batched, self._obs_sharding)
                      if self._obs_sharding is not None else batched)
            rng, actions, logp, values = step_fn(params, staged, rng)
            if not use_slab:
                if traj_obs is None:
                    traj_obs = {k: np.empty((T,) + batched[k].shape,
                                            batched[k].dtype)
                                for k in OBS_KEYS}
                # the copy into the traj buffers runs while the device is
                # still computing this step's forward
                for k in OBS_KEYS:
                    traj_obs[k][t] = batched[k]
                if telemetry.enabled():
                    telemetry.inc("rollout.obs.bytes_traj_copy",
                                  sum(np.asarray(batched[k]).nbytes
                                      for k in OBS_KEYS))
            actions = jax.device_get(actions)
            act_buf[t] = actions
            logp_refs[t] = logp
            val_refs[t] = values
            _, rewards, dones = self.vec_env.step(actions)
            rew_buf[t] = rewards
            done_buf[t] = dones
        if segment is not None:
            # ring path: the trajectory IS the leased segment's rows —
            # zero-copy views, safe without the bulk defensive copy
            # because the segment is not rewritten until its release
            # token (attached by the caller once the staged batch is
            # provably consumed) reports ready
            traj_obs = dict(self.vec_env.traj_obs_views(T))
        elif use_slab:
            # single-slab path: one bulk memcpy of the worker-written
            # slab rows into a fresh buffer (see docstring: staging
            # must never alias the reused slab); np.array allocates +
            # copies in one call
            views = self.vec_env.traj_obs_views(T)
            traj_obs = {k: np.array(v) for k, v in views.items()}
            if telemetry.enabled():
                telemetry.inc("rollout.obs.bytes_traj_copy",
                              sum(v.nbytes for v in traj_obs.values()))
        final = self.vec_env.stacked_obs()
        final_staged = (jax.device_put(final, self._obs_sharding)
                        if self._obs_sharding is not None else final)
        rng, _, _, last_values = step_fn(params, final_staged, rng)
        # ONE drain for every deferred future (all long since ready —
        # this is a batch of buffer copies, not a wait). It also blocks
        # on the bootstrap forward, so the staged `final` (possibly an
        # alias of the segment's bootstrap row) is consumed before the
        # segment is handed over.
        logp_host, val_host, last_host = jax.device_get(
            (logp_refs, val_refs, last_values))
        out = {
            "traj": {"obs": traj_obs, "actions": act_buf,
                     "logp": np.stack(logp_host).astype(np.float32),
                     "values": np.stack(val_host).astype(np.float32),
                     "rewards": rew_buf, "dones": done_buf},
            "last_values": np.asarray(last_host, np.float32),
            "episodes": self.vec_env.drain_completed_episodes(),
            "env_steps": T * B,
        }
        if segment is not None:
            # ownership passes to the learner; the caller MUST run the
            # two-phase token protocol (ring.note_staged/note_update —
            # train/loops.py is the model), quoting the
            # generation so a late token can't release a recycled
            # segment
            ring.publish(segment)
            out["ring"] = ring
            out["ring_segment"] = segment
            out["ring_generation"] = segment.generation
        return out

    def collect(self, params, rng) -> Dict[str, Any]:
        """Run rollout_length steps in every env; returns a trajectory dict
        of [T, B, ...] host arrays plus bootstrap values [B]."""
        T, B = self.rollout_length, self.vec_env.num_envs
        if self._needs_reset:
            self.vec_env.reset()
            self._needs_reset = False
        if self.deferred_fetch:
            return self._collect_deferred(params, rng)
        if self.pipeline and B >= 2 and B % 2 == 0:
            return self._collect_pipelined(params, rng)

        obs_buf: List[Dict[str, np.ndarray]] = []
        act_buf = np.zeros((T, B), dtype=np.int32)
        logp_buf = np.zeros((T, B), dtype=np.float32)
        val_buf = np.zeros((T, B), dtype=np.float32)
        rew_buf = np.zeros((T, B), dtype=np.float32)
        done_buf = np.zeros((T, B), dtype=bool)

        measure = self.pipeline is None and B >= 2 and B % 2 == 0
        sample_time = env_time = 0.0
        for t in range(T):
            batched = stack_obs(self.vec_env.obs)
            rng, step_rng = jax.random.split(rng)
            # t == 0 pays jit trace+compile for sample_actions; excluding
            # it keeps the measurement at steady-state cost
            timing = measure and t > 0
            t0 = time.perf_counter() if timing else 0.0
            actions, logp, values = self.learner.sample_actions(
                params, batched, step_rng)
            actions = np.asarray(actions)
            if timing:
                sample_time += time.perf_counter() - t0
            obs_buf.append(batched)
            act_buf[t] = actions
            logp_buf[t] = np.asarray(logp)
            val_buf[t] = np.asarray(values)
            t0 = time.perf_counter() if timing else 0.0
            _, rewards, dones = self.vec_env.step(actions)
            if timing:
                env_time += time.perf_counter() - t0
            rew_buf[t] = rewards
            done_buf[t] = dones
        if measure and T > 1:
            # see __init__: split-batch overlap wins iff sampling (device
            # round-trip incl. dispatch+fetch) is cheaper than env stepping
            self.pipeline = sample_time < env_time

        final = stack_obs(self.vec_env.obs)
        rng, val_rng = jax.random.split(rng)
        _, _, last_values = self.learner.sample_actions(params, final,
                                                        val_rng)

        traj_obs = {k: np.stack([o[k] for o in obs_buf])
                    for k in OBS_KEYS}
        return {
            "traj": {"obs": traj_obs, "actions": act_buf, "logp": logp_buf,
                     "values": val_buf, "rewards": rew_buf,
                     "dones": done_buf},
            "last_values": np.asarray(last_values),
            "episodes": self.vec_env.drain_completed_episodes(),
            "env_steps": T * B,
        }

    def _collect_pipelined(self, params, rng) -> Dict[str, Any]:
        """Two-group interleaved collection (see class docstring).

        Device-dispatch order per step t: sample(G0, t), sample(G1, t),
        sample(G0, t+1), ... — each half's host env stepping overlaps the
        other half's device sampling.
        """
        T, B = self.rollout_length, self.vec_env.num_envs
        H = B // 2
        groups = [list(range(H)), list(range(H, B))]

        obs_buf: List[List[Dict[str, np.ndarray]]] = [[], []]
        act_buf = np.zeros((T, B), dtype=np.int32)
        logp_buf = np.zeros((T, B), dtype=np.float32)
        val_buf = np.zeros((T, B), dtype=np.float32)
        rew_buf = np.zeros((T, B), dtype=np.float32)
        done_buf = np.zeros((T, B), dtype=bool)
        last_values = [None, None]

        def sample(g, step_rng):
            batched = stack_obs([self.vec_env.obs[i] for i in groups[g]])
            return batched, self.learner.sample_actions(params, batched,
                                                        step_rng)

        cols = [slice(0, H), slice(H, B)]
        rng, r0 = jax.random.split(rng)
        pending = [sample(0, r0), None]
        for t in range(T):
            rng, r1 = jax.random.split(rng)
            pending[1] = sample(1, r1)
            for g in (0, 1):
                batched, (actions, logp, values) = pending[g]
                actions = np.asarray(actions)  # blocks on this half only
                obs_buf[g].append(batched)
                act_buf[t, cols[g]] = actions
                logp_buf[t, cols[g]] = np.asarray(logp)
                val_buf[t, cols[g]] = np.asarray(values)
                # host steps this half while the device runs the other half's
                # (already dispatched) sampling
                _, rewards, dones = self.vec_env.step_subset(groups[g],
                                                             actions)
                rew_buf[t, cols[g]] = rewards
                done_buf[t, cols[g]] = dones
                if g == 0:
                    rng, rnext = jax.random.split(rng)
                    pending[0] = sample(0, rnext)
                    if t + 1 == T:
                        last_values[0] = pending[0][1][2]
        # group 1 bootstrap: dispatched after group 0's
        rng, rlast = jax.random.split(rng)
        last_values[1] = sample(1, rlast)[1][2]

        traj_obs = {
            k: np.concatenate(
                [np.stack([o[k] for o in obs_buf[0]]),
                 np.stack([o[k] for o in obs_buf[1]])], axis=1)
            for k in OBS_KEYS}
        return {
            "traj": {"obs": traj_obs, "actions": act_buf, "logp": logp_buf,
                     "values": val_buf, "rewards": rew_buf,
                     "dones": done_buf},
            "last_values": np.concatenate([np.asarray(last_values[0]),
                                           np.asarray(last_values[1])]),
            "episodes": self.vec_env.drain_completed_episodes(),
            "env_steps": T * B,
        }
