"""Training and evaluation loops.

TPU-native replacements for the reference's loop layer (SURVEY.md §2.7):

* ``RLEpochLoop`` — replaces ``RLlibEpochLoop`` (ddls/loops/
  rllib_epoch_loop.py:34). Instead of wrapping an RLlib Trainer (Ray
  process topology), it owns the flax GNN policy, the mesh-sharded
  ``PPOLearner``, and a vectorised rollout collector; ``run()`` is one
  collect+update epoch as two jitted device programs. Accepts the
  reference's RLlib-style ``algo_config``/``model`` dicts so the existing
  config trees drive it unchanged.
* ``EvalLoop`` — heuristic-actor evaluation (ddls/loops/eval_loop.py:14).
* ``RLEvalLoop`` — trained-policy evaluation from a checkpoint
  (ddls/loops/rllib_eval_loop.py:11).
* ``EnvLoop`` / ``EpochLoop`` — generic episode/epoch drivers
  (ddls/loops/env_loop.py:4, epoch_loop.py:5).
"""
from __future__ import annotations

import time
from typing import Any, Callable, Dict, List, Optional, Sequence

import numpy as np

from ddls_tpu import telemetry
from ddls_tpu.telemetry import startup
from ddls_tpu.utils.common import (available_cores, get_class_from_path,
                                   seed_everything)

# RLlib PPO keys (algo/ppo.yaml) -> PPOConfig fields
_RLLIB_TO_PPO = {
    "lr": "lr",
    "gamma": "gamma",
    "lambda": "gae_lambda",
    "lambda_": "gae_lambda",
    "kl_coeff": "kl_coeff",
    "kl_target": "kl_target",
    "clip_param": "clip_param",
    "vf_clip_param": "vf_clip_param",
    "vf_loss_coeff": "vf_loss_coeff",
    "entropy_coeff": "entropy_coeff",
    "num_sgd_iter": "num_sgd_iter",
    "sgd_minibatch_size": "sgd_minibatch_size",
    "train_batch_size": "train_batch_size",
    "grad_clip": "grad_clip",
}


# algo_config keys consumed by the epoch loops themselves rather than the
# per-algorithm translators (num_workers sizes the vectorised env pool;
# device_collector flips PPO collection to the jitted in-kernel env,
# device_bank_jobs sizes its per-lane sampled job banks,
# use_jax_lookahead_memo gates the in-kernel lookahead memo:
# "auto" (default) = on at every lane count (the wide-vmap batched
# probe), True/False force it — sim/jax_memo.py)
_LOOP_LEVEL_ALGO_KEYS = {"num_workers", "device_collector",
                         "device_bank_jobs", "use_jax_lookahead_memo"}


def _reject_unknown_algo_keys(algo_name: str, keys, known) -> None:
    """Hard-error on algo_config keys nothing consumes. Silently accepting
    and ignoring a hyperparameter is the failure mode round 1 flagged for
    algo configs (VERDICT r2 weakness 6): a user sweeping such a key would
    sweep a no-op. Ray-only plumbing keys are not grandfathered — the
    shipped yamls omit them, and a config carrying them should say so
    loudly rather than pretend they took effect."""
    unknown = sorted(set(keys) - set(known) - _LOOP_LEVEL_ALGO_KEYS)
    if unknown:
        raise ValueError(
            f"{algo_name} algo_config keys {unknown} are not consumed by "
            f"the TPU stack; remove them (or map them in train/loops.py). "
            f"Known keys: {sorted(set(known) | _LOOP_LEVEL_ALGO_KEYS)}")


def ppo_config_from_rllib(algo_config: Optional[dict]):
    """Translate an RLlib-style PPO config dict into a ``PPOConfig``."""
    from ddls_tpu.rl.ppo import PPOConfig

    _reject_unknown_algo_keys("ppo", (algo_config or {}), _RLLIB_TO_PPO)
    kwargs = {}
    for src, dst in _RLLIB_TO_PPO.items():
        if algo_config and algo_config.get(src) is not None:
            kwargs[dst] = algo_config[src]
    return PPOConfig(**kwargs)


# RLlib Ape-X DQN keys (algo/apex_dqn.yaml) -> DQNConfig fields; nested
# replay_buffer_config / exploration_config keys are flattened first
_RLLIB_TO_DQN = {
    "lr": "lr",
    "gamma": "gamma",
    "n_step": "n_step",
    "train_batch_size": "train_batch_size",
    "target_network_update_freq": "target_network_update_freq",
    "double_q": "double_q",
    "dueling": "dueling",
    "num_atoms": "num_atoms",
    "grad_clip": "grad_clip",
    "training_intensity": "training_intensity",
    "capacity": "buffer_capacity",
    "prioritized_replay_alpha": "prioritized_replay_alpha",
    "prioritized_replay_beta": "prioritized_replay_beta",
    "prioritized_replay_eps": "prioritized_replay_eps",
    "learning_starts": "learning_starts",
    "initial_epsilon": "initial_epsilon",
    "final_epsilon": "final_epsilon",
    "epsilon_timesteps": "epsilon_timesteps",
}


def dqn_config_from_rllib(algo_config: Optional[dict]):
    """Translate an RLlib-style Ape-X DQN config dict into a ``DQNConfig``
    (reference surface: scripts/ramp_job_partitioning_configs/algo/
    apex_dqn.yaml; Ray-plumbing keys are ignored)."""
    from ddls_tpu.rl.dqn import DQNConfig

    flat = dict(algo_config or {})
    for nested in ("replay_buffer_config", "exploration_config"):
        flat.update(flat.pop(nested, None) or {})
    _reject_unknown_algo_keys("apex_dqn", flat, _RLLIB_TO_DQN)
    kwargs = {}
    for src, dst in _RLLIB_TO_DQN.items():
        if flat.get(src) is not None:
            kwargs[dst] = flat[src]
    return DQNConfig(**kwargs)


def build_policy_from_model_config(n_actions: int,
                                   model_config: Optional[dict]):
    """Build a ``GNNPolicy`` from the reference's model/gnn.yaml surface."""
    from ddls_tpu.models.policy import GNNPolicy

    model_config = model_config or {}
    cmc = model_config.get("custom_model_config", {})
    fcnet_hiddens = model_config.get("fcnet_hiddens") or (256, 256)
    return GNNPolicy(
        n_actions=n_actions,
        out_features_msg=cmc.get("out_features_msg", 32),
        out_features_hidden=cmc.get("out_features_hidden", 64),
        out_features_node=cmc.get("out_features_node", 16),
        out_features_graph=cmc.get("out_features_graph", 8),
        num_rounds=cmc.get("num_rounds", 2),
        module_depth=cmc.get("module_depth", 1),
        activation=cmc.get("aggregator_activation", "relu"),
        fcnet_hiddens=tuple(fcnet_hiddens),
        fcnet_activation=model_config.get("fcnet_activation", "relu"),
        apply_action_mask=cmc.get("apply_action_mask", True))


def _episode_summary(episodes: List[dict]) -> Dict[str, float]:
    # scalar coercions go through the lazy-materialisation helper's
    # as_float: episode records are host state by contract (never device
    # fetches on the per-update path), and routing the coercion through
    # one place keeps it that way if a collector ever slips a device
    # scalar into a record
    from ddls_tpu.train.metrics import as_float

    if not episodes:
        return {}
    out: Dict[str, float] = {
        "episode_reward_mean": as_float(np.mean(
            [e["episode_return"] for e in episodes])),
        "episode_reward_min": as_float(np.min(
            [e["episode_return"] for e in episodes])),
        "episode_reward_max": as_float(np.max(
            [e["episode_return"] for e in episodes])),
        "episode_len_mean": as_float(np.mean(
            [e["episode_length"] for e in episodes])),
        "episodes_this_iter": len(episodes),
    }
    # cluster custom metrics, averaged over episodes (what the reference's
    # RLlib callback surfaces as custom_metrics: ramp_cluster/utils.py:25-73)
    for key in ("num_jobs_completed", "num_jobs_blocked", "blocking_rate",
                "acceptance_rate", "mean_job_completion_time",
                "mean_job_completion_time_speedup"):
        vals = [e[key] for e in episodes if key in e]
        if vals:
            out[f"custom_metrics/{key}_mean"] = as_float(np.mean(vals))
    return out


class RLEpochLoop:
    """One PPO epoch per ``run()`` call, with periodic greedy evaluation.

    ``env_config`` / ``model`` / ``algo_config`` follow the reference's
    config surfaces; mesh/rollout sizing is TPU-specific:

    * ``num_envs`` — parallel env instances (reference: PPO num_workers);
    * ``rollout_length`` — steps per env per epoch (derived from
      train_batch_size when omitted);
    * ``n_devices`` — mesh size for the dp axis (defaults to all devices).

    Pipelining (docs/perf_round6.md):

    * ``loop_mode="pipelined"`` (default) keeps the hot collect→update
      path free of blocking device→host transfers: learner metrics stay
      on device as futures (``LazyMetrics``) and are drained in ONE
      batched fetch at a sync boundary (every ``metrics_sync_interval``
      epochs, an eval epoch, or first scalar access); collection uses
      the deferred-fetch collector (one fused dispatch per step, actions
      the only per-step fetch). ``"sequential"`` reproduces the pre-
      pipelining loop exactly: per-update ``float(device_get(metrics))``
      under ``train.host_sync``. The two modes are bit-identical in
      params/metrics/episodes (pinned in tests/test_train_pipeline.py);
      only the dispatch/sync schedule differs.
    * ``pipeline_depth >= 1`` (opt-in, off-policy-tolerant learners only
      — IMPALA, whose V-trace correction exists precisely for this lag)
      additionally keeps up to ``pipeline_depth`` collected batches in
      flight on a background thread against pre-update params, so host
      env stepping overlaps the device update. Each batch's params
      snapshot is taken at submission; the behavior logp travelling in
      the traj lets V-trace absorb however many updates land before the
      batch is consumed (the per-batch ``params_age_updates`` metric
      reports exactly that lag). On the shm backend the batches ride a
      ``pipeline_depth + 2``-segment trajectory ring (rl/ring.py) whose
      lease→publish→release ownership replaces the per-segment bulk
      copy. Learners whose update assumes fresh data (ppo/pg/dqn/es)
      reject ``pipeline_depth > 0`` loudly, as does any
      ``loop_mode != "pipelined"``.

    Fused mode (rl/fused.py, docs/perf_round8.md):

    * ``loop_mode="fused"`` runs the whole epoch as ONE jitted program —
      a ``lax.scan`` over ``updates_per_epoch`` collect→update rounds on
      the in-kernel environment (the Podracer/Anakin shape; implies
      device collection, single-process only). Learner metrics come back
      as a [U]-stacked device dict (one ``LazyMetrics`` per epoch) and
      episode counters as compact [U, B, T] device traces; BOTH are
      drained per ``metrics_sync_interval`` epochs in one batched fetch
      — never per update — so the steady-state epoch is transfer-free
      (pinned under ``jax.transfer_guard`` in tests/test_fused.py).
      The program's shape is what the user already said: ``num_envs``
      lanes x ``rollout_length`` steps, ``num_envs`` meaning what it
      means on every other loop mode. ``fused_config={"lanes",
      "segment_len"}`` re-factorises the same per-update batch
      (both keys, their product unchanged, lanes a multiple of the
      mesh's ``dp`` axis; anything else raises before a driver is
      built). A program that does not compile raises the compiler's
      own error, naming the shape — it never trains on another path.
      Learners without the scan-based in-kernel contract
      (DQN: host replay insertion; ES: population fitness on host envs)
      reject fused before any env construction.
    """

    # pipeline_depth > 0 staleness is only sound for learners with an
    # explicit off-policy correction; subclasses opt in (ImpalaEpochLoop)
    SUPPORTS_STALE_COLLECTION = False
    # fused epochs need the shared [T, B] traj contract AND an update
    # that traces as one pure function (state, traj, last_values, rng)
    # -> (state, metrics); DQN/ES opt out (host replay / host fitness)
    SUPPORTS_FUSED = True
    # sharded param layouts (parallel/partition.py fsdp/tp) ride the
    # device-collection trajectory contract; DQN/ES opt out (their
    # host replay / population paths never consume the spec table)
    SUPPORTS_PARAM_SHARDING = True
    # socket collection (rl/fragments.py) ships whole [T, B] trajectory
    # segments from actor-host processes over the shared traj contract;
    # DQN's replay insertion and ES's population fitness step the host
    # envs directly and opt out
    SUPPORTS_SOCKET_COLLECTION = True

    def __init__(self,
                 path_to_env_cls: str,
                 env_config: dict,
                 model: Optional[dict] = None,
                 algo_config: Optional[dict] = None,
                 num_envs: Optional[int] = None,
                 rollout_length: Optional[int] = None,
                 n_devices: Optional[int] = None,
                 use_parallel_envs="auto",
                 metric: str = "evaluation/episode_reward_mean",
                 metric_goal: str = "maximise",
                 evaluation_interval: Optional[int] = 1,
                 evaluation_duration: int = 3,
                 evaluation_config: Optional[dict] = None,
                 seed: Optional[int] = 0,
                 test_seed: Optional[int] = None,
                 wandb=None,
                 loop_mode: str = "pipelined",
                 metrics_sync_interval: int = 10,
                 pipeline_depth: int = 0,
                 vec_env_backend: str = "auto",
                 updates_per_epoch: int = 4,
                 fused_config: Optional[dict] = None,
                 sebulba_config: Optional[dict] = None,
                 param_sharding: str = "replicated",
                 tp_size: Optional[int] = None,
                 path_to_model_cls: Optional[str] = None,  # config parity
                 collect_transport: str = "inprocess",
                 socket_config: Optional[dict] = None,
                 scenario=None,
                 run_ledger=None,
                 **kwargs):
        import jax

        from ddls_tpu.rl.rollout import ParallelVectorEnv, VectorEnv

        # scenario plumbing (ddls_tpu/scenarios, ROADMAP item 5): one
        # ScenarioSpec (name, path, or instance) supplies the env
        # construction kwargs and (for failure specs) the runtime; an
        # explicit env_config entry overrides the spec's TOP-LEVEL key
        # wholesale (never a deep merge — a merged jobs_config would
        # silently union synthesis knobs). The canonical spec resolves
        # runtime=None, so its env path is byte-identical to passing the
        # same env_config by hand.
        self.scenario_fingerprint: Optional[str] = None
        if scenario is not None:
            from ddls_tpu.hardware.topologies import build_topology
            from ddls_tpu.scenarios.spec import (build_runtime,
                                                 env_kwargs as
                                                 _scenario_env_kwargs,
                                                 get_spec,
                                                 spec_fingerprint)

            spec = get_spec(scenario) if isinstance(scenario, str) \
                else scenario
            merged = dict(_scenario_env_kwargs(spec))
            merged.update(env_config or {})
            runtime = build_runtime(spec, build_topology(spec.topology))
            if runtime is not None:
                merged["scenario_runtime"] = runtime
            env_config = merged
            self.scenario_fingerprint = spec_fingerprint(spec)

        self._env_cls_path = path_to_env_cls
        self.env_cls = get_class_from_path(path_to_env_cls)
        self.env_config = dict(env_config)
        self.metric = metric
        self.metric_goal = metric_goal
        self.evaluation_interval = evaluation_interval
        self.evaluation_duration = evaluation_duration
        self.evaluation_config = evaluation_config or {}
        self.wandb = wandb
        self.seed = 0 if seed is None else int(seed)
        self.test_seed = test_seed

        if loop_mode not in ("sequential", "pipelined", "fused",
                             "sebulba"):
            raise ValueError(
                f"loop_mode must be 'sequential', 'pipelined', 'fused' "
                f"or 'sebulba', got {loop_mode!r}")
        if (loop_mode in ("fused", "sebulba")
                and not self.SUPPORTS_FUSED):
            # SUPPORTS_FUSED gates BOTH in-kernel-collection drivers:
            # fused (one traced collect→update program) and sebulba
            # (in-kernel collection on an actor sub-mesh) need the
            # shared traj contract plus a standalone jitted update
            raise ValueError(
                f"{type(self).__name__} does not support loop_mode="
                f"{loop_mode!r}: the fused/sebulba drivers need "
                "in-kernel collection plus a jitted scan-based update "
                "— DQN's replay insertion and ES's population fitness "
                "step the host envs by contract (use ppo/impala/pg, or "
                "rl/es_device.py for on-device ES)")
        if loop_mode == "fused" and jax.process_count() > 1:
            raise ValueError(
                "loop_mode='fused' is single-process: collection lanes "
                "and the sharded update live in ONE program, which "
                "would need globally-assembled bank/sim-state arrays "
                "under multi-host (use loop_mode='pipelined' with "
                "device_collector there)")
        if loop_mode == "sebulba" and jax.process_count() > 1:
            raise ValueError(
                "loop_mode='sebulba' is single-process: the actor/"
                "learner split partitions the LOCAL devices and hands "
                "batches over a process-local device ring (use "
                "loop_mode='pipelined' with device_collector under "
                "multi-host)")
        # param layout knob (parallel/partition.py): validated BEFORE any
        # env construction, the fused/sebulba loud-rejection convention
        from ddls_tpu.parallel import partition as _partition

        _partition.validate_layout(param_sharding)
        self.param_sharding = param_sharding
        self.tp_size = tp_size
        if param_sharding != "replicated":
            if not self.SUPPORTS_PARAM_SHARDING:
                raise ValueError(
                    f"{type(self).__name__} does not support "
                    f"param_sharding={param_sharding!r}: the sharded "
                    "layouts ride the device-collection trajectory "
                    "contract — DQN's replay insertion and ES's "
                    "population fitness never consume the spec table "
                    "(use ppo/impala/pg, or param_sharding='replicated')")
            if jax.process_count() > 1:
                raise ValueError(
                    f"param_sharding={param_sharding!r} is single-"
                    "process: the sharded state lives on one process's "
                    "mesh — the multi-host identical-state placement "
                    "contract (parallel/mesh.py:place_state_tree) only "
                    "covers replicated layouts today (use "
                    "param_sharding='replicated' under multi-host)")
            if loop_mode == "sebulba" and param_sharding == "tp":
                raise ValueError(
                    "param_sharding='tp' cannot combine with "
                    "loop_mode='sebulba': the actor/learner sub-meshes "
                    "are 1-axis dp meshes (rl/sebulba.py:split_meshes) "
                    "and have no 'mp' axis to shard over — use "
                    "param_sharding='fsdp' or a non-split loop_mode")
            # fail fast on an infeasible mesh for the layout (e.g. a tp
            # factorisation that does not divide the device count)
            _partition.mesh_for_layout(n_devices, param_sharding,
                                       tp_size)
        self.loop_mode = loop_mode
        self.updates_per_epoch = max(int(updates_per_epoch or 1), 1)
        self.fused_config = dict(fused_config or {})
        # sebulba runtime state: the sub-mesh split (self.mesh becomes
        # the LEARNER sub-mesh after _build_sebulba so the update/
        # checkpoints/eval keep using it) — keys: actor_devices (count,
        # default half the local devices), ring_segments (default
        # pipeline_depth + 2)
        self.sebulba_config = dict(sebulba_config or {})
        self.actor_mesh = None
        # fused runtime state: the driver and the undrained compact
        # episode-counter traces
        self.fused = None
        self._fused_episode_ring: List[Any] = []
        # the epoch whose drain boundary already waited for the device
        # under ``train.device_wait`` (telemetry-only, _device_wait)
        self._device_waited_epoch = -1
        self.metrics_sync_interval = max(int(metrics_sync_interval or 1), 1)
        self.pipeline_depth = int(pipeline_depth or 0)
        if self.pipeline_depth < 0:
            raise ValueError(
                f"pipeline_depth must be >= 0, got {pipeline_depth}")
        if self.pipeline_depth and not self.SUPPORTS_STALE_COLLECTION:
            raise ValueError(
                f"{type(self).__name__} does not support pipeline_depth > "
                "0: collecting against stale params needs an explicit "
                "off-policy correction (IMPALA's V-trace); ppo/pg/dqn/es "
                "must collect with the current params (pipeline_depth=0)")
        if (self.pipeline_depth
                and self.loop_mode not in ("pipelined", "sebulba")):
            raise ValueError(
                "pipeline_depth > 0 requires loop_mode='pipelined' or "
                "'sebulba'")
        if vec_env_backend not in ("auto", "pipe", "shm"):
            raise ValueError(
                f"vec_env_backend must be 'auto', 'pipe' or 'shm', got "
                f"{vec_env_backend!r}")
        # subprocess obs transport (rl/rollout.py): 'auto' = zero-copy
        # shared-memory slabs where POSIX shm is usable, pipe otherwise;
        # bit-exact either way (tests/test_shm.py pins pipe==shm params/
        # episodes), so the default favours the cheaper transport
        self.vec_env_backend = vec_env_backend
        # pipelining runtime state: the queue of prefetched
        # (out, straj, slv) futures (depth entries deep, each tagged
        # with the update-counter version its params snapshot was taken
        # at), the unsynced-metrics ring, and the lazily-created
        # executors (collection thread / device-update watcher)
        self._collect_futures: List[Any] = []
        self._collect_executor = None
        self._watch_executor = None
        self._metrics_ring: List[Any] = []
        self._updates_dispatched = 0

        self._configure_algo(algo_config, num_envs, rollout_length)
        # collection backend: host vectorised envs (default) or the
        # fully-jitted in-kernel env (rl/ppo_device.py) — one device
        # dispatch per [T, B] segment instead of T round-trips. Parsed
        # here (not in _configure_algo, which subclasses replace) so every
        # algo sees the key; loops whose collection cannot run in-kernel
        # (DQN, ES) reject it loudly in their _build_learner.
        self.device_collector = bool(
            (algo_config or {}).get("device_collector", False))
        if self.loop_mode in ("fused", "sebulba"):
            # fused/sebulba collection runs the in-kernel env by
            # construction: the same template-env/bank setup as
            # device_collector
            self.device_collector = True
        self.device_bank_jobs = (algo_config or {}).get("device_bank_jobs")
        # the in-kernel lookahead memo knob (ISSUE 13/17,
        # sim/jax_memo.py): "auto" resolves to ON at every lane count —
        # the batched probe masks hit lanes out of the lookahead
        # while_loop, so multi-lane vmap collection hits the cache too
        self.use_jax_lookahead_memo = (algo_config or {}).get(
            "use_jax_lookahead_memo", "auto")
        if (self.use_jax_lookahead_memo != "auto"
                and not self.device_collector):
            # loud-rejection convention (as pipeline_depth /
            # device_collector on DQN/ES): a forced value on a path that
            # never consults it would silently no-op while the user
            # believes the memo is active in their comparison runs
            raise ValueError(
                "use_jax_lookahead_memo is an in-kernel-collection knob "
                "(sim/jax_memo.py): it needs algo_config."
                "device_collector=true or loop_mode='fused' — remove it "
                "or leave it 'auto' for host collection")

        # socket collection knob (rl/fragments.py, ROADMAP item 4):
        # trajectory ring segments arrive framed from actor-host
        # processes; validated BEFORE env construction, the loud-
        # rejection convention
        if collect_transport not in ("inprocess", "socket"):
            raise ValueError(
                f"collect_transport must be 'inprocess' or 'socket', "
                f"got {collect_transport!r}")
        if socket_config and collect_transport != "socket":
            raise ValueError(
                "socket_config is a collect_transport='socket' knob: a "
                "forced config on the in-process path would silently "
                "no-op — remove it or set collect_transport='socket'")
        self.collect_transport = collect_transport
        self.socket_config = dict(socket_config or {})
        if collect_transport == "socket":
            if not self.SUPPORTS_SOCKET_COLLECTION:
                raise ValueError(
                    f"{type(self).__name__} does not support "
                    "collect_transport='socket': fragments ship whole "
                    "[T, B] trajectory segments over the shared traj "
                    "contract — DQN's replay insertion and ES's "
                    "population fitness step the host envs directly "
                    "(use ppo/impala/pg)")
            if self.loop_mode != "pipelined":
                raise ValueError(
                    "collect_transport='socket' requires loop_mode="
                    "'pipelined': the fragment consumer is the deferred-"
                    "fetch collector contract (fused/sebulba collect "
                    "in-kernel; sequential would serialise the only "
                    "overlap the second process buys)")
            if self.device_collector:
                raise ValueError(
                    "collect_transport='socket' is host collection on "
                    "the actor hosts — it cannot combine with "
                    "algo_config.device_collector (the in-kernel env "
                    "has no vec env to ship)")
            if jax.process_count() > 1:
                raise ValueError(
                    "collect_transport='socket' is single-LEARNER-"
                    "process: actor hosts are its own spawned "
                    "subprocesses (multi-host jax runtimes coordinate "
                    "collectives, not fragment sockets)")

        # Multi-host: each process must collect DIFFERENT rollouts (its
        # shard of the global batch), so env seeds and the action-sampling
        # rng are offset by the process index; parameter init and the rng
        # fed into the jitted sharded update must stay IDENTICAL on every
        # process, or the nominally replicated state silently diverges.
        self._collect_seed = self.seed + jax.process_index() * 100_003

        seed_everything(self.seed)
        host_pool_size = self.num_envs
        # the actor hosts inherit the caller's env-parallelism intent
        # even though the learner itself only keeps a template env
        self._actor_use_parallel_envs = (
            use_parallel_envs if use_parallel_envs != "auto"
            else available_cores() > 1)
        if self.device_collector or self.collect_transport == "socket":
            # collection runs in-kernel (device_collector) or on the
            # actor hosts (socket fragments); the learner side only
            # needs ONE in-process env as the obs/param template
            # (evaluation builds its own envs via make_eval_env)
            use_parallel_envs = False
            host_pool_size = 1
        elif use_parallel_envs == "auto":
            # subprocess env workers only pay off with real cores to run on
            use_parallel_envs = available_cores() > 1
        with startup.span("startup.env"):
            if use_parallel_envs:
                self.vec_env = ParallelVectorEnv(
                    self.env_cls, self.env_config, self.num_envs,
                    seeds=[self._collect_seed + i
                           for i in range(self.num_envs)],
                    backend=self.vec_env_backend)
            else:
                self.vec_env = VectorEnv(
                    [lambda: self.env_cls(**self.env_config)
                     for _ in range(host_pool_size)],
                    seeds=[self._collect_seed + i
                           for i in range(host_pool_size)])
            self.vec_env.reset()

        template_env = getattr(self.vec_env, "envs", [None])[0]
        if template_env is not None:
            n_actions = template_env.action_space.n
        else:
            n_actions = int(np.asarray(
                self.vec_env.obs[0]["action_mask"]).shape[0])
        self.n_actions = n_actions
        # raw model config rides the fragment CONFIG frame so actor
        # hosts build the identical policy (frozen param-tree paths)
        self._model_config = model
        with startup.span("startup.model"):
            self.model = self._build_model(n_actions, model)
            obs0 = jax.tree_util.tree_map(np.asarray, self.vec_env.obs[0])
            self.params = self.model.init(jax.random.PRNGKey(self.seed),
                                          obs0)

        from ddls_tpu.models.policy import batched_policy_apply
        # replicated/fsdp build the exact 1-D dp mesh make_mesh always
        # built; tp builds the ("dp", "mp") mesh its layout shards over
        self.mesh = _partition.mesh_for_layout(n_devices,
                                               self.param_sharding,
                                               self.tp_size)
        self.apply_fn = lambda p, o: batched_policy_apply(self.model, p, o)
        self._build_learner()
        # warm-start / mid-training resume (the reference has no Launcher
        # resume — SURVEY §5.4; here any saved train state can seed a new
        # run, e.g. fine-tuning the best checkpoint at a lower lr)
        if kwargs.get("initial_checkpoint_path"):
            self.load_agent_checkpoint(kwargs["initial_checkpoint_path"])
            print(f"Warm-started train state from "
                  f"{kwargs['initial_checkpoint_path']}")

        self._rng = jax.random.PRNGKey(self.seed + 1)
        # offset keeps the collect stream distinct from the update stream
        # even on process 0, where _collect_seed == seed
        self._collect_rng = jax.random.PRNGKey(self._collect_seed + 7919)
        self.epoch_counter = 0
        self.total_env_steps = 0
        self.best_metric_value: Optional[float] = None
        self.best_checkpoint_path: Optional[str] = None
        self.checkpoint_history: List[dict] = []
        self.run_time = 0.0

        # opt-in run ledger (telemetry/runlog.py, ISSUE 18): the
        # manifest records the RESOLVED loop config; close() finalizes
        # it with the ring/memo counter blocks and final results
        self.run_ledger = run_ledger
        if self.run_ledger is not None:
            self.run_ledger.update_config({
                "algo": next((k for k, v in EPOCH_LOOPS.items()
                              if v is type(self)), type(self).__name__),
                "loop_mode": self.loop_mode,
                "num_envs": self.num_envs,
                "rollout_length": self.rollout_length,
                "updates_per_epoch": self.updates_per_epoch,
                "pipeline_depth": self.pipeline_depth,
                "metrics_sync_interval": self.metrics_sync_interval,
                "device_collector": self.device_collector,
                "param_sharding": self.param_sharding,
                "vec_env_backend": self.vec_env_backend,
                "collect_transport": self.collect_transport,
                "n_devices": getattr(self.mesh, "size", None),
                "seed": self.seed,
            })
            if (self.scenario_fingerprint is not None
                    and self.run_ledger.scenario_fingerprint is None):
                # scenario-built runs are fingerprint-reproducible: the
                # manifest carries the spec hash unless the caller
                # already pinned one
                self.run_ledger.scenario_fingerprint = \
                    self.scenario_fingerprint
            self.run_ledger.open()

    # ------------------------------------------------------------ algo hooks
    def _size_rollouts(self, algo_config, num_envs, rollout_length,
                       train_batch_size: int) -> None:
        """num_envs from config (reference: num_workers), rollout length
        sized so one epoch collects about one train batch."""
        self.num_envs = int(num_envs
                            or (algo_config or {}).get("num_workers") or 8)
        self.rollout_length = int(
            rollout_length or max(train_batch_size // self.num_envs, 1))

    def _configure_algo(self, algo_config, num_envs, rollout_length) -> None:
        """Translate the RLlib-style algo_config; PPO by default."""
        self.ppo_cfg = ppo_config_from_rllib(algo_config)
        self._size_rollouts(algo_config, num_envs, rollout_length,
                            self.ppo_cfg.train_batch_size)

    def _build_model(self, n_actions: int, model_config):
        return build_policy_from_model_config(n_actions, model_config)

    def _make_learner(self):
        from ddls_tpu.rl.ppo import PPOLearner

        return PPOLearner(self.apply_fn, self.ppo_cfg, self.mesh,
                          param_sharding=self.param_sharding)

    def _build_learner(self) -> None:
        from ddls_tpu.rl.rollout import RolloutCollector

        if self.loop_mode == "sebulba":
            # split BEFORE the learner builds: self.mesh becomes the
            # LEARNER sub-mesh (may fall back to pipelined, loudly)
            self._split_sebulba_mesh()
        with startup.span("startup.learner"):
            self.learner = self._make_learner()
            self.state = self.learner.init_state(self.params)
        if self.loop_mode == "fused":
            # holds startup.device_tables and startup.job_banks
            with startup.span("startup.fused_build"):
                self._build_fused()
            return
        if self.loop_mode == "sebulba":
            self.collector = self._make_sebulba_collector()
            return
        if getattr(self, "device_collector", False):
            self.collector = self._make_device_collector()
            return
        if self.collect_transport == "socket":
            self.collector = self._make_fragment_collector()
            return
        self.collector = RolloutCollector(
            self.vec_env, self.learner, self.rollout_length,
            deferred_fetch=(self.loop_mode == "pipelined"),
            # ring capacity: depth prefetched batches + the one being
            # consumed + one of slack, so a healthy steady state never
            # stalls a lease (rl/ring.py counts the stalls if it does)
            ring_segments=(self.pipeline_depth + 2
                           if self.loop_mode == "pipelined" else None))
        self.collector._needs_reset = False  # env already reset in __init__

    def _make_fragment_collector(self):
        """Socket fragment consumer (rl/fragments.py): actor-host
        subprocesses run the deferred-fetch collector against THEIR
        envs and ship trajectory ring segments as framed messages; the
        returned LearnerFragment duck-types the collector contract —
        its segments live in the learner's OWN TrajRing, so run()'s
        canonical two-phase release (note_staged/note_update) applies
        unchanged."""
        from ddls_tpu.rl.fragments import LearnerFragment

        cfg = dict(self.socket_config)
        return LearnerFragment(
            env_cls_path=self._env_cls_path,
            env_config=self.env_config,
            model_config=self._model_config,
            n_actions=self.n_actions,
            num_envs=self.num_envs,
            rollout_length=self.rollout_length,
            collect_seed=self._collect_seed,
            global_seed=self.seed,
            # same sizing as the in-process pipelined ring: depth
            # prefetched batches + the one consumed + one of slack
            ring_segments=self.pipeline_depth + 2,
            num_actor_hosts=int(cfg.pop("num_actor_hosts", 1)),
            use_parallel_envs=self._actor_use_parallel_envs,
            vec_env_backend=self.vec_env_backend,
            **cfg)

    def _fused_step_fn(self):
        """The learner's UNJITTED update for in-scan tracing inside the
        fused epoch program, normalised to the PPO signature
        ``(state, traj, last_values, rng) -> (state, metrics)``.
        Learners whose update takes no rng override this to drop it
        (the rng stream is still split per round so the update-key
        bookkeeping matches the sequential loop exactly)."""
        return self.learner._train_step

    def _build_fused(self) -> None:
        """Build the fused epoch driver at ``num_envs`` lanes x
        ``rollout_length`` steps, or at the ``fused_config`` pin that
        re-factorises the same per-update batch (validated here, before
        any bank is sampled or driver built)."""
        from ddls_tpu.rl.fused import FusedEpochDriver

        cfg = self.fused_config
        unknown = sorted(set(cfg) - {"lanes", "segment_len"})
        if unknown:
            raise ValueError(
                f"fused_config takes 'lanes' and 'segment_len' only, "
                f"got {unknown}")
        if len(cfg) == 1:
            raise ValueError(
                "fused_config pins 'lanes' and 'segment_len' together "
                f"(or neither), got only {sorted(cfg)}")
        lanes = int(cfg.get("lanes", self.num_envs))
        segment_len = int(cfg.get("segment_len", self.rollout_length))
        total = self.num_envs * self.rollout_length
        if lanes < 1 or lanes * segment_len != total:
            raise ValueError(
                f"fused_config lanes ({lanes}) x segment_len "
                f"({segment_len}) must equal the per-update batch "
                f"num_envs x rollout_length ({self.num_envs} x "
                f"{self.rollout_length} = {total})")
        dp = int(self.mesh.shape["dp"])
        if lanes % dp:
            raise ValueError(
                f"loop_mode='fused': {lanes} lanes do not divide over "
                f"the mesh's dp axis ({dp})")

        env0, et, ot = self._device_tables()
        self._set_aggregate_gauges(lanes * segment_len, ot)
        sh_fn = getattr(self.learner, "_state_shardings", None)
        state_shardings = (sh_fn(self.state) if sh_fn is not None
                           else getattr(self.learner, "_replicated",
                                        None))
        self.fused = FusedEpochDriver(
            et, ot, self.model, self._stacked_banks(et, env0, lanes),
            segment_len, self.updates_per_epoch,
            train_step_fn=self._fused_step_fn(),
            state_shardings=state_shardings, mesh=self.mesh,
            memo_cfg=self._memo_knob())

    def _set_aggregate_gauges(self, batch: int, ot) -> None:
        """The start-up gauges of the GNN's aggregation in the update
        (`models/policy.py:aggregate_gauges`), at the update's
        minibatch of the observation the device tables ``ot`` carry:
        the template env's, its padded arrays at the tables' pads."""
        import jax

        from ddls_tpu.models.policy import (AGGREGATE_GAUGES,
                                            aggregate_gauges)
        from ddls_tpu.sim.jax_env import OBS_PAD_KEYS

        cfg = getattr(self.learner, "cfg", None)
        minibatch = min(int(getattr(cfg, "sgd_minibatch_size", batch)),
                        batch)
        obs = {key: jax.ShapeDtypeStruct(
            (minibatch,) + (ot[key].shape[1:] if key in OBS_PAD_KEYS
                            else x.shape), x.dtype)
            for key, x in self.vec_env.obs[0].items()}
        for name, value in zip(AGGREGATE_GAUGES, aggregate_gauges(
                self.apply_fn, self.params, obs)):
            startup.set_gauge(name, value)

    def _split_sebulba_mesh(self) -> None:
        """Partition the configured training mesh into the actor
        sub-mesh and the learner complement (rl/sebulba.py) BEFORE the
        learner builds: ``self.mesh`` becomes the LEARNER sub-mesh, so
        the update, checkpoints and eval keep their one mesh handle.
        An infeasible AUTO split (one device, or lanes that do not
        divide a sub-mesh) falls back LOUDLY to ``loop_mode=
        'pipelined'`` with device collection (the fused-fallback
        convention); an EXPLICIT ``sebulba_config`` that cannot split
        is a config error and raises."""
        import warnings

        from ddls_tpu.rl.sebulba import split_meshes

        devs = list(self.mesh.devices.flat)
        explicit = self.sebulba_config.get("actor_devices")
        try:
            actor_mesh, learner_mesh = split_meshes(explicit,
                                                    devices=devs)
        except ValueError as err:
            if explicit is not None:
                raise
            warnings.warn(
                f"sebulba: {err} — falling back to "
                "loop_mode='pipelined' with device collection")
            self.loop_mode = "pipelined"
            return
        bad = [f"num_envs={self.num_envs} does not divide the {name} "
               f"sub-mesh dp axis ({int(m.shape['dp'])})"
               for name, m in (("actor", actor_mesh),
                               ("learner", learner_mesh))
               if self.num_envs % int(m.shape["dp"])]
        if bad:
            if explicit is not None:
                raise ValueError("sebulba: " + "; ".join(bad))
            warnings.warn(
                "sebulba: " + "; ".join(bad) + " — falling back to "
                "loop_mode='pipelined' with device collection")
            self.loop_mode = "pipelined"
            return
        self.actor_mesh = actor_mesh
        self.mesh = learner_mesh

    def _make_sebulba_collector(self):
        """The actor half of the Sebulba split (rl/sebulba.py): the
        fused-style in-kernel collection jitted over the actor
        sub-mesh, handing device trajectories to the learner sub-mesh
        through a device-mode trajectory ring."""
        from ddls_tpu.rl.sebulba import SebulbaCollector

        env0, et, ot = self._device_tables()
        stacked = self._stacked_banks(et, env0, self.num_envs)
        return SebulbaCollector(
            et, ot, self.model, stacked, self.rollout_length,
            actor_mesh=self.actor_mesh,
            # ring capacity: the depth-K sizing of the shm ring
            # (depth in-flight batches + the consumed one + slack)
            ring_segments=int(self.sebulba_config.get("ring_segments")
                              or self.pipeline_depth + 2),
            memo_cfg=self._memo_knob(),
            param_layout=self.param_sharding)

    def _memo_knob(self):
        """The ``use_jax_lookahead_memo`` algo key as the value the
        collectors' ``resolve_memo_cfg`` consumes: "auto" passes
        through (per-build lane-count resolution), True/False force a
        MemoConfig/None."""
        from ddls_tpu.sim.jax_memo import MemoConfig

        knob = self.use_jax_lookahead_memo
        if knob == "auto":
            return "auto"
        return MemoConfig() if knob else None

    def _device_tables(self):
        """Static jitted-env tables from the template env (shared by the
        device collector and the fused epoch driver)."""
        from ddls_tpu.sim.jax_env import (ALLOCATE_GAUGE, MASK_GAUGES,
                                          OBS_PAD_GAUGES, PRICE_GAUGE,
                                          allocate_indexed_ops,
                                          build_episode_tables,
                                          build_obs_tables, fit_obs_tables,
                                          mask_rows_on_empty_cluster,
                                          obs_pads, price_dep_indexed_ops,
                                          ragged_forward_ops)

        env0 = self.vec_env.envs[0]
        with startup.span("startup.device_tables"):
            et = build_episode_tables(env0)
            configured = build_obs_tables(env0, et)
            ot = fit_obs_tables(configured)
        for name, pad in zip(OBS_PAD_GAUGES,
                             obs_pads(ot) + obs_pads(configured)):
            startup.set_gauge(name, pad)
        startup.set_gauge(PRICE_GAUGE, price_dep_indexed_ops(et))
        startup.set_gauge(ALLOCATE_GAUGE,
                          allocate_indexed_ops(et.tables, et.st, et.pads))
        for name, rows in zip(MASK_GAUGES,
                              mask_rows_on_empty_cluster(env0, et, ot)):
            startup.set_gauge(name, rows)
        # beside the gauges an architecture job source set for the model
        for model, ragged in ragged_forward_ops(et).items():
            if startup.registry().gauge(
                    f"graphs.arch.forward_ops.{model}").value is not None:
                startup.set_gauge(f"graphs.arch.ragged_ops.{model}", ragged)
        return env0, et, ot

    def _device_bank_size(self, env0) -> int:
        """Jobs per lane bank via the ONE sizing home
        (rl/fused.py:horizon_bank_jobs): explicit config, else the sim
        horizon with CLT margin."""
        from ddls_tpu.rl.fused import horizon_bank_jobs

        return horizon_bank_jobs(env0, self.seed + 31,
                                 explicit=self.device_bank_jobs)

    def _stacked_banks(self, et, env0, n_lanes: int):
        """Per-lane job banks via the ONE seed-formula home
        (rl/fused.py:stacked_job_banks — lane i keeps the seed the
        device collector always gave env i, so fused lanes == num_envs
        reproduce the collector's banks bit-for-bit)."""
        from ddls_tpu.rl.fused import stacked_job_banks

        with startup.span("startup.job_banks"):
            return stacked_job_banks(et, env0, n_lanes,
                                     self._device_bank_size(env0),
                                     seed_base=self._collect_seed)

    def _collection_mesh(self, n_lanes: int):
        """The mesh lanes shard over, or None for single-device
        collection: shard lanes over LOCAL devices when they divide
        evenly (the pod collection shape: each chip runs its own lanes;
        without this a multi-chip slice collects on one chip and
        updates on all). Multi-process: a per-process LOCAL mesh keeps
        each process's banks/rngs its own (the global mesh would demand
        cross-process arrays) while still using every local chip."""
        import jax

        local = jax.local_devices()
        if len(local) <= 1:
            return None
        # the candidate mesh is what the collector would actually
        # shard over: the configured training mesh in single-process
        # mode (possibly FEWER devices than the host exposes), a
        # per-process local mesh otherwise
        if jax.process_count() == 1:
            candidate = self.mesh
        else:
            from ddls_tpu.parallel.mesh import make_mesh
            candidate = make_mesh(len(local), devices=local)
        # gate on the value DevicePPOCollector validates (ppo_device
        # .py: num_envs % mesh.shape['dp']), not the local device
        # count — e.g. n_devices=3 on an 8-device host with
        # num_envs=8 divides the host but not the mesh, and must
        # fall back to single-device collection instead of raising
        # (ADVICE r5 item 1)
        dp = int(candidate.shape["dp"])
        if n_lanes % dp == 0:
            return candidate
        import warnings
        warnings.warn(
            f"device_collector: num_envs={n_lanes} not "
            f"divisible by the mesh dp axis ({dp}); lanes "
            "will collect on ONE device (set num_envs to a "
            "multiple for sharded collection)")
        return None

    def _make_device_collector(self):
        """The jitted-env collection path (algo_config
        ``device_collector: true``): per-lane job banks sampled from the
        env's own workload distributions, episodes stepped entirely
        in-kernel. Serves every loop that consumes the shared traj dict
        (ppo, impala, pg). Requires the canonical-RAMP jitted env
        (sim/jax_env.py) and a priceless observation."""
        from ddls_tpu.rl.ppo_device import DevicePPOCollector

        env0, et, ot = self._device_tables()
        stacked = self._stacked_banks(et, env0, self.num_envs)
        mesh = self._collection_mesh(self.num_envs)
        params_shardings = None
        if self.param_sharding != "replicated":
            if mesh is None:
                raise ValueError(
                    f"param_sharding={self.param_sharding!r} needs the "
                    "device collector's lanes sharded over the training "
                    f"mesh, but num_envs={self.num_envs} does not "
                    "divide its dp axis — size num_envs to a multiple "
                    "of the dp width (single-device collection would "
                    "implicitly gather the sharded params every "
                    "collect)")
            from ddls_tpu.parallel.partition import params_shardings_of
            params_shardings = params_shardings_of(
                self.learner._state_shardings(self.state))
        return DevicePPOCollector(et, ot, self.model, stacked,
                                  self.rollout_length,
                                  mesh=mesh,
                                  memo_cfg=self._memo_knob(),
                                  params_shardings=params_shardings)

    # ----------------------------------------------------------------- epoch
    def _split_rng(self):
        """Update rng: the same sequence on every process (fed into the
        jitted sharded train step)."""
        import jax

        self._rng, sub = jax.random.split(self._rng)
        if (self.loop_mode in ("pipelined", "sebulba")
                and jax.process_count() == 1):
            # explicit placement beside the replicated params: the jitted
            # update would otherwise reshard the key implicitly onto the
            # mesh every epoch (the transfer-guard pin catches exactly
            # this class of hidden per-update transfer). Single-process
            # only: under multi-host the key must ride into the jit as a
            # host-local value on every process (a device_put onto the
            # global mesh would fabricate a global array per process)
            replicated = getattr(getattr(self, "learner", None),
                                 "_replicated", None)
            if replicated is not None:
                sub = jax.device_put(sub, replicated)
        return sub

    def _split_collect_rng(self):
        """Collection rng: process-distinct, so hosts sample different
        actions and contribute genuinely different batch shards."""
        import jax

        self._collect_rng, sub = jax.random.split(self._collect_rng)
        return sub

    # ------------------------------------------------- pipelining plumbing
    def _collect_and_stage(self, params, rng):
        """Collect one batch and stage it on the mesh (double-buffered
        under ``pipeline_depth >= 1``: staging the next batches runs on
        the collection thread while the update consumes the previous
        one, whose donated buffers free as it runs).

        Ring handoff (rl/ring.py): when the collector leased a
        trajectory-ring segment, the alias verdict is probed here on
        the segment's FIRST staging (does ``shard_traj``'s device_put
        share the segment's host memory? — the np.shares_memory
        question, answered pointer-wise so it runs under the transfer
        guard). Alias-free segments get the staged tree itself as
        their release token (free the moment the copies land); aliased
        segments wait for an update-output token attached in ``run``."""
        with telemetry.span("train.collect"):
            out = self.collector.collect(params, rng)
        # the staging hop is also a transfer-ledger record (ISSUE 18):
        # host→device for host collection, actor→learner mesh for
        # sebulba — bytes from .nbytes metadata only
        direction = "a2l" if self.loop_mode == "sebulba" else "h2d"
        with telemetry.span("train.device_transfer"):
            with telemetry.transfer("stage.traj", direction) as tr:
                straj, slv = self.learner.shard_traj(out["traj"],
                                                     out["last_values"])
                tr.add(straj)
                tr.add(slv)
        segment = out.get("ring_segment")
        if segment is not None:
            # phase 1 of the ring token protocol (rl/ring.py
            # note_staged): alias verdict + copy-case token
            out["ring"].note_staged(segment, straj["obs"],
                                    generation=out.get("ring_generation"))
        return out, straj, slv

    def _next_batch(self):
        """The epoch's staged batch; under ``pipeline_depth >= 1`` also
        tops the background-collection queue back up to ``depth``
        batches, each submitted against the CURRENT (pre-update) params
        — once the caller dispatches updates, a queued batch is as many
        updates stale as landed before its consumption (its
        ``params_age``), which V-trace corrects. The rng stream is
        split on the main thread in submission order, so collection n
        consumes the same key in every mode (bit-exactness across
        depths of what each batch is collected WITH is not promised —
        staleness is the point — but the rng bookkeeping stays
        deterministic and process-local, preserving the multi-host
        rules). The queue-top-up gate is a pure function of the queue
        length and the configured depth — deterministic, multi-host
        safe."""
        import jax
        import jax.numpy as jnp

        if self._collect_futures:
            future, version = self._collect_futures.pop(0)
            out, straj, slv = future.result()
            out["params_age"] = self._updates_dispatched - version
        else:
            out, straj, slv = self._collect_and_stage(
                self.state.params, self._split_collect_rng())
            out["params_age"] = 0
        if self.pipeline_depth:
            if self._collect_executor is None:
                from concurrent.futures import ThreadPoolExecutor

                self._collect_executor = ThreadPoolExecutor(
                    max_workers=1, thread_name_prefix="collect-pipeline")
            while len(self._collect_futures) < self.pipeline_depth:
                # jnp.copy: the live state is about to be DONATED into
                # the update, which deletes its param buffers out from
                # under a concurrent reader; the stale collector needs
                # its own copy
                params = jax.tree_util.tree_map(jnp.copy,
                                                self.state.params)
                rng = self._split_collect_rng()
                self._collect_futures.append((
                    self._collect_executor.submit(
                        self._collect_and_stage, params, rng),
                    self._updates_dispatched))
        return out, straj, slv

    def _watch_update(self, metrics, t0: float) -> None:
        """Record the in-flight update's device wall as a
        ``train.update_device`` span from a monitor thread, so the span
        overlap view (telemetry.overlap_summary) can MEASURE how much of
        it ran concurrently with collection instead of asserting it.
        Only active while telemetry is enabled — the monitor blocks on
        the device off the critical path."""
        if not telemetry.enabled():
            return
        if self._watch_executor is None:
            from concurrent.futures import ThreadPoolExecutor

            self._watch_executor = ThreadPoolExecutor(
                max_workers=1, thread_name_prefix="update-watch")

        def _block():
            import jax

            try:
                jax.block_until_ready(metrics)
                telemetry.record_span("train.update_device", t0)
            except Exception:
                pass  # observability must never break training

        self._watch_executor.submit(_block)

    def _harvest_metrics(self, metrics, extras: Optional[dict] = None
                         ) -> Any:
        """Sequential mode: the pre-pipelining per-update blocking fetch
        (one ``train.host_sync`` span per epoch). Pipelined mode: wrap
        the device dict as a LazyMetrics future on the unsynced ring;
        ``_maybe_sync_metrics`` drains the ring at sync boundaries.
        ``extras`` are host-side scalars (e.g. the depth-K loop's
        ``params_age_updates``) that ride the mapping without touching
        the device."""
        import jax

        if self.loop_mode == "sequential":
            with telemetry.span("train.host_sync"):
                fetched = {k: float(v)
                           for k, v in jax.device_get(metrics).items()}
            fetched.update(extras or {})
            return fetched
        from ddls_tpu.train.metrics import LazyMetrics

        lazy = LazyMetrics(metrics, extras=extras)
        self._metrics_ring.append(lazy)
        return lazy

    def _device_wait(self, tree) -> None:
        """Telemetry-only (the caller gates on ``telemetry.enabled()``):
        wait for the device BEFORE a drain boundary's first fetch, under
        ``train.device_wait``, so that the wait (the device is busy, not
        idle) is one span and ``train.host_sync`` holds the
        device->host copies alone. Once a boundary: the boundary's
        second fetch (the episode trace, an output of the program the
        first wait covered) opens no second span. With telemetry off
        the first fetch blocks as it always did."""
        if self._device_waited_epoch == self.epoch_counter:
            return
        import jax

        self._device_waited_epoch = self.epoch_counter
        with telemetry.span("train.device_wait"):
            jax.block_until_ready(tree)

    def _maybe_sync_metrics(self, force: bool = False) -> None:
        """Drain the unsynced-metrics ring in ONE batched device fetch
        when a sync boundary is reached (every ``metrics_sync_interval``
        epochs, an eval epoch, or ``force``). The gate is deterministic
        (epoch counter only) — multi-host safe."""
        if not self._metrics_ring:
            return
        if not (force
                or self.epoch_counter % self.metrics_sync_interval == 0):
            return
        from ddls_tpu.train.metrics import LazyMetrics

        ring, self._metrics_ring = self._metrics_ring, []
        # fused / sebulba alone split the wait from the copy, and record
        # the memo's counters with the episode drain, where they ride
        # the trace's fetch; the other loops keep their spans as they are
        drains_episodes = self.loop_mode in ("fused", "sebulba")
        if drains_episodes and telemetry.enabled():
            self._device_wait(([lm.device_values() for lm in ring],
                               self._fused_episode_ring))
        with telemetry.span("train.host_sync"):
            with telemetry.transfer("drain.metrics", "d2h") as tr:
                if telemetry.enabled():
                    for lm in ring:
                        tr.add(lm.device_values())
                LazyMetrics.materialize_group(ring)
        if not drains_episodes and telemetry.enabled():
            self._record_memo_drain()

    def _memo_source(self):
        """The driver that carries the in-kernel lookahead memo (None
        where the loop collects on the host)."""
        source = self.fused if self.fused is not None else getattr(
            self, "collector", None)
        return source if hasattr(source, "memo_counter_arrays") else None

    def _record_memo_drain(self, fetched: Optional[dict] = None) -> None:
        """Telemetry-only memo-counter event at a sync boundary (the
        timeline's memo hit-rate counter track); the caller gates on
        ``telemetry.enabled()``. ``fetched`` are the three counter
        arrays where they rode the boundary's batched fetch; otherwise
        ONE ledgered fetch of their own (``drain.memo``; local arrays —
        no collective, so a per-process telemetry toggle stays
        multi-host safe), which may find the state donated by a
        background collection: observability never breaks training."""
        import jax

        from ddls_tpu.sim.jax_memo import summarize_fetched

        if fetched is None:
            source = self._memo_source()
            arrays = (source.memo_counter_arrays()
                      if source is not None else None)
            if arrays is None:
                return
            try:
                with telemetry.transfer("drain.memo", "d2h") as tr:
                    tr.add(arrays)
                    fetched = jax.device_get(arrays)
            except Exception:
                return
        telemetry.record_event("memo_counters",
                               **summarize_fetched(fetched))

    def sync_metrics(self) -> None:
        """Force-drain any unsynced metrics (checkpoint/shutdown/test
        boundary)."""
        self._maybe_sync_metrics(force=True)

    def ring_stats(self) -> Optional[Dict[str, Any]]:
        """The trajectory ring's ledger counters (rl/ring.py stats:
        segments/leases/stalls/occupancy/mean params-age), or None when
        no ring is installed. Host ints only — safe to fetch at a
        reporting boundary (the run ledger's ``ring`` block)."""
        ring = getattr(self.vec_env, "traj_ring", None)
        if ring is None:
            # the sebulba device-mode ring lives on the collector, not
            # the vec env (rl/sebulba.py)
            ring = getattr(getattr(self, "collector", None), "ring",
                           None)
        return ring.stats() if ring is not None else None

    # ------------------------------------------------------- fused epoch
    @property
    def _episode_harvester(self):
        """The driver that owns the pending episode traces:
        ``self.fused`` ([U, B, T] traces) or the sebulba collector
        ([B, T] traces) — both keep host-side episode lengths, so
        drains must stay in collection order."""
        return self.fused if self.fused is not None else self.collector

    def _maybe_drain_fused_episodes(self, force: bool = False
                                    ) -> List[dict]:
        """Fetch the fused/sebulba epochs' compact episode-counter
        traces in ONE batched device_get, at the SAME sync boundaries as
        the metrics ring (every ``metrics_sync_interval`` epochs, an
        eval epoch, or ``force``) — never per update. The gate is
        deterministic (epoch counter + config only — multi-host rules).
        Returns the fetched traces (host arrays, collection order) for
        ``_finalize_drained`` to harvest; while telemetry is on they are
        first reduced into its counters, under
        ``train.telemetry_reduce``."""
        if not self._fused_episode_ring:
            return []
        is_eval = bool(self.evaluation_interval
                       and self.epoch_counter
                       % self.evaluation_interval == 0)
        if not (force or is_eval
                or self.epoch_counter % self.metrics_sync_interval == 0):
            return []
        import jax

        from ddls_tpu.rl.fused import (record_decisions,
                                       record_lookahead_trips,
                                       record_padding_fill)

        harvester = self._episode_harvester
        ring, self._fused_episode_ring = self._fused_episode_ring, []
        # telemetry-only: the memo's counter arrays ride the trace's
        # fetch — no round trip of their own — where no background
        # collection can donate the state they live in meanwhile
        memo = None
        if telemetry.enabled():
            if not self.pipeline_depth:
                memo = harvester.memo_counter_arrays()
            self._device_wait((ring, memo))
        with telemetry.span("train.host_sync"):
            with telemetry.transfer("drain.episodes", "d2h") as tr:
                tr.add((ring, memo))
                fetched, memo = jax.device_get((ring, memo))
        if telemetry.enabled():
            # what runs only because telemetry is on: the instrument's
            # own cost, under its own name
            with telemetry.span("train.telemetry_reduce"):
                self._record_memo_drain(memo)
                for ep in fetched:
                    record_lookahead_trips(ep, harvester.et.pads,
                                           harvester.et.n_srv)
                    record_padding_fill(ep, harvester.et, harvester.ot)
                    record_decisions(ep, harvester.et, harvester.ot)
        return fetched

    def _run_fused(self) -> Dict[str, Any]:
        """One fused epoch: ONE device dispatch runs
        ``updates_per_epoch`` collect→update rounds (`rl/fused.py`).
        Metrics ride the epoch as a [U]-stacked LazyMetrics future and
        episode counters as a pending device trace; both drain per
        ``metrics_sync_interval`` under ``train.host_sync`` — the
        steady-state epoch performs NO device→host transfer. Episode
        summaries therefore appear on drain epochs (covering every
        epoch since the last drain), not per epoch.

        Five spans tile the call (docs/telemetry.md): ``train.
        fused_epoch`` (the dispatch), at a drain boundary
        ``train.device_wait`` (telemetry-only: the wait for the device,
        taken out of the first fetch), ``train.host_sync`` (the two
        device->host copies) and ``train.telemetry_reduce``
        (telemetry-only: the instrument's own reductions), and
        ``train.harvest`` (episode records, summary, bookkeeping)."""
        from ddls_tpu.train.metrics import LazyMetrics

        start = time.time()
        with telemetry.span("train.fused_epoch"):
            (self.state, (self._collect_rng, self._rng), metrics,
             ep) = self.fused.fused_epoch(
                self.state, (self._collect_rng, self._rng))
        self.epoch_counter += 1
        env_steps = self.fused.env_steps_per_epoch
        self.total_env_steps += env_steps
        lazy = LazyMetrics(
            metrics, reduce="mean",
            extras={"num_updates": self.fused.updates_per_epoch})
        self._metrics_ring.append(lazy)
        self._fused_episode_ring.append(ep)
        self._maybe_sync_metrics()
        fetched = self._maybe_drain_fused_episodes()
        results: Dict[str, Any] = {
            "epoch_counter": self.epoch_counter,
            "env_steps_this_iter": env_steps,
            "total_env_steps": self.total_env_steps,
            "learner": lazy,
        }
        return self._finalize_drained(results, fetched, start)

    def _finalize_drained(self, results: Dict[str, Any],
                          fetched: List[dict], start: float
                          ) -> Dict[str, Any]:
        """The fused / sebulba epilogue, under ``train.harvest``:
        episode records from the drained traces, their summary and the
        bookkeeping — host work that runs with telemetry off too."""
        with telemetry.span("train.harvest"):
            return self._finalize_results(
                results, self._harvest_drained(fetched), start)

    def _harvest_drained(self, fetched: List[dict]) -> List[dict]:
        """Episode records of the drained traces, in collection order
        (the harvester keeps host-side episode lengths; a loop that
        drains nothing — DQN, ES — has none)."""
        return [record for ep in fetched for record
                in self._episode_harvester.harvest_episodes(ep)]

    def run(self) -> Dict[str, Any]:
        """Collect one trajectory batch and apply one PPO update.

        Per-update phase spans (no-ops while telemetry is disabled): note
        jax dispatch is async, so ``train.train_step`` measures trace/
        dispatch and ``train.host_sync`` absorbs the device wait — in
        sequential mode once per update, in pipelined mode once per sync
        boundary, with ``train.update_device`` (monitor thread) carrying
        the true device wall of the update (the attribution
        Podracer/MSRL instrument for)."""
        if self.loop_mode == "fused":
            return self._run_fused()
        start = time.time()
        out, straj, slv = self._next_batch()
        update_t0 = telemetry.clock_now() if telemetry.enabled() else 0.0
        with telemetry.span("train.train_step"):
            self.state, metrics = self.learner.train_step(
                self.state, straj, slv, self._split_rng())
        del straj, slv  # donated on accelerator backends: moved-from
        self._updates_dispatched += 1
        segment = out.get("ring_segment")
        if segment is not None:
            # phase 2 of the ring token protocol: alias-case segments
            # may only be rewritten once the update that read their
            # bytes is done — an update output is exactly that marker
            out["ring"].note_update(segment, metrics["total_loss"],
                                    generation=out.get("ring_generation"))
        if self.loop_mode in ("pipelined", "sebulba"):
            self._watch_update(metrics, update_t0)

        self.epoch_counter += 1
        self.total_env_steps += out["env_steps"]
        extras = None
        if self.pipeline_depth:
            # per-batch staleness in updates (the lag V-trace absorbs);
            # host ints — never a device fetch
            age = int(out.get("params_age", 0))
            extras = {"params_age_updates": age}
            ring = out.get("ring")
            if ring is not None:
                ring.observe_params_age(age)
        transit = out.get("segment_transit_s")
        if transit is not None:
            # params_age_updates' sibling (rl/fragments.py): wire +
            # framing lag per segment, net of the actor's own collect
            # wall — says what the network costs, in seconds, next to
            # what staleness costs, in updates. Already a host float
            # (single-clock durations), never a device fetch.
            extras = dict(extras or {})
            extras["segment_transit_s"] = transit
        learner_metrics = self._harvest_metrics(metrics, extras=extras)
        self._maybe_sync_metrics()
        results: Dict[str, Any] = {
            "epoch_counter": self.epoch_counter,
            "env_steps_this_iter": out["env_steps"],
            "total_env_steps": self.total_env_steps,
            "learner": learner_metrics,
        }
        if self.loop_mode == "sebulba":
            # episode counters stay device-resident until the drain
            # boundary (fused discipline: the steady-state epoch stays
            # transfer-free)
            self._fused_episode_ring.append(out["ep_pending"])
            return self._finalize_drained(
                results, self._maybe_drain_fused_episodes(), start)
        return self._finalize_results(results, out["episodes"], start)

    def _finalize_results(self, results: Dict[str, Any],
                          episodes: List[dict], start: float) -> Dict[str, Any]:
        """Shared epoch epilogue: episode summary, periodic evaluation,
        timing bookkeeping."""
        results.update(_episode_summary(episodes))
        results["episodes"] = episodes

        if (self.evaluation_interval
                and self.epoch_counter % self.evaluation_interval == 0):
            # eval is a logging boundary: drain any unsynced metric
            # futures first (the deterministic eval gate itself already
            # syncs the host with the device). Any pipeline_depth >= 1
            # background collections must also settle first — their env
            # stepping draws from the process-global numpy/random state
            # that evaluate() snapshots and reseeds, and racing those
            # would corrupt both streams.
            self._maybe_sync_metrics(force=True)
            for future, _ in self._collect_futures:
                future.result()
            with telemetry.span("train.eval"):
                results["evaluation"] = self.evaluate(
                    self.evaluation_duration)
        self.run_time += time.time() - start
        results["epoch_time"] = time.time() - start
        results["run_time"] = self.run_time
        return results

    # ------------------------------------------------------------ evaluation
    def make_eval_env(self):
        """Build the evaluation env: training env_config with the
        evaluation_config env overrides applied (eval_default.yaml
        evaluation_config.env_config surface)."""
        import copy

        from ddls_tpu.utils.common import recursive_update

        env_config = copy.deepcopy(self.env_config)
        eval_env_overrides = (self.evaluation_config or {}).get(
            "env_config") or {}
        env_config = recursive_update(env_config, eval_env_overrides)
        return self.env_cls(**env_config)

    def evaluate(self, num_episodes: int,
                 seed: Optional[int] = None) -> Dict[str, Any]:
        """Greedy-policy evaluation episodes on a fresh env (the reference
        evaluates with explore=False on eval workers: eval_default.yaml).

        The process-global RNG state is snapshotted around evaluation:
        env.reset(seed) seeds numpy/random globally, and letting the fixed
        test seed leak into the training envs' workload sampling would both
        corrupt training stochasticity and contaminate the held-out test
        stream."""
        import random as _random

        np_state = np.random.get_state()
        py_state = _random.getstate()
        try:
            base_seed = (seed if seed is not None
                         else (self.test_seed
                               if self.test_seed is not None
                               else self.seed + 10_000))
            episodes = self._run_greedy_episodes_batched(num_episodes,
                                                         base_seed)
            return _episode_summary(episodes)
        finally:
            np.random.set_state(np_state)
            _random.setstate(py_state)

    def _run_greedy_episodes_batched(self, num_episodes: int,
                                     base_seed: int) -> List[dict]:
        """One episode per parallel eval env, all driven by a single
        jitted greedy call per step (the TPU-native replacement for the
        reference's parallel eval workers, eval_default.yaml). Finished
        envs keep contributing their last obs to the (static-shape) batch
        but are no longer stepped.

        Env stochasticity is drawn lazily from the process-global
        numpy/random state that ``env.reset(seed)`` seeds, so each env's
        global-RNG state is swapped in around its reset and every step —
        episode i consumes exactly the stream seeded by ``base_seed + i``,
        bit-identical to running the episodes sequentially (and therefore
        invariant to ``num_episodes``)."""
        import random as _random

        from ddls_tpu.rl.rollout import harvest_episode_record, stack_obs

        def rng_state():
            return (np.random.get_state(), _random.getstate())

        def set_rng_state(state) -> None:
            np.random.set_state(state[0])
            _random.setstate(state[1])

        # env construction is expensive (full cluster/topology build);
        # reuse across evaluate() calls — env.reset(seed) makes reuse
        # bit-identical to fresh envs (asserted in tests)
        cache = getattr(self, "_eval_envs", [])
        while len(cache) < num_episodes:
            cache.append(self.make_eval_env())
        self._eval_envs = cache
        envs = cache[:num_episodes]
        obs, rng_states = [], []
        for i, env in enumerate(envs):
            obs.append(env.reset(seed=base_seed + i))
            rng_states.append(rng_state())
        done = np.zeros(num_episodes, dtype=bool)
        totals = np.zeros(num_episodes)
        lengths = np.zeros(num_episodes, dtype=np.int64)
        records: List[Optional[dict]] = [None] * num_episodes
        while not done.all():
            actions = self._greedy_actions(stack_obs(obs))
            for i in np.flatnonzero(~done):
                set_rng_state(rng_states[i])
                obs[i], reward, d, _ = envs[i].step(int(actions[i]))
                rng_states[i] = rng_state()
                totals[i] += reward
                lengths[i] += 1
                if d:
                    done[i] = True
                    records[i] = harvest_episode_record(
                        envs[i], i, totals[i], lengths[i])
        return [r for r in records if r is not None]

    def _run_greedy_episode(self, env, seed: int) -> Dict[str, Any]:
        """Single-episode evaluation on a caller-provided env (RLEvalLoop
        surface); same greedy policy as the batched path."""
        from ddls_tpu.rl.rollout import harvest_episode_record, stack_obs

        obs = env.reset(seed=seed)
        done = False
        total, steps = 0.0, 0
        while not done:
            action = int(self._greedy_actions(stack_obs([obs]))[0])
            obs, reward, done, _ = env.step(action)
            total += reward
            steps += 1
        return harvest_episode_record(env, 0, total, steps)

    def _greedy_actions(self, batched_obs) -> np.ndarray:
        """Greedy actions for a [B, ...] obs batch via one jitted device
        call; PPO-family: argmax of the (mask-adjusted) policy logits."""
        import jax

        if not hasattr(self, "_jit_greedy"):
            self._jit_greedy = jax.jit(
                lambda p, o: self.learner.apply_fn(p, o)[0].argmax(axis=-1))
        return np.asarray(jax.device_get(
            self._jit_greedy(self.state.params, batched_obs)))


    # ----------------------------------------------------------- checkpoints
    def save_agent_checkpoint(self, path: str) -> str:
        from ddls_tpu.train.checkpointer import save_train_state

        save_train_state(self.state, path)
        return path

    def load_agent_checkpoint(self, path: str) -> None:
        from ddls_tpu.train.checkpointer import restore_train_state

        self.state = restore_train_state(path, target=self.state)

    @staticmethod
    def _lookup_metric(results: Dict[str, Any], metric: str):
        """Resolve a '/'-separated metric path, allowing keys that contain
        literal '/' (e.g. 'evaluation/custom_metrics/blocking_rate_mean'
        where 'custom_metrics/blocking_rate_mean' is one key): at each dict
        level the longest matching '/'-joined key wins."""
        from collections.abc import Mapping

        def walk(node, segments):
            if not segments:
                return node
            if not isinstance(node, Mapping):  # dicts AND LazyMetrics
                return None
            for cut in range(len(segments), 0, -1):
                key = "/".join(segments[:cut])
                if key in node:
                    found = walk(node[key], segments[cut:])
                    if found is not None:
                        return found
            return None

        return walk(results, metric.split("/"))

    def register_checkpoint(self, path: str,
                            results: Dict[str, Any]) -> None:
        """Track the best checkpoint by the configured metric (reference:
        rllib_epoch_loop.py:184-227)."""
        value = self._lookup_metric(results, self.metric)
        record = {"epoch": self.epoch_counter, "path": path,
                  "metric": self.metric, "value": value}
        self.checkpoint_history.append(record)
        if value is None:
            return
        better = (self.best_metric_value is None
                  or (value > self.best_metric_value
                      if self.metric_goal == "maximise"
                      else value < self.best_metric_value))
        if better:
            self.best_metric_value = value
            self.best_checkpoint_path = path

    # ---------------------------------------------------------------- misc
    def log(self, results: Dict[str, Any]) -> None:
        """Flatten scalars to W&B if configured (reference:
        rllib_epoch_loop.py:144)."""
        if self.wandb is None:
            return
        from collections.abc import Mapping

        flat = {}

        def walk(node, prefix=""):
            if isinstance(node, Mapping):  # dicts AND LazyMetrics (the
                # W&B flatten IS a logging boundary: iterating a pending
                # LazyMetrics materialises it — one batched fetch)
                for k, v in node.items():
                    walk(v, f"{prefix}{k}/")
            elif isinstance(node, (int, float, np.floating, np.integer)):
                flat[prefix[:-1]] = float(node)

        walk(results)
        # telemetry phase spans ride the same flatten (one vocabulary for
        # per-update timing whether read from W&B or a snapshot)
        if telemetry.enabled():
            for name, summ in telemetry.span_summaries().items():
                for key, value in summ.items():
                    flat[f"telemetry/span/{name}/{key}"] = float(value)
        self.wandb.log(flat)

    def close(self) -> None:
        for future, _ in self._collect_futures:
            try:  # leave the env workers in a consistent state
                future.result(timeout=60)
            except Exception:
                pass
        self._collect_futures = []
        for executor in (self._collect_executor, self._watch_executor):
            if executor is not None:
                executor.shutdown(wait=True)
        self._collect_executor = self._watch_executor = None
        self.sync_metrics()
        # the final undrained interval's fused episode records are
        # harvested (completed episodes must not vanish with the loop);
        # no run() remains to return them, so they land on
        # ``undrained_episodes`` for callers that aggregate records
        self.undrained_episodes = self._harvest_drained(
            self._maybe_drain_fused_episodes(force=True))
        if self.run_ledger is not None:
            # run-boundary counter blocks for snapshot.json (host ints /
            # already-fetched values only — one memo fetch, no per-epoch
            # cost)
            source = self._memo_source()
            memo = None
            if source is not None:
                try:
                    memo = source.memo_counters()
                except Exception:
                    memo = None
            if memo and telemetry.enabled():
                telemetry.record_event("memo_counters", **memo)
            self.run_ledger.finalize(blocks={
                "ring": self.ring_stats(),
                "memo": memo,
                "train": {"epochs": self.epoch_counter,
                          "total_env_steps": self.total_env_steps,
                          "run_time_s": self.run_time},
            })
        collector = getattr(self, "collector", None)
        if collector is not None and hasattr(collector, "close"):
            collector.close()  # the sebulba device ring's ledger
        self.vec_env.close()


class ApexDQNEpochLoop(RLEpochLoop):
    """Ape-X DQN epoch loop: vectorised epsilon-greedy collection into a
    prioritised replay buffer + jitted double/dueling DQN updates on the
    mesh (reference trains the same env through RLlib's ApexTrainer,
    algo/apex_dqn.yaml; see ddls_tpu.rl.dqn for the TPU-native redesign)."""

    # replay insertion + epsilon schedules step the HOST envs; a fused
    # in-kernel epoch cannot express them (rejected loudly in __init__)
    SUPPORTS_FUSED = False
    SUPPORTS_PARAM_SHARDING = False  # host replay insertion path
    SUPPORTS_SOCKET_COLLECTION = False  # replay needs per-step control

    def _configure_algo(self, algo_config, num_envs, rollout_length) -> None:
        self.dqn_cfg = dqn_config_from_rllib(algo_config)
        self._size_rollouts(algo_config, num_envs, rollout_length,
                            self.dqn_cfg.train_batch_size)

    def _build_model(self, n_actions: int, model_config):
        import copy

        # Q-net logits must stay finite for the dueling mean; invalid
        # actions are masked at selection instead (dqn.py module docstring)
        model_config = copy.deepcopy(model_config or {})
        model_config.setdefault("custom_model_config", {})[
            "apply_action_mask"] = False
        return build_policy_from_model_config(n_actions, model_config)

    def _build_learner(self) -> None:
        from ddls_tpu.rl.dqn import ApexDQNLearner, PrioritizedReplayBuffer

        if self.device_collector:
            raise ValueError(
                "device_collector is not supported for apex_dqn: replay "
                "insertion + epsilon schedules step the host envs (use "
                "ppo/impala/pg, or rl/es_device.py for on-device ES)")
        cfg = self.dqn_cfg
        self.learner = ApexDQNLearner(self.apply_fn, cfg, self.mesh)
        self.state = self.learner.init_state(self.params)
        self.replay = PrioritizedReplayBuffer(
            cfg.buffer_capacity, cfg.prioritized_replay_alpha,
            cfg.prioritized_replay_beta, cfg.prioritized_replay_eps,
            seed=self.seed)
        self._nstep_queues: List[List[dict]] = [
            [] for _ in range(self.num_envs)]
        if (self.loop_mode == "pipelined"
                and getattr(self.vec_env, "prefetch_stacked", None)
                is False):
            self.vec_env.prefetch_stacked = True

    def run(self) -> Dict[str, Any]:
        """Collect rollout_length epsilon-greedy steps per env into replay,
        then apply ``training_intensity``-matched DQN updates.

        Replay insertion and epsilon schedules keep collection on the
        host, so only the metric-sync schedule changes between loop
        modes: sequential fetches each update's metrics under its own
        ``train.host_sync``; pipelined keeps the per-update dicts on
        device and logs their mean as one LazyMetrics future (the
        per-update ``td`` fetch stays — priorities feed the next
        sample). ``pipeline_depth > 0`` is rejected in __init__."""
        import jax

        from ddls_tpu.rl.dqn import nstep_transitions, per_worker_epsilons
        from ddls_tpu.rl.rollout import OBS_KEYS

        def slim(obs):
            # keep only network-consumed keys (drops e.g. the constant
            # action_set) so replay storage matches the acting pytree
            return {k: obs[k] for k in OBS_KEYS}

        cfg = self.dqn_cfg
        start = time.time()
        T, B = self.rollout_length, self.num_envs

        with telemetry.span("train.collect"):
            for _ in range(T):
                # stacked_obs: with the prefetching vec env this batch
                # was assembled while the previous step's workers ran
                batched = self.vec_env.stacked_obs()
                eps = per_worker_epsilons(B, self.total_env_steps, cfg)
                actions = np.asarray(self.learner.sample_actions(
                    self.state.params, batched, self._split_collect_rng(),
                    eps))
                prev_obs = list(self.vec_env.obs)
                _, rewards, dones = self.vec_env.step(actions)
                for i in range(B):
                    queue = self._nstep_queues[i]
                    queue.append({
                        "obs": slim(prev_obs[i]), "action": int(actions[i]),
                        "reward": float(rewards[i]), "done": bool(dones[i]),
                        # at episode end this is the auto-reset obs, but
                        # then discount == 0 so the target never reads it
                        "next_obs": slim(self.vec_env.obs[i])})
                    for tr in nstep_transitions(queue, cfg.n_step,
                                                cfg.gamma,
                                                flush=bool(dones[i])):
                        self.replay.add(tr)
                self.total_env_steps += B

        env_steps = T * B
        metrics_acc: List[Dict[str, float]] = []
        # learning_starts counts cumulative sampled transitions (as RLlib
        # does), NOT current buffer occupancy — a capacity smaller than
        # learning_starts must still start training once enough steps were
        # sampled. The buffer-warm gate is a *deterministic lower bound* on
        # replay size (sampled steps minus the worst-case n-step queue
        # residue) rather than the actual per-host size: under multi-host
        # training the jitted update is a cross-process collective, so
        # every process must take this branch on the same epoch.
        replay_lower_bound = self.total_env_steps - B * (cfg.n_step - 1)
        if (self.total_env_steps >= cfg.learning_starts
                and replay_lower_bound >= cfg.train_batch_size
                and self.replay.size >= cfg.train_batch_size):
            num_updates = max(1, int(round(
                env_steps * cfg.training_intensity / cfg.train_batch_size)))
            for _ in range(num_updates):
                batch, idx, weights = self.replay.sample(
                    cfg.train_batch_size)
                tbatch = {"obs": batch["obs"],
                          "actions": batch["action"],
                          "rewards": batch["reward"],
                          "next_obs": batch["next_obs"],
                          "discounts": batch["discount"],
                          "weights": weights}
                with telemetry.span("train.train_step"):
                    self.state, metrics, td = self.learner.train_step(
                        self.state, tbatch)
                # host-side replay work gets its own span: train.host_sync
                # must attribute DEVICE wait only (run() docstring), not
                # priority-update CPU time
                with telemetry.span("train.replay_update"):
                    self.replay.update_priorities(idx, td)
                if self.loop_mode == "sequential":
                    with telemetry.span("train.host_sync"):
                        metrics_acc.append({k: float(v) for k, v in
                                            jax.device_get(metrics).items()})
                else:
                    metrics_acc.append(metrics)  # device futures

        self.epoch_counter += 1
        extras = {"num_updates": len(metrics_acc),
                  "replay_size": self.replay.size}
        if self.loop_mode == "sequential":
            learner_metrics = (
                {k: float(np.mean([m[k] for m in metrics_acc]))
                 for k in metrics_acc[0]} if metrics_acc else {})
            learner_metrics.update(extras)
        else:
            from ddls_tpu.train.metrics import LazyMetrics

            learner_metrics = LazyMetrics(metrics_acc, reduce="mean",
                                          extras=extras)
            self._metrics_ring.append(learner_metrics)
            self._maybe_sync_metrics()
        results: Dict[str, Any] = {
            "epoch_counter": self.epoch_counter,
            "env_steps_this_iter": env_steps,
            "total_env_steps": self.total_env_steps,
            "learner": learner_metrics,
        }
        return self._finalize_results(
            results, self.vec_env.drain_completed_episodes(), start)

    def _greedy_actions(self, batched_obs) -> np.ndarray:
        # epsilon-0 through the learner's sampler so invalid actions stay
        # masked at selection (Q-logits themselves are unmasked, dqn.py)
        import jax

        B = int(np.asarray(batched_obs["action_mask"]).shape[0])
        actions = self.learner.sample_actions(
            self.state.params, batched_obs, jax.random.PRNGKey(0),
            np.zeros(B, np.float32))
        return np.asarray(actions)


# RLlib IMPALA keys (algo/impala.yaml) -> ImpalaConfig fields; Ray queue /
# aggregation plumbing keys are ignored
_RLLIB_TO_IMPALA = {
    "lr": "lr",
    "gamma": "gamma",
    "vtrace_clip_rho_threshold": "vtrace_clip_rho_threshold",
    "vtrace_clip_pg_rho_threshold": "vtrace_clip_pg_rho_threshold",
    "vtrace_drop_last_ts": "vtrace_drop_last_ts",
    "vf_loss_coeff": "vf_loss_coeff",
    "entropy_coeff": "entropy_coeff",
    "grad_clip": "grad_clip",
    "opt_type": "opt_type",
    "decay": "decay",
    "momentum": "momentum",
    "epsilon": "epsilon",
    "train_batch_size": "train_batch_size",
}


def impala_config_from_rllib(algo_config: Optional[dict]):
    from ddls_tpu.rl.impala import ImpalaConfig

    _reject_unknown_algo_keys("impala", (algo_config or {}),
                              _RLLIB_TO_IMPALA)
    kwargs = {}
    for src, dst in _RLLIB_TO_IMPALA.items():
        if algo_config and algo_config.get(src) is not None:
            kwargs[dst] = algo_config[src]
    return ImpalaConfig(**kwargs)


def pg_config_from_rllib(algo_config: Optional[dict]):
    from ddls_tpu.rl.pg import PGConfig

    known = (("lr", "lr"), ("gamma", "gamma"), ("grad_clip", "grad_clip"),
             ("train_batch_size", "train_batch_size"))
    _reject_unknown_algo_keys("pg", (algo_config or {}),
                              [src for src, _ in known])
    kwargs = {}
    for src, dst in known:
        if algo_config and algo_config.get(src) is not None:
            kwargs[dst] = algo_config[src]
    return PGConfig(**kwargs)


def es_config_from_rllib(algo_config: Optional[dict]):
    from ddls_tpu.rl.es import ESConfig

    known = ("stepsize", "noise_stdev", "l2_coeff", "episodes_per_batch",
             "report_length", "eval_prob", "action_noise_std",
             "train_batch_size")
    _reject_unknown_algo_keys("es", (algo_config or {}), known)
    kwargs = {}
    for key in known:
        if algo_config and algo_config.get(key) is not None:
            kwargs[key] = algo_config[key]
    return ESConfig(**kwargs)


class ImpalaEpochLoop(RLEpochLoop):
    """IMPALA epoch loop: the same vectorised collector as PPO (its one-
    epoch policy lag is exactly what V-trace corrects) with a single jitted
    V-trace update per batch (reference: algo/impala.yaml through
    rllib_epoch_loop.py:34).

    The one loop where ``pipeline_depth >= 1`` is sound: up to ``depth``
    collections run ahead on the background thread against pre-update
    params while the device applies updates — V-trace's importance
    weighting corrects exactly that policy lag (reported per batch as
    ``params_age_updates``), in the actor/learner-decoupled shape of
    the Podracer/MSRL/SEED-RL pipelines. On the shm backend the
    in-flight batches live in a ``depth + 2``-segment trajectory ring
    (rl/ring.py) whose ownership ledger stands in for the per-segment
    bulk copy; other backends fall back to fresh per-collect buffers,
    correct either way."""

    SUPPORTS_STALE_COLLECTION = True

    def _configure_algo(self, algo_config, num_envs, rollout_length) -> None:
        self.impala_cfg = impala_config_from_rllib(algo_config)
        self._size_rollouts(algo_config, num_envs, rollout_length,
                            self.impala_cfg.train_batch_size)

    def _make_learner(self):
        from ddls_tpu.rl.impala import ImpalaLearner

        return ImpalaLearner(self.apply_fn, self.impala_cfg, self.mesh,
                             param_sharding=self.param_sharding)

    def _fused_step_fn(self):
        # V-trace update takes no rng; the per-round key split still
        # happens in-kernel so the stream bookkeeping matches the
        # sequential loop (which also splits then ignores the key)
        step = self.learner._train_step
        return lambda state, traj, last_values, rng: step(
            state, traj, last_values)


class PGEpochLoop(RLEpochLoop):
    """Vanilla policy-gradient epoch loop (reference: algo/pg.yaml)."""

    def _configure_algo(self, algo_config, num_envs, rollout_length) -> None:
        self.pg_cfg = pg_config_from_rllib(algo_config)
        self._size_rollouts(algo_config, num_envs, rollout_length,
                            self.pg_cfg.train_batch_size)

    def _make_learner(self):
        from ddls_tpu.rl.pg import PGLearner

        return PGLearner(self.apply_fn, self.pg_cfg, self.mesh,
                         param_sharding=self.param_sharding)

    def _fused_step_fn(self):
        step = self.learner._train_step  # REINFORCE update takes no rng
        return lambda state, traj, last_values, rng: step(
            state, traj, last_values)


class ESEpochLoop(RLEpochLoop):
    """Evolution-strategies epoch loop (reference: algo/es.yaml).

    Each epoch: draw an antithetic population (one member per vectorised
    env), evaluate every member's fitness over a fixed interaction window
    with a single vmapped population forward per step, then apply the
    rank-shaped ES update on device. ``num_envs`` is the population size
    and must be even.
    """

    # population fitness steps the HOST envs (the fully on-device ES
    # path is rl/es_device.py); fused epochs are rejected loudly
    SUPPORTS_FUSED = False
    SUPPORTS_PARAM_SHARDING = False  # host population-fitness path
    SUPPORTS_SOCKET_COLLECTION = False  # fitness steps envs directly

    def _configure_algo(self, algo_config, num_envs, rollout_length) -> None:
        self.es_cfg = es_config_from_rllib(algo_config)
        self.num_envs = int(num_envs
                            or (algo_config or {}).get("num_workers") or 10)
        if self.num_envs % 2:
            self.num_envs += 1  # antithetic pairs
        self.rollout_length = int(
            rollout_length
            or max(self.es_cfg.train_batch_size // self.num_envs, 1))

    def _build_learner(self) -> None:
        from ddls_tpu.rl.es import ESLearner

        self.learner = ESLearner(self.apply_fn, self.es_cfg, self.mesh,
                                 population=self.num_envs)
        if self.device_collector:
            raise ValueError(
                "device_collector is not supported for es (population "
                "fitness steps the host envs; the fully on-device ES "
                "path is rl/es_device.py:train_es_on_device)")
        self.state = self.learner.init_state(self.params)
        self.collector = None

    def run(self) -> Dict[str, Any]:
        import jax

        start = time.time()
        # the perturbation rng feeds a state update, so it must be the
        # SHARED stream: every host draws the identical population. Hosts
        # then evaluate it on their own (differently seeded) envs and the
        # per-member fitness is averaged across hosts — multi-host ES is
        # fitness variance reduction, not population scale-out.
        epoch_rng = self._split_rng()
        perturb_rng, eval_gate_rng = jax.random.split(epoch_rng)
        # action-noise rng is COLLECT randomness (per-process, like env
        # seeds): hosts must explore independently for the cross-host
        # fitness average to reduce variance. Only perturb/gate draws come
        # from the shared stream (they feed the update / guard a branch)
        noise_rng = self._split_collect_rng()
        with telemetry.span("train.collect"):
            stacked, eps = self.learner.perturb(self.state.params,
                                                perturb_rng)
            fitness = self.learner.evaluate_population(
                stacked, self.vec_env, window=self.rollout_length,
                rng=noise_rng)
        if jax.process_count() > 1:
            from jax.experimental import multihost_utils

            fitness = np.mean(
                multihost_utils.process_allgather(
                    np.asarray(fitness, np.float32)), axis=0)
        with telemetry.span("train.train_step"):
            self.state, metrics = self.learner.update(self.state, eps,
                                                      fitness)
        metrics = self._harvest_metrics(metrics)
        # training episodes are drained BEFORE any eval window so the eval
        # policy's episodes can never leak into the training stats
        completed_episodes = self.vec_env.drain_completed_episodes()
        # eval_prob: occasionally measure the unperturbed mean params
        # (noise-free, excluded from the gradient). The gate draws from the
        # SHARED rng stream, so every host takes the same branch and the
        # fitness allgather above can never desync (CLAUDE.md multi-host
        # rule: deterministic gates only). The window runs on the training
        # vec env — window fitness already carries state across epochs (the
        # next population inherits the last one's env states by design), so
        # the mean policy advancing them is the same regime; its episodes
        # are drained and dropped, and its steps are reported separately
        eval_env_steps = 0
        if (self.es_cfg.eval_prob > 0
                and float(jax.random.uniform(eval_gate_rng))
                < self.es_cfg.eval_prob):
            metrics["eval_fitness_mean"] = self.learner.evaluate_mean_params(
                self.state.params, self.vec_env,
                window=self.rollout_length)
            eval_env_steps = self.rollout_length * self.num_envs
            # drop the eval window's own episodes AND the part-eval partial
            # episodes still in flight: a fresh restart is the only way
            # mean-policy steps can't straddle into next epoch's stats
            self.vec_env.drain_completed_episodes()
            self.vec_env.restart_episodes()

        self.epoch_counter += 1
        # sync gate AFTER the increment, so the drain cadence matches the
        # base/DQN loops (epochs interval, 2*interval, ...) exactly
        self._maybe_sync_metrics()
        env_steps = self.rollout_length * self.num_envs
        self.total_env_steps += env_steps
        results: Dict[str, Any] = {
            "epoch_counter": self.epoch_counter,
            "env_steps_this_iter": env_steps,
            "total_env_steps": self.total_env_steps,
            "learner": metrics,
        }
        if eval_env_steps:
            results["eval_env_steps_this_iter"] = eval_env_steps
        return self._finalize_results(results, completed_episodes, start)


# algo_name (our algo/*.yaml) -> epoch-loop class; train_from_config
# dispatches through this and hard-errors on unknown names so a mistyped
# algo can never silently train PPO-with-defaults
EPOCH_LOOPS = {
    "ppo": RLEpochLoop,
    "apex_dqn": ApexDQNEpochLoop,
    "impala": ImpalaEpochLoop,
    "pg": PGEpochLoop,
    "es": ESEpochLoop,
}


def make_epoch_loop(algo_name: Optional[str], **kwargs):
    name = (algo_name or "ppo").lower()
    if name not in EPOCH_LOOPS:
        raise ValueError(
            f"unknown algo_name {algo_name!r}; available: "
            f"{sorted(EPOCH_LOOPS)}")
    return EPOCH_LOOPS[name](**kwargs)


class EvalLoop:
    """Heuristic-actor evaluation (reference: ddls/loops/eval_loop.py:14).

    ``actor`` implements ``compute_action(obs, job_to_place=...)``; results
    harvest the cluster's steps_log and episode_stats.
    """

    def __init__(self, env, actor, wandb=None, verbose: bool = False,
                 **kwargs):
        self.env = env
        self.actor = actor
        self.wandb = wandb
        self.verbose = verbose

    def run(self, seed: Optional[int] = None,
            max_steps: Optional[int] = None) -> Dict[str, Any]:
        obs = self.env.reset(seed=seed)
        # episode boundary for stateful actors (e.g. AdaptiveDegreePacking's
        # legacy load estimate): explicit reset beats heuristic detection
        reset = getattr(self.actor, "reset", None)
        if callable(reset):
            reset()
        done, steps, total_reward = False, 0, 0.0
        start = time.time()
        while not done and (max_steps is None or steps < max_steps):
            job = None
            queue = getattr(self.env.cluster, "job_queue", None)
            if queue is not None and len(queue.jobs):
                job = list(queue.jobs.values())[0]
            action = self.actor.compute_action(obs, job_to_place=job,
                                               env=self.env)
            obs, reward, done, _ = self.env.step(action)
            total_reward += reward
            steps += 1
            if self.verbose:
                print(f"step {steps}: action={action} reward={reward:.4f}")
        results = {
            "episode_return": total_reward,
            "episode_length": steps,
            "wall_time": time.time() - start,
            "episode_stats": dict(self.env.cluster.episode_stats),
            "steps_log": {k: list(v)
                          for k, v in self.env.cluster.steps_log.items()},
        }
        if self.wandb is not None:
            self.wandb.log({"eval/episode_return": total_reward,
                            "eval/episode_length": steps})
        return results


class RLEvalLoop:
    """Checkpoint-restoring policy evaluation (reference:
    ddls/loops/rllib_eval_loop.py:11)."""

    def __init__(self, epoch_loop: RLEpochLoop, **kwargs):
        self.epoch_loop = epoch_loop

    def run(self, checkpoint_path: Optional[str] = None,
            seed: Optional[int] = None) -> Dict[str, Any]:
        if checkpoint_path:
            self.epoch_loop.load_agent_checkpoint(checkpoint_path)
        env = self.epoch_loop.make_eval_env()
        record = self.epoch_loop._run_greedy_episode(
            env, seed if seed is not None
            else (self.epoch_loop.test_seed or 0))
        return {
            "episode": record,
            "episode_stats": dict(env.cluster.episode_stats),
            "steps_log": {k: list(v)
                          for k, v in env.cluster.steps_log.items()},
        }


class EnvLoop:
    """Generic single-episode driver (reference: ddls/loops/env_loop.py:4)."""

    def __init__(self, env, actor):
        self.env = env
        self.actor = actor

    def run(self, seed: Optional[int] = None) -> Dict[str, Any]:
        obs = self.env.reset(seed=seed)
        done, steps, total = False, 0, 0.0
        while not done:
            action = self.actor.compute_action(obs)
            obs, reward, done, _ = self.env.step(action)
            total += reward
            steps += 1
        return {"episode_return": total, "episode_length": steps}


class EpochLoop:
    """Generic batch-of-episodes driver (reference:
    ddls/loops/epoch_loop.py:5)."""

    def __init__(self, env_loop: EnvLoop, episodes_per_epoch: int = 1):
        self.env_loop = env_loop
        self.episodes_per_epoch = episodes_per_epoch

    def run(self) -> Dict[str, Any]:
        episodes = [self.env_loop.run()
                    for _ in range(self.episodes_per_epoch)]
        return {"episodes": episodes}
