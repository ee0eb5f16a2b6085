"""The placement-shaping MDP: the agent chooses a job's meta-block shape.

Reference: ddls/environments/ramp_job_placement_shaping/
ramp_job_placement_shaping_environment.py:29. The second PAC-ML MDP framing:
a heuristic op partitioner (SiP-ML by default) decides per-op partition
counts before the agent acts; the agent's Discrete(C*R*S + 1) action selects
the (c, r, s) meta-block shape the placer must fit the job into (0 = do not
place). The rest of the pipeline (first-fit placer constrained to the chosen
shape -> SRPT op scheduler -> first-fit dep placer -> SRPT dep scheduler ->
cluster step -> reward -> auto-step to the next decision point) matches the
partitioning env.
"""
from __future__ import annotations

from typing import Optional

from ddls_tpu.agents.partitioners import (RandomOpPartitioner,
                                          SipMlOpPartitioner)
from ddls_tpu.agents.placers import (FirstFitDepPlacer, RampFirstFitOpPlacer,
                                     RandomOpPlacer)
from ddls_tpu.agents.schedulers import SRPTDepScheduler, SRPTOpScheduler
from ddls_tpu.envs import spaces
from ddls_tpu.envs.rewards import make_reward_function
from ddls_tpu.envs.shaping_obs import (RampJobPlacementShapingObservation,
                                       shape_action_table)
from ddls_tpu.sim.actions import Action, JobPlacementShape, OpPartition
from ddls_tpu.sim.cluster import (RampClusterEnvironment,
                                  refuse_retired_kwargs)

OP_PARTITIONERS = {
    "sip_ml_op_partitioner": SipMlOpPartitioner,
    "random_op_partitioner": RandomOpPartitioner,
}
OP_PLACERS = {
    "ramp_first_fit_op_placer": RampFirstFitOpPlacer,
    "random_op_placer": RandomOpPlacer,
}
OP_SCHEDULERS = {"srpt_op_scheduler": SRPTOpScheduler}
DEP_PLACERS = {"first_fit_dep_placer": FirstFitDepPlacer}
DEP_SCHEDULERS = {"srpt_dep_scheduler": SRPTDepScheduler}


class RampJobPlacementShapingEnvironment:
    def __init__(self,
                 topology_config: dict,
                 node_config: dict,
                 jobs_config: dict,
                 op_partitioner: str = "sip_ml_op_partitioner",
                 op_partitioner_kwargs: Optional[dict] = None,
                 op_placer: str = "ramp_first_fit_op_placer",
                 op_placer_kwargs: Optional[dict] = None,
                 op_scheduler: str = "srpt_op_scheduler",
                 op_scheduler_kwargs: Optional[dict] = None,
                 dep_placer: str = "first_fit_dep_placer",
                 dep_placer_kwargs: Optional[dict] = None,
                 dep_scheduler: str = "srpt_dep_scheduler",
                 dep_scheduler_kwargs: Optional[dict] = None,
                 observation_function: str = (
                     "ramp_job_placement_shaping_observation"),
                 pad_obs_kwargs: Optional[dict] = None,
                 information_function: str = "default",
                 reward_function: str = "lookahead_job_completion_time",
                 reward_function_kwargs: Optional[dict] = None,
                 max_simulation_run_time: Optional[float] = None,
                 job_queue_capacity: int = 10,
                 suppress_warnings: bool = True,
                 name: str = "ramp_job_placement_shaping",
                 path_to_save: Optional[str] = None,
                 save_cluster_data: bool = False,
                 save_freq: int = 1,
                 use_sqlite_database: bool = False,
                 use_native_lookahead: str | bool = "auto",
                 apply_action_mask: bool = True,
                 **kwargs):
        refuse_retired_kwargs(kwargs)
        self.topology_config = topology_config
        self.node_config = node_config
        self.jobs_config = jobs_config
        self.max_simulation_run_time = (
            float("inf") if max_simulation_run_time is None
            else float(max_simulation_run_time))
        self.job_queue_capacity = job_queue_capacity
        self.apply_action_mask = apply_action_mask
        self.name = name

        self.cluster = RampClusterEnvironment(
            topology_config=topology_config,
            node_config=node_config,
            path_to_save=path_to_save if save_cluster_data else None,
            save_freq=save_freq,
            use_sqlite_database=use_sqlite_database,
            use_native_lookahead=use_native_lookahead)

        if observation_function != "ramp_job_placement_shaping_observation":
            raise ValueError(
                f"unrecognised observation_function {observation_function}")
        self.observation_function = RampJobPlacementShapingObservation(
            pad_obs_kwargs=pad_obs_kwargs)

        self.action_to_shape = shape_action_table(self.cluster.topology)
        self.action_set = list(self.action_to_shape)
        self.action_space = spaces.Discrete(len(self.action_set))
        self.observation_space: Optional[spaces.Dict] = None

        self.reward_function = make_reward_function(
            reward_function, reward_function_kwargs)

        from ddls_tpu.envs.interfaces import make_information_function
        self.information_function = make_information_function(
            information_function)

        self.op_partitioner = OP_PARTITIONERS[op_partitioner](
            **(op_partitioner_kwargs or {}))
        self.op_placer = OP_PLACERS[op_placer](**(op_placer_kwargs or {}))
        self.op_scheduler = OP_SCHEDULERS[op_scheduler](
            **(op_scheduler_kwargs or {}))
        self.dep_placer = DEP_PLACERS[dep_placer](**(dep_placer_kwargs or {}))
        self.dep_scheduler = DEP_SCHEDULERS[dep_scheduler](
            **(dep_scheduler_kwargs or {}))

    # ------------------------------------------------------------------- api
    def reset(self, seed: Optional[int] = None, verbose: bool = False):
        self.step_counter = 1
        self.op_partition = None
        self.cluster.reset(jobs_config=self.jobs_config,
                           max_simulation_run_time=self.max_simulation_run_time,
                           job_queue_capacity=self.job_queue_capacity,
                           seed=seed)
        self._update_op_partition()
        self.observation_function.reset(self)
        self.observation_space = self.observation_function.observation_space
        self.reward_function.reset(env=self)
        self.information_function.reset(self)
        self.obs = self._get_observation()
        return self.obs

    def _update_op_partition(self) -> None:
        """Run the heuristic partitioner on the queued job (reference:
        :196-198,294-296); degree cap comes from
        jobs_config.max_partitions_per_op_in_observation."""
        if len(self.cluster.job_queue) == 0:
            self.op_partition = None
            return
        max_parts = self.cluster.jobs_generator\
            .max_partitions_per_op_in_observation
        self.op_partition = self.op_partitioner.get(
            cluster=self.cluster, max_partitions_per_op=max_parts)

    def _is_done(self) -> bool:
        return self.cluster.is_done()

    def _get_observation(self):
        return self.observation_function.extract(env=self,
                                                 done=self._is_done())

    def _step_cluster(self, action: Action) -> None:
        self.cluster.step(action)
        self.cluster_step_stats[self.cluster.step_counter] = (
            self.cluster.step_stats)

    def step(self, action: int, verbose: bool = False):
        self.cluster_step_stats = {}

        action = int(action)
        if action not in self.action_to_shape:
            raise ValueError(
                f"action {action} not in action set {self.action_set}")
        if not self.obs["action_mask"][action]:
            if self.apply_action_mask:
                raise ValueError(
                    f"action {action} is invalid under the current action "
                    f"mask {self.obs['action_mask']}; set "
                    "apply_action_mask=False to silently fall back to 0")
            action = 0

        shape = self.action_to_shape[action]
        if shape is not None and self.op_partition is not None:
            op_partition = self.op_partition
            job_id = next(iter(op_partition.partitioned_jobs))
            job_placement_shape = JobPlacementShape({job_id: shape})
            meta_block_shapes = {job_id: shape}
        else:
            op_partition = OpPartition({}, cluster=self.cluster)
            job_placement_shape = JobPlacementShape({})
            meta_block_shapes = None
        self.op_placement = self.op_placer.get(
            op_partition=op_partition, cluster=self.cluster,
            meta_block_shapes=meta_block_shapes)
        self.op_schedule = self.op_scheduler.get(
            op_partition=op_partition, op_placement=self.op_placement,
            cluster=self.cluster)
        self.dep_placement = self.dep_placer.get(
            op_partition=op_partition, op_placement=self.op_placement,
            cluster=self.cluster)
        self.dep_schedule = self.dep_scheduler.get(
            op_partition=op_partition, dep_placement=self.dep_placement,
            cluster=self.cluster)
        self.action = Action(op_partition=op_partition,
                             op_placement=self.op_placement,
                             op_schedule=self.op_schedule,
                             dep_placement=self.dep_placement,
                             dep_schedule=self.dep_schedule,
                             job_placement_shape=job_placement_shape)

        self.last_job_arrived_job_idx = self.cluster.last_job_arrived_job_idx
        self._step_cluster(self.action)

        self.placed_job_idxs = set(self.action.job_idxs)
        for job_idx in list(self.placed_job_idxs):
            if job_idx in self.cluster.jobs_blocked:
                self.placed_job_idxs.discard(job_idx)
        # stash before auto-stepping: episode finalisation can sweep the
        # placed job out of jobs_running (see partitioning_env.step)
        self.last_placed_job = (
            self.cluster.jobs_running.get(self.last_job_arrived_job_idx)
            if self.last_job_arrived_job_idx in self.placed_job_idxs
            else None)

        # auto-step to the next decision point, then extract the reward
        # (same ordering as the partitioning env)
        while len(self.cluster.job_queue) == 0 and not self.cluster.is_done():
            self._step_cluster(Action())

        self.reward = self.reward_function.extract(env=self,
                                                   done=self._is_done())

        self.done = self._is_done()
        if not self.done:
            self._update_op_partition()
            self.obs = self._get_observation()
        self.info = self.information_function.extract(env=self,
                                                      done=self.done)
        self.step_counter += 1
        return self.obs, self.reward, self.done, self.info
