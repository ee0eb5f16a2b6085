"""Fused on-device collect→update training (the Podracer/Anakin shape).

ONE jitted program runs a whole epoch: a `lax.scan` over
``updates_per_epoch`` collect→update rounds. Each round collects a
[T, B] segment with the in-kernel environment (`sim/jax_env.py
make_segment_fn`, vmapped over B job-bank lanes sharded on the mesh's
``dp`` axis) and applies the learner's scan-based update in-scan — the
gradient all-reduce over dp is emitted by XLA from the very sharding
annotations the standalone update uses. Params/opt-state/rng keys are
carried on device for the entire epoch, so the only host↔device traffic
per epoch is the ONE dispatch of the fused call, paid once per
``updates_per_epoch`` updates instead of twice per update
(PAPERS.md: arXiv 2104.06272 Podracer/Anakin; the pattern JAX-native
env suites are built for, Jumanji arXiv 2306.09884).

Parity contract: the fused program is the SAME math as the sequential
device-collector path (`rl/ppo_device.py:DevicePPOCollector` +
`PPOLearner.train_step`) — same segment kernel, same obs rebuild
(`_kernel_obs`), same f64-then-f32 cast order on the traj leaves, same
rng-split bookkeeping as `RLEpochLoop._split_rng`/`_split_collect_rng`
— pinned exactly in x64 by tests/test_fused.py's full-epoch parity
driver. Metrics and episode counters come back as DEVICE arrays
([U]-stacked metric dicts, compact [U, B, T] episode-counter traces)
and ride the existing LazyMetrics futures contract: the training loop
drains them per ``metrics_sync_interval`` epochs, never per update
(hot-path-transfer rule; the steady-state epoch passes
``jax.transfer_guard("disallow")``).

Shape: the driver takes its lane count from the banks it is given and
its segment length as an argument; `train/loops.py:_build_fused` is the
one place that decides them (``num_envs`` lanes x ``rollout_length``
steps, or a ``fused_config`` pin that re-factorises the same batch).
Which shape is FASTEST on a chip is a measurement: `PERF.md`.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional, Tuple

import numpy as np

from ddls_tpu import telemetry
from ddls_tpu.demands.jobs_generator import BANK_GAUGES
from ddls_tpu.sim.jax_env import (CAUSE_ACCEPTED, CAUSE_OP_PLACEMENT,
                                  MASK_GAUGES)
from ddls_tpu.sim.jax_lookahead import (MINOR_GAUGES, channel_trips,
                                        stage_widths)
from ddls_tpu.sim.jax_memo import TABLE_GAUGE, MemoCounters, table_bytes
from ddls_tpu.telemetry import scopes, startup


def horizon_bank_jobs(env, seed: int,
                      explicit: Optional[int] = None) -> int:
    """Jobs per lane bank: the explicit config when given, else sized to
    cover the sim horizon — the ONE sizing home for the device
    collector and the fused loop (an under-sized bank ends
    in-kernel episodes early: arrival_t=inf silently truncates them).

    Sizing provisions for the SUM of interarrivals, not its mean: a
    heavy-tailed distribution can draw a lighter-than-mean bank and
    exhaust early, so a 2-sigma CLT margin on the horizon's arrival
    count rides on top of 10% slack. The process-global numpy rng the
    distributions draw from is snapshotted/restored, so sizing never
    perturbs a caller's stochastic streams."""
    if explicit:
        return int(explicit)
    msrt = float(env.max_simulation_run_time)
    if not np.isfinite(msrt):
        raise ValueError(
            "device/fused collection with an unbounded "
            "max_simulation_run_time needs an explicit "
            "algo_config device_bank_jobs")
    rng_state = np.random.get_state()
    try:
        np.random.seed(seed)
        ias = np.array([env.cluster.jobs_generator
                        .interarrival_dist.sample()
                        for _ in range(1000)], np.float64)
    finally:
        np.random.set_state(rng_state)
    mean = max(float(ias.mean()), 1e-9)
    base = msrt / mean
    return int(base * 1.1
               + 2.0 * (float(ias.std()) / mean) * np.sqrt(base)) + 10


def stacked_job_banks(et, env, n_lanes: int, n_jobs: int,
                      seed_base: int = 0) -> Dict:
    """Per-lane job banks sampled from ``env``'s own workload machinery,
    stacked along a leading lane axis. Lane i draws with seed
    ``seed_base + 7559 * i + 17`` — THE device-collection seed formula
    (one home, so fused lanes == num_envs reproduce the device
    collector's banks bit-for-bit)."""
    import jax.numpy as jnp

    from ddls_tpu.sim.jax_env import sample_job_bank

    banks = [sample_job_bank(et, env, n_jobs, seed_base + 7559 * i + 17)
             for i in range(n_lanes)]
    return {k: jnp.asarray(np.stack([b[k] for b in banks]))
            for k in banks[0]}


#: the compact trace keys the fused program returns per decision step
#: (the rest of the segment trace — obs fields, actions — stays INSIDE
#: the program; only these [U, B, T] scalars ever leave): the episode
#: counters ``harvest_episodes`` reads, the lookahead trip count
#: (``make_segment_fn(trace_trips=True)``) that
#: ``record_lookahead_trips`` reads while telemetry is on, and the
#: decision's job type and action, from which ``record_padding_fill``
#: finds the (model, degree) row each decision ran, and its verdict and
#: the occupied-server count it saw (``record_decisions``). ``la_trips``,
#: ``la_rode`` (the servers the decision's job rode, beside its trips),
#: ``jtype``, ``action``, ``cause`` and ``n_occupied`` are read only
#: while telemetry is on and are NOT gated on it (ROADMAP D13): the
#: traced run must be the program the untraced run measures
EPISODE_TRACE_KEYS = ("done", "ep_return", "ep_blocked", "ep_completed",
                      "ep_arrived", "la_trips", "la_rode", "jtype",
                      "action", "cause", "n_occupied")

def _count_startup_gauges(names) -> None:
    """Add each set start-up gauge onto the telemetry counter of its
    name, once per drained epoch trace (telemetry is off while a program
    is traced or built, so such sizes live in the start-up registry).
    The named gauges alone: ``startup.gauges()`` snapshots the whole
    start-up registry (thousands of jax spans)."""
    for name in names:
        value = startup.registry().gauge(name).value
        if value is not None:
            telemetry.inc(name, value)


def record_lookahead_trips(ep_trace, pads, num_workers: int) -> None:
    """Reduce a FETCHED ``[..., B, T]`` lookahead trip trace
    (``la_trips``: each lane-step's own loop count; ``la_rode``: the
    servers its job rode, 0 where it ran no trip) into the
    ``sim.lookahead.*`` telemetry counters: ``trips`` — the trips of the
    lane-steps whose lookahead ran (a memo hit and an action that runs
    no lookahead run none), summed;
    ``lockstep_trips`` — summed over steps, the maximum over the lanes:
    the trips the lockstep of loops executed, since it runs while any
    lane's cond holds and every lane that loops carries its count out;
    ``stage_trips.<W>`` — those trips by the width they ran at: the
    lockstep runs in stages of falling width
    (`sim/jax_lookahead.py:stage_widths`, the function the kernel
    itself calls, on the trace's lanes and the tables' block side), a
    stage ending at the trip after which the next width holds the
    lanes still live, which each step's own counts give
    (`stage_trips`); ``lockstep_lane_trips`` — each stage's trips times
    its width, summed: the lane-trips the device paid for;
    ``narrow_trips`` — the lockstep's trips that ran over a channel
    table NARROWER than the cluster's (`sim/jax_lookahead.py:
    channel_widths` of the cluster's ``num_workers`` servers and the
    block side; every trip where the table has one width), and
    ``narrowest_trips`` — those over its first rung: a lane-packed
    stage ticks each trip over the narrowest table that holds what
    every lane still live rode (`channel_trips`, the loop's own rule);
    ``rode.<n>`` — the lane-steps that ran trips, by the servers their
    job rode. From the tables' ``pads`` (a ``ConfigPads``), once per
    drained epoch trace: ``dep_slots`` — the dep slots a trip
    passes over (blocks x split^2) — and ``dep_slots_used`` — the
    largest row's real deps; their ratio is what the block layout's
    padding costs. And from the lookahead's own start-up gauges, set
    when its batching rule ran in a trace
    (`sim/jax_lookahead.py:_lane_batched_lookahead`; absent before):
    ``minor_slots`` — the minor-axis extent of the dep state the loop
    carries, in whole 128-wide registers — and ``minor_used`` — the
    real slots of it. The caller gates on ``telemetry.enabled()``."""
    own = np.moveaxis(np.asarray(ep_trace["la_trips"]), -2, -1)
    rode = np.moveaxis(np.asarray(ep_trace["la_rode"]), -2, -1)
    side = int(pads.max_split)
    widths = stage_widths(own.shape[-1], side)
    # [stages, channel widths], summed over the steps
    trips = channel_trips(own, rode, widths, num_workers, side)
    trips = trips.reshape((-1,) + trips.shape[-2:]).sum(axis=0)
    by_width, by_channel = trips.sum(axis=1), trips.sum(axis=0)
    telemetry.inc("sim.lookahead.trips", int(own.sum()))
    telemetry.inc("sim.lookahead.lockstep_trips", int(by_width.sum()))
    telemetry.inc("sim.lookahead.lockstep_lane_trips",
                  int(by_width @ np.asarray(widths)))
    for width, count in zip(widths, by_width.tolist()):
        telemetry.inc(f"sim.lookahead.stage_trips.{width}", count)
    telemetry.inc("sim.lookahead.narrow_trips", int(
        by_channel[:-1].sum() if len(by_channel) > 1 else by_channel[0]))
    telemetry.inc("sim.lookahead.narrowest_trips", int(by_channel[0]))
    for servers, count in zip(*np.unique(rode[own > 0],
                                         return_counts=True)):
        telemetry.inc(f"sim.lookahead.rode.{servers}", int(count))
    telemetry.inc("sim.lookahead.dep_slots", int(pads.n_deps))
    telemetry.inc("sim.lookahead.dep_slots_used", int(pads.n_deps_used))
    _count_startup_gauges(MINOR_GAUGES)


def _chosen_rows(et, jtype, action):
    """The stacked tables' (job type, degree) row of each decision
    (action 0, no row, reads the type's degree-1 row)."""
    column = np.zeros(et.max_action + 1, np.int64)
    column[et.degrees] = np.arange(len(et.degrees))
    return jtype * len(et.degrees) + column[action]


def record_padding_fill(ep_trace, et, ot) -> None:
    """What padding cost the decisions that RAN, from a FETCHED
    ``[..., B, T]`` trace and the tables it ran on. Over the lane-steps
    whose lookahead looped (``la_trips`` > 0):
    ``sim.lookahead.dep_slots_decided`` — the real deps of each
    decision's own (model, degree) row (``et.row_deps``, the tables'
    host copy: nothing is fetched here), summed — beside
    ``sim.lookahead.dep_slots_offered`` — those decisions x the dep
    slots every trip passes over (``pads.n_deps``) — and
    ``sim.lookahead.ops_decided`` — the ops (forward and mirrored) of
    each such decision's job, from the static per-type node counts: the
    lanes' own ``sim.lookahead.trips`` over it is the trips an op of a
    decided job cost, what a graph's shape (a chain, a branch) does to
    the event count. Over every step:
    ``env.obs.nodes_real`` — the queued job's graph nodes — beside
    ``env.obs.nodes_padded`` — steps x the observation's node pad, the
    GNN's padded work. The caller gates on ``telemetry.enabled()``."""
    jtype = np.asarray(ep_trace["jtype"])
    ran = np.asarray(ep_trace["la_trips"]) > 0
    row = _chosen_rows(et, jtype[ran],
                       np.asarray(ep_trace["action"])[ran])
    nodes = ot["node_split"][:, 0]      # a job type's ops, unsplit
    telemetry.inc("sim.lookahead.dep_slots_decided",
                  int(et.row_deps[row].sum()))
    telemetry.inc("sim.lookahead.dep_slots_offered",
                  int(ran.sum()) * int(et.pads.n_deps))
    telemetry.inc("sim.lookahead.ops_decided", int(nodes[jtype[ran]].sum()))
    telemetry.inc("env.obs.nodes_real", int(nodes[jtype].sum()))
    telemetry.inc("env.obs.nodes_padded",
                  jtype.size * int(ot["node_features"].shape[1]))


def record_decisions(ep_trace, et, ot) -> None:
    """What the decisions met, from a FETCHED ``[..., B, T]`` trace:
    ``env.decisions.offered`` — decisions taken — beside
    ``env.decisions.accepted`` — those whose job was mounted (``cause``
    is ``CAUSE_ACCEPTED``) — and ``env.decisions.blocked_placement`` —
    those whose job then failed ``op_placement`` (no run of servers with
    the memory: what the mask offered and a memory-aware one would not);
    ``env.decisions.offered_longest`` / ``accepted_longest`` — the same
    two over the decisions on the bank's job type with the largest
    degree-1 step time (``ot["orig_seq_sum"]``);
    ``env.decisions.offered_ragged`` / ``accepted_ragged`` — the same
    two over the decisions whose chosen (job type, degree) row is ragged
    (``et.row_ragged`` > 0: some forward op split fewer ways than the
    degree; 0 where no row of the bank is);
    ``env.cluster.occupied_servers`` — the servers other jobs held when
    each decision was taken, summed — beside ``env.cluster.servers`` —
    decisions x the cluster's servers. And from the mask's start-up
    gauges (`sim/jax_env.py:mask_rows_on_empty_cluster`), once per
    drained epoch trace: ``env.mask.rows_offered`` / ``rows_placeable``
    and, where an architecture built the jobs, its
    ``demands/jobs_generator.py:BANK_GAUGES``.
    The caller gates on ``telemetry.enabled()``."""
    cause = np.asarray(ep_trace["cause"])
    accepted = cause == CAUSE_ACCEPTED
    jtype = np.asarray(ep_trace["jtype"])
    longest = jtype == int(np.argmax(ot["orig_seq_sum"]))
    action = np.asarray(ep_trace["action"])
    ragged = (action > 0) & (et.row_ragged[_chosen_rows(et, jtype, action)]
                             > 0)
    telemetry.inc("env.decisions.offered", int(accepted.size))
    telemetry.inc("env.decisions.accepted", int(accepted.sum()))
    telemetry.inc("env.decisions.blocked_placement",
                  int((cause == CAUSE_OP_PLACEMENT).sum()))
    telemetry.inc("env.decisions.offered_longest", int(longest.sum()))
    telemetry.inc("env.decisions.accepted_longest",
                  int(accepted[longest].sum()))
    telemetry.inc("env.decisions.offered_ragged", int(ragged.sum()))
    telemetry.inc("env.decisions.accepted_ragged",
                  int(accepted[ragged].sum()))
    telemetry.inc("env.cluster.occupied_servers",
                  int(np.asarray(ep_trace["n_occupied"]).sum()))
    telemetry.inc("env.cluster.servers", int(accepted.size) * et.n_srv)
    _count_startup_gauges((*MASK_GAUGES, *BANK_GAUGES))


class FusedEpochDriver(MemoCounters):
    """One jitted collect→update epoch over the in-kernel environment.

    Counterpart of `DevicePPOCollector` + the standalone jitted
    ``train_step``, fused: ``fused_epoch(state, rngs)`` scans
    ``updates_per_epoch`` rounds of [segment_len, num_lanes] collection
    + one update each, entirely on device. ``train_step_fn(state, traj,
    last_values, rng) -> (state, metrics)`` is the learner's UNJITTED
    update (e.g. ``PPOLearner._train_step``) so it traces into the
    epoch program; ``state_shardings`` mirrors the standalone jit's
    in/out shardings so the in-scan update partitions identically (the
    x64 parity contract).

    The simulator state is carried on device ACROSS epochs (episodes
    span epoch boundaries exactly as they span the sequential
    collector's segments); per-lane episode lengths are tracked
    host-side and consumed by ``harvest_episodes`` at drain boundaries.
    """

    def __init__(self, et, ot, model, banks: Dict, segment_len: int,
                 updates_per_epoch: int, train_step_fn: Callable,
                 state_shardings=None, mesh=None, memo_cfg="auto"):
        import jax
        import jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P

        from ddls_tpu.models.policy import batched_policy_apply
        from ddls_tpu.rl.ppo import traj_donate_argnums
        from ddls_tpu.sim.jax_env import (_kernel_obs, make_segment_fn,
                                          segment_init, vmap_segment_fn)
        from ddls_tpu.sim.jax_memo import resolve_memo_cfg

        self.et, self.ot, self.model = et, ot, model
        self.segment_len = int(segment_len)
        self.updates_per_epoch = int(updates_per_epoch)
        self.num_lanes = int(
            jax.tree_util.tree_leaves(banks)[0].shape[0])
        self.mesh = mesh
        self.env_steps_per_epoch = (self.updates_per_epoch
                                    * self.segment_len * self.num_lanes)
        # in-kernel lookahead memo: "auto" enables it at every lane
        # count (the batched probe masks hit lanes out of the lookahead
        # while_loop — sim/jax_memo.py, ISSUE 17); each lane carries its
        # own table, riding the carried sim state across epochs like
        # the rest of it
        self.memo_cfg = resolve_memo_cfg(memo_cfg, self.num_lanes)
        T, B, U = self.segment_len, self.num_lanes, self.updates_per_epoch
        # trace_obs: the in-scan update carry — the update consumes the
        # segment's own observations instead of re-deriving them from
        # the compact fields (a second _kernel_obs sweep over T x B
        # samples, measured ~30% of the fused epoch on CPU); same
        # _kernel_obs values either way, so parity with the sequential
        # rebuild-from-fields path is unchanged
        segment = make_segment_fn(et, ot, model, T, trace_obs=True,
                                  memo_cfg=self.memo_cfg,
                                  trace_trips=True)
        # one-lane fast path shared with DevicePPOCollector (a 1-wide
        # vmap halves the kernel's XLA:CPU throughput)
        lane_segment = vmap_segment_fn(segment, self.num_lanes)

        lane = repl = None
        if mesh is not None:
            if B % mesh.shape["dp"] != 0:
                raise ValueError(
                    f"num_lanes {B} must divide over the mesh dp axis "
                    f"({mesh.shape['dp']})")
            lane = NamedSharding(mesh, P("dp"))
            repl = NamedSharding(mesh, P())
            banks = jax.device_put(banks, lane)
            batch_time = NamedSharding(mesh, P(None, "dp"))
            batch_only = NamedSharding(mesh, P("dp"))
        self._banks = banks
        # per-lane initial sim state from each lane's OWN bank; carried
        # across fused_epoch calls like the collector's self._state
        self._state = jax.vmap(
            lambda b: segment_init(et, b, self.memo_cfg))(banks)
        startup.set_gauge(TABLE_GAUGE, table_bytes(
            self._state[1] if self.memo_cfg is not None else None))
        self._repl = repl
        if mesh is not None:
            # jax keys its jit cache on the MESH an input's sharding
            # names, not just shape/dtype: a freshly built state that
            # never saw the mesh and the same state returned by the
            # epoch program are different cache keys, and the second
            # call would trace and compile the whole program again.
            # Place the first call's inputs where the program returns
            # them (the rng keys likewise, in fused_epoch).
            self._state = jax.device_put(self._state, lane)
        self._ep_len = np.zeros(B, np.int64)
        self._first_call = True

        def obs_from_fields(jtype, frac, steps, n_occ, n_run):
            return _kernel_obs(ot, et, jtype, frac, steps, n_occ, n_run)

        def traj_from_trace(trace):
            """The exact DevicePPOCollector.collect staging, traced:
            [B, T] kernel trace -> [T, B] learner traj with the same
            f64-then-f32 casts as the host path. The obs ride the trace
            (``trace_obs`` carry) — bit-equal to the host path's
            rebuild-from-fields, which vmaps the same `_kernel_obs`."""
            def tb(x):
                return jnp.swapaxes(x, 0, 1)

            return {
                "obs": {k: tb(v) for k, v in trace["obs"].items()},
                "actions": tb(trace["action"]).astype(jnp.int32),
                "logp": tb(trace["logp"]).astype(jnp.float32),
                "values": tb(trace["value"]).astype(jnp.float32),
                "rewards": tb(trace["reward"]).astype(jnp.float32),
                "dones": tb(trace["done"]),
            }

        def one_round(carry, _):
            state, sim_state, crng, urng = carry
            # rng bookkeeping mirrors RLEpochLoop._split_collect_rng /
            # _split_rng exactly: same streams, same per-round splits,
            # so fused and sequential updates consume identical keys
            crng, csub = jax.random.split(crng)
            lane_rngs = jax.random.split(csub, B)
            sim_state, trace, next_fields = lane_segment(
                self._banks, state.params, sim_state, lane_rngs)
            traj = traj_from_trace(trace)
            next_obs = jax.vmap(obs_from_fields)(
                next_fields["jtype"], next_fields["frac"],
                next_fields["steps"], next_fields["n_occupied"],
                next_fields["n_running"])
            with jax.named_scope(scopes.POLICY_FORWARD):
                _, last_values = batched_policy_apply(model, state.params,
                                                      next_obs)
            last_values = last_values.astype(jnp.float32)
            if mesh is not None:
                # pin the staged batch to the standalone train_step's
                # in_shardings so the in-scan update partitions (and
                # therefore rounds) identically to the sequential path
                traj = jax.lax.with_sharding_constraint(
                    traj, jax.tree_util.tree_map(
                        lambda _: batch_time, traj))
                last_values = jax.lax.with_sharding_constraint(
                    last_values, batch_only)
            urng, usub = jax.random.split(urng)
            state, metrics = train_step_fn(state, traj, last_values,
                                           usub)
            # memo trace keys stay INSIDE the program (XLA DCEs the
            # unused stacking): cumulative counters are reported from the
            # carried memo state via memo_counters() at drain boundaries
            ep = {k: trace[k] for k in EPISODE_TRACE_KEYS}
            return (state, sim_state, crng, urng), (metrics, ep)

        def epoch(state, sim_state, crng, urng):
            (state, sim_state, crng, urng), (metrics, ep) = jax.lax.scan(
                one_round, (state, sim_state, crng, urng), None,
                length=U)
            return state, sim_state, crng, urng, metrics, ep

        if mesh is not None:
            sharded_sim = jax.tree_util.tree_map(lambda _: lane,
                                                 self._state)
            # episode-counter outputs are [U, B, T]: B on axis 1
            ep_sh = NamedSharding(mesh, P(None, "dp"))
            state_sh = (state_shardings if state_shardings is not None
                        else repl)
            self._jit_epoch = jax.jit(
                epoch,
                in_shardings=(state_sh, sharded_sim, repl, repl),
                out_shardings=(state_sh, sharded_sim, repl, repl, repl,
                               ep_sh),
                donate_argnums=traj_donate_argnums(0, 1))
        else:
            self._jit_epoch = jax.jit(
                epoch, donate_argnums=traj_donate_argnums(0, 1))

    # ------------------------------------------------------------- run
    def lower(self, state):
        """Lower (trace, no compile/execute) the fused program: what
        the benchmark reads the program's scratch bytes from, and what
        tests compare as text."""
        import jax

        crng = urng = jax.random.PRNGKey(0)
        return self._jit_epoch.lower(state, self._state, crng, urng)

    def fused_epoch(self, state, rngs: Tuple):
        """ONE device dispatch: ``updates_per_epoch`` collect→update
        rounds. ``rngs`` is (collect_rng, update_rng); both are split
        in-kernel with the host loop's exact bookkeeping and returned
        advanced. Returns (state, (collect_rng, update_rng),
        metrics [U]-stacked dict, episode_trace dict of [U, B, T]) —
        ALL device values; no transfer happens here (the LazyMetrics /
        episode-drain boundaries fetch later, batched).
        """
        crng, urng = rngs
        if self._repl is not None:
            import jax

            # same jit cache key on every call (see __init__); a no-op
            # for the advanced keys a previous call returned
            crng, urng = jax.device_put((crng, urng), self._repl)
        if self._first_call:
            self._first_call = False
            return self._first_epoch(state, crng, urng)
        (state, self._state, crng, urng, metrics,
         ep) = self._jit_epoch(state, self._state, crng, urng)
        return state, (crng, urng), metrics, ep

    def _first_epoch(self, state, crng, urng):
        """The first call, under ``startup.first_epoch``: tracing,
        lowering and compiling (or loading) the epoch program — jax's
        own durations land beside the span as ``startup.jax.*`` — then
        dispatch and the first execution, waited for here so that the
        span holds all of it. One wait in a run's life; every later
        call stays an asynchronous dispatch. Start-up ends here, so the
        start-up registry is printed here, once."""
        import jax

        with startup.span("startup.first_epoch"):
            try:
                (state, self._state, crng, urng, metrics,
                 ep) = self._jit_epoch(state, self._state, crng, urng)
            except Exception as err:
                # the compiler's own error, with the shape it refused
                err.add_note(
                    "fused epoch program: "
                    f"{self.num_lanes} lanes x {self.segment_len} steps "
                    f"x {self.updates_per_epoch} updates")
                raise
            jax.block_until_ready((state, ep))
        print(startup.report(), flush=True)
        return state, (crng, urng), metrics, ep

    # --------------------------------------------------------- harvest
    def harvest_episodes(self, ep_trace) -> list:
        """Episode records from a FETCHED [U, B, T] episode-counter
        trace (the drain boundary hands host numpy arrays) — the same
        records, in the same (round, t, b) order, as
        ``DevicePPOCollector._harvest_episodes`` emits across U
        sequential collects, using the host denominators
        (cluster.py:1020-1023)."""
        episodes = []
        done = np.asarray(ep_trace["done"])
        U, B, T = done.shape
        for u in range(U):
            for t in range(T):
                self._ep_len += 1
                for b in np.nonzero(done[u, :, t])[0]:
                    blk = int(ep_trace["ep_blocked"][u, b, t])
                    com = int(ep_trace["ep_completed"][u, b, t])
                    arr = int(ep_trace["ep_arrived"][u, b, t])
                    episodes.append({
                        "env_index": int(b),
                        "episode_return": float(
                            ep_trace["ep_return"][u, b, t]),
                        "episode_length": int(self._ep_len[b]),
                        "num_jobs_arrived": arr,
                        "num_jobs_completed": com,
                        "num_jobs_blocked": blk,
                        "acceptance_rate": com / arr if arr else 0.0,
                        "blocking_rate": blk / arr if arr else 0.0,
                    })
                    self._ep_len[b] = 0
        return episodes
