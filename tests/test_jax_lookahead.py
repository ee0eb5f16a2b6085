"""The in-kernel lookahead (`sim/jax_lookahead.py`) against the host
tick engine, by way of its flat reference (`tests/flat_lookahead.py`):
the reference reproduces the host engine's JCT/overhead outputs on real
mounted jobs (SURVEY.md §7.4.1: build the host oracle first, then
property-test the array engine against it), and the block and
lane-packed forms the package runs equal the reference bit for bit."""
import numpy as np
import pytest

from ddls_tpu.envs.partitioning_env import RampJobPartitioningEnvironment
from ddls_tpu.envs.placement_shaping_env import (
    RampJobPlacementShapingEnvironment)
from ddls_tpu.sim.cluster import RampClusterEnvironment


def _make_env(dataset_dir, max_partitions=4):
    # the C++ engine (auto-enabled) would absorb every cache-miss lookahead
    # before the host engine under test here ever ran
    return RampJobPartitioningEnvironment(
        use_native_lookahead=False,
        topology_config={"type": "ramp", "kwargs": {
            "num_communication_groups": 2,
            "num_racks_per_communication_group": 2,
            "num_servers_per_rack": 2,
            "num_channels": 1,
            "total_node_bandwidth": 1.6e12}},
        node_config={"type_1": {"num_nodes": 8, "workers_config": [
            {"num_workers": 1, "worker": "A100"}]}},
        jobs_config={
            "path_to_files": dataset_dir,
            "job_interarrival_time_dist": {
                "_target_": "ddls_tpu.demands.distributions.Fixed",
                "val": 100.0},
            "replication_factor": 4,
            "job_sampling_mode": "remove_and_repeat",
            "num_training_steps": 3},
        max_partitions_per_op=max_partitions,
        reward_function="job_acceptance",
        max_simulation_run_time=1e5,
        pad_obs_kwargs={"max_nodes": 64, "max_edges": 256})


def _collect_cases(env, actions, n_cases):
    """Step the env with the given action sequence, capturing
    (host lookahead outputs, the C++ engine's packing of the job) per
    successfully placed job."""
    from ddls_tpu.native.arrays import build_native_lookahead_arrays

    cases = []
    obs = env.reset(seed=0)
    rng = np.random.RandomState(0)
    cluster = env.cluster
    orig = cluster._run_lookahead

    def spy(job):
        jct, comm, comp, busy = orig(job)
        steps = job.num_training_steps
        arrays = build_native_lookahead_arrays(cluster, job)
        cases.append({"host": (jct / steps, comm / steps, comp / steps),
                      "host_busy": busy,
                      "arrays": arrays})
        return jct, comm, comp, busy

    cluster._run_lookahead = spy
    try:
        i = 0
        while len(cases) < n_cases:
            mask = np.asarray(obs["action_mask"])
            valid = np.nonzero(mask)[0]
            if actions == "max":
                a = int(valid[-1])
            elif actions == "min":
                a = int(valid[0])
            else:
                a = int(rng.choice(valid))
            obs, _, done, _ = env.step(a)
            i += 1
            if done or i > 200:
                obs = env.reset(seed=i)
                # memo caches persist across resets (same workload); clear
                # so repeated episodes keep producing cache-miss lookaheads
                # for the spy to capture
                cluster.lookahead_cache.clear()
    finally:
        cluster._run_lookahead = orig
    return cases


@pytest.mark.parametrize("actions", ["max", "random"])
def test_matches_host_engine(dataset_dir, actions):
    """The flat reference, in f32 at fixed pads, on the jobs a real
    episode mounts: the host engine's outputs to f32 precision."""
    from functools import partial

    import jax
    from flat_lookahead import flat_lookahead, padded_args

    env = _make_env(dataset_dir)
    cases = _collect_cases(env, actions, n_cases=6)
    assert cases, "no lookahead cases captured"

    fns = {}
    for case in cases:
        a = case["arrays"]
        key = (a.num_workers, a.num_channels)
        fn = fns.setdefault(key, jax.jit(partial(
            flat_lookahead, num_workers=key[0], num_channels=key[1])))
        t, comm, comp, busy, ok, _trips = fn(*padded_args(
            a, pad_ops=160, pad_deps=520, pad_links=2))
        assert bool(ok), "array engine failed to converge"
        host_t, host_comm, host_comp = case["host"]
        assert float(t) == pytest.approx(host_t, rel=1e-4), \
            f"jct mismatch: jax {float(t)} vs host {host_t}"
        assert float(comm) == pytest.approx(host_comm, rel=1e-4, abs=1e-6)
        assert float(comp) == pytest.approx(host_comp, rel=1e-4, abs=1e-6)
        assert float(busy) == pytest.approx(case["host_busy"], rel=1e-4,
                                            abs=1e-6)


@pytest.mark.parametrize("make,is_env", [
    (RampJobPartitioningEnvironment, True),
    (RampJobPlacementShapingEnvironment, True),
    (RampClusterEnvironment, False)],
    ids=["partitioning", "shaping", "cluster"])
def test_use_jax_lookahead_is_refused_by_name(make, is_env):
    """The retired engine's flag is REFUSED, by name, whatever its
    value: both envs end in ``**kwargs`` and would swallow a config that
    still sets it (a silently ignored engine choice), the cluster's
    signature no longer has it."""
    kwargs = {"topology_config": {}, "node_config": {}}
    if is_env:
        kwargs["jobs_config"] = {}
    for value in (True, False):
        with pytest.raises(TypeError, match="use_jax_lookahead") as err:
            make(**kwargs, use_jax_lookahead=value)
        # the envs: by the retirement's own message, not by accident
        assert not is_env or "retired in PR 42" in str(err.value)


# ---------------------------------------------------------------------------
# The block path (DepBlocks: broadcast + reduction over the partitioner's
# (block, i, j) layout) against the flat path (one gather/scatter per dep)
# on the in-kernel env's own tables: the same bits on all six outputs.
# ---------------------------------------------------------------------------

#: degree columns of the small build; with ``_BLOCK_QUANTUM`` the
#: degree-16 row of ``translation_0`` splits its ops 16/16/4/16/12/4 ways
#: (16x4 and 4x16 blocks), degree 1 gives 1x1 blocks
_BLOCK_DEGREES = [1, 2, 4, 6, 8, 10, 12, 14, 16]
_BLOCK_MODELS = ["cnn_0", "translation_0"]
_BLOCK_QUANTUM = 0.25
_BLOCK_ROWS = [(m, d) for m in _BLOCK_MODELS for d in _BLOCK_DEGREES]


def _ramp_env(dataset_dir, shape, max_partitions, **jobs):
    c, r, s = shape
    return RampJobPartitioningEnvironment(
        topology_config={"type": "ramp", "kwargs": {
            "num_communication_groups": c,
            "num_racks_per_communication_group": r,
            "num_servers_per_rack": s,
            "num_channels": 1,
            "total_node_bandwidth": 1.6e12,
            "intra_gpu_propagation_latency": 50e-9,
            "worker_io_latency": 100e-9}},
        node_config={"type_1": {"num_nodes": c * r * s, "workers_config": [
            {"num_workers": 1, "worker": "A100"}]}},
        jobs_config={
            "path_to_files": dataset_dir,
            "job_interarrival_time_dist": {
                "_target_": "ddls_tpu.demands.distributions.Fixed",
                "val": 100.0},
            "replication_factor": 2,
            "job_sampling_mode": "remove_and_repeat",
            "num_training_steps": 3, **jobs},
        max_partitions_per_op=max_partitions,
        reward_function="job_acceptance",
        max_simulation_run_time=1e5,
        pad_obs_kwargs={"max_nodes": 150, "max_edges": 512})


class _BlockBuild:
    """Episode tables of one env + jitted (place, price) -> lookahead
    arguments for a (cfg row, cluster state), and both lookahead paths."""

    def __init__(self, env, quantum=None):
        import jax
        import jax.numpy as jnp

        from flat_lookahead import block_arguments, flat_lookahead
        from flat_pricing import block_endpoint_slots

        from ddls_tpu.sim import jax_env as je
        from ddls_tpu.sim.jax_lookahead import DepBlocks, jax_lookahead

        env.reset(seed=0)
        self.et = et = je.build_episode_tables(env, quantum=quantum)
        tb = et.tables

        def arguments(cfg, other_free, scatter=0, flip=0, collide=0):
            mem = jnp.full((et.n_srv,), et.worker_mem, tb["dep_size"].dtype)
            rows = je.config_rows(tb, cfg)
            ots, _, ok = je.jax_allocate_job(mem, other_free, rows,
                                             et.st, et.pads)
            # ``scatter`` moves original op o's shards ``scatter * o``
            # servers on, before pricing: a mounted graph no allocator
            # makes, riding more servers than a block holds
            S = et.pads.max_split
            op, shard = jnp.divmod(jnp.arange(ots.shape[0]), S)
            ots = jnp.where(ots >= 0, (ots + scatter * op) % et.n_srv, ots)
            # ``flip`` puts every odd op's shards on its servers in the
            # REVERSE order (co-location refused: the ops of one job on
            # the same servers in different orders), and ``collide`` op
            # 0's second shard on its first one's server — a placement
            # the allocator never makes (a block's servers are distinct)
            split = jnp.sum((ots >= 0).reshape(-1, S), axis=1)[op]
            flipped = ots[op * S + jnp.clip(split - 1 - shard, 0)]
            ots = jnp.where((flip > 0) & (op % 2 == 1) & (ots >= 0),
                            flipped, ots)
            ots = ots.at[1].set(jnp.where((collide > 0) & (ots[1] >= 0),
                                          ots[0], ots[1]))
            times, is_flow, _, op_score, dep_score, _ = \
                je.jax_price_and_score(ots, rows, et.st, et.pads,
                                       et.comm)
            ov = rows["op_valid"]
            blocks = DepBlocks(rows["blk_src"], rows["blk_dst"])
            # the flat path's per-dep endpoints and channel, which the
            # tables no longer carry (pricing reads the blocks)
            dep_src, dep_dst = block_endpoint_slots(
                blocks.src, blocks.dst, et.pads.max_split)
            scp = jnp.clip(ots, 0)
            chan = jnp.where(
                is_flow, et.pair_channel[scp[jnp.clip(dep_src, 0)],
                                         scp[jnp.clip(dep_dst, 0)]], -1)
            return ((rows["op_compute"], ov, jnp.where(ov, ots, -1),
                     op_score, rows["num_parents"], times,
                     rows["dep_valid"], dep_src, dep_dst,
                     rows["dep_mutual"], is_flow, dep_score,
                     chan[:, None]), blocks, ok)

        def flat(args, blocks, skip=None):
            del blocks
            return flat_lookahead(*args, num_workers=et.n_srv,
                                  num_channels=et.n_chan, skip=skip)

        def block(args, blocks, skip=None):
            return jax_lookahead(*block_arguments(args), blocks,
                                 num_workers=et.n_srv, skip=skip)

        self.flat_fn, self.block_fn = flat, block
        self.arguments = jax.jit(arguments)
        self.flat, self.block = jax.jit(flat), jax.jit(block)
        # two cluster states: empty, and one where every fourth server
        # is another job's (placements land elsewhere; the widest rows
        # cannot place at all, and BOTH paths then tick the same garbage)
        self.states = [jnp.ones((et.n_srv,), bool),
                       jnp.arange(et.n_srv) % 4 != 1]

    def row(self, model, degree):
        return (self.et.types.index(model) * len(self.et.degrees)
                + self.et.degrees.index(degree))


@pytest.fixture(scope="module")
def block_build(tmp_path_factory):
    from ddls_tpu.graphs.synthetic import generate_pipedream_txt_files

    out = tmp_path_factory.mktemp("block_graphs")
    generate_pipedream_txt_files(str(out), n_cnn=1, n_translation=1,
                                 seed=31, min_ops=4, max_ops=5)
    build = _BlockBuild(_ramp_env(str(out), (2, 2, 4), 16),
                        quantum=_BLOCK_QUANTUM)
    assert build.et.types == _BLOCK_MODELS
    assert build.et.degrees == _BLOCK_DEGREES
    return build


def _assert_same_bits(got, want, what):
    names = ("t", "comm_oh", "comp_oh", "busy", "ok", "trips")
    for name, g, w in zip(names, got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and (g == w).all(), (what, name, g, w)


@pytest.mark.parametrize("model,degree", _BLOCK_ROWS,
                         ids=[f"{m}-{d}" for m, d in _BLOCK_ROWS])
def test_block_path_is_flat_path(block_build, model, degree):
    """Every (model, degree) row, placed on each cluster state: block
    path == flat path with ``==`` on all six outputs."""
    cfg = block_build.row(model, degree)
    ran = 0
    for state in block_build.states:
        args, blocks, placed = block_build.arguments(cfg, state)
        want = block_build.flat(args, blocks)
        got = block_build.block(args, blocks)
        _assert_same_bits(got, want, (model, degree, bool(placed)))
        ran += int(want[5])
        if bool(placed):
            assert bool(want[4]), "a placed job's lookahead converges"
    assert ran > 0
    if (model, degree) == ("translation_0", 16):
        splits = sorted(set(np.asarray(
            block_build.et.tables["f_split"][cfg]).tolist()))
        assert splits == [4, 12, 16], splits   # the uneven row


def _lanes(block_build, n):
    """``n`` (cfg row, cluster state) lanes of DIFFERENT rows: the
    uneven row (16/12/4) first, then a row the second state cannot
    place, then every other (row, state) in turn."""
    every = [(block_build.row(m, d), s) for m, d in _BLOCK_ROWS
             for s in range(len(block_build.states))]
    first = [(block_build.row("translation_0", 16), 0),
             (block_build.row("cnn_0", 16), 1)]
    order = first + [lane for lane in every if lane not in first]
    return [order[i % len(order)] for i in range(n)]


def _lane_arguments(block_build, lanes):
    import jax
    import jax.numpy as jnp

    cfgs = jnp.asarray([c for c, _ in lanes], jnp.int32)
    states = jnp.stack([block_build.states[s] for _, s in lanes])
    return jax.vmap(block_build.arguments)(cfgs, states)


def _flat_per_lane(block_build, args, blocks, skip, lanes):
    """The unbatched flat path of every lane, stacked; ``lanes`` names
    each lane's (cfg row, cluster state), and lanes of one name and one
    ``skip`` share their arguments, so the first of them runs for all."""
    import jax

    kinds, flat = list(zip(lanes, np.asarray(skip).tolist())), {}
    for lane, kind in enumerate(kinds):
        if kind not in flat:
            flat[kind] = block_build.flat(*jax.tree_util.tree_map(
                lambda x: x[lane], (args, blocks, skip)))
    return [np.stack([np.asarray(flat[kind][k]) for kind in kinds])
            for k in range(6)]


@pytest.mark.parametrize("skip_every", [0, 3])
@pytest.mark.parametrize("n_lanes", [1, 2, 3, 8, 24, 32, 40, 48, 80, 128,
                                     160, 320])
def test_block_path_is_flat_path_vmapped(block_build, n_lanes, skip_every):
    """Lanes of DIFFERENT rows under one vmap (the fused epoch's shape:
    the lane-packed loop at L lanes; from 128 on, one job a lane with
    the lanes minor; past one register's worth of lanes, in stages of
    falling width), with and without a ``skip`` mask: each lane's six
    outputs equal the UNBATCHED flat path's, skipped lanes (0 trips,
    init accumulators) included."""
    import jax
    import jax.numpy as jnp

    lanes = _lanes(block_build, n_lanes)
    args, blocks, placed = _lane_arguments(block_build, lanes)
    skip = (jnp.arange(n_lanes) % skip_every == 1 if skip_every
            else jnp.zeros(n_lanes, bool))
    want = _flat_per_lane(block_build, args, blocks, skip, lanes)
    got = jax.jit(jax.vmap(block_build.block_fn))(args, blocks, skip)
    _assert_same_bits(got, want, ("vmap", n_lanes, skip_every))
    trips = want[5]
    assert (trips[np.asarray(skip)] == 0).all()
    assert (trips[~np.asarray(skip)] > 0).all()
    if n_lanes > 1:
        assert not bool(placed[1])         # the unplaceable row
    if n_lanes >= 8:
        assert len(set(trips.tolist())) > 4    # lanes really differ


#: (lanes, block side) -> the widths the lockstep runs at
_STAGE_WIDTHS = [
    (1, 16, [1]), (8, 16, [8]), (9, 16, [9, 8]), (24, 16, [24, 16, 8]),
    (32, 16, [32, 16, 8]), (48, 16, [48, 24, 16, 8]),
    (80, 16, [80, 40, 24, 16, 8]), (128, 16, [128, 64, 32, 16, 8]),
    (160, 16, [160, 80, 40, 24, 16, 8]),
    (320, 16, [320, 256, 128, 64, 32, 16]),
    (2880, 16, [2880, 1536, 768, 384, 256, 128]),
    (16, 4, [16]), (40, 4, [40, 32]), (100, 4, [100, 64, 32])]


@pytest.mark.parametrize("n_lanes,side,widths", _STAGE_WIDTHS,
                         ids=[f"{n}x{s}" for n, s, _ in _STAGE_WIDTHS])
def test_stage_widths(n_lanes, side, widths):
    """The schedule is a function of the lanes and the block side
    alone: strictly descending from the lanes, whole registers of the
    form each width runs in, at most five widths under the first, and
    one loop for lanes that fit one register (the unbatched call)."""
    from ddls_tpu.sim.jax_lookahead import (MAX_NARROWER_STAGES,
                                            REGISTER_WIDTH, stage_widths)

    assert stage_widths(n_lanes, side) == widths
    assert widths[0] == n_lanes and len(widths) <= 1 + MAX_NARROWER_STAGES
    assert all(a > b for a, b in zip(widths, widths[1:]))
    for width in widths[1:]:
        unit = REGISTER_WIDTH if width >= REGISTER_WIDTH \
            else REGISTER_WIDTH // side
        assert width % unit == 0, (width, unit)


def _block_arguments(args, blocks, skip):
    """``_lane_arguments``' outputs as the lane-batched lookahead takes
    them: without the flat path's per-dep endpoints and channel."""
    from flat_lookahead import block_arguments

    return (*block_arguments(args), blocks, skip)


def _staged(block_build):
    """The lane-batched lookahead with each stage's own trip count
    beside the six results, jitted, on ``_lane_arguments``' outputs."""
    import jax

    from ddls_tpu.sim.jax_lookahead import _lane_batched_lookahead

    staged = _lane_batched_lookahead(block_build.et.n_srv).staged
    return jax.jit(lambda *lane_arguments: staged(
        *_block_arguments(*lane_arguments)))


_STAGED_MIXES = ("rows", "all_skip", "one_live", "longest_first",
                 "longest_last")


@pytest.mark.parametrize("mix", _STAGED_MIXES)
@pytest.mark.parametrize("n_lanes", [24, 80, 320])
def test_stages_run_the_trips_the_host_reckons(block_build, n_lanes, mix):
    """Lane mixes that cross every stage boundary (different rows, an
    unplaceable one among them; every lane skipped; all but one; the
    longest lane first, and last): every lane's six results are the
    unbatched flat path's bits, and each stage's loops ran exactly the
    trips `stage_trips` reckons from the lanes' own counts — what
    `record_lookahead_trips` charges the device for — at the widths of
    the channel table `channel_trips` reckons from them and the servers
    the lanes rode (16 servers under a block side of 16: two widths)."""
    import jax.numpy as jnp

    from ddls_tpu.sim.jax_lookahead import (REGISTER_WIDTH, channel_trips,
                                            channel_widths, stage_trips,
                                            stage_widths)

    lanes = _lanes(block_build, n_lanes)
    skip = {"rows": jnp.arange(n_lanes) % 5 == 2,
            "all_skip": jnp.ones(n_lanes, bool),
            "one_live": jnp.arange(n_lanes) != n_lanes // 2}.get(
                mix, jnp.zeros(n_lanes, bool))
    if mix.startswith("longest"):
        args, blocks, _ = _lane_arguments(block_build, lanes)
        own = _flat_per_lane(block_build, args, blocks, skip, lanes)[5]
        longest = lanes[int(own.argmax())]
        others = [lane for lane, trips in zip(lanes, own)
                  if trips < own.max()]
        others = [others[i % len(others)] for i in range(n_lanes - 1)]
        lanes = [longest] + others if mix == "longest_first" \
            else others + [longest]
    args, blocks, placed = _lane_arguments(block_build, lanes)
    want = _flat_per_lane(block_build, args, blocks, skip, lanes)
    got, ran = _staged(block_build)(args, blocks, skip)
    _assert_same_bits(got, want, (n_lanes, mix))
    own, S = want[5], block_build.et.pads.max_split
    widths = stage_widths(n_lanes, S)
    assert channel_widths(block_build.et.n_srv, S) == (8, 16)
    by_channel = np.asarray(ran)
    assert by_channel.tolist() == channel_trips(
        own, np.where(own > 0, _rides(args, S), 0), widths,
        block_build.et.n_srv, S).tolist()
    # one job a lane keeps the cluster's width
    assert not by_channel[np.asarray(widths) >= REGISTER_WIDTH, 0].any()
    ran = by_channel.sum(axis=1)
    assert not np.asarray(placed).all()        # an unplaceable row
    assert (own > 0).sum() == {"all_skip": 0, "one_live": 1}.get(
        mix, int((~np.asarray(skip)).sum()))
    if mix.startswith("longest"):
        assert (own == own.max()).sum() == 1
        assert int(own.argmax()) == (0 if mix == "longest_first"
                                     else n_lanes - 1)
    assert ran.tolist() == stage_trips(own, widths).tolist()
    assert ran.sum() == own.max()
    if mix == "rows":
        assert (ran > 0).sum() >= 3, ran               # stages that tick
    if mix in ("all_skip", "one_live"):
        assert [int(r) for r in ran[:-1]] == [0] * (len(widths) - 1)


@pytest.fixture(scope="module")
def wide_build(tmp_path_factory):
    """`block_build`'s two tiny graphs on RAMP 4x4x2: 32 servers under
    a block side of 16, so the lane-packed tick has THREE widths
    (`channel_widths`): the cluster's channel table and, under it, the
    state by server over the whole block side and over its first half
    — at the small pads (192 op x 4,352 dep slots)."""
    from ddls_tpu.graphs.synthetic import generate_pipedream_txt_files
    from ddls_tpu.sim.jax_lookahead import channel_widths

    out = tmp_path_factory.mktemp("wide_graphs")
    generate_pipedream_txt_files(str(out), n_cnn=1, n_translation=1,
                                 seed=31, min_ops=4, max_ops=5)
    build = _BlockBuild(_ramp_env(str(out), (4, 4, 2), 16),
                        quantum=_BLOCK_QUANTUM)
    assert build.et.n_srv == 32 and build.et.pads.max_split == 16
    assert channel_widths(32, 16) == (8, 16, 32)
    return build


def _rode(args):
    """Per lane, the servers its valid sub-ops sit on (numpy), a valid
    unplaced one counting as on server 0 — `dense_servers`' count."""
    valid, worker = np.asarray(args[1]), np.asarray(args[2])
    return np.asarray([len(set(np.clip(w[v], 0, None).tolist()))
                       for v, w in zip(valid, worker)])


def _rides(args, side):
    """`_rode`, but more than a block's side for a lane the by-server
    forms may not hold (`server_slots`: a valid sub-op nowhere — a job
    that did not place, which the env never runs — or two valid sub-ops
    of one op on one server): the cluster's form holds it while it is
    live, so that is what `channel_trips` must be told it rode."""
    valid, worker = np.asarray(args[1]), np.asarray(args[2])
    rode = _rode(args)
    for lane, (ok, on) in enumerate(zip(valid.reshape(len(rode), -1, side),
                                        worker.reshape(len(rode), -1, side))):
        if (on[ok] < 0).any() or any(
                len(set(w[v].tolist())) < v.sum() for v, w in zip(ok, on)):
            rode[lane] = max(rode[lane], side + 1)
    return rode


#: a lane: (model, degree, cluster state, scatter[, flip]). On the empty
#: 32 servers the rows ride as many servers as their degree — but the
#: ragged ones, whose 4-way ops sit on another block: degree 6 rides 8,
#: and degree 8 rides 10 beside another job (state 1). ``scatter`` 1
#: spreads a degree-2 row over 18 servers and a degree-1 row over 12,
#: ``scatter`` 2 a degree-2 or -4 row over 16, every op on ANOTHER set;
#: ``flip`` lays every odd op on its servers in the reverse order
#: (`_BlockBuild.arguments`)
_SHORT, _MID, _LONG = (("cnn_0", 1, 0, 0), ("translation_0", 2, 0, 0),
                       ("translation_0", 8, 0, 0))
_RAGGED = [("translation_0", 6, 0, 0), ("translation_0", 8, 1, 0),
           ("cnn_0", 6, 1, 0)]
_OVER = ("cnn_0", 2, 0, 1)
_MIXED = [_SHORT, _MID, _LONG, *_RAGGED, ("cnn_0", 8, 0, 0),
          ("translation_0", 4, 1, 0)]
#: riders of 9-16 servers: a short one (12 servers, 23 trips), a middle
#: one (10, 33) and a long one (10, 50); and rows on <= 8 servers
_ON_12, _ON_10, _ON_10_LONG = (("translation_0", 1, 0, 1),
                               ("cnn_0", 8, 1, 0), ("translation_0", 8, 1, 0))
_ON_8 = [_SHORT, _MID, _LONG, ("translation_0", 6, 0, 0), ("cnn_0", 8, 0, 0),
         ("translation_0", 4, 1, 0), ("cnn_0", 2, 0, 0)]
#: the ops of one job on different server sets and in different orders:
#: no op's shard k sits on the server of rank k
_SHUFFLED = [("translation_0", 4, 0, 2, 1), ("translation_0", 2, 0, 2, 1),
             ("translation_0", 1, 0, 1, 1), ("translation_0", 8, 0, 0, 1),
             ("translation_0", 6, 0, 0, 1), ("cnn_0", 8, 1, 0, 1),
             ("cnn_0", 4, 0, 2, 1), ("translation_0", 8, 1, 0, 1)]
#: rows no shape of RAMP 4x4x2 places: some ops sit nowhere (-1)
_UNPLACED = [("translation_0", 16, 0, 0), ("cnn_0", 16, 1, 0)]
#: case -> (lanes, skipped lanes): (a) every lane on <= 16 servers, the
#: longest on 8; (b) one live lane on 17-20; (c) a skipped lane on
#: > 16, and one that finishes first and is carried along as a filler,
#: beside live lanes on <= 8; (d) ragged rows alone (ops split 4 and 6
#: / 8 ways on different blocks); (e) every LIVE lane on <= 8, a
#: skipped lane on 18 and one on 10 among them; (f) a 12-server rider
#: that finishes first; (g) a 10-server rider that is the longest; (h)
#: an 18-, a 10- and <= 8-server riders in one stage; (i) every op of a
#: job on its own servers in its own order, ragged rows among them;
#: (j) two VOID lanes — a job that did not place, skipped as the env
#: skips it — frozen beside live lanes
_CHANNEL_CASES = {
    "all_narrow": ([_MIXED[i % len(_MIXED)] for i in range(24)], (5, 12)),
    "one_wide_live": ([_OVER if i == 7 else _MIXED[i % len(_MIXED)]
                       for i in range(24)], (5,)),
    "wide_skipped": ([_OVER if i in (0, 9) else _MIXED[i % len(_MIXED)]
                      for i in range(24)], (0, 9)),
    "wide_finished_filler": ([_OVER] + [_MID] * 8 + [_LONG] * 15, ()),
    "ragged_rows": ([_RAGGED[i % len(_RAGGED)] for i in range(24)], ()),
    "all_on_8": ([_OVER if i == 3 else _ON_10_LONG if i == 11
                  else _ON_8[i % len(_ON_8)] for i in range(24)], (3, 11)),
    "mid_finishes_first": ([_ON_12] + [("cnn_0", 2, 0, 0)] * 8
                           + [_LONG] * 15, ()),
    "mid_is_longest": ([_ON_10_LONG if i == 13 else
                        (_MID, ("cnn_0", 2, 0, 0),
                         ("translation_0", 4, 0, 0))[i % 3]
                        for i in range(24)], ()),
    "three_forms_one_stage": ([_OVER, _ON_10] + [_MID] * 7 + [_LONG] * 15,
                              ()),
    "shuffled_servers": ([_SHUFFLED[i % len(_SHUFFLED)] for i in range(24)],
                         ()),
    "void_lanes": ([_UNPLACED[0] if i == 4 else _UNPLACED[1] if i == 10
                    else _MIXED[i % len(_MIXED)] for i in range(24)],
                   (4, 10)),
}


def _channel_lanes(build, lanes):
    """`_BlockBuild.arguments` of ``lanes`` ((model, degree, cluster
    state, scatter[, flip]) each) under one vmap."""
    import jax
    import jax.numpy as jnp

    cfgs = jnp.asarray([build.row(m, d) for m, d, *_ in lanes], jnp.int32)
    states = jnp.stack([build.states[lane[2]] for lane in lanes])
    scatter = jnp.asarray([lane[3] for lane in lanes], jnp.int32)
    flip = jnp.asarray([lane[4] if len(lane) > 4 else 0 for lane in lanes],
                       jnp.int32)
    return jax.vmap(build.arguments)(cfgs, states, scatter, flip)


@pytest.mark.parametrize("case", _CHANNEL_CASES)
def test_channel_table_width_follows_the_servers_ridden(wide_build, case):
    """A cluster wider than a block: every lane's six results are the
    UNBATCHED FLAT path's bits whichever widths of channel table a
    stage's cascade ran through, and each stage ran each trip over the
    narrowest table that holds what every lane still LIVE rode —
    `channel_trips`, what `record_lookahead_trips` reckons on the host
    from the same counts."""
    import jax
    import jax.numpy as jnp

    from ddls_tpu.sim.jax_lookahead import (channel_trips, stage_trips,
                                            stage_widths)

    build, (lanes, skipped) = wide_build, _CHANNEL_CASES[case]
    n_lanes, S = len(lanes), wide_build.et.pads.max_split
    args, blocks, placed = _channel_lanes(build, lanes)
    void = [lane in _UNPLACED for lane in lanes]
    assert (np.asarray(placed) != void).all()
    assert set(np.nonzero(void)[0]) <= set(skipped)
    skip = jnp.zeros(n_lanes, bool).at[jnp.asarray(skipped, int)].set(True)
    want = _flat_per_lane(build, args, blocks, skip, lanes)
    got, ran = _staged(build)(args, blocks, skip)
    _assert_same_bits(got, want, case)

    own, rode = want[5], _rode(args)
    widths = stage_widths(n_lanes, S)
    assert widths == [24, 16, 8]
    ran = np.asarray(ran)                      # [stages, (8, 16, 32)]
    assert ran.sum(axis=1).tolist() == stage_trips(own, widths).tolist()
    assert ran.tolist() == channel_trips(
        own, np.where(own > 0, rode, 0), widths, 32, S).tolist()
    over, mid = rode > S, (rode > S // 2) & (rode <= S)
    assert over.sum() == {"one_wide_live": 1, "wide_skipped": 2,
                          "wide_finished_filler": 1, "all_on_8": 1,
                          "three_forms_one_stage": 1}.get(case, 0)
    assert ((rode[over] >= 17) & (rode[over] <= 20)).all()
    assert (own[list(skipped)] == 0).all()
    at_8, at_16, at_32 = ran.sum(axis=0)
    if case in ("all_narrow", "wide_skipped"):
        # lanes on 10 servers live for 50 trips beside one on 8 for 54
        assert mid.any() and at_32 == 0
        assert (at_8, at_16) == (own.max() - own[mid].max(), own[mid].max())
    if case == "one_wide_live":
        assert ran[0, 2] == own[7] > 0 and at_32 == own[7]
    if case == "wide_finished_filler":
        # the lane on 18 servers finishes first (stage one starts
        # wide), the eight next lanes end the stage together, and it
        # is the sixteenth lane of a stage whose fifteen live lanes
        # ride 8
        w, m, l = own[0], own[1], own[9]
        assert w < m < l and set(own[1:9]) == {m} and set(own[9:]) == {l}
        assert ran.tolist() == [[m - w, 0, w], [l - m, 0, 0], [0, 0, 0]]
    if case == "ragged_rows":
        splits = {tuple(sorted(set(np.asarray(
            build.et.tables["f_split"][build.row(m, d)]).tolist())))
            for m, d, *_ in lanes}
        assert splits == {(4, 6), (4, 8), (1, 2, 6)}
        assert sorted(set(rode.tolist())) == [8, 10]   # wider than degree
        assert at_32 == 0 and at_16 == own[rode == 10].max()
    if case == "all_on_8":
        # the 16- and the 32-wide loop run no trip: the lanes that
        # would need them are skipped
        assert mid.sum() == 1 and (rode[[3, 11]] > S // 2).all()
        assert (at_8, at_16, at_32) == (own.max(), 0, 0)
    if case == "mid_finishes_first":
        # the stage hands over to the 8-wide table when the lane on 12
        # servers ends, and carries it on as a filler that holds no form
        w, m, l = own[0], own[1], own[9]
        assert rode[0] == 12 and w < m < l
        assert ran.tolist() == [[m - w, w, 0], [l - m, 0, 0], [0, 0, 0]]
    if case == "mid_is_longest":
        assert rode[13] == 10 and (own[13] > np.delete(own, 13)).all()
        assert (at_8, at_16, at_32) == (0, own.max(), 0)
        assert (ran[:, 1] > 0).sum() >= 2      # ... in every stage that ran
    if case == "three_forms_one_stage":
        w, m, e, l = own[0], own[1], own[2], own[9]
        assert (rode[0], rode[1]) == (18, 10) and w < m < e < l
        assert ran.tolist() == [[e - m, m - w, w], [l - e, 0, 0], [0, 0, 0]]
    if case == "shuffled_servers":
        # every op of a job on another set of servers, odd ops in the
        # reverse order: no lane's state by server is its state by shard
        worker = np.asarray(args[2]).reshape(n_lanes, -1, S)
        valid = np.asarray(args[1]).reshape(n_lanes, -1, S)
        for on, ok in zip(worker, valid):
            used = sorted(set(on[ok].tolist()))
            at_rank = [(np.asarray([used.index(w) for w in row[v]])
                        == np.arange(v.sum())).all()
                       for row, v in zip(on, ok) if v.any()]
            assert not all(at_rank)
        assert 8 <= rode.min() < rode.max() == S
        assert at_32 == 0 and at_16 > 0 and at_8 > 0
    if case == "void_lanes":
        # a job that did not place holds valid sub-ops nowhere: away,
        # but skipped, so the cluster's form runs no trip for it
        worker = np.asarray(args[2])[[4, 10]]
        assert (worker[np.asarray(args[1])[[4, 10]]] < 0).any()
        assert at_32 == 0 and at_8 + at_16 == own.max() > 0


@pytest.mark.parametrize("case", ["shuffled_servers",
                                  "three_forms_one_stage"])
def test_by_server_forms_are_the_flat_path_under_x64(wide_build, case):
    """``JAX_ENABLE_X64``: the same lanes with every time and score in
    f64 — the move to server coordinates is a select-and-sum of one
    source a target, exact in any float type — through all three forms
    of the cascade: f64 results, the flat path's bits."""
    import jax
    import jax.numpy as jnp

    build, (lanes, skipped) = wide_build, _CHANNEL_CASES[case]
    args, blocks, _ = _channel_lanes(build, lanes)
    skip = jnp.zeros(len(lanes), bool).at[
        jnp.asarray(skipped, int)].set(True)
    with jax.enable_x64(True):
        args = tuple(jnp.asarray(np.asarray(x, np.float64))
                     if x.dtype == jnp.float32 else x for x in args)
        want = _flat_per_lane(build, args, blocks, skip, lanes)
        got, ran = _staged(build)(args, blocks, skip)
        _assert_same_bits(got, want, (case, "x64"))
        assert [np.asarray(x).dtype for x in got[:4]] == [np.float64] * 4
        assert (np.asarray(ran).sum(axis=0) > 0).sum() >= 2


def _whiles_and_conds(staged, *arguments):
    """How many ``while`` and ``cond`` equations ``staged`` traces to."""
    import jax

    text = str(jax.make_jaxpr(staged)(*arguments))
    return text.count(" while["), text.count(" cond[")


def test_channel_widths_read_the_two_shapes_alone(block_build):
    """Half the block side, the block side, the cluster: a cluster no
    wider than a rung drops that rung and the ones above it, and a rung
    under a register's eight sublanes is none. A packed stage is one
    ``while`` a width and never a ``cond``: 16 servers under a block
    side of 16 have two, 8 servers one."""
    import jax.numpy as jnp

    from ddls_tpu.sim.jax_lookahead import (_lane_batched_lookahead,
                                            channel_widths)

    assert channel_widths(32, 16) == (8, 16, 32)
    assert channel_widths(16, 16) == (8, 16)
    assert channel_widths(8, 16) == (8,)
    assert channel_widths(72, 16) == (8, 16, 72)
    assert channel_widths(8, 8) == (8,) and channel_widths(16, 8) == (8, 16)
    lanes = _lanes(block_build, 24)
    args, blocks, _ = _lane_arguments(block_build, lanes)
    skip = jnp.zeros(24, bool)
    staged = _staged(block_build)
    assert _whiles_and_conds(staged, args, blocks, skip) == (3 * 2, 0)
    _, ran = staged(args, blocks, skip)
    assert np.asarray(ran).shape == (3, 2)
    # the same lanes as an 8-server cluster's (traced, not run: the
    # placements are a 16-server cluster's)
    on_8 = _lane_batched_lookahead(8).staged
    assert _whiles_and_conds(
        lambda *a: on_8(*_block_arguments(*a)), args, blocks, skip) == (3, 0)


def test_wide_cluster_cascades_three_loops_a_packed_stage(wide_build):
    """32 servers: each lane-packed stage is THREE ``while``s over one
    state, widest first, and no ``cond``; a stage of 128 lanes or more
    (one job a lane under ``vmap``) is one loop at the cluster's
    width."""
    import jax
    import jax.numpy as jnp

    def loops(n_lanes):
        lanes = [_MIXED[i % len(_MIXED)] for i in range(n_lanes)]
        cfgs = jnp.asarray([wide_build.row(m, d) for m, d, _, _ in lanes],
                           jnp.int32)
        states = jnp.stack([wide_build.states[s] for _, _, s, _ in lanes])
        args, blocks, _ = jax.vmap(wide_build.arguments)(cfgs, states)
        skip = jnp.zeros(n_lanes, bool)
        staged = _staged(wide_build)
        return (*_whiles_and_conds(staged, args, blocks, skip),
                np.asarray(staged(args, blocks, skip)[1]))

    whiles, conds, ran = loops(8)
    assert (whiles, conds) == (3, 0) and ran.shape == (1, 3)
    # the lanes on 10 servers end before the longest on 8
    assert (ran > 0).tolist() == [[True, True, False]]
    whiles, conds, ran = loops(24)
    assert (whiles, conds) == (9, 0) and ran.shape == (3, 3)
    whiles, conds, ran = loops(160)         # 160 -> 80 -> 40 -> 24 -> 16 -> 8
    assert (whiles, conds) == (1 + 5 * 3, 0)
    assert ran[0].tolist() == [0, 0, ran[0, 2]] and not ran[1:, 2].any()


def test_dense_servers_is_a_bijection_on_the_servers_a_lane_uses():
    """The renumbering: per lane, ranks 0..rode-1 one to one on the
    servers its valid sub-ops sit on, in server order; -1 stays -1; a
    VALID unplaced sub-op counts as on server 0 (what the tick's clip
    makes of it) and an invalid one counts for nothing."""
    import jax.numpy as jnp

    from ddls_tpu.sim.jax_lookahead import dense_servers

    rng = np.random.default_rng(5)
    L, No, S, W = 3, 7, 4, 32
    worker = rng.integers(0, W, (L, No, S)).astype(np.int32)
    worker[0] = rng.choice([3, 9, 30, 31], (No, S))     # no server 0
    worker[1] = rng.permutation(np.arange(No * S) % 20 + 5).reshape(No, S)
    valid = rng.random((L, No, S)) < 0.8
    worker[~valid] = -1
    worker[2, 0, 0], valid[2, 0, 0] = -1, True          # valid, unplaced
    worker[2][worker[2] == 0] = 1

    def packed(x):      # [L, No, S] -> [No, (l, k)]
        return jnp.asarray(x.transpose(1, 0, 2).reshape(No, L * S))

    dense, rode = dense_servers(packed(worker), packed(valid), L, W)
    dense = np.asarray(dense).reshape(No, L, S).transpose(1, 0, 2)
    for lane in range(L):
        used = sorted(set(np.clip(worker[lane][valid[lane]], 0, None)
                          .tolist()))
        assert int(rode[lane]) == len(used)
        rank = {server: i for i, server in enumerate(used)}
        for w, d, v in zip(worker[lane].ravel(), dense[lane].ravel(),
                           valid[lane].ravel()):
            assert d == (-1 if w < 0 else rank[w]) or not v
        assert (dense[lane][worker[lane] < 0] == -1).all()
    assert int(rode[0]) == 4 and int(rode[1]) > 16
    # server 0 counts for lane 2 through its valid unplaced sub-op alone
    assert 0 not in set(worker[2][valid[2]].tolist())
    assert int(rode[2]) == len(set(worker[2][valid[2]].tolist()))


def _packed(x, n_ops, side):
    """[L, (o, k)] -> [No, (l, k)], as the lane-packed stages carry it."""
    import jax.numpy as jnp

    L = x.shape[0]
    return jnp.asarray(x).reshape(L, n_ops, side).transpose(1, 0, 2).reshape(
        n_ops, L * side)


@pytest.mark.parametrize("cluster", ["block_build", "wide_build"])
def test_a_placed_jobs_ops_sit_on_distinct_servers(request, cluster):
    """The precondition of the by-server layout, where it is PRODUCED:
    over every (row, cluster state) of the small presets,
    `jax_allocate_job` puts the valid sub-ops of every op of a job it
    PLACED on distinct servers and none nowhere, so `server_slots` finds
    the lane at home — at its servers' ranks, riding what it rode —
    unless it rides more servers than a block has shards; a job it did
    not place has valid sub-ops nowhere (the env runs no lookahead for
    it: ``void``, tests/test_program_tracing.py) and is away."""
    import jax
    import jax.numpy as jnp

    from ddls_tpu.sim.jax_lookahead import server_slots

    build = request.getfixturevalue(cluster)
    pads = build.et.pads
    S, No = pads.max_split, pads.n_ops // pads.max_split
    rows = [(build.row(m, d), s) for m, d in _BLOCK_ROWS
            for s in range(len(build.states))]
    args, _, placed = _lane_arguments(build, rows)
    placed = np.asarray(placed)
    valid = np.asarray(args[1]).reshape(len(rows), No, S)
    worker = np.asarray(args[2]).reshape(len(rows), No, S)
    assert placed.any() and not placed.all()
    for lane in np.nonzero(placed)[0]:
        for on, ok in zip(worker[lane], valid[lane]):
            assert (on[ok] >= 0).all()
            assert len(set(on[ok].tolist())) == ok.sum()
    for lane in np.nonzero(~placed)[0]:
        assert (worker[lane][valid[lane]] < 0).any()
    slot, rides = jax.jit(lambda w, v: server_slots(
        w, v, len(rows), build.et.n_srv))(_packed(args[2], No, S),
                                          _packed(args[1], No, S))
    slot = np.asarray(slot).reshape(No, len(rows), S).transpose(1, 0, 2)
    rode, rides = _rode(args), np.asarray(rides)
    home = placed & (rode <= S)
    assert home.sum() > len(rows) // 3
    assert (rides[home] == rode[home]).all() and (rides[~home] > S).all()
    for lane in range(len(rows)):
        if not home[lane]:
            assert (slot[lane] == np.arange(S)).all()      # where it was
            continue
        used = sorted(set(worker[lane][valid[lane]].tolist()))
        for on, ok, to in zip(worker[lane], valid[lane], slot[lane]):
            assert [used[x] for x in to[ok]] == on[ok].tolist()
            assert (to[~ok] == -1).all()


def test_a_lane_that_is_not_one_to_one_holds_the_clusters_form(wide_build):
    """The guard: a lane whose op has two valid sub-ops on ONE server (a
    placement no allocator makes) or a valid sub-op nowhere (a job that
    did not place, run all the same) rides few servers, yet by server
    two sub-ops would share a slot — `server_slots` finds both AWAY, the
    stage ticks over the cluster's table for as long as either is live,
    and every lane's six results are the flat path's bits."""
    import jax
    import jax.numpy as jnp

    from ddls_tpu.sim.jax_lookahead import (channel_widths, server_slots,
                                            stage_trips, stage_widths)

    build = wide_build
    pads = build.et.pads
    S, No = pads.max_split, pads.n_ops // pads.max_split
    lanes = [_MIXED[i % len(_MIXED)] for i in range(24)]
    lanes[2], lanes[5] = ("translation_0", 4, 0, 0), _UNPLACED[0]
    cfgs = jnp.asarray([build.row(m, d) for m, d, *_ in lanes], jnp.int32)
    states = jnp.stack([build.states[lane[2]] for lane in lanes])
    none = jnp.zeros(24, jnp.int32)
    args, blocks, placed = jax.vmap(build.arguments)(
        cfgs, states, none, none, none.at[2].set(1))
    assert np.asarray(placed).tolist() == [i != 5 for i in range(24)]
    worker = np.asarray(args[2]).reshape(24, No, S)
    assert worker[2, 0, 0] == worker[2, 0, 1] >= 0          # the collision
    _, rides = server_slots(_packed(args[2], No, S), _packed(args[1], No, S),
                            24, build.et.n_srv)
    rode, rides = _rode(args), np.asarray(rides)
    assert rode[2] == 4 and (rode <= S).all()        # few servers
    away = np.arange(24) % 24 == 2
    away[5] = True
    assert (rides[away] == S + 1).all()
    assert (rides[~away] == rode[~away]).all()

    skip = jnp.zeros(24, bool)
    want = _flat_per_lane(build, args, blocks, skip, lanes)
    got, ran = _staged(build)(args, blocks, skip)
    _assert_same_bits(got, want, "guard")
    own, ran = want[5], np.asarray(ran)
    assert len(channel_widths(build.et.n_srv, S)) == ran.shape[1] == 3
    assert ran.sum(axis=1).tolist() == stage_trips(
        own, stage_widths(24, S)).tolist()
    # the cluster's form for as long as an away lane is live, then none
    assert 0 < own[away].max() < own.max()
    assert ran[:, -1].sum() == own[away].max()


def test_rode_is_the_hosts_count_on_a_mounted_job(dataset_dir):
    """`dense_servers`' ``rode`` of a mounted job's op -> server map
    equals the host's ``len(set(job_op_to_worker.values()))``."""
    import jax.numpy as jnp

    from ddls_tpu.sim.jax_lookahead import dense_servers

    env = _make_env(dataset_dir, max_partitions=8)
    cluster, seen = env.cluster, []
    workers = sorted(cluster.topology.workers)
    orig = cluster._run_lookahead

    def spy(job):
        seen.append(list(cluster.job_op_to_worker[
            job.details["job_idx"]].values()))
        return orig(job)

    cluster._run_lookahead = spy
    obs, rng = env.reset(seed=0), np.random.RandomState(1)
    while len(seen) < 6:
        obs, _, done, _ = env.step(int(rng.choice(np.nonzero(
            np.asarray(obs["action_mask"]))[0])))
        assert not done
    S = 8
    for on in seen:
        servers = [workers.index(w) for w in on]
        worker = np.asarray(servers + [-1] * (-len(servers) % S),
                            np.int32).reshape(-1, S)
        _, rode = dense_servers(jnp.asarray(worker),
                                jnp.asarray(worker >= 0), 1, len(workers))
        assert int(rode[0]) == len(set(on))
    assert len({len(set(on)) for on in seen}) > 1


def _one_loop_program(num_workers):
    """The lane-packed loop as it ran before it ran in stages: every
    lane in ONE loop to the longest lane's last trip."""
    from ddls_tpu.sim import jax_lookahead as jl

    def run(op_remaining, op_valid, op_worker, op_score, num_parents,
            dep_remaining, dep_valid, dep_mutual, dep_is_flow, dep_score,
            blocks, skip):
        (L, N), E, B = op_remaining.shape, dep_remaining.shape[1], \
            blocks.src.shape[1]
        S = jl._block_side(E, B)

        def ops(x):
            return x.reshape(L, N // S, S).transpose(1, 0, 2).reshape(
                N // S, L * S)

        def deps(x):
            return x.reshape(L, B, S, S).transpose(1, 2, 0, 3).reshape(
                B, S, L * S)

        op_worker, op_valid = ops(op_worker), ops(op_valid)
        blocks = jl.DepBlocks(blocks.src.T, blocks.dst.T)
        lay, = jl._packed_layouts(op_worker, blocks, L, (num_workers,),
                                  jl.endpoint_onehots(blocks, N // S))
        return jl._tick_loop(
            lay, ops(op_remaining), op_valid, ops(op_score),
            ops(num_parents), deps(dep_remaining), deps(dep_valid),
            deps(dep_mutual), deps(dep_is_flow), deps(dep_score), skip,
            N + E + 4)[0]

    return run


@pytest.mark.parametrize("with_skip", [False, True])
@pytest.mark.parametrize("n_lanes", [1, 8])
def test_one_register_of_lanes_traces_the_one_loop_program(
        block_build, n_lanes, with_skip):
    """Lanes that fit one vector register run no stages, and a cluster
    the first rung of the channel table holds no cascade: the traced
    program is the one loop's, text for text once the trip counts
    `run.staged` reports beside the results are dropped as dead code
    (the unbatched call — the episode kernel, the fidelity replay — is
    that loop at one lane). Traced as an 8-server cluster's, not run:
    the placements are a 16-server cluster's."""
    import jax
    import jax.numpy as jnp
    from jax._src.interpreters import partial_eval as pe

    from ddls_tpu.sim.jax_lookahead import (_lane_batched_lookahead,
                                            channel_widths, stage_widths)

    def live_text(fn, *args):
        traced = jax.make_jaxpr(fn)(*args)
        jaxpr, _ = pe.dce_jaxpr(traced.jaxpr, [True] * len(traced.out_avals))
        return str(jaxpr)

    n_srv, S = 8, block_build.et.pads.max_split
    assert stage_widths(n_lanes, S) == [n_lanes]
    assert channel_widths(n_srv, S) == (n_srv,)
    args, blocks, _ = _lane_arguments(block_build,
                                      _lanes(block_build, n_lanes))
    args = _block_arguments(
        args, blocks, jnp.arange(n_lanes) % 3 == 1 if with_skip else None)
    staged = _lane_batched_lookahead(n_srv).staged
    assert live_text(lambda *a: staged(*a)[0], *args) == \
        live_text(_one_loop_program(n_srv), *args)


def test_block_path_vmapped_with_unbatched_tables(block_build):
    """One row on three cluster states: the row's tables (and its
    ``blocks``) reach the vmap UNBATCHED, what placement and pricing
    made of them batched; the packing rule broadcasts the former."""
    import jax
    import jax.numpy as jnp

    cfg = block_build.row("translation_0", 12)
    states = block_build.states + [jnp.arange(block_build.et.n_srv) % 8 != 3]
    per_state = [block_build.arguments(cfg, state)[0] for state in states]
    batched = [False, False, True, True, False, True, False, False, False,
               False, True, True, True]
    args = tuple(jnp.stack([a[k] for a in per_state]) if on else
                 per_state[0][k] for k, on in enumerate(batched))
    for a in per_state[1:]:
        assert all((a[k] == per_state[0][k]).all()
                   for k, on in enumerate(batched) if not on)
    _, blocks, _ = block_build.arguments(cfg, states[0])
    got = jax.jit(jax.vmap(
        block_build.block_fn,
        in_axes=(tuple(0 if on else None for on in batched), None)))(
            args, blocks)
    want = [np.stack([np.asarray(block_build.flat(a, blocks)[k])
                      for a in per_state]) for k in range(6)]
    _assert_same_bits(got, want, "unbatched tables")
    assert len(set(want[5].tolist())) > 1


def _sub_jaxprs(eqn):
    """(param name, jaxpr) of every jaxpr an equation carries (closed or
    open, alone or in a tuple as ``cond``'s branches are)."""
    for name, value in eqn.params.items():
        for sub in (value if isinstance(value, (tuple, list)) else (value,)):
            inner = getattr(sub, "jaxpr", sub)
            if hasattr(inner, "eqns"):
                yield name, inner


def _while_bodies(jaxpr):
    """Every ``while`` body jaxpr reachable from ``jaxpr``."""
    for eqn in jaxpr.eqns:
        for name, inner in _sub_jaxprs(eqn):
            if eqn.primitive.name == "while" and name == "body_jaxpr":
                yield inner
            yield from _while_bodies(inner)


def _per_dep_indexing(jaxpr, n_deps):
    """Names of the gather/scatter equations in ``jaxpr`` (nested calls
    included) that touch >= ``n_deps`` elements: an operand or an index
    array that large is one address computation per dep."""
    found = []
    for eqn in jaxpr.eqns:
        if eqn.primitive.name == "gather" or \
                eqn.primitive.name.startswith("scatter"):
            sizes = [int(np.prod(v.aval.shape)) for v in eqn.invars
                     if hasattr(v.aval, "shape")]
            if max(sizes) >= n_deps:
                found.append(eqn.primitive.name)
        for _, inner in _sub_jaxprs(eqn):
            found += _per_dep_indexing(inner, n_deps)
    return found


def _lookahead_bodies(closed_jaxpr, dep_state, forms=1):
    """The lookahead's tick bodies: the ``while``s whose carry holds
    the per-dep remaining times and done flags (f32 and bool of shape
    ``dep_state``) — one a width of the channel table (``forms``) where
    the state is lane-packed."""
    bodies = [b for b in _while_bodies(closed_jaxpr.jaxpr)
              if sum(v.aval.shape == dep_state for v in b.invars) >= 2]
    assert len(bodies) == forms, len(bodies)
    return bodies


def _lookahead_body(closed_jaxpr, dep_state):
    return _lookahead_bodies(closed_jaxpr, dep_state)[0]


def _cascade_bodies(closed_jaxpr, n_blocks, side, n_lanes, servers):
    """The tick bodies of one lane-packed stage's cascade, widest width
    first (`channel_widths`): those over the stage's whole state [B, S,
    L*S] — the cluster's table, then the state by server —, and the
    first rung's over half the rows [B, S/2, L*S] where there is
    one: ``(whole, half)``."""
    from ddls_tpu.sim.jax_lookahead import channel_widths

    channels = channel_widths(servers, side)
    halved = len(channels) > 1 and channels[0] < side
    whole = _lookahead_bodies(
        closed_jaxpr, (n_blocks, side, side * n_lanes),
        forms=len(channels) - halved)
    half = _lookahead_bodies(
        closed_jaxpr, (n_blocks, side // 2, side * n_lanes),
        forms=1) if halved else []
    return whole, half


def test_block_path_nested_vmaps_pack_as_one_loop(block_build):
    """`price_all`'s shape — a vmap over the cfg axis, cluster state
    unbatched — inside a vmap over lanes (cluster states): ONE loop at
    lanes x cfgs packed lanes, each (lane, cfg) result the unbatched
    call's bits."""
    import jax
    import jax.numpy as jnp

    cfgs = jnp.asarray([block_build.row("translation_0", d)
                        for d in (2, 8, 16)], jnp.int32)
    states = jnp.stack(block_build.states)

    def one(cfg, state):
        args, blocks, _ = block_build.arguments(cfg, state)
        return block_build.block_fn(args, blocks)

    nested = jax.vmap(jax.vmap(one, in_axes=(0, None)), in_axes=(None, 0))
    got = jax.jit(nested)(cfgs, states)
    want = [[block_build.flat(*block_build.arguments(cfg, state)[:2])
             for cfg in cfgs] for state in states]
    want = [np.asarray([[np.asarray(r[k]) for r in row] for row in want])
            for k in range(6)]
    _assert_same_bits(got, want, "nested")
    pads = block_build.et.pads
    S, L = pads.max_split, len(cfgs) * len(states)
    whole, half = _cascade_bodies(jax.make_jaxpr(nested)(cfgs, states),
                                  pads.n_blocks, S, L, block_build.et.n_srv)
    assert (len(whole), len(half)) == (1, 1)


#: equations of the flat path's tick body as jax 0.9 traces it: the
#: ``blocks=None`` loop is not the packed form's to change
_FLAT_BODY_EQNS = 110


def test_env_lookahead_body_indexes_no_dep(block_build):
    """The engagement pin: the tick body the in-kernel env traces (from
    `make_episode_fn`: the lane-packed loop at one lane) holds NO
    gather/scatter of ``pads.n_deps`` elements, and under a 32-lane
    vmap its dep state is [B, S, 32 x S] — (lane, shard) on the minor
    axis — and still holds none, as the 128-lane loop does, whose lanes
    alone fill a register and whose state stays one job's a lane; the
    same walk over the flat path,
    whose loop is the one it always was, finds the four that were 95 %
    of the fused epoch (source gather, channel scatter-max and
    read-back, parent-count scatter-add)."""
    import jax
    import jax.numpy as jnp

    from ddls_tpu.sim import jax_env as je
    from ddls_tpu.telemetry import startup

    et = block_build.et
    M, S, B = et.pads.n_deps, et.pads.max_split, et.pads.n_blocks
    bank = je.build_job_bank(et, [
        {"model": m, "num_training_steps": 3, "sla_frac": 1.0,
         "time_arrived": 100.0 * i} for i, m in enumerate(_BLOCK_MODELS)])
    bank = {k: jnp.asarray(v) for k, v in bank.items()}
    episode = je.make_episode_fn(et)
    traced = jax.make_jaxpr(episode)(bank, jnp.asarray([16, 4], jnp.int32))
    whole, half = _cascade_bodies(traced, B, S, 1, et.n_srv)
    for body in whole + half:
        assert _per_dep_indexing(body, M) == []

    args, blocks, _ = _lane_arguments(block_build, _lanes(block_build, 32))
    lanes = jax.make_jaxpr(jax.vmap(block_build.block_fn))(args, blocks)
    whole, half = _cascade_bodies(lanes, B, S, 32, et.n_srv)
    for body in whole + half:
        assert _per_dep_indexing(body, M) == []
    assert startup.gauges()["sim.lookahead.minor_used"] == 32 * S
    args, blocks, _ = _lane_arguments(block_build, _lanes(block_build, 128))
    lanes = jax.make_jaxpr(jax.vmap(block_build.block_fn))(args, blocks)
    assert _per_dep_indexing(_lookahead_body(lanes, (128, M)), M) == []
    assert startup.gauges()["sim.lookahead.minor_used"] == 128
    assert startup.gauges()["sim.lookahead.endpoint_onehot_elems"] == \
        B * (et.pads.n_ops // S) * 128 * S

    args, blocks, _ = block_build.arguments(0, block_build.states[0])
    flat = _lookahead_body(jax.make_jaxpr(block_build.flat_fn)(args, blocks),
                           (M,))
    found = sorted(n.replace("_", "-") for n in _per_dep_indexing(flat, M))
    assert found == ["gather", "gather", "scatter-add", "scatter-max"]
    N = et.pads.n_ops
    assert [(v.aval.shape, v.aval.dtype.name) for v in flat.invars[-11:]] == [
        ((N,), "float32"), ((M,), "float32"), ((N,), "bool"), ((M,), "bool"),
        ((N,), "int32"), ((), "float32"), ((), "float32"), ((), "float32"),
        ((), "float32"), ((), "int32"), ((), "bool")]
    assert len(flat.eqns) == _FLAT_BODY_EQNS


def _equation_shapes(jaxpr):
    """(primitive name, shapes of its operands and results) of every
    equation in ``jaxpr``, nested calls included."""
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name, [tuple(v.aval.shape)
                                   for v in (*eqn.invars, *eqn.outvars)
                                   if hasattr(v.aval, "shape")]
        for _, inner in _sub_jaxprs(eqn):
            yield from _equation_shapes(inner)


@pytest.mark.parametrize("n_lanes", [1, 8, 32])
def test_packed_body_reaches_endpoints_by_contraction(block_build, n_lanes):
    """The engagement pin of the endpoint form: the lane-packed tick
    body (the unbatched call's one lane; 8 and 32 lanes under `vmap`)
    reaches a block's source and destination op through exactly TWO
    ``dot_general``s, a lane a batch, over the [L, B, No] 0/1 tables —
    `src_done` and `count_parents` — and holds NO equation over blocks
    x ops x (lane, shard), the B * No * L * S elements either cost as a
    compare-and-reduce pass; the gauge says the same."""
    import jax

    from ddls_tpu.sim.jax_lookahead import ENDPOINT_GAUGE
    from ddls_tpu.telemetry import startup

    pads = block_build.et.pads
    B, S, No = pads.n_blocks, pads.max_split, pads.n_ops // pads.max_split
    if n_lanes == 1:
        args, blocks, _ = block_build.arguments(0, block_build.states[0])
        traced = jax.make_jaxpr(block_build.block_fn)(args, blocks)
    else:
        args, blocks, _ = _lane_arguments(block_build,
                                          _lanes(block_build, n_lanes))
        traced = jax.make_jaxpr(jax.vmap(block_build.block_fn))(args, blocks)
    whole, half = _cascade_bodies(traced, B, S, n_lanes,
                                  block_build.et.n_srv)
    assert (len(whole), len(half)) == (1, 1)
    for body in whole + half:
        shapes = list(_equation_shapes(body))
        dots = [ops for name, ops in shapes if name == "dot_general"]
        assert [ops[0] for ops in dots] == [(n_lanes, B, No)] * 2
        assert sorted(ops[-1] for ops in dots) == sorted(
            [(n_lanes, B, S), (n_lanes, No, S)])
        passes = {(B, No, n_lanes * S), (No, B, n_lanes * S)}
        assert not [name for name, ops in shapes if passes & set(ops)]
    if n_lanes > 1:
        assert startup.gauges()[ENDPOINT_GAUGE] == 0


@pytest.mark.parametrize("n_lanes", [8, 32])
def test_by_server_body_compares_with_no_worker_iota(wide_build, n_lanes):
    """The engagement pin of the by-server form: under a cluster wider
    than a block the first stage's cascade is the cluster's table form
    and then the form under it, whose traced tick body holds NO
    equation over more elements than the dep state itself — the
    table's one-hots are B * S * W * L * S and B * W * W * L * S — and
    no compare against a worker iota: a slot's worker is its position.
    The cluster's form beside it holds both, and the gauge reads 0."""
    import jax

    from ddls_tpu.sim.jax_lookahead import ONEHOT_GAUGE, channel_widths
    from ddls_tpu.telemetry import startup

    pads = wide_build.et.pads
    B, S, W = pads.n_blocks, pads.max_split, wide_build.et.n_srv
    channels = channel_widths(W, S)
    args, blocks, _ = _channel_lanes(
        wide_build, [_MIXED[i % len(_MIXED)] for i in range(n_lanes)])
    traced = jax.make_jaxpr(jax.vmap(wide_build.block_fn))(args, blocks)
    (table, *by_server), half = _cascade_bodies(traced, B, S, n_lanes, W)
    assert len(by_server) == len(half) == 1 and channels == (S // 2, S, W)
    by_server += half
    state = B * S * S * n_lanes

    def over_state(body):
        return [(name, shape) for name, ops in _equation_shapes(body)
                for shape in ops if int(np.prod(shape)) > state]

    def iota_compares(body):
        """``eq`` into more than the op state — a [No, L*S] compare is
        the selected op's ``scores == best`` —: a one-hot over the
        worker iota."""
        return [ops[-1] for name, ops in _equation_shapes(body)
                if name == "eq" and int(np.prod(ops[-1]))
                > pads.n_ops * n_lanes]

    assert (B, S, W, S * n_lanes) in [shape for _, shape in over_state(table)]
    assert set(iota_compares(table)) == {
        (B, S, W, S * n_lanes), (B, 1, W, S * n_lanes),
        (W, pads.n_ops // S, S * n_lanes)}
    assert by_server
    for body in by_server:
        assert over_state(body) == [] and iota_compares(body) == []
        dots = [ops for name, ops in _equation_shapes(body)
                if name == "dot_general"]
        assert len(dots) == 2                  # the endpoints, as before
    assert startup.gauges()[ONEHOT_GAUGE] == 0


#: (lanes, blocks, block side, servers) -> the elements a trip of the
#: first stage compares with a worker iota in `nominate`, in its form
#: over a table no wider than a block's side
_ONEHOT_ELEMS = [
    (16, 1162, 16, 32, 0), (48, 456, 16, 32, 0), (80, 222, 16, 32, 0),
    (64, 52, 16, 32, 0), (127, 52, 16, 32, 0),
    (128, 52, 16, 32, 2 * 52 * 16 * 32 * 128 * (16 + 32)),
    (320, 52, 16, 32, 2 * 52 * 16 * 32 * 320 * (16 + 32)),
    (32, 17, 16, 16, 0), (32, 17, 8, 16, 0),
    (8, 20, 8, 8, 2 * 20 * 8 * 8 * 8 * (8 + 8)),
    (4, 20, 16, 8, 2 * 20 * 16 * 8 * 4 * (16 + 8)),
    (8, 20, 4, 8, 2 * 20 * 4 * 8 * 8 * (4 + 8))]


@pytest.mark.parametrize("n_lanes,n_blocks,side,servers,elems", _ONEHOT_ELEMS,
                         ids=[f"{c[0]}x{c[1]}on{c[3]}" for c in _ONEHOT_ELEMS])
def test_channel_onehot_elems(n_lanes, n_blocks, side, servers, elems):
    """The gauge's table: a lane-packed first stage whose cascade has a
    width under the cluster's finds a dep's channel by its position
    there (0); the one-job-a-lane form from 128 lanes on reduces onto
    and reads back from the cluster's table (2 * B*S*W*L*S + 2 *
    B*W*W*L*S), and so does a cluster of one width."""
    from ddls_tpu.sim.jax_lookahead import (REGISTER_WIDTH,
                                            channel_onehot_elems,
                                            channel_widths)

    assert channel_onehot_elems(n_lanes, n_blocks, side, servers) == elems
    assert (elems == 0) == (n_lanes < REGISTER_WIDTH
                            and len(channel_widths(servers, side)) > 1)


#: (lanes, original ops, blocks, block side) -> elements a trip of the
#: first stage compares with the op-row iota in each endpoint primitive
_ENDPOINT_ELEMS = [
    (1, 30, 52, 16, 0), (16, 570, 1162, 16, 0), (48, 218, 456, 16, 0),
    (80, 114, 222, 16, 0), (127, 30, 52, 16, 0),
    (128, 30, 52, 16, 3_194_880), (320, 30, 52, 16, 7_987_200)]


@pytest.mark.parametrize("n_lanes,n_ops,n_blocks,side,elems", _ENDPOINT_ELEMS,
                         ids=[f"{c[0]}x{c[1]}" for c in _ENDPOINT_ELEMS])
def test_endpoint_onehot_elems(n_lanes, n_ops, n_blocks, side, elems):
    """The gauge's table: a lane-packed first stage (under 128 lanes)
    contracts, whatever the ops and blocks — there is no shape rule —
    and the one-job-a-lane form from 128 lanes on compares blocks x ops
    x shards a lane."""
    from ddls_tpu.sim.jax_lookahead import (REGISTER_WIDTH,
                                            endpoint_onehot_elems)

    assert endpoint_onehot_elems(n_lanes, n_ops, n_blocks, side) == elems
    assert (elems == 0) == (n_lanes < REGISTER_WIDTH)


def _converging_blocks(n_lanes, n_ops, n_blocks, into):
    """Hand-built [L, B] block tables in which lane l's first ``into +
    l`` blocks all END at original op ``l`` (their sources the ops
    after it, in turn), the rest chain op to op, and the last two are
    padding."""
    src = np.full((n_lanes, n_blocks), -1, np.int32)
    dst = np.full((n_lanes, n_blocks), -1, np.int32)
    for lane in range(n_lanes):
        fan = into + lane
        others = [o for o in range(n_ops) if o != lane]
        for b in range(n_blocks - 2):
            if b < fan:
                src[lane, b], dst[lane, b] = others[b % len(others)], lane
            else:
                src[lane, b] = (b + lane) % n_ops
                dst[lane, b] = (b + lane + 1) % n_ops
    return src, dst


@pytest.mark.parametrize("x64", [False, True], ids=["f32", "x64"])
@pytest.mark.parametrize("into", [17, 40])
def test_endpoint_contractions_are_exact_past_bf16(into, x64):
    """Past bfloat16's whole numbers: ``into`` (>= 17) non-mutual
    blocks of side 16 complete into ONE destination op in one trip, so
    a sub-op's parent count rises by ``into`` x 16 >= 272 at once. The
    lane-packed layout's `count_parents` and `src_done`, called as the
    tick calls them, equal `flat_dep_ops`' scatter-add and gather bit
    for bit and dtype for dtype, on lanes with DIFFERENT tables, with
    and without ``JAX_ENABLE_X64``."""
    import jax
    import jax.numpy as jnp

    from flat_lookahead import flat_dep_ops
    from flat_pricing import block_endpoint_slots

    from ddls_tpu.sim.jax_lookahead import DepBlocks, _packed_layouts

    L, No, S, W = 3, 24, 16, 8
    B = into + L + 6
    src, dst = _converging_blocks(L, No, B, into)
    rng = np.random.default_rng(into)
    op_done = rng.random((L, No * S)) < 0.5
    parent_done = rng.integers(0, 5, (L, No * S)).astype(np.int32)
    inc = np.ones((L, B * S * S), np.int32)      # every dep, this trip
    inc[:, -2 * S * S:] = 0                       # but the padding's
    inc[1, : S * S] = rng.integers(0, 2, S * S)   # and a ragged block

    def ops(x):      # [L, (o, k)] -> [No, (l, k)]
        return jnp.asarray(x.reshape(L, No, S).transpose(1, 0, 2).reshape(
            No, L * S))

    def deps(x):     # [L, (b, i, j)] -> [B, S_i, (l, j)]
        return jnp.asarray(x.reshape(L, B, S, S).transpose(1, 2, 0, 3)
                           .reshape(B, S, L * S))

    with jax.enable_x64(x64):
        lay, = _packed_layouts(
            jnp.asarray(rng.integers(0, W, (No, L * S)), jnp.int32),
            DepBlocks(jnp.asarray(src.T), jnp.asarray(dst.T)), L, (W,))
        got_parents = np.asarray(jax.jit(lay.count_parents)(
            ops(parent_done), deps(inc)))
        got_done = np.asarray(jax.jit(lay.src_done)(ops(op_done)))
        assert got_parents.dtype == np.int32 and got_done.dtype == bool
        for lane in range(L):
            dep_src, dep_dst = block_endpoint_slots(
                jnp.asarray(src[lane]), jnp.asarray(dst[lane]), S)
            flat_done, flat_parents, _ = flat_dep_ops(
                jnp.clip(dep_src, 0), jnp.clip(dep_dst, 0), None, 1)
            valid = np.repeat(src[lane] >= 0, S * S)
            want = np.asarray(flat_parents(
                jnp.asarray(parent_done[lane]),
                jnp.asarray(np.where(valid, inc[lane], 0))))
            mine = got_parents.reshape(No, L, S)[:, lane].reshape(-1)
            assert mine.dtype == want.dtype and (mine == want).all()
            want = np.asarray(flat_done(jnp.asarray(op_done[lane])))
            mine = got_done.reshape(B, S, L, S)[:, :, lane].reshape(-1)
            assert (mine[valid] == want[valid]).all()
            assert not mine[~valid].any()      # a padded block: no source
        rose = (got_parents - np.asarray(ops(parent_done))).reshape(No, L, S)
        ending = (dst[:, :, None] == np.arange(No)).sum(axis=1)    # [L, No]
        assert (rose[2, 2] == ending[2, 2] * S).all()      # all-ones lane
        assert rose.max() == ending.max() * S >= (into + L - 1) * S > 272


def test_block_path_is_flat_path_at_the_benchmark_pads(tmp_path):
    """The benchmark's own pads (480 op slots x 52 blocks x 16 x 16 =
    13,312 dep slots: the shipped env_dev dataset on RAMP 4x4x2 at
    degree 16) and the row whose lookahead is the fused cells' lockstep
    maximum: cnn_1 at degree 8 on an empty cluster runs 152 trips."""
    import shutil

    from ddls_tpu.graphs.synthetic import generate_pipedream_txt_files

    full, only = tmp_path / "all", tmp_path / "cnn_1"
    full.mkdir(), only.mkdir()
    generate_pipedream_txt_files(str(full), n_cnn=3, n_translation=2,
                                 seed=0, min_ops=8, max_ops=16)
    shutil.copy(full / "cnn_1.txt", only / "cnn_1.txt")
    build = _BlockBuild(_ramp_env(str(only), (4, 4, 2), 16))
    pads = build.et.pads
    assert (pads.n_ops, pads.n_deps, pads.n_deps_used) == (480, 13312, 13072)
    args, blocks, placed = build.arguments(build.row("cnn_1", 8),
                                           build.states[0])
    want = build.flat(args, blocks)
    _assert_same_bits(build.block(args, blocks), want, "cnn_1-8")
    assert bool(placed) and bool(want[4]) and int(want[5]) == 152
