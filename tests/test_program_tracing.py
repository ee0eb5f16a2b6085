"""What the fused training path says about itself from INSIDE the
program (ISSUE 23): the ``jax.named_scope`` names on the lowered epoch
program, the per-lane lookahead trip trace and the ``sim.lookahead.*``
counters reduced from it, and the always-on ``startup.*`` spans of
``build_run``."""
import json
import os
import sys

import numpy as np
import pytest

import test_fused
import test_jax_lookahead
import test_jax_memo
from ddls_tpu import telemetry
from ddls_tpu.sim.jax_env import config_rows
from ddls_tpu.telemetry import scopes, startup

pytestmark = pytest.mark.telemetry

fused_dataset = test_fused.fused_dataset
memo_env = test_jax_memo.memo_env
block_build = test_jax_lookahead.block_build
wide_build = test_jax_lookahead.wide_build

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _clean_registries():
    def clean():
        telemetry.disable()
        telemetry.reset()
        startup.registry().reset()

    clean()
    yield
    clean()


# ------------------------------------------------------------- scopes
def _compiled_text(dataset):
    loop = test_fused._make_fused_loop(dataset)
    try:
        return loop.fused.lower(loop.state).compile().as_text()
    finally:
        loop.close()


@pytest.fixture(scope="module")
def program_text(fused_dataset):
    """The compiled tiny fused epoch program (8 lanes), as text."""
    return _compiled_text(fused_dataset)


@pytest.fixture(scope="module")
def program_paths(program_text):
    import re

    paths = set(re.findall(r'op_name="([^"]+)"', program_text))
    assert paths, "no op_name metadata in the compiled program"
    return paths


def _holds(scope):
    """Matches a path that has ``scope`` as a segment, bare or wrapped
    by vmap/jvp/transpose: the benchmark's own rule."""
    from benchmarks.reduce.op_scopes import scope_pattern

    return scope_pattern([scope]).search


def test_every_scope_names_ops_of_the_fused_epoch_program(program_paths):
    """Each name in ``telemetry/scopes.py`` is a path segment — bare or
    wrapped by vmap/jvp/transpose — of some operation's ``op_name`` in
    the lowered fused epoch program (the path a TPU profile carries per
    instruction)."""
    for scope in scopes.ALL:
        assert any(map(_holds(scope), program_paths)), scope
    assert len(set(scopes.ALL)) == len(scopes.ALL)


def _tree_names():
    return sorted({c for cs in scopes.TREE.values() for c in cs}
                  | (set(scopes.TREE) - {scopes.ROOT}))


@pytest.mark.parametrize("scope", _tree_names())
def test_every_name_of_the_tree_is_in_the_program_under_its_parent(
        program_paths, scope):
    """``scopes.TREE`` is the program's own: every name in it is a path
    segment of some operation, and every path that holds a name holds
    one of the parents the tree gives it, BEFORE it (the root — the
    program — is held by all). A path the compiler kept only the tail
    of (no ``jit(`` at its start) says nothing about what stood before
    it."""
    mine = [p for p in program_paths if _holds(scope)(p)]
    assert mine, scope
    parents = [p for p, children in scopes.TREE.items()
               if scope in children]
    assert parents, scope
    if scopes.ROOT in parents:
        return
    for path in mine:
        if not path.startswith("jit("):
            continue
        at = _holds(scope)(path).start()
        assert any(m and m.start() < at
                   for m in (_holds(p)(path) for p in parents)), path


@pytest.mark.parametrize("parent", [p for p in scopes.TREE
                                    if p is not scopes.ROOT])
def test_an_enclosing_scope_has_operations_of_its_own(program_paths,
                                                      parent):
    """Each enclosing scope names operations that none of its children
    names — what its self time is read from — and every child stands
    DIRECTLY under it somewhere: no other name of the tree between."""
    children = scopes.TREE[parent]
    own = [p for p in program_paths if _holds(parent)(p)
           and not any(_holds(c)(p) for c in children)]
    assert own, parent
    others = set(_tree_names()) - {parent}
    for child in children:
        def direct(path):
            a, b = _holds(parent)(path), _holds(child)(path)
            if not (a and b and a.end() <= b.start()):
                return False
            between = path[a.end():b.start()]
            return not any(_holds(o)(between) for o in others - {child})
        assert any(map(direct, program_paths)), (parent, child)


def test_what_vmap_makes_of_the_decisions_cond_is_the_decisions(
        program_paths):
    """At 8 lanes the decision's ``lax.cond`` is both branches and a
    select over every output, with the untaken branch's constants
    broadcast over the lanes: those operations are bound under the
    name stack of the CALL, so they carry ``sim_decide`` and no child
    of it — the decision's self time, which no leaf scope could name."""
    children = scopes.TREE[scopes.SIM_DECIDE]
    glue = [p for p in program_paths if _holds(scopes.SIM_DECIDE)(p)
            and not any(_holds(c)(p) for c in children)]
    kinds = {p.rstrip(":").rsplit("/", 1)[-1] for p in glue}
    assert {"select_n", "broadcast_in_dim"} <= kinds, kinds
    # and no ``cond`` is left for a branch to hide in
    assert not any("/cond/" in p for p in glue)


def test_the_scopes_change_no_instruction(fused_dataset, program_text,
                                          monkeypatch):
    """A scope is metadata: the same program compiled with
    ``jax.named_scope`` a null context gives the same text once each
    instruction's ``metadata={...}`` is cut. (``ppo_update`` is bound
    when ``rl/ppo.py`` is imported and stays; every scope of the tree
    is opened at trace time and goes.)"""
    import contextlib
    import re

    import jax

    def bare(text):
        """The instructions alone: no ``metadata={...}``, none of the
        tables of files and frames it points into."""
        blocks = [b for b in text.split("\n\n") if not b.startswith(
            ("FileNames", "FunctionNames", "FileLocations", "StackFrames"))]
        text = re.sub(r",? ?metadata=\{[^}]*\}", "", "\n\n".join(blocks))
        # an instruction's NAME is made from its location and a
        # counter: number the names by first appearance, so an operand
        # still says which instruction it reads
        seen = {}
        return re.sub(
            r"%[\w.\-]+",
            lambda m: seen.setdefault(m.group(), f"%{len(seen)}"),
            text).split("\n")

    class no_scope(contextlib.ContextDecorator):
        """Opens nothing, around a block or a function."""

        def __init__(self, name):
            pass

        def __enter__(self):
            pass

        def __exit__(self, *exc):
            return False

    monkeypatch.setattr(jax, "named_scope", no_scope)
    plain = _compiled_text(fused_dataset)
    assert scopes.SIM_SEGMENT in program_text
    for name in _tree_names():
        if name != scopes.PPO_UPDATE:
            assert name not in plain, name
    named, plain = bare(program_text), bare(plain)
    assert len(named) == len(plain) > 1000
    # (not ``named == plain``: pytest would diff two 10,000-line lists)
    differ = [i for i, pair in enumerate(zip(named, plain))
              if pair[0] != pair[1]]
    assert not differ, (named[differ[0]], plain[differ[0]])


# --------------------------------------------------------- trip counts
def _segment_lanes(memo_env, n_lanes, T=12):
    import jax

    from ddls_tpu.sim.jax_env import (make_segment_fn, segment_init,
                                      vmap_segment_fn)
    from ddls_tpu.sim.jax_memo import MemoConfig

    et, ot = memo_env["et"], memo_env["ot"]
    model, params = test_jax_memo._ReplayPolicy(), memo_env["params"]
    banks = test_jax_memo._lane_banks(memo_env, n_lanes)
    mc = MemoConfig(n_sets=16, n_ways=2)
    seg = make_segment_fn(et, ot, model, T, memo_cfg=mc, trace_trips=True)
    states = jax.vmap(lambda b: segment_init(et, b, mc))(banks)
    rngs = jax.random.split(jax.random.PRNGKey(3), n_lanes)
    _, wide, _ = jax.jit(vmap_segment_fn(seg, n_lanes))(
        banks, params, states, rngs)

    def single(lane):
        bank = jax.tree_util.tree_map(lambda x: x[lane], banks)
        return seg(bank, params, segment_init(et, bank, mc),
                   rngs[lane])[1]

    return wide, single


@pytest.mark.parametrize("n_lanes", [2, 8])
def test_traced_trips_are_each_lanes_own_loop_count(memo_env, n_lanes):
    """Lane by lane the vmapped segment's trip trace equals the
    single-lane kernel's on the same decisions (there the count IS the
    loop's ``it``); a memo hit reads 0. Every lane that loops carries
    its count out, so the per-step maximum over the lanes is what the
    batched loop executed."""
    wide, single = _segment_lanes(memo_env, n_lanes)
    own = np.asarray(wide["la_trips"])
    assert own.shape == (n_lanes, 12) and own.dtype == np.int32
    hits = np.diff(np.asarray(wide["memo_hits"]), axis=1, prepend=0)
    assert hits.sum() > 0 and own.max() > 0
    assert np.all(own[hits > 0] == 0), "a memo hit ran trips"
    for lane in range(n_lanes):
        alone = single(lane)
        np.testing.assert_array_equal(np.asarray(alone["action"]),
                                      np.asarray(wide["action"])[lane])
        np.testing.assert_array_equal(np.asarray(alone["la_trips"]),
                                      own[lane])


def _one_lane_kernels(memo_env):
    """(et, kernels, lane 0's bank, its initial carry, job row 0)."""
    import jax
    import jax.numpy as jnp

    from ddls_tpu.sim.jax_env import _episode_kernels

    et = memo_env["et"]
    k = _episode_kernels(et)
    bank = jax.tree_util.tree_map(
        lambda x: x[0], test_jax_memo._lane_banks(memo_env, 1))
    return et, k, bank, k.init_state(bank)[0], jnp.int32(0)


def test_a_discarded_lane_runs_no_trips(memo_env):
    """Under vmap the decision's ``cond`` is a select and every lane
    runs the heavy branch; a lane whose action takes the zero path is
    masked out of the lookahead loop (``skip``), so the loop's own count
    — BEFORE the select — is 0 there, and what the batched loop runs is
    the maximum over lanes whose result is used."""
    import jax
    import jax.numpy as jnp

    et, k, bank, carry, row = _one_lane_kernels(memo_env)
    n_deg = len(et.degrees)
    cfg = bank["type"][row] * n_deg + (n_deg - 1)   # the largest degree

    def trips_before_the_select(discard):
        return k.eval_cfg(bank, carry, row, cfg,
                          config_rows(et.tables, cfg),
                          discard=discard)[0]["la_trips"]

    discard = jnp.asarray([False, True, False, True])
    trips = np.asarray(jax.jit(jax.vmap(trips_before_the_select))(discard))
    assert trips[0] == trips[2] > 0
    assert trips[1] == trips[3] == 0
    # through the decision itself: action 0 takes the zero path
    actions = jnp.where(discard, 0, et.degrees[-1]).astype(jnp.int32)
    la = np.asarray(jax.jit(jax.vmap(
        lambda a: k.decision(bank, carry, a, row)[1][4]))(actions))
    assert la.tolist() == trips.tolist()


def test_traced_rode_votes_the_width_the_lockstep_took(wide_build):
    """32 servers under a block side of 16: a decision's ``la_rode`` is
    the servers its job's sub-ops sit on where its lookahead ran trips
    and 0 on the zero path, and `channel_trips` — the host's reckoning
    from the two traces — gives the trips at each width of the channel
    table that each stage of the kernel's own lockstep reports on the
    same lanes."""
    import jax
    import jax.numpy as jnp

    from ddls_tpu.sim import jax_env as je
    from ddls_tpu.sim.jax_lookahead import channel_trips, stage_widths

    et = wide_build.et
    k = je._episode_kernels(et)
    bank = {key: jnp.asarray(v) for key, v in je.build_job_bank(et, [
        {"model": "translation_0", "num_training_steps": 3,
         "sla_frac": 1.0, "time_arrived": 0.0}]).items()}
    carry, row = k.init_state(bank)[0], jnp.int32(0)
    # 24 lanes: every placeable degree in turn, each seventh on the zero path
    degrees = [d for d in et.degrees if d <= 8]
    actions = jnp.asarray([0 if i % 7 == 3 else degrees[i % len(degrees)]
                           for i in range(24)], jnp.int32)
    trips, rode = (np.asarray(x) for x in jax.jit(jax.vmap(
        lambda a: k.decision(bank, carry, a, row)[1][4:6]))(actions))
    assert trips.dtype == rode.dtype == np.int32
    assert ((trips > 0) == (np.asarray(actions) > 0)).all()
    assert (rode[trips == 0] == 0).all()

    live = np.asarray(actions) > 0
    cfgs = jnp.asarray([wide_build.row("translation_0", int(a))
                        for a in np.asarray(actions)[live]], jnp.int32)
    args, blocks, placed = jax.vmap(
        lambda c: wide_build.arguments(c, wide_build.states[0]))(cfgs)
    assert np.asarray(placed).all()
    assert rode[live].tolist() == test_jax_lookahead._rode(args).tolist()
    assert set(rode[live].tolist()) == {1, 2, 4, 8}     # 6 rides 8

    # the same lanes through the lookahead's own staged report
    every = jax.vmap(lambda c: wide_build.arguments(
        c, wide_build.states[0]))(jnp.asarray(
            [wide_build.row("translation_0", max(int(a), 1))
             for a in np.asarray(actions)], jnp.int32))
    got, ran = test_jax_lookahead._staged(wide_build)(
        every[0], every[1], jnp.asarray(~live))
    assert np.asarray(got[5]).tolist() == trips.tolist()
    widths = stage_widths(24, et.pads.max_split)
    ran = np.asarray(ran)                      # [stages, (8, 16, 32)]
    assert ran.tolist() == channel_trips(
        trips, rode, widths, et.n_srv, et.pads.max_split).tolist()
    # every lane on <= 8 servers: the 16- and the 32-wide loop ran none
    assert ran[:, 0].sum() == trips.max() and not ran[:, 1:].any()


def test_an_unplaced_job_runs_no_trips_and_stays_out_of_the_memo(memo_env):
    """The host drops a job it could not place before any lookahead; in
    the kernel such a lane is masked out of the loop and its probe is
    void (`sim/jax_memo.py`): the table and its counters are untouched,
    and the same job on an empty cluster then misses and enters."""
    import jax
    import jax.numpy as jnp

    from ddls_tpu.sim import jax_memo

    et, k, bank, carry, row = _one_lane_kernels(memo_env)
    cfg = bank["type"][row] * len(et.degrees)        # degree 1
    memo0 = jax_memo.memo_init(et, jax_memo.MemoConfig(2, 1))

    @jax.jit
    def probe(mem, memo):
        ev, pending = k.eval_cfg(bank, (carry[0], mem) + carry[2:], row,
                                 cfg, config_rows(et.tables, cfg), memo)
        return (ev["ok_place"], ev["la_trips"],
                jax_memo.memo_commit(memo, pending))

    ok, trips, memo = probe(jnp.zeros_like(carry[1]), memo0)
    assert not bool(ok) and int(trips) == 0
    for key in memo0:
        assert np.array_equal(np.asarray(memo0[key]),
                              np.asarray(memo[key])), key
    ok, trips, memo = probe(carry[1], memo)
    assert bool(ok) and int(trips) > 0
    assert (int(memo["hits"]), int(memo["misses"])) == (0, 1)


@pytest.mark.parametrize("jtype", [0, 1])
def test_a_placement_that_stops_short_never_answers_the_complete_one(
        memo_env, jtype):
    """Servers with room for one large op each: on m of them the
    degree-1 job stops ops short, on m + 1 it places — and the ops that
    placed are the SAME groups with the same dep times, so both probe
    one memo key. The short one's lookahead would end stuck; stored, it
    answered the complete one with ``engine_ok`` False (PR 36: the
    benchmark's replay of 570-op jobs met it at decision 20 of its
    twelfth seed)."""
    import jax
    import jax.numpy as jnp

    from ddls_tpu.sim import jax_memo

    et, k, bank, carry, row = _one_lane_kernels(memo_env)
    cfg = jnp.int32(jtype * len(et.degrees))         # degree 1
    f_mem = np.asarray(et.tables["f_mem"])[int(cfg)]
    unit = float(f_mem[np.asarray(et.tables["f_valid"])[int(cfg)]].max())
    memo0 = jax_memo.memo_init(et, jax_memo.MemoConfig(2, 1))

    @jax.jit
    def probe(n_servers, memo):
        mem = jnp.where(jnp.arange(et.n_srv) < n_servers, unit, 0.0)
        ev, pending = k.eval_cfg(
            bank, (carry[0], mem.astype(carry[1].dtype)) + carry[2:], row,
            cfg, config_rows(et.tables, cfg), memo)
        return ((ev["ok_place"], ev["engine_ok"], ev["jct"]),
                jax_memo.memo_commit(memo, pending))

    enough = next(m for m in range(1, et.n_srv + 1)
                  if bool(probe(m, memo0)[0][0]))
    assert enough > 1, "the job must need more than one such server"
    (placed, _, _), after_short = probe(enough - 1, memo0)
    assert not bool(placed)
    got, memo = probe(enough, after_short)
    want, _ = probe(enough, memo0)
    assert bool(got[0]) and bool(got[1]), "served the short one's stuck"
    assert float(got[2]) == float(want[2])
    assert (int(memo["hits"]), int(memo["misses"])) == (0, 1)


#: the degree-16 pads of the shipped dataset (tests/test_jax_pricing.py
#: counts them from the tables)
_BENCH_PADS = dict(n_ops=480, n_deps=13312, n_fwd=15, n_parents=2,
                   max_split=16, n_groups=1, n_orig=30, n_blocks=52,
                   n_deps_used=13072)

#: a fetched [U=1, B=3, T=2] trace: each lane-step's own trips, and the
#: servers its job rode where it ran any
_TRIPS_EP = {"la_trips": np.array([[[5, 7], [9, 0], [0, 0]]], np.int32),
             "la_rode": np.array([[[20, 16], [8, 0], [0, 0]]], np.int32)}


def test_trip_counters_are_the_hosts_reduction_of_the_trace():
    from ddls_tpu.rl.fused import record_lookahead_trips
    from ddls_tpu.sim.jax_env import ConfigPads

    # [U=1, B=3, T=2]: step 0 — lanes ran 5 and 9, one ran none (a hit
    # or an action without a lookahead); step 1 — a miss of 7 only
    # ... on 20 and 8 servers (step 0) and on 16 (step 1): under 32
    # servers and a block side of 16 the first 5 trips of step 0 ran
    # over the cluster-wide channel table and, the lane on 20 servers
    # done, its last 4 over the 8-wide one; the 7 of step 1 over the
    # 16-wide one
    ep = dict(_TRIPS_EP)
    telemetry.enable()
    record_lookahead_trips(ep, ConfigPads(**_BENCH_PADS), 32)
    snap = telemetry.snapshot()
    counted = {
        "sim.lookahead.trips": 21,
        "sim.lookahead.lockstep_trips": 16,
        "sim.lookahead.lockstep_lane_trips": 48,
        "sim.lookahead.stage_trips.3": 16,
        "sim.lookahead.narrow_trips": 11,
        "sim.lookahead.narrowest_trips": 4,
        "sim.lookahead.rode.8": 1, "sim.lookahead.rode.16": 1,
        "sim.lookahead.rode.20": 1,
        "sim.lookahead.dep_slots": 13312,
        "sim.lookahead.dep_slots_used": 13072}
    assert {k: v for k, v in snap["counters"].items()
            if k.startswith("sim.lookahead.")} == counted
    # ... and what the lookahead's packing rule left in the start-up
    # registry when it ran in a trace (none has, in this test)
    startup.set_gauge("sim.lookahead.minor_slots", 128)
    startup.set_gauge("sim.lookahead.minor_used", 48)
    record_lookahead_trips(ep, ConfigPads(**_BENCH_PADS), 32)
    assert {k: v for k, v in telemetry.snapshot()["counters"].items()
            if k.startswith("sim.lookahead.")} == {
        **{k: 2 * v for k, v in counted.items()},
        "sim.lookahead.minor_slots": 128, "sim.lookahead.minor_used": 48}
    # counters alone: no per-lane-step histogram (PR 34 removed
    # ``trips_per_call``, which nothing read)
    assert "histograms" not in telemetry.snapshot()


def _lockstep_by_hand(own, rode, widths, channels=(8, 16, 32)):
    """Walk one call's lockstep trip by trip: the width steps down when
    the next one holds the lanes still live, and a lane-packed stage
    (under 128 lanes) ticks the trip over the narrowest channel table
    that holds what every lane still live rode. Trips run at each
    (width, channel width)."""
    ran, stage, trip = np.zeros((len(widths), len(channels)), int), 0, 0
    while (own > trip).any():
        while stage + 1 < len(widths) and \
                (own > trip).sum() <= widths[stage + 1]:
            stage += 1
        widest = rode[own > trip].max()
        ran[stage, -1 if widths[stage] >= 128 else
            min(i for i, c in enumerate(channels) if widest <= c)] += 1
        trip += 1
    return ran


@pytest.mark.parametrize("lanes,hit_share", [
    (3, 0.0), (24, 0.3), (48, 0.5), (80, 0.5), (80, 1.0), (320, 0.1),
    (320, 0.85)])
def test_paid_lane_trips_are_the_stages_widths_times_their_trips(
        lanes, hit_share):
    """`sim.lookahead.lockstep_lane_trips` is what the staged lockstep
    paid: over every step of a [U, B, T] trace, each width of the
    kernel's own `stage_widths` times the trips a walk of that step's
    lockstep runs at it; `stage_trips.<W>` are those trips by width,
    `lockstep_trips` their sum (the longest lane's count, as before);
    `narrow_trips` / `narrowest_trips` those that ran under the
    cluster's 32-wide channel table / over the 8-wide one."""
    from ddls_tpu.rl.fused import record_lookahead_trips
    from ddls_tpu.sim.jax_env import ConfigPads
    from ddls_tpu.sim.jax_lookahead import stage_widths

    rng = np.random.default_rng(lanes)
    own = rng.integers(1, 153, size=(2, lanes, 3)).astype(np.int32)
    own[rng.random(own.shape) < hit_share] = 0
    widths = stage_widths(lanes, 16)
    rode = np.where(own > 0, rng.integers(1, 21, size=own.shape), 0)
    by_channel = np.sum([_lockstep_by_hand(own[u, :, t], rode[u, :, t],
                                           widths)
                         for u in range(2) for t in range(3)], axis=0)
    by_hand = by_channel.sum(axis=1)
    telemetry.enable()
    record_lookahead_trips({"la_trips": own, "la_rode": rode},
                           ConfigPads(**_BENCH_PADS), 32)
    counters = telemetry.snapshot()["counters"]
    assert counters["sim.lookahead.narrow_trips"] \
        == by_channel[:, :2].sum()
    assert counters["sim.lookahead.narrowest_trips"] \
        == by_channel[:, 0].sum()
    if (lanes, hit_share) == (24, 0.3):
        assert 0 < by_channel[:, 0].sum() < by_channel[:, :2].sum() \
            < by_channel.sum()
    assert sum(v for k, v in counters.items()
               if k.startswith("sim.lookahead.rode.")) == (own > 0).sum()
    assert [counters[f"sim.lookahead.stage_trips.{w}"] for w in widths] \
        == by_hand.tolist()
    assert counters["sim.lookahead.lockstep_trips"] == by_hand.sum() \
        == own.max(axis=1).sum()
    assert counters["sim.lookahead.lockstep_lane_trips"] \
        == int(by_hand @ np.asarray(widths))
    assert counters["sim.lookahead.trips"] == own.sum() \
        <= counters["sim.lookahead.lockstep_lane_trips"] \
        <= lanes * counters["sim.lookahead.lockstep_trips"]


def test_block_fill_metric_reads_the_dep_slot_counters():
    """The benchmark's ``lookahead_block_fill`` is the ratio of the two
    dep-slot counters the trip drain adds per epoch trace, and reads
    nothing (never raises) from a program that has none."""
    from benchmarks import harness
    from ddls_tpu.rl.fused import record_lookahead_trips
    from ddls_tpu.sim.jax_env import ConfigPads

    ep = dict(_TRIPS_EP)
    ctx = {"spans": {"bench": {"epoch": [(0.0, 1.0), (1.0, 2.0)]}}}
    telemetry.enable()
    assert harness.read_layer_metric("lookahead_block_fill", ctx) is None
    for _ in range(2):
        record_lookahead_trips(ep, ConfigPads(**_BENCH_PADS), 32)
    assert harness.read_layer_metric("lookahead_dep_slots", ctx) == 13312
    assert harness.read_layer_metric("lookahead_block_fill", ctx) == \
        pytest.approx(100 * 13072 / 13312)


@pytest.fixture
def block_lanes(block_build):
    """The small block tables and three lanes' lookahead arguments."""
    return block_build, test_jax_lookahead._lane_arguments(
        block_build, test_jax_lookahead._lanes(block_build, 3))


def test_minor_fill_metric_reads_what_the_traced_loop_carries(block_lanes):
    """The benchmark's ``lookahead_minor_fill``: a lane-batched block
    lookahead leaves the minor extent of its packed dep state in the
    start-up registry when it is TRACED (the unbatched call, which no
    rule batches, leaves nothing), the trip drain counts it per epoch
    trace, and the metric is their ratio; nothing to read (never
    raises) from a program that counts neither."""
    import jax

    from benchmarks import harness
    from ddls_tpu.rl.fused import record_lookahead_trips
    from ddls_tpu.sim.jax_env import ConfigPads

    build, (args, blocks, _) = block_lanes
    ep = dict(_TRIPS_EP)
    ctx = {"spans": {"bench": {"epoch": [(0.0, 1.0), (1.0, 2.0)]}}}
    telemetry.enable()
    one = jax.tree_util.tree_map(lambda x: x[0], (args, blocks))
    jax.make_jaxpr(build.block_fn)(*one)
    assert startup.gauges() == {}
    record_lookahead_trips(ep, ConfigPads(**_BENCH_PADS), 32)
    assert harness.read_layer_metric("lookahead_minor_fill", ctx) is None

    jax.make_jaxpr(jax.vmap(build.block_fn))(args, blocks)
    S = build.et.pads.max_split
    assert startup.gauges() == {"sim.lookahead.minor_slots": 128,
                                "sim.lookahead.minor_used": S * 3,
                                "sim.lookahead.channel_widths": [8, 16],
                                "sim.lookahead.endpoint_onehot_elems": 0,
                                # a width under the cluster's: by server
                                "sim.lookahead.channel_onehot_elems": 0}
    args128, blocks128, _ = test_jax_lookahead._lane_arguments(
        build, test_jax_lookahead._lanes(build, 128))
    jax.make_jaxpr(jax.vmap(build.block_fn))(args128, blocks128)
    # one job a lane: the cluster's table, 2 * B*S*W*L*S + 2 * B*W*W*L*S
    B, W = build.et.pads.n_blocks, build.et.n_srv
    assert startup.gauges()["sim.lookahead.channel_onehot_elems"] == \
        2 * B * S * W * 128 * S + 2 * B * W * W * 128 * S > 0
    jax.make_jaxpr(jax.vmap(build.block_fn))(args, blocks)
    for _ in range(2):
        record_lookahead_trips(ep, ConfigPads(**_BENCH_PADS), 32)
    assert harness.read_layer_metric("lookahead_minor_slots", ctx) == 128
    assert harness.read_layer_metric("lookahead_minor_fill", ctx) == \
        pytest.approx(100 * S * 3 / 128)


def test_fused_loop_counts_trips_only_while_telemetry_is_on(
        fused_dataset):
    loop = test_fused._make_fused_loop(fused_dataset,
                                       metrics_sync_interval=1)
    try:
        loop.run()                       # telemetry off: the drain ran
        assert telemetry.snapshot() == {}
        telemetry.enable()
        telemetry.reset()
        loop.run()
        counters = telemetry.snapshot()["counters"]
        lanes = loop.fused.num_lanes
        assert 0 < counters["sim.lookahead.trips"]
        assert (counters["sim.lookahead.lockstep_lane_trips"]
                == lanes * counters["sim.lookahead.lockstep_trips"])
        assert (counters["sim.lookahead.lockstep_trips"]
                <= counters["sim.lookahead.trips"]
                <= counters["sim.lookahead.lockstep_lane_trips"])
        # one drained epoch trace: the tables' dep slots, once
        pads = loop.fused.et.pads
        assert counters["sim.lookahead.dep_slots"] == pads.n_deps \
            == pads.n_blocks * pads.max_split ** 2
        assert 0 < counters["sim.lookahead.dep_slots_used"] \
            == pads.n_deps_used <= pads.n_deps
        # memo counters keep their own path
        assert counters["event.memo_counters"] >= 1
    finally:
        loop.close()


# ----------------------------------------- the fused epoch's anatomy
#: the five spans that tile ``run()`` of a fused epoch (PR 34)
EPOCH_SPANS = ("train.fused_epoch", "train.device_wait",
               "train.host_sync", "train.telemetry_reduce",
               "train.harvest")


@pytest.fixture(scope="module")
def warm_fused_loop(fused_dataset):
    """One tiny fused loop, its program compiled and one epoch run,
    draining every epoch (as the benchmark's mixes do)."""
    loop = test_fused._make_fused_loop(fused_dataset,
                                       metrics_sync_interval=1)
    loop.run()
    yield loop
    loop.close()


def _count_calls(monkeypatch, module, name):
    """Count calls of ``module.name`` whose argument holds a device
    array (a host tree costs no transfer and no wait)."""
    import jax

    calls, inner = [], getattr(module, name)

    def counted(tree, *args, **kwargs):
        if any(isinstance(leaf, jax.Array)
               for leaf in jax.tree_util.tree_leaves(tree)):
            calls.append(tree)
        return inner(tree, *args, **kwargs)

    monkeypatch.setattr(module, name, counted)
    return calls


def test_five_spans_tile_every_fused_epoch(warm_fused_loop):
    """Over 5 fused epochs with telemetry on: one ``train.fused_epoch``
    an epoch and, at each drain boundary, one ``train.device_wait``, one
    ``train.harvest``, one ``train.telemetry_reduce`` and the two
    copies under ``train.host_sync``; in program order, none nested in
    another, and together >= 90 % of ``run()``'s wall on the registry's
    clock."""
    from benchmarks.reduce import xplane

    loop = warm_fused_loop
    telemetry.enable(record_intervals=True)
    telemetry.reset()
    walls = []
    for _ in range(5):
        t0 = telemetry.clock_now()
        loop.run()
        walls.append((t0, telemetry.clock_now()))
    telemetry.disable()
    intervals = [iv for iv in telemetry.span_intervals()
                 if iv[0] in EPOCH_SPANS]
    for t0, t1 in walls:
        inside = sorted((iv for iv in intervals if t0 <= iv[1] < t1),
                        key=lambda iv: iv[1])
        assert [name for name, _, _ in inside] == [
            "train.fused_epoch", "train.device_wait", "train.host_sync",
            "train.host_sync", "train.telemetry_reduce", "train.harvest"]
        assert all(a[2] <= b[1] for a, b in zip(inside, inside[1:]))
    covered = xplane.total(xplane.union((a, b) for _, a, b in intervals))
    assert covered >= 0.9 * sum(t1 - t0 for t0, t1 in walls)
    # the copies are ledgered under the spans that hold them, and the
    # memo's counters rode the episode trace's fetch
    counters = telemetry.snapshot()["counters"]
    assert counters["transfer.drain.metrics.calls"] == 5
    assert counters["transfer.drain.episodes.calls"] == 5
    assert "transfer.drain.memo.calls" not in counters
    assert counters["event.memo_counters"] == 5


def test_device_wait_opens_at_the_sync_boundary_only(fused_dataset):
    """With ``metrics_sync_interval`` 3, epochs 1-2 wait for nothing
    (and copy nothing); epoch 3 opens the one wait, and the harvest
    span closes every epoch."""
    loop = test_fused._make_fused_loop(fused_dataset,
                                       metrics_sync_interval=3)
    try:
        telemetry.enable()
        for epoch, waits in ((1, 0), (2, 0), (3, 1)):
            loop.run()
            spans = telemetry.snapshot()["spans"]
            assert spans["train.fused_epoch"]["count"] == epoch
            assert spans["train.harvest"]["count"] == epoch
            assert spans.get("train.device_wait",
                             {"count": 0})["count"] == waits
            assert ("train.host_sync" in spans) == bool(waits)
        assert spans["train.host_sync"]["count"] == 2
        assert spans["train.telemetry_reduce"]["count"] == 1
    finally:
        loop.close()


def test_telemetry_off_opens_no_span_and_waits_for_nothing(
        warm_fused_loop, monkeypatch):
    """Off, ``run()`` is the epoch it was: every ``telemetry.span`` /
    ``transfer`` hands out the ``NULL_SPAN`` singleton (no ``Span`` is
    allocated), nothing is recorded, the loop calls no
    ``block_until_ready`` (the first fetch blocks, as it always did),
    and what only the instrument needs (the wait, the memo's counter
    arrays, the memo event) is gated at its call site."""
    import jax

    from ddls_tpu.telemetry import metrics

    def no_span(*args, **kwargs):
        raise AssertionError("a Span was allocated with telemetry off")

    def not_off(*args, **kwargs):
        raise AssertionError("telemetry-only work ran with telemetry off")

    monkeypatch.setattr(metrics.Span, "__init__", no_span)
    monkeypatch.setattr(metrics.TransferSpan, "__init__", no_span)
    loop = warm_fused_loop
    monkeypatch.setattr(loop, "_device_wait", not_off)
    monkeypatch.setattr(loop, "_record_memo_drain", not_off)
    monkeypatch.setattr(loop.fused, "memo_counter_arrays", not_off)
    waits = _count_calls(monkeypatch, jax, "block_until_ready")
    assert telemetry.span("train.device_wait") is telemetry.NULL_SPAN
    loop.run()
    assert waits == [] and telemetry.snapshot() == {}


def test_telemetry_on_adds_no_transfer_to_a_drain_boundary(
        warm_fused_loop, monkeypatch):
    """A drain boundary makes two device->host fetches with telemetry
    off (the metrics, the episode traces) and the same two with it on:
    the memo's counters ride the second, ``record_padding_fill`` reads
    the tables' host copy, and the one wait is the ``train.device_wait``
    span's."""
    import jax

    from ddls_tpu.rl import fused

    loop = warm_fused_loop
    fetches = _count_calls(monkeypatch, jax, "device_get")
    waits = _count_calls(monkeypatch, jax, "block_until_ready")
    loop.run()
    assert (len(fetches), len(waits)) == (2, 0)
    telemetry.enable()
    telemetry.reset()
    del fetches[:]
    loop.run()
    assert (len(fetches), len(waits)) == (2, 1)
    counters = telemetry.snapshot()["counters"]
    assert {k: v for k, v in counters.items()
            if k.startswith("transfer.") and k.endswith(".calls")} == {
        "transfer.drain.metrics.calls": 1,
        "transfer.drain.episodes.calls": 1}
    # ... and the reducers fetch nothing: fed a host trace under a
    # guard that refuses every device->host transfer
    ep = {k: np.zeros((2, loop.fused.num_lanes, 2), np.int32)
          for k in fused.EPISODE_TRACE_KEYS}
    ep["la_trips"][0, :, 0] = 5
    ep["action"][:] = loop.fused.et.degrees[-1]
    del fetches[:]
    with jax.transfer_guard_device_to_host("disallow"):
        fused.record_padding_fill(ep, loop.fused.et, loop.fused.ot)
    assert fetches == []
    assert telemetry.snapshot()["counters"][
        "sim.lookahead.dep_slots_decided"] == counters[
        "sim.lookahead.dep_slots_decided"] + loop.fused.num_lanes * int(
        loop.fused.et.row_deps[len(loop.fused.et.degrees) - 1])
    assert np.array_equal(loop.fused.et.row_deps,
                          np.asarray(loop.fused.et.tables["n_deps"]))


def test_a_background_collection_keeps_the_memo_fetch_apart(
        warm_fused_loop, monkeypatch):
    """Where a background collection may donate the collector's state
    meanwhile (``pipeline_depth`` >= 1), the memo's counters do not
    ride the trace's fetch: ONE ledgered fetch of their own
    (``drain.memo``), inside ``train.telemetry_reduce`` — at most one
    transfer more than with telemetry off."""
    import jax

    loop = warm_fused_loop
    monkeypatch.setattr(loop, "pipeline_depth", 1)
    fetches = _count_calls(monkeypatch, jax, "device_get")
    telemetry.enable(record_intervals=True)
    telemetry.reset()
    loop.run()
    assert len(fetches) == 3
    counters = telemetry.snapshot()["counters"]
    assert counters["transfer.drain.memo.calls"] == 1
    assert counters["event.memo_counters"] == 1
    spans = {name: (t0, t1) for name, t0, t1
             in telemetry.span_intervals()}
    lo, hi = spans["train.telemetry_reduce"]
    assert lo <= spans["transfer.drain.memo"][0] \
        and spans["transfer.drain.memo"][1] <= hi


def test_report_renders_the_epochs_the_program_grouped(warm_fused_loop,
                                                       tmp_path):
    """An operator's report and the benchmark's reader see one anatomy:
    ``telemetry.per_epoch_sums`` groups the registry's intervals by
    ``train.fused_epoch`` starts, and ``scripts/telemetry_report.py``
    renders the same sums, part by part, from the sink's span records
    of the same run."""
    sys.path.insert(0, os.path.join(REPO, "scripts"))
    import telemetry_report

    sink = tmp_path / "run.jsonl"
    telemetry.enable(sink_path=str(sink), record_intervals=True)
    try:
        telemetry.reset()
        for _ in range(3):
            warm_fused_loop.run()
    finally:
        telemetry.disable()
        telemetry.registry().sink.close()
        telemetry.registry().sink = None
    intervals = telemetry.span_intervals()
    assert telemetry.per_epoch_sums(
        [("train.collect", 0.0, 1.0)], {"train.collect"}) == []
    report = telemetry_report.render_report(str(sink))
    at = report.index("== epoch anatomy (3 fused epochs; per-epoch sums "
                      "of the program's spans) ==")
    assert set(EPOCH_SPANS) == {
        name for _, names in telemetry_report.EPOCH_PARTS
        for name in names}
    for line, (label, names) in zip(report[at + 2:],
                                    telemetry_report.EPOCH_PARTS):
        sums = telemetry.per_epoch_sums(intervals, names)
        assert len(sums) == 3 and all(v > 0 for v in sums)
        assert line.startswith(label)
        p50, top = (float(x) for x in line[len(label):].split())
        assert p50 == pytest.approx(np.median(sums) * 1e3, abs=2e-3)
        assert top == pytest.approx(max(sums) * 1e3, abs=2e-3)


#: every ``program_counter`` name a metric listed in BENCHMARK.json
#: reads through ``telemetry_counter`` (directly or as a ratio's part)
def _listed_counter_names():
    from benchmarks import harness

    bench = harness.read_json(os.path.join(REPO, "BENCHMARK.json"))
    names, todo = set(), [m["name"] for m in bench["per_layer"]]
    while todo:
        source = harness.read_json(os.path.join(
            harness.BENCH_DIR, "layer_metrics", todo.pop() + ".json")
        )["source"]
        if source["kind"] == "telemetry_counter":
            names.add(source["counter"])
        elif source["kind"] == "metric_ratio":
            todo += [source["num"], source["den"]]
    return names


def test_listed_counters_are_the_parents_on_the_same_trace(
        warm_fused_loop):
    """Every counter a listed benchmark metric reads, reduced from one
    drained trace, equals the parent's reduction of that trace (PR 33's
    formulas, written out here), bit for bit."""
    from ddls_tpu.rl.fused import (record_decisions,
                                   record_lookahead_trips,
                                   record_padding_fill)
    from ddls_tpu.sim.jax_env import CAUSE_ACCEPTED, CAUSE_OP_PLACEMENT
    from ddls_tpu.sim.jax_lookahead import stage_trips, stage_widths

    et, ot = warm_fused_loop.fused.et, warm_fused_loop.fused.ot
    rng = np.random.default_rng(34)
    shape = (2, 8, 3)
    ep = {"la_trips": rng.integers(0, 40, shape).astype(np.int32),
          "la_rode": rng.integers(1, et.n_srv + 1, shape).astype(np.int32),
          "jtype": rng.integers(0, len(et.types), shape).astype(np.int32),
          "action": rng.choice(et.degrees, shape).astype(np.int32),
          "cause": rng.integers(0, 6, shape).astype(np.int32),
          "n_occupied": rng.integers(0, et.n_srv, shape).astype(np.int32)}
    ep["la_trips"][rng.random(shape) < 0.4] = 0
    ep["la_rode"][ep["la_trips"] == 0] = 0
    telemetry.enable()
    record_lookahead_trips(ep, et.pads, et.n_srv)
    record_padding_fill(ep, et, ot)
    record_decisions(ep, et, ot)
    counters = telemetry.snapshot()["counters"]

    own, ran = ep["la_trips"], ep["la_trips"] > 0
    widths = stage_widths(8, int(et.pads.max_split))
    assert widths == [8] and int(et.pads.max_split) < et.n_srv == 8
    by_width = stage_trips(np.moveaxis(own, -2, -1), widths).reshape(
        -1, len(widths)).sum(axis=0)
    column = np.zeros(et.max_action + 1, np.int64)
    column[et.degrees] = np.arange(len(et.degrees))
    row = ep["jtype"][ran] * len(et.degrees) + column[ep["action"][ran]]
    longest = ep["jtype"] == int(np.argmax(ot["orig_seq_sum"]))
    accepted = ep["cause"] == CAUSE_ACCEPTED
    every = ep["jtype"] * len(et.degrees) + column[ep["action"]]
    ragged = ((np.asarray(et.tables["f_valid"])[every]
               & (np.asarray(et.tables["f_split"])[every]
                  < ep["action"][..., None])).sum(-1) > 0)
    parent = {
        "sim.lookahead.trips": int(own.sum()),
        "sim.lookahead.lockstep_trips": int(own.max(axis=1).sum()),
        "sim.lookahead.lockstep_lane_trips":
            int(by_width @ np.asarray(widths)),
        # 8 servers: no rung of the channel table is under them (a
        # rung under a register's 8 sublanes is none), so every trip
        # ran over the one table there is
        "sim.lookahead.narrow_trips": int(own.max(axis=1).sum()),
        "sim.lookahead.narrowest_trips": int(own.max(axis=1).sum()),
        "sim.lookahead.dep_slots": int(et.pads.n_deps),
        "sim.lookahead.dep_slots_used": int(et.pads.n_deps_used),
        "sim.lookahead.dep_slots_decided":
            int(np.asarray(et.tables["n_deps"])[row].sum()),
        "sim.lookahead.dep_slots_offered":
            int(ran.sum()) * int(et.pads.n_deps),
        # PR 48's: the ops of each decided job, written out from the
        # tables' degree-1 rows (an unsplit job's op slots)
        "sim.lookahead.ops_decided":
            int(np.asarray(et.tables["n_ops"])[
                ep["jtype"][ran] * len(et.degrees) + column[1]].sum()),
        "env.obs.nodes_real":
            int(ot["node_split"][:, 0][ep["jtype"]].sum()),
        "env.obs.nodes_padded":
            ep["jtype"].size * int(ot["node_features"].shape[1]),
        "env.decisions.offered": accepted.size,
        "env.decisions.accepted": int(accepted.sum()),
        "env.decisions.blocked_placement":
            int((ep["cause"] == CAUSE_OP_PLACEMENT).sum()),
        "env.decisions.offered_longest": int(longest.sum()),
        "env.decisions.accepted_longest": int(accepted[longest].sum()),
        "env.cluster.occupied_servers": int(ep["n_occupied"].sum()),
        "env.cluster.servers": accepted.size * et.n_srv,
        # PR 39's two, written out from the tables themselves: the
        # chosen row has a forward op split fewer ways than its degree
        "env.decisions.offered_ragged": int(ragged.sum()),
        "env.decisions.accepted_ragged": int(accepted[ragged].sum()),
    }
    assert {k: counters[k] for k in parent} == parent
    # what is left of the listed names are start-up gauges counted once
    # a drained trace (the lookahead's minor axis, the mask's rows, an
    # architecture's bank): none set in this process, none counted
    rest = _listed_counter_names() - set(parent)
    assert rest and all(
        name.startswith(("sim.lookahead.minor_", "env.mask.rows_",
                         "graphs.arch.")) for name in rest)
    assert not rest & set(counters)


# ------------------------------------------------------ start-up spans
def test_build_run_leaves_each_startup_span_once(fused_dataset, tmp_path,
                                                 capsys):
    """``build_run`` at a tiny size: every phase once, the phases inside
    ``startup.build_run`` and no longer than it together; the first
    fused epoch is its own span with jax's durations beside it; global
    telemetry stays off throughout."""
    scripts = os.path.join(REPO, "scripts")
    if scripts not in sys.path:
        sys.path.insert(0, scripts)
    import train_from_config

    from ddls_tpu.config import load_config
    from ddls_tpu.sim import jax_env
    from ddls_tpu.train.compat import apply_reference_compat

    sys.path.insert(0, os.path.join(REPO, "tests", "benchmarks"))
    import bench_tiny

    cfg = load_config(
        os.path.join(scripts, "ramp_job_partitioning_configs"),
        "rllib_config",
        [*bench_tiny.TINY_OVERRIDES, *bench_tiny.COMMON,
         "epoch_loop.loop_mode=fused", "epoch_loop.updates_per_epoch=1",
         "epoch_loop.fused_config={lanes: 4, segment_len: 2}",
         "epoch_loop.num_envs=4", "epoch_loop.rollout_length=2",
         "epoch_loop.n_devices=1", "experiment.train_seed=0",
         f"experiment.path_to_save={tmp_path}", "experiment.name=t"])
    apply_reference_compat(cfg)
    loop = train_from_config.build_run(cfg).epoch_loop
    try:
        reg = startup.registry()
        spans = {}
        for name, t0, t1 in reg.span_intervals():
            spans.setdefault(name, []).append((t0, t1))
        phases = ["startup.config", "startup.env", "startup.model",
                  "startup.learner", "startup.fused_build"]
        nested = ["startup.device_tables", "startup.job_banks"]
        for name in ["startup.build_run", *phases, *nested]:
            assert len(spans.get(name, ())) == 1, (name, sorted(spans))
        (b0, b1), = spans["startup.build_run"]
        for name in phases:
            (t0, t1), = spans[name]
            assert b0 <= t0 <= t1 <= b1, name
        f0, f1 = spans["startup.fused_build"][0]
        for name in nested:
            (t0, t1), = spans[name]
            assert f0 <= t0 <= t1 <= f1, name
        assert sum(spans[n][0][1] - spans[n][0][0]
                   for n in phases) <= b1 - b0
        # what the process did before build_run, once, ending where
        # build_run starts
        (p0, p1), = spans["startup.before_build"]
        assert p0 < p1 <= b0
        assert "startup.first_epoch" not in spans
        assert "startup.jax.trace" in spans    # model init traced a jit

        loop.run()
        loop.run()
        first = [(t0, t1) for n, t0, t1 in reg.span_intervals()
                 if n == "startup.first_epoch"]
        assert len(first) == 1, "only the first epoch is start-up"
        f0, f1 = first[0]
        inside = {n for n, t0, t1 in reg.span_intervals()
                  if n.startswith("startup.jax.") and f0 <= t0 and t1 <= f1}
        assert inside == {"startup.jax.trace", "startup.jax.lower",
                          "startup.jax.compile"}
        assert not telemetry.enabled() and telemetry.snapshot() == {}
        # the registry's reader: every span name, once, in one line
        line = capsys.readouterr().out.splitlines()
        report, = [ln for ln in line if ln.startswith("[startup] ")]
        assert report == startup.report()
        seconds = json.loads(report[len("[startup] "):])
        # ... then the gauges: the epoch program's lookahead loop is
        # lane-packed, the driver's (few) lanes x 16 shards on its minor
        # axis
        assert loop.fused.num_lanes < 128
        pads = loop.fused.et.pads
        minor = pads.max_split * loop.fused.num_lanes
        # ... and pricing and the channel / server checks of the
        # program's `eval_cfg` index no dep, nor its placement scan a
        # cell, a server or a sub-op
        # ... and of the rows the mask offers on an empty cluster the
        # allocator places every one (small synthetic jobs)
        # ... and the pads the device tables carry — the rung of the
        # halving ladder that holds the largest synthetic graph — beside
        # the env's configured ones
        # ... and the GNN's aggregation in the update, at the update's
        # minibatch of the observation the tables carry: the index form
        # on a CPU (6 scatter-adds + 4 gathers of >= B*E indices)
        gauges = startup.gauges()
        obs0 = loop.vec_env.obs[0]
        configured = (obs0["node_features"].shape[0],
                      obs0["edge_features"].shape[0])
        carried = jax_env.obs_pads(loop.fused.ot)
        assert (configured, carried) == ((150, 512), (38, 128))
        minibatch = min(loop.learner.cfg.sgd_minibatch_size, 4 * 2)
        incidence = minibatch * carried[0] * carried[1]
        offered = gauges["env.mask.rows_offered"]
        # the arithmetic is tests/test_jax_memo.py's
        table_bytes = sum(
            leaf.nbytes for leaf in loop.fused._state[1].values())
        assert table_bytes > loop.fused.num_lanes * 64 * 2 * pads.n_deps * 4
        assert offered == len(loop.fused.et.types) * sum(
            bool(loop.fused.ot["shapes_exist"][d]) or d == 1
            for d in loop.fused.et.degrees)
        assert gauges == {
            "sim.lookahead.minor_slots": -(-minor // 128) * 128,
            "sim.lookahead.minor_used": minor,
            # 8 servers under a block side of 8: one width
            "sim.lookahead.channel_widths": [8],
            # a lane-packed first stage: both endpoint primitives contract
            "sim.lookahead.endpoint_onehot_elems": 0,
            # ... and one width has no form by server: `nominate`
            # compares 2 * B*S*W*L*S + 2 * B*W*W*L*S elements with the
            # worker iota a trip
            "sim.lookahead.channel_onehot_elems": 2 * pads.n_blocks * (
                pads.max_split * 8 * minor + 8 * 8 * minor),
            "sim.price.dep_indexed_ops": 0,
            "sim.allocate.indexed_ops": 0,
            # the lanes' memo tables, from their shapes: what the epoch
            # program's scratch is read against (under a half of it:
            # updated in place; near 1: a copy of the tables is back)
            "sim.memo.table_bytes": table_bytes,
            "env.mask.rows_offered": offered,
            "env.mask.rows_placeable": offered,
            **dict(zip(jax_env.OBS_PAD_GAUGES, carried + configured)),
            "gnn.aggregate.indexed_ops": 10,
            "gnn.aggregate.incidence_elems": incidence}
        order = list(seconds)
        assert (order.index("sim.price.dep_indexed_ops")
                == order.index("sim.allocate.indexed_ops") - 1
                < order.index("gnn.aggregate.indexed_ops")
                < order.index("gnn.aggregate.incidence_elems"))
        assert set(seconds) == {n.removeprefix("startup.")
                                for n, _, _ in reg.span_intervals()} \
            | set(startup.gauges())
        assert seconds["first_epoch"] > 0
    finally:
        loop.close()



# ------------------------------------------ the aggregation's gauge
@pytest.mark.parametrize("form,indexed", [("segment", 10), ("dense", 0)])
def test_aggregate_gauge_counts_the_updates_indexed_ops(form, indexed,
                                                        monkeypatch):
    """`gnn.aggregate.indexed_ops` over the forward + backward of one
    update minibatch: 6 scatter-adds + 4 gathers of >= B*E indices in
    the index form, none when every aggregation is a contraction; and
    the PPO minibatch step's own traced program says the same (what it
    holds beside them — `take_along_axis` over B actions — is under the
    threshold). `gnn.aggregate.incidence_elems` is B*N*E."""
    import jax

    import test_rl
    from ddls_tpu.models import policy
    from ddls_tpu.ops import segment
    from ddls_tpu.parallel import make_mesh
    from ddls_tpu.utils.jaxprs import indexed_ops

    monkeypatch.setattr(segment, "aggregate_form",
                        lambda platform, n_nodes, n_edges: form)
    B, N, E = 8, test_rl.MAX_NODES, test_rl.MAX_EDGES
    rng = np.random.RandomState(3)
    obs = test_rl._fake_obs(rng, (B,))
    model = policy.GNNPolicy(n_actions=test_rl.N_ACTIONS)
    params = model.init(jax.random.PRNGKey(0),
                        jax.tree_util.tree_map(lambda x: x[0], obs))
    learner = test_rl._make_learner(make_mesh(1), model)
    spec = jax.tree_util.tree_map(
        lambda x: jax.ShapeDtypeStruct(x.shape, x.dtype), obs)
    assert policy.aggregate_gauges(learner.apply_fn, params, spec) == (
        indexed, B * N * E)

    state = learner.init_state(params)
    per_sample = jax.ShapeDtypeStruct((B,), np.float32)
    minibatch = {"obs": spec,
                 "actions": jax.ShapeDtypeStruct((B,), np.int32),
                 **{k: per_sample for k in (
                     "old_logp", "old_values", "advantages",
                     "value_targets")}}
    step = jax.make_jaxpr(learner._minibatch_step)(state, minibatch)
    found = indexed_ops(step.jaxpr, B * E)
    assert len(found) == indexed
    assert sorted(set(found)) == (["gather", "scatter-add"] if indexed
                                  else [])
    assert found.count("gather") == (4 if indexed else 0)
    assert indexed_ops(step.jaxpr, B)     # the action read, the shuffle
