"""In-kernel lookahead memo (sim/jax_memo.py, ISSUE 13 + 17).

Unit level: a forced hash collision must MISS (bitwise residual compare)
and recompute — never serve the colliding entry; eviction is
deterministic round-robin; the canonical grouping matches the host's
``np.unique``-based canonicalisation (cluster.py:468-476).

Kernel level: a memo-enabled segment is BITWISE identical to a memo-off
segment (traces, bootstrap fields) — the hit==recompute contract — AT
EVERY VMAP WIDTH (lanes 1, 2 and 8 — the wide batched probe, ISSUE 17),
the table persists across in-kernel episode resets exactly like the
host ``lookahead_cache`` persists across ``reset()`` (misses stop
growing once the first episode has populated the table), per-lane
counters drain independently, and the hit rate on a repeated-placement
episode is strictly positive. The x64 leg of the hit==recompute
contract rides the EXISTING full-episode parity suites
(test_jax_episode / test_jax_policy_episode run the episode kernels
with the memo enabled by default and pin them against the host
simulator exactly).

Loop level: a lanes=1 fused epoch loop resolves the memo ON by default,
stays transfer-free in steady state under ``jax.transfer_guard``, and
reports counters at the drain boundary only; multi-lane collectors
resolve the memo ON too (resolve_memo_cfg "auto" at every width).
"""
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)


# ============================================================ unit level
class _EtStub:
    """Minimal et for memo_init: pads + the dtype-bearing table."""

    def __init__(self, n_ops=4, n_deps=6):
        import types

        self.pads = types.SimpleNamespace(n_ops=n_ops, n_deps=n_deps)
        self.tables = {"dep_size": np.zeros(n_deps, np.float32)}


def _key(seed, n_ops=4, n_deps=6):
    import jax.numpy as jnp

    r = np.random.RandomState(seed)
    groups = jnp.asarray(r.randint(0, 3, n_ops), jnp.int32)
    times = jnp.asarray(r.rand(n_deps), jnp.float32)
    return jnp.int32(0), groups, times


def _probe(memo, key, value, ok=True, void=None):
    from ddls_tpu.sim.jax_memo import memo_lookahead

    import jax.numpy as jnp

    # compute takes the probe's hit flag (the wide-probe mask the real
    # caller threads into jax_lookahead's while_loop cond); a plain
    # value ignores it
    (t, ok), memo = memo_lookahead(
        memo, *key, lambda skip: (jnp.float32(value), jnp.bool_(ok)),
        None if void is None else jnp.bool_(void))
    return float(t), memo


@pytest.mark.parametrize("void_first", [True, False])
def test_a_void_probe_neither_counts_nor_enters_the_table(void_first):
    """A job that did not place probes under the key of the ops that
    DID place, which a later complete placement can share: its "stuck"
    must not be what that one is served (PR 36: Trinity-Mini's head on a
    ninth server, 8,192 x 4 at degree 1)."""
    from ddls_tpu.sim.jax_memo import MemoConfig, memo_init

    memo = memo_init(_EtStub(), MemoConfig(n_sets=1, n_ways=1))
    a = _key(1)
    if void_first:
        before = {k: np.asarray(v) for k, v in memo.items()}
        _, memo = _probe(memo, a, 0.0, ok=False, void=True)
        for k in before:
            assert np.array_equal(before[k], np.asarray(memo[k])), k
    t, memo = _probe(memo, a, 1.5, void=False)
    assert t == 1.5 and int(memo["misses"]) == 1
    # resident now: a void probe of the same key changes nothing either
    _, memo = _probe(memo, a, 0.0, ok=False, void=True)
    assert (int(memo["hits"]), int(memo["misses"]),
            int(memo["evicts"])) == (0, 1, 0)
    t, memo = _probe(memo, a, 9.5, void=False)
    assert t == 1.5 and int(memo["hits"]) == 1


def test_forced_hash_collision_recomputes_never_serves_colliding_entry():
    from ddls_tpu.sim.jax_memo import MemoConfig, memo_init

    et = _EtStub()
    # ONE set, ONE way: every distinct key collides by construction
    memo = memo_init(et, MemoConfig(n_sets=1, n_ways=1))
    a, b = _key(1), _key(2)
    t, memo = _probe(memo, a, 1.5)      # miss: insert A
    assert t == 1.5
    t, memo = _probe(memo, b, 2.5)      # collides with A's set/way
    assert t == 2.5, "collision served the colliding entry's value"
    assert int(memo["misses"]) == 2 and int(memo["hits"]) == 0
    assert int(memo["evicts"]) == 1     # B evicted A (1-way set)
    t, memo = _probe(memo, b, 9.5)      # B now resident: hit serves 2.5
    assert t == 2.5
    assert int(memo["hits"]) == 1
    t, memo = _probe(memo, a, 7.25)     # A was evicted: recompute
    assert t == 7.25


def test_eviction_is_deterministic_round_robin():
    import jax

    from ddls_tpu.sim.jax_memo import MemoConfig, memo_init

    et = _EtStub()
    keys = [_key(s) for s in (1, 2, 3)]

    def drive():
        memo = memo_init(et, MemoConfig(n_sets=1, n_ways=2))
        for i, k in enumerate(keys):
            _, memo = _probe(memo, k, float(i))
        return memo

    m1, m2 = drive(), drive()
    # identical decision stream -> bit-identical table (incl. rr state)
    for k in m1:
        assert np.array_equal(np.asarray(m1[k]), np.asarray(m2[k])), k
    # key 3 evicted way 0 (round-robin): key 1 misses, keys 2/3 hit
    memo = m1
    t, memo = _probe(memo, keys[1], 8.0)
    assert t == 1.0  # hit: stored value
    t, memo = _probe(memo, keys[2], 8.0)
    assert t == 2.0  # hit: stored value
    t, memo = _probe(memo, keys[0], 8.0)
    assert t == 8.0  # evicted: recompute
    del jax


def test_zero_vs_negative_zero_times_never_alias():
    import jax.numpy as jnp

    from ddls_tpu.sim.jax_memo import MemoConfig, memo_init

    et = _EtStub()
    memo = memo_init(et, MemoConfig(n_sets=1, n_ways=2))
    cfg, groups, _ = _key(1)
    tz = jnp.zeros(6, jnp.float32)
    t, memo = _probe(memo, (cfg, groups, tz), 1.0)
    # -0.0 == 0.0 under float ==, but the probe compares BIT patterns
    t, memo = _probe(memo, (cfg, groups, -tz), 2.0)
    assert t == 2.0 and int(memo["hits"]) == 0


def test_canonical_groups_matches_host_canonicalisation():
    import jax.numpy as jnp

    from ddls_tpu.sim.jax_memo import canonical_groups

    r = np.random.RandomState(7)
    for _ in range(20):
        n = int(r.randint(1, 12))
        sc = r.randint(0, 5, n)
        n_valid = int(r.randint(1, n + 1))
        valid = np.zeros(n, bool)
        valid[:n_valid] = True
        # the host's vectorised first-appearance renumbering
        # (cluster.py:468-476) over the valid prefix
        _, first_idx, inv = np.unique(sc[:n_valid], return_index=True,
                                      return_inverse=True)
        rank = np.argsort(np.argsort(first_idx))
        want = np.full(n, -1, np.int32)
        want[:n_valid] = rank[inv]
        got = np.asarray(canonical_groups(jnp.asarray(sc, jnp.int32),
                                          jnp.asarray(valid)))
        assert np.array_equal(got, want), (sc, valid, got, want)


#: a probe stream that misses, hits, evicts and voids at every geometry
#: below: (key seed, void) a step; a void probe of a resident key, of an
#: absent one, and a key that comes back after its eviction
_STREAM = ((1, False), (1, False), (2, False), (1, False), (3, True),
           (3, False), (3, False), (4, False), (2, True), (5, False),
           (1, False), (6, False), (2, False), (5, False), (7, True),
           (1, False))


@pytest.mark.parametrize("lanes", [0, 3])
@pytest.mark.parametrize("n_sets,n_ways", [(1, 1), (2, 2), (64, 2)])
def test_probe_then_commit_is_the_one_function_memo_leaf_for_leaf(
        n_sets, n_ways, lanes):
    """`memo_probe` + `memo_commit` against the form the package had up
    to PR 48 (`tests/onefn_memo.py`, which returned the tables from the
    probe's own body): the value served, every table leaf, `rr` and
    the three counters equal after EVERY step of a stream that misses,
    hits, evicts and voids — unbatched (``lanes`` 0) and under a
    3-lane ``vmap`` whose lanes probe different keys. Only where the
    write is issued moved."""
    import jax
    import jax.numpy as jnp

    import onefn_memo
    from ddls_tpu.sim import jax_memo

    et = _EtStub()
    memo0 = jax_memo.memo_init(et, jax_memo.MemoConfig(n_sets, n_ways))

    def split(memo, cfg, groups, times, value, void):
        (t, ok), pending = jax_memo.memo_probe(
            memo, cfg, groups, times, lambda skip: (value, value > 2),
            void)
        assert tuple(pending) == jax_memo.PENDING_KEYS
        return (t, ok), jax_memo.memo_commit(memo, pending)

    def package(memo, cfg, groups, times, value, void):
        return jax_memo.memo_lookahead(
            memo, cfg, groups, times, lambda skip: (value, value > 2),
            void)

    def reference(memo, cfg, groups, times, value, void):
        return onefn_memo.memo_lookahead(
            memo, cfg, groups, times, lambda skip: (value, value > 2),
            void)

    forms = [split, package, reference]
    if lanes:
        memo0 = jax.tree_util.tree_map(
            lambda x: jnp.stack([x] * lanes), memo0)
        forms = [jax.vmap(f) for f in forms]
    forms = [jax.jit(f) for f in forms]
    memos = [memo0] * len(forms)
    for step, (seed, void) in enumerate(_STREAM):
        if lanes:
            # lane l walks the stream l steps ahead, so one batched
            # scatter holds hits, misses and voids side by side
            rows = [_STREAM[(step + lane) % len(_STREAM)]
                    for lane in range(lanes)]
            keys = [_key(sd) for sd, _ in rows]
            args = (jnp.stack([k[0] for k in keys]),
                    jnp.stack([k[1] for k in keys]),
                    jnp.stack([k[2] for k in keys]),
                    jnp.full((lanes,), step + 0.5, jnp.float32),
                    jnp.asarray([v for _, v in rows]))
        else:
            args = (*_key(seed), jnp.float32(step + 0.5),
                    jnp.bool_(void))
        outs = [f(m, *args) for f, m in zip(forms, memos)]
        memos = [m for _, m in outs]
        (t_ref, ok_ref), memo_ref = outs[-1]
        for (t, ok), memo in outs[:-1]:
            assert np.array_equal(np.asarray(t), np.asarray(t_ref)), step
            assert np.array_equal(np.asarray(ok), np.asarray(ok_ref))
            assert tuple(memo) == tuple(memo_ref)
            for leaf in memo_ref:
                assert np.array_equal(np.asarray(memo[leaf]),
                                      np.asarray(memo_ref[leaf])), (
                    step, leaf)
    seen = {k: int(np.sum(np.asarray(memos[0][k])))
            for k in jax_memo.COUNTER_KEYS}
    assert seen["hits"] > 0 and seen["misses"] > 0, seen
    if n_sets < 64:
        assert seen["evicts"] > 0, seen
    # a void probe counted nowhere
    voids = sum(v for _, v in _STREAM) * max(lanes, 1)
    assert (seen["hits"] + seen["misses"]
            == len(_STREAM) * max(lanes, 1) - voids)


def test_table_bytes_is_the_leaves_own_bytes():
    """`sim.memo.table_bytes`' arithmetic: every leaf of the carried
    (lane-stacked) state, from its shape; 0 with the memo off."""
    import jax
    import jax.numpy as jnp

    from ddls_tpu.sim.jax_memo import MemoConfig, memo_init, table_bytes

    memo = memo_init(_EtStub(n_ops=4, n_deps=6), MemoConfig(8, 2))
    one = (8 * 2 * (4 + 4 * 4 + 6 * 4 + 4 + 1)   # keys and values
           + 8 * 4 + 3 * 4)                       # rr, three counters
    assert table_bytes(memo) == one
    stacked = jax.tree_util.tree_map(lambda x: jnp.stack([x] * 5), memo)
    assert table_bytes(stacked) == 5 * one
    assert table_bytes(None) == 0


def test_memo_knob_rejected_loudly_without_device_collection():
    """Forcing the knob on a host-collection loop must fail before any
    env construction (the loud-rejection convention: a silent no-op
    would let a memo-off run masquerade as memo-on in comparisons)."""
    from ddls_tpu.train import make_epoch_loop

    with pytest.raises(ValueError, match="use_jax_lookahead_memo"):
        make_epoch_loop("ppo", path_to_env_cls=ENV_CLS, env_config={},
                        algo_config={"use_jax_lookahead_memo": True})


def test_resolve_memo_cfg_knob():
    from ddls_tpu.sim.jax_memo import MemoConfig, resolve_memo_cfg

    assert resolve_memo_cfg("auto", 1) == MemoConfig()
    # ISSUE 17: "auto" enables the memo at EVERY lane count — the
    # batched probe masks hit lanes out of the lookahead while_loop
    assert resolve_memo_cfg("auto", 8) == MemoConfig()
    assert resolve_memo_cfg(None, 1) is None
    assert resolve_memo_cfg(None, 8) is None
    cfg = MemoConfig(n_sets=4, n_ways=1)
    assert resolve_memo_cfg(cfg, 8) is cfg
    with pytest.raises(ValueError, match="memo_cfg"):
        resolve_memo_cfg(True, 1)
    with pytest.raises(ValueError, match="n_lanes"):
        resolve_memo_cfg("auto", 0)


# ========================================================== kernel level
ENV_CLS = "ddls_tpu.envs.partitioning_env.RampJobPartitioningEnvironment"

_TINY_MODEL = {"fcnet_hiddens": [16],
               "custom_model_config": {"out_features_msg": 4,
                                       "out_features_hidden": 8,
                                       "out_features_node": 4,
                                       "out_features_graph": 4}}


@pytest.fixture(scope="module")
def memo_env(tmp_path_factory):
    """Small canonical env + tables + tiny policy, shared by the kernel-
    and loop-level tests (one dataset, one table build)."""
    import jax
    import jax.numpy as jnp

    from ddls_tpu.envs import RampJobPartitioningEnvironment
    from ddls_tpu.graphs.synthetic import generate_pipedream_txt_files
    from ddls_tpu.models.policy import GNNPolicy
    from ddls_tpu.sim.jax_env import (build_episode_tables,
                                      build_job_bank, build_obs_tables)

    d = str(tmp_path_factory.mktemp("memo_jobs"))
    generate_pipedream_txt_files(d, n_cnn=1, n_translation=1, seed=9,
                                 min_ops=4, max_ops=6)
    env_config = dict(
        topology_config={"type": "ramp", "kwargs": {
            "num_communication_groups": 2,
            "num_racks_per_communication_group": 2,
            "num_servers_per_rack": 2, "num_channels": 1,
            "total_node_bandwidth": 1.6e12,
            "intra_gpu_propagation_latency": 50e-9,
            "worker_io_latency": 100e-9}},
        node_config={"type_1": {"num_nodes": 8, "workers_config": [
            {"num_workers": 1, "worker": "A100"}]}},
        jobs_config={"path_to_files": d,
                     "job_interarrival_time_dist": {
                         "_target_":
                             "ddls_tpu.demands.distributions.Fixed",
                         "val": 60.0},
                     "max_acceptable_job_completion_time_frac_dist": {
                         "_target_":
                             "ddls_tpu.demands.distributions.Uniform",
                         "min_val": 0.2, "max_val": 1.0, "decimals": 2},
                     "replication_factor": 10,
                     "job_sampling_mode": "remove_and_repeat",
                     "num_training_steps": 10},
        max_partitions_per_op=4, min_op_run_time_quantum=0.01,
        reward_function="job_acceptance", max_simulation_run_time=6e2,
        pad_obs_kwargs={"max_nodes": 32, "max_edges": 64})
    env = RampJobPartitioningEnvironment(**env_config)
    obs0 = env.reset(seed=0)
    et = build_episode_tables(env)
    ot = build_obs_tables(env, et)
    model = GNNPolicy(n_actions=5, out_features_msg=4,
                      out_features_hidden=8, out_features_node=4,
                      out_features_graph=4, fcnet_hiddens=(16,))
    params = model.init(jax.random.PRNGKey(0),
                        jax.tree_util.tree_map(jnp.asarray, obs0))
    r = np.random.RandomState(0)
    recs = [{"model": et.types[int(r.randint(0, len(et.types)))],
             "num_training_steps": 10,
             "sla_frac": round(float(r.uniform(0.2, 1.0)), 2),
             "time_arrived": 60.0 * i} for i in range(12)]
    bank = {k: jnp.asarray(v)
            for k, v in build_job_bank(et, recs).items()}
    return {"dataset": d, "env": env, "env_config": env_config,
            "et": et, "ot": ot, "model": model, "params": params,
            "bank": bank}


class _ReplayPolicy:
    """A deterministic, state-dependent stand-in for the policy forward:
    the preferred action rotates with the observation, so one episode
    visits several (job, degree, cluster-state) memo keys — and, because
    nothing is sampled, every later episode of the same bank replays
    exactly the same decisions. That makes the persistence pin exact: a
    SAMPLED stream (the pins were first written against one, and its
    bits change with the jax version) legitimately reaches new keys in
    later episodes, which reads as a miss whether or not the table
    survived the reset. ``masked=False`` rotates over ALL of
    0 .. n - 1: the stream then also holds action 0 and the odd actions
    the mask keeps out, which the kernel takes down the zero path
    (`sim/jax_env.py:decision`'s ``action_ok``)."""

    def __init__(self, masked: bool = True):
        self.masked = masked

    def apply(self, params, obs):
        import jax.numpy as jnp

        mask = obs["action_mask"] > 0
        n = mask.shape[-1]
        rot = jnp.floor(obs["graph_features"].sum() * 97.0).astype(
            jnp.int32)
        pref = (jnp.arange(n, dtype=jnp.int32) + rot) % n
        logits = pref.astype(jnp.float32) * 1e3
        if self.masked:
            logits = jnp.where(mask, logits, -1e9)
        return logits, jnp.float32(0.0)


def test_segment_memo_bitwise_parity_and_cross_reset_persistence(
        memo_env):
    """The load-bearing kernel pin: memo-on == memo-off BITWISE across
    three carried segments spanning multiple in-kernel episode resets;
    the memo persists across those resets (on the replayed action
    stream misses FREEZE once the first episode populated the table —
    the host lookahead_cache contract), and the repeated-placement hit
    rate is > 0."""
    import jax

    from ddls_tpu.sim.jax_env import make_segment_fn, segment_init
    from ddls_tpu.sim.jax_memo import MemoConfig

    et, ot = memo_env["et"], memo_env["ot"]
    model, params, bank = (_ReplayPolicy(), memo_env["params"],
                           memo_env["bank"])
    mc = MemoConfig(n_sets=16, n_ways=2)
    seg_on = make_segment_fn(et, ot, model, 24, memo_cfg=mc)
    seg_off = make_segment_fn(et, ot, model, 24)
    st_on = segment_init(et, bank, mc)
    st_off = segment_init(et, bank)
    rng = jax.random.PRNGKey(7)
    dones = 0
    miss_curve, hit_curve = [], []
    for _ in range(3):
        rng, sub = jax.random.split(rng)
        st_on, tr_on, nf_on = seg_on(bank, params, st_on, sub)
        st_off, tr_off, nf_off = seg_off(bank, params, st_off, sub)
        for k in tr_off:  # identical actions/rewards/counters/fields
            assert np.array_equal(np.asarray(tr_on[k]),
                                  np.asarray(tr_off[k])), k
        for k in nf_off:  # identical bootstrap fields
            assert np.array_equal(np.asarray(nf_on[k]),
                                  np.asarray(nf_off[k])), k
        dones += int(np.asarray(tr_on["done"]).sum())
        miss_curve.append(int(np.asarray(tr_on["memo_misses"])[-1]))
        hit_curve.append(int(np.asarray(tr_on["memo_hits"])[-1]))
    assert dones >= 2, "horizon must complete episodes for this pin"
    # cross-reset persistence: every episode after the first replays
    # bank placements already in the table — misses stop growing (and
    # one episode visits several keys, so the freeze is not vacuous)
    assert miss_curve[0] > 1
    assert miss_curve[1] == miss_curve[0] == miss_curve[2], miss_curve
    # repeated-placement hit rate > 0 (ISSUE 13 satellite)
    assert hit_curve[-1] > 0
    assert hit_curve[-1] / (hit_curve[-1] + miss_curve[-1]) > 0.5


def _lane_banks(memo_env, n_lanes):
    """``n_lanes`` DISTINCT job banks (different sla/type streams per
    lane) stacked on a leading lane axis — distinct lanes make the wide
    probe's per-lane tables genuinely diverge."""
    import jax.numpy as jnp

    from ddls_tpu.sim.jax_env import build_job_bank

    et = memo_env["et"]
    banks = []
    for lane in range(n_lanes):
        r = np.random.RandomState(100 + lane)
        recs = [{"model": et.types[int(r.randint(0, len(et.types)))],
                 "num_training_steps": 10,
                 "sla_frac": round(float(r.uniform(0.2, 1.0)), 2),
                 "time_arrived": 60.0 * i} for i in range(12)]
        banks.append({k: jnp.asarray(v)
                      for k, v in build_job_bank(et, recs).items()})
    return {k: jnp.stack([b[k] for b in banks]) for k in banks[0]}


def _free_and_full_carries(memo_env):
    """The kernel's fresh cluster state, and the same with every server
    held by a running job: nothing places there."""
    import jax.numpy as jnp

    from ddls_tpu.sim.jax_env import _episode_kernels

    k = _episode_kernels(memo_env["et"])
    free = k.init_state(memo_env["bank"])[0]
    full = (*free[:2], jnp.zeros_like(free[2]), *free[3:])
    return k, free, full


@pytest.mark.parametrize("lane", ["zero_path", "void", "no_job"])
def test_a_lane_that_inserts_nothing_leaves_its_memo_bit_equal(
        memo_env, lane):
    """The three lanes whose pending entry has ``miss`` false — an
    action on the zero path (`memo_pending_none` out of the decision's
    ``cond``), a job that did not place (a void probe) and a scan step
    with no queued job (`memo_pending_none` out of the episode
    kernels' ``cond``) — beside a lane that misses, in ONE batched
    commit: the first lane's every leaf and counter stay bit-equal,
    the second's table takes its entry."""
    import jax
    import jax.numpy as jnp

    from ddls_tpu.sim import jax_env, jax_memo

    k, free, full = _free_and_full_carries(memo_env)
    bank = memo_env["bank"]
    row = jnp.int32(0)
    degrees = memo_env["et"].degrees
    first, second = int(degrees[-1]), int(degrees[-2])

    def lane_step(memo, carry, action, has_job):
        # the episode kernels' form: the pending entry out of the cond,
        # one commit after it
        def run():
            _, outs, pending = k.decision(bank, carry, action, row, memo)
            return outs[2], pending

        def skip():
            return jnp.int32(-1), jax_memo.memo_pending_none(memo)

        cause, pending = jax.lax.cond(has_job, run, skip)
        return cause, jax_memo.memo_commit(memo, pending)

    # a table that already holds an entry, so "unchanged" is not "empty"
    memo0 = jax_memo.memo_init(memo_env["et"], jax_memo.MemoConfig(2, 1))
    cause, memo1 = jax.jit(lane_step)(memo0, free, jnp.int32(first),
                                      jnp.bool_(True))
    assert int(cause) != jax_env.CAUSE_OP_PLACEMENT
    assert int(memo1["misses"]) == 1

    carry, action, has_job, want = {
        "zero_path": (free, 0, True, jax_env.CAUSE_NOT_HANDLED),
        "void": (full, first, True, jax_env.CAUSE_OP_PLACEMENT),
        "no_job": (free, first, False, -1)}[lane]
    stack = lambda a, b: jax.tree_util.tree_map(   # noqa: E731
        lambda x, y: jnp.stack([x, y]), a, b)
    causes, memos = jax.jit(jax.vmap(lane_step))(
        stack(memo1, memo1), stack(carry, free),
        jnp.asarray([action, second], jnp.int32),
        jnp.asarray([has_job, True]))
    assert int(causes[0]) == want
    for leaf in memo1:
        assert np.array_equal(np.asarray(memos[leaf][0]),
                              np.asarray(memo1[leaf])), leaf
    # the neighbour missed (another degree: another key) and its entry
    # went in through the same batched scatter
    assert int(memos["misses"][1]) == 2
    assert int(memos["hits"][1]) == 0


def _memo_shaped(jaxpr, shapes):
    """The ``cond`` equations of ``jaxpr`` (nested jaxprs included) with
    an output of one of ``shapes``, and the ``select_n`` equations with
    one that CHOOSE: ``vmap``'s rule for a ``cond`` also wraps every
    operand the branches read in ``select_n(pred, stop_gradient(x),
    x)`` — both cases the one array, which the compiler folds to ``x``
    — and those are not selects of anything."""
    from ddls_tpu.utils.jaxprs import equations

    found, same = [], {}
    for eqn in equations(jaxpr):
        name = eqn.primitive.name
        if name == "stop_gradient":
            same[eqn.outvars[0]] = same.get(eqn.invars[0], eqn.invars[0])
        chooses = name == "cond" or (
            name == "select_n"
            and len({same.get(v, v) for v in eqn.invars[1:]}) > 1)
        if chooses:
            found += [(name, v.aval.shape) for v in eqn.outvars
                      if v.aval.shape in shapes]
    return found


@pytest.mark.parametrize("lanes", [1, 3])
@pytest.mark.parametrize("kernel", ["segment", "episode",
                                    "policy_episode", "oracle_episode"])
def test_no_cond_and_no_select_returns_a_memo_table(memo_env, kernel,
                                                    lanes):
    """NO ``cond`` returns a memo (PR 49): the traced program of each
    of the four kernels — lane-batched as the fused driver, `es_device`
    and the policy-episode collector run them, and at one lane, where
    the ``cond`` stays a branch — holds no ``select_n`` and no ``cond``
    with an output of a table leaf's shape. Under ``vmap`` a ``cond``
    that returned the tables was both branches and a select over them,
    whole, and a copy for the select to read: two passes over 2.2-2.9
    GB an epoch's lanes on the chip. (On the parent every case fails:
    six leaves out of the decision's ``cond``, six more out of an
    episode kernel's ``has_job`` one.)"""
    import jax
    import jax.numpy as jnp

    from ddls_tpu.sim import jax_env, jax_memo
    from ddls_tpu.utils.jaxprs import equations

    et, ot, params = memo_env["et"], memo_env["ot"], memo_env["params"]
    model = memo_env["model"]
    # a geometry no other array of the program shares
    mc = jax_memo.MemoConfig(n_sets=37, n_ways=3)
    banks = _lane_banks(memo_env, lanes)
    rngs = jax.random.split(jax.random.PRNGKey(0), lanes)
    if kernel == "segment":
        fn = jax_env.make_segment_fn(et, ot, model, 2, memo_cfg=mc)
        states = jax.vmap(lambda b: jax_env.segment_init(et, b, mc))(
            banks)
        axes, args = (0, None, 0, 0), (banks, params, states, rngs)
    elif kernel == "episode":
        fn = jax_env.make_episode_fn(et, memo_cfg=mc)
        axes = (0, 0)
        args = (banks, jnp.ones((lanes, 3), jnp.int32))
    elif kernel == "policy_episode":
        fn = jax_env.make_policy_episode_fn(et, ot, model, memo_cfg=mc)
        axes, args = (0, None, 0), (banks, params, rngs)
    else:
        fn = jax_env.make_oracle_episode_fn(et, ot, memo_cfg=mc)
        axes, args = (0,), (banks,)
    if lanes == 1:
        # one lane, no vmap: `vmap_segment_fn`'s squeeze
        take = lambda t: jax.tree_util.tree_map(   # noqa: E731
            lambda x: x[0], t)
        args = tuple(a if ax is None else take(a)
                     for a, ax in zip(args, axes))
        traced = jax.make_jaxpr(fn)(*args)
        lead = ()
    else:
        traced = jax.make_jaxpr(jax.vmap(fn, in_axes=axes))(*args)
        lead = (lanes,)
    table = jax_memo.memo_init(et, mc)
    shapes = {lead + table[leaf].shape
              for leaf in ("key_cfg", "key_groups", "key_times", "val_t",
                           "val_ok", "rr")}
    assert len(shapes) == 4
    # the tables ARE in the program (a scatter writes each) ...
    written = {v.aval.shape for eqn in equations(traced.jaxpr)
               if eqn.primitive.name.startswith("scatter")
               for v in eqn.outvars}
    assert shapes <= written, shapes - written
    # ... and no select and no branch hands one out
    assert _memo_shaped(traced.jaxpr, shapes) == []


@pytest.mark.parametrize("lanes", [1, 3])
@pytest.mark.parametrize("memo", ["memo_on", "memo_off"])
def test_no_cond_carries_a_config_table(memo_env, memo, lanes):
    """The decision's ``cond`` takes one config's ROWS and closes over
    no ``[n_cfg, ...]`` table (PR 51). Under the lanes' ``vmap`` a
    ``cond`` with a per-lane predicate gives every operand the batch
    axis first, closed-over constants included, so a table in its
    branch was written out at ``[lanes, n_cfg, ...]`` on every
    lane-step only to be row-indexed a moment later: no equation of the
    fused path's traced kernel, at any depth, has an output of that
    shape for any leaf of ``et.tables``. At one lane
    (`vmap_segment_fn`'s squeeze) the ``cond`` stays a branch: its
    operands hold the rows and no whole table. (On the parent all four
    cases fail: 155 equations with a table-wide output at three lanes —
    ``broadcast_in_dim``, then the rule's ``stop_gradient`` and
    ``select_n`` — and 23 whole tables among the one-lane ``cond``'s
    operands.)"""
    import jax

    from ddls_tpu.sim import jax_env, jax_memo
    from ddls_tpu.utils.jaxprs import equations

    et, ot, params = memo_env["et"], memo_env["ot"], memo_env["params"]
    mc = jax_memo.MemoConfig(n_sets=37, n_ways=3) if memo == "memo_on" \
        else None
    fn = jax_env.make_segment_fn(et, ot, memo_env["model"], 2, memo_cfg=mc)
    banks = _lane_banks(memo_env, lanes)
    states = jax.vmap(lambda b: jax_env.segment_init(et, b, mc))(banks)
    rngs = jax.random.split(jax.random.PRNGKey(0), lanes)
    # a row's own shape is no table's: [n_cfg] beside [n_cfg, n_fwd]
    # where the pads make n_fwd == n_cfg
    row_shapes = {leaf.shape[1:] for leaf in et.tables.values()}
    by_dep = {et.tables[name].shape for name in (
        "dep_size", "dep_edge", "dep_sorted_rank", "dep_valid",
        "dep_mutual")}
    if lanes == 1:
        take = lambda t: jax.tree_util.tree_map(   # noqa: E731
            lambda x: x[0], t)
        traced = jax.make_jaxpr(fn)(take(banks), params, take(states),
                                    rngs[0])
        whole = {leaf.shape for leaf in et.tables.values()} - row_shapes
        assert by_dep <= whole
        operands = [v.aval.shape for eqn in equations(traced.jaxpr)
                    if eqn.primitive.name == "cond" for v in eqn.invars]
        assert [shape for shape in operands if shape in whole] == []
        assert et.tables["dep_size"].shape[1:] in operands
        return
    traced = jax.make_jaxpr(jax.vmap(fn, in_axes=(0, None, 0, 0)))(
        banks, params, states, rngs)
    wide = ({(lanes,) + leaf.shape for leaf in et.tables.values()}
            - {(lanes,) + shape for shape in row_shapes})
    assert {(lanes,) + shape for shape in by_dep} <= wide
    assert [(eqn.primitive.name, v.aval.shape)
            for eqn in equations(traced.jaxpr) for v in eqn.outvars
            if v.aval.shape in wide] == []


@pytest.mark.parametrize("memo", ["memo_on", "memo_off"])
def test_three_kinds_of_lane_in_one_batch_equal_the_one_lane_path(
        memo_env, memo):
    """A valid degree, action 0 and an odd action outside the degree
    set in ONE batch: ``vmap(segment)`` — the decision's ``cond`` a
    select over rows read above it — against the one-lane path lane by
    lane, where the ``cond`` is a branch: every trace key, the carried
    state, the memo's leaves and the bootstrap fields bit for bit, over
    two carried segments."""
    import jax

    from ddls_tpu.sim.jax_env import (make_segment_fn, segment_init,
                                      vmap_segment_fn)
    from ddls_tpu.sim.jax_memo import MemoConfig

    n_lanes, n_steps = 6, 12
    et, ot, params = memo_env["et"], memo_env["ot"], memo_env["params"]
    mc = MemoConfig(n_sets=16, n_ways=2) if memo == "memo_on" else None
    seg = make_segment_fn(et, ot, _ReplayPolicy(masked=False), n_steps,
                          memo_cfg=mc, trace_trips=True)
    wide = jax.jit(vmap_segment_fn(seg, n_lanes))
    one = jax.jit(vmap_segment_fn(seg, 1))
    banks = _lane_banks(memo_env, n_lanes)
    states = jax.vmap(lambda b: segment_init(et, b, mc))(banks)
    lane = lambda t, i: jax.tree_util.tree_map(    # noqa: E731
        lambda x: x[i:i + 1], t)
    lane_states = [lane(states, i) for i in range(n_lanes)]
    rng = jax.random.PRNGKey(5)
    kinds = set()
    for _ in range(2):
        rng, sub = jax.random.split(rng)
        rngs = jax.random.split(sub, n_lanes)
        states, trace, fields = wide(banks, params, states, rngs)
        for i in range(n_lanes):
            lane_states[i], lane_trace, lane_fields = one(
                lane(banks, i), params, lane_states[i], rngs[i:i + 1])
            for got, want in zip(
                    jax.tree_util.tree_leaves_with_path(
                        (lane(states, i), lane(trace, i),
                         lane(fields, i))),
                    jax.tree_util.tree_leaves(
                        (lane_states[i], lane_trace, lane_fields))):
                path, got = got
                got, want = np.asarray(got), np.asarray(want)
                assert got.dtype == want.dtype and np.array_equal(
                    got, want), (i, jax.tree_util.keystr(path))
        action = np.asarray(trace["action"])              # [B, T]
        kind = np.where(action == 0, 0,
                        np.where(np.isin(action, et.degrees), 1, 2))
        kinds |= {frozenset(step) for step in kind.T}
        cause = np.asarray(trace["cause"])
        assert (cause[kind != 1] == 1).all()          # CAUSE_NOT_HANDLED
        assert (cause[kind == 1] != 1).all()
        assert (np.asarray(trace["la_trips"])[kind != 1] == 0).all()
    assert frozenset({0, 1, 2}) in kinds, kinds


@pytest.mark.parametrize("n_lanes", [2, 8])
def test_vmapped_segment_memo_bitwise_parity_and_per_lane_drain(
        memo_env, n_lanes):
    """The ISSUE 17 load-bearing pin: memo-on == memo-off BITWISE under
    a multi-lane vmap (the batched probe serves stored bits to hit
    lanes and masked miss lanes iterate under their own cond), across
    carried segments spanning in-kernel episode resets; each lane's
    table persists across ITS resets (on the replayed action stream
    per-lane misses freeze once that lane's first episode populated its
    table), per-lane counters drain independently, and the lane-summed
    summary matches their total."""
    import jax

    from ddls_tpu.sim.jax_env import (make_segment_fn, segment_init,
                                      vmap_segment_fn)
    from ddls_tpu.sim.jax_memo import MemoConfig, summarize_counters

    et, ot = memo_env["et"], memo_env["ot"]
    model, params = _ReplayPolicy(), memo_env["params"]
    banks = _lane_banks(memo_env, n_lanes)
    mc = MemoConfig(n_sets=16, n_ways=2)
    seg_on = vmap_segment_fn(
        make_segment_fn(et, ot, model, 24, memo_cfg=mc), n_lanes)
    seg_off = vmap_segment_fn(
        make_segment_fn(et, ot, model, 24), n_lanes)
    st_on = jax.vmap(lambda b: segment_init(et, b, mc))(banks)
    st_off = jax.vmap(lambda b: segment_init(et, b))(banks)
    rng = jax.random.PRNGKey(11)
    dones = np.zeros(n_lanes, np.int64)
    miss_curve, hit_curve = [], []
    for _ in range(3):
        rng, sub = jax.random.split(rng)
        lane_rngs = jax.random.split(sub, n_lanes)
        st_on, tr_on, nf_on = seg_on(banks, params, st_on, lane_rngs)
        st_off, tr_off, nf_off = seg_off(banks, params, st_off,
                                         lane_rngs)
        for k in tr_off:  # identical actions/rewards/counters/fields
            assert np.array_equal(np.asarray(tr_on[k]),
                                  np.asarray(tr_off[k])), k
        for k in nf_off:  # identical bootstrap fields
            assert np.array_equal(np.asarray(nf_on[k]),
                                  np.asarray(nf_off[k])), k
        dones += np.asarray(tr_on["done"]).sum(axis=-1)
        # per-lane cumulative counters ride the trace: [B, T], last step
        miss_curve.append(np.asarray(tr_on["memo_misses"])[:, -1])
        hit_curve.append(np.asarray(tr_on["memo_hits"])[:, -1])
    assert (dones >= 2).all(), ("every lane must complete episodes for "
                                f"the cross-reset pin, got {dones}")
    # cross-reset persistence PER LANE: every lane's first episode ends
    # inside the first segment, and its replays serve from the table it
    # populated BEFORE the in-kernel resets — misses freeze exactly
    assert np.array_equal(miss_curve[1], miss_curve[0]), miss_curve
    assert np.array_equal(miss_curve[2], miss_curve[0]), miss_curve
    # every lane hits its own cache (distinct banks, distinct tables)
    assert (hit_curve[-1] > 0).all(), hit_curve[-1]
    # distinct banks produce genuinely per-lane counter streams
    if n_lanes > 1:
        assert len({int(h) for h in hit_curve[-1]}
                   | {int(m) for m in miss_curve[-1]}) > 1
    # the lane-summed reporting summary == sum of per-lane finals
    summary = summarize_counters(st_on[1])
    assert summary["hits"] == int(hit_curve[-1].sum())
    assert summary["misses"] == int(miss_curve[-1].sum())
    assert 0.0 < summary["hit_rate"] <= 1.0


def test_device_collector_resolves_memo_by_lanes_and_reports(memo_env):
    """num_envs=1 -> memo auto-ON with counters at the drain boundary;
    num_envs>1 -> ALSO auto-ON (the wide batched probe, ISSUE 17) with
    counters summed over lanes."""
    import jax

    from ddls_tpu.rl.ppo_device import DevicePPOCollector

    et, ot = memo_env["et"], memo_env["ot"]
    model, params, bank = (memo_env["model"], memo_env["params"],
                           memo_env["bank"])
    one = {k: v[None] for k, v in bank.items()}
    col = DevicePPOCollector(et, ot, model, one, rollout_length=24)
    assert col.memo_cfg is not None
    for seed in (3, 4):
        out = col.collect(params, jax.random.PRNGKey(seed))
    assert out["traj"]["actions"].shape == (24, 1)
    counters = col.memo_counters()
    assert counters is not None and counters["hits"] > 0
    assert 0.0 < counters["hit_rate"] <= 1.0
    # one probe per decision whose action enters the heavy path
    # (action-0 decisions skip eval_cfg entirely), never more
    assert 0 < (counters["hits"] + counters["misses"]) <= 48

    two = _lane_banks(memo_env, 2)
    col2 = DevicePPOCollector(et, ot, model, two, rollout_length=24)
    assert col2.memo_cfg is not None, (
        "auto must resolve the memo ON at every lane count (ISSUE 17)")
    for seed in (5, 6):
        col2.collect(params, jax.random.PRNGKey(seed))
    c2 = col2.memo_counters()
    assert c2 is not None and c2["hits"] > 0
    # lane-summed probe count: ≤ one per heavy-path decision per lane
    assert 0 < (c2["hits"] + c2["misses"]) <= 2 * 48


def test_fused_lanes1_memo_on_transfer_free_then_reports(memo_env,
                                                         monkeypatch):
    """The fused loop at lanes=1 (the narrowest program) resolves the
    memo ON, its steady-state epoch stays transfer-free under
    ``jax.transfer_guard`` (ISSUE 13 acceptance), and the memo
    counters surface only at the reporting boundary."""
    import jax

    from ddls_tpu.train import make_epoch_loop

    loop = make_epoch_loop(
        "ppo",
        path_to_env_cls=ENV_CLS,
        env_config=memo_env["env_config"],
        model=_TINY_MODEL,
        algo_config={"train_batch_size": 16, "sgd_minibatch_size": 8,
                     "num_sgd_iter": 1, "num_workers": 1},
        num_envs=1, rollout_length=16, n_devices=1,
        use_parallel_envs=False, evaluation_interval=None, seed=0,
        loop_mode="fused", updates_per_epoch=1,
        metrics_sync_interval=3,
        fused_config={"lanes": 1, "segment_len": 16})
    try:
        assert loop.fused is not None, "fused build fell back"
        assert loop.fused.memo_cfg is not None, (
            "lanes=1 fused must resolve the memo ON by default")
        loop.run()  # warm: compile + first-use constant transfers
        with jax.transfer_guard("disallow"):
            loop.run()  # steady state: memo table stays on device
        r3 = loop.run()  # drain boundary
        assert np.isfinite(r3["learner"]["total_loss"])
        counters = loop.fused.memo_counters()
        assert counters is not None
        # one probe per heavy-path decision across 3 epochs x 16 steps
        assert 0 < counters["hits"] + counters["misses"] <= 3 * 16
        assert counters["hits"] > 0
    finally:
        loop.close()
