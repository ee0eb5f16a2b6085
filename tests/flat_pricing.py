"""The flat forms `ddls_tpu/sim/jax_env.py` had before PR 29 priced a
placed job by block: one gather or scatter per dep through index
tables. Kept here, verbatim, as the reference the block forms must
equal BIT FOR BIT (tests/test_jax_pricing.py), and as the source of the
per-dep endpoints the flat lookahead path is driven with
(tests/test_jax_lookahead.py)."""
import numpy as np

from ddls_tpu.sim import jax_env as je


def flat_tables(per_cfg, pads):
    """The index tables `stack_config_tables` built before the block
    forms: per stacked row, each dep slot's source / destination op
    slot, each candidate group's edges and endpoints, the 2-edge sync
    pairs and the static one-to-one edges, padded."""
    S, K, M = pads.max_split, len(per_cfg), pads.n_deps
    G = pads.n_groups
    Eg = max((len(g["edges"]) for c in per_cfg for g in c["groups"]),
             default=1) or 1
    Sy = max((len(c["sync"]) for c in per_cfg), default=1) or 1
    O = max((len(c["o2o_edges"]) for c in per_cfg), default=1) or 1
    out = {
        "dep_src": np.zeros((K, M), np.int32),
        "dep_dst": np.zeros((K, M), np.int32),
        "grp_edges": np.full((K, G, Eg), -1, np.int32),
        "grp_u": np.zeros((K, G, Eg), np.int32),
        "grp_v": np.zeros((K, G, Eg), np.int32),
        "grp_edge_valid": np.zeros((K, G, Eg), bool),
        "sync_valid": np.zeros((K, Sy), bool),
        "sync_edges": np.full((K, Sy, 2), -1, np.int32),
        "sync_u": np.zeros((K, Sy), np.int32),
        "sync_v": np.zeros((K, Sy), np.int32),
        "sync_msg": np.zeros((K, Sy), np.float64),
        "o2o_valid": np.zeros((K, O), bool),
        "o2o_edges": np.zeros((K, O), np.int32),
    }
    for k, c in enumerate(per_cfg):
        ops, deps = je.table_slots(c, S)
        out["dep_src"][k, deps] = ops[c["dep_src"]]
        out["dep_dst"][k, deps] = ops[c["dep_dst"]]
        for gi, g in enumerate(c["groups"]):
            ne = len(g["edges"])
            out["grp_edges"][k, gi, :ne] = deps[g["edges"]]
            out["grp_u"][k, gi, :ne] = ops[g["u"]]
            out["grp_v"][k, gi, :ne] = ops[g["v"]]
            out["grp_edge_valid"][k, gi, :ne] = True
        for si, g in enumerate(c["sync"]):
            out["sync_valid"][k, si] = True
            ne = len(g["edges"])
            out["sync_edges"][k, si, :ne] = deps[g["edges"]]
            out["sync_u"][k, si] = ops[g["u"][0]]
            out["sync_v"][k, si] = ops[g["v"][0]]
            out["sync_msg"][k, si] = g["msg"]
        no = len(c["o2o_edges"])
        out["o2o_valid"][k, :no] = True
        out["o2o_edges"][k, :no] = deps[c["o2o_edges"]]
    return out


def block_endpoint_slots(blk_src, blk_dst, side):
    """(dep_src [M], dep_dst [M]) of one row from its block tables
    alone: dep (b, i, j) runs from op slot src[b]*S + i to dst[b]*S + j
    (`DepBlocks`); what `flat_tables` gives for every VALID dep."""
    import jax.numpy as jnp

    i = jnp.arange(side, dtype=jnp.int32)
    shape = (blk_src.shape[0], side, side)
    return (jnp.broadcast_to((blk_src[:, None] * side + i)[:, :, None],
                             shape).reshape(-1),
            jnp.broadcast_to((blk_dst[:, None] * side + i)[:, None, :],
                             shape).reshape(-1))


def flat_masks(ots, op_valid, is_flow, chan, chan_occ, n_srv, n_chan):
    """`eval_cfg`'s channel and server checks as they were: (ok_chan,
    chan_mask [n_chan], srv_mask [n_srv]) from the per-dep ``chan``."""
    import jax.numpy as jnp

    occ_vals = chan_occ[jnp.clip(chan, 0)]
    ok_chan = jnp.all(~is_flow | (occ_vals < 0))
    srv_mask = jnp.zeros((n_srv,), bool).at[
        jnp.clip(ots, 0)].max(op_valid & (ots >= 0))
    chan_mask = jnp.zeros((n_chan,), bool).at[
        jnp.clip(chan, 0)].max(is_flow)
    return ok_chan, chan_mask, srv_mask


def flat_price_and_score(sc, cfg, tables, st, pads, comm, pair_channel):
    """`jax_price_and_score` as it was before it priced by block: every
    dep reached through an index table (``tables`` = the stacked tables
    plus `flat_tables`). Returns (times [M], is_flow [M], chan [M],
    op_score [N], dep_score [M], finite_ok)."""
    import jax.numpy as jnp

    C, R, S = st.ramp_shape
    n_srv = C * R * S
    M, N = pads.n_deps, pads.n_ops
    x = float(comm["x"])
    rate, prop, io = comm["rate"], comm["prop"], comm["io"]

    codes = np.arange(n_srv)
    c_of_np = codes // (R * S)
    r_of_np = (codes // S) % R
    s_of_np = codes % S
    c_of = jnp.asarray(c_of_np, jnp.int32)
    r_of = jnp.asarray(r_of_np, jnp.int32)
    s_of = jnp.asarray(s_of_np, jnp.int32)

    dep_valid = tables["dep_valid"][cfg]
    dep_src = tables["dep_src"][cfg]
    dep_dst = tables["dep_dst"][cfg]
    dep_size = tables["dep_size"][cfg]

    scp = jnp.clip(sc, 0)
    sc_src = scp[jnp.clip(dep_src, 0)]
    sc_dst = scp[jnp.clip(dep_dst, 0)]
    # THE flow predicate, traced: mirrors OpGraph.flow_mask_from_codes
    # (graphs/op_graph.py:268) — the canonical numpy helper cannot run
    # under trace, so this is the one sanctioned re-statement; its parity
    # with the native path is pinned by tests/test_jax_pricing.py's
    # is_flow comparison
    is_flow = dep_valid & (dep_size > 0) & (sc_src != sc_dst)

    dt = dep_size.dtype
    times = jnp.zeros((M + 1,), dt)

    def span_counts(present):
        """Distinct (s, r, c) component counts among present servers;
        present: [..., n_srv] bool."""
        def cnt(comp_of_np, n_comp):
            onehot = jnp.asarray(np.eye(n_comp)[comp_of_np], dt)
            return ((present.astype(dt) @ onehot) > 0).sum(-1).astype(dt)
        return (cnt(s_of_np, S), cnt(r_of_np, R), cnt(c_of_np, C))

    # ---- candidate collective groups (symmetry-tested)
    grp_valid = tables["grp_valid"][cfg]              # [G]
    grp_edges = tables["grp_edges"][cfg]              # [G, Eg]
    grp_u = tables["grp_u"][cfg]
    grp_v = tables["grp_v"][cfg]
    grp_ev = tables["grp_edge_valid"][cfg]            # [G, Eg]
    grp_msg = tables["grp_msg"][cfg]                  # [G]

    u_codes = scp[jnp.clip(grp_u, 0)]
    v_codes = scp[jnp.clip(grp_v, 0)]
    sentinel = jnp.int32(n_srv + 1)
    u_sorted = jnp.sort(jnp.where(grp_ev, u_codes, sentinel), axis=1)
    v_sorted = jnp.sort(jnp.where(grp_ev, v_codes, sentinel), axis=1)
    symmetric = jnp.all(u_sorted == v_sorted, axis=1) & grp_valid

    G, Eg = grp_u.shape
    rows = jnp.broadcast_to(jnp.arange(G)[:, None], (G, 2 * Eg))
    both = jnp.concatenate([u_codes, v_codes], axis=1)
    both_valid = jnp.concatenate([grp_ev, grp_ev], axis=1)
    present = jnp.zeros((G, n_srv), bool).at[
        rows, jnp.clip(both, 0, n_srv - 1)].max(both_valid)
    n_in_group = present.sum(-1)
    cnt_s, cnt_r, cnt_c = span_counts(present)
    grp_time = je._jnp_all_reduce_time(
        grp_msg, jnp.maximum(cnt_s, 1.0), jnp.maximum(cnt_r, 1.0),
        jnp.maximum(cnt_c, 1.0), x=x, rate=rate, prop=prop, io=io)
    grp_time = jnp.where(n_in_group <= 1, jnp.zeros_like(grp_time),
                         grp_time)

    # edges of asymmetric groups fall back to one-to-one pricing
    # (assign_dep_run_times's extra_e path, sim/actions.py:505-540)
    e_size = tables["dep_size"][cfg][jnp.clip(grp_edges, 0)]
    e_same = u_codes == v_codes
    e_o2o = jnp.where(e_same | (e_size == 0), jnp.zeros_like(e_size),
                      prop + 2 * io + e_size / rate)
    e_val = jnp.where(symmetric[:, None], grp_time[:, None], e_o2o)
    times = times.at[jnp.where(grp_ev, grp_edges, M)].set(e_val)

    # ---- sync pairs (always collectives; 2 servers or same-server zero)
    sync_valid = tables["sync_valid"][cfg]            # [Sy]
    sync_edges = tables["sync_edges"][cfg]            # [Sy, 2]
    sync_u = scp[jnp.clip(tables["sync_u"][cfg], 0)]
    sync_v = scp[jnp.clip(tables["sync_v"][cfg], 0)]
    sync_msg = tables["sync_msg"][cfg]
    same = sync_u == sync_v
    scnt_s = jnp.where(s_of[sync_u] == s_of[sync_v], 1.0, 2.0)
    scnt_r = jnp.where(r_of[sync_u] == r_of[sync_v], 1.0, 2.0)
    scnt_c = jnp.where(c_of[sync_u] == c_of[sync_v], 1.0, 2.0)
    sync_time = je._jnp_all_reduce_time(sync_msg, scnt_s, scnt_r, scnt_c,
                                     x=x, rate=rate, prop=prop, io=io)
    sync_time = jnp.where(same, jnp.zeros_like(sync_time), sync_time)
    sv = sync_valid[:, None] & (sync_edges >= 0)
    times = times.at[jnp.where(sv, sync_edges, M)].set(
        jnp.broadcast_to(sync_time[:, None], sync_edges.shape))

    # ---- static one-to-one edges
    o2o_valid = tables["o2o_valid"][cfg]
    o2o_edges = tables["o2o_edges"][cfg]
    o_size = tables["dep_size"][cfg][jnp.clip(o2o_edges, 0)]
    o_src = sc_src[jnp.clip(o2o_edges, 0)]
    o_dst = sc_dst[jnp.clip(o2o_edges, 0)]
    o_val = jnp.where((o_src == o_dst) | (o_size == 0),
                      jnp.zeros_like(o_size),
                      prop + 2 * io + o_size / rate)
    times = times.at[jnp.where(o2o_valid, o2o_edges, M)].set(o_val)

    times = times[:M]
    # the cluster zeroes non-flow dep run times at mount
    # (cluster.py:_register_running_job:708-718); SRPT ranking below uses
    # the RAW priced times because the schedulers run before the mount
    mounted_times = jnp.where(is_flow, times, jnp.zeros_like(times))

    # ---- SRPT dep priorities: one stable descending argsort over the
    # priced costs in edge order (agents/schedulers.py:_srpt_priorities)
    m = tables["n_deps"][cfg].astype(dt)
    cost_key = jnp.where(dep_valid, -times, jnp.asarray(jnp.inf, dt))
    # "edge order" is the HOST's: the tables are in block order, so ties
    # break on each slot's own edge index, not on its position
    order = jnp.lexsort((tables["dep_edge"][cfg], cost_key))
    dep_pri = jnp.zeros((M,), dt).at[order].set(
        jnp.arange(M, dtype=dt))
    # the lookahead engines read dep priorities off the channel mounts, so
    # only FLOW deps carry their SRPT rank; non-flows score with priority 0
    # (native/arrays.py:build_native_lookahead_arrays prices flow_idx
    # only)
    dep_pri = jnp.where(is_flow, dep_pri, jnp.zeros_like(dep_pri))
    dep_score = dep_pri * (m + 1) + (
        m - tables["dep_sorted_rank"][cfg].astype(dt))

    # ---- SRPT op priorities: per-worker stable sort by compute cost
    # descending, insertion (placement) order breaking ties
    # (agents/schedulers.py:29-38 + OpPlacement.worker_to_ops order)
    op_valid = tables["op_valid"][cfg]
    op_cost = tables["op_compute"][cfg]
    ins = tables["insertion_rank"][cfg]
    same_srv = (sc[:, None] == sc[None, :]) & (sc[:, None] >= 0)
    before = (op_cost[None, :] > op_cost[:, None]) | (
        (op_cost[None, :] == op_cost[:, None]) & (ins[None, :] < ins[:, None]))
    op_pri = (same_srv & before & op_valid[None, :]).sum(1).astype(dt)
    n = tables["n_ops"][cfg].astype(dt)
    op_score = op_pri * (n + 1) + (
        n - tables["op_sorted_rank"][cfg].astype(dt))

    # ---- channels (single-channel complete topology: the direct link)
    chan = jnp.where(is_flow,
                     pair_channel[sc_src, sc_dst], jnp.int32(-1))
    # the host raises on non-finite priced times (comm_model.py:99-100,
    # actions.py:541-543); a traced kernel cannot, so callers must treat
    # finite_ok=False as that hard failure
    finite_ok = jnp.all(jnp.isfinite(mounted_times))
    return mounted_times, is_flow, chan, op_score, dep_score, finite_ok


# ---------------------------------------------------------------------------
# Both forms side by side, for the parity tests (in-process f32 and the
# x64 subprocess driver of tests/test_jax_pricing.py).
# ---------------------------------------------------------------------------

#: what each form returns, in order: pricing's outputs, then
#: `eval_cfg`'s channel / server checks
OUTPUTS = ("times", "is_flow", "op_score", "dep_score", "finite_ok",
           "ok_chan", "chan_mask", "srv_mask")


class Forms:
    """``block(sc, cfg, chan_occ)`` — the package's pricing and
    `placement_masks` — and ``flat(sc, cfg, chan_occ)`` — the forms
    above — over one set of stacked rows; each returns `OUTPUTS`."""

    def __init__(self, cfgs, st, comm, pair_channel):
        import jax
        import jax.numpy as jnp

        self.cfgs, self.st = cfgs, st
        self.tables, self.pads = je.stack_config_tables(cfgs, st)
        pads = self.pads
        self.jt = jt = {k: jnp.asarray(v) for k, v in self.tables.items()}
        self.flat_np = flat_tables(cfgs, pads)
        jf = {**jt, **{k: jnp.asarray(v) for k, v in self.flat_np.items()}}
        pair_channel = np.asarray(pair_channel)
        self.n_srv = n_srv = pair_channel.shape[0]
        self.n_chan = n_chan = int(pair_channel.max()) + 1
        pair_is_chan = je.pair_channel_one_hot(pair_channel, n_chan)
        pc = jnp.asarray(pair_channel)

        def block(sc, cfg, chan_occ):
            rows = je.config_rows(jt, cfg)
            times, is_flow, pair_used, op_score, dep_score, finite_ok = \
                je.jax_price_and_score(sc, rows, st, pads, comm)
            return (times, is_flow, op_score, dep_score, finite_ok,
                    *je.placement_masks(sc, rows["op_valid"], pair_used,
                                        pair_is_chan, chan_occ))

        def flat(sc, cfg, chan_occ):
            times, is_flow, chan, op_score, dep_score, finite_ok = \
                flat_price_and_score(sc, cfg, jf, st, pads, comm, pc)
            return (times, is_flow, op_score, dep_score, finite_ok,
                    *flat_masks(sc, jf["op_valid"][cfg], is_flow, chan,
                                chan_occ, n_srv, n_chan))

        def allocate(cfg):
            mem = jnp.full((n_srv,), 1e30, jt["dep_size"].dtype)
            return je.jax_allocate_job(mem, jnp.ones((n_srv,), bool),
                                       je.config_rows(jt, cfg), st,
                                       pads)[0]

        self.block_fn, self.flat_fn = block, flat
        self.block, self.flat = jax.jit(block), jax.jit(flat)
        self.allocate = jax.jit(allocate)

    def placements(self, cfg, rng):
        """Named per-op server codes [N] for one row: the allocator's
        own (symmetric groups: the collective price), uniform random
        (asymmetric: the one-to-one fall-back), two servers (mostly
        same-server pairs), random with unplaced ops (-1), one server."""
        N, valid = self.pads.n_ops, self.tables["op_valid"][cfg]
        some_unplaced = rng.randint(0, self.n_srv, N)
        some_unplaced[rng.rand(N) < 0.25] = -1
        cases = {
            "allocated": np.asarray(self.allocate(cfg)),
            "random": rng.randint(0, self.n_srv, N),
            "two_servers": rng.randint(0, 2, N),
            "some_unplaced": some_unplaced,
            "one_server": np.zeros(N, np.int64)}
        return {name: np.where(valid, sc, -1).astype(np.int32)
                for name, sc in cases.items()}

    def symmetric_groups(self, cfg, sc):
        """[G] bool, on the host: does a candidate group pass the
        symmetry test (equal multisets of its edges' source and
        destination servers) under placement ``sc``."""
        scp = np.clip(sc, 0, None)
        ok = self.flat_np["grp_edge_valid"][cfg]
        u = scp[self.flat_np["grp_u"][cfg]]
        v = scp[self.flat_np["grp_v"][cfg]]
        return np.array([
            bool(o.any()) and sorted(a[o]) == sorted(b[o])
            for a, b, o in zip(u, v, ok)])

    def occupancy(self, rng, taken=0.3):
        """A channel occupancy vector: ``taken`` of the channels are
        another job's (slot id >= 0)."""
        occ = np.full(self.n_chan, -1, np.int32)
        occ[rng.rand(self.n_chan) < taken] = 3
        return occ


def assert_same_bits(got, want, what):
    for name, g, w in zip(OUTPUTS, got, want):
        g, w = np.asarray(g), np.asarray(w)
        assert g.dtype == w.dtype and g.shape == w.shape, (what, name)
        assert (g == w).all(), (what, name, np.nonzero(g != w)[0][:8])


def check_row(forms: Forms, cfg: int, rng) -> dict:
    """Block == flat, bit for bit, on every placement of one row, with
    free and with partly taken channels. Returns what the cases showed
    (for the callers' coverage assertions)."""
    import jax.numpy as jnp

    seen = {"tied": 0, "chan_blocked": 0, "chan_free": 0, "flows": 0,
            "collective": 0, "fell_back": 0}
    for name, sc in forms.placements(cfg, rng).items():
        for occ in (np.full(forms.n_chan, -1, np.int32),
                    forms.occupancy(rng)):
            args = (jnp.asarray(sc), cfg, jnp.asarray(occ))
            want = forms.flat(*args)
            assert_same_bits(forms.block(*args), want, (cfg, name))
            times, is_flow = np.asarray(want[0]), np.asarray(want[1])
            flow_t = times[is_flow]
            seen["flows"] += int(is_flow.sum())
            seen["tied"] += int(len(np.unique(flow_t)) < len(flow_t))
            seen["chan_blocked"] += int(not bool(want[5]))
            seen["chan_free"] += int(bool(want[5]))
            symmetric = forms.symmetric_groups(cfg, sc)
            seen["collective"] += int(symmetric.any())
            seen["fell_back"] += int(
                (~symmetric & forms.tables["grp_valid"][cfg]).any())
    return seen


def check_lanes(forms: Forms, n_lanes: int, rng):
    """``vmap`` of the block form over ``n_lanes`` lanes of DIFFERENT
    rows, placements and occupancies == the unbatched flat form, lane
    by lane."""
    import jax
    import jax.numpy as jnp

    rows = len(forms.cfgs)
    lanes = []
    for lane in range(n_lanes):
        cfg = lane % rows
        cases = list(forms.placements(cfg, rng).values())
        lanes.append((cases[(lane // rows) % len(cases)], cfg,
                      forms.occupancy(rng, 0.1)))
    scs, cfgs, occs = (jnp.asarray(np.stack(x)) for x in zip(*lanes))
    got = jax.jit(jax.vmap(forms.block_fn))(scs, cfgs.astype(jnp.int32),
                                            occs)
    want = [forms.flat(jnp.asarray(sc), cfg, jnp.asarray(occ))
            for sc, cfg, occ in lanes]
    want = [np.stack([np.asarray(w[k]) for w in want])
            for k in range(len(OUTPUTS))]
    assert_same_bits(got, want, f"{n_lanes} lanes")
    assert len({int(c) for c in cfgs}) == min(rows, n_lanes)


def complete_pair_channel(n_srv: int) -> np.ndarray:
    """A single-channel complete topology's pair -> channel table: one
    channel per ordered pair of distinct servers, -1 on the diagonal."""
    table = np.full((n_srv, n_srv), -1, np.int32)
    off = ~np.eye(n_srv, dtype=bool)
    table[off] = np.arange(off.sum())
    return table
