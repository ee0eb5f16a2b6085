"""Scan-ified allocate_job vs the host placer: randomized full-job parity
(the placer-side step beyond test_jax_block_search's single-search fuzz;
VERDICT r3 next #2).

Graph memory values are dyadic integers so the kernel's f32 arithmetic is
exact and any mismatch is a semantics bug, not rounding."""
import os
import tempfile

import numpy as np
import pytest

import jax.numpy as jnp

from ddls_tpu.agents.partitioners import build_partition_action
from ddls_tpu.agents.placers import allocate_job
from ddls_tpu.graphs.readers import read_graph_file
from ddls_tpu.sim.jax_env import (build_shape_tables, config_rows,
                                  config_tables_for, jax_allocate_job,
                                  stack_config_tables, table_slots)


def _allocate_rows(mem, other_free, cfg, tables, st, pads):
    """The package's scan under the indexed reference's signature (it
    takes one config's rows, the reference the tables and the row)."""
    return jax_allocate_job(mem, other_free, config_rows(tables, cfg), st,
                            pads)


def _write_profile(path, n_fwd, rng):
    """A chain-with-skips pipedream profile with integer dyadic sizes."""
    lines = []
    for i in range(1, n_fwd + 1):
        act = int(rng.randint(1, 20)) * 4
        par = int(rng.randint(0, 10)) * 4
        fwd = int(rng.randint(1, 50))
        bwd = int(rng.randint(1, 50))
        lines.append(
            f"node{i} -- Op(x) -- forward_compute_time={fwd}, "
            f"backward_compute_time={bwd}, activation_size={act}, "
            f"parameter_size={par}")
    for i in range(1, n_fwd):
        lines.append(f"node{i} -- node{i + 1}")
        if i + 2 <= n_fwd and rng.rand() < 0.4:
            lines.append(f"node{i} -- node{i + 2}")
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")


@pytest.fixture(scope="module", params=[(2, 2, 2), (4, 4, 2)])
def setup(request):
    ramp_shape = request.param
    n_srv = int(np.prod(ramp_shape))
    max_split = min(16, n_srv)
    rng = np.random.RandomState(sum(ramp_shape))
    d = tempfile.mkdtemp(prefix="jax_placer_")
    graphs = []
    for gi, n_fwd in enumerate([4, 7, 10]):
        path = os.path.join(d, f"g{gi}.txt")
        _write_profile(path, n_fwd, rng)
        graphs.append(read_graph_file(path))

    degrees = [dg for dg in (1, 2, 4, 8, 16) if dg <= max_split]
    st = build_shape_tables(ramp_shape, max_split)
    cfgs = []
    cfg_meta = []  # (graph index, degree)
    for gi, g in enumerate(graphs):
        for dg in degrees:
            cfgs.append(config_tables_for(g, dg, 0.01))
            cfg_meta.append((gi, dg))
    tables, pads = stack_config_tables(cfgs, st)
    jtables = {k: jnp.asarray(v) for k, v in tables.items()}
    # the tables are in block order: host op index -> op slot, per row
    op_slots = [table_slots(c, pads.max_split)[0] for c in cfgs]
    return ramp_shape, graphs, st, jtables, pads, cfg_meta, op_slots


def _random_state(rng, ramp_shape, occupancy_p):
    n_srv = int(np.prod(ramp_shape))
    mem = (rng.randint(50, 1200, size=n_srv)).astype(np.float64)
    other = rng.rand(n_srv) < occupancy_p
    ramp = {}
    codes = []
    for c in range(ramp_shape[0]):
        for r in range(ramp_shape[1]):
            for s in range(ramp_shape[2]):
                codes.append((c, r, s))
    for i, coord in enumerate(codes):
        ramp[coord] = {"mem": float(mem[i]),
                       "job_idxs": {77} if other[i] else set()}
    return mem, ~other, ramp, codes


def test_full_job_parity_randomized(setup):
    ramp_shape, graphs, st, jtables, pads, cfg_meta, op_slots = setup
    import jax

    fn = jax.jit(lambda mem, free, cfg: jax_allocate_job(
        mem, free, config_rows(jtables, cfg), st, pads))

    rng = np.random.RandomState(0)
    n_checked_placed = 0
    for trial in range(40):
        cfg = int(rng.randint(0, len(cfg_meta)))
        gi, degree = cfg_meta[cfg]
        graph = graphs[gi]
        mem, other_free, ramp, codes = _random_state(
            rng, ramp_shape, rng.choice([0.0, 0.25, 0.6]))

        action = build_partition_action(graph, 0.01, degree)
        split_fwd = {op: n for op, n in action.items()
                     if n > 1 and graph.is_forward(op)}
        forward_graph = graph.forward_view()
        meta_servers = set(codes)
        host = allocate_job(dict((k, dict(mem=v["mem"],
                                          job_idxs=set(v["job_idxs"])))
                                 for k, v in ramp.items()),
                            ramp_shape, forward_graph, graph, split_fwd,
                            meta_servers, ramp_shape, job_idx=1)

        ots, new_mem, ok = fn(jnp.asarray(mem, jnp.float32),
                              jnp.asarray(other_free), cfg)
        ots = np.asarray(ots)
        ok = bool(ok)

        if host is None:
            assert not ok, (trial, cfg_meta[cfg])
            continue
        assert ok, (trial, cfg_meta[cfg])
        n_checked_placed += 1

        # host placed dict -> server codes, compared op by op
        from ddls_tpu.sim.partition import partition_graph

        pgraph = partition_graph(graph, action)
        op_index = pgraph.finalize()["op_index"]
        R, S = ramp_shape[1], ramp_shape[2]
        assert len(host) == pgraph.n_ops
        for op_id, coord in host.items():
            code = (coord[0] * R + coord[1]) * S + coord[2]
            slot = op_slots[cfg][op_index[op_id]]
            assert ots[slot] == code, (
                trial, cfg_meta[cfg], op_id, coord, ots[slot])
        # every slot no real op sits on stays unassigned
        assert (np.delete(ots, op_slots[cfg]) == -1).all()
    assert n_checked_placed >= 8


def test_memory_accounting_matches_host(setup):
    """New free-memory grid equals the host's mutated snapshot after a
    successful allocation (placement deducts fwd+bwd pair memory)."""
    ramp_shape, graphs, st, jtables, pads, cfg_meta, _ = setup
    import jax

    fn = jax.jit(lambda mem, free, cfg: jax_allocate_job(
        mem, free, config_rows(jtables, cfg), st, pads))
    rng = np.random.RandomState(7)
    checked = 0
    for trial in range(30):
        cfg = int(rng.randint(0, len(cfg_meta)))
        gi, degree = cfg_meta[cfg]
        graph = graphs[gi]
        mem, other_free, ramp, codes = _random_state(rng, ramp_shape, 0.2)
        action = build_partition_action(graph, 0.01, degree)
        split_fwd = {op: n for op, n in action.items()
                     if n > 1 and graph.is_forward(op)}
        host_ramp = {k: dict(mem=v["mem"], job_idxs=set(v["job_idxs"]))
                     for k, v in ramp.items()}
        host = allocate_job(host_ramp, ramp_shape, graph.forward_view(),
                            graph, split_fwd, set(codes), ramp_shape,
                            job_idx=1)
        if host is None:
            continue
        _, new_mem, ok = fn(jnp.asarray(mem, jnp.float32),
                            jnp.asarray(other_free), cfg)
        assert bool(ok)
        new_mem = np.asarray(new_mem)
        for i, coord in enumerate(codes):
            assert new_mem[i] == pytest.approx(host_ramp[coord]["mem"],
                                               abs=1e-4), (trial, coord)
        checked += 1
    assert checked >= 5


# ---------------------------------------------------------------------------
# The package's scan against the indexed scan it replaced
# (tests/indexed_placer.py), bit for bit: the same (ots, new_mem, ok) on
# succeeding AND failing placements, whatever batches the call.
# ---------------------------------------------------------------------------

RAGGED_QUANTUM = 1.0


def _write_ragged_profile(path, max_split):
    """A chain whose forward times make the SiP-ML rule split its ops
    1, 2, 4, 6, ... max_split ways in one row (a zero-cost op splits
    once) at ``RAGGED_QUANTUM``."""
    times = [0] + list(range(1, max_split + 1, 2)) + [max_split, 3]
    lines = [f"node{i} -- Op(x) -- forward_compute_time={t}, "
             f"backward_compute_time={t}, activation_size={8 * (1 + i % 5)}, "
             f"parameter_size={4 * (i % 3)}"
             for i, t in enumerate(times, 1)]
    lines += [f"node{i} -- node{i + 1}" for i in range(1, len(times))]
    lines += [f"node{i} -- node{i + 2}" for i in range(1, len(times) - 1, 3)]
    with open(path, "w") as f:
        f.write("\n".join(lines) + "\n")


@pytest.fixture(scope="module")
def rows(setup, tmp_path_factory):
    """`setup`'s graphs at every degree plus a ragged graph's rows, as
    numpy tables (a case converts them under its own precision)."""
    ramp_shape, graphs, st, _, _, cfg_meta, _ = setup
    max_split = int(st.counts.max())
    path = tmp_path_factory.mktemp("ragged") / "ragged.txt"
    _write_ragged_profile(path, max_split)
    ragged = read_graph_file(str(path))
    degrees = sorted({dg for _, dg in cfg_meta})
    cfgs = [config_tables_for(graphs[gi], dg, 0.01) for gi, dg in cfg_meta]
    cfgs += [config_tables_for(ragged, dg, RAGGED_QUANTUM) for dg in degrees]
    tables, pads = stack_config_tables(cfgs, st)
    top = tables["f_split"][-1][tables["f_valid"][-1]]
    assert set(top) == {1} | set(range(2, max_split + 1, 2)), top
    return ramp_shape, st, tables, pads, len(degrees)


def _clusters(kind, rng, n_srv, lanes):
    """[lanes, n_srv] free memory and not-otherwise-occupied flags."""
    if kind == "empty":
        return (np.full((lanes, n_srv), 1200.0),
                np.ones((lanes, n_srv), bool))
    if kind == "half":
        return (rng.randint(50, 1200, (lanes, n_srv)).astype(np.float64),
                rng.rand(lanes, n_srv) < 0.5)
    assert kind == "starved"
    return (rng.randint(0, 70, (lanes, n_srv)).astype(np.float64),
            rng.rand(lanes, n_srv) < 0.8)


@pytest.mark.parametrize("x64", [False, True], ids=["f32", "x64"])
@pytest.mark.parametrize("cluster", ["empty", "half", "starved"])
@pytest.mark.parametrize("batching", ["unbatched", "vmap8", "vmap320",
                                      "price_all"])
def test_scan_equals_the_indexed_scan_bit_for_bit(rows, batching, cluster,
                                                  x64):
    """Every row (each graph at each degree, the ragged rows among
    them) on empty, half-occupied and memory-starved clusters; one call
    a row, a `vmap` over 8 and over 320 lanes with another row a lane,
    and `price_all`'s nesting (lanes over a `vmap` of one job's degree
    columns against the lane's one cluster); float32 and x64."""
    import jax

    import indexed_placer

    ramp_shape, st, tables, pads, n_deg = rows
    n_srv = int(np.prod(ramp_shape))
    n_rows = tables["f_split"].shape[0]
    rng = np.random.RandomState(len(batching) + 7 * len(cluster))
    with jax.enable_x64(x64):
        jt = {k: jnp.asarray(v) for k, v in tables.items()}
        dt = jt["f_mem"].dtype
        assert dt == (jnp.float64 if x64 else jnp.float32)

        def per_lane(allocate):
            def one(mem, free, cfg):
                return allocate(mem, free, cfg, jt, st, pads)
            if batching == "price_all":
                return lambda mem, free, job: jax.vmap(
                    one, in_axes=(None, None, 0))(
                        mem, free, job * n_deg + jnp.arange(n_deg))
            return one

        new = per_lane(_allocate_rows)
        old = per_lane(indexed_placer.jax_allocate_job)
        if batching == "unbatched":
            lanes, cfgs = n_rows, np.arange(n_rows)
            new, old = jax.jit(new), jax.jit(old)

            def run(fn, mem, free, cfg):
                outs = [fn(m, f, c) for m, f, c in zip(mem, free, cfg)]
                return [np.stack([np.asarray(o[i]) for o in outs])
                        for i in range(3)]
        else:
            lanes = 320 if batching == "vmap320" else 8
            cfgs = rng.permutation(np.arange(lanes) % n_rows)
            if batching == "price_all":
                cfgs = cfgs % (n_rows // n_deg)      # a job a lane
            new, old = jax.jit(jax.vmap(new)), jax.jit(jax.vmap(old))

            def run(fn, mem, free, cfg):
                return [np.asarray(o) for o in fn(mem, free, cfg)]

        mem, free = _clusters(cluster, rng, n_srv, lanes)
        args = (jnp.asarray(mem, dt), jnp.asarray(free),
                jnp.asarray(cfgs, jnp.int32))
        got, want = run(new, *args), run(old, *args)
    for name, g, w in zip(("ots", "new_mem", "ok"), got, want):
        assert g.dtype == w.dtype and g.shape == w.shape, name
        assert np.array_equal(g, w), (name, np.argwhere(g != w)[:5])
    ots, _, ok = got
    if cluster == "starved":
        # placements FAIL here, and a failed one keeps what it placed
        assert (~ok).any()
        assert ((ots >= 0).any(axis=-1) & ~ok).any()
    if cluster == "empty":
        assert ok.any()


def test_allocate_gauge_counts_no_indexed_op(rows):
    """`sim.allocate.indexed_ops` — `allocate_indexed_ops` — reads 0 on
    the package's scan; the same count over the indexed scan finds one
    gather a cell of every block shape, one a parent (`mem[servers]`)
    and the three scatters of the commit: the number an index creeping
    back into the package's scan would bring back."""
    import jax

    import indexed_placer
    from ddls_tpu.sim.jax_env import allocate_indexed_ops
    from ddls_tpu.utils.jaxprs import indexed_ops

    ramp_shape, st, tables, pads, _ = rows
    n_srv = int(np.prod(ramp_shape))
    jt = {k: jnp.asarray(v) for k, v in tables.items()}
    assert allocate_indexed_ops(jt, st, pads) == 0
    traced = jax.make_jaxpr(
        lambda mem, free, cfg: indexed_placer.jax_allocate_job(
            mem, free, cfg, jt, st, pads))(
        jnp.zeros((n_srv,), jt["f_mem"].dtype), jnp.ones((n_srv,), bool),
        jnp.int32(0))
    found = indexed_ops(traced.jaxpr, pads.max_split)
    assert pads.n_parents == 2
    assert len(found) == int(st.counts.sum()) + pads.n_parents + 3
    if ramp_shape == (4, 4, 2):
        assert int(st.counts.sum()) == 78 and len(found) == 83
        assert sorted(set(found)) == ["gather", "scatter", "scatter-add"]


ARCH_JOBS = {
    # a 57-step scan over 1,824 op slots, every row even (`mimo`'s pads)
    "mimo": ({"config": "ddls_tpu/graphs/arch_configs/mimo_v2_flash.json",
              "layers": {"leading_dense": 1, "following": 6},
              "experts_held": 64,
              "shapes": [{"seq_len": 8192, "micro_batch": 4}]}, 57, 1824,
             (True, True, True)),
    # a 131-step scan over 4,192 op slots, ragged rows (`olmoe`'s pads):
    # at degree 16 a norm splits 10 ways, and no RAMP 4x4x2 block holds
    # 10 servers — that row fails on an empty cluster
    "olmoe": ({"config": "ddls_tpu/graphs/arch_configs/olmoe_1b_7b_0125.json",
               "shapes": [{"seq_len": 4096, "micro_batch": 1}]}, 131, 4192,
              (True, True, False)),
}


@pytest.mark.parametrize("arch", sorted(ARCH_JOBS))
def test_scan_equals_the_indexed_scan_at_an_architecture_pad_class(arch):
    """The same equality at a stated architecture's own tables on RAMP
    4x4x2 (degrees 1, 8 and 16 of one job shape): clusters empty,
    half-occupied and too short of memory for the whole job."""
    import jax

    import indexed_placer
    from ddls_tpu.demands import JobsGenerator

    architecture, n_fwd, n_ops, placeable = ARCH_JOBS[arch]
    graph = JobsGenerator(
        architecture=architecture,
        job_interarrival_time_dist={
            "_target_": "ddls_tpu.demands.distributions.Fixed",
            "val": 1.0}).sample_job().graph
    st = build_shape_tables((4, 4, 2), 16)
    tables, pads = stack_config_tables(
        [config_tables_for(graph, dg, 10e-6) for dg in (1, 8, 16)], st)
    assert (pads.n_fwd, pads.n_ops) == (n_fwd, n_ops)
    jt = {k: jnp.asarray(v) for k, v in tables.items()}
    new, old = (jax.jit(jax.vmap(
        lambda mem, free, cfg, fn=fn: fn(mem, free, cfg, jt, st, pads)))
        for fn in (_allocate_rows, indexed_placer.jax_allocate_job))

    rng = np.random.RandomState(3)
    lanes, n_srv = 9, 32
    mem = np.full((lanes, n_srv), 80e9)
    mem[3:6] = rng.uniform(0, 80e9, (3, n_srv))
    mem[6:] = rng.uniform(0, 4e9, (3, n_srv))
    free = np.ones((lanes, n_srv), bool)
    free[3:6] = rng.rand(3, n_srv) < 0.5
    args = (jnp.asarray(mem, jnp.float32), jnp.asarray(free),
            jnp.asarray(np.arange(lanes) % 3, jnp.int32))
    got = [np.asarray(o) for o in new(*args)]
    want = [np.asarray(o) for o in old(*args)]
    for name, g, w in zip(("ots", "new_mem", "ok"), got, want):
        assert np.array_equal(g, w), (name, np.argwhere(g != w)[:5])
    ots, _, ok = got
    assert tuple(ok[:3]) == placeable and not ok[6:].any()
    assert (ots[6:] >= 0).any()      # failed, and kept what it placed


def test_tables_out_of_block_order_are_refused(setup):
    """`op_fwd` — how the scan's per-forward-op record becomes the
    op -> server map — rests on a forward slot's sub-ops being the
    first op slots of ONE original op, in shard order:
    `stack_config_tables` raises on a row where they are not."""
    _, graphs, st, _, pads, _, _ = setup
    row = config_tables_for(graphs[0], 2, 0.01)
    stack_config_tables([row], st)
    swapped = dict(row, f_sub_fwd=row["f_sub_fwd"][:, ::-1].copy())
    with pytest.raises(ValueError, match="not in block order"):
        stack_config_tables([swapped], st)
    twice = dict(row, f_sub_bwd=row["f_sub_fwd"])
    with pytest.raises(ValueError, match="placed by forward slots"):
        stack_config_tables([twice], st)
