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
from ddls_tpu.sim.jax_env import (build_shape_tables, config_tables_for,
                                  jax_allocate_job, stack_config_tables,
                                  table_slots)


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
        mem, free, cfg, jtables, st, pads))

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
        mem, free, cfg, jtables, st, pads))
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
