"""Property/fuzz test for the C++ lookahead engine.

Random lookahead instances (random DAGs with mutual sync pairs, random
worker assignment, multi-channel flow routing, permutation priority
scores) are run through the C++ engine and through an independent,
deliberately-naive numpy mirror of the pinned tick semantics
(sim/jax_lookahead.py module docstring). Outcomes must agree exactly in f64:
this exercises the engine's incremental data structures (lazy heaps,
readiness staging, channel nomination) on tie-break and contention
patterns that episode-captured cases may never produce.
"""
import numpy as np
import pytest

from ddls_tpu.native import native_available, run_lookahead
from ddls_tpu.native.arrays import LookaheadArrays

pytestmark = pytest.mark.skipif(
    not native_available(), reason="native toolchain unavailable")


def _numpy_reference(a: LookaheadArrays):
    """Straightforward O(iters x (N+E)) mirror of the host semantics."""
    N = a.op_remaining.shape[0]
    E = a.dep_remaining.shape[0]
    rem_op = a.op_remaining.astype(np.float64).copy()
    rem_dep = a.dep_remaining.astype(np.float64).copy()
    op_done = np.zeros(N, bool)
    dep_done = np.zeros(E, bool)
    parent_done = np.zeros(N, np.int64)
    t = comm = comp = busy = 0.0
    BIG = 1.7e308

    for _ in range(2 * (N + E) + 16):
        if op_done.all() and dep_done.all():
            return t, comm, comp, busy, True
        ops_ready = ~op_done & (parent_done >= a.num_parents)
        deps_ready = ~dep_done & op_done[a.dep_src]
        flow_ready = deps_ready & a.dep_is_flow
        nonflow_ready = deps_ready & ~a.dep_is_flow

        # per-worker best ready op by score
        selected = np.zeros(N, bool)
        for w in range(a.num_workers):
            cand = np.nonzero(ops_ready & (a.op_worker == w))[0]
            if len(cand):
                selected[cand[np.argmax(a.op_score[cand])]] = True
        shortest_op = rem_op[selected].min() if selected.any() else BIG

        if nonflow_ready.any():
            shortest_comm = 0.0
        else:
            shortest_comm = BIG
            for c in range(a.num_channels):
                on_c = np.nonzero(flow_ready
                                  & (a.dep_channel == c).any(axis=1))[0]
                if len(on_c):
                    top = on_c[np.argmax(a.dep_score[on_c])]
                    shortest_comm = min(shortest_comm, rem_dep[top])

        tick = min(shortest_op, shortest_comm)
        if tick >= BIG:
            return t, comm, comp, busy, False

        # advance selected ops (dep readiness was snapshotted above)
        for oi in np.nonzero(selected)[0]:
            rem_op[oi] = rem_op[oi] - min(tick, rem_op[oi])
            if rem_op[oi] == 0.0:
                op_done[oi] = True
        # advance deps from the snapshot
        tick_mask = nonflow_ready if nonflow_ready.any() else flow_ready
        ticked_flows = (not nonflow_ready.any()) and bool(flow_ready.any())
        for ei in np.nonzero(tick_mask)[0]:
            rem_dep[ei] = rem_dep[ei] - min(tick, rem_dep[ei])
            if rem_dep[ei] == 0.0 and not dep_done[ei]:
                dep_done[ei] = True
                if not a.dep_mutual[ei]:
                    parent_done[a.dep_dst[ei]] += 1

        if selected.any() and ticked_flows:
            comm += tick
            comp += tick
        elif ticked_flows:
            comm += tick
        elif selected.any():
            comp += tick
        busy += float(selected.sum()) * tick
        t += tick
    return t, comm, comp, busy, False


def _random_instance(rng: np.random.RandomState) -> LookaheadArrays:
    n = rng.randint(3, 13)
    W = rng.randint(1, min(n, 4) + 1)
    C = rng.randint(1, 4)
    L = rng.randint(1, 3)

    # forward (non-mutual) DAG edges i < j, plus mutual sync pairs
    edges, mutual = [], []
    for j in range(1, n):
        for i in rng.choice(j, size=min(j, rng.randint(1, 3)),
                            replace=False):
            edges.append((int(i), j))
            mutual.append(False)
    for _ in range(rng.randint(0, 3)):
        i, j = rng.choice(n, size=2, replace=False)
        edges.append((int(i), int(j)))
        mutual.append(True)
        edges.append((int(j), int(i)))
        mutual.append(True)
    m = len(edges)

    dep_src = np.array([e[0] for e in edges], np.int32)
    dep_dst = np.array([e[1] for e in edges], np.int32)
    dep_mutual = np.array(mutual)
    num_parents = np.zeros(n, np.int32)
    for (u, v), mu in zip(edges, mutual):
        if not mu:
            num_parents[v] += 1

    dep_is_flow = rng.rand(m) < 0.5
    dep_remaining = np.where(
        dep_is_flow,
        np.round(rng.rand(m) * 10, 2) * (rng.rand(m) < 0.8),
        0.0)
    dep_channel = np.full((m, L), -1, np.int32)
    for ei in np.nonzero(dep_is_flow)[0]:
        k = rng.randint(1, min(L, C) + 1)
        dep_channel[ei, :k] = rng.choice(C, size=k, replace=False)

    return LookaheadArrays(
        op_remaining=np.round(rng.rand(n) * 5, 2) * (rng.rand(n) < 0.9),
        op_valid=np.ones(n, bool),
        op_worker=rng.randint(0, W, size=n).astype(np.int32),
        op_score=(rng.permutation(n) + 1).astype(np.float64),
        num_parents=num_parents,
        dep_remaining=dep_remaining.astype(np.float64),
        dep_valid=np.ones(m, bool),
        dep_src=dep_src, dep_dst=dep_dst,
        dep_mutual=dep_mutual,
        dep_is_flow=dep_is_flow,
        dep_score=(rng.permutation(m) + 1).astype(np.float64),
        dep_channel=dep_channel,
        num_workers=W, num_channels=C)


def test_native_matches_numpy_reference_on_random_instances():
    rng = np.random.RandomState(0)
    solved = 0
    for case in range(300):
        arrays = _random_instance(rng)
        expected = _numpy_reference(arrays)
        got = run_lookahead(arrays)
        if not expected[4]:
            # unfinishable instance: the native engine must bail too
            assert got is None, f"case {case}: native solved a stuck instance"
            continue
        solved += 1
        assert got is not None, f"case {case}: native bailed on solvable"
        assert got == pytest.approx(expected[:4], rel=0, abs=0), \
            f"case {case}: {got} != {expected[:4]}"
    assert solved > 200, f"only {solved} solvable instances generated"


def test_native_block_search_matches_python():
    """The C++ first-fit block search reproduces the Python search
    (shapes -> origins -> cells, first fit) exactly on random snapshots,
    including the diagonal layout and meta-mode whole-extent scans."""
    from ddls_tpu.agents.block_search import (block_shapes_for,
                                              enumerate_block, block_ok,
                                              factor_pairs,
                                              first_fit_block,
                                              _ramp_arrays)
    from ddls_tpu.native import run_first_fit_block

    rng = np.random.RandomState(1)
    for case in range(200):
        ramp_shape = (int(rng.randint(1, 5)), int(rng.randint(1, 5)),
                      int(rng.randint(1, 3)))
        ramp = {}
        for c in range(ramp_shape[0]):
            for r in range(ramp_shape[1]):
                for s in range(ramp_shape[2]):
                    occ = set()
                    if rng.rand() < 0.3:
                        occ.add(int(rng.randint(0, 3)))
                    ramp[(c, r, s)] = {
                        "mem": float(rng.randint(0, 5)),
                        "job_idxs": occ}
        meta_shape = (int(rng.randint(1, ramp_shape[0] + 1)),
                      int(rng.randint(1, ramp_shape[1] + 1)),
                      int(rng.randint(1, ramp_shape[2] + 1)))
        job_idx = int(rng.randint(0, 3))
        num_servers = int(rng.randint(1, 7))
        op_size = float(rng.randint(0, 4))

        shapes = block_shapes_for(factor_pairs(num_servers), meta_shape)
        shapes += [(num_servers, num_servers, -1), (num_servers, 1, 1)]
        expected = first_fit_block(shapes, meta_shape, ramp_shape, ramp,
                                   job_idx, op_size=op_size)
        got = run_first_fit_block(shapes, meta_shape, ramp_shape,
                                  *_ramp_arrays(ramp, ramp_shape, job_idx),
                                  op_size=op_size, meta_scan=False)
        assert got != "unavailable"
        assert (got[0] if got else None) == expected, f"case {case}"

        # meta-mode parity
        expected_meta = None
        for i in range(ramp_shape[0]):
            for j in range(ramp_shape[1]):
                for k in range(ramp_shape[2]):
                    block = enumerate_block(meta_shape, ramp_shape,
                                            (i, j, k))
                    if block_ok(ramp, block, None, job_idx="__meta__"):
                        expected_meta = (block, (i, j, k))
                        break
                if expected_meta:
                    break
            if expected_meta:
                break
        got_meta = run_first_fit_block(
            [meta_shape], meta_shape, ramp_shape,
            *_ramp_arrays(ramp, ramp_shape, "__meta__"),
            op_size=None, meta_scan=True)
        assert got_meta != "unavailable"
        assert got_meta == expected_meta, f"meta case {case}"
