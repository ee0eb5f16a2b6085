"""Jittable (vmappable) lookahead tick engine over fixed-size padded arrays.

The north-star prototype (SURVEY.md §3.5, §7.4.2): the host engine
(``cluster._run_lookahead``) simulates one training step of a mounted job by
dependency-driven ticking; this module reproduces those exact semantics as a
``lax.while_loop`` over padded arrays so the lookahead can run inside jit —
one step toward HBM-resident environment rollouts — and be vmapped over a
batch of jobs.

Semantics mirrored from the host engine (cluster.py ``_run_lookahead``):

* per worker, the highest-priority *ready* op is selected (ties break to the
  smallest op id in sorted order); the op bound is the shortest remaining
  time among selected ops;
* ready non-flow deps (zero size or same server) complete at zero cost, and
  any such dep forces a zero tick (host: ``shortest_comm = 0.0``);
* otherwise each channel nominates its highest-priority ready flow dep and
  the comm bound is the shortest remaining among nominated deps, while ALL
  ready flow deps tick in parallel (the reference's documented
  parallel-flow-tick hack, ramp_cluster_environment.py:756);
* deps readied by op completions within a tick do not advance until the next
  tick (the host snapshots ready deps before op ticking);
* mutual (backward-sync) deps never gate their destination op's readiness;
* comm/comp overhead accumulate per tick according to whether ops and/or
  flow deps advanced.

Priorities are combined with sorted-id ranks into a single score so argmax
reproduces the host's deterministic tie-breaking. All arrays are padded to
static shapes; invalid slots carry ``valid=False`` masks.
"""
from __future__ import annotations

import dataclasses
from functools import lru_cache as _lru_cache
from typing import Dict, NamedTuple, Tuple

import numpy as np

from ddls_tpu.telemetry import scopes

BIG = np.float32(3.4e38)  # stands in for +inf inside the kernel


@dataclasses.dataclass
class LookaheadArrays:
    """Padded single-job lookahead inputs (all numpy, ready for device).

    Shapes: N = padded ops, E = padded deps, L = max channels per flow dep.
    ``op_score``/``dep_score`` are priority-with-rank combined scores
    (higher wins; distinct per valid slot). ``dep_channel`` holds channel
    indices (-1 padding) into a dense per-job channel renumbering.
    """
    op_remaining: np.ndarray   # [N] f32
    op_valid: np.ndarray       # [N] bool
    op_worker: np.ndarray      # [N] i32 (dense worker index, -1 pad)
    op_score: np.ndarray       # [N] f32
    num_parents: np.ndarray    # [N] i32 (non-mutual parent deps)
    dep_remaining: np.ndarray  # [E] f32
    dep_valid: np.ndarray      # [E] bool
    dep_src: np.ndarray        # [E] i32
    dep_dst: np.ndarray        # [E] i32
    dep_mutual: np.ndarray     # [E] bool
    dep_is_flow: np.ndarray    # [E] bool
    dep_score: np.ndarray      # [E] f32
    dep_channel: np.ndarray    # [E, L] i32 (-1 pad)
    num_workers: int           # static
    num_channels: int          # static


def build_lookahead_arrays(cluster, job, pad_ops: int, pad_deps: int,
                           pad_links: int = 1,
                           context: dict | None = None) -> LookaheadArrays:
    """Assemble padded arrays for a job already mounted on the cluster
    (the same inputs the host engine reads). f32: feeds the jitted engine
    (the C++ engine has its own exact-size f64 packer,
    :func:`build_native_lookahead_arrays`). ``context`` as in
    :func:`build_native_lookahead_arrays` (candidate pricing of unmounted
    placements)."""
    job_idx = job.details["job_idx"]
    graph = job.graph
    arrays = graph.finalize()
    n, m = graph.n_ops, graph.n_deps
    if n > pad_ops or m > pad_deps:
        raise ValueError(f"job needs ({n},{m}) > padding ({pad_ops},{pad_deps})")

    topo = cluster.topology
    op_to_worker = (context["op_to_worker"] if context is not None
                    else cluster.job_op_to_worker[job_idx])
    # dense per-job worker renumbering (only workers holding this job matter)
    worker_ids = sorted({op_to_worker[op] for op in graph.op_ids})
    worker_dense = {w: i for i, w in enumerate(worker_ids)}

    op_remaining = np.zeros(pad_ops, np.float32)
    op_remaining[:n] = arrays["compute"]
    op_valid = np.zeros(pad_ops, bool)
    op_valid[:n] = True
    op_worker = np.full(pad_ops, -1, np.int32)
    op_score = np.zeros(pad_ops, np.float32)
    num_parents = np.zeros(pad_ops, np.int32)
    num_parents[:n] = arrays["num_parents"]

    # host tie-break: first op in sorted-id order among priority maxes
    sorted_rank = {op: r for r, op in enumerate(sorted(graph.op_ids))}
    ctx_op_pri = context.get("op_pri") if context is not None else None
    for op_id in graph.op_ids:
        i = arrays["op_index"][op_id]
        w = op_to_worker[op_id]
        op_worker[i] = worker_dense[w]
        if ctx_op_pri is not None:
            pri = ctx_op_pri.get(op_id, 0)
        else:
            pri = topo.workers[w].op_priority.get(job_idx, {}).get(op_id, 0)
        op_score[i] = pri * (n + 1) + (n - sorted_rank[op_id])

    dep_remaining = np.zeros(pad_deps, np.float32)
    dep_valid = np.zeros(pad_deps, bool)
    dep_valid[:m] = True
    dep_src = np.zeros(pad_deps, np.int32)
    dep_dst = np.zeros(pad_deps, np.int32)
    dep_mutual = np.zeros(pad_deps, bool)
    dep_mutual[:m] = arrays["edge_mutual"]
    dep_is_flow = np.zeros(pad_deps, bool)
    dep_score = np.zeros(pad_deps, np.float32)
    dep_channel = np.full((pad_deps, pad_links), -1, np.int32)

    # dense per-job channel renumbering
    chan_dense: Dict[str, int] = {}
    dep_sorted_rank = {e: r for r, e in enumerate(sorted(graph.edge_ids))}
    worker_to_server = topo.worker_to_server
    # array pipeline: channel/priority reads come off the DepArrays
    # payload (the channel dicts stay empty on that path)
    payload = (context.get("payload") if context is not None
               else getattr(cluster, "job_dep_arrays", {}).get(job_idx))
    if payload is not None:
        chan_l = payload.chan.tolist()
        pri_l = (payload.pri.tolist() if payload.pri is not None
                 else [0] * len(chan_l))
        edge_chan = {e: ((c,) if c >= 0 else ())
                     for e, c in zip(payload.edge_ids, chan_l)}
        edge_pri = dict(zip(payload.edge_ids, pri_l))
    else:
        edge_chan = edge_pri = None
    # flow-ness comes from THE canonical predicate (OpGraph.flow_mask);
    # the mask is aligned with finalize()'s edge order, which is exactly
    # what arrays["edge_index"] indexes
    _, edge_flow = graph.flow_mask(
        [worker_to_server[op_to_worker[op]] for op in graph.op_ids])
    for edge in graph.edge_ids:
        ei = arrays["edge_index"][edge]
        u, v = edge
        dep_src[ei] = arrays["op_index"][u]
        dep_dst[ei] = arrays["op_index"][v]
        dep_remaining[ei] = job.dep_init_run_time.get(edge, 0.0)
        is_flow = bool(edge_flow[ei])
        dep_is_flow[ei] = is_flow
        if is_flow:
            if edge_chan is not None:
                channels = edge_chan.get(edge, ())
            else:
                channels = sorted(cluster.job_dep_to_channels.get(
                    job_idx, {}).get(edge, ()))
            if len(channels) > pad_links:
                raise ValueError(
                    f"dep {edge} rides {len(channels)} channels > pad_links "
                    f"{pad_links}")
            for li, ch_id in enumerate(channels):
                dep_channel[ei, li] = chan_dense.setdefault(
                    ch_id, len(chan_dense))
            if edge_pri is not None:
                pri = edge_pri.get(edge, 0) if channels else 0
            else:
                ch = (topo.channel_id_to_channel[channels[0]]
                      if channels else None)
                pri = (ch.dep_priority.get(job_idx, {}).get(edge, 0)
                       if ch is not None else 0)
        else:
            pri = 0
        dep_score[ei] = pri * (m + 1) + (m - dep_sorted_rank[edge])

    return LookaheadArrays(
        op_remaining=op_remaining, op_valid=op_valid, op_worker=op_worker,
        op_score=op_score, num_parents=num_parents,
        dep_remaining=dep_remaining, dep_valid=dep_valid, dep_src=dep_src,
        dep_dst=dep_dst, dep_mutual=dep_mutual, dep_is_flow=dep_is_flow,
        dep_score=dep_score, dep_channel=dep_channel,
        num_workers=max(len(worker_dense), 1),
        num_channels=max(len(chan_dense), 1))


def build_native_lookahead_arrays(cluster, job,
                                  context: dict | None = None
                                  ) -> LookaheadArrays:
    """Exact-size f64 packing for the C++ engine (ddls_tpu/native).

    Produces the same arrays as :func:`build_lookahead_arrays` (same score
    formulas, so results are identical), but vectorised: the only Python
    loops left are one O(n_ops) pass for worker/priority lookups and one
    pass over *flow* deps for channel lists — the O(n_deps) per-edge dict
    walk is replaced by index arithmetic on ``graph.finalize()`` arrays.

    ``context`` supplies placement state for a job NOT mounted on the
    cluster (candidate pricing): {"op_to_worker": {op: worker_id},
    "op_pri": {op: pri}, "payload": DepArrays}. Without it, state is read
    from the cluster's mounted structures.
    """
    job_idx = job.details["job_idx"]
    graph = job.graph
    arrays = graph.finalize()
    n, m = graph.n_ops, graph.n_deps
    topo = cluster.topology
    op_ids = arrays["op_ids"]
    if context is not None:
        op_to_worker = context["op_to_worker"]
        ctx_op_pri = context.get("op_pri") or {}
    else:
        op_to_worker = cluster.job_op_to_worker[job_idx]
        ctx_op_pri = None
    worker_to_server = topo.worker_to_server
    workers = topo.workers

    op_worker = np.empty(n, np.int32)
    op_pri = np.zeros(n, np.float64)
    server_of_op = []
    worker_dense: Dict[str, int] = {}
    pri_maps: Dict[str, Dict[str, int]] = {}
    for i, op_id in enumerate(op_ids):
        w = op_to_worker[op_id]
        wi = worker_dense.get(w)
        if wi is None:
            wi = worker_dense.setdefault(w, len(worker_dense))
            pri_maps[w] = (ctx_op_pri if ctx_op_pri is not None
                           else workers[w].op_priority.get(job_idx, {}))
        op_worker[i] = wi
        server_of_op.append(worker_to_server[w])
        pri = pri_maps[w].get(op_id, 0)
        if pri:
            op_pri[i] = pri

    op_score = op_pri * (n + 1) + (n - arrays["op_sorted_rank"])

    edge_src = arrays["edge_src"].astype(np.int32)
    edge_dst = arrays["edge_dst"].astype(np.int32)
    _, dep_is_flow = graph.flow_mask(server_of_op)

    if getattr(job, "dep_init_run_time_arr", None) is not None:
        dep_remaining = job.dep_init_run_time_arr
    else:
        dep_remaining = np.zeros(m, np.float64)
        edge_index = arrays["edge_index"]
        for edge, t in job.dep_init_run_time.items():
            dep_remaining[edge_index[edge]] = t

    # channels + priorities: flow deps only
    dep_pri = np.zeros(m, np.float64)
    edge_ids = arrays["edge_ids"]
    flow_idx = np.nonzero(dep_is_flow)[0]
    payload = (context.get("payload") if context is not None
               else getattr(cluster, "job_dep_arrays", {}).get(job_idx))
    if payload is not None:
        # array pipeline: channels/priorities straight off the DepArrays
        # payload; per-job local channel renumbering is one searchsorted
        # (numbering order is irrelevant — channels only partition deps).
        # pri=None (placement without a schedule) degrades to priority 0
        # exactly like the host engine's zeros fallback
        pri_src = (payload.pri if payload.pri is not None
                   else np.zeros(m, np.int64))
        dep_pri[flow_idx] = pri_src[flow_idx].astype(np.float64)
        uniq = np.unique(payload.chan[flow_idx])
        n_chan = len(uniq)
        dep_channel = np.full((m, 1), -1, np.int32)
        dep_channel[flow_idx, 0] = np.searchsorted(
            uniq, payload.chan[flow_idx]).astype(np.int32)
    else:
        chan_dense: Dict[str, int] = {}
        dep_to_channels = cluster.job_dep_to_channels.get(job_idx, {})
        channel_id_to_channel = topo.channel_id_to_channel
        flow_channels = []
        links = 1
        for ei in flow_idx:
            edge = edge_ids[ei]
            channels = sorted(dep_to_channels.get(edge, ()))
            dense = []
            for ch_id in channels:
                ci = chan_dense.get(ch_id)
                if ci is None:
                    ci = chan_dense.setdefault(ch_id, len(chan_dense))
                dense.append(ci)
            flow_channels.append(dense)
            if len(dense) > links:
                links = len(dense)
            if channels:
                pri = channel_id_to_channel[channels[0]].dep_priority.get(
                    job_idx, {}).get(edge, 0)
                if pri:
                    dep_pri[ei] = pri
        n_chan = len(chan_dense)
        dep_channel = np.full((m, links), -1, np.int32)
        for ei, dense in zip(flow_idx, flow_channels):
            dep_channel[ei, :len(dense)] = dense

    dep_score = dep_pri * (m + 1) + (m - arrays["edge_sorted_rank"])

    return LookaheadArrays(
        op_remaining=arrays["compute"], op_valid=np.ones(n, bool),
        op_worker=op_worker, op_score=op_score,
        num_parents=arrays["num_parents"].astype(np.int32),
        dep_remaining=dep_remaining, dep_valid=np.ones(m, bool),
        dep_src=edge_src, dep_dst=edge_dst,
        dep_mutual=arrays["edge_mutual"], dep_is_flow=dep_is_flow,
        dep_score=dep_score, dep_channel=dep_channel,
        num_workers=max(len(worker_dense), 1),
        num_channels=max(n_chan, 1))


class DepBlocks(NamedTuple):
    """The block structure `partition_graph` gives a job's deps
    (sim/jax_env.py:stack_config_tables), which lets the tick body read
    a dep's endpoints by broadcast and reduction instead of per-element
    gather and scatter. With S = the block side and B = ``src.shape[0]``:
    op slot (o, k) = o*S + k over N = No*S, dep slot (b, i, j) =
    (b*S + i)*S + j over E = B*S*S, and every valid dep has ``dep_src``
    = src[b]*S + i and ``dep_dst`` = dst[b]*S + j. A flow dep's channel
    is its ordered (source worker, destination worker) pair — one
    channel per direction of a server pair, workers clipped at 0 as the
    caller's channel lookup clips them — and ``dep_channel`` is not
    read."""
    src: object  # [B] i32 original-op slot of each block's source, -1 pad
    dst: object  # [B] i32 ... of its destination


def _flat_dep_ops(dep_src, dep_dst, dep_channel, num_channels):
    """The tick body's three dep primitives for an ARBITRARY graph: one
    gather or scatter per dep (the mounted-graph callers, and the
    reference the block forms are tested against)."""
    import jax.numpy as jnp

    def src_done(op_done):
        return op_done[dep_src]

    def count_parents(parent_done, inc):
        return parent_done.at[dep_dst].add(inc)

    def nominate(dscores, flow_ready):
        # per-channel highest-score ready flow dep (scatter-max); a dep
        # is nominated iff it is the best on at least one of its channels
        ch_best = jnp.full((num_channels,), -1.0)
        for li in range(dep_channel.shape[1]):
            ch_idx = dep_channel[:, li]
            contrib = jnp.where(ch_idx >= 0, dscores, -1.0)
            ch_best = ch_best.at[jnp.clip(ch_idx, 0)].max(contrib)
        nominated = jnp.zeros(dscores.shape, bool)
        for li in range(dep_channel.shape[1]):
            ch_idx = dep_channel[:, li]
            nominated = nominated | (
                (ch_idx >= 0) & flow_ready
                & (dscores >= ch_best[jnp.clip(ch_idx, 0)]) & (dscores > 0))
        return nominated

    return src_done, count_parents, nominate


def _block_dep_ops(op_worker, blocks: DepBlocks, n_deps: int,
                   num_workers: int):
    """The same three primitives over :class:`DepBlocks` tables: dep
    state is [B, S_i, S_j], op state [No, S], and nothing indexes per
    dep. Integer counts and max are order-free, so each returns the
    flat form's bits. The one-hot masks are loop-invariant: built here,
    outside the ``while_loop``."""
    import jax
    import jax.numpy as jnp

    B = blocks.src.shape[0]
    S = int(round((n_deps // B) ** 0.5))
    N = op_worker.shape[0]
    if B * S * S != n_deps or N % S:
        raise ValueError(f"({N}, {n_deps}) is not a block layout of {B} "
                         "blocks")
    No, W = N // S, num_workers
    rows = jnp.arange(No, dtype=jnp.int32)
    from_src = blocks.src[:, None] == rows[None, :]        # [B, No]
    into_dst = blocks.dst[None, :] == rows[:, None]        # [No, B]
    # endpoint workers of each block's rows and columns; an unplaced op
    # (-1) rides server 0's channels exactly as the flat caller's
    # clipped ``pair_channel`` lookup has it
    worker = jnp.clip(op_worker, 0).reshape(No, S)
    w_src = jnp.max(jnp.where(from_src[:, :, None], worker[None], 0), 1)
    w_dst = jnp.max(jnp.where(into_dst.T[:, :, None], worker[None], 0), 1)
    on_src = jax.nn.one_hot(w_src, W, dtype=bool)          # [B, S_i, W]
    on_dst = jax.nn.one_hot(w_dst, W, dtype=bool)          # [B, S_j, W]

    def src_done(op_done):
        done = jnp.any(from_src[:, :, None] & op_done.reshape(No, S)[None],
                       axis=1)                             # [B, S_i]
        return jnp.broadcast_to(done[:, :, None], (B, S, S)).reshape(-1)

    def count_parents(parent_done, inc):
        into = inc.reshape(B, S, S).sum(axis=1, dtype=inc.dtype)  # [B, S_j]
        add = jnp.sum(jnp.where(into_dst[:, :, None], into[None], 0),
                      axis=1, dtype=inc.dtype)             # [No, S]
        return parent_done + add.reshape(-1)

    def nominate(dscores, flow_ready):
        ds = dscores.reshape(B, S, S)
        # best[X, Y] over deps whose source sits on X and destination on
        # Y: max over j into Y, then over (b, i) into X
        to_y = jnp.max(jnp.where(on_dst[:, None, :, :],
                                 ds[:, :, :, None], -1.0), axis=2)
        best = jnp.max(jnp.where(on_src[:, :, :, None],
                                 to_y[:, :, None, :], -1.0), axis=(0, 1))
        # ... and back: each dep reads best[X(b, i), Y(b, j)]
        of_x = jnp.max(jnp.where(on_src[:, :, :, None],
                                 best[None, None], -1.0), axis=2)
        mine = jnp.max(jnp.where(on_dst[:, None, :, :],
                                 of_x[:, :, None, :], -1.0), axis=3)
        return flow_ready & (dscores >= mine.reshape(-1)) & (dscores > 0)

    return src_done, count_parents, nominate


def jax_lookahead(op_remaining, op_valid, op_worker, op_score, num_parents,
                  dep_remaining, dep_valid, dep_src, dep_dst, dep_mutual,
                  dep_is_flow, dep_score, dep_channel,
                  *, num_workers: int, num_channels: int, skip=None,
                  blocks: DepBlocks | None = None):
    """One-training-step lookahead; returns
    (t, comm_oh, comp_oh, busy, ok, trips).

    ``blocks`` chooses how the tick body reaches a dep's endpoints and
    channel: None — per-dep gather/scatter through ``dep_src`` /
    ``dep_dst`` / ``dep_channel``, for any graph; a :class:`DepBlocks`
    — broadcast and reduction over the partitioner's (block, i, j)
    layout, for tables laid out that way (the in-kernel env's). Same
    tick, same bits.

    ``trips`` is the loop's own iteration count (i32): 0 for a
    ``skip``-masked lane, and under ``vmap`` each lane's OWN count — the
    batched loop itself runs while ANY lane's cond holds, so the device
    executes the maximum over the lanes.

    ``busy`` is the worker-busy time integral (sum over ticks of
    active-worker count x tick), the quantity utilisation stats divide by
    mounted-worker count x step time. Pure function of arrays —
    jit/vmap-friendly. ``ok`` is False when the engine could not progress
    (the host raises in that case).

    ``skip`` (optional bool scalar) masks the while_loop cond: a True
    lane exits before its first body iteration and returns the (garbage)
    init accumulators — the memo probe's wide-vmap lever
    (sim/jax_memo.py): jax batches ``lax.while_loop`` to run while ANY
    lane's cond holds, select-freezing finished lanes, so seeding
    memo-HIT lanes with ``skip=True`` makes the batched loop run exactly
    to the max trip count over MISS lanes (zero when every lane hit).
    Miss lanes iterate under their own cond regardless of neighbours, so
    their results stay bit-identical to an unbatched run. ``None`` (the
    default) traces the historical unmasked cond byte-for-byte.
    """
    import jax
    import jax.numpy as jnp

    N = op_remaining.shape[0]
    E = dep_remaining.shape[0]
    max_iters = N + E + 4
    # scalar accumulators follow the input dtype: f32 on the standard
    # path, f64 when the caller runs under JAX_ENABLE_X64 (the jitted
    # env-step parity mode, sim/jax_env.py)
    dt = op_remaining.dtype

    worker_onehot = (jax.nn.one_hot(op_worker, num_workers, dtype=jnp.float32)
                     .T)  # [W, N]; -1 (padding) one-hots to zeros
    if blocks is None:
        src_done, count_parents, nominate = _flat_dep_ops(
            dep_src, dep_dst, dep_channel, num_channels)
    else:
        src_done, count_parents, nominate = _block_dep_ops(
            op_worker, blocks, E, num_workers)

    def cond(state):
        (_, _, op_done, dep_done, _, _, _, _, _, it, stuck) = state
        all_done = (jnp.all(op_done | ~op_valid)
                    & jnp.all(dep_done | ~dep_valid))
        live = (~all_done) & (it < max_iters) & (~stuck)
        return live if skip is None else live & ~skip

    def body(state):
        (rem_op, rem_dep, op_done, dep_done, parent_done,
         t, comm_oh, comp_oh, busy, it, stuck) = state

        # 1. readiness (snapshotted BEFORE this tick's completions)
        ops_ready = op_valid & ~op_done & (parent_done >= num_parents)
        deps_ready = dep_valid & ~dep_done & src_done(op_done)
        flow_ready = deps_ready & dep_is_flow
        nonflow_ready = deps_ready & ~dep_is_flow
        any_nonflow = jnp.any(nonflow_ready)

        # 2. per-worker highest-score ready op
        scores = jnp.where(ops_ready, op_score, -1.0)
        per_worker = worker_onehot * scores[None, :]  # [W, N]
        best_score = per_worker.max(axis=1)           # [W]
        has_op = best_score > 0
        # an op is selected iff it is its worker's best ready op
        sel_ops = ops_ready & jnp.any(
            (per_worker == best_score[:, None]) & (best_score[:, None] > 0)
            & (worker_onehot > 0), axis=0)
        shortest_op = jnp.min(jnp.where(sel_ops, rem_op, BIG))

        # 3. per-channel highest-score ready flow dep
        dscores = jnp.where(flow_ready, dep_score, -1.0)
        nominated = nominate(dscores, flow_ready)
        shortest_comm = jnp.where(
            any_nonflow, 0.0,
            jnp.min(jnp.where(nominated, rem_dep, BIG)))

        tick = jnp.minimum(shortest_op, shortest_comm)
        new_stuck = tick >= BIG  # nothing can progress: host raises

        # 4. advance ops
        rem_op2 = jnp.where(sel_ops, jnp.maximum(rem_op - tick, 0.0), rem_op)
        op_now_done = sel_ops & (rem_op2 <= 0.0) & ~op_done
        op_done2 = op_done | op_now_done

        # 5. advance deps: the snapshot's non-flow deps if any, else ALL
        # snapshot-ready flow deps (parallel-flow hack)
        dep_tick_mask = jnp.where(any_nonflow, nonflow_ready, flow_ready)
        rem_dep2 = jnp.where(dep_tick_mask,
                             jnp.maximum(rem_dep - tick, 0.0), rem_dep)
        dep_now_done = dep_tick_mask & (rem_dep2 <= 0.0) & ~dep_done
        dep_done2 = dep_done | dep_now_done

        # 6. non-mutual completed deps advance their child's parent count
        inc = (dep_now_done & ~dep_mutual).astype(jnp.int32)
        parent_done2 = count_parents(parent_done, inc)

        ticked_ops = jnp.any(sel_ops)
        ticked_flows = (~any_nonflow) & jnp.any(flow_ready)
        safe_tick = jnp.where(new_stuck, 0.0, tick)
        comp_oh2 = comp_oh + jnp.where(ticked_ops, safe_tick, 0.0)
        comm_oh2 = comm_oh + jnp.where(ticked_flows, safe_tick, 0.0)
        busy2 = busy + safe_tick * jnp.sum(sel_ops).astype(dt)
        t2 = t + safe_tick

        return (rem_op2, rem_dep2, op_done2, dep_done2, parent_done2,
                t2, comm_oh2, comp_oh2, busy2, it + 1, stuck | new_stuck)

    init = (op_remaining, dep_remaining,
            jnp.zeros((N,), bool), jnp.zeros((E,), bool),
            jnp.zeros((N,), jnp.int32),
            jnp.zeros((), dt), jnp.zeros((), dt), jnp.zeros((), dt),
            jnp.zeros((), dt), jnp.int32(0), jnp.bool_(False))
    with jax.named_scope(scopes.SIM_LOOKAHEAD):
        out = jax.lax.while_loop(cond, body, init)
    (_, _, op_done, dep_done, _, t, comm_oh, comp_oh, busy, it,
     stuck) = out
    finished = (jnp.all(op_done | ~op_valid)
                & jnp.all(dep_done | ~dep_valid))
    return t, comm_oh, comp_oh, busy, finished & ~stuck, it


def lookahead_fn(num_workers: int, num_channels: int):
    """Jitted single-job lookahead closure over static sizes (memoised
    process-wide: identical (workers, channels) share one trace; array
    shapes further specialise inside jax's own jit cache)."""
    return _lookahead_fn_cached(num_workers, num_channels)


@_lru_cache(maxsize=None)
def _lookahead_fn_cached(num_workers: int, num_channels: int):
    import jax
    from functools import partial

    return jax.jit(partial(jax_lookahead, num_workers=num_workers,
                           num_channels=num_channels))


def batched_lookahead_fn(num_workers: int, num_channels: int):
    """vmapped+jitted lookahead over a batch of padded jobs (leading batch
    axis on every array input). Memoised per static (workers, channels)
    pair — a fresh jax.jit object would recompile on every call."""
    return _batched_lookahead_fn_cached(num_workers, num_channels)


@_lru_cache(maxsize=None)
def _batched_lookahead_fn_cached(num_workers: int, num_channels: int):
    import jax
    from functools import partial

    fn = partial(jax_lookahead, num_workers=num_workers,
                 num_channels=num_channels)
    return jax.jit(jax.vmap(fn))


def arrays_as_args(a: LookaheadArrays) -> Tuple[np.ndarray, ...]:
    return (a.op_remaining, a.op_valid, a.op_worker, a.op_score,
            a.num_parents, a.dep_remaining, a.dep_valid, a.dep_src,
            a.dep_dst, a.dep_mutual, a.dep_is_flow, a.dep_score,
            a.dep_channel)
