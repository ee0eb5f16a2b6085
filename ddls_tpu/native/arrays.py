"""The C++ lookahead engine's input: one job's ops and deps as flat
arrays (:class:`LookaheadArrays`, the fields ``run_lookahead`` in this
package reads and ``engine.cpp`` defines), and the packer that fills
them from a job and the cluster it is, or would be, placed on."""
from __future__ import annotations

import dataclasses
from typing import Dict

import numpy as np


@dataclasses.dataclass
class LookaheadArrays:
    """One job's lookahead inputs (all numpy, exact size, f64).

    Shapes: N = ops, E = deps, L = max channels per flow dep.
    ``op_score``/``dep_score`` are priority-with-rank combined scores
    (higher wins; distinct per slot): priority * (count + 1) + (count -
    rank in sorted-id order), so argmax breaks ties as the host engine
    does. ``dep_channel`` holds channel indices (-1 padding) into a
    dense per-job channel renumbering. The two ``*_valid`` masks are all
    True here; a caller that pads the arrays clears them on the pad.
    """
    op_remaining: np.ndarray   # [N] f64
    op_valid: np.ndarray       # [N] bool
    op_worker: np.ndarray      # [N] i32 (dense worker index)
    op_score: np.ndarray       # [N] f64
    num_parents: np.ndarray    # [N] i32 (non-mutual parent deps)
    dep_remaining: np.ndarray  # [E] f64
    dep_valid: np.ndarray      # [E] bool
    dep_src: np.ndarray        # [E] i32
    dep_dst: np.ndarray        # [E] i32
    dep_mutual: np.ndarray     # [E] bool
    dep_is_flow: np.ndarray    # [E] bool
    dep_score: np.ndarray      # [E] f64
    dep_channel: np.ndarray    # [E, L] i32 (-1 pad)
    num_workers: int           # static
    num_channels: int          # static


def build_native_lookahead_arrays(cluster, job,
                                  context: dict | None = None
                                  ) -> LookaheadArrays:
    """Exact-size f64 packing of a job for the C++ engine, the same
    inputs the host engine (``cluster._run_lookahead``) reads.

    Vectorised: the only Python loops are one O(n_ops) pass for
    worker/priority lookups and one pass over *flow* deps for channel
    lists; per-edge work is index arithmetic on ``graph.finalize()``
    arrays.

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
