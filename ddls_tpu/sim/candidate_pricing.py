"""Batched candidate-degree pricing: lookahead JCTs for EVERY valid
partition degree of the queued job, without mutating cluster state.

The integration point the jax-lookahead go/no-go named (VERDICT r2 next
#3; docs/jax_lookahead_gonogo.md point 2): a policy/heuristic deciding a
job's partition degree wants the lookahead outcome of all ~16 candidate
actions, not just the one it takes. Pricing them one-by-one through the
host tick engine costs ~100 ms each at RAMP-32 scale (a CPU timing);
here each candidate's control-plane (partition -> first-fit placement
-> SRPT schedules -> pricing) runs on host over the array pipeline, and the tick engines
evaluate the batch — the C++ engine per candidate (~0.2 ms, bit-exact
f64; the default everywhere), or the opt-in vmapped jitted call (kept
for parity testing; host-dispatched, not measured on the current chip).

Every priced candidate is inserted into ``cluster.lookahead_cache`` under
its exact memo key, so the subsequent ``env.step`` with any priced action
is a guaranteed cache hit — pricing is also prefetching.

Requires the dense array dep pipeline (single-channel complete topology,
the canonical RAMP shape); returns {} on other topologies or when the
op placer is non-deterministic w.r.t. replays (RandomOpPlacer), where a
prefetched key could never be hit again.
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np

PriceTuple = Tuple[float, float, float, float]  # scaled (jct, comm, comp, busy)


def price_candidate_degrees(env, degrees=None,
                            backend: str = "auto"
                            ) -> Dict[int, Optional[PriceTuple]]:
    """Price candidate max-partition degrees for the head-of-queue job.

    Returns {degree: (jct, comm_oh, comp_oh, busy) | None} where None
    means the candidate is unplaceable (no worker block / busy channels).
    Values are scaled by ``num_training_steps`` exactly like the cluster's
    own lookahead results.
    """
    from ddls_tpu.agents.placers import RandomOpPlacer
    from ddls_tpu.sim.actions import DepArrays, OpPartition

    cluster = env.cluster
    if len(cluster.job_queue) == 0:
        return {}
    if isinstance(env.op_placer, RandomOpPlacer):
        return {}
    job_id, job = next(iter(cluster.job_queue.jobs.items()))
    if degrees is None:
        # compute action validity directly: pricing now runs BEFORE the
        # observation is extracted (so price features can describe the
        # current job), and env.obs would be the PREVIOUS decision's mask
        from ddls_tpu.envs.obs import action_is_valid

        degrees = [a for a in env.action_set
                   if a != 0 and action_is_valid(a, env)]

    results: Dict[int, Optional[PriceTuple]] = {}
    pending = []  # (degree, key, partitioned, context)
    for d in degrees:
        partition_map = {job_id: env._partition_action_for(job, d)}
        op_partition = OpPartition(partition_map, cluster=cluster)
        op_placement = env.op_placer.get(op_partition=op_partition,
                                         cluster=cluster)
        if job_id not in op_placement.action:
            results[d] = None
            continue
        op_schedule = env.op_scheduler.get(
            op_partition=op_partition, op_placement=op_placement,
            cluster=cluster)
        dep_placement = env.dep_placer.get(
            op_partition=op_partition, op_placement=op_placement,
            cluster=cluster)
        if job_id not in dep_placement.action:
            results[d] = None
            continue
        env.dep_scheduler.get(op_partition=op_partition,
                              dep_placement=dep_placement, cluster=cluster)
        payload = dep_placement.action[job_id]
        if not isinstance(payload, DepArrays):
            return {}  # dict pipeline: unsupported (see module docstring)
        partitioned = op_partition.partitioned_jobs[job_id]
        # register-time zeroing parity: the mounted path zeroes non-flow
        # dep times in _register_running_job before the memo key is built
        sc = op_placement.job_server_codes[job_id]
        is_flow = partitioned.graph.flow_mask_from_codes(sc)
        partitioned.set_dep_init_run_times_bulk(
            np.where(is_flow, partitioned.dep_init_run_time_arr, 0.0))

        split = tuple(sorted(
            op_partition.job_id_to_split_forward_ops[job_id].items()))
        key = cluster.lookahead_key_for(partitioned, split,
                                        op_placement.action[job_id])
        cached = cluster.lookahead_cache.get(key)
        if cached is not None:
            results[d] = cached
            continue
        op_pri: Dict[str, int] = {}
        for worker_id, job_map in op_schedule.action.items():
            op_pri.update(job_map.get(job_id, {}))
        context = {"op_to_worker": op_placement.action[job_id],
                   "op_pri": op_pri, "payload": payload}
        pending.append((d, key, partitioned, context))

    if pending:
        for (d, key, partitioned, _), res in zip(
                pending, _evaluate(cluster, pending, backend)):
            if res is None:
                results[d] = None
                continue
            t, comm, comp, busy = res
            steps = partitioned.num_training_steps
            scaled = (t * steps, comm * steps, comp * steps, busy)
            cluster.lookahead_cache[key] = scaled
            results[d] = scaled
    return results


def _resolve_backend(backend: str) -> str:
    if backend != "auto":
        return backend
    # auto is the C++ engine wherever it exists: host-dispatched jax
    # pricing pays a dispatch per candidate batch and a retrace per
    # distinct batch size. Toolchain-less hosts fall back to jax — slow
    # prices beat every candidate silently reading "unplaceable". The
    # jitted env (sim/jax_env.py) prices IN-kernel instead; this host
    # helper's jax backend remains opt-in for parity tests.
    from ddls_tpu.native import native_available

    return "native" if native_available() else "jax"


def _evaluate(cluster, pending, backend: str):
    """Run the tick engine over the pending candidates; returns a list of
    per-step (t, comm, comp, busy) tuples (None = engine failed)."""
    from ddls_tpu.sim.jax_lookahead import (arrays_as_args,
                                            batched_lookahead_fn,
                                            build_lookahead_arrays,
                                            build_native_lookahead_arrays)

    backend = _resolve_backend(backend)
    if backend == "native":
        from ddls_tpu.native import run_lookahead

        out = []
        for _, _, partitioned, ctx in pending:
            arrays = build_native_lookahead_arrays(cluster, partitioned,
                                                   context=ctx)
            out.append(run_lookahead(arrays))
        return out
    if backend != "jax":
        raise ValueError(f"unknown candidate-pricing backend {backend!r}"
                         " (native | jax | auto)")

    def bucket(x: int) -> int:
        size = 16
        while size < x:
            size *= 2
        return size

    pad_ops = bucket(max(p.graph.n_ops for _, _, p, _ in pending))
    pad_deps = bucket(max(p.graph.n_deps for _, _, p, _ in pending))
    batch = [build_lookahead_arrays(cluster, p, pad_ops, pad_deps,
                                    context=ctx)
             for _, _, p, ctx in pending]
    num_workers = max(a.num_workers for a in batch)
    num_channels = max(a.num_channels for a in batch)
    fn = batched_lookahead_fn(num_workers, num_channels)
    stacked = [np.stack(parts) for parts in
               zip(*(arrays_as_args(a) for a in batch))]
    t, comm, comp, busy, ok, _trips = (np.asarray(x)
                                       for x in fn(*stacked))
    return [((float(t[i]), float(comm[i]), float(comp[i]), float(busy[i]))
             if bool(ok[i]) else None)
            for i in range(len(pending))]
