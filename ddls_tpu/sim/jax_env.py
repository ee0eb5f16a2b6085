"""Fully-jitted canonical-RAMP environment stepping (the §5.8 north star).

This module composes the proven jitted pieces — the block-search primitive
(`sim/jax_block_search.py`), the lookahead tick engine (`sim/jax_lookahead.py`)
— with a `lax.scan`-ified `allocate_job` (reference:
ddls/environments/ramp_cluster/agents/placers/utils.py:532 ``allocate``, here
re-derived from `agents/placers.py:allocate_job`) and array formulations of
dep placement/pricing/scheduling into ONE jitted decision step and a jitted
episode loop for the canonical RAMP partitioning environment
(single-channel complete topology, whole-cluster meta block, one decided job
per step — the `RampJobPartitioningEnvironment` path).

Design: everything that depends only on (model, partition degree) is
precomputed on the host into padded, stacked *config tables* — the
partitioned graph arrays, placement scan order, collective grouping, SRPT
tie ranks, candidate block shapes per split — and everything that depends on
cluster state (free memory, server/channel occupancy, running jobs, the
arrival clock) lives in small state arrays. A decision is then: gather the
config row -> scan the padded forward-op sequence placing each op (parent
co-location, else generic first-fit block search; servers, anchor cells
and op slots reached by comparison and reduction over static tables,
never by an index per element: `jax_allocate_job`) -> price deps (collective
symmetry test + the RAMP all-reduce formula) -> SRPT scores -> the jitted
lookahead -> SLA gate -> masked commit. The episode loop advances the event
clock (completions, arrivals) between decisions exactly like
``RampClusterEnvironment.step``'s tick loop.

Build state: ALL stages are landed and parity-pinned — the table
builders and the scan-ified `jax_allocate_job` kernel (parity-fuzzed in
tests/test_jax_placer.py), the pricing/score kernels
(tests/test_jax_pricing.py), the replay/policy/oracle episode kernels
(x64 full-episode drivers tests/test_jax_episode.py,
test_jax_policy_episode.py, test_jax_oracle_episode.py) and the
fixed-length segment kernel feeding the device PPO collector
(tests/test_ppo_device.py). The in-kernel observation (`_kernel_obs`)
is BIT-equal to `envs/obs.py` (same formulas, same f64-then-f32 cast
order — CLAUDE.md invariant).

Numerics: tables are built in f64; under ``JAX_ENABLE_X64=1`` the whole
step runs in f64 and reproduces host decisions exactly (the parity
drivers run that way); under default f32 results carry f32 rounding.

Scope (honest): the placement-shaping env's restricted meta blocks and
multi-channel topologies stay host-side, and price-feature observations
are episode-kernel-only (the compact segment trace carries no pricing
state — `make_segment_fn` rejects them loudly).
"""
from __future__ import annotations

import dataclasses
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from ddls_tpu.agents.block_search import block_shapes_for, factor_pairs
from ddls_tpu.agents.partitioners import build_partition_action
from ddls_tpu.graphs.readers import backward_op_id
from ddls_tpu.sim import jax_memo
from ddls_tpu.sim.jax_lookahead import (DepBlocks, block_endpoints,
                                        jax_lookahead)
from ddls_tpu.sim.partition import partition_graph, partitioned_op_id
from ddls_tpu.telemetry import scopes
from ddls_tpu.utils.jaxprs import indexed_ops

#: episode-kernel default: the in-kernel lookahead memo (sim/jax_memo.py)
#: is ON for the episode builders at EVERY lane count — memoised and
#: recomputed lookaheads are bitwise identical by construction, so the
#: x64 parity suites run with it enabled unchanged, and the batched
#: probe masks hit lanes out of the lookahead while_loop so multi-lane
#: vmap callers (es_device, multi-lane collectors) hit the cache too (ISSUE 17;
#: each vmapped lane carries its own table).
DEFAULT_EPISODE_MEMO = jax_memo.MemoConfig()

Coord = Tuple[int, int, int]


# =========================================================================
# Shape system: the static candidate-block geometry for one RAMP topology.
# =========================================================================

@dataclasses.dataclass
class ShapeTables:
    """Distinct candidate block shapes for every possible split value, in
    host `find_sub_block` order, as padded index tables.

    ``row[s]`` lists (possibly duplicated) shape ids for split value ``s``
    in exactly the host's scan order (`block_shapes_for` + the diagonal
    fallback + the trailing (s,1,1)), with shapes whose origin span is
    empty already dropped (the host skips them inside `first_fit_block`).

    ``anchor_valid`` / ``member`` / ``servers_of`` are the same geometry
    spelled out per (shape, anchor cell) — is the anchor inside the
    shape's origin span with every cell of its block inside the ramp,
    which servers the block covers, and those servers in
    `enumerate_block` order — so that the placement scan reaches a
    block's servers by comparison and reduction over these tables,
    never by an index per cell (`jax_allocate_job`).
    """
    ramp_shape: Coord
    shapes: List[Coord]            # distinct shapes (S==-1 -> diagonal)
    row: np.ndarray                # [max_split+1, MAX_SHAPES] i32, -1 pad
    offsets: np.ndarray            # [n_shapes, MAX_CELLS, 3] i32 cell offsets
    counts: np.ndarray             # [n_shapes] i32 servers per block
    bases: np.ndarray              # [n_shapes, 3] i32 modulo base per axis
    spans: np.ndarray              # [n_shapes, 3] i32 origin span extents
    diagonal: np.ndarray           # [n_shapes] bool
    anchor_valid: np.ndarray       # [n_shapes, n_cells] bool
    member: np.ndarray             # [n_shapes, n_cells, n_srv] bool
    servers_of: np.ndarray         # [n_shapes, n_cells, MAX_CELLS] i32, -1 pad


def _shape_span(shape: Coord, meta: Coord) -> Coord:
    # identical to first_fit_block's span arithmetic, including the S == -1
    # quirk span[2] = meta[2] + 2 (agents/block_search.py:115-118)
    return (meta[0] - shape[0] + 1, meta[1] - shape[1] + 1,
            meta[2] - shape[2] + 1)


def _shape_cells(shape: Coord) -> List[Coord]:
    """Cell offsets at origin (0,0,0) — delegated to the host's
    `enumerate_block` (with a huge phantom ramp so no modulo fires) so the
    enumeration order can never diverge from it."""
    from ddls_tpu.agents.block_search import enumerate_block

    big = (1 << 20, 1 << 20, 1 << 20)
    return enumerate_block(shape, big, (0, 0, 0))


def build_shape_tables(ramp_shape: Coord, max_split: int) -> ShapeTables:
    meta = tuple(ramp_shape)
    per_split: Dict[int, List[Coord]] = {}
    for s in range(1, max_split + 1):
        if s != 1 and s % 2 != 0:
            continue  # odd splits >1 cannot occur (RAMP symmetry)
        shapes = block_shapes_for(factor_pairs(s), meta)
        shapes += [(s, s, -1), (s, 1, 1)]
        shapes = [sh for sh in shapes
                  if all(x > 0 for x in _shape_span(sh, meta))]
        per_split[s] = shapes

    distinct: List[Coord] = []
    index: Dict[Coord, int] = {}
    for shapes in per_split.values():
        for sh in shapes:
            if sh not in index:
                index[sh] = len(distinct)
                distinct.append(sh)

    max_row = max((len(v) for v in per_split.values()), default=1)
    row = np.full((max_split + 1, max_row), -1, np.int32)
    for s, shapes in per_split.items():
        for p, sh in enumerate(shapes):
            row[s, p] = index[sh]

    n_shapes = max(len(distinct), 1)
    cell_lists = [_shape_cells(sh) for sh in distinct]
    max_cells = max((len(c) for c in cell_lists), default=1)
    offsets = np.zeros((n_shapes, max_cells, 3), np.int32)
    counts = np.zeros(n_shapes, np.int32)
    bases = np.zeros((n_shapes, 3), np.int32)
    spans = np.zeros((n_shapes, 3), np.int32)
    diagonal = np.zeros(n_shapes, bool)
    for i, sh in enumerate(distinct):
        cells = cell_lists[i]
        counts[i] = len(cells)
        offsets[i, :len(cells)] = cells
        diagonal[i] = sh[2] == -1
        # enumerate_block's modulo: regular blocks wrap at ramp dims (a
        # no-op inside the span), diagonals at (dim+1, dim+1, dim)
        bases[i] = ((ramp_shape[0] + 1, ramp_shape[1] + 1, ramp_shape[2])
                    if sh[2] == -1 else ramp_shape)
        spans[i] = _shape_span(sh, meta)
    anchor_valid, member, servers_of = _block_membership(
        meta, offsets, counts, bases, spans)
    return ShapeTables(ramp_shape=meta, shapes=distinct, row=row,
                       offsets=offsets, counts=counts, bases=bases,
                       spans=spans, diagonal=diagonal,
                       anchor_valid=anchor_valid, member=member,
                       servers_of=servers_of)


def _block_membership(ramp_shape: Coord, offsets, counts, bases, spans):
    """(anchor_valid [n_shapes, n_cells], member [n_shapes, n_cells,
    n_srv], servers_of [n_shapes, n_cells, MAX_CELLS]) of every distinct
    shape anchored at every cell of the ramp (cells and servers both in
    grid-flattened order): `enumerate_block`'s modulo per cell, the
    explicit in-ramp test the diagonal's (dim + 1) wrap needs, and the
    origin span. The host scans diagonal origins k over meta[2] + 2
    values, but k and k - S alias the same block, so the k < S anchors
    cover every class in the same first-fit order. Invalid anchors hold
    no member and no server."""
    C, R, S = ramp_shape
    dims = np.array(ramp_shape)
    n_cells = C * R * S
    n_shapes, max_cells, _ = offsets.shape
    origins = np.stack(np.unravel_index(np.arange(n_cells), ramp_shape), -1)
    anchor_valid = np.zeros((n_shapes, n_cells), bool)
    member = np.zeros((n_shapes, n_cells, n_cells), bool)
    servers_of = np.full((n_shapes, n_cells, max_cells), -1, np.int32)
    for i in range(n_shapes):
        cnt = int(counts[i])
        cells = (origins[:, None, :] + offsets[i, None, :cnt]) % bases[i]
        in_ramp = (cells < dims).all(axis=(1, 2))
        in_span = (origins < np.minimum(spans[i], (C, R, S))).all(axis=1)
        valid = in_ramp & in_span
        codes = np.ravel_multi_index(
            tuple(cells[valid].transpose(2, 0, 1)), ramp_shape)
        anchor_valid[i] = valid
        servers_of[i, valid, :cnt] = codes
        member[i][np.nonzero(valid)[0][:, None], codes] = True
    return anchor_valid, member, servers_of


# =========================================================================
# Config tables: everything static per (model, partition degree).
# =========================================================================

@dataclasses.dataclass
class ConfigPads:
    n_ops: int        # N = n_orig * max_split: op slot (o, k) = o*S + k
    n_deps: int       # M = n_blocks * max_split**2: dep slot (b, i, j)
    n_fwd: int        # F: padded forward-op scan slots
    n_parents: int    # P: padded parent-candidate slots
    max_split: int    # S: maximum sub-ops per op (block side)
    n_groups: int     # G: padded candidate collective groups
    n_orig: int       # No: padded original (unpartitioned) op slots
    n_blocks: int     # B: padded dep blocks (original edges + cliques)
    n_deps_used: int  # the largest row's real deps (the rest: padding)


def config_tables_for(graph, degree: int, quantum: float) -> dict:
    """Unpadded per-(model, degree) tables (numpy, f64), in the HOST's
    order (``partition_graph(...).finalize()``), with each sub-op's and
    sub-dep's block coordinates beside them (`_block_coords`):
    `stack_config_tables` lays the rows out by those.

    ``graph`` is the job's raw profile graph; ``degree`` the action (the
    per-op split cap fed to the SiP-ML rule, reference:
    agents/partitioners/sip_ml_op_partitioner.py:46).
    """
    from ddls_tpu.demands.job import Job
    from ddls_tpu.sim.actions import build_grouping_arrays

    if degree != 1 and degree % 2 != 0:
        # build_shape_tables has no rows for odd splits > 1 (the RAMP
        # symmetry rule the partitioners enforce); a silent all-fail row
        # would diverge from the host placer, which happily scans
        # factor_pairs(3) shapes
        raise ValueError(f"degree must be 1 or even, got {degree}")
    action = build_partition_action(graph, quantum, degree)
    pgraph = partition_graph(graph, action)
    arrays = pgraph.finalize()
    n, m = pgraph.n_ops, pgraph.n_deps
    op_index = arrays["op_index"]

    original = Job(graph=graph, num_training_steps=1,
                   max_acceptable_jct_frac=1.0, job_id=0,
                   details={"model": "cfg", "job_idx": 0})
    partitioned = Job(graph=pgraph, num_training_steps=1,
                      max_acceptable_jct_frac=1.0, job_id=0,
                      details={"model": "cfg", "job_idx": 0},
                      original_job=original)

    forward_graph = graph.forward_view()
    n_forward = len(forward_graph.op_ids)
    split_fwd = {str(int(op)): int(action.get(str(int(op)), 1))
                 for op in forward_graph.op_ids}
    split_fwd = {k: v for k, v in split_fwd.items() if v > 1}

    topo = forward_graph.topo_order()
    fwd_slot = {str(int(op)): i for i, op in enumerate(topo)}

    f_split = np.zeros(len(topo), np.int32)
    f_mem = np.zeros(len(topo), np.float64)
    f_parents = []
    f_sub_fwd = np.full((len(topo), degree if degree > 0 else 1), -1,
                        np.int32)
    f_sub_bwd = np.full_like(f_sub_fwd, -1)
    insertion_rank = np.full(n, 0, np.int64)
    ins = 0
    for i, op in enumerate(topo):
        op_s = str(int(op))
        split = split_fwd.get(op_s, 1)
        b_op = backward_op_id(op_s, n_forward)
        mem = graph.memory_cost(op_s)
        if graph.has_op(b_op):
            mem += graph.memory_cost(b_op)
        f_split[i] = split
        f_mem[i] = mem / split
        f_parents.append([fwd_slot[str(int(p))]
                          for p in forward_graph.parents(op)])
        for k in range(split):
            if split > 1:
                fid = op_index[partitioned_op_id(op_s, k)]
                bid = op_index[partitioned_op_id(b_op, k)]
            else:
                fid = op_index[op_s]
                bid = op_index[b_op]
            f_sub_fwd[i, k] = fid
            f_sub_bwd[i, k] = bid
            # host insertion order: per placed server, fwd sub then bwd sub
            # (agents/placers.py:67-74,91-98) — feeds the SRPT stable-sort
            # tie-break (OpPlacement.worker_to_ops insertion order)
            insertion_rank[fid] = ins
            insertion_rank[bid] = ins + 1
            ins += 2

    grouping = build_grouping_arrays(original, partitioned, split_fwd)
    cand = [g for g in grouping["groups"] if not g["sync"]]
    sync = [g for g in grouping["groups"] if g["sync"]]
    edge_size = arrays["edge_size"]
    op_ok, dep_bij, blk_src, blk_dst = _block_coords(
        graph, pgraph, split_fwd, n_forward)

    return {
        "n_ops": n, "n_deps": m,
        "op_ok": op_ok, "dep_bij": dep_bij,
        "blk_src": blk_src, "blk_dst": blk_dst,
        "n_orig": len(graph.op_ids),
        "op_compute": arrays["compute"].astype(np.float64),
        "op_sorted_rank": arrays["op_sorted_rank"].astype(np.int32),
        "num_parents": arrays["num_parents"].astype(np.int32),
        "insertion_rank": insertion_rank.astype(np.int32),
        "dep_src": arrays["edge_src"].astype(np.int32),
        "dep_dst": arrays["edge_dst"].astype(np.int32),
        "dep_size": edge_size.astype(np.float64),
        "dep_mutual": arrays["edge_mutual"].astype(bool),
        "dep_sorted_rank": arrays["edge_sorted_rank"].astype(np.int32),
        "f_split": f_split, "f_mem": f_mem, "f_parents": f_parents,
        "f_sub_fwd": f_sub_fwd, "f_sub_bwd": f_sub_bwd,
        "groups": cand, "sync": sync,
        "o2o_edges": grouping["o2o_edges"].astype(np.int32),
        "seq_compute": float(arrays["compute"].sum()),
    }


def _block_coords(graph, pgraph, split_fwd: Dict[str, int], n_forward: int):
    """Where `partition_graph` put every sub-op and sub-dep, as block
    coordinates (sim/partition.py:model_split): sub-op k of original op
    o is (o, k); an original edge u -> v is the all-to-all block of its
    split(u) x split(v) sub-deps, a split backward op u the clique
    block u -> u without its diagonal, and a sub-dep is (block b,
    source shard i, destination shard j). In ``pgraph.finalize()``
    order: ``op_ok`` [n, 2], ``dep_bij`` [m, 3]; per block the original
    op slot of its source and destination, ``blk_src`` / ``blk_dst``
    [B] (original edges in ``graph.edge_ids`` order, then cliques)."""
    arrays = pgraph.finalize()
    split_of = dict(split_fwd)
    for f_op, split in split_fwd.items():
        split_of[backward_op_id(f_op, n_forward)] = split
    o_slot = {str(int(op)): o for o, op in enumerate(graph.op_ids)}
    sub = {}
    for op_s, o in o_slot.items():
        split = split_of.get(op_s, 1)
        if split > 1:
            for k in range(split):
                sub[partitioned_op_id(op_s, k)] = (o, k)
        else:
            sub[op_s] = (o, 0)
    op_ok = np.array([sub[op] for op in arrays["op_ids"]],
                     np.int32).reshape(-1, 2)
    block = {(o_slot[str(int(u))], o_slot[str(int(v))]): b
             for b, (u, v) in enumerate(graph.edge_ids)}
    dep_bij = np.zeros((pgraph.n_deps, 3), np.int32)
    for e, (u, v) in enumerate(arrays["edge_ids"]):
        (ou, i), (ov, j) = sub[u], sub[v]
        dep_bij[e] = (block.setdefault((ou, ov), len(block)), i, j)
    ends = np.array(sorted(block, key=block.get), np.int32).reshape(-1, 2)
    return op_ok, dep_bij, ends[:, 0], ends[:, 1]


#: how a block's deps are priced (``blk_kind``; 0 = a padded block)
BLK_CANDIDATE, BLK_SYNC, BLK_O2O = 1, 2, 3


def _block_pricing(c: dict) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """(kind [B], candidate group [B] or -1, sync message size [B]) of
    one `config_tables_for` row's blocks, from the grouping's edge
    lists. Raises where a block's deps are not all priced one way (two
    kinds, two candidate groups, or sync pairs of different message
    sizes): `jax_price_and_score` gives a block ONE group value."""
    m = c["n_deps"]
    kind = np.zeros(m, np.int32)
    grp = np.full(m, -1, np.int32)
    msg = np.zeros(m, np.float64)
    for gi, g in enumerate(c["groups"]):
        kind[g["edges"]], grp[g["edges"]] = BLK_CANDIDATE, gi
    for g in c["sync"]:
        kind[g["edges"]], msg[g["edges"]] = BLK_SYNC, g["msg"]
    kind[c["o2o_edges"]] = BLK_O2O
    if not kind.all():
        raise ValueError("a dep is in no collective group and not "
                         "one-to-one")
    block = c["dep_bij"][:, 0]
    first = np.zeros(len(c["blk_src"]), np.int64)
    first[block[::-1]] = np.arange(m)[::-1]   # each block's first dep
    split = ((kind != kind[first][block]) | (grp != grp[first][block])
             | (msg != msg[first][block]))
    if split.any():
        b = int(block[np.argmax(split)])
        raise ValueError(
            f"block {b} ({int(c['blk_src'][b])} -> {int(c['blk_dst'][b])})"
            " is split between collective groups: its deps are not "
            "priced one way")
    return kind[first], grp[first], msg[first]


def table_slots(c: dict, max_split: int) -> Tuple[np.ndarray, np.ndarray]:
    """(op slot [n], dep slot [m]) of one `config_tables_for` row in the
    stacked tables: host (``finalize()``) index -> table position."""
    S = max_split
    op_slot = c["op_ok"][:, 0] * S + c["op_ok"][:, 1]
    b, i, j = c["dep_bij"].T
    return op_slot.astype(np.int32), ((b * S + i) * S + j).astype(np.int32)


def stack_config_tables(per_cfg: Sequence[dict],
                        shape_tables: ShapeTables) -> Tuple[dict, ConfigPads]:
    """Pad + stack per-config tables along a leading cfg axis, in BLOCK
    order: op slot (o, k) = o*S + k and dep slot (b, i, j) = (b*S + i)*S
    + j (`_block_coords`), so that the lookahead reads a dep's source
    and destination by broadcast and reduction, never per-element
    indirection (sim/jax_lookahead.py:DepBlocks). Slots no sub-op or
    sub-dep lands on are masked by ``op_valid`` / ``dep_valid``, as
    trailing pads were. ``dep_edge`` keeps each dep's host edge index:
    the one place flat order is semantic (`jax_price_and_score`'s SRPT
    tie-break).

    `group_collectives` (sim/actions.py) claims whole out-edge sets of a
    forward op's shards and whole in-edge sets of its backward op's, so
    a block's deps are priced ONE way (`_block_pricing` checks it, row
    by row): ``blk_kind`` says which (:data:`BLK_CANDIDATE` collective,
    :data:`BLK_SYNC` clique, :data:`BLK_O2O`; 0 = padding), ``blk_grp``
    the candidate group of a block's deps (-1: none) — read the other
    way, a group's member blocks, whose rows and columns are its member
    ops — and ``blk_msg`` a clique's message size, the same for each of
    its 2-edge sync pairs. Pricing needs no index table beyond these.

    ``op_fwd`` [cfg, n_orig] is the placement scan's way back from its
    forward-op slots to the op slots: original op o (forward or its
    backward mirror) is placed with forward slot ``op_fwd[o]`` (-1: a
    padded o), and shard k of either sits on server k of that slot's
    block — which holds because the sub-ops of forward slot f are the
    op slots o*S + 0 .. o*S + split - 1 of ONE original op o
    (`_forward_slot_of_ops` checks it, row by row, and raises)."""
    S = int(shape_tables.counts.max())
    n_orig = max(c["n_orig"] for c in per_cfg)
    n_blocks = max((len(c["blk_src"]) for c in per_cfg), default=1) or 1
    pads = ConfigPads(
        n_ops=n_orig * S,
        n_deps=n_blocks * S * S,
        n_deps_used=max(c["n_deps"] for c in per_cfg),
        n_orig=n_orig,
        n_blocks=n_blocks,
        n_fwd=max(len(c["f_split"]) for c in per_cfg),
        n_parents=max((len(p) for c in per_cfg for p in c["f_parents"]),
                      default=1) or 1,
        max_split=S,
        n_groups=max((len(c["groups"]) for c in per_cfg), default=1) or 1,
    )
    K = len(per_cfg)
    N, M, F, P = pads.n_ops, pads.n_deps, pads.n_fwd, pads.n_parents
    G = pads.n_groups

    out = {
        "n_ops": np.zeros(K, np.int32),
        "n_deps": np.zeros(K, np.int32),
        "n_fwd": np.zeros(K, np.int32),
        "op_valid": np.zeros((K, N), bool),
        "op_compute": np.zeros((K, N), np.float64),
        "op_sorted_rank": np.zeros((K, N), np.int32),
        "num_parents": np.zeros((K, N), np.int32),
        "insertion_rank": np.zeros((K, N), np.int32),
        "dep_valid": np.zeros((K, M), bool),
        "dep_size": np.zeros((K, M), np.float64),
        "dep_mutual": np.zeros((K, M), bool),
        "dep_sorted_rank": np.zeros((K, M), np.int32),
        "dep_edge": np.full((K, M), M, np.int32),
        "blk_src": np.full((K, n_blocks), -1, np.int32),
        "blk_dst": np.full((K, n_blocks), -1, np.int32),
        "blk_kind": np.zeros((K, n_blocks), np.int32),
        "blk_grp": np.full((K, n_blocks), -1, np.int32),
        "blk_msg": np.zeros((K, n_blocks), np.float64),
        "f_valid": np.zeros((K, F), bool),
        "f_split": np.ones((K, F), np.int32),
        "f_mem": np.zeros((K, F), np.float64),
        "f_parents": np.full((K, F, P), -1, np.int32),
        "f_sub_fwd": np.full((K, F, S), -1, np.int32),
        "f_sub_bwd": np.full((K, F, S), -1, np.int32),
        "op_fwd": np.full((K, n_orig), -1, np.int32),
        "grp_valid": np.zeros((K, G), bool),
        "grp_msg": np.zeros((K, G), np.float64),
        "seq_compute": np.zeros(K, np.float64),
    }
    for k, c in enumerate(per_cfg):
        n, m, f = c["n_ops"], c["n_deps"], len(c["f_split"])
        if c["op_ok"][:, 1].max(initial=0) >= S:
            raise ValueError("a row splits an op more than max_split ways")
        ops, deps = table_slots(c, S)
        # host index -> slot for index-valued entries; -1 pads stay -1
        op_at = np.append(ops, -1)
        out["n_ops"][k], out["n_deps"][k], out["n_fwd"][k] = n, m, f
        out["op_valid"][k, ops] = True
        out["op_compute"][k, ops] = c["op_compute"]
        out["op_sorted_rank"][k, ops] = c["op_sorted_rank"]
        out["num_parents"][k, ops] = c["num_parents"]
        out["insertion_rank"][k, ops] = c["insertion_rank"]
        out["dep_valid"][k, deps] = True
        out["dep_size"][k, deps] = c["dep_size"]
        out["dep_mutual"][k, deps] = c["dep_mutual"]
        out["dep_sorted_rank"][k, deps] = c["dep_sorted_rank"]
        out["dep_edge"][k, deps] = np.arange(m)
        out["blk_src"][k, :len(c["blk_src"])] = c["blk_src"]
        out["blk_dst"][k, :len(c["blk_dst"])] = c["blk_dst"]
        for name, per_block in zip(("blk_kind", "blk_grp", "blk_msg"),
                                   _block_pricing(c)):
            out[name][k, :len(per_block)] = per_block
        out["f_valid"][k, :f] = True
        out["f_split"][k, :f] = c["f_split"]
        out["f_mem"][k, :f] = c["f_mem"]
        for i, parents in enumerate(c["f_parents"]):
            out["f_parents"][k, i, :len(parents)] = parents
        out["f_sub_fwd"][k, :f, :c["f_sub_fwd"].shape[1]] = \
            op_at[c["f_sub_fwd"]]
        out["f_sub_bwd"][k, :f, :c["f_sub_bwd"].shape[1]] = \
            op_at[c["f_sub_bwd"]]
        out["op_fwd"][k] = _forward_slot_of_ops(
            out["f_sub_fwd"][k], out["f_sub_bwd"][k], out["f_split"][k],
            out["f_valid"][k], n_orig)
        out["grp_valid"][k, :len(c["groups"])] = True
        out["grp_msg"][k, :len(c["groups"])] = [g["msg"] for g in c["groups"]]
        out["seq_compute"][k] = c["seq_compute"]
    return out, pads


def _forward_slot_of_ops(f_sub_fwd, f_sub_bwd, f_split, f_valid,
                         n_orig: int) -> np.ndarray:
    """[n_orig] forward scan slot of each original op of one stacked
    row (-1: no forward slot places it). Raises unless the row is in
    block order the way `jax_allocate_job` assumes: the ``f_split[f]``
    valid sub-op slots of forward slot f (and of its backward mirror)
    are o*S + 0, o*S + 1, ... of one original op o, and no two forward
    slots share an o."""
    S = f_sub_fwd.shape[1]
    op_fwd = np.full(n_orig, -1, np.int32)
    shard = np.arange(S)
    for f in np.nonzero(f_valid)[0]:
        for name, sub in (("f_sub_fwd", f_sub_fwd[f]),
                          ("f_sub_bwd", f_sub_bwd[f])):
            o, rem = divmod(int(sub[0]), S)
            want = np.where(shard < f_split[f], o * S + shard, -1)
            if sub[0] < 0 or rem or not (sub == want).all():
                raise ValueError(
                    f"{name}[{f}] = {sub.tolist()} is not the first "
                    f"{int(f_split[f])} op slots of one original op: the "
                    "tables are not in block order")
            if op_fwd[o] >= 0:
                raise ValueError(
                    f"original op {o} is placed by forward slots "
                    f"{int(op_fwd[o])} and {int(f)}")
            op_fwd[o] = f
    return op_fwd


# =========================================================================
# The scan-ified allocate_job kernel.
# =========================================================================

def _anchor_masks(free, st: ShapeTables):
    """[n_shapes, n_cells] anchor-validity masks for EVERY distinct shape
    given the flat free-server vector (True = free of other jobs AND
    enough memory — block_ok's conjunction, agents/block_search.py:84-101):
    an anchor stands where the static geometry allows one
    (``st.anchor_valid``) and none of its block's members
    (``st.member``) is taken — ONE contraction of the taken servers with
    the static 0/1 membership table (counts <= max_split: exact in a
    single bf16 pass), a matmul over the lanes under their ``vmap``."""
    import jax.numpy as jnp

    taken = jnp.einsum("v,scv->sc", (~free).astype(jnp.float32),
                       jnp.asarray(st.member, jnp.float32))
    return jnp.asarray(st.anchor_valid) & (taken == 0)


def _first_fit_from_masks(masks, shape_row):
    """First-fit over a (traced) per-split shape-order row: returns
    (shape_id, origin_rank, found) — the first shape in row order with any
    valid anchor, and its smallest lexicographic anchor, exactly
    `first_fit_block`'s (shape order, then origin lex order) semantics.
    Every shape's verdict is reduced once; a candidate reads its shape's
    by comparison with the shape ids."""
    import jax.numpy as jnp

    n_shapes, n_cells = masks.shape
    big = jnp.int32(n_cells + 1)
    lex = jnp.arange(n_cells, dtype=jnp.int32)
    # a shape's first anchor, `big` where it has none
    rank = jnp.where(masks, lex, big).min(axis=1)          # [n_shapes]
    is_shape = shape_row[:, None] == jnp.arange(n_shapes)  # [row, n_shapes]
    cand_rank = jnp.where(is_shape, rank, big).min(axis=1)
    cand_valid = cand_rank < big

    best_shape = jnp.int32(-1)
    best_rank = big
    found = jnp.bool_(False)
    for p in range(shape_row.shape[0]):
        take = cand_valid[p] & ~found
        best_shape = jnp.where(take, shape_row[p], best_shape)
        best_rank = jnp.where(take, cand_rank[p], best_rank)
        found = found | cand_valid[p]
    return best_shape, best_rank, found


def _select_row(table, index, fill):
    """``table[index]`` of a small table with rows along axis 0 — for
    each ``index`` of any shape — as a comparison with the row numbers
    and a max over them; an index that names no row reads ``fill``
    (which no entry may lie under)."""
    import jax.numpy as jnp

    hit = index[..., None] == jnp.arange(table.shape[0])
    hit = hit.reshape(hit.shape + (1,) * (table.ndim - 1))
    return jnp.where(hit, table, fill).max(axis=index.ndim)


def config_rows(tables: dict, cfg) -> dict:
    """ONE config's rows: every ``[n_cfg, ...]`` leaf of the stacked
    tables (`stack_config_tables`) read at the traced (model, degree)
    row ``cfg``. The kernels below take these rows, never the tables: a
    ``lax.cond`` on the lanes' path then carries ``[lanes, ...]`` rows
    that are batched already, where a table its branch closed over was
    written out once a lane (`_episode_kernels`' ``decision``)."""
    return {name: table[cfg] for name, table in tables.items()}


def jax_allocate_job(mem, other_free, rows, st: ShapeTables,
                     pads: ConfigPads):
    """Scan-ified `allocate_job` (agents/placers.py:103; reference
    placers/utils.py:532): walk the padded forward-op sequence in topo
    order; per op try parent co-location then the generic first-fit block
    search, and commit memory + the op's servers between steps.

    The scan reaches servers, cells and op slots by comparison, broadcast
    and reduction over static tables, never by an index per element (on
    the chip each index vector of a gather or scatter is one serial
    address computation, and under the lanes' ``vmap`` there is one a
    lane): anchor masks from ``st.member``, a block's servers from
    ``st.servers_of``, a parent's servers, the room check and the memory
    commit through ``[max_split] x [n_srv]`` one-hots, and the
    op -> server map assembled ONCE after the scan from the per-forward-op
    record the scan carries (the tables' ``op_fwd``: block order,
    `stack_config_tables`). tests/indexed_placer.py keeps the indexed
    scan this replaced as the bitwise reference.

    ``mem`` [n_srv] free memory per server; ``other_free`` [n_srv] bool
    (True = not occupied by another job; constant during one job's
    allocation); ``rows`` the (model, degree) config's rows
    (`config_rows`). Returns
    (op_to_server [N] i32, -1 where unplaced, new_mem [n_srv], ok bool).
    On ok=False outputs are partial and must be discarded by the caller
    (the host returns None and the composite action drops the job)."""
    import jax
    import jax.numpy as jnp

    n_cells, n_srv = st.member.shape[1:]
    Smax, F = pads.max_split, pads.n_fwd

    # a block's servers by (shape, anchor cell), + 1: a one-hot row of
    # the found anchor contracts with it to the servers + 1, and no
    # anchor to 0 (whole numbers; HIGHEST keeps them exact past the 256
    # a single bf16 pass holds)
    servers_of1 = jnp.asarray(st.servers_of.reshape(-1, Smax) + 1,
                              jnp.float32)
    anchor = jnp.arange(servers_of1.shape[0])
    lane = jnp.arange(Smax)
    server = jnp.arange(n_srv)

    f_split = rows["f_split"]
    # every forward op's candidate shapes, in find_sub_block order
    f_shapes = _select_row(jnp.asarray(st.row), f_split, -1)

    def body(carry, op):
        (mem, op_servers, op_count, ok) = carry
        f, valid, split, per_mem, parents, shape_row = op
        room = mem >= per_mem

        # ---- parent co-location (placers.py:49-77): first parent whose
        # server count equals split and whose servers all have room
        p_servers = _select_row(op_servers, parents, -1)    # [P, Smax]
        p_count = _select_row(op_count, parents, 0)         # [P]
        on_full = ((p_servers[:, :, None] == server) & ~room).any(axis=-1)
        mem_ok = ~((lane < p_count[:, None]) & on_full).any(axis=-1)
        okp = (parents >= 0) & (p_count > 0) & (p_count == split) & mem_ok
        colo_found = jnp.bool_(False)
        colo_servers = jnp.full((Smax,), -1, jnp.int32)
        for pi in range(parents.shape[0]):
            take = okp[pi] & ~colo_found
            colo_servers = jnp.where(take, p_servers[pi], colo_servers)
            colo_found = colo_found | okp[pi]

        # ---- regular symmetric block search (find_sub_block order)
        masks = _anchor_masks(other_free & room, st)
        sid, rank, block_found = _first_fit_from_masks(masks, shape_row)
        at = jnp.where(block_found, sid * n_cells + rank, -1) == anchor
        block_servers = jnp.dot(
            at.astype(jnp.float32), servers_of1,
            precision=jax.lax.Precision.HIGHEST).astype(jnp.int32) - 1

        servers = jnp.where(colo_found, colo_servers, block_servers)
        placed_ok = colo_found | block_found

        # ---- masked commit of this op's fwd+bwd sub-op pairs: a block's
        # servers are distinct, so at most one term lands on a server
        active = (lane < split) & placed_ok & valid & (servers >= 0)
        lands = active[:, None] & (servers[:, None] == server)
        mem = mem - jnp.where(lands, per_mem, 0).sum(axis=0)
        op_servers = op_servers.at[f].set(jnp.where(active, servers, -1))
        op_count = op_count.at[f].set(
            jnp.where(valid & placed_ok, split, 0))
        return (mem, op_servers, op_count, ok & (placed_ok | ~valid)), None

    init = (mem,
            jnp.full((F, Smax), -1, jnp.int32),
            jnp.zeros((F,), jnp.int32),
            jnp.bool_(True))
    ops = (jnp.arange(F, dtype=jnp.int32), rows["f_valid"], f_split,
           rows["f_mem"], rows["f_parents"], f_shapes)
    (new_mem, op_servers, _, ok), _ = jax.lax.scan(body, init, ops)
    # shard k of an op (or of its backward mirror) sits on server k of
    # its forward slot's block: op slot (o, k) = o * Smax + k
    ots = _select_row(op_servers, rows["op_fwd"], -1)
    return ots.reshape(-1), new_mem, ok


# =========================================================================
# Dep pricing + SRPT scores (the array mirror of assign_dep_run_times and
# the SRPT schedulers, for a single placed job).
# =========================================================================

def _jnp_all_reduce_time(msg, n_servers, n_racks, n_cgs, *, x, rate,
                         prop, io):
    """Vectorised mirror of `sim/comm_model.py:ramp_all_reduce_time`
    (reference: actions/utils.py:42-88), identical accumulation order so
    f64 results match the host bit-for-bit. All span inputs are traced
    f64 >= 1; ``msg`` static per group."""
    import jax.numpy as jnp

    mem_frequency, peak_flops, bytes_per_comp = 2e12, 130e12, 2
    data_per_tx = rate / x

    subs = [n_cgs, jnp.minimum(n_cgs, n_servers), n_racks,
            jnp.ceil(n_servers / x)]
    msg_sizes = [jnp.ceil(msg / subs[0])]
    for sub in subs[1:]:
        msg_sizes.append(jnp.ceil(msg_sizes[-1] / sub))

    comm = jnp.zeros_like(msg)
    comp = jnp.zeros_like(msg)
    for step, sub in enumerate(subs):
        live = sub > 1
        safe_sub = jnp.where(live, sub, 2.0)
        # parallel_add_time (comm_model.py:44-56)
        n_op = jnp.ceil(jnp.log2(safe_sub))
        n_bytes = (safe_sub + 1) * bytes_per_comp
        ai = n_op / n_bytes
        # host: parallel_add_time(msg_sizes[step] * sub, sub) computes
        # n_op * (data_sz / devices) / bytes_per_comp; the product and
        # quotient are exact in f64 at these magnitudes
        total_ops = n_op * (msg_sizes[step] * safe_sub / safe_sub) \
            / bytes_per_comp
        add_t = total_ops / jnp.minimum(mem_frequency * ai, peak_flops)
        comp = comp + jnp.where(live, add_t, 0.0)
        # effective_transceivers(x, sub, J=1) (comm_model.py:34-41)
        spare = jnp.minimum(jnp.floor(x / 1.0),
                            jnp.floor(x / (safe_sub - 1))) - 1.0
        bw = (1.0 + spare) * data_per_tx
        comm = comm + jnp.where(
            live, prop + 2 * io + msg_sizes[step] / bw, 0.0)
    return 2 * comm + comp


def jax_price_and_score(sc, rows, st: ShapeTables,
                        pads: ConfigPads, comm: dict):
    """Price every dep of one placed job and build the SRPT lookahead
    scores — the array mirror of `assign_dep_run_times`
    (sim/actions.py:436), `SRPTOpScheduler`/`SRPTDepScheduler`
    (agents/schedulers.py) and the score assembly in
    `build_native_lookahead_arrays` (native/arrays.py).

    Every operand reaches its dep through the block layout
    (`stack_config_tables`): a dep (b, i, j) runs from the server of
    row i of block b to the server of its column j, and is priced by
    its block's kind — broadcasts, reductions and one-hots over the
    servers, never an index per dep or per sub-op (one gather or
    scatter of M elements runs element by element on the chip).

    ``sc`` [N] per-op server codes (grid-flattened, -1 pads); ``rows``
    the placed config's rows (`config_rows`). Returns
    (times [M], is_flow [M], pair_used [n_srv, n_srv], op_score [N],
    dep_score [M], finite_ok): ``pair_used[x, y]`` — does a flow dep
    run from server x to server y — is all the channel checks need of
    a single-channel complete topology (`eval_cfg`).
    """
    import jax
    import jax.numpy as jnp

    C, R, S = st.ramp_shape
    n_srv = C * R * S
    M, B, side, G = (pads.n_deps, pads.n_blocks, pads.max_split,
                     pads.n_groups)
    x = float(comm["x"])
    rate, prop, io = comm["rate"], comm["prop"], comm["io"]

    codes = np.arange(n_srv)
    c_of_np = codes // (R * S)
    r_of_np = (codes // S) % R
    s_of_np = codes % S

    dep_valid = rows["dep_valid"].reshape(B, side, side)
    dep_size = rows["dep_size"].reshape(B, side, side)
    blk_kind = rows["blk_kind"]                           # [B]
    blk_grp = rows["blk_grp"]

    # a dep's endpoints: the servers on its block's row and column
    _, _, src_rows, dst_rows = block_endpoints(
        sc, DepBlocks(rows["blk_src"], rows["blk_dst"]), side)
    sc_src, sc_dst = src_rows[:, :, None], dst_rows[:, None, :]
    same = sc_src == sc_dst
    # THE flow predicate, traced: mirrors OpGraph.flow_mask_from_codes
    # (graphs/op_graph.py:268) — the canonical numpy helper cannot run
    # under trace, so this is the one sanctioned re-statement; its parity
    # with the native path is pinned by tests/test_jax_pricing.py's
    # is_flow comparison
    is_flow = dep_valid & (dep_size > 0) & ~same  # ddls-lint: allow(flow-mask) -- the one sanctioned traced mirror of flow_mask_from_codes: the numpy helper cannot run under jit trace; parity pinned by test_jax_pricing.py

    dt = dep_size.dtype
    on_src = jax.nn.one_hot(src_rows, n_srv, dtype=bool)  # [B, S_i, W]
    on_dst = jax.nn.one_hot(dst_rows, n_srv, dtype=bool)  # [B, S_j, W]

    def span_counts(present):
        """Distinct (s, r, c) component counts among present servers;
        present: [..., n_srv] bool."""
        def cnt(comp_of_np, n_comp):
            onehot = jnp.asarray(np.eye(n_comp)[comp_of_np], dt)
            return ((present.astype(dt) @ onehot) > 0).sum(-1).astype(dt)
        return (cnt(s_of_np, S), cnt(r_of_np, R), cnt(c_of_np, C))

    # ---- candidate collective groups (symmetry-tested). A group's
    # edges are its member blocks' deps, so the servers of its sources
    # (u) and destinations (v), counted with multiplicity, are its
    # blocks' row and column servers times the deps on each row and
    # column: equal multisets of u- and v-codes = equal histograms
    grp_valid = rows["grp_valid"]                     # [G]
    grp_msg = rows["grp_msg"]                         # [G]
    on_row = dep_valid.sum(2, dtype=jnp.int32)        # [B, S_i] deps a row
    on_col = dep_valid.sum(1, dtype=jnp.int32)        # [B, S_j]
    blk_u = jnp.sum(jnp.where(on_src, on_row[:, :, None], 0), 1)  # [B, W]
    blk_v = jnp.sum(jnp.where(on_dst, on_col[:, :, None], 0), 1)
    member = blk_grp[:, None] == jnp.arange(G, dtype=jnp.int32)   # [B, G]
    grp_u = jnp.sum(jnp.where(member[:, :, None], blk_u[:, None, :], 0), 0)
    grp_v = jnp.sum(jnp.where(member[:, :, None], blk_v[:, None, :], 0), 0)
    symmetric = jnp.all(grp_u == grp_v, axis=1) & grp_valid
    present = (grp_u + grp_v) > 0                     # [G, n_srv]
    n_in_group = present.sum(-1)
    cnt_s, cnt_r, cnt_c = span_counts(present)
    grp_time = _jnp_all_reduce_time(
        grp_msg, jnp.maximum(cnt_s, 1.0), jnp.maximum(cnt_r, 1.0),
        jnp.maximum(cnt_c, 1.0), x=x, rate=rate, prop=prop, io=io)
    grp_time = jnp.where(n_in_group <= 1, jnp.zeros_like(grp_time),
                         grp_time)
    of_blk = jnp.clip(blk_grp, 0)                     # B indices, not M
    blk_collective = (blk_kind == BLK_CANDIDATE) & symmetric[of_blk]

    # ---- one-to-one pricing: the static one-to-one edges, and the
    # edges of asymmetric groups, which fall back to it
    # (assign_dep_run_times's extra_e path, sim/actions.py:505-540)
    o2o_time = jnp.where(same | (dep_size == 0), jnp.zeros_like(dep_size),
                         prop + 2 * io + dep_size / rate)

    # ---- sync pairs (always collectives; 2 servers or same-server zero)
    def spans(comp):
        return jnp.where(comp(sc_src) == comp(sc_dst), 1.0, 2.0)
    sync_time = _jnp_all_reduce_time(
        rows["blk_msg"][:, None, None], spans(lambda c: c % S),
        spans(lambda c: (c // S) % R), spans(lambda c: c // (R * S)),
        x=x, rate=rate, prop=prop, io=io)
    sync_time = jnp.where(same, jnp.zeros_like(sync_time), sync_time)

    times = jnp.where(
        (blk_kind == BLK_SYNC)[:, None, None], sync_time,
        jnp.where(blk_collective[:, None, None],
                  grp_time[of_blk][:, None, None], o2o_time)).reshape(M)
    dep_valid, is_flow = dep_valid.reshape(M), is_flow.reshape(M)
    # the cluster zeroes non-flow dep run times at mount
    # (cluster.py:_register_running_job:708-718); SRPT ranking below uses
    # the RAW priced times because the schedulers run before the mount
    mounted_times = jnp.where(is_flow, times, jnp.zeros_like(times))

    # ---- SRPT dep priorities: one stable descending argsort over the
    # priced costs in edge order (agents/schedulers.py:_srpt_priorities)
    m = rows["n_deps"].astype(dt)
    cost_key = jnp.where(dep_valid, -times, jnp.asarray(jnp.inf, dt))
    # "edge order" is the HOST's: the tables are in block order, so ties
    # break on each slot's own edge index, not on its position
    order = jnp.lexsort((rows["dep_edge"], cost_key))
    # each dep's rank = the inverse permutation, by a second sort: a
    # scatter of M elements is the loop the first paragraph names
    dep_pri = jnp.argsort(order).astype(dt)
    # the lookahead engines read dep priorities off the channel mounts, so
    # only FLOW deps carry their SRPT rank; non-flows score with priority 0
    # (native/arrays.py:build_native_lookahead_arrays prices flow_idx
    # only)
    dep_pri = jnp.where(is_flow, dep_pri, jnp.zeros_like(dep_pri))
    dep_score = dep_pri * (m + 1) + (
        m - rows["dep_sorted_rank"].astype(dt))

    # ---- SRPT op priorities: per-worker stable sort by compute cost
    # descending, insertion (placement) order breaking ties
    # (agents/schedulers.py:29-38 + OpPlacement.worker_to_ops order)
    op_valid = rows["op_valid"]
    op_cost = rows["op_compute"]
    ins = rows["insertion_rank"]
    same_srv = (sc[:, None] == sc[None, :]) & (sc[:, None] >= 0)
    before = (op_cost[None, :] > op_cost[:, None]) | (
        (op_cost[None, :] == op_cost[:, None]) & (ins[None, :] < ins[:, None]))
    op_pri = (same_srv & before & op_valid[None, :]).sum(1).astype(dt)
    n = rows["n_ops"].astype(dt)
    op_score = op_pri * (n + 1) + (
        n - rows["op_sorted_rank"].astype(dt))

    # ---- channels (single-channel complete topology: a flow rides the
    # direct link of its ordered server pair): which pairs carry one,
    # over j into the destination server, then over (b, i) from the source
    to_dst = jnp.any(is_flow.reshape(B, side, side)[:, :, :, None]
                     & on_dst[:, None, :, :], axis=2)  # [B, S_i, W]
    pair_used = jnp.any(on_src[:, :, :, None] & to_dst[:, :, None, :],
                        axis=(0, 1))                   # [W, W]
    # the host raises on non-finite priced times (comm_model.py:99-100,
    # actions.py:541-543); a traced kernel cannot, so callers must treat
    # finite_ok=False as that hard failure
    finite_ok = jnp.all(jnp.isfinite(mounted_times))
    return mounted_times, is_flow, pair_used, op_score, dep_score, finite_ok


def pair_channel_one_hot(pair_channel, n_chan: int):
    """Which channel an ordered server pair's direct link is, as a
    one-hot [n_srv, n_srv, n_chan] bool (the diagonal, -1, is no
    channel): `placement_masks`'s static operand."""
    import jax.numpy as jnp

    return jnp.asarray(
        np.asarray(pair_channel)[:, :, None] == np.arange(n_chan))


def placement_masks(ots, op_valid, pair_used, pair_is_chan, chan_occ):
    """What a placed job would occupy, and whether it may: (ok_chan —
    no channel its flows ride is another job's; chan_mask [n_chan] —
    those channels; srv_mask [n_srv] — the servers its ops sit on).
    ``pair_used`` [n_srv, n_srv] is `jax_price_and_score`'s,
    ``pair_is_chan`` `pair_channel_one_hot`'s: reductions over the
    servers, where a per-dep channel vector would be indexed dep by
    dep."""
    import jax.numpy as jnp

    chan_mask = jnp.any(pair_is_chan & pair_used[:, :, None], axis=(0, 1))
    ok_chan = ~jnp.any(chan_mask & (chan_occ >= 0))
    n_srv = pair_used.shape[0]
    srv_mask = jnp.any(
        (ots[:, None] == jnp.arange(n_srv, dtype=ots.dtype))
        & op_valid[:, None], axis=0)
    return ok_chan, chan_mask, srv_mask


# =========================================================================
# The jitted decision step + episode loop.
# =========================================================================

# blocked-cause codes in the decision trace (mirrors the host's cause
# strings: actions.py Action.job_id_to_cause_of_unsuccessful_handling +
# cluster._register_blocked_job)
CAUSE_ACCEPTED = 0
CAUSE_NOT_HANDLED = 1        # action 0
CAUSE_OP_PLACEMENT = 2
CAUSE_DEP_PLACEMENT = 3
CAUSE_SLA = 4                # max_acceptable_job_completion_time_exceeded
CAUSE_ENGINE = 5             # lookahead non-convergence / non-finite price
                             # (the host raises; must never appear)

# trace-code <-> host cause-string maps (flight-recorder decision diffs:
# scripts/trace_diff.py converts a jitted decision trace into the same
# `action_decided` events the host env emits). CAUSE_ACCEPTED maps to
# None — accepted decisions carry no blocked cause.
CAUSE_CODE_TO_STR = {
    CAUSE_ACCEPTED: None,
    CAUSE_NOT_HANDLED: "not_handled",
    CAUSE_OP_PLACEMENT: "op_placement",
    CAUSE_DEP_PLACEMENT: "dep_placement",
    CAUSE_SLA: "max_acceptable_job_completion_time_exceeded",
    CAUSE_ENGINE: "engine_failure",
}
CAUSE_STR_TO_CODE = {v: k for k, v in CAUSE_CODE_TO_STR.items()
                     if v is not None}
# the host's per-sub-action causes that collapse onto one code
CAUSE_STR_TO_CODE["op_partition"] = CAUSE_OP_PLACEMENT


@dataclasses.dataclass
class EpisodeTables:
    """Everything static for a jitted canonical-RAMP episode."""
    st: ShapeTables
    tables: dict               # stacked config tables (jnp arrays)
    row_deps: np.ndarray       # host copy of tables["n_deps"]: each
    #                            (model, degree) row's real deps, for
    #                            host reducers (rl/fused.py) — no fetch
    row_ragged: np.ndarray     # per row, the forward ops the SiP-ML rule
    #                            splits fewer ways than the row's degree
    #                            (`ragged_rows`): what makes it ragged
    pads: ConfigPads
    types: List[str]           # model name -> type index (list order)
    degrees: List[int]         # action degree -> cfg column (list order)
    comm: dict                 # {x, rate, prop, io}
    pair_channel: object       # [n_srv, n_srv] jnp i32
    n_chan: int
    n_srv: int
    max_action: int            # env.max_partitions_per_op (action bound)
    sim_end: float
    eps: float                 # cluster.machine_epsilon
    success_reward: float
    fail_reward: float
    worker_mem: float          # per-server memory capacity at reset
    # scenario mirror (ddls_tpu/scenarios): dense speeds + failure
    # windows captured from env.cluster.scenario_runtime; None when the
    # scenario is nominal, so the kernels build NO inflation code and
    # the default episode program stays byte-identical
    scenario: Optional[dict] = None


#: the last workload's stacked tables (`build_episode_tables`): one
#: entry, replaced when another workload's are built
_STACKED_TABLES: dict = {}


def build_episode_tables(env, max_degree: Optional[int] = None,
                         quantum: Optional[float] = None) -> EpisodeTables:
    """Assemble the static side of the jitted episode from a host env
    (canonical RAMP single-channel complete topology only)."""
    import jax.numpy as jnp

    topo = env.cluster.topology
    dense = topo.dense_tables()
    if dense["pair_channel"] is None:
        raise ValueError("jitted episode needs a single-channel complete "
                         "topology (canonical RAMP)")
    max_degree = max_degree or env.max_partitions_per_op
    quantum = quantum or env.min_op_run_time_quantum
    if max_degree > topo.num_workers:
        # config columns above num_workers would clamp onto smaller
        # splits' shape rows inside the gather-based block search
        raise ValueError(
            f"max_degree {max_degree} exceeds the {topo.num_workers}-"
            "worker topology; cap max_partitions_per_op")

    gen = env.cluster.jobs_generator
    # one profile graph per distinct model, in sorted-model order
    model_graphs = {}
    for proto in gen.sampler.prototypes:
        model_graphs[proto.details["model"]] = proto.graph
    types = sorted(model_graphs)
    degrees = [d for d in range(1, max_degree + 1)
               if d == 1 or d % 2 == 0]

    # the stacked tables are a function of the job graphs, the degrees,
    # the quantum and the topology's shape alone; a process that builds
    # them twice for one workload (a training loop's, then the fidelity
    # replay's: `scenarios/conformance.py:jitted_decision_events`) builds
    # them once — 26 s at 570-op graphs. Keyed by the identity the
    # cluster's own memo caches trust (`cluster.py:_workload_fingerprint`)
    fingerprint = getattr(gen, "workload_fingerprint", None)
    key = (fingerprint, tuple(types), max_degree, quantum, topo.shape)
    if fingerprint is None or _STACKED_TABLES.get("key") != key:
        st = build_shape_tables(topo.shape,
                                min(max_degree, topo.num_workers))
        cfgs = [config_tables_for(model_graphs[m], d, quantum)
                for m in types for d in degrees]
        _STACKED_TABLES.update(
            key=key, value=(st, *stack_config_tables(cfgs, st)))
    st, tables, pads = _STACKED_TABLES["value"]
    jt = {k: jnp.asarray(v) for k, v in tables.items()}

    from ddls_tpu.envs.rewards import JobAcceptance

    if not isinstance(env.reward_function, JobAcceptance):
        # other reward families read lookahead details off live Job
        # objects; the jitted trace only carries the acceptance signal
        raise ValueError(
            "jitted episode replay supports the job_acceptance reward "
            f"only, env has {type(env.reward_function).__name__}")
    workers = list(topo.workers.values())
    if len({w.memory_capacity for w in workers}) != 1:
        raise ValueError("jitted episode needs homogeneous worker memory")
    # scenario mirror: completion-time inflation inputs in dense index
    # space (window kind/resource stay HOST ints -> static unroll)
    sr = getattr(env.cluster, "scenario_runtime", None)
    scenario = None
    if sr is not None and not sr.is_nominal:
        scenario = {
            "speeds": np.asarray(sr.speeds, np.float64),
            "t0": np.asarray(sr.win_t0, np.float64),
            "t1": np.asarray(sr.win_t1, np.float64),
            "rate": np.asarray(sr.win_rate, np.float64),
            "kind": [int(k) for k in sr.win_kind],
            "res": [int(r) for r in sr.win_res],
        }
    return EpisodeTables(
        st=st, tables=jt, row_deps=tables["n_deps"],
        row_ragged=ragged_rows(tables, len(types), degrees), pads=pads,
        types=types, degrees=degrees,
        comm={"x": topo.num_communication_groups,
              "rate": topo.channel_bandwidth,
              "prop": topo.intra_gpu_propagation_latency,
              "io": topo.worker_io_latency},
        pair_channel=jnp.asarray(dense["pair_channel"]),
        n_chan=len(dense["channel_ids"]),
        n_srv=topo.num_workers,
        max_action=int(env.max_partitions_per_op),
        sim_end=float(env.max_simulation_run_time),
        eps=env.cluster.machine_epsilon,
        success_reward=getattr(env.reward_function, "success_reward", 1.0),
        fail_reward=getattr(env.reward_function, "fail_reward", -1.0),
        worker_mem=float(workers[0].memory_capacity),
        scenario=scenario)


def build_job_bank(et: EpisodeTables, records: Sequence[dict]) -> dict:
    """Job bank arrays from per-arrival records: each record carries
    {model, num_training_steps, sla_frac, time_arrived}."""
    J = len(records)
    bank = {
        "type": np.zeros(J, np.int32),
        "steps": np.zeros(J, np.float64),
        "sla_frac": np.zeros(J, np.float64),
        "arrival_t": np.zeros(J + 1, np.float64),
    }
    if records and records[0]["time_arrived"] != 0.0:
        # the episode kernel seeds job 0 as queued at t=0, mirroring the
        # cluster reset ("first arrival at t=0", cluster.py:175-177)
        raise ValueError("job bank must start with a t=0 arrival")
    for i, r in enumerate(records):
        bank["type"][i] = et.types.index(r["model"])
        bank["steps"][i] = r["num_training_steps"]
        bank["sla_frac"][i] = r["sla_frac"]
        bank["arrival_t"][i] = r["time_arrived"]
    bank["arrival_t"][J] = np.inf
    return bank


def sample_job_bank(et: EpisodeTables, env, n_jobs: int, seed: int) -> dict:
    """A job bank SAMPLED from the env's own workload machinery — the
    device-collection counterpart of the host cluster's arrival stream
    (cluster.py:224: ``jobs_generator.sample_job()`` +
    ``sample_interarrival_time()``).

    The env's generator is deep-copied so its pool state (sampling-mode
    bookkeeping, job ids) is untouched, and BOTH process-global rngs the
    workload machinery draws from (numpy for the distributions, python's
    ``random`` for pool shuffles on refill) are seeded then
    snapshotted/restored around the draw, so banks are determined by
    ``seed`` alone and building them never perturbs the host envs'
    stochastic streams.

    A ``remove``-mode pool that exhausts before ``n_jobs`` ends the bank
    early — the host counterpart returns an infinite interarrival there
    and the episode simply sees no further arrivals.
    """
    import copy
    import random as _random

    gen = copy.deepcopy(env.cluster.jobs_generator)
    np_state = np.random.get_state()
    py_state = _random.getstate()
    try:
        np.random.seed(seed)
        _random.seed(seed ^ 0x5DEECE66D)
        t, recs = 0.0, []
        for _ in range(n_jobs):
            if len(gen.sampler) == 0:
                break
            job = gen.sample_job()
            recs.append({
                "model": job.details.get("model"),
                "num_training_steps": job.num_training_steps,
                "sla_frac": float(job.max_acceptable_jct_frac),
                "time_arrived": t,
            })
            t += float(gen.sample_interarrival_time())
    finally:
        np.random.set_state(np_state)
        _random.setstate(py_state)
    return build_job_bank(et, recs)


def _episode_kernels(et: EpisodeTables):
    """Shared decision / event-clock / initial-state kernels for the
    replay (`make_episode_fn`) and policy (`make_policy_episode_fn`)
    episodes."""
    import types as _types

    import jax
    import jax.numpy as jnp

    st, pads = et.st, et.pads
    n_srv, n_chan = et.n_srv, et.n_chan
    R = n_srv  # max concurrent jobs: every running job owns >= 1 server
    n_deg = len(et.degrees)
    # action value -> cfg column (-1 for odd/invalid actions); sized by
    # the env's full action bound so no action can clamp onto a valid
    # column through the gather
    deg_col = np.full(max(et.max_action, max(et.degrees)) + 1, -1,
                      np.int32)
    for i, d in enumerate(et.degrees):
        deg_col[d] = i
    deg_col = jnp.asarray(deg_col)
    eps = et.eps
    sim_end = et.sim_end
    pair_is_chan = pair_channel_one_hot(et.pair_channel, n_chan)

    # scenario inflation mirror (ddls_tpu/scenarios/failures.py): same
    # shared f64 formula the host applies at lookahead registration —
    # SLA stays judged on the NOMINAL jct (eval_cfg), only the committed
    # completion time and the traced jct are adjusted. None -> no code.
    scenario = et.scenario
    if scenario is not None:
        from ddls_tpu.scenarios.failures import (FAILURE_WORKER_PREEMPT,
                                                 inflate_duration_jax)

        _sdt = et.tables["dep_size"].dtype
        sc_speeds = jnp.asarray(scenario["speeds"], _sdt)
        sc_t0 = jnp.asarray(scenario["t0"], _sdt)
        sc_t1 = jnp.asarray(scenario["t1"], _sdt)
        sc_rate = jnp.asarray(scenario["rate"], _sdt)
        sc_kind, sc_res = scenario["kind"], scenario["res"]

        def scenario_adjusted(t, jct, srv_mask, chan_mask):
            r0 = jnp.min(jnp.where(srv_mask, sc_speeds,
                                   jnp.asarray(jnp.inf, _sdt)))
            affects = [srv_mask[r] if k == FAILURE_WORKER_PREEMPT
                       else chan_mask[r]
                       for k, r in zip(sc_kind, sc_res)]
            return inflate_duration_jax(t, jct, r0, sc_t0, sc_t1,
                                        sc_rate, affects)

    def eval_cfg(bank, carry, row, cfg, rows, memo=None, discard=None):
        """Evaluate ONE (job, degree) candidate against the live cluster
        state: placement, dep pricing, channel check, lookahead, SLA —
        everything a decision needs, minus the commit. ``rows`` are the
        candidate's rows of the config tables (`config_rows` at ``cfg``):
        every kernel below reads them and none reads a table, and
        ``cfg`` itself rides for the memo key alone. XLA dead-code
        eliminates the commit outputs when a caller (candidate pricing)
        only reads (ok, jct). Returns ``(ev, pending)``; with ``memo``
        (the in-kernel lookahead memo table, sim/jax_memo.py) the
        lookahead is probed under the host memo-key signature (cfg row,
        canonical worker grouping, mounted dep times) and served from
        the table on a bitwise full-key hit — memoised and recomputed
        results are bit-identical by construction, any precision mode.
        The table is only READ here: ``pending`` is the one entry the
        probe would insert (`jax_memo.memo_probe`; None without a memo),
        the caller's to `jax_memo.memo_commit`. ``discard``
        (bool) marks a lane whose result the caller will throw away, and
        a job that did not place has no lookahead to run (the host drops
        it before pricing): either joins the memo's hit mask in the
        lookahead's ``skip``, so under ``vmap`` such a lane runs no trips
        (``ev["la_trips"]`` is the loop's own count: 0 for a skipped
        lane; ``ev["la_rode"]`` the servers the job's sub-ops sit on
        where it ran trips, 0 where it ran none — what decides the
        width of the lookahead's channel table,
        `sim/jax_lookahead.py:channel_widths`), and neither probes nor
        enters the memo."""
        (t, mem, srv_job, chan_occ, slot_valid, slot_t_done, slot_mem,
         slot_servers, slot_chan) = carry
        dt = mem.dtype
        steps = bank["steps"][row].astype(dt)
        other_free = srv_job < 0
        with jax.named_scope(scopes.SIM_ALLOCATE):
            ots, new_mem, ok_place = jax_allocate_job(
                mem, other_free, rows, st, pads)
        with jax.named_scope(scopes.SIM_PRICE):
            times, is_flow, pair_used, op_score, dep_score, finite_ok = \
                jax_price_and_score(ots, rows, st, pads, et.comm)
        op_valid = rows["op_valid"]
        ok_chan, chan_mask, srv_mask = placement_masks(
            ots, op_valid, pair_used, pair_is_chan, chan_occ)
        # an unplaced job stays out of loop and memo alike: its key is
        # that of the ops that placed, which a complete placement can
        # share (sim/jax_memo.py, "COMPLETE placement only")
        void = ~ok_place if discard is None else discard | ~ok_place

        def run_lookahead(skip=None):
            # ``skip`` is the memo probe's hit mask, threaded into the
            # lookahead while_loop cond (jax_memo.WIDE_PROBE_SURFACE) so
            # hit lanes contribute zero trips to the batched loop; a
            # void lane is masked out the same way
            skip = void if skip is None else skip | void
            t_la, _, _, _, ok, trips = jax_lookahead(
                rows["op_compute"], op_valid,
                jnp.where(op_valid, ots, -1), op_score,
                rows["num_parents"], times, rows["dep_valid"],
                rows["dep_mutual"], is_flow, dep_score,
                DepBlocks(rows["blk_src"], rows["blk_dst"]),
                num_workers=n_srv, skip=skip)
            return t_la, ok, trips

        if memo is None:
            (t_step, ok_la, trips), pending = run_lookahead(), None
        else:
            with jax.named_scope(scopes.SIM_MEMO_PROBE):
                groups = jax_memo.canonical_groups(
                    jnp.where(op_valid, ots, -1), op_valid)
            (t_step, ok_la, trips), pending = jax_memo.memo_probe(
                memo, cfg, groups, times, run_lookahead, void)
        jct = t_step * steps
        max_jct = (bank["sla_frac"][row].astype(dt)
                   * rows["seq_compute"].astype(dt) * steps)
        sla_ok = ~(jct > max_jct)
        engine_ok = ok_la & finite_ok
        return {"ok_place": ok_place, "ok_chan": ok_chan,
                "engine_ok": engine_ok, "sla_ok": sla_ok, "jct": jct,
                "new_mem": new_mem, "srv_mask": srv_mask,
                "chan_mask": chan_mask, "la_trips": trips,
                "la_rode": jnp.where(trips > 0, srv_mask.sum(),
                                     0).astype(jnp.int32)}, pending

    def price_all(bank, carry, row):
        """In-kernel candidate pricing: (placeable [n_deg], jct [n_deg])
        for every degree column against the live cluster state — the
        jitted counterpart of sim/candidate_pricing.py. One VMAPPED
        evaluation over the cfg batch (cfg only feeds gathers), so the
        traced program contains the placement/pricing/lookahead kernels
        once, not n_deg times; each column's rows are read inside the
        ``vmap``."""
        jtype = bank["type"][row]
        cfgs = jtype * n_deg + jnp.arange(n_deg, dtype=jnp.int32)
        # memo-less on purpose: this vmap batches the CFG axis within
        # one env, whose single memo table cannot absorb n_deg scattered
        # insertions through an in_axes=None carry (the wide probe
        # batches over LANES, each with its own table) — the host
        # counterpart keeps candidate pricing fast through its own
        # prefetch instead
        ev, _ = jax.vmap(lambda cfg: eval_cfg(
            bank, carry, row, cfg, config_rows(et.tables, cfg)))(cfgs)
        return (ev["ok_place"] & ev["ok_chan"] & ev["engine_ok"],
                ev["jct"])

    @jax.named_scope(scopes.SIM_DECIDE)
    def decision(bank, carry, action, row, memo=None):
        """Decide one queued job; returns ``(carry', (reward, accept,
        cause, jct, la_trips, la_rode), pending)``. ``la_trips`` (i32)
        is the lookahead loop's own trip count for this decision: 0 on a
        memo hit and on an action that runs no lookahead; ``la_rode``
        (i32) the servers the job rode where it ran trips, else 0.
        ``memo`` is only READ: ``pending`` (None without a memo) is the
        one entry this decision would insert, for the caller to
        `jax_memo.memo_commit` once it stands under no ``lax.cond`` of
        its own. NO ``cond`` returns a memo: under ``vmap`` a ``cond``
        is both branches and a select over every output, and a table
        among them was selected, and copied, whole on every lane-step
        (2.2-2.9 GB over a cell's lanes; PERF.md section 6, PR 49).
        And the ``cond`` below takes the config's ROWS as its operand
        (`config_rows`, read once, above it) and its branch closes over
        NO ``[n_cfg, ...]`` table: ``vmap``'s rule for a ``cond`` with a
        per-lane predicate gives every operand the lanes' axis first —
        what a branch closes over is an operand too — so a table in the
        branch was written out at ``[lanes, n_cfg, M]`` on every
        lane-step only to be row-indexed a moment later (five dep
        tables, 3.3-4.6 ms of every cell's epoch; PERF.md section 6,
        PR 51), while the rows are ``[lanes, M]`` and batched already.
        At one lane the ``cond`` stays a branch, and an action-0 step
        pays the rows' dynamic slices."""
        (t, mem, srv_job, chan_occ, slot_valid, slot_t_done, slot_mem,
         slot_servers, slot_chan) = carry
        dt = mem.dtype
        jtype = bank["type"][row]
        cfg = jtype * n_deg + deg_col[jnp.clip(action, 0)]

        # actions outside the jitted degree set (odd > 1 — the host
        # coerces masked-invalid actions to 0, partitioning_env.py:195)
        # take the zero path instead of wrapping deg_col's -1 into
        # another config row
        action_ok = (action > 0) & (deg_col[jnp.clip(action, 0)] >= 0)
        rows = config_rows(et.tables, cfg)

        def heavy(rows):
            # under vmap the cond below is a select and every lane runs
            # this branch: a lane on the zero path is masked out of the
            # lookahead loop, so the trips the batched loop executes are
            # the maximum over lanes whose result is used
            ev, pending = eval_cfg(bank, carry, row, cfg, rows, memo,
                                   discard=~action_ok)
            accept = (ev["ok_place"] & ev["ok_chan"] & ev["sla_ok"]
                      & ev["engine_ok"])
            cause = jnp.where(
                ~ev["ok_place"], CAUSE_OP_PLACEMENT,
                jnp.where(~ev["ok_chan"], CAUSE_DEP_PLACEMENT,
                          jnp.where(~ev["engine_ok"], CAUSE_ENGINE,
                                    jnp.where(~ev["sla_ok"], CAUSE_SLA,
                                              CAUSE_ACCEPTED))))
            return (accept, cause.astype(jnp.int32), ev["jct"],
                    ev["new_mem"], ev["srv_mask"], ev["chan_mask"],
                    ev["la_trips"], ev["la_rode"]), pending

        def zero(rows):
            return (jnp.bool_(False), jnp.int32(CAUSE_NOT_HANDLED),
                    jnp.zeros((), dt), mem, jnp.zeros((n_srv,), bool),
                    jnp.zeros((n_chan,), bool), jnp.int32(0),
                    jnp.int32(0)), jax_memo.memo_pending_none(memo)

        ((accept, cause, jct, new_mem, srv_mask, chan_mask, la_trips,
          la_rode), pending) = jax.lax.cond(action_ok, heavy, zero, rows)

        if scenario is not None:
            # inflate AFTER the accept/cause decision: admission is
            # failure-blind (host: _register_completed_lookahead)
            jct = scenario_adjusted(t, jct, srv_mask, chan_mask)

        slot = jnp.argmin(slot_valid).astype(jnp.int32)  # first free slot
        accept = accept & ~jnp.all(slot_valid)  # cannot trigger (R=n_srv)
        delta = mem - new_mem
        mem2 = jnp.where(accept, new_mem, mem)
        srv_job2 = jnp.where(accept & srv_mask, slot, srv_job)
        chan_occ2 = jnp.where(accept & chan_mask, slot, chan_occ)
        slot_valid2 = slot_valid.at[slot].set(
            jnp.where(accept, True, slot_valid[slot]))
        slot_t_done2 = slot_t_done.at[slot].set(
            jnp.where(accept, t + jct, slot_t_done[slot]))
        slot_mem2 = slot_mem.at[slot].set(
            jnp.where(accept, delta, slot_mem[slot]))
        slot_servers2 = slot_servers.at[slot].set(
            jnp.where(accept, srv_mask, slot_servers[slot]))
        slot_chan2 = slot_chan.at[slot].set(
            jnp.where(accept, chan_mask, slot_chan[slot]))
        reward = jnp.where(accept, et.success_reward, et.fail_reward)

        return ((t, mem2, srv_job2, chan_occ2, slot_valid2, slot_t_done2,
                 slot_mem2, slot_servers2, slot_chan2),
                (reward.astype(dt), accept, cause, jct, la_trips, la_rode),
                pending)

    def advance(bank, carry, queue_row, ptr, next_arrival, done,
                completed):
        """Tick the event clock until a job queues or the episode ends
        (cluster.py:616-657 + the env's auto-step loop)."""
        (t, mem, srv_job, chan_occ, slot_valid, slot_t_done, slot_mem,
         slot_servers, slot_chan) = carry
        dt = mem.dtype
        J = bank["type"].shape[0]

        def cond(s):
            (_, _, _, _, _, _, _, _, _, queue_row, _, _, done, _) = s
            return (queue_row < 0) & ~done

        def body(s):
            (t, mem, srv_job, chan_occ, slot_valid, slot_t_done,
             slot_mem, slot_servers, slot_chan, queue_row, ptr,
             next_arrival, done, completed) = s
            remaining = jnp.where(slot_valid, slot_t_done - t,
                                  jnp.asarray(jnp.inf, dt))
            tick = jnp.minimum(jnp.minimum(next_arrival - t, sim_end - t),
                               remaining.min())
            tick = jnp.maximum(tick, 0.0)
            t2 = t + tick

            completions = slot_valid & (slot_t_done - t2 - eps <= 0)
            mem2 = mem + (completions.astype(dt) @ slot_mem)
            freed_srv = (completions[:, None] & slot_servers).any(0)
            freed_chan = (completions[:, None] & slot_chan).any(0)
            srv_job2 = jnp.where(freed_srv, -1, srv_job)
            chan_occ2 = jnp.where(freed_chan, -1, chan_occ)
            slot_valid2 = slot_valid & ~completions
            completed2 = completed + completions.sum().astype(jnp.int32)

            arrived = (ptr < J) & (t2 + eps >= next_arrival)
            queue_row2 = jnp.where(arrived, ptr, queue_row)
            ptr2 = ptr + arrived.astype(jnp.int32)
            next_arrival2 = jnp.where(
                arrived, bank["arrival_t"][jnp.clip(ptr2, 0, J)],
                next_arrival)

            done2 = (t2 >= sim_end) | ((ptr2 >= J)
                                       & ~slot_valid2.any()
                                       & (queue_row2 < 0))
            return (t2, mem2, srv_job2, chan_occ2, slot_valid2,
                    slot_t_done, slot_mem, slot_servers, slot_chan,
                    queue_row2, ptr2, next_arrival2, done2, completed2)

        s = carry + (queue_row, ptr, next_arrival, done, completed)
        with jax.named_scope(scopes.SIM_ADVANCE):
            s = jax.lax.while_loop(cond, body, s)
        return s[:9], s[9], s[10], s[11], s[12], s[13]

    def init_state(bank):
        dt = et.tables["dep_size"].dtype
        carry0 = (jnp.zeros((), dt),                       # t
                  jnp.full((n_srv,), et.worker_mem, dt),   # mem
                  jnp.full((n_srv,), -1, jnp.int32),       # srv_job
                  jnp.full((n_chan,), -1, jnp.int32),      # chan_occ
                  jnp.zeros((R,), bool),                   # slot_valid
                  jnp.zeros((R,), dt),                     # slot_t_done
                  jnp.zeros((R, n_srv), dt),               # slot_mem
                  jnp.zeros((R, n_srv), bool),             # slot_servers
                  jnp.zeros((R, n_chan), bool))            # slot_chan
        return (carry0,
                jnp.int32(0),                              # queue_row: job 0
                jnp.int32(1),                              # ptr
                bank["arrival_t"][1],                      # next arrival
                jnp.bool_(False),
                jnp.int32(0),
                (jnp.int32(0), jnp.int32(0), jnp.zeros((), dt)))

    return _types.SimpleNamespace(decision=decision, advance=advance,
                                  init_state=init_state,
                                  price_all=price_all, eval_cfg=eval_cfg)


#: start-up gauge (`price_dep_indexed_ops`): 0 says pricing and the
#: channel / server checks reach every dep by block
PRICE_GAUGE = "sim.price.dep_indexed_ops"


def dep_indexed_ops(jaxpr, n_indices: int) -> List[str]:
    """`utils/jaxprs.py:indexed_ops` with the lookahead's own call
    (`jax_lookahead`'s batching rule) left out."""
    return indexed_ops(jaxpr, n_indices, skip=("custom_vmap_call",))


def price_dep_indexed_ops(et: EpisodeTables) -> int:
    """How many equations of one traced `eval_cfg` (placement, pricing,
    channel and server checks; the lookahead excluded) still index by
    dep: gathers and scatters of at least ``n_blocks * max_split``
    indices — one per block row, a sixteenth of the dep pad M, so any
    index of a dep's worth counts and a read of a B- or G-long table
    does not. Abstract trace, nothing runs."""
    import jax

    k = _episode_kernels(et)
    dt = et.tables["dep_size"].dtype
    bank = {"steps": jax.ShapeDtypeStruct((1,), dt),
            "sla_frac": jax.ShapeDtypeStruct((1,), dt)}
    carry = jax.eval_shape(
        lambda: k.init_state({"arrival_t": jax.numpy.zeros((2,), dt)})[0])
    i32 = jax.ShapeDtypeStruct((), np.int32)
    traced = jax.make_jaxpr(
        lambda bank, carry, row, cfg: k.eval_cfg(
            bank, carry, row, cfg, config_rows(et.tables, cfg))[0])(
                bank, carry, i32, i32)
    return len(dep_indexed_ops(traced.jaxpr,
                               et.pads.n_blocks * et.pads.max_split))


#: start-up gauge (`allocate_indexed_ops`): 0 says the placement scan
#: reaches servers, cells and op slots without an index per element
ALLOCATE_GAUGE = "sim.allocate.indexed_ops"


def allocate_indexed_ops(tables: dict, st: ShapeTables,
                         pads: ConfigPads) -> int:
    """How many equations of one traced `jax_allocate_job` (the scan's
    body and the assembly after it) are gathers or scatters of at least
    ``max_split`` index vectors — one per server of a block, the fewest
    a per-cell, per-server or per-sub-op index issues; a row read of a
    table (one index) does not count. Abstract trace, nothing runs."""
    import jax

    n_srv = int(np.prod(st.ramp_shape))
    traced = jax.make_jaxpr(
        lambda mem, other_free, cfg: jax_allocate_job(
            mem, other_free, config_rows(tables, cfg), st, pads))(
        jax.ShapeDtypeStruct((n_srv,), tables["f_mem"].dtype),
        jax.ShapeDtypeStruct((n_srv,), bool),
        jax.ShapeDtypeStruct((), np.int32))
    return len(indexed_ops(traced.jaxpr, pads.max_split))


def ragged_rows(tables: dict, n_types: int, degrees: Sequence[int]
                ) -> np.ndarray:
    """Per (job type, degree) row of the stacked tables (type-major),
    the forward ops that the SiP-ML rule splits fewer ways than the
    row's degree asks for (an op under that many quanta): what makes the
    row's blocks ragged. From the host's numpy tables: no fetch."""
    degree = np.tile(np.asarray(degrees), n_types)[:, None]
    return (np.asarray(tables["f_valid"])
            & (np.asarray(tables["f_split"]) < degree)).sum(axis=1)


def ragged_forward_ops(et: EpisodeTables) -> Dict[str, int]:
    """Per job type, the ragged forward ops of its TOP-degree row
    (`ragged_rows`)."""
    n = len(et.degrees)
    return {model: int(et.row_ragged[(i + 1) * n - 1])
            for i, model in enumerate(et.types)}


#: start-up gauges (`mask_rows_on_empty_cluster`), in this order
MASK_GAUGES = ("env.mask.rows_offered", "env.mask.rows_placeable")


def mask_rows_on_empty_cluster(env, et: EpisodeTables, ot: dict
                               ) -> Tuple[int, int]:
    """Of the (model, degree) rows the action mask offers on an EMPTY
    cluster (`_kernel_action_mask` at 0 occupied servers; action 0 is no
    row), how many the allocator places there: the host's
    `agents/placers.py:allocate_job`, which `jax_allocate_job` mirrors,
    on an all-free RAMP at full memory (~0.1 s for 24 rows of 131 ops;
    the jitted allocator over the same rows costs seconds of tracing at
    every start). The mask knows occupancy and block shapes, not memory
    or a row's splits, so the difference is what it offers and placement
    then blocks. Once, at set-up."""
    from ddls_tpu.agents.placers import allocate_job

    topo = env.cluster.topology
    graphs = {proto.details["model"]: proto.graph
              for proto in env.cluster.jobs_generator.sampler.prototypes}
    servers = {topo.parse_server_id(s) for s in topo.server_ids}
    mask = np.asarray(_kernel_action_mask(ot, et, 0))
    offered = placeable = 0
    for model in et.types:
        graph = graphs[model]
        forward = graph.forward_view()
        for degree in (d for d in et.degrees if mask[d]):
            action = build_partition_action(
                graph, env.min_op_run_time_quantum, degree)
            split_fwd = {op: n for op, n in action.items() if n > 1}
            ramp = {s: {"mem": et.worker_mem, "job_idxs": set()}
                    for s in servers}
            offered += 1
            placeable += allocate_job(
                ramp, topo.shape, forward, graph, split_fwd, servers,
                topo.shape, 0) is not None
    return offered, placeable


def make_episode_fn(et: EpisodeTables,
                    memo_cfg: Optional[jax_memo.MemoConfig]
                    = DEFAULT_EPISODE_MEMO):
    """Build the jitted episode replay: (bank, actions [n_decisions]) ->
    per-decision traces (reward, accept, cause, jct, t) + final counters.

    One `lax.scan` over decisions; each decision runs the scan-ified
    placer, the pricing/score kernel and the jitted lookahead under a
    `lax.cond` (skipped for action 0), then a `lax.while_loop` advances
    the event clock (completions, arrivals) to the next decision exactly
    like `RampClusterEnvironment.step`'s tick loop (cluster.py:616-657).

    The in-kernel lookahead memo (``memo_cfg``, sim/jax_memo.py) rides
    the scan carry and defaults ON — hits and recomputes are bitwise
    identical, so results never depend on it, and the batched probe
    stays effective under vmap (hit lanes are masked out of the
    lookahead while_loop; each lane carries its own table). With the
    memo on, the output dict carries the final
    ``memo_hits``/``memo_misses``/``memo_evicts`` counters.
    """
    import jax
    import jax.numpy as jnp

    k = _episode_kernels(et)
    decision, advance = k.decision, k.advance

    def episode(bank, actions):
        dt = et.tables["dep_size"].dtype

        def scan_body(sm, action):
            state, memo = sm
            (carry, queue_row, ptr, next_arrival, done, completed,
             counters) = state
            t = carry[0]
            has_job = (queue_row >= 0) & ~done

            # the cond hands out the decision's PENDING memo entry, the
            # table never (`decision`); one commit after it
            def run():
                new_carry, (reward, accept, cause, jct, *_), pending = \
                    decision(bank, carry, action, jnp.clip(queue_row, 0),
                             memo)
                return (new_carry, reward, accept, cause, jct), pending

            def skip():
                return (carry, jnp.zeros((), dt), jnp.bool_(False),
                        jnp.int32(-1), jnp.zeros((), dt)), \
                    jax_memo.memo_pending_none(memo)

            (new_carry, reward, accept, cause, jct), pending = \
                jax.lax.cond(has_job, run, skip)
            memo = jax_memo.memo_commit(memo, pending)
            accepted, blocked, ret = counters
            counters2 = (accepted + (has_job & accept),
                         blocked + (has_job & ~accept),
                         ret + jnp.where(has_job, reward, 0.0))
            queue_row2 = jnp.where(has_job, -1, queue_row)
            (carry3, queue_row3, ptr3, next_arrival3, done3,
             completed3) = advance(bank, new_carry, queue_row2, ptr,
                                   next_arrival, done, completed)
            out = (reward, accept, cause, jct, t, has_job)
            return (((carry3, queue_row3, ptr3, next_arrival3, done3,
                      completed3, counters2), memo), out)

        memo0 = (jax_memo.memo_init(et, memo_cfg)
                 if memo_cfg is not None else None)
        state0 = (k.init_state(bank), memo0)
        (final, memo), trace = jax.lax.scan(scan_body, state0, actions)
        (carry, queue_row, ptr, next_arrival, done, completed,
         counters) = final
        out = {"trace": trace, "accepted": counters[0],
               "blocked": counters[1], "ret": counters[2],
               "completed": completed, "t": carry[0], "done": done,
               # host episode finalisation blocks anything still running
               # at simulation end (cluster.py:1010-1013); num_jobs_blocked
               # parity = decision blocks + still-running slots
               "blocked_total": (counters[1]
                                 + carry[4].sum().astype(jnp.int32)),
               "arrived": ptr}
        if memo is not None:
            out.update(jax_memo.memo_trace_counters(memo))
        return out

    # bank arrays are traced arguments: one compile serves every bank of
    # the same shape (per-seed episodes, vmapped batches)
    return jax.jit(episode)


# =========================================================================
# In-kernel observations + policy-in-the-loop episodes (the full
# HBM-resident rollout: obs, policy forward, sampling, decision, event
# clock — all inside one lax.scan).
# =========================================================================

def build_obs_tables(env, et: EpisodeTables) -> dict:
    """Static per-type observation rows + the normalisation constants the
    kernel needs to rebuild the job-specific entries.

    Everything in the standard observation (envs/obs.py) except seven
    entries is a pure function of the job's MODEL: node/edge features and
    most graph features. The seven dynamic entries are rebuilt in-kernel:
    graph_features[2,3,8] (sequential JCT / max-acceptable JCT / training
    steps — functions of the bank row), graph_features[4,5] (SLA frac),
    graph_features[15,16] (cluster occupancy), plus the action mask.
    """
    gen = env.cluster.jobs_generator
    obs_fn = env.observation_function
    with_prices = bool(getattr(obs_fn, "include_candidate_prices", False))
    params = gen.jobs_params

    proto_by_model = {}
    for proto in gen.sampler.prototypes:
        proto_by_model.setdefault(proto.details["model"], proto)

    rows = []
    for model in et.types:
        job = proto_by_model[model]
        obs = obs_fn.encode(job, env)
        obs = {k: np.asarray(v) for k, v in obs.items()}
        if with_prices:
            # the template's baked price block is decision-time data of
            # whatever job was queued at encode time — drop it; the
            # kernel rebuilds the block from its own in-kernel pricing
            obs["graph_features"] = obs["graph_features"][
                :-(et.max_action + 1)]
        rows.append(obs)

    def stack(key):
        return np.stack([r[key] for r in rows])

    def bounds(key):
        return (float(params[f"min_{key}"]), float(params[f"max_{key}"]))

    return {
        "node_features": stack("node_features"),
        "edge_features": stack("edge_features"),
        "edges_src": stack("edges_src"),
        "edges_dst": stack("edges_dst"),
        "node_split": stack("node_split"),
        "edge_split": stack("edge_split"),
        "graph_features": stack("graph_features"),
        # the exact compute.sum() the host multiplies by num_training_steps
        # (demands/job.py:55) — dividing seq_completion_time back out by
        # steps would cost an ulp and break the bit-equal obs contract
        "orig_seq_sum": np.array(
            [float(proto_by_model[m].graph.finalize()["compute"].sum())
             for m in et.types], np.float64),
        "seq_bounds": bounds("job_sequential_completion_times"),
        "jct_bounds": bounds("max_acceptable_job_completion_times"),
        "frac_bounds": bounds("max_acceptable_job_completion_time_fracs"),
        "steps_bounds": bounds("job_num_training_steps"),
        # static per-action "a symmetric block shape exists" row
        # (envs/obs.py:action_is_valid:56-59)
        "shapes_exist": np.array(
            [bool(block_shapes_for(factor_pairs(a), et.st.ramp_shape))
             for a in range(et.max_action + 1)], bool),
        "with_prices": with_prices,
    }


#: the per-type arrays of ``build_obs_tables`` padded along their axis 1
#: (the node rows first, then the three per-edge arrays)
OBS_PAD_KEYS = ("node_features", "edge_features", "edges_src", "edges_dst")

#: start-up gauges, in this order: the (node, edge) pads the device
#: tables carry, then the pads the env's ``pad_obs_kwargs`` configure
OBS_PAD_GAUGES = ("env.obs.node_pad", "env.obs.edge_pad",
                  "env.obs.node_pad_configured",
                  "env.obs.edge_pad_configured")


def obs_pads(ot: dict) -> Tuple[int, int]:
    """The (node, edge) pad a set of observation tables carries."""
    return (int(ot["node_features"].shape[1]),
            int(ot["edge_features"].shape[1]))


def fit_obs_tables(ot: dict) -> dict:
    """``build_obs_tables``' rows on the smallest rung of the package's
    halving ladder (`serve/bucketing.py:default_buckets`: pad, pad / 2,
    pad / 4, both axes together) that holds the bank's LARGEST graph —
    ``max(node_split)`` x ``max(edge_split)``, static numpy, known before
    anything is traced. The pad is a container (RLlib's fixed-shape
    observations, envs/obs.py); no parameter's shape depends on it, real
    rows come first and the rest is zeros, so cutting the four padded
    arrays along axis 1 IS `envs/obs.py:pad_obs_to` at the rung, bit
    for bit, and the programs fed by the tables (collect, forward, PPO
    update) stop computing rows that are masked to zero afterwards.

    A ladder and not a tight fit: a bank whose largest graph fits no
    rung under its pad gets ``ot`` back — the SAME arrays, so the same
    lowered program and the same trajectories as without the fit. No
    option: the rule reads the tables."""
    from ddls_tpu.serve.bucketing import default_buckets

    n_max = int(np.max(ot["node_split"]))
    e_max = int(np.max(ot["edge_split"]))
    pads = obs_pads(ot)
    rung = next((n, e) for n, e in default_buckets(*pads)
                if n >= n_max and e >= e_max)
    if rung == pads:
        return ot
    n, e = rung
    fitted = dict(ot)
    fitted["node_features"] = np.ascontiguousarray(
        ot["node_features"][:, :n])
    for key in OBS_PAD_KEYS[1:]:
        fitted[key] = np.ascontiguousarray(ot[key][:, :e])
    return fitted


def _kernel_action_mask(ot: dict, et: EpisodeTables, n_occupied):
    """The obs action mask (envs/obs.py:action_is_valid) from occupancy:
    0 always; 1 needs a free worker; even a needs a <= free workers AND
    an existing block shape. The ONE in-kernel statement of the rule."""
    import jax.numpy as jnp

    free = et.n_srv - n_occupied
    a = jnp.arange(et.max_action + 1)
    exists = jnp.asarray(ot["shapes_exist"])
    return ((a == 0)
            | ((a == 1) & (free >= 1))
            | ((a > 1) & (a % 2 == 0) & (a <= free) & exists))


def _kernel_obs(ot: dict, et: EpisodeTables, jtype, frac, steps,
                n_occupied, n_running, price_feats=None):
    """Rebuild the exact host observation for one queued job inside jit.

    Dynamic entries are computed with the host's formulas (f64) and the
    whole feature vector is cast to f32 like the host encoder, so the
    policy sees bit-identical inputs."""
    import jax
    import jax.numpy as jnp

    with jax.named_scope(scopes.ENV_OBS):
        def norm(val, lo, hi):
            return jnp.where(hi - lo == 0, 1.0, (val - lo) / (hi - lo))

        gf = jnp.asarray(ot["graph_features"])[jtype].astype(jnp.float64)
        seq_ct = jnp.asarray(ot["orig_seq_sum"])[jtype] * steps
        max_jct = frac * seq_ct
        gf = gf.at[2].set(norm(seq_ct, *ot["seq_bounds"]))
        gf = gf.at[3].set(norm(max_jct, *ot["jct_bounds"]))
        gf = gf.at[4].set(norm(frac, *ot["frac_bounds"]))
        gf = gf.at[5].set(frac)
        gf = gf.at[8].set(norm(steps, *ot["steps_bounds"]))
        n_srv = et.n_srv
        gf = gf.at[15].set(n_occupied / n_srv)
        gf = gf.at[16].set(n_running / n_srv)

        mask = _kernel_action_mask(ot, et, n_occupied)
        n_feat = jnp.asarray(ot["graph_features"]).shape[1]
        gf17 = jnp.clip(gf[:n_feat - mask.shape[0]], 0.0, 1.0)
        parts = [gf17, mask.astype(jnp.float64)]
        if ot.get("with_prices"):
            if price_feats is None:
                raise ValueError("obs tables carry price features; pass "
                                 "price_feats (envs/obs.py:_price_features)")
            parts.append(price_feats.astype(jnp.float64))
        gf = jnp.concatenate(parts)

        return {
            "action_set": jnp.arange(et.max_action + 1, dtype=jnp.int32),
            "node_features": jnp.asarray(ot["node_features"])[jtype],
            "edge_features": jnp.asarray(ot["edge_features"])[jtype],
            "edges_src": jnp.asarray(ot["edges_src"])[jtype],
            "edges_dst": jnp.asarray(ot["edges_dst"])[jtype],
            "node_split": jnp.asarray(ot["node_split"])[jtype],
            "edge_split": jnp.asarray(ot["edge_split"])[jtype],
            "graph_features": gf.astype(jnp.float32),
            "action_mask": mask.astype(jnp.int32),
        }


def make_policy_episode_fn(et: EpisodeTables, ot: dict, model,
                           greedy: bool = False,
                           memo_cfg: Optional[jax_memo.MemoConfig]
                           = DEFAULT_EPISODE_MEMO):
    """Full policy-in-the-loop jitted episode: (bank, params, rng) ->
    traces. Per decision the kernel rebuilds the observation, runs the
    GNN policy forward, samples (or argmaxes) an action under the mask,
    then executes the decision + event clock exactly like
    `make_episode_fn`. ONE device dispatch per episode — the complete
    §5.8 HBM-resident rollout shape; vmap over (bank, rng) for batched
    collection (the memo stays ON there: the batched probe masks hit
    lanes out of the lookahead while_loop and each lane carries its own
    table — sim/jax_memo.py, ISSUE 17)."""
    import jax
    import jax.numpy as jnp

    k = _episode_kernels(et)

    def episode(bank, params, rng):
        dt = et.tables["dep_size"].dtype

        def scan_body(sm, step_rng):
            state, memo = sm
            (carry, queue_row, ptr, next_arrival, done, completed,
             counters) = state
            t = carry[0]
            has_job = (queue_row >= 0) & ~done
            row = jnp.clip(queue_row, 0)

            def run():
                # obs rebuild + GNN forward + sampling live INSIDE the
                # cond so dead scan steps after episode end cost nothing;
                # it hands out the decision's PENDING memo entry, the
                # table never (`decision`): one commit after it
                srv_job = carry[2]
                slot_valid = carry[4]
                price_feats = None
                if ot.get("with_prices"):
                    # in-kernel candidate pricing as observation features
                    # (envs/obs.py:_price_features: min(jct/limit, 2)/2,
                    # 1.0 for unpriceable; the host prices only
                    # mask-valid degrees). Limit multiplies in the HOST's
                    # association order frac * (sum * steps)
                    # (demands/job.py:55,273) — bit-equal features
                    frac64 = bank["sla_frac"][row].astype(jnp.float64)
                    steps64 = bank["steps"][row].astype(jnp.float64)
                    ok, jcts = k.price_all(bank, carry, row)
                    limit = jnp.maximum(
                        frac64 * (jnp.asarray(ot["orig_seq_sum"])[
                            bank["type"][row]] * steps64), 1e-30)
                    degs = jnp.asarray(np.array(et.degrees, np.int32))
                    dmask = _kernel_action_mask(
                        ot, et, (srv_job >= 0).sum())[degs]
                    vals = jnp.minimum(jcts.astype(jnp.float64) / limit,
                                       2.0) / 2.0
                    price_feats = jnp.ones(
                        (et.max_action + 1,), jnp.float64).at[degs].set(
                        jnp.where(ok & dmask, vals, 1.0))
                obs = _kernel_obs(
                    ot, et, bank["type"][row],
                    bank["sla_frac"][row].astype(jnp.float64),
                    bank["steps"][row].astype(jnp.float64),
                    (srv_job >= 0).sum(), slot_valid.sum(),
                    price_feats=price_feats)
                with jax.named_scope(scopes.POLICY_FORWARD):
                    logits, value = model.apply(params, obs)
                if greedy:
                    action = jnp.argmax(logits).astype(jnp.int32)
                else:
                    action = jax.random.categorical(
                        step_rng, logits).astype(jnp.int32)
                logp = jax.nn.log_softmax(logits)[action]
                new_carry, (reward, accept, cause, jct, *_), pending = \
                    k.decision(bank, carry, action, row, memo)
                return (new_carry, action, logp, value, reward, accept,
                        cause, jct), pending

            def skip():
                f32 = jnp.float32
                return (carry, jnp.int32(0), f32(0.0), f32(0.0),
                        jnp.zeros((), dt), jnp.bool_(False),
                        jnp.int32(-1), jnp.zeros((), dt)), \
                    jax_memo.memo_pending_none(memo)

            ((new_carry, action, logp, value, reward, accept, cause,
              jct), pending) = jax.lax.cond(has_job, run, skip)
            memo = jax_memo.memo_commit(memo, pending)
            accepted, blocked, ret = counters
            counters2 = (accepted + (has_job & accept),
                         blocked + (has_job & ~accept),
                         ret + jnp.where(has_job, reward, 0.0))
            queue_row2 = jnp.where(has_job, -1, queue_row)
            (carry3, queue_row3, ptr3, next_arrival3, done3,
             completed3) = k.advance(bank, new_carry, queue_row2,
                                     ptr, next_arrival, done,
                                     completed)
            out = (action, logp, value, reward, accept, cause, jct, t,
                   has_job)
            return (((carry3, queue_row3, ptr3, next_arrival3, done3,
                      completed3, counters2), memo), out)

        memo0 = (jax_memo.memo_init(et, memo_cfg)
                 if memo_cfg is not None else None)
        state0 = (k.init_state(bank), memo0)
        n_steps = bank["type"].shape[0]
        rngs = jax.random.split(rng, n_steps)
        (final, memo), trace = jax.lax.scan(scan_body, state0, rngs)
        counters = final[6]
        out = {"trace": trace, "accepted": counters[0],
               "blocked": counters[1], "ret": counters[2],
               "completed": final[5], "t": final[0][0],
               "done": final[4],
               # host episode finalisation blocks anything still running
               # at simulation end (cluster.py:1010-1013); num_jobs_blocked
               # parity = decision blocks + still-running slots
               "blocked_total": (counters[1]
                                 + final[0][4].sum().astype(jnp.int32)),
               # ptr = jobs that entered the queue (host num_jobs_arrived
               # semantics, cluster.py:240) — the same expression the
               # segment kernel traces as ep_arrived
               "arrived": final[2]}
        if memo is not None:
            out.update(jax_memo.memo_trace_counters(memo))
        return out

    return jax.jit(episode)


# =========================================================================
# Fixed-length segment collection (the PPO rollout shape): the env lives
# on device across collect calls; episodes reset in-kernel.
# =========================================================================

def segment_init(et: EpisodeTables, bank,
                 memo_cfg: Optional[jax_memo.MemoConfig] = None):
    """Initial carried simulator state for `make_segment_fn`. With
    ``memo_cfg`` the state is ``(env_state, memo_table)`` — pass the
    SAME config the segment fn was built with."""
    state = _episode_kernels(et).init_state(bank)
    if memo_cfg is None:
        return state
    return (state, jax_memo.memo_init(et, memo_cfg))


def make_segment_fn(et: EpisodeTables, ot: dict, model, n_steps: int,
                    trace_obs: bool = False,
                    memo_cfg: Optional[jax_memo.MemoConfig] = None,
                    trace_trips: bool = False):
    """(bank, params, sim_state, rng) -> (new_sim_state, trace, next_fields)

    Exactly ``n_steps`` policy decisions per call — the [T, B] segment
    shape PPO consumes — with the simulator state carried across calls
    and episodes resetting IN-KERNEL to a fresh run of the same bank when
    they end (``done`` marks the boundary step, so GAE truncates there).

    The trace carries, per step: action, logp, value, reward, done, and
    the compact observation fields (jtype, sla frac, steps, occupied
    count, running count) from which `rebuild_obs_batch` reconstructs the
    exact observation on host for the learner's re-forward.
    ``next_fields`` are the same fields for the bootstrap state after the
    segment.

    ``memo_cfg`` threads the in-kernel lookahead memo (sim/jax_memo.py)
    through the carried state as ``(env_state, memo_table)``; per-step
    cumulative ``memo_hits``/``memo_misses``/``memo_evicts`` counters
    ride the trace next to the episode counters (drained with them at
    sync boundaries). THE PERSISTENCE CONTRACT: the in-kernel episode
    reset below restores the env state to ``fresh`` but NEVER touches
    the memo — the exact mirror of the host ``cluster.lookahead_cache``
    persisting across ``reset()`` under an unchanged workload signature
    (each lane replays one fixed bank, so its signature never changes).
    Effective at EVERY lane count (``jax_memo.resolve_memo_cfg``'s
    "auto" enables it everywhere): under a multi-lane vmap the batched
    probe masks hit lanes out of the lookahead while_loop and each lane
    carries its own table.

    ``trace_obs=True`` additionally carries the FULL observation dict the
    in-scan policy forward consumed (``trace["obs"]``) — the in-scan
    update carry for the fused epoch (rl/fused.py): its learner update
    reads the segment's own obs instead of re-deriving them from the
    compact fields, skipping a second `_kernel_obs` sweep over T x B
    samples. The values are the SAME `_kernel_obs` outputs either way
    (one function, elementwise per sample), so the fused x64 parity
    against the rebuild-from-fields path stays exact; host collectors
    keep ``trace_obs=False`` — shipping full padded obs through the
    per-collect device->host fetch is precisely what the compact trace
    exists to avoid.

    ``trace_trips=True`` adds ``la_trips`` (i32): the lookahead loop's
    own trip count of each decision (0 on a memo hit and on an action
    that runs no lookahead). A program counter, not a simulation result
    — the collectors that drain it (``EPISODE_TRACE_KEYS``) ask for it;
    the host reduces it into the ``sim.lookahead.*`` telemetry counters
    (`rl/fused.py:record_lookahead_trips`), and ``la_rode`` (i32): the
    servers that decision's job rode, 0 where its lookahead ran no
    trip. With them rides ``cause``
    (i32, a ``CAUSE_*`` code), the decision's verdict (accepted where it
    is ``CAUSE_ACCEPTED``), which with the ``n_occupied`` field the
    decision saw becomes ``env.decisions.*`` / ``env.cluster.*``
    (`rl/fused.py:record_decisions`).
    """
    import jax
    import jax.numpy as jnp

    if ot.get("with_prices"):
        raise ValueError(
            "segment collection does not support price-feature "
            "observations (the compact PPO trace carries no pricing "
            "state); build obs tables from an env without "
            "obs_include_candidate_prices")
    k = _episode_kernels(et)

    def obs_fields(bank, state):
        (carry, queue_row, *_rest) = state
        row = jnp.clip(queue_row, 0)
        srv_job = carry[2]
        slot_valid = carry[4]
        with jax.named_scope(scopes.ENV_OBS):
            return {"jtype": bank["type"][row],
                    "frac": bank["sla_frac"][row].astype(jnp.float64),
                    "steps": bank["steps"][row].astype(jnp.float64),
                    "n_occupied": (srv_job >= 0).sum().astype(jnp.int32),
                    "n_running": slot_valid.sum().astype(jnp.int32)}

    @jax.named_scope(scopes.SIM_SEGMENT)
    def segment(bank, params, sim_state, rng):
        dt = et.tables["dep_size"].dtype
        if memo_cfg is not None:
            sim_state, memo0 = sim_state
        else:
            memo0 = None
        fresh = k.init_state(bank)

        def scan_body(sm, step_rng):
            state, memo = sm
            (carry, queue_row, ptr, next_arrival, done, completed,
             counters) = state
            row = jnp.clip(queue_row, 0)
            fields = obs_fields(bank, state)
            obs = _kernel_obs(ot, et, fields["jtype"], fields["frac"],
                              fields["steps"], fields["n_occupied"],
                              fields["n_running"])
            with jax.named_scope(scopes.POLICY_FORWARD):
                logits, value = model.apply(params, obs)
            action = jax.random.categorical(step_rng,
                                            logits).astype(jnp.int32)
            logp = jax.nn.log_softmax(logits)[action]

            (new_carry, (reward, accept, cause, jct, la_trips, la_rode),
             pending) = k.decision(bank, carry, action, row, memo)
            memo = jax_memo.memo_commit(memo, pending)
            accepted, blocked, ret = counters
            # unlike the policy-episode kernel these counters need no
            # has_job guard: every segment step has a queued job by
            # construction (advance exits only on queue_row >= 0 or done,
            # and done states reset to fresh — which queues bank job 0)
            counters2 = (accepted + accept.astype(jnp.int32),
                         blocked + (~accept).astype(jnp.int32),
                         ret + reward)
            (carry3, queue_row3, ptr3, next_arrival3, done3,
             completed3) = k.advance(bank, new_carry, jnp.int32(-1), ptr,
                                     next_arrival, done, completed)
            ended = done3
            state3 = (carry3, queue_row3, ptr3, next_arrival3, done3,
                      completed3, counters2)
            # in-kernel episode reset: a fresh run of the same bank.
            # The memo is deliberately OUTSIDE this tree_map — it
            # persists across resets like the host lookahead_cache
            # (workload signature unchanged: same bank every episode)
            state4 = jax.tree_util.tree_map(
                lambda f, s: jnp.where(ended, f, s), fresh, state3)
            # episode counters ride the trace so the training loop can
            # harvest episode records at done boundaries (the reset wipes
            # them from the carried state the very same step)
            out = {"action": action, "logp": logp, "value": value,
                   "reward": reward.astype(dt), "done": ended,
                   "ep_accepted": counters2[0],
                   # at the episode-end step, fold in the jobs still
                   # running at simulation end — the host finalisation
                   # blocks them (cluster.py:1010-1013), so harvested
                   # num_jobs_blocked/blocking_rate match host records
                   "ep_blocked": counters2[1] + jnp.where(
                       ended, carry3[4].sum().astype(jnp.int32), 0),
                   "ep_return": counters2[2], "ep_completed": completed3,
                   # ptr counts every bank job that has entered the queue,
                   # decided or not — the host's num_jobs_arrived semantics
                   # (cluster.py:240); parity pinned via the policy-episode
                   # kernel's identical expression
                   # (tests/test_jax_policy_episode.py)
                   "ep_arrived": ptr3,
                   **fields}
            if trace_obs:
                out["obs"] = obs
            if trace_trips:
                out["la_trips"] = la_trips
                out["la_rode"] = la_rode
                out["cause"] = cause
            if memo is not None:
                out.update(jax_memo.memo_trace_counters(memo))
            return (state4, memo), out

        rngs = jax.random.split(rng, n_steps)
        (final, memo), trace = jax.lax.scan(scan_body, (sim_state, memo0),
                                            rngs)
        ret_state = final if memo_cfg is None else (final, memo)
        return ret_state, trace, obs_fields(bank, final)

    return jax.jit(segment)


def vmap_segment_fn(segment, n_lanes: int):
    """Lane-batched wrapper of a `make_segment_fn` kernel:
    ``(banks [B,...], params, states [B,...], rngs [B]) -> outputs with
    a leading B axis``. Real lane counts vmap; ONE lane takes a
    squeeze/expand fast path instead — batching a singleton lane axis
    through the decision kernels costs ~2x on XLA:CPU (measured
    docs/perf_round8.md: 738 -> 392 decisions/s at degree 2, a CPU
    timing), and a 1-wide vmap buys nothing anywhere. Shared by the
    device collector and the fused epoch driver so the two paths stay
    the same compiled math at every lane count."""
    import jax

    if n_lanes > 1:
        return jax.vmap(segment, in_axes=(0, None, 0, 0))

    def one_lane(banks, params, states, rngs):
        sq = lambda t: jax.tree_util.tree_map(lambda x: x[0], t)  # noqa: E731
        state, trace, next_fields = segment(sq(banks), params,
                                            sq(states), rngs[0])
        ex = lambda t: jax.tree_util.tree_map(  # noqa: E731
            lambda x: x[None], t)
        return ex(state), ex(trace), ex(next_fields)

    return one_lane


def rebuild_obs_batch(et: EpisodeTables, ot: dict, fields: dict):
    """Host-side exact reconstruction of the observations the kernel saw,
    from the compact trace fields (any leading batch shape).

    Implemented as `jax.vmap(_kernel_obs)` over the flattened fields —
    the ONE source of truth for the obs math — so the re-forward
    reproduces the in-kernel logits bit-for-bit under either precision
    mode by construction."""
    import jax
    import jax.numpy as jnp

    if ot.get("with_prices"):
        raise ValueError(
            "rebuild_obs_batch does not support price-feature "
            "observations (the compact trace carries no pricing state)")
    jtype = np.asarray(fields["jtype"])
    shape = jtype.shape

    def one(t, f, s, o, r):
        return _kernel_obs(ot, et, t, f, s, o, r)

    flat = [jnp.asarray(np.asarray(fields[k]).reshape(-1))
            for k in ("jtype", "frac", "steps", "n_occupied", "n_running")]
    obs = jax.jit(jax.vmap(one))(*flat)
    return {k: np.asarray(v).reshape(shape + v.shape[1:])
            for k, v in obs.items()}


# =========================================================================
# The OracleJCT heuristic running entirely in-kernel: candidate pricing,
# action selection, decision, event clock — one dispatch per episode.
# =========================================================================

def make_oracle_episode_fn(et: EpisodeTables, ot: dict,
                           memo_cfg: Optional[jax_memo.MemoConfig]
                           = DEFAULT_EPISODE_MEMO):
    """Jitted OracleJCT episodes: per decision, price EVERY candidate
    degree in-kernel (`price_all`), pick the smallest degree whose priced
    JCT meets the SLA (else the smallest-JCT placeable candidate, else
    the smallest valid degree, else 0 — exactly
    `envs/baselines.py:OracleJCT.compute_action`), then run the decision
    and event clock. (bank) -> traces. The memo serves the DECISION's
    lookahead only (candidate pricing vmaps the cfg axis within one
    env, whose single table cannot take the scattered insertions — see
    `price_all`).
    """
    import jax
    import jax.numpy as jnp

    k = _episode_kernels(et)
    degrees = jnp.asarray(np.array(et.degrees, np.int32))
    n_deg = len(et.degrees)

    def episode(bank):
        dt = et.tables["dep_size"].dtype

        def scan_body(sm, _):
            state, memo = sm
            (carry, queue_row, ptr, next_arrival, done, completed,
             counters) = state
            t = carry[0]
            has_job = (queue_row >= 0) & ~done
            row = jnp.clip(queue_row, 0)

            # the cond hands out the decision's PENDING memo entry, the
            # table never (`decision`); one commit after it
            def run():
                srv_job = carry[2]
                # the obs action mask restricted to the degree columns
                mask = _kernel_action_mask(
                    ot, et, (srv_job >= 0).sum())[degrees]
                ok, jcts = k.price_all(bank, carry, row)
                steps = bank["steps"][row].astype(dt)
                # the host oracle's limit is the ORIGINAL (unpartitioned)
                # job's max_acceptable_jct (baselines.py:143 reads the
                # queue job), not the per-degree partitioned sums the
                # cluster's own SLA gate uses — mirror exactly
                max_jct = (bank["sla_frac"][row].astype(dt)
                           * (jnp.asarray(ot["orig_seq_sum"]).astype(dt)[
                               bank["type"][row]] * steps))
                acceptable = mask & ok & (jcts <= max_jct)
                placeable = mask & ok

                big = jnp.asarray(jnp.inf, dt)
                # 1) smallest acceptable degree
                first_acc = jnp.where(
                    acceptable.any(),
                    degrees[jnp.argmax(acceptable)], -1)
                # 2) else smallest-JCT placeable (first minimum in degree
                # order — strict < scan reproduces the host's min())
                best_jct = big
                best_deg = jnp.int32(-1)
                for d in range(n_deg):
                    take = placeable[d] & (jcts[d] < best_jct)
                    best_jct = jnp.where(take, jcts[d], best_jct)
                    best_deg = jnp.where(take, degrees[d], best_deg)
                # 3) else smallest valid degree, else 0
                first_valid = jnp.where(mask.any(),
                                        degrees[jnp.argmax(mask)], 0)
                action = jnp.where(
                    first_acc >= 0, first_acc,
                    jnp.where(best_deg >= 0, best_deg, first_valid)
                ).astype(jnp.int32)

                new_carry, (reward, accept, cause, jct, *_), pending = \
                    k.decision(bank, carry, action, row, memo)
                return (new_carry, action, reward, accept, cause,
                        jct), pending

            def skip():
                return (carry, jnp.int32(0), jnp.zeros((), dt),
                        jnp.bool_(False), jnp.int32(-1),
                        jnp.zeros((), dt)), \
                    jax_memo.memo_pending_none(memo)

            ((new_carry, action, reward, accept, cause, jct),
             pending) = jax.lax.cond(has_job, run, skip)
            memo = jax_memo.memo_commit(memo, pending)
            accepted, blocked, ret = counters
            counters2 = (accepted + (has_job & accept),
                         blocked + (has_job & ~accept),
                         ret + jnp.where(has_job, reward, 0.0))
            queue_row2 = jnp.where(has_job, -1, queue_row)
            (carry3, queue_row3, ptr3, next_arrival3, done3,
             completed3) = k.advance(bank, new_carry, queue_row2, ptr,
                                     next_arrival, done, completed)
            out = (action, reward, accept, cause, jct, t, has_job)
            return (((carry3, queue_row3, ptr3, next_arrival3, done3,
                      completed3, counters2), memo), out)

        memo0 = (jax_memo.memo_init(et, memo_cfg)
                 if memo_cfg is not None else None)
        state0 = (k.init_state(bank), memo0)
        n_steps = bank["type"].shape[0]
        (final, memo), trace = jax.lax.scan(scan_body, state0, None,
                                            length=n_steps)
        counters = final[6]
        out = {"trace": trace, "accepted": counters[0],
               "blocked": counters[1], "ret": counters[2],
               "completed": final[5], "t": final[0][0],
               "done": final[4],
               # host-parity blocked count incl. jobs still running at
               # simulation end (cluster.py:1010-1013)
               "blocked_total": (counters[1]
                                 + final[0][4].sum().astype(jnp.int32)),
               "arrived": final[2]}
        if memo is not None:
            out.update(jax_memo.memo_trace_counters(memo))
        return out

    return jax.jit(episode)
