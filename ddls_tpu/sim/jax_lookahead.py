"""The in-kernel lookahead: a jittable, vmappable tick engine over
fixed-size padded arrays in the partitioner's block layout.

The host engine (``cluster._run_lookahead``; SURVEY.md §3.5, §7.4.2)
simulates one training step of a mounted job by dependency-driven
ticking; this module reproduces those exact semantics as a
``lax.while_loop`` over padded arrays, for the in-kernel environment
(``sim/jax_env.py``), which calls it inside its device program once per
(job, degree) candidate. It holds the kernel and nothing of the host
simulator: the C++ engine's packer is ``ddls_tpu/native/arrays.py``, and
the per-dep gather/scatter form the block path is held to bit for bit is
the tests' (``tests/flat_lookahead.py``).

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

from fractions import Fraction
from typing import NamedTuple

import numpy as np

from ddls_tpu.telemetry import scopes

BIG = np.float32(3.4e38)  # stands in for +inf inside the kernel


class DepBlocks(NamedTuple):
    """The block structure `partition_graph` gives a job's deps
    (sim/jax_env.py:stack_config_tables), which lets the tick body read
    a dep's endpoints by broadcast and reduction instead of per-element
    gather and scatter. With S = the block side and B = ``src.shape[0]``:
    op slot (o, k) = o*S + k over N = No*S, dep slot (b, i, j) =
    (b*S + i)*S + j over E = B*S*S, and every valid dep has ``dep_src``
    = src[b]*S + i and ``dep_dst`` = dst[b]*S + j. A flow dep's channel
    is its ordered (source worker, destination worker) pair — one
    channel per direction of a server pair, an unplaced op's worker (-1)
    read as 0, as the caller's channel lookup (``placement_masks``,
    sim/jax_env.py) reads it: no per-dep channel table exists."""
    src: object  # [B] i32 original-op slot of each block's source, -1 pad
    dst: object  # [B] i32 ... of its destination


#: width of a TPU vector register: a minor axis shorter than this, or
#: not a multiple of it, leaves lanes of every register empty
REGISTER_WIDTH = 128

#: ... and its sublanes: the second-minor axis of a 32-bit array is
#: laid out in tiles of this many rows, so a channel table's worker
#: axis narrower than this fills no fewer registers
REGISTER_SUBLANES = 8

#: what each next width of the lane schedule (:func:`stage_widths`) is
#: of the last, before rounding up to whole registers, and how many
#: widths under the first it may hold: every stage is one more loop to
#: trace and compile. PERF.md section 6 (PR 33) has the chip runs
STAGE_RATIO = Fraction(1, 2)
MAX_NARROWER_STAGES = 5

#: the ``vmap`` axis of the one-job-a-lane form, over which a stage
#: counts its live lanes
LANE_AXIS = "lookahead_lanes"

#: start-up gauges the batching rule sets when it runs in a trace, and
#: the counters the trip drain adds them to per epoch
#: (rl/fused.py:record_lookahead_trips): the minor-axis extent of the
#: dep state the loop carries, in whole registers, and its real slots
MINOR_GAUGES = ("sim.lookahead.minor_slots", "sim.lookahead.minor_used")

#: start-up gauge set beside them: the widths of the channel table the
#: lockstep's lane-packed stages are built at (:func:`channel_widths`)
CHANNEL_GAUGE = "sim.lookahead.channel_widths"

#: ... and the elements a trip of the lockstep's first stage compares
#: against the op-row iota on the vector unit to reach a block's source
#: and destination op (:func:`endpoint_onehot_elems`)
ENDPOINT_GAUGE = "sim.lookahead.endpoint_onehot_elems"

#: ... and those it compares against a WORKER iota in `nominate`, at the
#: narrowest width of the first stage's cascade
#: (:func:`channel_onehot_elems`)
ONEHOT_GAUGE = "sim.lookahead.channel_onehot_elems"


class _Layout(NamedTuple):
    """What the tick body (:func:`_tick_loop`) leaves to the shape its
    state is carried in: the three dep primitives (``src_done`` — has a
    dep's source op completed; ``count_parents`` — add completed deps
    onto their destination ops; ``nominate`` — is a ready flow dep the
    best on its channel), the per-worker op selection, the reductions
    of op or dep state to one value per lane, ``lanes`` (a per-lane
    accumulator from a scalar), ``spread`` (a per-lane value, to what
    broadcasts against op and dep state) and ``loop`` (``while_loop``
    over a per-lane ``live``; as one form of a cascade, only while a
    live lane ``needs`` it)."""
    src_done: object
    count_parents: object
    nominate: object
    select_ops: object
    any: object
    all: object
    min: object
    count: object
    lanes: object
    spread: object
    loop: object


def _block_side(n_deps: int, n_blocks: int) -> int:
    """S of a (block, i, j) dep layout: ``n_deps`` = ``n_blocks`` * S * S."""
    return int(round((n_deps // n_blocks) ** 0.5))


def block_endpoints(op_worker, blocks: DepBlocks, side: int):
    """Where a block's deps start and end, without an index per dep:
    ``from_src`` [B, No] / ``into_dst`` [No, B] (is original op o the
    block's source / destination) and ``w_src`` [B, S_i] / ``w_dst``
    [B, S_j], the worker of the sub-op on each of the block's rows and
    columns, selected from per-sub-op ``op_worker`` [No * S] by those
    one-hots. An unplaced op (-1) reads as worker 0 — it rides server
    0's channels, as a clipped per-dep lookup would have it — and so
    does every row of a padded block (-1)."""
    import jax.numpy as jnp

    No = op_worker.shape[0] // side
    rows = jnp.arange(No, dtype=jnp.int32)
    from_src = blocks.src[:, None] == rows[None, :]        # [B, No]
    into_dst = blocks.dst[None, :] == rows[:, None]        # [No, B]
    worker = jnp.clip(op_worker, 0).reshape(No, side)
    w_src = jnp.max(jnp.where(from_src[:, :, None], worker[None], 0), 1)
    w_dst = jnp.max(jnp.where(into_dst.T[:, :, None], worker[None], 0), 1)
    return from_src, into_dst, w_src, w_dst


def _block_dep_ops(op_worker, blocks: DepBlocks, n_deps: int,
                   num_workers: int):
    """The same three primitives over :class:`DepBlocks` tables: dep
    state is [B, S_i, S_j], op state [No, S], and nothing indexes per
    dep. Integer counts and max are order-free, so each returns the
    bits of a gather or scatter per dep (the tests' reference,
    tests/flat_lookahead.py). The one-hot masks are loop-invariant:
    built here, outside the ``while_loop``."""
    import jax
    import jax.numpy as jnp

    B = blocks.src.shape[0]
    S = _block_side(n_deps, B)
    N = op_worker.shape[0]
    if B * S * S != n_deps or N % S:
        raise ValueError(f"({N}, {n_deps}) is not a block layout of {B} "
                         "blocks")
    No, W = N // S, num_workers
    from_src, into_dst, w_src, w_dst = block_endpoints(op_worker, blocks, S)
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


def _job_layout(op_worker, num_workers: int, dep_ops) -> _Layout:
    """ONE job: op state [N], dep state [E], scalar accumulators, and
    ``dep_ops`` its three dep primitives — :func:`_block_dep_ops`' here;
    a parameter because the tests' bitwise reference
    (tests/flat_lookahead.py) runs this layout and the same tick body
    over a gather or scatter per dep. Batched by ``jax.vmap`` it stays
    one job a lane, and XLA lays the lanes minor."""
    import jax
    import jax.numpy as jnp

    worker_onehot = (jax.nn.one_hot(op_worker, num_workers, dtype=jnp.float32)
                     .T)  # [W, N]; -1 (padding) one-hots to zeros

    def select_ops(scores, ops_ready):
        per_worker = worker_onehot * scores[None, :]  # [W, N]
        best_score = per_worker.max(axis=1)           # [W]
        has_op = best_score > 0
        # an op is selected iff it is its worker's best ready op
        return ops_ready & jnp.any(
            (per_worker == best_score[:, None]) & (best_score[:, None] > 0)
            & (worker_onehot > 0), axis=0)

    def loop(live, tick, init, fit, needs=None):
        del needs   # one form: the cluster's width
        if not fit:
            return jax.lax.while_loop(live, tick, init)
        # a stage of the lane schedule (:func:`stage_widths`), one job
        # a lane under ``vmap``: tick while more lanes are live than the
        # next width holds; jax's batching freezes the lanes that are not
        def more(state):
            on = live(state)
            return on & (jax.lax.psum(on.astype(jnp.int32), LANE_AXIS) > fit)

        return jax.lax.while_loop(more, tick, init)

    return _Layout(*dep_ops, select_ops, jnp.any, jnp.all, jnp.min,
                   jnp.sum, lanes=lambda x: x, spread=lambda x: x,
                   loop=loop)


def channel_widths(num_workers: int, side: int) -> tuple:
    """The widths of the worker axis the lane-packed tick is built at,
    narrowest first: half the block side, the block side (what one
    block of a partitioned op can span, and with parent co-location
    what a job RIDES but for a ragged row on an empty cluster) and the
    cluster's servers. Read from the two shapes alone: a rung under a
    register's sublanes (:data:`REGISTER_SUBLANES`) saves no register,
    and a rung no narrower than the cluster is the cluster — one width
    where the cluster is no wider than the first rung. The cluster's
    width is a channel TABLE's; a rung under it says how many servers a
    lane may ride to tick in a form that carries its state by SERVER
    (:func:`server_slots`), where a dep's channel is its position and
    there is no table — over the whole block side, or over the first
    half of every block's rows alone."""
    return (*(rung for rung in (side // 2, side)
              if REGISTER_SUBLANES <= rung < num_workers), num_workers)


def dense_servers(op_worker, op_valid, n_lanes: int, num_workers: int):
    """Each lane's servers renumbered densely, from packed ``op_worker``
    / ``op_valid`` [No, L*S]: ``(dense, rode)`` — per sub-op the rank of
    its server among the servers its lane's valid sub-ops sit on, -1
    where it was -1, and per lane [L] how many those are (the host's
    ``len(set(job_op_to_worker.values()))`` of a placed job). A valid
    unplaced sub-op counts as on server 0, which is what the tick's
    ``clip`` makes of it, so the ranks are a bijection on every server
    a lane's valid deps can name and channel pairs map one to one. By
    comparison and reduction over [W] x [No, L*S]: no index per
    element. Once a stage, outside the loop."""
    import jax.numpy as jnp

    S = op_worker.shape[1] // n_lanes
    servers = jnp.arange(num_workers, dtype=jnp.int32)[:, None, None]
    worker = jnp.clip(op_worker, 0)
    used = jnp.any(((worker == servers) & op_valid).reshape(
        num_workers, -1, n_lanes, S), axis=(1, 3))          # [W, L]
    # a server's new id: the used servers under it
    below = jnp.repeat(used, S, axis=-1)[:, None] & (servers < worker)
    dense = jnp.sum(below, axis=0, dtype=jnp.int32)         # [No, L*S]
    return (jnp.where(op_worker >= 0, dense, -1),
            jnp.sum(used, axis=0, dtype=jnp.int32))


def server_slots(op_worker, op_valid, n_lanes: int, num_workers: int):
    """Where each sub-op goes when a lane's state is laid out by SERVER,
    from packed ``op_worker`` / ``op_valid`` [No, L*S]: ``(slot, rides)``
    — per sub-op the shard of its op's row it moves to, the rank of its
    server among the lane's (:func:`dense_servers`), -1 for an invalid
    one (it lands nowhere); and per lane [L] the servers its job rides.
    Op slot (o, X) then holds the sub-op of op o on the lane's X-th
    server, so a sub-op's worker is its position.

    That needs the map to be one to one inside every op, which the
    program checks on its input and does not assume: a lane that rides
    more servers than a block has shards, holds a valid sub-op that is
    unplaced, or whose op has two valid sub-ops on one server is AWAY —
    it keeps every sub-op at its own shard (its state by server is its
    state by shard, bit for bit) and ``rides`` more than a block's side,
    so a cascade (:func:`_tick_loop`) holds the cluster's form, which
    reads workers from a table, for as long as it is live. A placed
    job's blocks sit on distinct servers (sim/jax_env.py:
    jax_allocate_job), so only a ragged row spread over an empty cluster
    is away among the lanes that tick. No index per element. Once a
    call, outside the loops."""
    import jax.numpy as jnp

    No, S = op_worker.shape[0], op_worker.shape[1] // n_lanes
    dense, rode = dense_servers(op_worker, op_valid, n_lanes, num_workers)
    rank = jnp.where(op_valid, dense, -1)
    shards = jnp.arange(S, dtype=jnp.int32)
    landing = jnp.sum(rank.reshape(No, n_lanes, S, 1) == shards, axis=2,
                      dtype=jnp.int32)                     # [No, L, S_X]
    away = (jnp.any(landing > 1, axis=(0, 2)) | (rode > S)
            | jnp.any((op_valid & (op_worker < 0)).reshape(
                No, n_lanes, S), axis=(0, 2)))             # [L]
    slot = jnp.where(jnp.repeat(away, S), jnp.tile(shards, n_lanes), rank)
    return slot, jnp.where(away, jnp.maximum(rode, S + 1), rode)


def _packing(n_lanes: int, n_blocks: int, side: int):
    """Between a lane a row ([L, (o, k)] op state, [L, (b, i, j)] dep
    state) and LANE-PACKED ([No, (l, k)], [B, S_i, (l, j)]:
    :func:`_packed_layouts`): ``(ops, deps, lane_ops, lane_deps)``, the
    last two the way back."""
    L, B, S = n_lanes, n_blocks, side

    def ops(x):
        return x.reshape(L, -1, S).transpose(1, 0, 2).reshape(-1, L * S)

    def deps(x):
        return x.reshape(L, B, S, S).transpose(1, 2, 0, 3).reshape(
            B, S, L * S)

    def lane_ops(x):
        return x.reshape(-1, L, S).transpose(1, 0, 2).reshape(L, -1)

    def lane_deps(x):
        return x.reshape(B, S, L, S).transpose(2, 0, 1, 3).reshape(L, -1)

    return ops, deps, lane_ops, lane_deps


def _contract(onehot, x, over: int, n_lanes: int):
    """``onehot`` [L, B, No] with lane-packed ``x`` [M, (l, k)] along
    the one-hot's axis ``over`` (1: M = B blocks, 2: M = No ops), a lane
    a batch: [the other axis, (l, k)] i32. 0/1 times whole numbers <= S
    in int8, summed in int32: exact."""
    import jax
    import jax.numpy as jnp

    L = n_lanes
    S = x.shape[-1] // L
    out = jax.lax.dot_general(
        onehot, x.reshape(-1, L, S).astype(jnp.int8),
        (((over,), (0,)), ((0,), (1,))),
        preferred_element_type=jnp.int32)                  # [L, other, S]
    return out.transpose(1, 0, 2).reshape(-1, L * S)


def _from_source(x, n_lanes: int):
    """[B, (l, i)] — a value of each block's source op shard — to dep
    state [B, i, (l, j)]: the same for every destination j."""
    import jax.numpy as jnp

    B, L = x.shape[0], n_lanes
    S = x.shape[1] // L
    return jnp.broadcast_to(x.reshape(B, L, S).transpose(0, 2, 1)[..., None],
                            (B, S, L, S)).reshape(B, S, L * S)


def by_server(slot, onehots, n_lanes: int):
    """``(move_ops, move_deps)``: lane-packed op state [No, (l, k)] and
    dep state [B, S_i, (l, j)] moved from shard to SERVER coordinates,
    where every sub-op sits at ``slot`` (:func:`server_slots`) of its
    op's row and the dep of block b from the sub-op at X to the sub-op
    at Y at (b, X, (l, Y)); a slot nothing lands on reads 0 / False. By
    select-and-sum against the shard iota, one axis at a time, each over
    a WIDE minor axis — the destination shard is moved while it is the
    row axis of the block's transpose: at most one source lands on a
    target, so the sum is that source, exact in any float or whole
    type. ``onehots`` are the lanes' :func:`endpoint_onehots`. No index
    per element. Once a call, outside the loops: a dep array moved is
    some thirty passes over itself."""
    import jax.numpy as jnp

    L = n_lanes
    No, S = slot.shape[0], slot.shape[1] // L
    at_src, at_dst = onehots
    shards = jnp.arange(S, dtype=jnp.int32)

    def whole(x):
        return x.astype(jnp.int8) if x.dtype == bool else x

    def move_ops(x):
        kind, x = x.dtype, whole(x)
        out = jnp.sum(jnp.where(slot.reshape(No, L, S, 1) == shards,
                                x.reshape(No, L, S, 1), 0),
                      axis=2, dtype=x.dtype)               # [No, L, S_X]
        return out.reshape(No, L * S).astype(kind)

    # where the sub-op on each row / column of a block goes: its op's
    # row of ``slot``, through the blocks' 0/1 endpoint matrices (a
    # padded block, no op's, reads -1: its deps land nowhere)
    to_x, to_y = (_from_source(_contract(onehot, slot + 1, 2, L) - 1,
                               L).astype(jnp.int8)[:, None]
                  for onehot in (at_src, at_dst))          # [B, 1, S_r, L*S]
    targets = jnp.arange(S, dtype=jnp.int8)[:, None, None]

    def rows_moved(x, to):
        """[B, S_r, (l, c)] -> the same with row r at row ``to``."""
        return jnp.sum(jnp.where(to == targets, x[:, None], 0), axis=2,
                       dtype=x.dtype)

    def transposed(x):
        """[B, i, (l, j)] <-> [B, j, (l, i)]."""
        B = x.shape[0]
        return x.reshape(B, S, L, S).transpose(0, 3, 2, 1).reshape(
            B, S, L * S)

    def move_deps(x):
        out = rows_moved(transposed(rows_moved(transposed(whole(x)), to_y)),
                         to_x)
        return out.astype(x.dtype)

    return move_ops, move_deps


def endpoint_onehots(blocks: DepBlocks, n_ops: int):
    """The 0/1 endpoint matrices of lane-packed ``blocks`` ([B, L]
    tables): ``(at_src, at_dst)``, each [L, B, No] int8 — is original
    op o the source / the destination of lane l's block b (a padded
    block, -1, is no op's). They depend on the tables alone, not on the
    channel table's width and not on loop state: built once a stage,
    outside the loop."""
    import jax.numpy as jnp

    ops = jnp.arange(n_ops, dtype=jnp.int32)
    return tuple((x.T[:, :, None] == ops).astype(jnp.int8)
                 for x in (blocks.src, blocks.dst))


def _packed_layouts(op_worker, blocks: DepBlocks, n_lanes: int,
                    widths, onehots=None, dep_rows=None) -> tuple:
    """L lanes of :class:`DepBlocks` tables, LANE-PACKED, one
    :class:`_Layout` a width of ``widths`` — the same state under
    channel tables of those many workers (None: under no table, by
    position: below), every one over ``op_worker``'s server ids and the
    SAME endpoint tables: only how `nominate` and `select_ops` find a
    slot's worker differs. Op state is [No, L*S] and
    dep state [B, S_i, L*S_j], the minor axis holding (lane, shard) at
    ``lane*S + shard`` — the DESTINATION shard j for a dep — so every
    select and reduction of a trip runs over L*S-wide rows however few
    the lanes (16 shards x 32 lanes fill four vector registers; 32 lanes
    alone a quarter of one). The lane is the MAJOR part so that lanes
    sharded over devices stay sharded through the merge (GSPMD splits a
    merged axis by its major factor only). A per-lane value is [L], and
    repeated over the shards (``spread``) where it meets either state.
    Nothing indexes per dep; integer counts and max are order-free and
    every float op is elementwise per lane, so each lane's bits are the
    one-job form's. ``op_worker`` comes packed; ``blocks`` holds [B, L]
    tables.

    A block's source and destination op are found on the MATRIX unit:
    `src_done` and `count_parents` are one ``dot_general`` each, a lane
    a batch, with the blocks' 0/1 endpoint matrices
    (:func:`endpoint_onehots`, [L, B, No] int8; ``onehots`` where the
    layouts of a stage share them) — [L, B, No] x [L, No, S] over the
    ops, and [L, No, B] x [L, B, S] over the blocks — where selecting
    one op row out of No by comparison costs B * No * L * S element
    steps of the vector unit a primitive a trip (170 M at 570 ops x
    1,162 blocks x 16 lanes, 36 x the dep state itself). Exact, not
    approximate: the operands are 0/1 and whole numbers <= S in int8,
    the products are summed in int32 (``preferred_element_type``, so no
    result follows ``JAX_ENABLE_X64``), and a sum over a destination's
    incoming blocks is a whole number however many they are.

    A width of None: the state is laid out by SERVER
    (:func:`server_slots`, :func:`by_server`) — op slot (o, X) is the
    sub-op of op o on the lane's X-th server and dep slot (b, X, Y) the
    dep of block b from the sub-op on X to the one on Y. The two
    contractions do not change (a block's row X reads op (src[b], X),
    its column Y adds onto op (dst[b], Y)), and there is no channel
    table: a dep's channel is its POSITION, so `nominate` is one max
    over the blocks and `select_ops` one max over the ops — a pass each,
    where the table's two reductions and two read-backs against a
    worker iota are 2 * B*S*W*L*S + 2 * B*W*W*L*S element steps
    (:func:`channel_onehot_elems`: 64 passes over the dep state at W =
    S = 16). Only lanes that are at home there may tick in it; a table
    reads ``op_worker``'s ids whatever the layout. ``dep_rows``: the
    dep state holds each block's first that many rows alone, [B,
    dep_rows, L*S] — all a lane that rides no more servers can hold a
    valid dep in; op state is whole.

    Loop-invariant tables are built here, outside the ``while_loop``s;
    the endpoint-worker tables are pinned there."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    L = n_lanes
    No, S = op_worker.shape[0], op_worker.shape[1] // L
    B = blocks.src.shape[0]
    if S > 127:
        raise ValueError(f"a block side of {S} passes int8: "
                         "`count_parents` contracts counts up to it")
    dep_rows = S if dep_rows is None else dep_rows
    rows = jnp.arange(No, dtype=jnp.int32)[:, None]        # [No, 1]

    def spread(x):
        """[..., L] -> [..., L*S]: a lane's value on each of its slots."""
        return jnp.repeat(x, S, axis=-1)

    def over_shards(reduce, x):
        """[..., L*S] -> [..., L]: each lane's slots, reduced."""
        return reduce(x.reshape(x.shape[:-1] + (L, S)), axis=-1)

    def over_lane(reduce):
        """op or dep state -> [L]: all of a lane's slots, reduced."""
        return lambda x: over_shards(
            reduce, reduce(x, axis=tuple(range(x.ndim - 1))))

    from_source = partial(_from_source, n_lanes=L)

    if any(width is not None for width in widths):
        # each block's endpoint rows, per (lane, shard) slot; an unplaced
        # op (-1) rides server 0's channels, as the caller's clipped
        # ``pair_channel`` lookup has it
        src, dst = spread(blocks.src), spread(blocks.dst)  # [B, L*S]
        worker = jnp.clip(op_worker, 0)

        def endpoint_worker(row):
            return jnp.max(jnp.where(row[:, None] == rows, worker, 0),
                           axis=1)

        w_src = from_source(endpoint_worker(src))          # [B, S_i, L*S]
        w_dst = endpoint_worker(dst)                       # [B, L*S_j]
        # held as built: left to XLA, every trip of every form copies
        # ``w_src`` (as large as the dep state) into the layout its
        # compare reads
        w_src, w_dst = jax.lax.optimization_barrier((w_src, w_dst))

    at_src, at_dst = (endpoint_onehots(blocks, No) if onehots is None
                      else onehots)                        # [L, B, No]

    def src_done(op_done):
        return from_source(_contract(at_src, op_done, 2, L) > 0)[:, :dep_rows]

    def count_parents(parent_done, inc):
        into = inc.sum(axis=1, dtype=inc.dtype)            # [B, L*S_j]
        return parent_done + _contract(at_dst, into, 1, L)

    def channel_ops(width):
        """`nominate` and `select_ops` over a table of ``width`` workers."""
        workers = jnp.arange(width, dtype=jnp.int32)[:, None]  # [W, 1]

        def nominate(dscores, flow_ready):
            # best[X, Y] over the deps whose source sits on worker X and
            # destination on Y: max over i into X, over b into Y, and
            # last over the shards j — on [W, W, L*S], not on dep state.
            # The one-hots are compared here, every trip: as loop
            # invariants they would be W times the dep state, read
            # twice a trip
            on_src = w_src[:, :, None] == workers          # [B, S_i, W, L*S]
            on_dst = w_dst[:, None, None] == workers       # [B, 1, W, L*S]
            to_x = jnp.max(jnp.where(on_src, dscores[:, :, None], -1.0),
                           axis=1)
            best = spread(over_shards(jnp.max, jnp.max(
                jnp.where(on_dst, to_x[:, :, None], -1.0), axis=0)))
            # ... and back: each dep reads best[X(b, i), Y(b, j)]
            of_y = jnp.max(jnp.where(on_dst, best, -1.0), axis=2)
            mine = jnp.max(jnp.where(on_src, of_y[:, None], -1.0), axis=2)
            return flow_ready & (dscores >= mine) & (dscores > 0)

        def select_ops(scores, ops_ready):
            mine = op_worker == workers[:, None]           # [W, No, L*S]
            best = spread(over_shards(jnp.max, jnp.max(
                jnp.where(mine, scores, 0.0), axis=1)))[:, None]
            # an op is selected iff it is its worker's best ready op
            return ops_ready & jnp.any(
                mine & (scores == best) & (best > 0), axis=0)

        return nominate, select_ops

    def position_ops():
        """`nominate` and `select_ops` of state laid out by server."""
        def nominate(dscores, flow_ready):
            # dep slot (b, X, (l, Y)) rides lane l's channel X -> Y
            best = jnp.max(dscores, axis=0)                # [S_X, L*S_Y]
            return flow_ready & (dscores >= best) & (dscores > 0)

        def select_ops(scores, ops_ready):
            # op slot (o, (l, X)) sits on lane l's server X: an op is
            # selected iff it is its worker's best ready op
            best = jnp.max(scores, axis=0)                 # [L*S_X]
            return ops_ready & (scores == best) & (best > 0)

        return nominate, select_ops

    def loop(live, tick, init, fit, needs=None):
        # what jax's batching makes of ``while_loop``: run while any
        # lane is live — as a stage of the lane schedule
        # (:func:`stage_widths`), while more are than the next width
        # holds (``fit``); as one form of a cascade of channel widths,
        # while a live lane is among those that need it (``needs``,
        # [L]) — and freeze the lanes that are not
        def frozen_tick(state):
            on = live(state)
            on_slots = spread(on)
            return jax.tree_util.tree_map(
                lambda new, old: jnp.where(on if new.ndim == 1 else on_slots,
                                           new, old), tick(state), state)

        def more(state):
            on = live(state)
            go = jnp.sum(on, dtype=jnp.int32) > fit if fit else jnp.any(on)
            return go if needs is None else go & jnp.any(on & needs)

        return jax.lax.while_loop(more, frozen_tick, init)

    return tuple(
        _Layout(src_done, count_parents,
                *(position_ops() if width is None else channel_ops(width)),
                over_lane(jnp.any), over_lane(jnp.all), over_lane(jnp.min),
                over_lane(partial(jnp.sum, dtype=jnp.int32)),
                lanes=lambda x: jnp.broadcast_to(x, (L,)), spread=spread,
                loop=loop)
        for width in widths)


def _tick_loop(lay: _Layout, op_remaining, op_valid, op_score, num_parents,
               dep_remaining, dep_valid, dep_mutual, dep_is_flow, dep_score,
               skip, max_iters: int, state=None, fit: int = 0,
               narrower=None):
    """THE tick loop, in whatever shape ``lay`` carries its state;
    returns (t, comm_oh, comp_oh, busy, ok, trips) per lane, the state
    the loop left, and the trips it ran in each form, narrowest first.
    As a stage of the lane schedule (:func:`stage_widths`) it starts
    from the ``state`` an earlier stage left (None: a job's start) and
    stops once the next width holds the live lanes (``fit``; 0: when
    none is live). ``narrower`` — (rode, forms) — is the servers each
    lane's job rides, [lanes], and the same state's narrower layouts as
    (servers held, layout) pairs, widest first. The stage is then a
    CASCADE of loops over one state, from ``lay``'s down: each form
    ticks while the stage is on and some LIVE lane rides more servers
    than the next form holds, the last to the stage's end — so the form
    follows the lanes still live, and a form no live lane needs runs no
    trip. A last pair with no layout names a form the CALLER runs, over
    another cut of the state: the cascade then stops where that form
    holds the live lanes. Every live lane ticks in every trip and a
    frozen lane's state is never written, so the forms a lane is
    carried through cannot show in its bits."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    # scalar accumulators follow the input dtype: f32 on the standard
    # path, f64 when the caller runs under JAX_ENABLE_X64 (the jitted
    # env-step parity mode, sim/jax_env.py)
    dt = op_remaining.dtype

    def cond(state):
        (_, _, op_done, dep_done, _, _, _, _, _, it, stuck) = state
        all_done = (lay.all(op_done | ~op_valid)
                    & lay.all(dep_done | ~dep_valid))
        live = (~all_done) & (it < max_iters) & (~stuck)
        return live if skip is None else live & ~skip

    def body(state, lay=lay):
        (rem_op, rem_dep, op_done, dep_done, parent_done,
         t, comm_oh, comp_oh, busy, it, stuck) = state

        # 1. readiness (snapshotted BEFORE this tick's completions)
        ops_ready = op_valid & ~op_done & (parent_done >= num_parents)
        deps_ready = dep_valid & ~dep_done & lay.src_done(op_done)
        flow_ready = deps_ready & dep_is_flow
        nonflow_ready = deps_ready & ~dep_is_flow
        any_nonflow = lay.any(nonflow_ready)

        # 2. per-worker highest-score ready op
        scores = jnp.where(ops_ready, op_score, -1.0)
        sel_ops = lay.select_ops(scores, ops_ready)
        shortest_op = lay.min(jnp.where(sel_ops, rem_op, BIG))

        # 3. per-channel highest-score ready flow dep
        dscores = jnp.where(flow_ready, dep_score, -1.0)
        nominated = lay.nominate(dscores, flow_ready)
        shortest_comm = jnp.where(
            any_nonflow, 0.0,
            lay.min(jnp.where(nominated, rem_dep, BIG)))

        tick = jnp.minimum(shortest_op, shortest_comm)
        new_stuck = tick >= BIG  # nothing can progress: host raises

        # 4. advance ops
        rem_op2 = jnp.where(
            sel_ops, jnp.maximum(rem_op - lay.spread(tick), 0.0), rem_op)
        op_now_done = sel_ops & (rem_op2 <= 0.0) & ~op_done
        op_done2 = op_done | op_now_done

        # 5. advance deps: the snapshot's non-flow deps if any, else ALL
        # snapshot-ready flow deps (parallel-flow hack)
        dep_tick_mask = jnp.where(lay.spread(any_nonflow), nonflow_ready,
                                  flow_ready)
        rem_dep2 = jnp.where(
            dep_tick_mask, jnp.maximum(rem_dep - lay.spread(tick), 0.0),
            rem_dep)
        dep_now_done = dep_tick_mask & (rem_dep2 <= 0.0) & ~dep_done
        dep_done2 = dep_done | dep_now_done

        # 6. non-mutual completed deps advance their child's parent count
        inc = (dep_now_done & ~dep_mutual).astype(jnp.int32)
        parent_done2 = lay.count_parents(parent_done, inc)

        ticked_ops = lay.any(sel_ops)
        ticked_flows = (~any_nonflow) & lay.any(flow_ready)
        safe_tick = jnp.where(new_stuck, 0.0, tick)
        comp_oh2 = comp_oh + jnp.where(ticked_ops, safe_tick, 0.0)
        comm_oh2 = comm_oh + jnp.where(ticked_flows, safe_tick, 0.0)
        busy2 = busy + safe_tick * lay.count(sel_ops).astype(dt)
        t2 = t + safe_tick

        return (rem_op2, rem_dep2, op_done2, dep_done2, parent_done2,
                t2, comm_oh2, comp_oh2, busy2, it + 1, stuck | new_stuck)

    if state is None:
        state = (op_remaining, dep_remaining,
                 jnp.zeros(op_remaining.shape, bool),
                 jnp.zeros(dep_remaining.shape, bool),
                 jnp.zeros(op_remaining.shape, jnp.int32),
                 lay.lanes(jnp.zeros((), dt)), lay.lanes(jnp.zeros((), dt)),
                 lay.lanes(jnp.zeros((), dt)), lay.lanes(jnp.zeros((), dt)),
                 lay.lanes(jnp.int32(0)), lay.lanes(jnp.bool_(False)))
    rode, forms = narrower or (None, ())
    # a width with no layout is run by the caller, over another state:
    # the cascade hands over to it, and does not run to the stage's end
    layouts = (lay, *(form for _, form in forms if form is not None))
    # what the next form down holds: who rides more needs this one
    holds_below = (*(width for width, _ in forms), None)
    out, ran = state, []
    for form, below in zip(layouts, holds_below):
        before = out[9]
        with jax.named_scope(scopes.SIM_LOOKAHEAD):
            out = form.loop(cond, partial(body, lay=form), out, fit,
                            None if below is None else rode > below)
        # every live lane ticks in every trip of a form's loop
        ran.append(jnp.max(out[9] - before))
    return (_results(lay, out, op_valid, dep_valid), out,
            jnp.stack(ran[::-1]))


def _results(lay: _Layout, state, op_valid, dep_valid):
    """(t, comm_oh, comp_oh, busy, ok, trips) per lane, of the state a
    tick loop left."""
    (_, _, op_done, dep_done, _, t, comm_oh, comp_oh, busy, it,
     stuck) = state
    finished = (lay.all(op_done | ~op_valid)
                & lay.all(dep_done | ~dep_valid))
    return t, comm_oh, comp_oh, busy, finished & ~stuck, it


def stage_widths(n_lanes: int, side: int) -> list:
    """The lane counts the block-path loop runs at, one stage each: a
    short, strictly descending list that starts at ``n_lanes``. Each
    next width is the last one times :data:`STAGE_RATIO`, rounded UP to
    whole vector registers of the form that width runs in — multiples
    of ``REGISTER_WIDTH // side`` lanes while lane-packed, of
    :data:`REGISTER_WIDTH` from 128 lanes on — or, where that is no
    narrower, the next whole width below; it ends at one register's
    worth of packed lanes, or after :data:`MAX_NARROWER_STAGES` widths
    under ``n_lanes``. Lanes that already fit one register (the
    unbatched call among them) give ``[n_lanes]``: one loop."""
    packed = max(REGISTER_WIDTH // side, 1)

    def whole(width, up):
        unit = REGISTER_WIDTH if width >= REGISTER_WIDTH else packed
        return (-(-width // unit) if up else width // unit) * unit

    widths = [n_lanes]
    while widths[-1] > packed and len(widths) <= MAX_NARROWER_STAGES:
        last = widths[-1]
        width = whole(-(-last * STAGE_RATIO.numerator
                        // STAGE_RATIO.denominator), up=True)
        widths.append(width if width < last else whole(last - 1, up=False))
    return widths


def endpoint_onehot_elems(n_lanes: int, n_ops: int, n_blocks: int,
                          side: int) -> int:
    """The elements a trip of the lockstep's first (widest) stage
    compares against the op-row iota on the vector unit in EACH of
    `src_done` and `count_parents`, over ``n_ops`` original ops and
    ``n_blocks`` blocks: none while the stage is lane-packed (both are
    contractions, :func:`_packed_layouts`), blocks x ops x shards a lane
    from :data:`REGISTER_WIDTH` lanes on (:func:`_block_dep_ops`)."""
    return 0 if n_lanes < REGISTER_WIDTH else \
        n_blocks * n_ops * n_lanes * side


def channel_onehot_elems(n_lanes: int, n_blocks: int, side: int,
                         num_workers: int) -> int:
    """The elements a trip of the lockstep's first (widest) stage
    compares against a WORKER iota in `nominate`, at the narrowest
    width of its cascade: none where that width carries its state by
    server (:func:`_packed_layouts`: lane-packed, with a width under
    the cluster's), else the table's two reductions and two read-backs
    over the cluster's W servers, 2 * B*S*W*L*S + 2 * B*W*W*L*S — the
    one-job-a-lane form from :data:`REGISTER_WIDTH` lanes on
    (:func:`_block_dep_ops`), and a cluster of one width."""
    if n_lanes < REGISTER_WIDTH and len(channel_widths(num_workers,
                                                       side)) > 1:
        return 0
    return 2 * n_blocks * side * num_workers * n_lanes * (side + num_workers)


def stage_trips(own, widths):
    """The trips each stage of ``widths`` (:func:`stage_widths`) runs,
    [..., stages], from every lane's OWN trip count (``own``,
    [..., lanes]: one call's lanes last). All lanes tick in lockstep
    from trip 0 and a lane is live for its own first trips, so a stage
    ends at the trip after which the next width holds the lanes still
    live — the count of the lane that many places from the longest —
    and the last stage at the longest lane's."""
    longest_first = -np.sort(-np.asarray(own), axis=-1)
    ends = np.stack([longest_first[..., width]
                     for width in widths[1:] + [0]], axis=-1)
    return np.diff(ends, axis=-1, prepend=0)


def channel_trips(own, rode, widths, num_workers: int, side: int):
    """The trips each stage of ``widths`` ran at each width of the
    channel table (:func:`channel_widths`, narrowest first),
    [..., stages, channel widths] (their sum over the last axis is
    :func:`stage_trips`), by the loop's own rule: from every lane's OWN
    trip count (``own``, [..., lanes]) and the servers its job rode
    (``rode``, alike; 0 where it ran no trip). A lane is live at
    lockstep trip t iff its own count passes t, and a lane-packed stage
    (under :data:`REGISTER_WIDTH` lanes) ticks trip t over the
    narrowest table that holds what every lane live at t rode — so a
    width holds from the trip at which the last lane that rode more
    ends, to the end. The stages of one job a lane keep the cluster's
    width."""
    own, rode = np.asarray(own), np.asarray(rode)
    trips = stage_trips(own, widths)
    ends = np.cumsum(trips, axis=-1)
    starts = ends - trips
    channels = channel_widths(num_workers, side)
    # the lockstep trip from which each width holds the live lanes
    since = np.stack([np.max(np.where(rode > width, own, 0), axis=-1)
                      for width in channels[:-1]]
                     + [np.zeros(own.shape[:-1], own.dtype)], axis=-1)
    cut = np.clip(since[..., None, :], starts[..., None], ends[..., None])
    ran = np.concatenate([ends[..., None], cut[..., :-1]], axis=-1) - cut
    unpacked = np.asarray(widths) >= REGISTER_WIDTH
    ran[..., unpacked, :-1] = 0
    ran[..., unpacked, -1] = trips[..., unpacked]
    return ran


def _lane_batched_lookahead(num_workers: int):
    """The block-path lookahead of L jobs at once: every argument
    carries a leading lane axis [L, ...] (``skip``: [L] or None) and so
    do the six results. Batching it (``vmap``) folds the new axis into
    the lanes and calls again at A*L, so any nest of vmaps around the
    per-job call runs ONE lockstep of loops whose lanes are their
    product.

    The shape of a loop's state is chosen on its lanes alone, by what
    fills a vector register (:data:`REGISTER_WIDTH`). While the lanes
    alone do not (under 128) the state is LANE-PACKED
    (:func:`_packed_layouts`): (lane, shard) merged on the minor axis.
    From 128 lanes on the loop is one job's (:func:`_block_dep_ops`)
    under ``jax.vmap``, whose lanes XLA lays minor by itself: there the
    packed form is the slower one (its worker x worker tables carry the
    shards too; at 320 lanes a trip is 1.33 ms packed against 1.21, my
    chip run, PR 27).

    A trip costs what the lanes it carries cost, and most lanes finish
    long before the longest (a memo hit ``skip``s from trip 0), so the
    lockstep runs in STAGES of falling width (:func:`stage_widths`, on
    L and the block side alone): a stage ticks until the next width
    holds the lanes still live, which are then gathered — whole lanes,
    once a stage, outside the loops — into the next stage's state. A
    lane's ticks do not depend on which lanes share its loop, so every
    lane's six results are the one-loop program's bits.

    The form a lane-packed stage ticks in follows the lanes still LIVE,
    over up to three widths (:func:`channel_widths`): what a job RIDES
    is at most a block's side but for a ragged row spread over an empty
    cluster, and mostly under half of it, and a placed job's sub-ops of
    one op sit on distinct servers. So a call lays its packed state out
    by SERVER — once, at its first lane-packed stage, outside every
    loop (``to_servers``: :func:`server_slots`, :func:`by_server`);
    where a sub-op goes depends on its own lane alone, so the later
    stages gather whole lanes of what was moved — and each stage runs
    as a cascade of ``while_loop``s over that state
    (:func:`_tick_loop`), widest first: the tick over the cluster's
    channel table while a live lane rides more than a block's side (or
    is not one to one: the program checks, per lane), then the tick
    with NO table — by server a dep's channel is its position, so the
    channel's best is one max over the blocks and a worker's best op
    one max over the ops — while one rides more than half a side, then
    the same over the first half of every block's ROWS alone, cut
    out of the state and laid back over it, to the stage's end. A form
    no live lane needs runs no trip; there is no branch. Max and whole
    counts are order-free and every float operation stays elementwise
    per slot, so every form gives each lane the same bits. No option
    selects one.

    Which op a block starts and ends at, a lane-packed stage asks the
    matrix unit: every form contracts op state and completed-dep counts
    with the stage's 0/1 endpoint matrices (:func:`endpoint_onehots`:
    built from the block tables once a stage, outside the loops), in
    int8 with int32 sums — exact (:func:`_packed_layouts`). The >=
    128-lane form keeps the compare: its one-hots are a sixtieth of its
    trip.

    ``run.staged`` is the same function with, beside the results, the
    trips each stage ran at each of those widths (:func:`channel_trips`
    is the host's reckoning of them), for tests."""
    from functools import partial

    import jax
    import jax.numpy as jnp

    from ddls_tpu.telemetry import startup

    def one_job(op_remaining, op_valid, op_worker, op_score, num_parents,
                dep_remaining, dep_valid, dep_mutual, dep_is_flow, dep_score,
                blocks, skip, state=None, fit=0):
        N, E = op_remaining.shape[0], dep_remaining.shape[0]
        return _tick_loop(
            _job_layout(op_worker, num_workers, _block_dep_ops(
                op_worker, blocks, E, num_workers)),
            op_remaining, op_valid, op_score, num_parents, dep_remaining,
            dep_valid, dep_mutual, dep_is_flow, dep_score, skip, N + E + 4,
            state, fit)[:2]

    def one_loop(args, state=None, fit=0, rides=None):
        """The loop at ``args``' own lane count, in that count's form,
        and the trips it ran at each width of the channel table
        (:func:`channel_widths`, narrowest first); ``state`` comes and
        goes a lane a row (it goes only from a stage that has a
        successor: ``fit``). ``rides`` [L]: ``args`` and ``state`` are
        laid out by server (``to_servers``) and each lane's job rides
        that many."""
        (op_remaining, op_valid, op_worker, op_score, num_parents,
         dep_remaining, dep_valid, dep_mutual, dep_is_flow, dep_score,
         blocks, skip) = args
        L, N = op_remaining.shape
        E, B = dep_remaining.shape[1], blocks.src.shape[1]
        S = _block_side(E, B)
        channels = channel_widths(num_workers, S)
        if L >= REGISTER_WIDTH:
            out, left = jax.vmap(partial(one_job, fit=fit),
                                 axis_name=LANE_AXIS)(*args, state)
            ran = jnp.max(out[5] - (0 if state is None else state[9]))
            return out, left, jnp.zeros(len(channels), jnp.int32).at[-1].set(
                ran)

        ops, deps, lane_ops, lane_deps = _packing(L, B, S)

        def pack(state, ops, deps):
            rem_op, rem_dep, op_done, dep_done, parent_done = state[:5]
            return (ops(rem_op), deps(rem_dep), ops(op_done), deps(dep_done),
                    ops(parent_done)) + tuple(state[5:])

        op_worker, op_valid = ops(op_worker), ops(op_valid)
        blocks = DepBlocks(blocks.src.T, blocks.dst.T)
        onehots = endpoint_onehots(blocks, N // S)
        # the servers a job RIDES are far fewer than the cluster's: the
        # widths under it tick with no channel table, while every live
        # lane is at home there (``rides``: :func:`server_slots`); the
        # one over the whole block side shares the cluster's state
        by_server = () if rides is None else channels[:-1]
        sided, halved = S in by_server, bool(by_server) and by_server[0] < S
        *under, wide = _packed_layouts(
            op_worker, blocks, L, (None,) * sided + channels[-1:], onehots)
        statics = (ops(op_remaining), op_valid, ops(op_score),
                   ops(num_parents), deps(dep_remaining), deps(dep_valid),
                   deps(dep_mutual), deps(dep_is_flow), deps(dep_score))
        state = None if state is None else pack(state, ops, deps)
        forms = tuple(zip((S,), under)) + (
            ((by_server[0], None),) if halved else ())
        out, left, ran = _tick_loop(
            wide, *statics, skip, N + E + 4, state, fit,
            (rides, forms) if forms else None)
        if halved:
            # ... and the first rung ticks the first half of every
            # block's rows alone — a lane that rides no more has no
            # valid dep beyond, nor in the columns beyond, which stay
            # for the lanes' sake: at 8 lanes a halved minor axis would
            # fill no fewer registers — cut out of that state and laid
            # back over it, so the rows of a frozen lane that rides
            # more are kept; op state is shared whole
            h = by_server[0]
            first, = _packed_layouts(op_worker, blocks, L, (None,), onehots,
                                     dep_rows=h)

            def half(x):
                return x[:, :h] if x.ndim == 3 else x

            _, halves, ran_first = _tick_loop(
                first, *map(half, statics), skip, N + E + 4,
                tuple(map(half, left)), fit)
            left = tuple(
                x if x.ndim < 3 else jnp.concatenate([x, full[:, h:]], 1)
                for x, full in zip(halves, left))
            out = _results(wide, left, op_valid, statics[5])
            ran = jnp.concatenate([ran_first, ran])
        return out, pack(left, lane_ops, lane_deps) if fit else None, ran

    def to_servers(args, state=None):
        """A lane-packed stage's ``args`` and ``state`` (a lane a row)
        laid out by SERVER, and the servers each lane's job rides
        (:func:`server_slots`, :func:`by_server`): once a call, at its
        first lane-packed stage — where a sub-op goes depends on its
        own lane alone, so the later stages gather whole lanes of what
        was moved here. The three dep masks move as one int8."""
        (op_remaining, op_valid, op_worker, op_score, num_parents,
         dep_remaining, dep_valid, dep_mutual, dep_is_flow, dep_score,
         blocks, skip) = args
        L, B = op_remaining.shape[0], blocks.src.shape[1]
        S = _block_side(dep_remaining.shape[1], B)
        ops, deps, lane_ops, lane_deps = _packing(L, B, S)
        slot, rides = server_slots(ops(op_worker), ops(op_valid), L,
                                   num_workers)
        move_ops, move_deps = by_server(slot, endpoint_onehots(
            DepBlocks(blocks.src.T, blocks.dst.T), op_remaining.shape[1] // S),
            L)

        def op_state(x):
            return lane_ops(move_ops(ops(x)))

        def dep_state(x):
            return lane_deps(move_deps(deps(x)))

        masks = dep_state(dep_valid.astype(jnp.int8)
                          + 2 * dep_mutual.astype(jnp.int8)
                          + 4 * dep_is_flow.astype(jnp.int8))
        args = (op_state(op_remaining), op_state(op_valid),
                op_state(op_worker + 1) - 1, op_state(op_score),
                op_state(num_parents), dep_state(dep_remaining),
                masks % 2 > 0, masks // 2 % 2 > 0, masks // 4 > 0,
                dep_state(dep_score), blocks, skip)
        if state is not None:
            rem_op, rem_dep, op_done, dep_done, parent_done = state[:5]
            state = (op_state(rem_op), dep_state(rem_dep), op_state(op_done),
                     dep_state(dep_done), op_state(parent_done),
                     *state[5:])
        return args, state, rides

    def rows(x, lanes):
        """Whole lanes of ``x``: ``lanes`` are distinct and in range."""
        return x.at[lanes].get(unique_indices=True,
                               mode="promise_in_bounds")

    def staged(*args):
        op_remaining, dep_remaining, blocks = args[0], args[5], args[10]
        (L, N), E, B = op_remaining.shape, dep_remaining.shape[1], \
            blocks.src.shape[1]
        S = _block_side(E, B)
        if B * S * S != E or N % S:
            raise ValueError(f"({N}, {E}) is not a block layout of {B} "
                             "blocks")
        widths = stage_widths(L, S)
        # a cluster of one width keeps its table, and its state by shard
        by_server = len(channel_widths(num_workers, S)) > 1

        def placed(width):
            return by_server and width < REGISTER_WIDTH

        state = rides = None
        if placed(L):
            args, state, rides = to_servers(args)
        if len(widths) == 1:
            part, _, ran = one_loop(args, rides=rides)
            return part, (ran,)
        part, state, ran = one_loop(args, fit=widths[1], rides=rides)
        results, lanes, ran = part, jnp.arange(L), [ran]
        for width, fit in zip(widths[1:], widths[2:] + [0]):
            # the lanes still live first, in their order, then as many
            # of the others (frozen: they carry their results along) as
            # fill the width
            ok, trips, stuck, skip = part[4], part[5], state[-1], args[11]
            live = ~ok & ~stuck & (trips < N + E + 4)
            if skip is not None:
                live = live & ~skip
            keep = jnp.argsort(~live, stable=True)[:width]
            lanes = rows(lanes, keep)
            args, state, rides = jax.tree_util.tree_map(
                lambda x: rows(x, keep), (args, state, rides))
            if placed(width) and rides is None:
                args, state, rides = to_servers(args, state)
            part, state, by_channel = one_loop(args, state, fit, rides)
            ran.append(by_channel)
            results = tuple(
                x.at[lanes].set(y, unique_indices=True,
                                mode="promise_in_bounds")
                for x, y in zip(results, part))
        return results, tuple(ran)

    @jax.custom_batching.custom_vmap
    def run(*args):
        return staged(*args)[0]

    run.staged = staged

    @run.def_vmap
    def fold_into_lanes(axis_size, in_batched, *args):
        def fold(x, batched):
            if not batched:
                x = jnp.broadcast_to(x, (axis_size,) + x.shape)
            return x.reshape((axis_size * x.shape[1],) + x.shape[2:])

        args = jax.tree_util.tree_map(fold, args, tuple(in_batched))
        lanes, n_deps, n_blocks = (args[0].shape[0], args[5].shape[1],
                                   args[10].src.shape[1])
        # what the lockstep traced last carries on the minor axis of its
        # dep state in its first (widest) stage, for the trip drain's
        # counters
        # (rl/fused.py:record_lookahead_trips): (lane, shard) slots, or
        # the lanes alone
        side = _block_side(n_deps, n_blocks)
        minor = lanes if lanes >= REGISTER_WIDTH else lanes * side
        for name, value in zip(MINOR_GAUGES, (
                -(-minor // REGISTER_WIDTH) * REGISTER_WIDTH, minor)):
            startup.set_gauge(name, value)
        startup.set_gauge(CHANNEL_GAUGE,
                          list(channel_widths(num_workers, side)))
        startup.set_gauge(ENDPOINT_GAUGE, endpoint_onehot_elems(
            lanes, args[0].shape[1] // side, n_blocks, side))
        startup.set_gauge(ONEHOT_GAUGE, channel_onehot_elems(
            lanes, n_blocks, side, num_workers))
        out = tuple(x.reshape((axis_size, -1) + x.shape[1:])
                    for x in run(*args))
        return out, (True,) * len(out)

    return run


def jax_lookahead(op_remaining, op_valid, op_worker, op_score, num_parents,
                  dep_remaining, dep_valid, dep_mutual, dep_is_flow, dep_score,
                  blocks: DepBlocks, *, num_workers: int, skip=None):
    """One-training-step lookahead of a job laid out by block; returns
    (t, comm_oh, comp_oh, busy, ok, trips).

    Op state is [N] and dep state [E] in the partitioner's (block, i, j)
    layout, and ``blocks`` (:class:`DepBlocks`) says where each block's
    deps start and end: the tick body reaches a dep's endpoints and
    channel by broadcast and reduction over that layout, never through
    an index per dep. Under ``vmap`` (any nest of them) the lanes run as
    ONE loop whose state is lane-packed while the lanes are few
    (:func:`_lane_batched_lookahead`), and the unbatched call is that
    loop at one lane.

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
    (sim/jax_memo.py): a batched loop runs while ANY lane's cond holds,
    select-freezing finished lanes, so seeding memo-HIT lanes with
    ``skip=True`` makes it run exactly to the max trip count over MISS
    lanes (zero when every lane hit). Miss lanes iterate under their own
    cond regardless of neighbours, so their results stay bit-identical
    to an unbatched run. ``None`` (the default) traces the historical
    unmasked cond byte-for-byte.
    """
    import jax

    # the whole call carries one name, so what runs AROUND the tick
    # loops (the move by server, the stage gathers, the endpoint
    # matrices, the scatter back — and, under ``vmap``, the fold into
    # lanes, which is bound under the call's name stack) can be read
    # apart from the loops, which keep ``SIM_LOOKAHEAD`` inside it
    with jax.named_scope(scopes.SIM_LOOKAHEAD_CALL):
        one_lane = jax.tree_util.tree_map(
            lambda x: x[None],
            (op_remaining, op_valid, op_worker, op_score, num_parents,
             dep_remaining, dep_valid, dep_mutual, dep_is_flow, dep_score,
             blocks, skip))
        return tuple(
            x[0] for x in _lane_batched_lookahead(num_workers)(*one_lane))
