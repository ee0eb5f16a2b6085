"""The INDEXED placement scan, kept as the bitwise reference of
`ddls_tpu/sim/jax_env.py:jax_allocate_job` (test code only): the
package's scan as it stood before PR 35, verbatim — anchor masks
rebuilt every step from one `free[ci, cj, ck]` gather a cell of every
block shape, `mem[servers]` gathered once a parent, the memory commit a
scatter-add and the op -> server map two scatters of ``max_split``
indices a step into an ``[N + 1]`` carry. The package reaches the same
servers, cells and op slots by comparison and reduction over static
tables (`ShapeTables.member` / `.servers_of`, the tables' ``op_fwd``);
tests/test_jax_placer.py holds the two equal with ``array_equal`` on
every output, failing placements included, and pins what
`utils/jaxprs.py:indexed_ops` counts in each."""
from __future__ import annotations

import numpy as np

from ddls_tpu.sim.jax_env import ConfigPads, ShapeTables


def _anchor_masks(free_flat, st: ShapeTables):
    """[n_shapes, n_cells] anchor-validity masks for EVERY distinct shape
    given the flat free-server grid (True = free of other jobs AND enough
    memory — block_ok's conjunction, agents/block_search.py:84-101).

    Shapes and cell counts are static, so the per-cell gathers unroll at
    trace time into pure vector ops on the [C, R, S] grid. Diagonal
    anchors gather through the (dim+1) modulo with explicit in-ramp
    masking (enumerate_block's S == -1 layout)."""
    import jax.numpy as jnp

    C, R, S = st.ramp_shape
    free = free_flat.reshape(C, R, S)
    ii, jj, kk = np.meshgrid(np.arange(C), np.arange(R), np.arange(S),
                             indexing="ij")
    masks = []
    for si in range(len(st.shapes)):
        cnt = int(st.counts[si])
        span = st.spans[si]
        base = st.bases[si]
        ok = jnp.ones((C, R, S), bool)
        for t in range(cnt):
            off = st.offsets[si, t]
            ci = (ii + int(off[0])) % int(base[0])
            cj = (jj + int(off[1])) % int(base[1])
            ck = (kk + int(off[2])) % int(base[2])
            in_ramp = (ci < C) & (cj < R) & (ck < S)
            cell_free = free[np.clip(ci, 0, C - 1),
                             np.clip(cj, 0, R - 1),
                             np.clip(ck, 0, S - 1)]
            ok = ok & jnp.asarray(in_ramp) & cell_free
        # origin span: the host scans diagonal origins k over
        # meta[2] + 2 values, but k and k - S alias the same block, so
        # the k < S anchors cover every class in the same first-fit order
        in_span = jnp.asarray((ii < int(span[0])) & (jj < int(span[1]))
                              & (kk < min(int(span[2]), S)))
        masks.append((ok & in_span).reshape(-1))
    return jnp.stack(masks)


def _first_fit_from_masks(masks, shape_row):
    """First-fit over a (traced) per-split shape-order row: returns
    (shape_id, origin_rank, found) — the first shape in row order with any
    valid anchor, and its smallest lexicographic anchor, exactly
    `first_fit_block`'s (shape order, then origin lex order) semantics."""
    import jax.numpy as jnp

    n_cells = masks.shape[1]
    big = jnp.int32(n_cells + 1)
    lex = jnp.arange(n_cells, dtype=jnp.int32)

    best_shape = jnp.int32(-1)
    best_rank = big
    found = jnp.bool_(False)
    for p in range(shape_row.shape[0]):
        sid = shape_row[p]
        mask = masks[jnp.clip(sid, 0)] & (sid >= 0)
        any_valid = mask.any()
        rank = jnp.where(mask, lex, big).min()
        take = any_valid & ~found
        best_shape = jnp.where(take, sid, best_shape)
        best_rank = jnp.where(take, rank, best_rank)
        found = found | any_valid
    return best_shape, best_rank, found


def jax_allocate_job(mem, other_free, cfg, tables, st: ShapeTables,
                     pads: ConfigPads):
    """Scan-ified `allocate_job` (agents/placers.py:103; reference
    placers/utils.py:532): walk the padded forward-op sequence in topo
    order; per op try parent co-location then the generic first-fit block
    search; scatter memory + op->server assignments between steps.

    ``mem`` [n_srv] free memory per server; ``other_free`` [n_srv] bool
    (True = not occupied by another job; constant during one job's
    allocation); ``cfg`` the traced (model, degree) config row. Returns
    (op_to_server [N] i32, -1 where unplaced, new_mem [n_srv], ok bool).
    On ok=False outputs are partial and must be discarded by the caller
    (the host returns None and the composite action drops the job)."""
    import jax
    import jax.numpy as jnp

    C, R, S = st.ramp_shape
    Smax = pads.max_split
    F, N = pads.n_fwd, pads.n_ops

    row_table = jnp.asarray(st.row)
    offsets_t = jnp.asarray(st.offsets)
    bases_t = jnp.asarray(st.bases)

    f_valid = tables["f_valid"][cfg]
    f_split = tables["f_split"][cfg]
    f_mem = tables["f_mem"][cfg]
    f_parents = tables["f_parents"][cfg]
    f_sub_fwd = tables["f_sub_fwd"][cfg]
    f_sub_bwd = tables["f_sub_bwd"][cfg]

    lane = jnp.arange(Smax)

    def body(carry, f):
        (mem, op_servers, op_count, ots, ok) = carry
        valid = f_valid[f]
        split = f_split[f]
        per_mem = f_mem[f]
        parents = f_parents[f]
        sub_fwd = f_sub_fwd[f]
        sub_bwd = f_sub_bwd[f]

        # ---- parent co-location (placers.py:49-77): first parent whose
        # server count equals split and whose servers all have room
        colo_found = jnp.bool_(False)
        colo_servers = jnp.full((Smax,), -1, jnp.int32)
        for pi in range(parents.shape[0]):
            p = parents[pi]
            servers = op_servers[jnp.clip(p, 0)]
            cnt = op_count[jnp.clip(p, 0)]
            active = lane < cnt
            mem_ok = jnp.all(~active
                             | (mem[jnp.clip(servers, 0)] >= per_mem))
            okp = (p >= 0) & (cnt > 0) & (cnt == split) & mem_ok
            take = okp & ~colo_found
            colo_servers = jnp.where(take, servers, colo_servers)
            colo_found = colo_found | okp

        # ---- regular symmetric block search (find_sub_block order)
        free = other_free & (mem >= per_mem)
        masks = _anchor_masks(free, st)
        shape_row = row_table[jnp.clip(split, 0, row_table.shape[0] - 1)]
        sid, rank, block_found = _first_fit_from_masks(masks, shape_row)

        origin = jnp.stack([rank // (R * S), (rank // S) % R,
                            rank % S]).astype(jnp.int32)
        offs = offsets_t[jnp.clip(sid, 0)]              # [MAX_CELLS, 3]
        base = bases_t[jnp.clip(sid, 0)]                # [3]
        cells = (origin[None, :] + offs) % base[None, :]
        block_servers = ((cells[:, 0] * R + cells[:, 1]) * S
                         + cells[:, 2]).astype(jnp.int32)
        if block_servers.shape[0] < Smax:
            block_servers = jnp.pad(block_servers,
                                    (0, Smax - block_servers.shape[0]))
        else:
            block_servers = block_servers[:Smax]

        servers = jnp.where(colo_found, colo_servers, block_servers)
        placed_ok = colo_found | block_found

        # ---- masked commit of this op's fwd+bwd sub-op pairs. Inactive
        # lanes scatter into a trailing dummy slot so they can never
        # collide with a real index.
        active = (lane < split) & placed_ok & valid & (servers >= 0)
        srv = jnp.clip(servers, 0)
        mem = mem - jnp.zeros_like(mem).at[srv].add(
            jnp.where(active, per_mem, jnp.zeros_like(per_mem)))
        idx_f = jnp.where(active & (sub_fwd >= 0), sub_fwd, N)
        idx_b = jnp.where(active & (sub_bwd >= 0), sub_bwd, N)
        ots = ots.at[idx_f].set(servers)
        ots = ots.at[idx_b].set(servers)

        write = valid & placed_ok
        op_servers = jnp.where(write, op_servers.at[f].set(servers),
                               op_servers)
        op_count = jnp.where(write, op_count.at[f].set(split), op_count)
        return ((mem, op_servers, op_count, ots,
                 ok & (placed_ok | ~valid)), None)

    init = (mem,
            jnp.full((F, Smax), -1, jnp.int32),
            jnp.zeros((F,), jnp.int32),
            jnp.full((N + 1,), -1, jnp.int32),   # +1 dummy scatter slot
            jnp.bool_(True))
    carry, _ = jax.lax.scan(body, init, jnp.arange(F, dtype=jnp.int32))
    (new_mem, _, _, ots, ok) = carry
    return ots[:N], new_mem, ok
