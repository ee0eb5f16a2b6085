"""The FLAT lookahead, kept as the bitwise reference of
`ddls_tpu/sim/jax_lookahead.py:jax_lookahead` (test code only): the
three dep primitives as the package had them up to PR 41 (commit
85b91ea, `_flat_dep_ops`, verbatim) — one gather or scatter per dep
through ``dep_src`` / ``dep_dst`` / ``dep_channel`` over flat [N] / [E]
state, for ANY graph — under the package's own tick body and one-job
layout (`_tick_loop`, `_job_layout`: imported, not copied), so the two
differ only in how a dep reaches its endpoints and its channel.
tests/test_jax_lookahead.py holds the block and lane-packed forms to it
with ``==`` on all six outputs, and holds IT to the host engine on
mounted jobs (`padded_args` of the C++ engine's packing)."""
import numpy as np

from ddls_tpu.sim.jax_lookahead import _job_layout, _tick_loop


def flat_dep_ops(dep_src, dep_dst, dep_channel, num_channels):
    """The tick body's three dep primitives for an ARBITRARY graph: one
    gather or scatter per dep."""
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


def flat_lookahead(op_remaining, op_valid, op_worker, op_score, num_parents,
                   dep_remaining, dep_valid, dep_src, dep_dst, dep_mutual,
                   dep_is_flow, dep_score, dep_channel,
                   *, num_workers: int, num_channels: int, skip=None):
    """`jax_lookahead`'s six results, (t, comm_oh, comp_oh, busy, ok,
    trips), for one job given by per-dep index: ``dep_src`` / ``dep_dst``
    [E] op slots and ``dep_channel`` [E, L] channel ids (-1: none).
    ``skip`` as there."""
    N, E = op_remaining.shape[0], dep_remaining.shape[0]
    return _tick_loop(
        _job_layout(op_worker, num_workers, flat_dep_ops(
            dep_src, dep_dst, dep_channel, num_channels)),
        op_remaining, op_valid, op_score, num_parents, dep_remaining,
        dep_valid, dep_mutual, dep_is_flow, dep_score, skip, N + E + 4)[0]


def block_arguments(args):
    """`flat_lookahead`'s thirteen positional arguments as
    `jax_lookahead` takes them before ``blocks``: without the per-dep
    endpoints and channel."""
    return (*args[:7], *args[9:12])


def padded_args(arrays, pad_ops: int, pad_deps: int, pad_links: int,
                dtype=np.float32):
    """`flat_lookahead`'s positional arguments from the C++ engine's
    exact-size f64 packing (`native/arrays.py:LookaheadArrays`), padded
    to static sizes with the masks cleared on the pad and the floats
    cast to ``dtype``."""
    n, m = arrays.op_remaining.shape[0], arrays.dep_remaining.shape[0]
    links = arrays.dep_channel.shape[1]
    if n > pad_ops or m > pad_deps or links > pad_links:
        raise ValueError(f"job needs ({n}, {m}, {links}) > padding "
                         f"({pad_ops}, {pad_deps}, {pad_links})")

    def padded(x, size, dt, fill=0):
        out = np.full((size,) + x.shape[1:], fill, dt)
        out[:x.shape[0]] = x
        return out

    channel = np.full((pad_deps, pad_links), -1, np.int32)
    channel[:m, :links] = arrays.dep_channel
    return (padded(arrays.op_remaining, pad_ops, dtype),
            padded(arrays.op_valid, pad_ops, bool),
            padded(arrays.op_worker, pad_ops, np.int32, -1),
            padded(arrays.op_score, pad_ops, dtype),
            padded(arrays.num_parents, pad_ops, np.int32),
            padded(arrays.dep_remaining, pad_deps, dtype),
            padded(arrays.dep_valid, pad_deps, bool),
            padded(arrays.dep_src, pad_deps, np.int32),
            padded(arrays.dep_dst, pad_deps, np.int32),
            padded(arrays.dep_mutual, pad_deps, bool),
            padded(arrays.dep_is_flow, pad_deps, bool),
            padded(arrays.dep_score, pad_deps, dtype),
            channel)
