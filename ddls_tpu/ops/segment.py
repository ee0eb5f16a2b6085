"""Masked reductions over padded edge lists, in two lowerings.

All functions take fixed-shape (padded) arrays plus boolean masks so they are
safe under ``jit``/``vmap``/``pjit`` — padding rows contribute nothing, and
output shapes are static. Padding edges should point at segment 0; the mask
is what removes their contribution, so the index values of padded entries
never matter.

The GNN's message passing — read each edge's source row, sum each node's
mailbox — is ONE algorithm with two lowerings (``edge_aggregator``):

* ``segment``: an index gather and ``jax.ops.segment_sum`` (a scatter-add).
  B·E serial address computations a batch whatever the pad holds; the
  CPU's form, and the parity oracle.
* ``dense``: contractions with per-graph 0/1 incidence matrices
  ``[N, E]``, built from the integer edge lists once a forward. B·N·E·F
  multiply-adds on the TPU's MXU (XLA fuses the compare into the dot's
  operand, so no matrix is stored): 10x faster than the serial scatter
  at 128 x 150 x 512, 5x at 32 x 300 x 512 (v5e; PERF.md §6, PR 31).

They compute the same f32 sums, equal to reassociation: the incidence is
exact in any float type and the contraction runs at
``Precision.HIGHEST`` (a default-precision TPU dot would round every
message to bf16 — a different result). ``aggregate_form`` chooses from
the platform and the static pad alone; nothing configures it.
"""
from __future__ import annotations

from typing import Callable, NamedTuple

import jax
import jax.numpy as jnp

#: cells of one graph's incidence matrix (padded nodes x padded edge
#: slots) up to which the dense form is chosen on a TPU. The dense form
#: costs N·E a graph, the segment form E, so they cross as N grows.
#: Measured on the v5e (PERF.md §6, PR 31; one aggregate, forward +
#: backward, inside a scan): dense is 2.2-3.4x faster at 2.46 M cells
#: and 1.4-1.9x at 4.92 M (both shapes tried); at 9.83 M the policy's
#: whole SGD step wins one shape of three — the bound is the power of
#: two under the last pad at which dense always won. The benchmark's
#: cells hold 76,800-153,600.
DENSE_MAX_CELLS = 1 << 22


def aggregate_form(platform: str, n_nodes: int, n_edges: int) -> str:
    """``"dense"`` or ``"segment"``: how a graph padded to ``n_nodes`` x
    ``n_edges`` aggregates its messages on ``platform``. A pure function
    of what a trace can observe — the rule, whole."""
    if platform == "tpu" and n_nodes * n_edges <= DENSE_MAX_CELLS:
        return "dense"
    return "segment"


def masked_segment_sum(data: jnp.ndarray,
                       segment_ids: jnp.ndarray,
                       mask: jnp.ndarray,
                       num_segments: int) -> jnp.ndarray:
    """Sum ``data[e]`` into ``out[segment_ids[e]]`` for unmasked edges.

    Args:
      data: [E, F] per-edge values.
      segment_ids: [E] int destination per edge (padding may be 0).
      mask: [E] bool, True for real edges.
      num_segments: static number of output segments (padded node count).

    Returns: [num_segments, F].
    """
    data = jnp.where(mask[:, None], data, 0.0)
    return jax.ops.segment_sum(data, segment_ids, num_segments=num_segments)


def masked_segment_mean(data: jnp.ndarray,
                        segment_ids: jnp.ndarray,
                        mask: jnp.ndarray,
                        num_segments: int,
                        extra: jnp.ndarray = None) -> jnp.ndarray:
    """Mean of incoming edge values per segment, optionally averaged together
    with one ``extra`` [num_segments, F] value per segment (the GNN's
    self-message: mean over {self} ∪ mailbox).

    Segments with no incoming edges (and no extra) return 0.
    """
    totals = masked_segment_sum(data, segment_ids, mask, num_segments)
    counts = jax.ops.segment_sum(mask.astype(data.dtype), segment_ids,
                                 num_segments=num_segments)
    return _mean(totals, counts, extra)


def _mean(totals, counts, extra):
    if extra is not None:
        totals = totals + extra
        counts = counts + 1.0
    return totals / jnp.maximum(counts, 1.0)[:, None]


class EdgeAggregator(NamedTuple):
    """The two indexed operations of a message-passing round over padded
    graph(s) ``[..., E]`` — any leading batch axes, one graph each. Rows
    in, rows out: the graphs' nodes and edges arrive flattened
    (``[G·N, F]``, ``[G·E, F]``), the layout the row-wise modules around
    them run on.

    ``gather_src(x)``: node rows -> edge rows, each edge's source row
    (a padded edge reads some row or zeros; ``mean_to_dst`` drops it).
    ``mean_to_dst(data, extra=None)``: edge rows -> node rows, each
    graph's ``masked_segment_mean``."""
    gather_src: Callable[[jnp.ndarray], jnp.ndarray]
    mean_to_dst: Callable[..., jnp.ndarray]


def segment_aggregator(edges_src: jnp.ndarray, edges_dst: jnp.ndarray,
                       edge_mask: jnp.ndarray, n_nodes: int
                       ) -> EdgeAggregator:
    """Index form. Batched graphs run as ONE flattened graph of G·N
    nodes and G·E edges (indices offset by ``graph * n_nodes``, DGL's
    ``dgl.batch`` trick): the segment sum adds each node's mailbox in
    the same edge order as the unbatched call."""
    n_graphs = 1
    if edges_src.ndim > 1:
        n_edges = edges_src.shape[-1]
        n_graphs = edges_src.size // n_edges
        offsets = (jnp.arange(n_graphs, dtype=edges_src.dtype)
                   * n_nodes)[:, None]
        edges_src = (edges_src.reshape(n_graphs, n_edges)
                     + offsets).reshape(-1)
        edges_dst = (edges_dst.reshape(n_graphs, n_edges)
                     + offsets).reshape(-1)
        edge_mask = edge_mask.reshape(-1)

    def gather_src(x):
        return x[edges_src]

    def mean_to_dst(data, extra=None):
        return masked_segment_mean(data, edges_dst, edge_mask,
                                   n_graphs * n_nodes, extra)

    return EdgeAggregator(gather_src, mean_to_dst)


def dense_aggregator(edges_src: jnp.ndarray, edges_dst: jnp.ndarray,
                     edge_mask: jnp.ndarray, n_nodes: int
                     ) -> EdgeAggregator:
    """Contraction form: per graph, ``S[n, e] = mask[e] & (src[e] == n)``
    and ``D[n, e] = mask[e] & (dst[e] == n)``; the gather is ``S^T x``,
    the mailbox sum ``D data``, the in-degree ``D``'s row sums. The
    matrices come from integers, so no gradient flows through them and
    the backward is the two transposed contractions. Per graph always:
    the rows are reshaped to ``[..., N | E, F]`` around a contraction
    with ``[..., N, E]``, never a ``[G·N, G·E]`` incidence."""
    lead, n_edges = edges_src.shape[:-1], edges_src.shape[-1]
    nodes = jnp.arange(n_nodes, dtype=edges_src.dtype)[:, None]
    real = edge_mask[..., None, :]
    src_is = real & (edges_src[..., None, :] == nodes)       # [..., N, E]
    dst_is = real & (edges_dst[..., None, :] == nodes)
    in_degree = dst_is.sum(-1).reshape(-1)

    def contract(spec, incidence, rows, per_graph):
        x = rows.reshape(lead + (per_graph, rows.shape[-1]))
        out = jnp.einsum(spec, incidence.astype(x.dtype), x,
                         precision=jax.lax.Precision.HIGHEST)
        return out.reshape((-1, rows.shape[-1]))

    def gather_src(x):
        return contract("...ne,...nf->...ef", src_is, x, n_nodes)

    def mean_to_dst(data, extra=None):
        # as the segment form: a padded edge's value never enters the sum
        data = jnp.where(edge_mask.reshape(-1)[:, None], data, 0.0)
        totals = contract("...ne,...ef->...nf", dst_is, data, n_edges)
        return _mean(totals, in_degree.astype(data.dtype), extra)

    return EdgeAggregator(gather_src, mean_to_dst)


def edge_aggregator(edges_src: jnp.ndarray, edges_dst: jnp.ndarray,
                    edge_mask: jnp.ndarray, n_nodes: int) -> EdgeAggregator:
    """The form ``aggregate_form`` picks for these pads on the backend
    this process computes on."""
    form = aggregate_form(jax.default_backend(), n_nodes,
                          edges_src.shape[-1])
    build = dense_aggregator if form == "dense" else segment_aggregator
    return build(edges_src, edges_dst, edge_mask, n_nodes)


def masked_mean(data: jnp.ndarray, mask: jnp.ndarray) -> jnp.ndarray:
    """Mean over the unmasked rows of ``data`` [N, F]; 0 if all masked."""
    weights = mask.astype(data.dtype)
    total = jnp.sum(data * weights[:, None], axis=0)
    count = jnp.maximum(jnp.sum(weights), 1.0)
    return total / count
