"""Message-passing GNN over padded op graphs.

Architecture parity with the reference (ddls/ml_models/models/mean_pool.py,
gnn.py), tuned hyperparameters from
scripts/ramp_job_partitioning_configs/model/gnn.yaml:

* ``MeanPoolLayer``: node and edge features pass through small
  LayerNorm→Dense→act modules; the message on edge (u→v) is
  concat(node_module(h_u), edge_module(e_uv)); every node also forms a
  self-message concat(node_module(h_v), 0); each message is embedded by a
  reduce module and a node's new embedding is the mean of its embedded
  self-message and embedded incoming messages.
* ``GNN``: num_rounds >= 2 stacked layers (in -> hidden^(r-2) -> out), the
  original edge features re-used at every round.

All ops are fixed-shape w.r.t. the padded node/edge counts; padding is
removed by masks, so the module is jit/vmap/pjit-safe.

``GNN`` takes one graph or a batch (leading axes on every argument, one
graph each). The parameterised modules are row-wise and run on the rows
flattened to rank 2 (``[G·N, F]``, ``[G·E, F]``); only a round's two indexed
operations — read each edge's source row, average each node's mailbox — see
the graphs, through the ``ops.segment.EdgeAggregator`` the GNN builds once
and every round shares (index gather + segment sum, or per-graph incidence
contractions on the TPU's MXU: ``ops/segment.py``).
"""
from __future__ import annotations

from typing import Callable, Sequence

import flax.linen as nn
import jax.numpy as jnp

from ddls_tpu.ops.segment import EdgeAggregator, edge_aggregator

ACTIVATIONS = {
    "relu": nn.relu,
    "leaky_relu": nn.leaky_relu,
    "tanh": nn.tanh,
    "swish": nn.swish,
    "gelu": nn.gelu,
}


def get_activation(name: str) -> Callable:
    try:
        return ACTIVATIONS[name]
    except KeyError:
        raise ValueError(f"unrecognised activation {name!r}; "
                         f"choose from {sorted(ACTIVATIONS)}")


class FeatureModule(nn.Module):
    """LayerNorm -> Dense -> act, repeated ``depth`` times (the reference's
    node/edge/reduce module shape, mean_pool.py:55-97)."""

    features: int
    depth: int = 1
    activation: str = "relu"

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        act = get_activation(self.activation)
        x = nn.LayerNorm()(x)
        x = act(nn.Dense(self.features)(x))
        for _ in range(self.depth - 1):
            x = act(nn.Dense(self.features)(x))
        return x


class MeanPoolLayer(nn.Module):
    """One round of message passing + mean aggregation over node rows
    ``[G·N, F]`` and edge rows ``[G·E, F]`` (G = 1: a single sample)."""

    out_features_msg: int
    out_features_reduce: int
    module_depth: int = 1
    activation: str = "relu"

    @nn.compact
    def __call__(self,
                 node_feats: jnp.ndarray,
                 edge_feats: jnp.ndarray,
                 edges: EdgeAggregator,
                 node_mask: jnp.ndarray) -> jnp.ndarray:
        half = self.out_features_msg // 2
        node_int = FeatureModule(half, self.module_depth, self.activation,
                                 name="node_module")(node_feats)
        edge_int = FeatureModule(half, self.module_depth, self.activation,
                                 name="edge_module")(edge_feats)
        reduce_module = FeatureModule(self.out_features_reduce,
                                      self.module_depth, self.activation,
                                      name="reduce_module")

        # message along each edge + a zero-edge self-message per node
        messages = jnp.concatenate(
            [edges.gather_src(node_int), edge_int], axis=-1)
        self_state = jnp.concatenate(
            [node_int, jnp.zeros_like(node_int)], axis=-1)

        embedded_msgs = reduce_module(messages)
        embedded_self = reduce_module(self_state)
        out = edges.mean_to_dst(embedded_msgs, extra=embedded_self)
        return out * node_mask[:, None]


class GNN(nn.Module):
    """Stack of ``num_rounds`` MeanPool layers (reference gnn.py:40-81)."""

    out_features_msg: int = 32
    out_features_hidden: int = 64
    out_features_node: int = 16
    num_rounds: int = 2
    module_depth: int = 1
    activation: str = "relu"

    @nn.compact
    def __call__(self, node_feats, edge_feats, edges_src, edges_dst,
                 node_mask, edge_mask) -> jnp.ndarray:
        if self.num_rounds < 2:
            raise ValueError("num_rounds must be >= 2")
        dims: Sequence[int] = (
            [self.out_features_hidden] * (self.num_rounds - 1)
            + [self.out_features_node])
        edges = edge_aggregator(edges_src, edges_dst, edge_mask,
                                n_nodes=node_feats.shape[-2])
        # the parameterised modules are row-wise: rank-2 rows throughout
        h = node_feats.reshape((-1, node_feats.shape[-1]))
        edge_rows = edge_feats.reshape((-1, edge_feats.shape[-1]))
        node_rows_mask = node_mask.reshape(-1)
        for i, dim in enumerate(dims):
            h = MeanPoolLayer(self.out_features_msg, dim, self.module_depth,
                              self.activation, name=f"round_{i}")(
                h, edge_rows, edges, node_rows_mask)
        return h.reshape(node_feats.shape[:-1] + h.shape[-1:])
