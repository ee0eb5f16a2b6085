"""GNN policy: per-node embeddings -> pooled graph embedding -> masked
action logits + value.

Parity with the reference RLlib policy (ddls/ml_models/policies/
gnn_policy.py:53): node embeddings from the GNN are masked-mean-pooled; the
graph features (which already include the action mask, obs.py) are embedded
by a LayerNorm MLP; both embeddings are concatenated and read out by an MLP
into action logits and, via a separate branch, a state-value estimate
(RLlib's FullyConnectedNetwork with vf_share_layers=False). Invalid actions
get log(0)-masked logits so they can never be sampled
(gnn_policy.py:265-271).

The forward is written for a single observation; ``batched_policy_apply``
runs a batch with every parameterised op on FLATTENED rows (all samples'
nodes ``[B*N, F]``, all samples' edges ``[B*E, F]``) — this replaces the
reference's Python loop building one DGL graph per batch element
(gnn_policy.py:226-253). The flattening matters for speed, not just
elegance: every LayerNorm/Dense in the model is row-wise, and XLA's backward
for Dense on rank-3 ``[B, N, F]`` inputs (what ``vmap`` produces) lowers the
dW reduction ~6x slower on CPU than the ``[B*N, F]`` matmul, which computes
the same sums. Between the row-wise ops sit a round's two indexed
operations (``ops/segment.py``): on a CPU an index gather and a segment sum
over the batch as one "mega-graph" (edge indices offset by ``sample *
n_nodes`` — DGL's own ``dgl.batch`` trick); on a TPU, while the pad is under
``DENSE_MAX_CELLS``, contractions with per-graph 0/1 incidence matrices
``[B, N, E]``, which put the aggregation on the MXU (a serial scatter of B*E
rows was 75-90 % of a minibatch step on the v5e: PERF.md §6, PR 31). One
sum in two lowerings, chosen from the platform and the static pad alone.
Outputs match ``vmap``-ing the single-sample ``__call__`` to
f32-reassociation tolerance under either form — XLA may tile the row-wise
matmuls differently per shape (tests/test_models.py pins this).
"""
from __future__ import annotations

from typing import Dict, Sequence, Tuple

import flax.linen as nn
import jax
import jax.numpy as jnp

from ddls_tpu.models.gnn import GNN, FeatureModule, get_activation
from ddls_tpu.ops.segment import masked_mean
from ddls_tpu.utils.jaxprs import indexed_ops


class MLPHead(nn.Module):
    """Plain Dense stack used for the logit and value readouts (the
    reference uses RLlib's FullyConnectedNetwork here)."""

    hiddens: Sequence[int]
    out_features: int
    activation: str = "relu"

    @nn.compact
    def __call__(self, x: jnp.ndarray) -> jnp.ndarray:
        act = get_activation(self.activation)
        for h in self.hiddens:
            x = act(nn.Dense(h)(x))
        return nn.Dense(self.out_features)(x)


class GNNPolicy(nn.Module):
    """Actor-critic over one padded-graph observation.

    Returns (logits [n_actions], value []). Defaults follow the tuned
    reference config (scripts/ramp_job_partitioning_configs/model/gnn.yaml).
    """

    n_actions: int
    out_features_msg: int = 32
    out_features_hidden: int = 64
    out_features_node: int = 16
    out_features_graph: int = 8
    num_rounds: int = 2
    module_depth: int = 1
    activation: str = "relu"
    fcnet_hiddens: Sequence[int] = (256, 256)
    fcnet_activation: str = "relu"
    apply_action_mask: bool = True

    def setup(self):
        # attribute names fix the param-tree paths; they match what the
        # original nn.compact version produced, so existing checkpoints
        # restore unchanged
        self.gnn = GNN(self.out_features_msg, self.out_features_hidden,
                       self.out_features_node, self.num_rounds,
                       self.module_depth, self.activation)
        self.graph_module = FeatureModule(self.out_features_graph,
                                          self.module_depth, self.activation)
        self.logit_head = MLPHead(self.fcnet_hiddens, self.n_actions,
                                  self.fcnet_activation)
        self.value_head = MLPHead(self.fcnet_hiddens, 1,
                                  self.fcnet_activation)

    def _mask_logits(self, logits, action_mask):
        if not self.apply_action_mask:
            return logits
        inf_mask = jnp.maximum(jnp.log(action_mask.astype(jnp.float32)),
                               jnp.finfo(jnp.float32).min)
        return logits + inf_mask

    def __call__(self, obs: Dict[str, jnp.ndarray]
                 ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        node_feats = obs["node_features"]
        edge_feats = obs["edge_features"]
        n_nodes = obs["node_split"][0]
        n_edges = obs["edge_split"][0]
        node_mask = (jnp.arange(node_feats.shape[0]) < n_nodes)
        edge_mask = (jnp.arange(edge_feats.shape[0]) < n_edges)

        node_emb = self.gnn(node_feats, edge_feats, obs["edges_src"],
                            obs["edges_dst"], node_mask, edge_mask)
        pooled = masked_mean(node_emb, node_mask)

        graph_emb = self.graph_module(obs["graph_features"])
        final_emb = jnp.concatenate([pooled, graph_emb], axis=-1)
        logits = self.logit_head(final_emb)
        value = self.value_head(final_emb)[0]
        return self._mask_logits(logits, obs["action_mask"]), value

    def flat_batched(self, obs: Dict[str, jnp.ndarray]
                     ) -> Tuple[jnp.ndarray, jnp.ndarray]:
        """Batch of B observations with the GNN's row-wise ops on
        flattened rows (``models/gnn.py`` flattens ``[B, N, F]`` /
        ``[B, E, F]`` to rank 2 and reshapes only around the two
        indexed operations of a round). Every parameterised op
        (LayerNorm/Dense) is row-wise and a node's mailbox is summed
        per graph, so this computes the same sums as
        ``vmap(__call__)`` (equal to f32 reassociation; XLA may tile
        matmuls differently per shape) — while the Dense backward runs
        on rank-2 inputs, the layout XLA CPU handles ~6x faster than
        the vmapped rank-3 one. The batch axis stays leading through
        the aggregation (index form: one flattened graph; dense form:
        ``[B, N, E]`` incidences, never ``[B*N, B*E]``), so a
        dp-sharded batch stays sharded.
        """
        nf = obs["node_features"]
        ef = obs["edge_features"]
        N, E = nf.shape[1], ef.shape[1]
        n_nodes = obs["node_split"][:, 0]
        n_edges = obs["edge_split"][:, 0]
        node_mask = jnp.arange(N) < n_nodes[:, None]   # [B, N]
        edge_mask = jnp.arange(E) < n_edges[:, None]   # [B, E]

        node_emb = self.gnn(nf, ef, obs["edges_src"], obs["edges_dst"],
                            node_mask, edge_mask)
        pooled = jax.vmap(masked_mean)(node_emb, node_mask)

        graph_emb = self.graph_module(obs["graph_features"])
        final_emb = jnp.concatenate([pooled, graph_emb], axis=-1)
        logits = self.logit_head(final_emb)
        value = self.value_head(final_emb)[:, 0]
        return self._mask_logits(logits, obs["action_mask"]), value


def batched_policy_apply(model: GNNPolicy, params,
                         obs: Dict[str, jnp.ndarray]):
    """Apply the policy over a batch: dict of [B, ...] arrays ->
    (logits [B, n_actions], values [B]). Runs the flattened-rows
    forward (see ``GNNPolicy.flat_batched``)."""
    return model.apply(params, obs, method=GNNPolicy.flat_batched)


def vmapped_policy_apply(model: GNNPolicy, params,
                         obs: Dict[str, jnp.ndarray]):
    """Reference implementation: vmap the single-sample forward. Slower
    backward on CPU (rank-3 Dense dW); kept as the parity oracle for
    ``batched_policy_apply`` (tests/test_models.py)."""
    return jax.vmap(lambda o: model.apply(params, o))(obs)


#: start-up gauges (`aggregate_gauges`), in this order
AGGREGATE_GAUGES = ("gnn.aggregate.indexed_ops",
                    "gnn.aggregate.incidence_elems")


def aggregate_gauges(apply_fn, params, obs) -> Tuple[int, int]:
    """What the GNN's aggregation is in the update, for one minibatch
    ``obs`` of B padded observations (arrays or ``ShapeDtypeStruct``s):
    the gather / scatter equations of >= B·E indices in the traced
    forward + backward of ``apply_fn`` (0 = every aggregation is a
    contraction; 6 scatter-adds + 4 gathers in the index form), and
    B·N·E, the elements of one incidence matrix of the batch. Abstract
    trace: nothing compiles, nothing runs."""
    n_graphs, n_nodes = obs["node_features"].shape[:2]
    n_edges = obs["edge_features"].shape[1]

    def loss(p, o):
        logits, values = apply_fn(p, o)
        return jnp.sum(logits) + jnp.sum(values)

    traced = jax.make_jaxpr(jax.grad(loss))(params, obs)
    return (len(indexed_ops(traced.jaxpr, n_graphs * n_edges)),
            n_graphs * n_nodes * n_edges)
