"""XLA-native graph primitives: masked reductions over padded edge lists,
used by the GNN.

The reference delegates message passing to DGL's C++ scatter/gather kernels
(ddls/ml_models/models/mean_pool.py). Here the fixed shapes make the whole
policy batchable (no per-sample graph construction, the reference's known
perf sink, ddls/ml_models/policies/gnn_policy.py:226-253), and the
aggregation has two lowerings of one sum (``segment.edge_aggregator``):
``jax.ops.segment_sum`` — a scatter-add, serial in the edges on a TPU — and
contractions with per-graph 0/1 incidence matrices, which run on the MXU.
"""
from ddls_tpu.ops.segment import (aggregate_form, edge_aggregator,
                                  masked_mean, masked_segment_mean,
                                  masked_segment_sum)

__all__ = ["masked_segment_sum", "masked_segment_mean", "masked_mean",
           "aggregate_form", "edge_aggregator"]
