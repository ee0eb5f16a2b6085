"""Operations and HBM bytes one PPO update of the GNN policy needs.

One update = ``num_sgd_iter`` passes over the epoch's batch in
minibatches of ``sgd_minibatch_size``; each minibatch step is one
forward and one backward through ``GNNPolicy.flat_batched`` on padded
graphs of ``max_nodes`` nodes and ``max_edges`` edges.

FLOPs: the Dense layers only (2*m*k*n each), times 3 for forward +
backward (input and weight gradients). LayerNorm, activations, the
segment mean and Adam are left out: they are O(rows * width), a few
per cent of the matmuls. Cross-check: XLA's ``cost_analysis`` of the
compiled update counts the scanned minibatch body ONCE and reported
1.88e9 FLOPs on the v5e (PERF.md, PR 21 lead); this function gives
1.455e9 per minibatch step for the shipped shapes, i.e. 0.77 of it —
the rest is the elementwise work left out here.

Bytes: what must cross HBM if every activation the backward needs is
written once and read once (minibatch activations exceed the chip's
on-chip memory), plus the minibatch's inputs read once and parameters,
gradients and Adam moments touched once per step. All float32.
``cost_analysis``'s "bytes accessed" (5.2e9 per body) counts every
operand of every fusion, on-chip reuse included, and is not used.
"""
from __future__ import annotations


def _dense(rows: int, k: int, n: int) -> int:
    return 2 * rows * k * n


def per_sample(model: dict, pads: dict):
    """(forward Dense FLOPs, saved activation floats, input floats,
    parameter count) for one padded graph."""
    n, e = pads["max_nodes"], pads["max_edges"]
    half = model["out_features_msg"] // 2
    msg = model["out_features_msg"]
    dims = ([model["out_features_hidden"]] * (model["num_rounds"] - 1)
            + [model["out_features_node"]])
    flops = acts = params = 0
    f_in = model["in_features_node"]
    f_edge = model["in_features_edge"]
    for dim in dims:
        flops += _dense(n, f_in, half) + _dense(e, f_edge, half)
        flops += _dense(e + n, msg, dim)
        # saved for the backward: LayerNorm outputs (the Dense inputs),
        # node/edge intermediates, gathered messages, reduce output
        acts += n * f_in + e * f_edge            # LN(node), LN(edge)
        acts += n * half + e * half              # node_int, edge_int
        acts += e * msg                          # gathered messages
        acts += (e + n) * msg                    # LN(messages, self)
        acts += (e + n) * dim                    # reduce output
        params += (f_in + 1) * half + (f_edge + 1) * half
        params += (msg + 1) * dim + 2 * (f_in + f_edge + msg)
        f_in = dim
    g_in, g_out = model["graph_features"], model["out_features_graph"]
    flops += _dense(1, g_in, g_out)
    params += (g_in + 1) * g_out + 2 * g_in
    emb = dims[-1] + g_out
    for out in (pads["n_actions"], 1):          # logit head, value head
        width = emb
        for hidden in model["fcnet_hiddens"]:
            flops += _dense(1, width, hidden)
            params += (width + 1) * hidden
            width = hidden
        flops += _dense(1, width, out)
        params += (width + 1) * out
    inputs = (n * model["in_features_node"] + e * model["in_features_edge"]
              + 2 * e + g_in + pads["n_actions"] + 8)
    return flops, acts, inputs, params


def flops_and_bytes(cell):
    config, traffic = cell.config, cell.traffic
    fwd, acts, inputs, params = per_sample(config["model"], config["pads"])
    batch = traffic["epoch"]["env_steps"]
    minibatch = config["ppo"]["sgd_minibatch_size"]
    steps = config["ppo"]["num_sgd_iter"] * max(batch // minibatch, 1)
    flops = 3 * fwd * minibatch * steps
    per_step = (minibatch * (inputs + 2 * acts)   # read in, save + reload
                + params * (3 + 2 * 2))           # w r/w, grad, 2 moments r/w
    return float(flops), float(4 * per_step * steps)
