"""Architecture config -> PipeDream ``.txt`` training-job profile.

A public ``config.json`` of a decoder-only transformer with routed
SwiGLU experts (the OLMoE family: MHA/GQA attention with q/k norms and
RoPE, a softmax top-k router, no shared expert), a sequence length and a
micro-batch in sequences become one forward-pass profile in the format
``graphs/readers.py:_parse_pipedream_txt`` reads, so reader -> mirror ->
``Job`` stays the one path every job takes.

Ops, per layer and in order (``LAYER_OPS``): input RMSNorm; QKV
projection (the q/k RMSNorms and RoPE folded in); attention core (causal
softmax(QK^T)V, flash-style: the S x S scores are never written);
output projection + residual; post-attention RMSNorm; router
(hidden -> experts, softmax, top-k); expert group (all experts, SwiGLU,
k per token, balanced routing); combine + residual. Before them the
embedding, after them the final norm and the LM head (+ loss). Edges:
the chain, the two residual skips per layer, router -> combine (the
routing weights) and post-attention norm -> expert group.

Costs are ANALYTIC, not profiled (:func:`op_costs` is the whole model):

* ``forward_compute_time = max(FLOPs / peak, bytes / memory bandwidth)``
  in SECONDS, the unit ``sim/comm_model.py`` prices communication in,
  with the worker's constants taken from where the simulator keeps them
  (``hardware/devices.py:A100``'s ``peak_flops`` and
  ``memory_bandwidth``, also ``comm_model``'s defaults);
* ``backward_compute_time = 2 x forward``;
* ``activation_size`` = the op's output tensor at ``ACT_BYTES`` an
  element (bf16);
* ``parameter_size`` = parameters x ``PARAM_BYTES`` (training state:
  bf16 weight and gradient, fp32 master weight and two Adam moments).

FLOPs are 2 per multiply-accumulate plus the small elementwise terms
written beside each op below; causal attention counts half of S x S.
"""
from __future__ import annotations

import hashlib
import json
import os
from typing import Dict, List, Sequence, Tuple

from ddls_tpu.hardware.devices import A100

#: bytes of one activation element and of one weight element read (bf16)
ACT_BYTES = 2
#: bytes of one parameter in ``parameter_size``: the training state a
#: worker holds for it (bf16 weight 2 + bf16 gradient 2 + fp32 master 4 +
#: two fp32 Adam moments 8). The simulator has one ``memory_cost`` =
#: activation + parameter per op, which occupies the worker AND sizes
#: every dep of a split op (sim/partition.py:model_split), so this state
#: is also what a split op's collective moves (ROADMAP R1b)
PARAM_BYTES = 16
BACKWARD_OVER_FORWARD = 2.0

LAYER_OPS = ("InputNorm", "QKVProj", "AttnCore", "OutProjResidual",
             "PostAttnNorm", "Router", "Experts", "CombineResidual")

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def load_arch_config(path: str) -> dict:
    """The architecture file: ``{"source_url": ..., "config": {...}}``
    (the public ``config.json``'s shape keys). A relative path that does
    not exist from the working directory is taken from the checkout's
    root."""
    if not os.path.isabs(path) and not os.path.exists(path):
        path = os.path.join(_REPO, path)
    with open(path) as fh:
        body = json.load(fh)
    return body["config"]


def op_costs(config: dict, seq_len: int, micro_batch: int) -> List[dict]:
    """Forward ops in profile order, each ``{"op_type", "flops",
    "bytes", "out_elems", "params"}``: FLOPs and bytes moved of one
    forward pass over ``micro_batch`` sequences of ``seq_len`` tokens,
    elements of the output tensor, parameters held."""
    H = int(config["hidden_size"])
    heads = int(config["num_attention_heads"])
    kv_heads = int(config.get("num_key_value_heads") or heads)
    head_dim = int(config.get("head_dim") or H // heads)
    inter = int(config["intermediate_size"])
    E = int(config["num_experts"])
    k = int(config["num_experts_per_tok"])
    V = int(config["vocab_size"])
    L = int(config["num_hidden_layers"])
    S, B = int(seq_len), int(micro_batch)
    T = S * B                        # tokens of the step
    q, kv = heads * head_dim, kv_heads * head_dim
    qkv = q + 2 * kv
    A = ACT_BYTES

    def op(op_type, flops, nbytes, out_elems, params):
        return {"op_type": op_type, "flops": float(flops),
                "bytes": float(nbytes), "out_elems": float(out_elems),
                "params": float(params)}

    def norm(op_type):
        # square, mean, rsqrt-scale, weight: 4 per element
        return op(op_type, 4 * T * H, A * (2 * T * H + H), T * H, H)

    layer = [
        norm("InputNorm"),
        # x W_qkv; q/k RMSNorm (4 per element) and RoPE (3) on q and k
        op("QKVProj", 2 * T * H * qkv + 7 * T * (q + kv),
           A * (T * H + H * qkv + q + kv + T * qkv), T * qkv,
           H * qkv + q + kv),
        # QK^T and PV over the causal half of S x S, softmax 5 a score
        op("AttnCore", B * heads * S * S * (2 * head_dim + 2.5),
           A * (T * qkv + T * q), T * q, 0),
        op("OutProjResidual", 2 * T * q * H + T * H,
           A * (T * q + q * H + 2 * T * H), T * H, q * H),
        norm("PostAttnNorm"),
        # x W_r, softmax over E (5 a logit); out: k weights + k indices
        op("Router", 2 * T * H * E + 5 * T * E,
           A * (T * H + H * E + 2 * T * k), 2 * T * k, H * E),
        # gate, up, down for k experts a token, silu * up (4 a value);
        # balanced routing: every expert that has a token is read once
        op("Experts", 2 * T * k * 3 * H * inter + 4 * T * k * inter,
           A * (2 * T * k * H + min(E, T * k) * 3 * H * inter),
           T * k * H, E * 3 * H * inter),
        # weighted sum of k expert outputs + residual
        op("CombineResidual", 2 * T * k * H + T * H,
           A * (T * k * H + T * k + 2 * T * H), T * H, 0),
    ]
    assert tuple(o["op_type"] for o in layer) == LAYER_OPS
    ops = [op("Embedding", 0, A * 2 * T * H + 4 * T, T * H, V * H)]
    for _ in range(L):
        ops.extend(dict(o) for o in layer)
    ops.append(norm("FinalNorm"))
    # logits + softmax cross-entropy (5 a logit); output = the logits
    ops.append(op("LMHeadLoss", 2 * T * H * V + 5 * T * V,
                  A * (T * H + H * V + T * V), T * V, H * V))
    return ops


def forward_edges(num_layers: int) -> List[Tuple[int, int]]:
    """1-based (u, v) edges of the forward pass: the chain, then per
    layer the two residual skips, router -> combine and post-attention
    norm -> experts."""
    n_layer = len(LAYER_OPS)
    n = 1 + num_layers * n_layer + 2
    edges = [(i, i + 1) for i in range(1, n)]
    at = {name: i for i, name in enumerate(LAYER_OPS)}
    for layer in range(num_layers):
        first = 2 + layer * n_layer          # this layer's InputNorm
        stream_in = first - 1                # embedding / previous combine
        edges += [
            (stream_in, first + at["OutProjResidual"]),
            (first + at["OutProjResidual"], first + at["CombineResidual"]),
            (first + at["Router"], first + at["CombineResidual"]),
            (first + at["PostAttnNorm"], first + at["Experts"]),
        ]
    return edges


def forward_time(cost: dict) -> float:
    """Seconds of one forward op on the simulated worker: the larger of
    its compute and its memory roofline."""
    return max(cost["flops"] / A100.peak_flops,
               cost["bytes"] / A100.memory_bandwidth)


def profile_text(config: dict, seq_len: int, micro_batch: int) -> str:
    """The PipeDream ``.txt`` profile. Times carry 17 significant
    digits (a 17 us op must not round to 0.000017)."""
    lines = []
    costs = op_costs(config, seq_len, micro_batch)
    for i, cost in enumerate(costs, start=1):
        fwd = forward_time(cost)
        lines.append(
            f"node{i} -- {cost['op_type']}(id={i}) -- "
            f"forward_compute_time={fwd:.17g}, "
            f"backward_compute_time={BACKWARD_OVER_FORWARD * fwd:.17g}, "
            f"activation_size={cost['out_elems'] * ACT_BYTES:.1f}, "
            f"parameter_size={cost['params'] * PARAM_BYTES:.1f}")
    for u, v in forward_edges(int(config["num_hidden_layers"])):
        lines.append(f"node{u} -- node{v}")
    return "\n".join(lines) + "\n"


def model_name(config: dict, seq_len: int, micro_batch: int) -> str:
    return f"{config['model_type']}_s{int(seq_len)}_b{int(micro_batch)}"


def write_profiles(out_dir: str, config: dict,
                   shapes: Sequence[Dict[str, int]]) -> List[str]:
    """One profile per ``{"seq_len", "micro_batch"}`` shape, named by
    the model type and the shape (the file's stem is the job's model
    name); returns the paths."""
    keys = [(shape["seq_len"], shape["micro_batch"]) for shape in shapes]
    if len(set(keys)) != len(keys):
        raise ValueError(f"architecture shapes repeat: {list(shapes)}")
    os.makedirs(out_dir, exist_ok=True)
    paths = []
    for seq_len, micro_batch in keys:
        path = os.path.join(
            out_dir, model_name(config, seq_len, micro_batch) + ".txt")
        with open(path, "w") as fh:
            fh.write(profile_text(config, seq_len, micro_batch))
        paths.append(path)
    return paths


def dataset_id(config: dict, shapes: Sequence[Dict[str, int]]) -> tuple:
    """What identifies the generated profiles wherever they were
    written: the config's content, the shapes and the cost model's
    constants."""
    body = json.dumps(
        {"config": config,
         "shapes": [[int(s["seq_len"]), int(s["micro_batch"])]
                    for s in shapes],
         "costs": [ACT_BYTES, PARAM_BYTES, BACKWARD_OVER_FORWARD,
                   A100.peak_flops, A100.memory_bandwidth]},
        sort_keys=True)
    return ("architecture", hashlib.sha1(body.encode()).hexdigest())
