"""Architecture config -> PipeDream ``.txt`` training-job profile.

A public ``config.json`` of a decoder-only transformer, a sequence
length and a micro-batch in sequences become one forward-pass profile in
the format ``graphs/readers.py:_parse_pipedream_txt`` reads, so reader ->
mirror -> ``Job`` stays the one path every job takes. What a layer is
made of is chosen per LAYER from the config's KEYS, never from its name:

* attention — ``kv_lora_rank`` present: multi-head latent attention
  (low-rank q and kv paths, one function: :func:`_latent_attention`;
  each normed latent scaled where ``mla_scale_q_lora`` /
  ``mla_scale_kv_lora`` say so), with an indexer a learned-sparse
  core behind it, without one a full causal core over the latent
  keys (``LatentAttnCore``). AN INDEXER (the DeepSeek-Sparse-Attention
  lightning indexer) is stated by ``index_topk`` with ``index_n_heads``
  / ``index_head_dim``, or by an ``sa_config`` block (``topk``,
  ``indexer_num_heads``, ``indexer_head_dim``; one reader a size:
  :func:`index_topk`, :func:`index_heads`, :func:`index_head_dim`) and
  is ONE function under both attention families (``_indexer``:
  ``IndexerProj``, ``IndexScoreTopK``, ``SparseAttnCore``): the index
  queries come from the q latent under latent attention and from the
  normed stream itself under MHA/GQA, the ONE index key and the head
  weights from the stream (``sa_config.indexer_num_kv_heads`` other
  than 1, an indexer beside a ``sparse_config``, on a window layer or
  beside a sink logit are refused; ``sa_config.q_chunk_size`` /
  ``kv_chunk_size`` are the tiles the scores are evaluated in, have to
  be positive and move no count); layer i of
  a ``mixer_types`` list with ``"lightning-attn"``: linear attention
  (:func:`_linear_attention`: a d x d state a head scanned in chunks of
  ``lightning_chunk_size``, cost linear in the sequence, at the
  ``lightning_*`` sizes); otherwise MHA/GQA (:func:`_gqa_attention`),
  whose core is causal over the whole sequence, or — where the config
  has a ``sparse_config`` and the sequence is longer than its
  ``dense_len`` — block-sparse behind compressed-key scores (``KCompress``,
  ``BlockScoreTopK``, ``BlockSparseAttnCore``: the JOB'S GRAPH THEN
  DEPENDS ON ITS SEQUENCE LENGTH), or, on a window layer
  (:func:`window_layers`: layer i of a
  ``hybrid_layer_pattern`` list with ``pattern[i] == 1``, or of a
  ``layer_types`` list with ``"sliding_attention"``), over a
  ``sliding_window`` with the ``swa_*`` head counts and sizes where the
  config has them. Each part is counted where a key states it:
  ``v_head_dim`` a v head size beside q and k's ``head_dim``,
  ``partial_rotary_factor`` RoPE on that part of each q/k head (absent:
  the whole head), ``attention_value_scale`` scaled values,
  ``use_qk_norm`` / ``qk_norm`` a q/k RMSNorm, ``attn_use_rope: false``
  no RoPE, ``attn_use_output_gate`` (the linear mixer:
  ``use_output_gate``) a ``GateProj`` whose sigmoid gates the core's
  output, ``use_output_norm`` an RMSNorm on the linear core's output,
  ``add_swa_attention_sink_bias`` /
  ``add_full_attention_sink_bias`` a learnable sink logit a head;
  ``causal_core_count: half_square`` (OLMoE's ``modeling`` block, whose
  profiles are pinned so) a full core as S x S / 2 keys, the triangle
  without its diagonal — every other config counts S (S + 1) / 2;
  ``rope_scaling.mrope_section`` (M-RoPE: three position streams a
  token, :func:`position_streams`) has to sum to half the rotary part
  of a head and costs RoPE's FLOPs; ``use_sliding_window: false`` means
  no window layer whatever ``sliding_window`` / ``max_window_layers``
  hold, ``true`` without a per-layer list is refused;
* feed-forward — dense SwiGLU at ``intermediate_size`` on every layer
  of a config with NO expert key (a dense model), else on layer i where
  a ``moe_layer_freq`` LIST has a 0 or where ``decoder_sparse_step`` /
  ``mlp_only_layers`` say so (:func:`routed_layers`: i routes iff it is
  not in ``mlp_only_layers`` and (i + 1) mod the step is 0; a scalar or
  no key: on the first ``first_k_dense_replace`` / ``num_dense_layers``
  layers); the others route over ``n_routed_experts`` / ``num_experts``
  / ``num_local_experts`` SwiGLU experts, with
  ``n_shared_experts`` / ``num_shared_experts`` always-on ones beside
  them when the key gives a number; ``scoring_func`` / ``score_func:
  sigmoid`` picks the bias-corrected sigmoid router (under the second
  name its selected weights are normalised where ``route_norm`` and
  scaled where ``route_scale`` say so), otherwise softmax top-k (its
  weights scaled where ``routed_scaling_factor`` is stated and
  renormalised, 2 FLOPs a selected weight, where ``norm_topk_prob`` is
  true, its selection biased where the ``modeling`` block states
  ``e_score_correction_bias``); ``zero_expert_num`` zero-compute
  experts widen the router to E + Z outputs: the expert group sees
  tokens x k x held / (E + Z) pairs and the combine adds w . x for the
  tokens x k x Z / (E + Z) identity pairs, whole on every pod;
* ``shortcut_sub_blocks`` (a ``modeling`` block's): a layer is that
  many attention + dense-FFN sub-blocks and ONE expert block that reads
  the first sub-block's normed state and joins the stream after the
  last one's FFN (``ShortcutCombineResidual``) — Router -> Experts run
  BESIDE every op between: the graph is not one chain;
* depth, widths and experts a token are read under either family of
  names (``num_hidden_layers`` / ``num_layers``, ``intermediate_size`` /
  ``ffn_hidden_size``, ``moe_intermediate_size`` /
  ``expert_ffn_hidden_size``, ``num_experts_per_tok`` / ``moe_topk``);
  a config that states both and disagrees is refused;
* ``num_nextn_predict_layers``: that many multi-token-prediction
  modules (the DeepSeek-V3 form) after the last layer;
* muP scalars, elementwise where a key states them: ``scale_emb`` 1 an
  embedding element, ``scale_depth`` (/ sqrt(layers)) 1 an element of
  each residual branch, ``dim_model_base`` (hidden_size / it) 1 an
  element the head reads.

Seven families are built today: OLMoE (full attention, softmax router:
embedding, L x [InputNorm, QKVProj, AttnCore, OutProjResidual,
PostAttnNorm, Router, Experts, CombineResidual], FinalNorm, LMHeadLoss),
``glm_moe_dsa``, ``mimo_v2_flash`` (OLMoE's 8-op layer with
WindowAttnCore on the window layers and DenseMLPResidual on the dense
one) and ``afmoe`` (Trinity: the same layer with a SharedExpert, 9 ops) and
``minicpm_sala`` (dense: [InputNorm, QKVProj, GateProj, LinearAttnCore
or AttnCore or the three sparse ops, OutProjResidual, PostAttnNorm,
DenseMLPResidual], 7 or 9 ops) and ``longcat_flash`` (a double layer of
21 ops: 2 x [InputNorm, QAProj, QBProj, KVAProj, KVBProj,
LatentAttnCore, OutProjResidual, PostAttnNorm, DenseMLPResidual] with
Router and Experts behind the first PostAttnNorm and
ShortcutCombineResidual at the end) and ``KeyeVL2`` (every layer
[InputNorm, QKVProj, IndexerProj, IndexScoreTopK, SparseAttnCore,
OutProjResidual, PostAttnNorm, Router, Experts, CombineResidual]: GQA
behind the indexer, which reads InputNorm BESIDE QKVProj, no shared
expert, no dense layer; equations beside each op below, ``x`` the
normed hidden state). A config's ``model_type`` names the job
(:func:`model_name`) and chooses nothing.
Ops are at one granularity in all: each norm, each projection, the
indexer's projections, index score + top-k, key compression, block
score + top-k, the gate's projection, the attention core,
out-projection + residual, router, shared expert, expert group,
combine + residual; there is an edge for every true data dependency
and no other.

**The cut** (``jobs_config.architecture``'s ``layers`` and
``experts_held``; absent = the config's own): ``layers: {leading_dense,
following}`` keeps the first ``leading_dense + following`` layers of the
published stack — layer i of the job is layer i of the config's
per-layer lists, so a stage keeps the pattern's own order and ratio, and
``leading_dense`` has to be the dense layers those lists start with; the
others lie on further pods as pipeline stages. ``experts_held`` is this
pod's share
of every expert layer's routed experts: the router keeps its published
width and its experts per token, the expert group holds ``experts_held``
experts and computes their part for the tokens routed to them (balanced:
tokens x k x held / experts token-expert pairs); the shared expert and
everything else are whole. No code stands in for the absent pods.

Costs are ANALYTIC, not profiled (:func:`op_costs` is the whole model):

* ``forward_compute_time = max(FLOPs / peak, bytes / memory bandwidth)``
  in SECONDS, the unit ``sim/comm_model.py`` prices communication in,
  with the worker's constants taken from where the simulator keeps them
  (``hardware/devices.py:A100``'s ``peak_flops`` and
  ``memory_bandwidth``, also ``comm_model``'s defaults);
* ``backward_compute_time = 2 x forward``;
* ``activation_size`` = the op's output tensor at ``ACT_BYTES`` an
  element (bf16);
* ``parameter_size`` = parameters x the resident bytes of one (training
  state: bf16 weight and gradient, fp32 master weight and two Adam
  moments = ``PARAM_BYTES``);
* ``sync_size`` = parameters x the bytes of one that a backward weight
  sync moves — written ONLY when the architecture file's wrapper states
  ``training_state`` (beside ``source_url``). A profile with it is a
  STATED graph (``graphs/readers.py``): an op occupies activation +
  parameter state, its mirror the activation's gradient, deps carry
  activations and sync cliques ``sync_size``. Without it the reference's
  one ``memory_cost`` sizes all three, as for every profiled graph.

FLOPs are 2 per multiply-accumulate plus the small elementwise terms
written beside each op below; a causal core counts the keys each query
sees (:func:`attended_keys`).
"""
from __future__ import annotations

import hashlib
import json
import os
from typing import Dict, List, Optional, Sequence, Tuple

from ddls_tpu.hardware.devices import A100

#: bytes of one activation element and of one weight element read (bf16)
ACT_BYTES = 2
#: bytes of one parameter in ``parameter_size`` where the architecture
#: file states no ``training_state``: the training state a worker holds
#: for it (bf16 weight 2 + bf16 gradient 2 + fp32 master 4 + two fp32
#: Adam moments 8)
PARAM_BYTES = 16
BACKWARD_OVER_FORWARD = 2.0

#: the OLMoE layer, in profile order
LAYER_OPS = ("InputNorm", "QKVProj", "AttnCore", "OutProjResidual",
             "PostAttnNorm", "Router", "Experts", "CombineResidual")

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def load_arch_file(path: str) -> dict:
    """The architecture file: ``{"source_url": ..., "config": {...}}``
    (the public ``config.json``'s shape keys) and, where the family
    states them, ``"training_state": {"resident_bytes_per_parameter",
    "synced_bytes_per_parameter"}`` and ``"modeling": {...}``
    (:func:`builder_config`). A relative path that does not exist from
    the working directory is taken from the checkout's root."""
    if not os.path.isabs(path) and not os.path.exists(path):
        path = os.path.join(_REPO, path)
    with open(path) as fh:
        return json.load(fh)


def builder_config(arch_file: dict) -> dict:
    """What :func:`build_graph` reads: the public config's keys and,
    over them, the wrapper's ``modeling`` block — what the family's
    modeling code fixes and its ``config.json`` leaves unsaid, under the
    key other public configs say it by (OLMoE's q/k RMSNorm:
    ``use_qk_norm``)."""
    return {**arch_file["config"], **arch_file.get("modeling", {})}


def load_arch_config(path: str) -> dict:
    return builder_config(load_arch_file(path))


def attended_keys(seq_len: int, limit: int) -> int:
    """Keys a causal core reads over one sequence when query t (1-based)
    sees min(t, limit) of them: a top-``limit`` sparse core, a sliding
    window of ``limit``, and with ``limit >= seq_len`` the full causal
    triangle S (S + 1) / 2."""
    S, K = int(seq_len), int(limit)
    if S <= K:
        return S * (S + 1) // 2
    return K * (K + 1) // 2 + (S - K) * K


def compressed_keys(seq_len: int, kernel_size: int,
                    kernel_stride: int) -> int:
    """Compressed keys a causal block score reads over one sequence:
    key j is the mean of tokens j stride + 1 .. j stride + kernel and
    query t (1-based) sees it once t >= j stride + kernel, so it sees
    (t - kernel) // stride + 1 of them from t = kernel on."""
    span = int(seq_len) - int(kernel_size)
    if span < 0:
        return 0
    whole, rest = divmod(span, int(kernel_stride))
    return int(kernel_stride) * whole * (whole - 1) // 2 \
        + whole * (rest + 1) + span + 1


def _leading_zeros(values: Sequence[int]) -> int:
    return next((i for i, v in enumerate(values) if v), len(values))


#: ``layer_types`` entries and whether the layer's core is a window
LAYER_TYPES = {"sliding_attention": True, "full_attention": False}


def _layer_kinds(config: dict, key: str, known: dict) -> Optional[list]:
    """``known``'s value for each entry of the config's per-layer list
    ``key``; None where the config has no such list. An unknown string
    is refused."""
    types = config.get(key)
    if not isinstance(types, list):
        return None
    unknown = sorted(set(types) - set(known))
    if unknown:
        raise ValueError(f"{key}: unknown {unknown} (known: "
                         f"{sorted(known)})")
    return [known[kind] for kind in types]


def window_layers(config: dict) -> Optional[List[bool]]:
    """Per layer of the published stack, whether its attention core
    reads a sliding window: ``hybrid_layer_pattern`` (1 = window) or
    ``layer_types`` (``"sliding_attention"``); None where the config has
    neither list (every core is full). An unknown ``layer_types`` string
    is refused, and so is a list that ``global_attn_every_n_layers``
    (every n-th layer full, the others window) contradicts.
    ``use_sliding_window: false`` means no window layer whatever
    ``sliding_window`` / ``max_window_layers`` hold (a list that has one
    is refused); ``true`` without a list that has one is refused."""
    pattern = config.get("hybrid_layer_pattern")
    if isinstance(pattern, list):
        window = [kind == 1 for kind in pattern]
    else:
        window = _layer_kinds(config, "layer_types", LAYER_TYPES)
        every = config.get("global_attn_every_n_layers")
        if window is not None and every and window != [
                (i + 1) % int(every) != 0 for i in range(len(window))]:
            raise ValueError(
                f"layer_types {config['layer_types']} disagrees with "
                f"global_attn_every_n_layers {every}")
    use = config.get("use_sliding_window")
    if use is not None and bool(use) != bool(window and any(window)):
        raise ValueError(
            f"use_sliding_window {use} beside "
            + ("a per-layer list with window layers" if not use else
               "no per-layer list that says which layers (sliding_window "
               "/ max_window_layers alone give no kind a layer)"))
    return window


#: ``mixer_types`` entries and whether the layer's mixer is the linear
#: kind (else softmax GQA, full or block-sparse by the sequence length)
MIXER_TYPES = {"lightning-attn": True, "minicpm4": False}


def linear_layers(config: dict) -> Optional[List[bool]]:
    """Per layer of the published stack, whether its mixer is linear
    attention: ``mixer_types`` (``"lightning-attn"``); None where the
    config has no such list. An unknown string is refused."""
    return _layer_kinds(config, "mixer_types", MIXER_TYPES)


_REQUIRED = object()


def _lookup(config: dict, name: str):
    """``config[name]``, a dotted ``name`` read through nested blocks
    (``sa_config.topk``); None where any part is absent."""
    value = config
    for part in name.split("."):
        value = value.get(part) if isinstance(value, dict) else None
    return value


def _stated(config: dict, names: Sequence[str], default=_REQUIRED):
    """The value a config states under one of ``names`` (one name a
    family of public configs, dotted where it sits in a nested block);
    ``default`` where it states none, a ``KeyError`` where none is
    given. A config that states two and disagrees is refused."""
    values = {name: value for name in names
              if (value := _lookup(config, name)) is not None}
    if len(set(values.values())) > 1:
        raise ValueError(f"config states {values}: they disagree")
    if not values and default is _REQUIRED:
        raise KeyError(" / ".join(names))
    return next(iter(values.values()), default)


def routed_experts(config: dict) -> int:
    """Routed experts an expert layer has; 0 for a config with no expert
    key (a dense model: every layer's feed-forward is dense)."""
    return int(_stated(config, ("n_routed_experts", "num_experts",
                                "num_local_experts"), 0))


def stack_depth(config: dict) -> int:
    """Layers of the published stack (``num_layers`` counts a
    ``shortcut_sub_blocks`` layer once, as its config does)."""
    return int(_stated(config, ("num_hidden_layers", "num_layers")))


def dense_width(config: dict) -> int:
    """Intermediate size of a dense SwiGLU."""
    return int(_stated(config, ("intermediate_size", "ffn_hidden_size")))


def expert_width(config: dict) -> int:
    """Intermediate size of one routed expert (a config that states
    none: the dense width, OLMoE's)."""
    return int(_stated(config, ("moe_intermediate_size",
                                "expert_ffn_hidden_size"), None)
               or dense_width(config))


def experts_per_token(config: dict) -> int:
    """Router outputs selected a token; 0 where the config states none
    (a dense model)."""
    return int(_stated(config, ("num_experts_per_tok", "moe_topk"), 0))


def index_heads(config: dict) -> int:
    """Query heads of the learned-sparse indexer."""
    return int(_stated(config, ("index_n_heads",
                                "sa_config.indexer_num_heads")))


def index_head_dim(config: dict) -> int:
    """Size of an indexer head (queries and the one shared key)."""
    return int(_stated(config, ("index_head_dim",
                                "sa_config.indexer_head_dim")))


def index_topk(config: dict) -> int:
    """Keys the indexer selects a query; 0 where the config states no
    indexer (neither ``index_topk`` nor an ``sa_config`` block). What an
    indexer states and the builder cannot build is refused here: more
    than the ONE shared index key head the score is counted for
    (``sa_config.indexer_num_kv_heads``), a tile of the score's
    evaluation that is not positive (``sa_config.q_chunk_size`` /
    ``kv_chunk_size``: the scores are never written, so no FLOP and no
    once-read byte depends on the tiles), and an indexer beside a
    ``sparse_config`` (two selections of one core's keys)."""
    block = config.get("sa_config")
    if block is None and config.get("index_topk") is None:
        return 0
    block = block or {}
    if int(block.get("indexer_num_kv_heads", 1)) != 1:
        raise ValueError(
            f"sa_config.indexer_num_kv_heads "
            f"{block['indexer_num_kv_heads']}: the index score is "
            f"counted for ONE key head all index heads share")
    for key in ("q_chunk_size", "kv_chunk_size"):
        if key in block and not int(block[key] or 0) > 0:
            raise ValueError(f"sa_config.{key} {block[key]}: a tile of "
                             f"the index score has to be positive")
    if config.get("sparse_config"):
        raise ValueError("an indexer (index_topk / sa_config) beside a "
                         "sparse_config: two selections of one core's keys")
    return int(_stated(config, ("index_topk", "sa_config.topk")))


def attended_keys_share(config: dict, seq_len: int) -> float:
    """Keys the learned-sparse cores read over one sequence of
    ``seq_len`` tokens over the keys full causal cores would (what the
    indexer buys); 1 where the config states no indexer."""
    topk = index_topk(config)
    return attended_keys(seq_len, topk) / attended_keys(seq_len, seq_len) \
        if topk else 1.0


def rotary_dim(config: dict, head_dim: int) -> int:
    """Elements of a ``head_dim`` q/k head that RoPE turns:
    ``partial_rotary_factor`` of it (absent: the whole head), none where
    ``attn_use_rope`` is false."""
    return round(float(config.get("partial_rotary_factor") or 1)
                 * head_dim) * bool(config.get("attn_use_rope", True))


def position_streams(config: dict) -> int:
    """Position streams a token has: the sections of
    ``rope_scaling.mrope_section`` (M-RoPE: each section of a head's
    rotary pairs is turned by its own stream — time, height, width; the
    count stays RoPE's 3 an element, the sections only pick which stream
    turns a pair); 1 where the config states none. Sections that do not
    sum to half the rotary part of a head are refused."""
    sections = _lookup(config, "rope_scaling.mrope_section")
    if sections is None:
        return 1
    rotary = int(config["qk_rope_head_dim"]) if "kv_lora_rank" in config \
        else rotary_dim(config, int(
            config.get("head_dim") or int(config["hidden_size"])
            // int(config["num_attention_heads"])))
    if 2 * sum(sections) != rotary:
        raise ValueError(f"rope_scaling.mrope_section {sections} sums to "
                         f"{sum(sections)}, not to half the {rotary} "
                         f"elements of a head that RoPE turns")
    return len(sections)


def routed_layers(config: dict) -> Optional[List[int]]:
    """Per layer of the published stack 1 where its feed-forward routes
    and 0 where it is dense: a ``moe_layer_freq`` LIST, or — where
    ``decoder_sparse_step`` / ``mlp_only_layers`` are stated — layer i
    routes iff i is not in ``mlp_only_layers`` and (i + 1) mod
    ``decoder_sparse_step`` is 0. None where the config states neither
    (the dense layers lead: ``first_k_dense_replace`` /
    ``num_dense_layers``); both, and unequal, is refused."""
    freq = config.get("moe_layer_freq")
    freq = freq if isinstance(freq, list) else None
    step, only = (config.get("decoder_sparse_step"),
                  config.get("mlp_only_layers"))
    if step is None and only is None:
        return freq
    step = 1 if step is None else int(step)
    if step < 1:
        raise ValueError(f"decoder_sparse_step {step}: has to be >= 1")
    stated = [int(i not in (only or ()) and (i + 1) % step == 0)
              for i in range(stack_depth(config))]
    if freq is not None and freq != stated:
        raise ValueError(
            f"config states moe_layer_freq {freq} and decoder_sparse_step "
            f"{step} / mlp_only_layers {only}: they disagree")
    return stated


#: ``zero_expert_type`` values the builder knows: an ``identity`` expert
#: returns its input (no FLOPs but the combine's weighted add)
ZERO_EXPERT_TYPES = ("identity",)


def zero_experts(config: dict) -> int:
    """Zero-compute experts beside the routed ones (``zero_expert_num``;
    0 where the config states none): the router is that much wider and
    a pair routed to one costs no expert FLOPs, no parameter and no
    dispatch. A ``zero_expert_type`` the builder does not know is
    refused."""
    zeros = int(config.get("zero_expert_num") or 0)
    kind = config.get("zero_expert_type")
    if zeros and kind not in ZERO_EXPERT_TYPES:
        raise ValueError(f"zero_expert_type: unknown {kind!r} (known: "
                         f"{list(ZERO_EXPERT_TYPES)})")
    return zeros


def zero_routed_share(config: dict) -> float:
    """Share of a token's routed pairs that go to a zero-compute expert
    under balanced routing over the router's outputs."""
    experts, zeros = routed_experts(config), zero_experts(config)
    return zeros / (experts + zeros) if zeros else 0.0


def resolve_cut(config: dict, layers: Optional[dict] = None,
                experts_held: Optional[int] = None) -> Dict[str, int]:
    """``{"leading_dense", "following", "experts_held"}`` with what the
    cut leaves out taken from the config. A config with no expert key is
    dense throughout: no layer follows the dense ones and there is no
    expert to hold (``experts_held`` 0; stating one is refused)."""
    freq = routed_layers(config)
    experts = routed_experts(config)
    depth = stack_depth(config)
    if not experts:
        dense = depth
    elif freq is not None:
        dense = _leading_zeros(freq)
    else:
        dense = int(config.get("first_k_dense_replace")
                    or config.get("num_dense_layers") or 0)
    cut = {"leading_dense": dense,
           "following": depth - dense,
           "experts_held": experts}
    if layers is not None:
        unknown = set(layers) - {"leading_dense", "following"}
        if unknown:
            raise ValueError(f"architecture layers: unknown {unknown}")
        cut.update({k: int(v) for k, v in layers.items()})
    if experts_held is not None:
        if not experts:
            raise ValueError(
                f"architecture experts_held: {experts_held} of a config "
                f"with no expert key (a dense model holds none)")
        cut["experts_held"] = int(experts_held)
    if not experts and cut["following"]:
        raise ValueError(f"architecture layers: {cut} of a config with "
                         f"no expert key (no layer follows the dense ones)")
    if config.get("shortcut_sub_blocks") and cut["leading_dense"]:
        raise ValueError(f"architecture layers: {cut} of a config whose "
                         f"every layer holds its expert branch "
                         f"(shortcut_sub_blocks: no dense layer leads)")
    if freq is not None:
        # kinds come from the list: the cut only says how many layers
        total = cut["leading_dense"] + cut["following"]
        if total > len(freq) \
                or _leading_zeros(freq[:total]) != cut["leading_dense"]:
            raise ValueError(
                f"architecture layers: {cut} departs from moe_layer_freq "
                f"/ decoder_sparse_step / mlp_only_layers "
                f"{freq[:total]} (of {len(freq)} layers)")
    types = config.get("layer_types")
    if isinstance(types, list):
        # a dense count stated beside per-layer attention kinds: the
        # cut keeps the stack's first layers, dense ones as published
        total = cut["leading_dense"] + cut["following"]
        if total > len(types) or cut["leading_dense"] != min(dense, total):
            raise ValueError(
                f"architecture layers: {cut} departs from layer_types "
                f"(of {len(types)} layers, the first {dense} dense)")
    mixers = config.get("mixer_types")
    if isinstance(mixers, list) \
            and cut["leading_dense"] + cut["following"] > len(mixers):
        raise ValueError(f"architecture layers: {cut} departs from "
                         f"mixer_types (of {len(mixers)} layers)")
    return cut


class _Graph:
    """Forward ops (1-based ids in insertion order) and their edges."""

    def __init__(self):
        self.ops: List[dict] = []
        self.edges: List[Tuple[int, int]] = []

    def add(self, op_type, flops, nbytes, out_elems, params,
            inputs: Sequence[int] = ()) -> int:
        self.ops.append({"op_type": op_type, "flops": float(flops),
                         "bytes": float(nbytes),
                         "out_elems": float(out_elems),
                         "params": float(params)})
        node = len(self.ops)
        self.edges += [(u, node) for u in inputs]
        return node

    def profile_edges(self) -> List[Tuple[int, int]]:
        """Edges as the profile lists them: the chain (an op to the op
        written right after it) first, the others in the order added."""
        chain = [e for e in self.edges if e[1] == e[0] + 1]
        return chain + [e for e in self.edges if e[1] != e[0] + 1]


def build_graph(config: dict, seq_len: int, micro_batch: int,
                layers: Optional[dict] = None,
                experts_held: Optional[int] = None) -> _Graph:
    """The forward pass over ``micro_batch`` sequences of ``seq_len``
    tokens: per op the FLOPs and bytes moved, elements of the output
    tensor and parameters held; per data dependency an edge."""
    cut = resolve_cut(config, layers, experts_held)
    H = int(config["hidden_size"])
    heads = int(config["num_attention_heads"])
    V = int(config["vocab_size"])
    E = routed_experts(config)          # 0: a dense model
    Z = zero_experts(config)            # zero-compute router outputs
    k = experts_per_token(config)
    dense_inter = dense_width(config)
    expert_inter = expert_width(config)
    shared_inter = int(config.get("n_shared_experts")
                       or config.get("num_shared_experts") or 0) * expert_inter
    sigmoid_router = "sigmoid" in (config.get("scoring_func"),
                                   config.get("score_func"))
    # FLOPs a selected expert's weight costs the sigmoid router: the
    # sum and the divide of s_sel / sum s_sel, then the scale. Under
    # ``score_func`` the config states each (``route_norm``,
    # ``route_scale``); under ``scoring_func`` all three are counted
    routed_weight = 3
    if "score_func" in config:
        routed_weight = 2 * bool(config.get("route_norm")) \
            + (config.get("route_scale") is not None)
    n_mtp = int(config.get("num_nextn_predict_layers") or 0)
    held = cut["experts_held"]
    S, B = int(seq_len), int(micro_batch)
    T = S * B                        # tokens of the step
    def share_of_pairs(outputs):
        """Token-expert pairs ``outputs`` of the router's E + Z see
        under balanced routing."""
        whole, rest = divmod(T * k * outputs, E + Z)
        return whole if not rest else T * k * outputs / (E + Z)

    # pairs the FFN experts held here see, and the zero-compute experts'
    # (whole on every pod: a token's own device adds them)
    pairs = share_of_pairs(held) if E else 0
    zero_pairs = share_of_pairs(Z) if Z else 0
    A = ACT_BYTES
    # muP's scalars, elementwise where a key states them: x scale_emb an
    # embedding element, x scale_depth / sqrt(layers) an element of each
    # residual branch, / (hidden_size / dim_model_base) an element the
    # head reads
    branch_scale = T * H * ("scale_depth" in config)
    qk_norm = bool(config.get("use_qk_norm") or config.get("qk_norm"))
    g = _Graph()

    def norm(op_type, inputs, tokens=T):
        # square, mean, rsqrt-scale, weight: 4 per element
        return g.add(op_type, 4 * tokens * H,
                     A * (2 * tokens * H + H), tokens * H, H, inputs)

    def _projections(stream, n, kv_heads, d_qk, d_v, rotary, gate,
                     value_scale=False):
        """InputNorm, ``QKVProj`` and, where the mixer is gated,
        ``GateProj``; returns (InputNorm, QKVProj, GateProj or None, the
        width of [q ; k ; v])."""
        q, kk, vv = n * d_qk, kv_heads * d_qk, kv_heads * d_v
        qkv = q + kk + vv
        # RoPE (3 an element) on the rotary part of each q and k head;
        # where stated, q/k RMSNorm (4 an element, a weight an element
        # of a token's q and k) and v <- attention_value_scale . v (1)
        elementwise = 3 * T * (n + kv_heads) * rotary \
            + qk_norm * 4 * T * (q + kk) \
            + value_scale * T * vv
        norm_weights = qk_norm * (q + kk)
        x = norm("InputNorm", [stream])
        # [q ; k ; v] = x W_qkv
        proj = g.add("QKVProj", 2 * T * H * qkv + elementwise,
                     A * (T * H + H * qkv + norm_weights + T * qkv),
                     T * qkv, H * qkv + norm_weights, [x])
        gate_proj = None
        if gate:
            # g = x W_g (H -> n d_v): one gate an element of the core's
            # output
            gate_proj = g.add("GateProj", 2 * T * H * n * d_v,
                              A * (T * H + H * n * d_v + T * n * d_v),
                              T * n * d_v, H * n * d_v, [x])
        return x, proj, gate_proj, qkv

    def _indexer(x, q_source, q_width, rope, pair, reads, out, inputs):
        """The DSA lightning indexer and the core behind it, ONE
        function for both attention families: ``IndexerProj``,
        ``IndexScoreTopK``, ``SparseAttnCore``; returns the core. The
        index queries are projected from op ``q_source`` (``q_width``
        wide: the q latent under latent attention, the normed stream
        ``x`` itself under MHA/GQA), RoPE turns ``rope`` elements of
        each index head; the core costs ``pair`` FLOPs a key a query
        reads, reads ``reads`` elements from ops ``inputs`` besides the
        indices and writes ``out``."""
        ni, di, topk = (index_heads(config), index_head_dim(config),
                        index_topk(config))
        # indexer: q^I = q_source W_Iq (q_width -> ni x di), k^I =
        # Norm(x W_Ik) (H -> di, norm 4: ONE key head), w = x W_Iw (H ->
        # ni); RoPE (3) on the rope part of each q^I head and of k^I
        index_out = ni * di + di + ni
        index_w = q_width * ni * di + H * di + H * ni
        sources = list(dict.fromkeys([q_source, x]))
        idx = g.add("IndexerProj",
                    2 * T * index_w + 4 * T * di + 3 * T * (ni + 1) * rope,
                    A * (T * q_width * (q_source != x) + T * H + index_w
                         + di + T * index_out),
                    T * index_out, index_w + di, sources)
        # I[t,s] = sum_j w[t,j] ReLU(q^I[t,j] . k^I[s]) over the causal
        # half of S x S (dot 2 di, ReLU and weighted sum 2), top-k of
        # each row; the S x S scores are never written, out = indices
        select = g.add("IndexScoreTopK",
                       B * S * S / 2 * ni * (2 * di + 2),
                       A * (T * index_out + T * min(S, topk)),
                       T * min(S, topk), 0, [idx])
        # o_t = sum_{s in S_t} softmax_s(q_t . k_s) v_s: a query reads
        # min(t, topk) keys (S <= topk: the full causal count)
        return g.add("SparseAttnCore", B * attended_keys(S, topk) * pair,
                     A * (reads + T * min(S, topk) + out), out, 0,
                     [*inputs, select])

    def _out_proj(core, stream, gate_proj, o, out_norm=False):
        """y = o W_o (o -> H) + residual; where stated, o RMS-normed
        first (4 an element, a weight an element) and gated, o .
        sigmoid(g) (3 an element: sigmoid 2 as in silu . up's 4, the
        product 1)."""
        gated = gate_proj is not None
        return g.add("OutProjResidual",
                     2 * T * o * H + T * H + branch_scale
                     + gated * 3 * T * o + out_norm * 4 * T * o,
                     A * (T * o + gated * T * o + o * H + out_norm * o
                          + 2 * T * H),
                     T * H, o * H + out_norm * o,
                     [core, stream] + [gate_proj] * gated)

    def _gqa_attention(stream, window):
        """MHA/GQA with a full causal core, behind an indexer a
        learned-sparse one, past a ``sparse_config``'s ``dense_len`` a
        block-sparse one, or, with ``window``, a sliding-window one at
        the ``swa_*`` sizes; returns OutProjResidual."""
        def size(key, default=None):
            value = config.get("swa_" + key) if window else None
            return int(value or config.get(key) or default)

        n = size("num_attention_heads")
        kv_heads = size("num_key_value_heads", n)
        d_qk = size("head_dim", H // n)
        d_v = size("v_head_dim", d_qk)
        rotary = rotary_dim(config, d_qk)
        x, proj, gate_proj, qkv = _projections(
            stream, n, kv_heads, d_qk, d_v, rotary,
            gate=config.get("attn_use_output_gate"),
            value_scale="attention_value_scale" in config)
        # o_t = sum_s softmax_s(q_t . k_s / sqrt(d_qk)) v_s over the
        # keys a query sees: QK^T 2 d_qk, PV 2 d_v, softmax 5 a key; a
        # learnable sink logit a head in the denominator (1 a query and
        # head) where stated
        sinks = n if config.get(
            "add_swa_attention_sink_bias" if window
            else "add_full_attention_sink_bias") else 0
        pair = n * (2 * d_qk + 2 * d_v + 5)

        def block_sparse_core(sparse):
            """The core of a sequence longer than ``sparse_config``'s
            ``dense_len`` (InfLLM v2): selection shared by the q heads
            of a kv head, no indexer heads."""
            kernel, stride = (int(sparse["kernel_size"]),
                              int(sparse["kernel_stride"]))
            block, topk = int(sparse["block_size"]), int(sparse["topk"])
            # K^c_j = mean of `kernel` keys every `stride` (kernel an
            # element: the sum and the scale), a kv head
            rows = (S - kernel) // stride + 1
            compressed = B * rows * kv_heads * d_qk
            kc = g.add("KCompress", compressed * kernel,
                       A * (T * kv_heads * d_qk + compressed), compressed,
                       0, [proj])
            # r[t, j] = sum over a group's q heads of softmax_j(q_t,h .
            # K^c_j / sqrt(d)) over the compressed keys behind the query
            # (dot 2 d, softmax 5), max-pooled to blocks, top-k blocks a
            # query and kv head; the scores are never written, out =
            # the indices
            kept = min(-(-S // block), topk)
            select = g.add("BlockScoreTopK",
                           B * compressed_keys(S, kernel, stride) * n
                           * (2 * d_qk + 5),
                           A * (T * n * d_qk + compressed
                                + T * kv_heads * kept),
                           T * kv_heads * kept, 0, [proj, kc])
            # softmax attention over the selected blocks, a sliding
            # window and the initial blocks: min(t, reach) keys a query
            reach = topk * block + int(sparse["window_size"]) \
                + int(sparse["init_blocks"]) * block
            return g.add("BlockSparseAttnCore",
                         B * attended_keys(S, reach) * pair,
                         A * (T * qkv + T * kv_heads * kept
                              + T * n * d_v),
                         T * n * d_v, 0, [proj, select])

        sparse = config.get("sparse_config")
        if indexed:
            if window or sinks:
                raise ValueError(
                    "an indexer (index_topk / sa_config) on a "
                    + ("sliding-window layer" if window
                       else "core with a sink logit") + ": not built")
            # the index queries come from x itself (no q latent) and
            # RoPE turns an index head as far as it turns a q/k head
            core = _indexer(x, x, H, rotary_dim(config,
                                                index_head_dim(config)),
                            pair, T * qkv, T * n * d_v, [proj])
        elif sparse and not window and S > int(sparse["dense_len"]):
            core = block_sparse_core(sparse)
        else:
            if window:
                keys = attended_keys(S, int(config["sliding_window"]))
            elif config.get("causal_core_count") == "half_square":
                # the causal half of S x S without its diagonal (1 / S
                # of it): what OLMoE's architecture file states, its
                # profiles being pinned so
                keys = S * S / 2
            else:
                keys = attended_keys(S, S)
            core = g.add("WindowAttnCore" if window else "AttnCore",
                         B * keys * pair + T * sinks,
                         A * (T * qkv + sinks + T * n * d_v),
                         T * n * d_v, sinks, [proj])
        return _out_proj(core, stream, gate_proj, n * d_v,
                         out_norm=bool(config.get("attn_use_output_norm")))

    def _linear_attention(stream):
        """Lightning linear attention at the ``lightning_*`` sizes: per
        head, with a fixed decay l (no parameter), S_t = l S_{t-1} +
        k_t^T v_t (d x d) and o_t = lightning_scale . q_t S_t, scanned
        in chunks of ``lightning_chunk_size``; returns
        OutProjResidual."""
        n, kv_heads = (int(config["lightning_nh"]),
                       int(config["lightning_nkv"]))
        d = int(config["lightning_head_dim"])
        chunk = int(config["lightning_chunk_size"])
        _, proj, gate_proj, qkv = _projections(
            stream, n, kv_heads, d, d,
            rotary=d * bool(config.get("lightning_use_rope")),
            gate=config.get("use_output_gate"))

        def chunk_flops(c):
            # intra-chunk [(Q K^T) . M] V over the causal half (2 d, the
            # decay mask 1, 2 d a pair), inter-chunk Q S_prev and the
            # update K^T V (2 d^2 a token each), the output's decay, sum
            # and scale and the update's decayed keys (4 d a token), the
            # state's decay and add (2 d^2)
            return c * (c + 1) // 2 * (4 * d + 1) + 4 * c * d * d \
                + 4 * c * d + 2 * d * d

        whole, rest = divmod(S, chunk)
        chunks = whole + (rest > 0)
        # bytes: q, k, v in, o out and a d x d state a head and chunk
        # written once (what the backward pass restarts from); linear in
        # S at a fixed chunk
        core = g.add("LinearAttnCore",
                     B * n * (whole * chunk_flops(chunk)
                              + (rest > 0) * chunk_flops(rest)),
                     A * (T * qkv + T * n * d + B * chunks * n * d * d),
                     T * n * d, 0, [proj])
        return _out_proj(core, stream, gate_proj, n * d,
                         out_norm=bool(config.get("use_output_norm")))

    def _latent_attention(stream):
        """MLA: low-rank q and kv paths (one function for every config
        with ``kv_lora_rank``), then — where the config states
        ``index_topk`` — the DSA indexer and a top-k sparse core, else a
        full causal core over the latent keys; returns
        OutProjResidual."""
        rq, rkv = int(config["q_lora_rank"]), int(config["kv_lora_rank"])
        dn, dr = (int(config["qk_nope_head_dim"]),
                  int(config["qk_rope_head_dim"]))
        dv = int(config["v_head_dim"])
        dqk = dn + dr
        # c . sqrt(H / rank), 1 an element, where the key says so
        scale_q = T * rq * bool(config.get("mla_scale_q_lora"))
        scale_kv = T * rkv * bool(config.get("mla_scale_kv_lora"))
        x = norm("InputNorm", [stream])
        # c_q = RMSNorm(x W_qa): H -> rq, norm 4 an element
        c_q = g.add("QAProj", 2 * T * H * rq + 4 * T * rq + scale_q,
                    A * (T * H + H * rq + rq + T * rq), T * rq,
                    H * rq + rq, [x])
        # q = c_q W_qb: rq -> heads x (nope + rope), RoPE (3) on the rope
        q = g.add("QBProj", 2 * T * rq * heads * dqk + 3 * T * heads * dr,
                  A * (T * rq + rq * heads * dqk + T * heads * dqk),
                  T * heads * dqk, rq * heads * dqk, [c_q])
        # [c_kv ; k_r] = x W_kva: H -> rkv + rope; RMSNorm (4) on c_kv,
        # RoPE (3) on the one k_r all heads share
        c_kv = g.add("KVAProj",
                     2 * T * H * (rkv + dr) + 4 * T * rkv + 3 * T * dr
                     + scale_kv,
                     A * (T * H + H * (rkv + dr) + rkv + T * (rkv + dr)),
                     T * (rkv + dr), H * (rkv + dr) + rkv, [x])
        # [k_n ; v] = c_kv W_kvb: rkv -> heads x (nope + v)
        kv = g.add("KVBProj", 2 * T * rkv * heads * (dn + dv),
                   A * (T * rkv + rkv * heads * (dn + dv)
                        + T * heads * (dn + dv)),
                   T * heads * (dn + dv), rkv * heads * (dn + dv), [c_kv])
        # QK^T 2 dqk, PV 2 dv, softmax 5 a key a query reads
        pair = heads * (2 * dqk + 2 * dv + 5)
        if indexed:
            # the index queries come from the q latent, RoPE turns the
            # rope part of an index head; the core reads q, [k_n ; v]
            # and the one k_r
            core = _indexer(x, c_q, rq, dr, pair,
                            T * heads * dqk + T * heads * (dn + dv) + T * dr,
                            T * heads * dv, [q, kv, c_kv])
        else:
            # o_t = sum_{s <= t} softmax_s(q_t . [k_n,s ; k_r,s] /
            # sqrt(dqk)) v_s: FULL causal, a query reads its t keys
            core = g.add("LatentAttnCore",
                         B * attended_keys(S, S) * pair,
                         A * (T * heads * dqk + T * heads * (dn + dv)
                              + T * dr + T * heads * dv),
                         T * heads * dv, 0, [q, kv, c_kv])
        return _out_proj(core, stream, None, heads * dv)

    window = window_layers(config)
    linear = linear_layers(config)
    freq = routed_layers(config)
    indexed = bool(index_topk(config))
    position_streams(config)    # sections that do not fit are refused
    sub_blocks = int(config.get("shortcut_sub_blocks") or 0)
    if n_mtp and (window is not None or linear is not None):
        raise ValueError("a per-layer attention list gives no kind for a "
                         "multi-token-prediction module's layer")

    def attention(stream, i):
        """Layer ``i``'s attention on the residual stream op ``stream``;
        returns OutProjResidual."""
        if "kv_lora_rank" in config:
            return _latent_attention(stream)
        if linear is not None and linear[i]:
            return _linear_attention(stream)
        return _gqa_attention(stream,
                              window=window is not None and window[i])

    def dense_mlp(x, stream):
        # gate, up, down, silu * up (4 a value), + residual
        return g.add("DenseMLPResidual",
                     2 * T * 3 * H * dense_inter + 4 * T * dense_inter
                     + T * H + branch_scale,
                     A * (3 * T * H + 3 * H * dense_inter),
                     T * H, 3 * H * dense_inter, [x, stream])

    # FLOPs of the softmax router's selected weights: x
    # routed_scaling_factor where the config states one, p_sel / sum
    # p_sel (the sum and the divide) where ``norm_topk_prob`` is true
    softmax_weight = T * k * (("routed_scaling_factor" in config)
                              + 2 * bool(config.get("norm_topk_prob")))
    # the selection bias b of top-k(p + b), a parameter an output, where
    # the ``modeling`` block states it
    score_bias = (E + Z) * bool(config.get("e_score_correction_bias"))

    def expert_block(x):
        """Router, the always-on experts and the expert group on the
        normed state ``x``; returns (Router, Experts, [SharedExpert])."""
        R = E + Z                    # the router's outputs
        if sigmoid_router:
            # s = sigmoid(x W_r), top-k of s + b (5 a logit), weights
            # s_sel / sum s_sel x scale (3 a selected expert)
            router = g.add("Router",
                           2 * T * H * R + 5 * T * R + routed_weight * T * k,
                           A * (T * H + H * R + R + 2 * T * k), 2 * T * k,
                           H * R + R, [x])
        else:
            # x W_r, softmax over the outputs (5 a logit); out: k
            # weights + indices
            router = g.add("Router",
                           2 * T * H * R + 5 * T * R + softmax_weight,
                           A * (T * H + H * R + score_bias + 2 * T * k),
                           2 * T * k, H * R + score_bias, [x])
        shared = []
        if shared_inter:
            # the always-on experts: gate, up, down, silu * up
            shared = [g.add(
                "SharedExpert",
                2 * T * 3 * H * shared_inter + 4 * T * shared_inter,
                A * (2 * T * H + 3 * H * shared_inter), T * H,
                3 * H * shared_inter, [x])]
        # gate, up, down for the pairs routed to the experts held here,
        # silu * up (4 a value); balanced routing: every held expert
        # that has a token is read once. A pair routed to a zero-compute
        # expert is not here: it costs the combine its weighted add
        experts = g.add("Experts",
                        2 * pairs * 3 * H * expert_inter
                        + 4 * pairs * expert_inter,
                        A * (2 * pairs * H
                             + min(held, pairs) * 3 * H * expert_inter),
                        pairs * H, held * 3 * H * expert_inter, [router])
        return router, experts, shared

    def combine(op_type, experts, stream, router, shared, x):
        """Weighted sum of the routed outputs (+ the shared one; + w . x
        for the pairs routed to an identity expert, which reads the
        block's input ``x``) + residual."""
        op = g.add(op_type,
                   2 * (pairs + zero_pairs) * H + T * H * (1 + len(shared))
                   + branch_scale,
                   A * (pairs * H + pairs + zero_pairs
                        + T * H * (2 + len(shared) + bool(Z))),
                   T * H, 0,
                   [experts, stream, router, *shared] + [x] * bool(Z))
        # Experts reads x too; the edge is listed behind the combine's,
        # where the pinned profiles of the older families have it
        g.edges.append((x, experts))
        return op

    def shortcut_layer(stream, i):
        """A layer of ``shortcut_sub_blocks`` attention + dense-FFN
        sub-blocks whose ONE expert block reads the first sub-block's
        normed state and is added back after the last one's FFN
        (shortcut-connected experts: Router -> Experts run beside every
        op between)."""
        for block in range(sub_blocks):
            out_proj = attention(stream, i)
            x = norm("PostAttnNorm", [out_proj])
            if block == 0:
                x_branch = x
                router, experts, shared = expert_block(x)
            stream = dense_mlp(x, out_proj)
        return combine("ShortcutCombineResidual", experts, stream, router,
                       shared, x_branch)

    def layer(stream, i=None):
        """Decoder layer ``i`` of the published stack (None: an MTP
        module's expert layer) on the residual stream op ``stream``;
        returns the op that carries the stream out."""
        if sub_blocks:
            return shortcut_layer(stream, i)
        out_proj = attention(stream, i)
        if not E:
            dense = True
        elif freq is not None:
            dense = freq[i] == 0
        else:
            dense = i is not None and i < cut["leading_dense"]
        x = norm("PostAttnNorm", [out_proj])
        if dense:
            return dense_mlp(x, out_proj)
        router, experts, shared = expert_block(x)
        return combine("CombineResidual", experts, out_proj, router,
                       shared, x)

    embedding = g.add("Embedding", T * H * ("scale_emb" in config),
                      A * 2 * T * H + 4 * T, T * H, V * H)
    stream = embedding
    for i in range(cut["leading_dense"] + cut["following"]):
        stream = layer(stream, i)
    streams = [stream]
    for _ in range(n_mtp):
        # h' = [RMSNorm(h_t) ; RMSNorm(Emb(x_{t+1}))] W_eh (2H -> H),
        # then one full expert layer; the embedding is the main model's
        # output shifted by a token
        h = norm("MTPHiddenNorm", [streams[-1]])
        e = norm("MTPEmbedNorm", [embedding])
        proj = g.add("MTPProj", 2 * T * 2 * H * H,
                     A * (2 * T * H + 2 * H * H + T * H), T * H,
                     2 * H * H, [h, e])
        streams.append(layer(proj))
    # the final norm and the head (+ loss) hold their parameters once
    # and run once per stream: the main model's, then each MTP module's
    tokens = T * len(streams)
    final = norm("FinalNorm", streams, tokens)
    # logits + softmax cross-entropy (5 a logit); output = the logits
    g.add("LMHeadLoss", 2 * tokens * H * V + 5 * tokens * V
          + tokens * H * ("dim_model_base" in config),
          A * (tokens * H + H * V + tokens * V), tokens * V, H * V, [final])
    return g


def op_costs(config: dict, seq_len: int, micro_batch: int,
             layers: Optional[dict] = None,
             experts_held: Optional[int] = None) -> List[dict]:
    """Forward ops in profile order, each ``{"op_type", "flops",
    "bytes", "out_elems", "params"}``."""
    return build_graph(config, seq_len, micro_batch, layers,
                       experts_held).ops


def forward_time(cost: dict) -> float:
    """Seconds of one forward op on the simulated worker: the larger of
    its compute and its memory roofline."""
    return max(cost["flops"] / A100.peak_flops,
               cost["bytes"] / A100.memory_bandwidth)


#: forward ops whose FLOPs grow as S^2: a full causal core (over heads'
#: own keys or over the latent ones), the sparse indexer's score and the
#: block score over compressed keys (a windowed, top-k or linear core
#: grows as S)
QUADRATIC_OPS = ("AttnCore", "IndexScoreTopK", "BlockScoreTopK",
                 "LatentAttnCore")


def profile_text(config: dict, seq_len: int, micro_batch: int,
                 layers: Optional[dict] = None,
                 experts_held: Optional[int] = None,
                 training_state: Optional[dict] = None) -> str:
    """The PipeDream ``.txt`` profile. Times carry 17 significant
    digits (a 17 us op must not round to 0.000017)."""
    resident, synced = PARAM_BYTES, None
    if training_state is not None:
        resident = training_state["resident_bytes_per_parameter"]
        synced = training_state["synced_bytes_per_parameter"]
    graph = build_graph(config, seq_len, micro_batch, layers, experts_held)
    lines = []
    for i, cost in enumerate(graph.ops, start=1):
        fwd = forward_time(cost)
        line = (
            f"node{i} -- {cost['op_type']}(id={i}) -- "
            f"forward_compute_time={fwd:.17g}, "
            f"backward_compute_time={BACKWARD_OVER_FORWARD * fwd:.17g}, "
            f"activation_size={cost['out_elems'] * ACT_BYTES:.1f}, "
            f"parameter_size={cost['params'] * resident:.1f}")
        if synced is not None:
            line += f", sync_size={cost['params'] * synced:.1f}"
        lines.append(line)
    for u, v in graph.profile_edges():
        lines.append(f"node{u} -- node{v}")
    return "\n".join(lines) + "\n"


def model_name(config: dict, seq_len: int, micro_batch: int) -> str:
    return f"{config['model_type']}_s{int(seq_len)}_b{int(micro_batch)}"


def write_profiles(out_dir: str, config: dict,
                   shapes: Sequence[Dict[str, int]],
                   layers: Optional[dict] = None,
                   experts_held: Optional[int] = None,
                   training_state: Optional[dict] = None) -> List[str]:
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
            fh.write(profile_text(config, seq_len, micro_batch, layers,
                                  experts_held, training_state))
        paths.append(path)
    return paths


def dataset_id(config: dict, shapes: Sequence[Dict[str, int]],
               layers: Optional[dict] = None,
               experts_held: Optional[int] = None,
               training_state: Optional[dict] = None) -> tuple:
    """What identifies the generated profiles wherever they were
    written: the config's content, the shapes, the cut and stated sizes
    (where given) and the cost model's constants."""
    body = {"config": config,
            "shapes": [[int(s["seq_len"]), int(s["micro_batch"])]
                       for s in shapes],
            "costs": [ACT_BYTES, PARAM_BYTES, BACKWARD_OVER_FORWARD,
                      A100.peak_flops, A100.memory_bandwidth]}
    stated = {"layers": layers, "experts_held": experts_held,
              "training_state": training_state}
    body.update({k: v for k, v in stated.items() if v is not None})
    return ("architecture", hashlib.sha1(
        json.dumps(body, sort_keys=True).encode()).hexdigest())
