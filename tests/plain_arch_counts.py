"""Independent plain counts of a ``glm_moe_dsa``, of a
``mimo_v2_flash``, of an ``afmoe`` (Trinity), of a ``minicpm_sala``, of
a ``longcat_flash`` and of a ``KeyeVL2`` training step: per op the
parameters, the forward FLOPs and the elements of the output tensor (for
``afmoe``, ``minicpm_sala``, ``longcat_flash`` and ``KeyeVL2`` also the
bytes moved and the edges), written straight from the layer equations
(ISSUE 30, 32, 36, 39, 48 and 52, Tentpole step 1) and importing nothing
from
``ddls_tpu/graphs/arch.py``, which ``tests/test_arch_graphs.py`` holds
to them op by op.

Conventions: 2 FLOPs a multiply-accumulate; RMSNorm 4 an element; RoPE 3
an element it turns; softmax / sigmoid-and-select 5 a score; SwiGLU's
silu * up 4 a value; a residual or a sum of streams 1 an element. ``x``
is the normed hidden state, T = S x B tokens.
"""


def keys_read(S, topk):
    """sum over queries t = 1..S of min(t, topk)."""
    return sum(min(t, topk) for t in range(1, S + 1))


def _rmsnorm(name, tokens, width):
    return (name, width, 4 * tokens * width, tokens * width)


def _attention(c, S, B):
    """MLA + DSA indexer + sparse core + out-projection, 9 ops after
    the input norm."""
    T = S * B
    H, n = c["hidden_size"], c["num_attention_heads"]
    rq, rkv = c["q_lora_rank"], c["kv_lora_rank"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    ni, di, topk = c["index_n_heads"], c["index_head_dim"], c["index_topk"]
    kept = min(S, topk)
    return [
        _rmsnorm("InputNorm", T, H),
        # c_q = RMSNorm(x W_qa)
        ("QAProj", H * rq + rq, 2 * T * H * rq + 4 * T * rq, T * rq),
        # q = c_q W_qb, RoPE on the 64 of each head
        ("QBProj", rq * n * (dn + dr),
         2 * T * rq * n * (dn + dr) + 3 * T * n * dr, T * n * (dn + dr)),
        # [c_kv ; k_r] = x W_kva, RMSNorm(c_kv), RoPE(k_r)
        ("KVAProj", H * (rkv + dr) + rkv,
         2 * T * H * (rkv + dr) + 4 * T * rkv + 3 * T * dr, T * (rkv + dr)),
        # [k_n ; v] = c_kv W_kvb
        ("KVBProj", rkv * n * (dn + dv), 2 * T * rkv * n * (dn + dv),
         T * n * (dn + dv)),
        # q^I = c_q W_Iq ; k^I = Norm(x W_Ik) ; w = x W_Iw ; RoPE on the
        # rope part of the ni query heads and of the one key
        ("IndexerProj", rq * ni * di + H * di + H * ni + di,
         2 * T * (rq * ni * di + H * di + H * ni) + 4 * T * di
         + 3 * T * (ni + 1) * dr,
         T * (ni * di + di + ni)),
        # I[t,s] = sum_j w[t,j] ReLU(q^I[t,j] . k^I[s]), s <= t; top-k
        ("IndexScoreTopK", 0, B * S * S / 2 * ni * (2 * di + 2), T * kept),
        # o_t = sum_{s in S_t} softmax(q_t . [k_n ; k_r] / sqrt(dqk)) v_s
        ("SparseAttnCore", 0,
         B * keys_read(S, topk) * n * (2 * (dn + dr) + 2 * dv + 5),
         T * n * dv),
        # y = o W_o + residual
        ("OutProjResidual", n * dv * H, 2 * T * n * dv * H + T * H, T * H),
        _rmsnorm("PostAttnNorm", T, H),
    ]


def _dense_layer(c, S, B):
    T, H, I = S * B, c["hidden_size"], c["intermediate_size"]
    return _attention(c, S, B) + [
        ("DenseMLPResidual", 3 * H * I,
         2 * T * 3 * H * I + 4 * T * I + T * H, T * H)]


def _expert_layer(c, S, B, held):
    T, H = S * B, c["hidden_size"]
    E, k = c["n_routed_experts"], c["num_experts_per_tok"]
    I = c["moe_intermediate_size"]
    Is = c["n_shared_experts"] * I
    pairs = T * k * held / E          # balanced routing, this pod's share
    return _attention(c, S, B) + [
        # s = sigmoid(x W_r), top-k of s + b, weights normalised, scaled
        ("Router", H * E + E, 2 * T * H * E + 5 * T * E + 3 * T * k,
         2 * T * k),
        ("SharedExpert", 3 * H * Is, 2 * T * 3 * H * Is + 4 * T * Is, T * H),
        ("Experts", held * 3 * H * I,
         2 * pairs * 3 * H * I + 4 * pairs * I, pairs * H),
        # shared + weighted routed + residual
        ("CombineResidual", 0, 2 * pairs * H + 2 * T * H, T * H)]


def plain_counts(c, S, B, leading_dense=None, following=None, held=None):
    """[(op_type, parameters, forward FLOPs, output elements)] in the
    profile's order: embedding, dense layers, expert layers, the MTP
    modules, final norm, head + loss."""
    if leading_dense is None:
        leading_dense = c["first_k_dense_replace"]
    if following is None:
        following = c["num_hidden_layers"] - c["first_k_dense_replace"]
    if held is None:
        held = c["n_routed_experts"]
    T, H, V = S * B, c["hidden_size"], c["vocab_size"]
    ops = [("Embedding", V * H, 0, T * H)]
    for _ in range(leading_dense):
        ops += _dense_layer(c, S, B)
    for _ in range(following):
        ops += _expert_layer(c, S, B, held)
    streams = 1
    for _ in range(c["num_nextn_predict_layers"]):
        # h' = [RMSNorm(h) ; RMSNorm(Emb(x_{t+1}))] W_eh, one expert layer
        ops += [_rmsnorm("MTPHiddenNorm", T, H),
                _rmsnorm("MTPEmbedNorm", T, H),
                ("MTPProj", 2 * H * H, 2 * T * 2 * H * H, T * H)]
        ops += _expert_layer(c, S, B, held)
        streams += 1
    # final norm and head: parameters once, a pass per stream
    ops += [_rmsnorm("FinalNorm", streams * T, H),
            ("LMHeadLoss", H * V,
             2 * streams * T * H * V + 5 * streams * T * V,
             streams * T * V)]
    return ops


# ========================================================== mimo_v2_flash
def _mimo_attention(c, S, B, window):
    """GQA with split head sizes: q and k heads of ``head_dim``, v heads
    of ``v_head_dim``; a full causal core, or a sliding-window one with
    its own kv head count and a sink logit a head. 4 ops after the
    input norm."""
    T, H = S * B, c["hidden_size"]
    pre = "swa_" if window else ""
    n = c[pre + "num_attention_heads"]
    g = c[pre + "num_key_value_heads"]
    dqk, dv = c[pre + "head_dim"], c[pre + "v_head_dim"]
    rotary = round(c["partial_rotary_factor"] * dqk)
    width = n * dqk + g * dqk + g * dv
    if window:
        keys = keys_read(S, c["sliding_window"])
        sink = n if c["add_swa_attention_sink_bias"] else 0
        name = "WindowAttnCore"
    else:
        keys = S * (S + 1) // 2
        sink = n if c["add_full_attention_sink_bias"] else 0
        name = "AttnCore"
    return [
        _rmsnorm("InputNorm", T, H),
        # [q ; k ; v] = x W_qkv, RoPE on the rotary part of the q and k
        # heads, v scaled
        ("QKVProj", H * width,
         2 * T * H * width + 3 * T * (n + g) * rotary + T * g * dv,
         T * width),
        # o_t = sum_s exp(q_t.k_s) v_s / (exp(b_h) + sum_s exp(q_t.k_s))
        (name, sink, B * keys * n * (2 * dqk + 2 * dv + 5) + T * sink,
         T * n * dv),
        ("OutProjResidual", n * dv * H, 2 * T * n * dv * H + T * H, T * H),
        _rmsnorm("PostAttnNorm", T, H),
    ]


def plain_counts_mimo(c, S, B, layers=None, held=None):
    """[(op_type, parameters, forward FLOPs, output elements)] of the
    first ``layers`` layers of the published per-layer lists."""
    if layers is None:
        layers = c["num_hidden_layers"]
    if held is None:
        held = c["n_routed_experts"]
    T, H, V = S * B, c["hidden_size"], c["vocab_size"]
    E, k = c["n_routed_experts"], c["num_experts_per_tok"]
    pairs = T * k * held / E
    ops = [("Embedding", V * H, 0, T * H)]
    for i in range(layers):
        ops += _mimo_attention(c, S, B, c["hybrid_layer_pattern"][i] == 1)
        if c["moe_layer_freq"][i] == 0:
            I = c["intermediate_size"]
            ops.append(("DenseMLPResidual", 3 * H * I,
                        2 * T * 3 * H * I + 4 * T * I + T * H, T * H))
            continue
        I = c["moe_intermediate_size"]
        ops += [
            ("Router", H * E + E, 2 * T * H * E + 5 * T * E + 3 * T * k,
             2 * T * k),
            ("Experts", held * 3 * H * I,
             2 * pairs * 3 * H * I + 4 * pairs * I, pairs * H),
            # weighted routed outputs + residual: no shared expert
            ("CombineResidual", 0, 2 * pairs * H + T * H, T * H)]
    ops += [_rmsnorm("FinalNorm", T, H),
            ("LMHeadLoss", H * V, 2 * T * H * V + 5 * T * V, T * V)]
    return ops


# ================================================================= afmoe
def plain_counts_trinity(c, S, B):
    """``(ops, edges)`` of a WHOLE ``afmoe`` model (Trinity-Mini: nothing
    is cut) over B sequences of S tokens. ``ops``: ``[(op_type,
    parameters, forward FLOPs, output elements, bytes moved)]`` in the
    profile's order; ``edges``: ``{(producer, consumer)}`` by 1-based
    position in ``ops``, one for every tensor an op reads from another.

    Layer i (x the normed stream, T = S B tokens, H = hidden_size, n q
    heads and g kv heads of d): ``[q ; k ; v] = x W_qkv`` (H -> n d +
    2 g d), RoPE on the whole of each q and k head; ``o_t = sum_s
    softmax_s(q_t . k_s / sqrt(d)) v_s`` over the min(t, sliding_window)
    latest keys where ``layer_types[i]`` is ``sliding_attention`` and
    over all t keys where it is ``full_attention``; ``y = o W_o +
    stream``. The first ``num_dense_layers`` layers then run SwiGLU at
    ``intermediate_size``; the others ``s = sigmoid(x W_r)`` (H ->
    ``num_experts``), the top ``num_experts_per_tok`` of ``s + b``,
    weights ``s_sel / sum s_sel x route_scale``, a shared SwiGLU at
    ``num_shared_experts x moe_intermediate_size`` on every token, the
    experts' SwiGLU at ``moe_intermediate_size`` over the T k balanced
    token-expert pairs, and their weighted sum + the shared output +
    the stream. Bytes: every tensor and weight read or written once at
    2 B an element (token ids 4 B)."""
    T, H, V = S * B, c["hidden_size"], c["vocab_size"]
    n, g, d = (c["num_attention_heads"], c["num_key_value_heads"],
               c["head_dim"])
    E, k = c["num_experts"], c["num_experts_per_tok"]
    I, Ie = c["intermediate_size"], c["moe_intermediate_size"]
    Is = c["num_shared_experts"] * Ie
    width = n * d + 2 * g * d
    pairs = T * k
    ops, edges = [], set()

    def add(kind, params, flops, out, nbytes, reads=()):
        ops.append((kind, params, flops, out, nbytes))
        edges.update((r, len(ops)) for r in reads)
        return len(ops)

    def rmsnorm(kind, reads):
        return add(kind, H, 4 * T * H, T * H, 2 * (2 * T * H + H), reads)

    stream = add("Embedding", V * H, 0, T * H, 2 * 2 * T * H + 4 * T)
    for i, kind in enumerate(c["layer_types"]):
        x = rmsnorm("InputNorm", [stream])
        qkv = add("QKVProj", H * width,
                  2 * T * H * width + 3 * T * (n + g) * d, T * width,
                  2 * (T * H + H * width + T * width), [x])
        if kind == "sliding_attention":
            keys, core = keys_read(S, c["sliding_window"]), "WindowAttnCore"
        else:
            keys, core = S * (S + 1) // 2, "AttnCore"
        o = add(core, 0, B * keys * n * (2 * d + 2 * d + 5), T * n * d,
                2 * (T * width + T * n * d), [qkv])
        y = add("OutProjResidual", n * d * H, 2 * T * n * d * H + T * H,
                T * H, 2 * (T * n * d + n * d * H + 2 * T * H),
                [o, stream])
        x = rmsnorm("PostAttnNorm", [y])
        if i < c["num_dense_layers"]:
            stream = add("DenseMLPResidual", 3 * H * I,
                         2 * T * 3 * H * I + 4 * T * I + T * H, T * H,
                         2 * (3 * T * H + 3 * H * I), [x, y])
            continue
        r = add("Router", H * E + E, 2 * T * H * E + 5 * T * E + 3 * T * k,
                2 * T * k, 2 * (T * H + H * E + E + 2 * T * k), [x])
        sh = add("SharedExpert", 3 * H * Is, 2 * T * 3 * H * Is + 4 * T * Is,
                 T * H, 2 * (2 * T * H + 3 * H * Is), [x])
        ex = add("Experts", E * 3 * H * Ie,
                 2 * pairs * 3 * H * Ie + 4 * pairs * Ie, pairs * H,
                 2 * (2 * pairs * H + min(E, pairs) * 3 * H * Ie), [r, x])
        stream = add("CombineResidual", 0, 2 * pairs * H + 2 * T * H, T * H,
                     2 * (pairs * H + pairs + 3 * T * H), [ex, y, r, sh])
    f = rmsnorm("FinalNorm", [stream])
    add("LMHeadLoss", H * V, 2 * T * H * V + 5 * T * V, T * V,
        2 * (T * H + H * V + T * V), [f])
    return ops, edges


# ========================================================== minicpm_sala
def compressed_keys_seen(S, kernel_size, kernel_stride):
    """sum over queries t = 1..S of the compressed keys wholly behind
    them: key j is the mean of tokens j stride + 1 .. j stride + kernel,
    seen by query t once t >= j stride + kernel."""
    return sum((t - kernel_size) // kernel_stride + 1
               for t in range(kernel_size, S + 1))


def plain_counts_sala(c, S, B):
    """``(ops, edges)`` of a WHOLE ``minicpm_sala`` model (MiniCPM-SALA:
    dense, nothing cut) over B sequences of S tokens, in the shape
    :func:`plain_counts_trinity` returns. ``c`` is the public config
    with the architecture file's ``modeling`` block over it
    (``sparse_config``, ``lightning_chunk_size``). Written from ISSUE
    39's equations; T = S B, H = hidden_size, x the normed stream.

    Both mixers: ``[q ; k ; v] = x W`` with RMSNorm on q and k
    (``qk_norm``: 4 an element, a weight an element), ``g = x W_g``
    where the mixer's gate key is true, ``y = scale_depth / sqrt(L) .
    ((o [RMSNormed] . sigmoid(g)) W_o) + stream`` (gate 3 an element:
    sigmoid 2 as in silu . up's 4, the product 1; the branch scale 1 an
    element of y), then SwiGLU at ``intermediate_size`` under the same
    branch scale. ``scale_emb`` costs 1 an embedding element and
    ``hidden_size / dim_model_base`` 1 an element the head reads.

    ``lightning-attn`` (n = ``lightning_nh`` q and ``lightning_nkv`` kv
    heads of ``lightning_head_dim``, RoPE on q and k): per head, with a
    fixed decay l, ``S_t = l S_{t-1} + k_t^T v_t`` (d x d) and ``o_t =
    lightning_scale . q_t S_t``, in chunks of C tokens: intra-chunk
    ``[(Q K^T) . M] V`` over the causal half of the chunk (2 d, 1, 2 d
    a pair), inter-chunk ``Q S_prev`` and the update ``K^T V`` (2 d^2 a
    token each), the output's decay, sum and scale (3 an element), the
    update's decayed keys (1 an element) and the state's decay and add
    (2 d^2 a chunk); bytes: q, k, v in, o out, a d x d state a head and
    chunk written once. ``use_output_norm`` / ``use_output_gate`` are
    this mixer's.

    ``minicpm4`` (n q heads and ``num_key_value_heads`` kv heads of
    ``head_dim``; ``attn_use_rope`` false: no RoPE; its gate is
    ``attn_use_output_gate``, it has no output norm): up to
    ``sparse_config.dense_len`` tokens a full causal core (t keys a
    query). Beyond: ``KCompress`` (the mean of ``kernel_size`` keys
    every ``kernel_stride``: kernel_size an element), ``BlockScoreTopK``
    (softmax over the compressed keys behind the query, 2 d + 5 a score
    and q head; out = ``topk`` block indices a query and kv head; the
    scores are never written) and ``BlockSparseAttnCore`` (softmax
    attention over min(t, topk block_size + window_size + init_blocks
    block_size) keys)."""
    T, H, V, I = S * B, c["hidden_size"], c["vocab_size"], c[
        "intermediate_size"]
    sparse, C = c["sparse_config"], c["lightning_chunk_size"]
    ops, edges = [], set()

    def add(kind, params, flops, out, nbytes, reads=()):
        ops.append((kind, params, flops, out, nbytes))
        edges.update((r, len(ops)) for r in reads)
        return len(ops)

    def rmsnorm(kind, reads):
        return add(kind, H, 4 * T * H, T * H, 2 * (2 * T * H + H), reads)

    def out_proj(o, stream, gate, width, normed):
        return add("OutProjResidual", width * H + normed * width,
                   2 * T * width * H + 2 * T * H + 3 * T * width
                   + normed * 4 * T * width,
                   T * H,
                   2 * (2 * T * width + width * H + normed * width
                        + 2 * T * H),
                   [o, stream, gate])

    stream = add("Embedding", V * H, T * H, T * H, 2 * 2 * T * H + 4 * T)
    for kind in c["mixer_types"]:
        x = rmsnorm("InputNorm", [stream])
        if kind == "lightning-attn":
            n, g, d = (c["lightning_nh"], c["lightning_nkv"],
                       c["lightning_head_dim"])
            width = (n + 2 * g) * d
            normed = (n + g) * d
            qkv = add("QKVProj", H * width + normed,
                      2 * T * H * width + 4 * T * normed
                      + 3 * T * (n + g) * d, T * width,
                      2 * (T * H + H * width + normed + T * width), [x])
            gate = add("GateProj", H * n * d, 2 * T * H * n * d, T * n * d,
                       2 * (T * H + H * n * d + T * n * d), [x])
            chunks = [C] * (S // C) + [S % C] * (S % C > 0)
            flops = sum(k * (k + 1) // 2 * (4 * d + 1) + 4 * k * d * d
                        + 4 * k * d + 2 * d * d for k in chunks)
            o = add("LinearAttnCore", 0, B * n * flops, T * n * d,
                    2 * (T * width + T * n * d
                         + B * len(chunks) * n * d * d), [qkv])
            y = out_proj(o, stream, gate, n * d, normed=1)
        else:
            assert kind == "minicpm4", kind
            n, g, d = (c["num_attention_heads"], c["num_key_value_heads"],
                       c["head_dim"])
            width = (n + 2 * g) * d
            normed = (n + g) * d
            qkv = add("QKVProj", H * width + normed,
                      2 * T * H * width + 4 * T * normed, T * width,
                      2 * (T * H + H * width + normed + T * width), [x])
            gate = add("GateProj", H * n * d, 2 * T * H * n * d, T * n * d,
                       2 * (T * H + H * n * d + T * n * d), [x])
            pair = n * (2 * d + 2 * d + 5)
            if S <= sparse["dense_len"]:
                o = add("AttnCore", 0, B * S * (S + 1) // 2 * pair,
                        T * n * d, 2 * (T * width + T * n * d), [qkv])
            else:
                ks, stride = sparse["kernel_size"], sparse["kernel_stride"]
                rows = (S - ks) // stride + 1
                kc = add("KCompress", 0, B * rows * g * d * ks,
                         B * rows * g * d,
                         2 * (T * g * d + B * rows * g * d), [qkv])
                blocks = -(-S // sparse["block_size"])
                kept = min(blocks, sparse["topk"])
                select = add(
                    "BlockScoreTopK", 0,
                    B * compressed_keys_seen(S, ks, stride) * n
                    * (2 * d + 5),
                    T * g * kept,
                    2 * (T * n * d + B * rows * g * d + T * g * kept),
                    [qkv, kc])
                reach = (sparse["topk"] * sparse["block_size"]
                         + sparse["window_size"]
                         + sparse["init_blocks"] * sparse["block_size"])
                o = add("BlockSparseAttnCore", 0,
                        B * keys_read(S, reach) * pair, T * n * d,
                        2 * (T * width + T * g * kept + T * n * d),
                        [qkv, select])
            y = out_proj(o, stream, gate, n * d, normed=0)
        x = rmsnorm("PostAttnNorm", [y])
        stream = add("DenseMLPResidual", 3 * H * I,
                     2 * T * 3 * H * I + 4 * T * I + 2 * T * H, T * H,
                     2 * (3 * T * H + 3 * H * I), [x, y])
    f = rmsnorm("FinalNorm", [stream])
    add("LMHeadLoss", H * V, 2 * T * H * V + 5 * T * V + T * H, T * V,
        2 * (T * H + H * V + T * V), [f])
    return ops, edges


# ========================================================= longcat_flash
def plain_counts_longcat(c, S, B, layers=None, held=None):
    """``(ops, edges)`` of ``layers`` double layers (None: all
    ``num_layers``) of a ``longcat_flash`` model holding ``held`` of its
    FFN experts (None: all) over B sequences of S tokens, in the shape
    :func:`plain_counts_sala` returns. ``c`` is the public config with
    the architecture file's ``modeling`` block over it. Written from
    ISSUE 48's equations; T = S B, H = hidden_size, ``x`` a normed
    stream.

    A layer is TWO sub-blocks — ``x = RMSNorm(h)``; ``c_q = RMSNorm(x
    W_qa)`` (. sqrt(H / q_lora_rank) where ``mla_scale_q_lora``: 1 an
    element); ``q = c_q W_qb`` to n heads of nope + rope, RoPE (3) on
    the rope part; ``[c_kv ; k_r] = x W_kva``, ``c_kv`` RMS-normed (and
    scaled where ``mla_scale_kv_lora``), RoPE on the one ``k_r``;
    ``[k_n ; v] = c_kv W_kvb``; a FULL causal core, query t reading its
    t keys at 2 (nope + rope) + 2 v + 5 a key and head; ``h += o W_o``;
    ``x' = RMSNorm(h)``; ``h += SwiGLU(x')`` at ``ffn_hidden_size`` —
    and ONE expert block that reads the FIRST sub-block's ``x'`` and is
    added after the SECOND sub-block's FFN: ``p = softmax(x' W_r)`` over
    R = ``n_routed_experts`` + ``zero_expert_num`` outputs (5 a logit),
    top-``moe_topk`` of p + b (b a parameter an output where the
    modeling block states ``e_score_correction_bias``), weights ``p[idx]
    . routed_scaling_factor`` (1 a selected weight where the key is
    stated, not renormalised); under balanced routing the ``held`` FFN
    experts see T k held / R pairs (gate, up, down, silu . up's 4 a
    value) and the identity experts T k Z / R pairs, WHOLE on every pod,
    which cost the combine 2 H a pair (``w . x'``) like any other pair
    and read ``x'`` (T H elements), no parameter."""
    T, H, V = S * B, c["hidden_size"], c["vocab_size"]
    n, rq, rkv = c["num_attention_heads"], c["q_lora_rank"], c["kv_lora_rank"]
    dn, dr, dv = (c["qk_nope_head_dim"], c["qk_rope_head_dim"],
                  c["v_head_dim"])
    dqk = dn + dr
    I, Ie = c["ffn_hidden_size"], c["expert_ffn_hidden_size"]
    E, Z, k = c["n_routed_experts"], c.get("zero_expert_num", 0), c[
        "moe_topk"]
    R = E + Z
    layers = c["num_layers"] if layers is None else layers
    held = E if held is None else held
    scale_q = T * rq if c.get("mla_scale_q_lora") else 0
    scale_kv = T * rkv if c.get("mla_scale_kv_lora") else 0
    bias = R if c.get("e_score_correction_bias") else 0
    scaled = T * k if "routed_scaling_factor" in c else 0
    pairs, zero_pairs = T * k * held / R, T * k * Z / R
    ops, edges = [], set()

    def add(kind, params, flops, out, nbytes, reads=()):
        ops.append((kind, params, flops, out, nbytes))
        edges.update((r, len(ops)) for r in reads)
        return len(ops)

    def rmsnorm(kind, reads):
        return add(kind, H, 4 * T * H, T * H, 2 * (2 * T * H + H), reads)

    stream = add("Embedding", V * H, 0, T * H, 2 * 2 * T * H + 4 * T)
    for _ in range(layers):
        for block in (0, 1):
            x = rmsnorm("InputNorm", [stream])
            c_q = add("QAProj", H * rq + rq,
                      2 * T * H * rq + 4 * T * rq + scale_q, T * rq,
                      2 * (T * H + H * rq + rq + T * rq), [x])
            q = add("QBProj", rq * n * dqk,
                    2 * T * rq * n * dqk + 3 * T * n * dr, T * n * dqk,
                    2 * (T * rq + rq * n * dqk + T * n * dqk), [c_q])
            c_kv = add("KVAProj", H * (rkv + dr) + rkv,
                       2 * T * H * (rkv + dr) + 4 * T * rkv + 3 * T * dr
                       + scale_kv, T * (rkv + dr),
                       2 * (T * H + H * (rkv + dr) + rkv
                            + T * (rkv + dr)), [x])
            kv = add("KVBProj", rkv * n * (dn + dv),
                     2 * T * rkv * n * (dn + dv), T * n * (dn + dv),
                     2 * (T * rkv + rkv * n * (dn + dv)
                          + T * n * (dn + dv)), [c_kv])
            o = add("LatentAttnCore", 0,
                    B * (S * (S + 1) // 2) * n * (2 * dqk + 2 * dv + 5),
                    T * n * dv,
                    2 * (T * n * dqk + T * n * (dn + dv) + T * dr
                         + T * n * dv), [q, kv, c_kv])
            y = add("OutProjResidual", n * dv * H,
                    2 * T * n * dv * H + T * H, T * H,
                    2 * (T * n * dv + n * dv * H + 2 * T * H), [o, stream])
            xp = rmsnorm("PostAttnNorm", [y])
            if block == 0:
                x0 = xp
                router = add("Router", H * R + bias,
                             2 * T * H * R + 5 * T * R + scaled, 2 * T * k,
                             2 * (T * H + H * R + bias + 2 * T * k), [xp])
                experts = add("Experts", held * 3 * H * Ie,
                              2 * pairs * 3 * H * Ie + 4 * pairs * Ie,
                              pairs * H,
                              2 * (2 * pairs * H
                                   + min(held, pairs) * 3 * H * Ie),
                              [router, xp])
            stream = add("DenseMLPResidual", 3 * H * I,
                         2 * T * 3 * H * I + 4 * T * I + T * H, T * H,
                         2 * (3 * T * H + 3 * H * I), [xp, y])
        # h = h + m, after the SECOND FFN: the routed outputs and, for
        # the identity pairs, x'_0 itself, each under its weight
        stream = add("ShortcutCombineResidual", 0,
                     2 * (pairs + zero_pairs) * H + T * H, T * H,
                     2 * (pairs * H + pairs + zero_pairs
                          + (3 if Z else 2) * T * H),
                     [experts, stream, router] + [x0] * (Z > 0))
    f = rmsnorm("FinalNorm", [stream])
    add("LMHeadLoss", H * V, 2 * T * H * V + 5 * T * V, T * V,
        2 * (T * H + H * V + T * V), [f])
    return ops, edges


# =============================================================== KeyeVL2
def plain_counts_keye(c, S, B, layers=None):
    """``(ops, edges)`` of the first ``layers`` layers (None: all
    ``num_hidden_layers``) of a ``KeyeVL2`` language model over B
    sequences of S tokens, in the shape :func:`plain_counts_sala`
    returns. ``c`` is the public config. Written from ISSUE 52's
    equations; T = S B, H = hidden_size, ``x`` a normed stream.

    A layer: ``x = RMSNorm(h)``; ``[q ; k ; v] = x W_qkv`` to n q and g
    kv heads of d, M-RoPE (3 an element) on the whole of every q and k
    head; the indexer ``q^I = x W_Iq`` (ni heads of di), ``k^I = Norm(x
    W_Ik)`` (ONE key head: norm 4 an element, a weight an element), ``w
    = x W_Iw`` (ni), RoPE on each q^I head and on k^I; ``I[t, s] =
    sum_j w[t, j] ReLU(q^I[t, j] . k^I[s])`` for s <= t over the causal
    half of S x S (2 di + 2 a head and pair), the top ``topk`` of each
    row, out = the indices; ``o[t, h] = sum_{s in S_t} softmax_s(q[t,
    h] . k[s, g(h)] / sqrt(d)) v[s, g(h)]``, query t reading min(t,
    topk) keys at 2 d + 2 d + 5 a key and q head; ``y = o W_o + h``;
    ``x' = RMSNorm(y)``; on a layer that routes ``p = softmax(x' W_r)``
    over E (5 a logit), top k, weights ``p_sel / sum p_sel`` (2 a
    selected weight where ``norm_topk_prob``), E SwiGLU experts at
    ``moe_intermediate_size`` over T k balanced pairs (silu . up 4 a
    value), ``h = sum w . expert + y``; on a dense one (i in
    ``mlp_only_layers`` or (i + 1) mod ``decoder_sparse_step`` != 0)
    SwiGLU at ``intermediate_size``."""
    T, H, V = S * B, c["hidden_size"], c["vocab_size"]
    n, g, d = (c["num_attention_heads"], c["num_key_value_heads"],
               c["head_dim"])
    sa = c["sa_config"]
    ni, di, topk = (sa["indexer_num_heads"], sa["indexer_head_dim"],
                    sa["topk"])
    E, k = c["num_experts"], c["num_experts_per_tok"]
    I, Ie = c["intermediate_size"], c["moe_intermediate_size"]
    layers = c["num_hidden_layers"] if layers is None else layers
    step, only = c.get("decoder_sparse_step", 1), c.get("mlp_only_layers",
                                                        [])
    renorm = 2 * T * k if c.get("norm_topk_prob") else 0
    qkv = n * d + 2 * g * d
    kept = min(S, topk)
    pairs = T * k
    ops, edges = [], set()

    def add(kind, params, flops, out, nbytes, reads=()):
        ops.append((kind, params, flops, out, nbytes))
        edges.update((r, len(ops)) for r in reads)
        return len(ops)

    def rmsnorm(kind, reads):
        return add(kind, H, 4 * T * H, T * H, 2 * (2 * T * H + H), reads)

    stream = add("Embedding", V * H, 0, T * H, 2 * 2 * T * H + 4 * T)
    for i in range(layers):
        x = rmsnorm("InputNorm", [stream])
        proj = add("QKVProj", H * qkv,
                   2 * T * H * qkv + 3 * T * (n + g) * d, T * qkv,
                   2 * (T * H + H * qkv + T * qkv), [x])
        index_w = H * ni * di + H * di + H * ni
        index_out = ni * di + di + ni
        idx = add("IndexerProj", index_w + di,
                  2 * T * index_w + 4 * T * di + 3 * T * (ni + 1) * di,
                  T * index_out,
                  2 * (T * H + index_w + di + T * index_out), [x])
        select = add("IndexScoreTopK", 0,
                     B * S * S / 2 * ni * (2 * di + 2), T * kept,
                     2 * (T * index_out + T * kept), [idx])
        o = add("SparseAttnCore", 0,
                B * keys_read(S, topk) * n * (2 * d + 2 * d + 5),
                T * n * d, 2 * (T * qkv + T * kept + T * n * d),
                [proj, select])
        y = add("OutProjResidual", n * d * H, 2 * T * n * d * H + T * H,
                T * H, 2 * (T * n * d + n * d * H + 2 * T * H),
                [o, stream])
        xp = rmsnorm("PostAttnNorm", [y])
        if i in only or (i + 1) % step:
            stream = add("DenseMLPResidual", 3 * H * I,
                         2 * T * 3 * H * I + 4 * T * I + T * H, T * H,
                         2 * (3 * T * H + 3 * H * I), [xp, y])
            continue
        router = add("Router", H * E, 2 * T * H * E + 5 * T * E + renorm,
                     2 * T * k, 2 * (T * H + H * E + 2 * T * k), [xp])
        experts = add("Experts", E * 3 * H * Ie,
                      2 * pairs * 3 * H * Ie + 4 * pairs * Ie, pairs * H,
                      2 * (2 * pairs * H + min(E, pairs) * 3 * H * Ie),
                      [router, xp])
        stream = add("CombineResidual", 0, 2 * pairs * H + T * H, T * H,
                     2 * (pairs * H + pairs + 2 * T * H),
                     [experts, y, router])
    f = rmsnorm("FinalNorm", [stream])
    add("LMHeadLoss", H * V, 2 * T * H * V + 5 * T * V, T * V,
        2 * (T * H + H * V + T * V), [f])
    return ops, edges
