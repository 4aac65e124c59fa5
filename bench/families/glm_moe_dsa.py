"""Family ``glm_moe_dsa``: a causal decoder with a learned sparse selection in
front of latent attention, over sparse experts (zai-org/GLM-5.2), its
checkpoint in the HuggingFace names of the DeepSeek-V3 layout with the
DeepSeek-V3.2 indexer's beside them, and the operations and bytes of its
forward as a judge runs it.

``cfg["n_routed_experts"]`` is the number of experts the CHECKPOINT holds
(experts 0..E-1: one chip's share where several chips share each layer's
experts); ``cfg["n_routed_experts_routed"]`` is the router's width, as
published.  ``cfg["mlp_layer_types"]`` and ``cfg["indexer_types"]`` are the
PUBLISHED lists, an entry a published layer: ``dense`` | ``sparse``, and
``full`` (the layer owns an indexer and chooses each query's keys) |
``shared`` (it attends over the last ``full`` layer's choice and has no
indexer weights); ``cfg["layers_served"][i]`` is the published layer that
layer i of the checkpoint is (one pipeline stage names its layers from 0).

The list's ORDER is the checkpoint (see ``families/bert.py``): embedding, the
layers from 0 up (norms, attention, the indexer where the layer owns one,
then the dense MLP or router, experts 0..E-1 and the shared expert), final
norm, head.  ``ln_scale`` tensors are 1 + N(0, std) (the RMSNorm scales and
the indexer's ``k_norm.weight``); everything else, ``e_score_correction_bias``
and ``k_norm.bias`` included, N(0, std), std the published
``initializer_range`` 0.02.

Operations are counted for the MATHEMATICS: a multiply-add is two, only matrix
products count.  The indexer scores every CAUSAL pair (query t, key s <= t),
index heads x index dims a pair.  Attention counts the SELECTED pairs: a
query at position t attends min(index_topk, t + 1) keys, whatever a kernel
multiplies to get there, so a form that multiplies every causal pair and
masks reads under selected / causal of its roofline and none can read over
100.  Both are counted over the SLOTS of a bucket, padding included (the
program computes it), as ``families/glm4_moe_lite.py::causal_attention_flops``
counts them: at 8192 slots and 2048 keys a query, 14,681,088 selected of
33,558,528 causal pairs a call, 43.7%; over a call's 7,525 tokens alone it
would be 47.0%.  The routed experts count the (token, expert) PAIRS THAT
REACHED AN EXPERT HELD HERE: ``held_pairs``, the sum over the layers for one
dispatch, from the program's counter; without it, their expectation.
``forward_flops(cfg, rows, seq)`` is one judge dispatch: ``rows`` calls, each
a prefill of ``seq`` slots, two head reads and one decoded token through the
three caches.
"""


def _swiglu(base: str, hidden: int, width: int) -> list:
    return [
        (f"{base}.gate_proj.weight", (width, hidden), "normal"),
        (f"{base}.up_proj.weight", (width, hidden), "normal"),
        (f"{base}.down_proj.weight", (hidden, width), "normal"),
    ]


def owns_indexer(cfg: dict, layer: int) -> bool:
    return cfg["indexer_types"][cfg["layers_served"][layer]] == "full"


def is_dense(cfg: dict, layer: int) -> bool:
    return cfg["mlp_layer_types"][cfg["layers_served"][layer]] == "dense"


def tensors(cfg: dict) -> list:
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    q_rank, kv_rank = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    held, inter = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    i_heads, i_dim = cfg["index_n_heads"], cfg["index_head_dim"]
    out = [("model.embed_tokens.weight", (cfg["vocab_size"], h), "normal")]
    for i in range(cfg["num_hidden_layers"]):
        base = f"model.layers.{i}"
        att = f"{base}.self_attn"
        out += [
            (f"{base}.input_layernorm.weight", (h,), "ln_scale"),
            (f"{base}.post_attention_layernorm.weight", (h,), "ln_scale"),
            (f"{att}.q_a_proj.weight", (q_rank, h), "normal"),
            (f"{att}.q_a_layernorm.weight", (q_rank,), "ln_scale"),
            (f"{att}.q_b_proj.weight", (heads * (nope + rope), q_rank), "normal"),
            (f"{att}.kv_a_proj_with_mqa.weight", (kv_rank + rope, h), "normal"),
            (f"{att}.kv_a_layernorm.weight", (kv_rank,), "ln_scale"),
            (f"{att}.kv_b_proj.weight", (heads * (nope + dv), kv_rank), "normal"),
            (f"{att}.o_proj.weight", (h, heads * dv), "normal"),
        ]
        if owns_indexer(cfg, i):
            out += [
                (f"{att}.indexer.wq_b.weight", (i_heads * i_dim, q_rank), "normal"),
                (f"{att}.indexer.wk.weight", (i_dim, h), "normal"),
                (f"{att}.indexer.k_norm.weight", (i_dim,), "ln_scale"),
                (f"{att}.indexer.k_norm.bias", (i_dim,), "normal"),
                (f"{att}.indexer.weights_proj.weight", (i_heads, h), "normal"),
            ]
        if is_dense(cfg, i):
            out += _swiglu(f"{base}.mlp", h, cfg["intermediate_size"])
            continue
        out += [
            (f"{base}.mlp.gate.weight", (cfg["n_routed_experts_routed"], h), "normal"),
            (f"{base}.mlp.gate.e_score_correction_bias", (cfg["n_routed_experts_routed"],), "normal"),
        ]
        for e in range(held):
            out += _swiglu(f"{base}.mlp.experts.{e}", h, inter)
        out += _swiglu(f"{base}.mlp.shared_experts", h, inter * cfg["n_shared_experts"])
    out += [
        ("model.norm.weight", (h,), "ln_scale"),
        ("lm_head.weight", (cfg["vocab_size"], h), "normal"),
    ]
    return out


def _layers(cfg: dict):
    """(layers, those that own an indexer, the sparse ones)."""
    n = cfg["num_hidden_layers"]
    return (
        n, sum(owns_indexer(cfg, i) for i in range(n)),
        sum(not is_dense(cfg, i) for i in range(n)),
    )


def _attention_weights(cfg: dict) -> int:
    """Parameters of one layer's five attention projections."""
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    return (
        h * cfg["q_lora_rank"]
        + cfg["q_lora_rank"] * heads * (nope + rope)
        + h * (cfg["kv_lora_rank"] + rope)
        + cfg["kv_lora_rank"] * heads * (nope + dv)
        + heads * dv * h
    )


def _indexer_weights(cfg: dict) -> int:
    """Parameters of an indexer's three products."""
    i_heads, i_dim = cfg["index_n_heads"], cfg["index_head_dim"]
    return cfg["q_lora_rank"] * i_heads * i_dim + cfg["hidden_size"] * (i_dim + i_heads)


def _dense_half_weights(cfg: dict, layer: int) -> int:
    """Parameters a token's second half multiplies by whatever its routing:
    a dense layer's MLP, or the router and the shared expert."""
    h = cfg["hidden_size"]
    if is_dense(cfg, layer):
        return 3 * h * cfg["intermediate_size"]
    shared = 3 * h * cfg["moe_intermediate_size"] * cfg["n_shared_experts"]
    return h * cfg["n_routed_experts_routed"] + shared


def causal_pairs(seq: int) -> int:
    """(query, key <= query) pairs of one call of ``seq`` slots."""
    return seq * (seq + 1) // 2


def selected_pairs(cfg: dict, seq: int) -> int:
    """Pairs a call's queries attend: min(index_topk, t + 1) at position t,
    over every slot of the bucket."""
    k = min(cfg["index_topk"], seq)
    return k * (k + 1) // 2 + (seq - k) * k


def index_scores_flops(cfg: dict, rows: int, seq: int) -> int:
    """q_I . k_I over the index dims, every index head, every causal pair,
    the layers that own an indexer."""
    _, full, _ = _layers(cfg)
    per_pair = 2 * cfg["index_n_heads"] * cfg["index_head_dim"]
    return full * rows * causal_pairs(seq) * per_pair


def index_scores_bytes(cfg: dict, rows: int, seq: int, itemsize: int = 2) -> int:
    """q_I and k_I read once, the heads' weights in float32, and a float32
    score written a causal pair."""
    _, full, _ = _layers(cfg)
    i_heads, i_dim = cfg["index_n_heads"], cfg["index_head_dim"]
    per_token = (i_heads * i_dim + i_dim) * itemsize + i_heads * 4
    return full * rows * (seq * per_token + causal_pairs(seq) * 4)


def index_select_flops(cfg: dict, rows: int, seq: int) -> int:
    """Choosing multiplies nothing."""
    return 0


def index_select_bytes(cfg: dict, rows: int, seq: int) -> int:
    """A float32 score read and one byte of the choice written a causal pair,
    the layers that own an indexer."""
    _, full, _ = _layers(cfg)
    return full * rows * causal_pairs(seq) * (4 + 1)


def selected_attention_flops(cfg: dict, rows: int, seq: int) -> int:
    """q.k over nope + rope dims and probs.v over the value dims, for the
    pairs a query SELECTED, every head, every layer (a ``shared`` layer
    attends its ``full`` layer's choice: as many pairs)."""
    layers, _, _ = _layers(cfg)
    width = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]
    return layers * rows * selected_pairs(cfg, seq) * 2 * cfg["num_attention_heads"] * width


def selected_attention_bytes(cfg: dict, rows: int, seq: int, itemsize: int = 2) -> int:
    """q, k, v read once and the context written once, and the choice read
    once at a bit a causal pair, every layer."""
    layers, _, _ = _layers(cfg)
    heads = cfg["num_attention_heads"]
    dq = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    per_token = heads * (2 * dq + 2 * cfg["v_head_dim"]) * itemsize
    return layers * rows * (seq * per_token + causal_pairs(seq) // 8)


def expected_held_pairs(cfg: dict, rows: int, seq: int) -> float:
    """Pairs a dispatch's prefill sends to the experts held, all sparse
    layers, if the router spread them evenly (it does not: count them)."""
    _, _, sparse = _layers(cfg)
    share = cfg["n_routed_experts"] / cfg["n_routed_experts_routed"]
    return sparse * rows * seq * cfg["num_experts_per_tok"] * share


def expert_products_flops(cfg: dict, rows: int, seq: int, held_pairs=None) -> float:
    """The routed experts' three products over the pairs held here."""
    if held_pairs is None:
        held_pairs = expected_held_pairs(cfg, rows, seq)
    return held_pairs * 2 * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_products_bytes(cfg: dict, rows: int, seq: int, held_pairs=None, itemsize: int = 2):
    """Every held expert's weights once a sparse layer, and each held pair's
    rows in and out of the three products."""
    if held_pairs is None:
        held_pairs = expected_held_pairs(cfg, rows, seq)
    h, inter = cfg["hidden_size"], cfg["moe_intermediate_size"]
    _, _, sparse = _layers(cfg)
    weights = sparse * cfg["n_routed_experts"] * 3 * h * inter
    return (weights + held_pairs * (2 * (h + inter) + (inter + h))) * itemsize


def forward_flops(cfg: dict, rows: int, seq: int, held_pairs=None) -> float:
    """One judge dispatch of ``rows`` calls in a bucket of ``seq`` slots."""
    layers, full, _ = _layers(cfg)
    per_token = 2 * (
        layers * _attention_weights(cfg) + full * _indexer_weights(cfg)
        + sum(_dense_half_weights(cfg, i) for i in range(layers))
    )
    prefill = (
        rows * seq * per_token
        + index_scores_flops(cfg, rows, seq)
        + selected_attention_flops(cfg, rows, seq)
        + expert_products_flops(cfg, rows, seq, held_pairs)
    )
    # the decoded token: the same products for one token (its experts by
    # their expectation: the counter holds the prefill's pairs), on a layer
    # with an indexer its scores against seq + 1 cached index keys, and on
    # every layer its scores against the latents it selected (absorbed: rank
    # + rope wide) and the weighted sum of them
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    kept = min(cfg["index_topk"], seq + 1)
    cached = layers * 2 * heads * (2 * rank + cfg["qk_rope_head_dim"]) * kept
    indexed = full * 2 * cfg["index_n_heads"] * cfg["index_head_dim"] * (seq + 1)
    decode = rows * (per_token + cached + indexed + expert_products_flops(cfg, 1, 1))
    heads_read = 2 * rows * 2 * cfg["hidden_size"] * cfg["vocab_size"]
    return prefill + decode + heads_read
