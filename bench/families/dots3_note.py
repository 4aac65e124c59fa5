"""Family ``dots3_note``: a causal decoder whose attention layers are of TWO
kinds (dots-studio/dots3-note-prev): full layers of latent attention behind a
learned sparse selection (the DeepSeek-V3.2 indexer, one on EVERY full layer),
and sliding layers of latent attention of another geometry over a window,
each head behind a sigmoid gate, over sparse experts; its checkpoint in the
HuggingFace names of the DeepSeek-V3 layout, and the operations and bytes of
its forward as a judge runs it.

A layer's kind is ``cfg["layer_types"][cfg["layers_served"][i]]``
(``full_attention`` | ``sliding_attention``; the PUBLISHED list, an entry a
published layer; one pipeline stage names its layers from 0).  A sliding layer
reads the ``swa_*`` keys where a full layer reads the plain ones (heads,
``q_lora_rank``, ``kv_lora_rank``, nope | rope | value dims, rotary base), has
the same tensor names at its own shapes, no indexer, and attends the
``sliding_window_size`` keys up to and with its own position.  A layer is
dense where its published number is under ``first_k_dense_replace``.
``cfg["n_routed_experts"]`` is the number of experts the CHECKPOINT holds
(experts 0..E-1: one chip's share); ``cfg["n_routed_experts_routed"]`` is the
router's width, as published.

The list's ORDER is the checkpoint (see ``families/bert.py``): embedding, the
layers from 0 up (norms, attention, the head gate, the indexer on a full layer,
then the dense MLP or router, experts 0..E-1 and the shared expert), final
norm, head.  ``ln_scale`` tensors are 1 + N(0, std); everything else N(0, std),
std 0.02.

Operations are counted for the MATHEMATICS at the PUBLISHED head widths (a
full layer's heads are 128 | 64 against the keys, whatever lanes a program
lays them in): a multiply-add is two, only matrix products count.  The indexer
scores every causal pair on a full layer; a full layer's attention counts the
SELECTED pairs (min(index_topk, t + 1) at position t), a sliding layer's the
pairs INSIDE THE BAND (min(window, t + 1)), over the slots of a bucket,
padding included: a kernel that multiplies more reads lower, none over 100.
The routed experts count the pairs that reached an expert held here
(``held_pairs``, from the program's counter; else their expectation).
``forward_flops(cfg, rows, seq)`` is one judge dispatch: ``rows`` calls, each a
prefill of ``seq`` slots, two head reads and one decoded token through the
caches.
"""

FULL, SLIDING = "full_attention", "sliding_attention"


def _swiglu(base: str, hidden: int, width: int) -> list:
    return [
        (f"{base}.gate_proj.weight", (width, hidden), "normal"),
        (f"{base}.up_proj.weight", (width, hidden), "normal"),
        (f"{base}.down_proj.weight", (hidden, width), "normal"),
    ]


def kind_of(cfg: dict, layer: int) -> str:
    return cfg["layer_types"][cfg["layers_served"][layer]]


def is_dense(cfg: dict, layer: int) -> bool:
    return cfg["layers_served"][layer] < cfg["first_k_dense_replace"]


def geometry(cfg: dict, kind: str) -> dict:
    """The attention shapes of a layer of ``kind``, under plain names."""
    swa = "swa_" if kind == SLIDING else ""
    return {
        "heads": cfg[swa + "num_attention_heads"],
        "q_rank": cfg[swa + "q_lora_rank"],
        "kv_rank": cfg[swa + "kv_lora_rank"],
        "nope": cfg[swa + "qk_nope_head_dim"],
        "rope": cfg[swa + "qk_rope_head_dim"],
        "v": cfg[swa + "v_head_dim"],
        "theta": float(cfg[swa + "rope_theta"]),
        "window": cfg["sliding_window_size"] if swa else 0,
    }


def tensors(cfg: dict) -> list:
    h = cfg["hidden_size"]
    held, inter = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
    i_heads, i_dim = cfg["index_n_heads"], cfg["index_head_dim"]
    out = [("model.embed_tokens.weight", (cfg["vocab_size"], h), "normal")]
    for i in range(cfg["num_hidden_layers"]):
        base = f"model.layers.{i}"
        att = f"{base}.self_attn"
        g = geometry(cfg, kind_of(cfg, i))
        heads, dq = g["heads"], g["nope"] + g["rope"]
        out += [
            (f"{base}.input_layernorm.weight", (h,), "ln_scale"),
            (f"{base}.post_attention_layernorm.weight", (h,), "ln_scale"),
            (f"{att}.q_a_proj.weight", (g["q_rank"], h), "normal"),
            (f"{att}.q_a_layernorm.weight", (g["q_rank"],), "ln_scale"),
            (f"{att}.q_b_proj.weight", (heads * dq, g["q_rank"]), "normal"),
            (f"{att}.kv_a_proj_with_mqa.weight", (g["kv_rank"] + g["rope"], h), "normal"),
            (f"{att}.kv_a_layernorm.weight", (g["kv_rank"],), "ln_scale"),
            (f"{att}.kv_b_proj.weight", (heads * (g["nope"] + g["v"]), g["kv_rank"]), "normal"),
            (f"{att}.o_proj.weight", (h, heads * g["v"]), "normal"),
            (f"{att}.g_proj.weight", (heads, h), "normal"),
        ]
        if kind_of(cfg, i) == FULL:
            out += [
                (f"{att}.indexer.wq_b.weight", (i_heads * i_dim, g["q_rank"]), "normal"),
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


def layers_of(cfg: dict, kind: str) -> int:
    return sum(kind_of(cfg, i) == kind for i in range(cfg["num_hidden_layers"]))


def _sparse_layers(cfg: dict) -> int:
    return sum(not is_dense(cfg, i) for i in range(cfg["num_hidden_layers"]))


def _attention_weights(cfg: dict, kind: str) -> int:
    """Parameters of one layer's five attention projections and its gate."""
    h, g = cfg["hidden_size"], geometry(cfg, kind)
    return (
        h * g["q_rank"]
        + g["q_rank"] * g["heads"] * (g["nope"] + g["rope"])
        + h * (g["kv_rank"] + g["rope"])
        + g["kv_rank"] * g["heads"] * (g["nope"] + g["v"])
        + g["heads"] * g["v"] * h
        + h * g["heads"]
    )


def _indexer_weights(cfg: dict) -> int:
    """Parameters of an indexer's three products."""
    i_heads, i_dim = cfg["index_n_heads"], cfg["index_head_dim"]
    return cfg["q_lora_rank"] * i_heads * i_dim + cfg["hidden_size"] * (i_dim + i_heads)


def _dense_half_weights(cfg: dict, layer: int) -> int:
    """Parameters a token's second half multiplies by whatever its routing."""
    h = cfg["hidden_size"]
    if is_dense(cfg, layer):
        return 3 * h * cfg["intermediate_size"]
    shared = 3 * h * cfg["moe_intermediate_size"] * cfg["n_shared_experts"]
    return h * cfg["n_routed_experts_routed"] + shared


def causal_pairs(seq: int) -> int:
    """(query, key <= query) pairs of one call of ``seq`` slots."""
    return seq * (seq + 1) // 2


def _capped_pairs(cap: int, seq: int) -> int:
    """Pairs where position t attends min(cap, t + 1) keys."""
    k = min(cap, seq)
    return k * (k + 1) // 2 + (seq - k) * k


def selected_pairs(cfg: dict, seq: int) -> int:
    """Pairs a call's queries attend on a full layer."""
    return _capped_pairs(cfg["index_topk"], seq)


def band_pairs(cfg: dict, seq: int) -> int:
    """Pairs inside the band of a sliding layer: the window's keys, a query's
    own position among them."""
    return _capped_pairs(cfg["sliding_window_size"], seq)


def index_scores_flops(cfg: dict, rows: int, seq: int) -> int:
    per_pair = 2 * cfg["index_n_heads"] * cfg["index_head_dim"]
    return layers_of(cfg, FULL) * rows * causal_pairs(seq) * per_pair


def index_scores_bytes(cfg: dict, rows: int, seq: int, itemsize: int = 2) -> int:
    """q_I and k_I read once, the heads' weights in float32, and a float32
    score written a causal pair."""
    i_heads, i_dim = cfg["index_n_heads"], cfg["index_head_dim"]
    per_token = (i_heads * i_dim + i_dim) * itemsize + i_heads * 4
    return layers_of(cfg, FULL) * rows * (seq * per_token + causal_pairs(seq) * 4)


def index_select_flops(cfg: dict, rows: int, seq: int) -> int:
    """Choosing multiplies nothing."""
    return 0


def index_select_bytes(cfg: dict, rows: int, seq: int) -> int:
    """A float32 score read and one byte of the choice written a causal pair."""
    return layers_of(cfg, FULL) * rows * causal_pairs(seq) * (4 + 1)


def _attention_flops(cfg: dict, kind: str, pairs: int) -> int:
    g = geometry(cfg, kind)
    return pairs * 2 * g["heads"] * (g["nope"] + g["rope"] + g["v"])


def _attention_bytes(cfg: dict, kind: str, seq: int, itemsize: int) -> int:
    """q and k at the published head width, v and the context at the value
    width, each once."""
    g = geometry(cfg, kind)
    return seq * g["heads"] * (2 * (g["nope"] + g["rope"]) + 2 * g["v"]) * itemsize


def selected_attention_flops(cfg: dict, rows: int, seq: int) -> int:
    """q.k over nope + rope dims and probs.v over the value dims, for the
    pairs a query SELECTED, every head, the full layers."""
    return layers_of(cfg, FULL) * rows * _attention_flops(cfg, FULL, selected_pairs(cfg, seq))


def selected_attention_bytes(cfg: dict, rows: int, seq: int, itemsize: int = 2) -> int:
    """q, k, v and the context once, and the choice at a bit a causal pair."""
    per_call = _attention_bytes(cfg, FULL, seq, itemsize) + causal_pairs(seq) // 8
    return layers_of(cfg, FULL) * rows * per_call


def window_attention_flops(cfg: dict, rows: int, seq: int) -> int:
    """The same two products for the pairs INSIDE THE BAND, the sliding layers."""
    return layers_of(cfg, SLIDING) * rows * _attention_flops(cfg, SLIDING, band_pairs(cfg, seq))


def window_attention_bytes(cfg: dict, rows: int, seq: int, itemsize: int = 2) -> int:
    return layers_of(cfg, SLIDING) * rows * _attention_bytes(cfg, SLIDING, seq, itemsize)


def expected_held_pairs(cfg: dict, rows: int, seq: int) -> float:
    """Pairs a dispatch's prefill sends to the experts held, all sparse
    layers, if the router spread them evenly (it does not: count them)."""
    share = cfg["n_routed_experts"] / cfg["n_routed_experts_routed"]
    return _sparse_layers(cfg) * rows * seq * cfg["num_experts_per_tok"] * share


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
    weights = _sparse_layers(cfg) * cfg["n_routed_experts"] * 3 * h * inter
    return (weights + held_pairs * (2 * (h + inter) + (inter + h))) * itemsize


def forward_flops(cfg: dict, rows: int, seq: int, held_pairs=None) -> float:
    """One judge dispatch of ``rows`` calls in a bucket of ``seq`` slots."""
    layers = cfg["num_hidden_layers"]
    full, sliding = layers_of(cfg, FULL), layers_of(cfg, SLIDING)
    per_token = 2 * (
        full * (_attention_weights(cfg, FULL) + _indexer_weights(cfg))
        + sliding * _attention_weights(cfg, SLIDING)
        + sum(_dense_half_weights(cfg, i) for i in range(layers))
    )
    prefill = (
        rows * seq * per_token
        + index_scores_flops(cfg, rows, seq)
        + selected_attention_flops(cfg, rows, seq)
        + window_attention_flops(cfg, rows, seq)
        + expert_products_flops(cfg, rows, seq, held_pairs)
    )
    # the decoded token: the same products for one token (its experts by their
    # expectation), on a full layer its index scores against seq + 1 cached
    # keys and its absorbed attention (rank + rope wide, and the weighted sum
    # of the latents) over the keys it selected, on a sliding layer the same
    # over the window
    cached = 0
    for kind, count, kept in (
        (FULL, full, min(cfg["index_topk"], seq + 1)),
        (SLIDING, sliding, min(cfg["sliding_window_size"], seq + 1)),
    ):
        g = geometry(cfg, kind)
        cached += count * 2 * g["heads"] * (2 * g["kv_rank"] + g["rope"]) * kept
    indexed = full * 2 * cfg["index_n_heads"] * cfg["index_head_dim"] * (seq + 1)
    decode = rows * (per_token + cached + indexed + expert_products_flops(cfg, 1, 1))
    heads_read = 2 * rows * 2 * cfg["hidden_size"] * cfg["vocab_size"]
    return prefill + decode + heads_read
