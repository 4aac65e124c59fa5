"""Family ``glm4_moe_lite``: a causal decoder with latent attention and sparse
experts (zai-org/GLM-4.7-Flash), its checkpoint in the HuggingFace names of
the DeepSeek-V3 layout this ``model_type`` follows, and the operations of its
forward as a judge runs it.

The list's ORDER is the checkpoint (see ``families/bert.py``): embedding, the
layers from 0 up (norms, attention, then the dense MLP or router, experts
0..E-1 and the shared expert), final norm, head.  ``ln_scale`` tensors are
1 + N(0, std); everything else, ``e_score_correction_bias`` included, N(0,
std), std the published ``initializer_range`` 0.02.

Operations are counted for the algorithm: a multiply-add is two, only matrix
products count.  Attention counts the CAUSAL half of the scores (position i
sees i + 1 keys) and the experts count the ``num_experts_per_tok`` a token is
routed to plus the shared one, not the 64 held.  ``forward_flops(cfg, rows,
seq)`` is one judge dispatch: ``rows`` calls, each a prefill of ``seq``
slots (padding is computed, so it is counted), two head reads and one
decoded token through the latent cache.
"""


def _swiglu(base: str, hidden: int, width: int) -> list:
    return [
        (f"{base}.gate_proj.weight", (width, hidden), "normal"),
        (f"{base}.up_proj.weight", (width, hidden), "normal"),
        (f"{base}.down_proj.weight", (hidden, width), "normal"),
    ]


def tensors(cfg: dict) -> list:
    h, heads = cfg["hidden_size"], cfg["num_attention_heads"]
    nope, rope, dv = cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"], cfg["v_head_dim"]
    q_rank, kv_rank = cfg["q_lora_rank"], cfg["kv_lora_rank"]
    experts, inter = cfg["n_routed_experts"], cfg["moe_intermediate_size"]
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
        if i < cfg["first_k_dense_replace"]:
            out += _swiglu(f"{base}.mlp", h, cfg["intermediate_size"])
            continue
        out += [
            (f"{base}.mlp.gate.weight", (experts, h), "normal"),
            (f"{base}.mlp.gate.e_score_correction_bias", (experts,), "normal"),
        ]
        for e in range(experts):
            out += _swiglu(f"{base}.mlp.experts.{e}", h, inter)
        out += _swiglu(f"{base}.mlp.shared_experts", h, inter * cfg["n_shared_experts"])
    out += [
        ("model.norm.weight", (h,), "ln_scale"),
        ("lm_head.weight", (cfg["vocab_size"], h), "normal"),
    ]
    return out


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


def _mlp_weights(cfg: dict, layer: int) -> int:
    """Parameters one token's second half of a layer multiplies by."""
    h = cfg["hidden_size"]
    if layer < cfg["first_k_dense_replace"]:
        return 3 * h * cfg["intermediate_size"]
    active = cfg["num_experts_per_tok"] + cfg["n_shared_experts"]
    return 3 * h * cfg["moe_intermediate_size"] * active + h * cfg["n_routed_experts"]


def causal_attention_flops(cfg: dict, rows: int, seq: int) -> int:
    """q·k over nope + rope dims and probs·v over the value dims, for the
    seq * (seq + 1) / 2 (query, key) pairs the causal mask keeps, every head,
    every layer."""
    heads = cfg["num_attention_heads"]
    width = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"] + cfg["v_head_dim"]
    pairs = seq * (seq + 1) // 2
    return cfg["num_hidden_layers"] * rows * 2 * heads * width * pairs


def causal_attention_bytes(cfg: dict, rows: int, seq: int, itemsize: int = 2) -> int:
    """q, k, v read once and the context written once (what a kernel that
    kept every key block on the chip would move), every layer."""
    heads = cfg["num_attention_heads"]
    dq = cfg["qk_nope_head_dim"] + cfg["qk_rope_head_dim"]
    per_token = heads * (2 * dq + 2 * cfg["v_head_dim"])
    return cfg["num_hidden_layers"] * rows * seq * per_token * itemsize


def _sparse_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg["first_k_dense_replace"]


def expert_products_flops(cfg: dict, rows: int, seq: int) -> int:
    """The routed experts' three products over the (token, expert) pairs."""
    pairs = rows * seq * cfg["num_experts_per_tok"]
    per_pair = 2 * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]
    return _sparse_layers(cfg) * pairs * per_pair


def expert_products_bytes(cfg: dict, rows: int, seq: int, itemsize: int = 2) -> int:
    """Every expert's weights once, and each pair's rows in and out of the
    three products."""
    h, inter = cfg["hidden_size"], cfg["moe_intermediate_size"]
    pairs = rows * seq * cfg["num_experts_per_tok"]
    weights = cfg["n_routed_experts"] * 3 * h * inter
    rows_moved = pairs * (2 * (h + inter) + (inter + h))
    return _sparse_layers(cfg) * (weights + rows_moved) * itemsize


def forward_flops(cfg: dict, rows: int, seq: int) -> int:
    """One judge dispatch of ``rows`` calls in a bucket of ``seq`` slots."""
    layers = range(cfg["num_hidden_layers"])
    per_token = sum(2 * (_attention_weights(cfg) + _mlp_weights(cfg, i)) for i in layers)
    prefill = rows * seq * per_token + causal_attention_flops(cfg, rows, seq)
    # the decoded token: the same products for one token, and per layer its
    # scores against seq + 1 cached latents (absorbed: rank + rope wide) and
    # the weighted sum of them
    heads, rank = cfg["num_attention_heads"], cfg["kv_lora_rank"]
    cached = 2 * heads * (2 * rank + cfg["qk_rope_head_dim"]) * (seq + 1)
    decode = rows * (per_token + cfg["num_hidden_layers"] * cached)
    heads_read = 2 * rows * 2 * cfg["hidden_size"] * cfg["vocab_size"]
    return prefill + decode + heads_read
