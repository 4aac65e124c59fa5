"""Family ``qwen3_next``: a causal decoder of gated delta-rule layers, three to
one with gated full attention, over sparse experts
(Qwen/Qwen3-Next-80B-A3B-Instruct), its checkpoint in the HuggingFace names of
its ``model_type``, and the operations and bytes of its forward as a judge
runs it.

``cfg["num_experts"]`` is the number of experts the CHECKPOINT holds (experts
0..E-1: one chip's share where several chips share each layer's experts);
``cfg["num_experts_routed"]`` is the router's width, as published.

The list's ORDER is the checkpoint (see ``families/bert.py``): embedding, the
layers from 0 up (the two norms, the token mixer, then router, experts
0..E-1, the shared expert and its gate), final norm, head.  ``ln_scale``
(1 + N(0, std)) is ``linear_attn.norm.weight`` alone, a plain scale; every
other tensor is N(0, std), the zero-centred norm scales, ``A_log`` and
``dt_bias`` among them (``checkpoints._draw`` has these two kinds: PERF.md,
open questions, says what that does to the recurrence's memory).

Operations are counted for the algorithm: a multiply-add is two, only matrix
products count.  The delta rule is counted in its RECURRENT form, a position
and a value head at a time (the state's read by the key, its rank-one update
and its read by the query: three products of dk x dv), whatever chunk a
kernel works in: the products inside a chunk that a chunked form adds are
the kernel's cost and not the algorithm's.  Attention counts the CAUSAL half
of the scores in the full-attention layers only.  The routed experts count
the (token, expert) PAIRS THAT REACHED AN EXPERT HELD HERE: ``held_pairs``,
the sum over the layers for one dispatch, from the program's counter; without
it, their expectation (``num_experts_per_tok`` x held / routed a token), for
a shape nobody ran.  ``forward_flops(cfg, rows, seq)`` is one judge dispatch:
``rows`` calls, each a prefill of ``seq`` slots (padding is computed, so it
is counted), two head reads and one decoded token through both caches.
"""


def _swiglu(base: str, hidden: int, width: int) -> list:
    return [
        (f"{base}.gate_proj.weight", (width, hidden), "normal"),
        (f"{base}.up_proj.weight", (width, hidden), "normal"),
        (f"{base}.down_proj.weight", (hidden, width), "normal"),
    ]


def is_full(cfg: dict, layer: int) -> bool:
    return (layer + 1) % cfg["full_attention_interval"] == 0


def _linear(cfg: dict):
    return (
        cfg["linear_num_key_heads"], cfg["linear_num_value_heads"],
        cfg["linear_key_head_dim"], cfg["linear_value_head_dim"],
    )


def tensors(cfg: dict) -> list:
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hk, hv, dk, dv = _linear(cfg)
    channels = 2 * hk * dk + hv * dv
    out = [("model.embed_tokens.weight", (cfg["vocab_size"], h), "normal")]
    for i in range(cfg["num_hidden_layers"]):
        base = f"model.layers.{i}"
        out += [
            (f"{base}.input_layernorm.weight", (h,), "normal"),
            (f"{base}.post_attention_layernorm.weight", (h,), "normal"),
        ]
        if is_full(cfg, i):
            att = f"{base}.self_attn"
            out += [
                (f"{att}.q_proj.weight", (heads * hd * 2, h), "normal"),
                (f"{att}.k_proj.weight", (kv * hd, h), "normal"),
                (f"{att}.v_proj.weight", (kv * hd, h), "normal"),
                (f"{att}.o_proj.weight", (h, heads * hd), "normal"),
                (f"{att}.q_norm.weight", (hd,), "normal"),
                (f"{att}.k_norm.weight", (hd,), "normal"),
            ]
        else:
            lin = f"{base}.linear_attn"
            out += [
                (f"{lin}.in_proj_qkvz.weight", (channels + hv * dv, h), "normal"),
                (f"{lin}.in_proj_ba.weight", (2 * hv, h), "normal"),
                (f"{lin}.conv1d.weight", (channels, 1, cfg["linear_conv_kernel_dim"]), "normal"),
                (f"{lin}.A_log", (hv,), "normal"),
                (f"{lin}.dt_bias", (hv,), "normal"),
                (f"{lin}.norm.weight", (dv,), "ln_scale"),
                (f"{lin}.out_proj.weight", (h, hv * dv), "normal"),
            ]
        out.append((f"{base}.mlp.gate.weight", (cfg["num_experts_routed"], h), "normal"))
        for e in range(cfg["num_experts"]):
            out += _swiglu(f"{base}.mlp.experts.{e}", h, cfg["moe_intermediate_size"])
        out += _swiglu(f"{base}.mlp.shared_expert", h, cfg["shared_expert_intermediate_size"])
        out.append((f"{base}.mlp.shared_expert_gate.weight", (1, h), "normal"))
    out += [
        ("model.norm.weight", (h,), "normal"),
        ("lm_head.weight", (cfg["vocab_size"], h), "normal"),
    ]
    return out


def _layers(cfg: dict):
    full = sum(is_full(cfg, i) for i in range(cfg["num_hidden_layers"]))
    return cfg["num_hidden_layers"] - full, full


def _mixer_weights(cfg: dict, full: bool) -> int:
    """Parameters of one layer's token-mixer products (in and out)."""
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    if full:
        heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
        return h * (2 * heads * hd + 2 * kv * hd) + heads * hd * h
    hk, hv, dk, dv = _linear(cfg)
    return h * (2 * hk * dk + 2 * hv * dv + 2 * hv) + hv * dv * h


def _dense_half_weights(cfg: dict) -> int:
    """Parameters a token's second half multiplies by whatever its routing:
    the router, the shared expert and its gate."""
    h = cfg["hidden_size"]
    return h * cfg["num_experts_routed"] + 3 * h * cfg["shared_expert_intermediate_size"] + h


def expected_held_pairs(cfg: dict, rows: int, seq: int) -> float:
    """Pairs a dispatch's prefill sends to the experts held, all layers, if
    the router spread them evenly (it does not: count them)."""
    share = cfg["num_experts"] / cfg["num_experts_routed"]
    return cfg["num_hidden_layers"] * rows * seq * cfg["num_experts_per_tok"] * share


def gated_delta_flops(cfg: dict, rows: int, seq: int) -> int:
    """The recurrence a position and a value head: S^T k, k d^T, S^T q."""
    linear, _ = _layers(cfg)
    _, hv, dk, dv = _linear(cfg)
    return linear * rows * seq * hv * 3 * 2 * dk * dv


def gated_delta_bytes(cfg: dict, rows: int, seq: int, itemsize: int = 2) -> int:
    """q, k, v read once and o written once, g and beta in float32, and the
    state written once a call (what a kernel that kept the state on the chip
    would move), every linear layer."""
    linear, _ = _layers(cfg)
    hk, hv, dk, dv = _linear(cfg)
    per_token = (2 * hk * dk + 2 * hv * dv) * itemsize + 2 * hv * 4
    return linear * rows * (seq * per_token + hv * dk * dv * 4)


def causal_attention_flops(cfg: dict, rows: int, seq: int) -> int:
    """q·k and probs·v over head_dim each, for the seq * (seq + 1) / 2 pairs
    the causal mask keeps, every query head, the full-attention layers."""
    _, full = _layers(cfg)
    pairs = seq * (seq + 1) // 2
    return full * rows * 2 * cfg["num_attention_heads"] * 2 * cfg["head_dim"] * pairs


def causal_attention_bytes(cfg: dict, rows: int, seq: int, itemsize: int = 2) -> int:
    """q read and the context written a query head, k and v read a KEY head."""
    _, full = _layers(cfg)
    per_token = 2 * (cfg["num_attention_heads"] + cfg["num_key_value_heads"]) * cfg["head_dim"]
    return full * rows * seq * per_token * itemsize


def expert_products_flops(cfg: dict, rows: int, seq: int, held_pairs=None) -> float:
    """The routed experts' three products over the pairs held here."""
    if held_pairs is None:
        held_pairs = expected_held_pairs(cfg, rows, seq)
    return held_pairs * 2 * 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def expert_products_bytes(cfg: dict, rows: int, seq: int, held_pairs=None, itemsize: int = 2):
    """Every held expert's weights once a layer, and each held pair's rows in
    and out of the three products."""
    if held_pairs is None:
        held_pairs = expected_held_pairs(cfg, rows, seq)
    h, inter = cfg["hidden_size"], cfg["moe_intermediate_size"]
    weights = cfg["num_hidden_layers"] * cfg["num_experts"] * 3 * h * inter
    return (weights + held_pairs * (2 * (h + inter) + (inter + h))) * itemsize


def forward_flops(cfg: dict, rows: int, seq: int, held_pairs=None) -> float:
    """One judge dispatch of ``rows`` calls in a bucket of ``seq`` slots."""
    linear, full = _layers(cfg)
    per_token = 2 * (
        linear * _mixer_weights(cfg, False) + full * _mixer_weights(cfg, True)
        + cfg["num_hidden_layers"] * _dense_half_weights(cfg)
    )
    prefill = (
        rows * seq * per_token
        + gated_delta_flops(cfg, rows, seq)
        + causal_attention_flops(cfg, rows, seq)
        + expert_products_flops(cfg, rows, seq, held_pairs)
    )
    # the decoded token: the same products for one token (its experts by
    # their expectation: the counter holds the prefill's pairs), one step of
    # the recurrence, and a row of scores against seq + 1 cached keys
    cached = full * 2 * cfg["num_attention_heads"] * 2 * cfg["head_dim"] * (seq + 1)
    decode = rows * (
        per_token + cached + gated_delta_flops(cfg, 1, 1) + expert_products_flops(cfg, 1, 1)
    )
    heads_read = 2 * rows * 2 * cfg["hidden_size"] * cfg["vocab_size"]
    return prefill + decode + heads_read
