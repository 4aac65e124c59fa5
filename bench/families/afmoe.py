"""Family ``afmoe``: a causal decoder of grouped-query attention of TWO kinds
in one stack (arcee-ai/Trinity-Large-Preview): sliding layers that turn their
heads and attend a window, full layers that turn nothing and attend every key
before them, an elementwise sigmoid gate on every head's output, four RMSNorms
a layer, over sparse experts with one shared; its checkpoint in the
HuggingFace names of its ``model_type``, and the operations and bytes of its
forward as a judge runs it.

Served layer i is the PUBLISHED layer ``cfg["layers_served"][i]`` and is NAMED
so in the checkpoint (``model.layers.5`` .. ``model.layers.9``: one pipeline
stage names its own run of the published layers, which is how the program
learns each layer's kind: ``cfg["layer_types"]``, the published list, an entry
a published layer; nothing in a layer's tensors tells a sliding layer from a
full one).  A layer is dense where i < ``cfg["num_dense_layers"]`` (the
stage's count; its dense layers lead).  ``cfg["num_experts"]`` is the number of
experts the CHECKPOINT holds (experts 0..E-1: one chip's share);
``cfg["num_experts_routed"]`` is the router's width, as published.

The list's ORDER is the checkpoint (see ``families/bert.py``): embedding, the
layers in order (the four norms, attention and its two head norms, then the
dense MLP or router, bias, experts 0..E-1 and the shared expert), final norm,
head.  ``ln_scale`` tensors are 1 + N(0, std); everything else N(0, std), std
0.02.

Operations are counted for the MATHEMATICS: a multiply-add is two, only matrix
products count.  A sliding layer's attention counts the pairs INSIDE THE BAND
(min(window, t + 1) at position t), a full layer's the CAUSAL half, over the
slots of a bucket, padding included, every query head against its key head's
keys (q.k over ``head_dim`` and probs.v over ``head_dim``): a kernel that
multiplies whole tiles along a band's edges reads lower, none over 100.  Bytes
are q and the context at the query heads' width, k and v at the key heads',
each once.  The routed experts count the pairs that reached an expert held here
(``held_pairs``, from the program's counter; else their expectation).
``forward_flops(cfg, rows, seq)`` is one judge dispatch: ``rows`` calls, each a
prefill of ``seq`` slots, two head reads and one decoded token through both
kinds of cache.
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
    return layer < cfg["num_dense_layers"]


def tensors(cfg: dict) -> list:
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    wide, narrow = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    held, inter = cfg["num_experts"], cfg["moe_intermediate_size"]
    out = [("model.embed_tokens.weight", (cfg["vocab_size"], h), "normal")]
    for i in range(cfg["num_hidden_layers"]):
        base = f"model.layers.{cfg['layers_served'][i]}"
        att = f"{base}.self_attn"
        out += [
            (f"{base}.input_layernorm.weight", (h,), "ln_scale"),
            (f"{base}.post_attention_layernorm.weight", (h,), "ln_scale"),
            (f"{base}.pre_mlp_layernorm.weight", (h,), "ln_scale"),
            (f"{base}.post_mlp_layernorm.weight", (h,), "ln_scale"),
            (f"{att}.q_proj.weight", (wide, h), "normal"),
            (f"{att}.k_proj.weight", (narrow, h), "normal"),
            (f"{att}.v_proj.weight", (narrow, h), "normal"),
            (f"{att}.gate_proj.weight", (wide, h), "normal"),
            (f"{att}.o_proj.weight", (h, wide), "normal"),
            (f"{att}.q_norm.weight", (hd,), "ln_scale"),
            (f"{att}.k_norm.weight", (hd,), "ln_scale"),
        ]
        if is_dense(cfg, i):
            out += _swiglu(f"{base}.mlp", h, cfg["intermediate_size"])
            continue
        out += [
            (f"{base}.mlp.router.gate.weight", (cfg["num_experts_routed"], h), "normal"),
            (f"{base}.mlp.expert_bias", (cfg["num_experts_routed"],), "normal"),
        ]
        for e in range(held):
            out += _swiglu(f"{base}.mlp.experts.{e}", h, inter)
        out += _swiglu(f"{base}.mlp.shared_experts", h, inter * cfg["num_shared_experts"])
    out += [
        ("model.norm.weight", (h,), "ln_scale"),
        ("lm_head.weight", (cfg["vocab_size"], h), "normal"),
    ]
    return out


def layers_of(cfg: dict, kind: str) -> int:
    return sum(kind_of(cfg, i) == kind for i in range(cfg["num_hidden_layers"]))


def _sparse_layers(cfg: dict) -> int:
    return sum(not is_dense(cfg, i) for i in range(cfg["num_hidden_layers"]))


def attention_weights(cfg: dict) -> int:
    """Parameters of one layer's five attention products (q, k, v, gate, o)."""
    h, hd = cfg["hidden_size"], cfg["head_dim"]
    wide, narrow = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    return h * (2 * wide + 2 * narrow) + wide * h


def _dense_half_weights(cfg: dict, layer: int) -> int:
    """Parameters a token's second half multiplies by whatever its routing."""
    h = cfg["hidden_size"]
    if is_dense(cfg, layer):
        return 3 * h * cfg["intermediate_size"]
    shared = 3 * h * cfg["moe_intermediate_size"] * cfg["num_shared_experts"]
    return h * cfg["num_experts_routed"] + shared


def causal_pairs(seq: int) -> int:
    """(query, key <= query) pairs of one call of ``seq`` slots."""
    return seq * (seq + 1) // 2


def band_pairs(cfg: dict, seq: int) -> int:
    """Pairs inside the band of a sliding layer: the window's keys, a query's
    own position among them."""
    k = min(cfg["sliding_window"], seq)
    return k * (k + 1) // 2 + (seq - k) * k


def _pair_flops(cfg: dict) -> int:
    """q.k over a head's dims and probs.v over them, every query head."""
    return 2 * cfg["num_attention_heads"] * 2 * cfg["head_dim"]


def _attention_bytes(cfg: dict, seq: int, itemsize: int) -> int:
    wide = cfg["num_attention_heads"] * cfg["head_dim"]
    narrow = cfg["num_key_value_heads"] * cfg["head_dim"]
    return seq * (2 * wide + 2 * narrow) * itemsize


def window_attention_flops(cfg: dict, rows: int, seq: int) -> int:
    """The two products for the pairs INSIDE THE BAND, the sliding layers."""
    return layers_of(cfg, SLIDING) * rows * band_pairs(cfg, seq) * _pair_flops(cfg)


def window_attention_bytes(cfg: dict, rows: int, seq: int, itemsize: int = 2) -> int:
    return layers_of(cfg, SLIDING) * rows * _attention_bytes(cfg, seq, itemsize)


def causal_attention_flops(cfg: dict, rows: int, seq: int) -> int:
    """The same two products for the CAUSAL half, the full layers."""
    return layers_of(cfg, FULL) * rows * causal_pairs(seq) * _pair_flops(cfg)


def causal_attention_bytes(cfg: dict, rows: int, seq: int, itemsize: int = 2) -> int:
    return layers_of(cfg, FULL) * rows * _attention_bytes(cfg, seq, itemsize)


def head_norm_flops(cfg: dict, rows: int, seq: int) -> int:
    """Normalising and turning multiply no matrices."""
    return 0


def head_norm_bytes(cfg: dict, rows: int, seq: int, itemsize: int = 2) -> int:
    """q and k read and written once, every layer."""
    width = (cfg["num_attention_heads"] + cfg["num_key_value_heads"]) * cfg["head_dim"]
    return cfg["num_hidden_layers"] * rows * seq * width * 2 * itemsize


def expected_held_pairs(cfg: dict, rows: int, seq: int) -> float:
    """Pairs a dispatch's prefill sends to the experts held, all sparse
    layers, if the router spread them evenly (it does not: count them)."""
    share = cfg["num_experts"] / cfg["num_experts_routed"]
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
    weights = _sparse_layers(cfg) * cfg["num_experts"] * 3 * h * inter
    return (weights + held_pairs * (2 * (h + inter) + (inter + h))) * itemsize


def forward_flops(cfg: dict, rows: int, seq: int, held_pairs=None) -> float:
    """One judge dispatch of ``rows`` calls in a bucket of ``seq`` slots."""
    layers = cfg["num_hidden_layers"]
    per_token = 2 * (
        layers * attention_weights(cfg)
        + sum(_dense_half_weights(cfg, i) for i in range(layers))
    )
    prefill = (
        rows * seq * per_token
        + window_attention_flops(cfg, rows, seq)
        + causal_attention_flops(cfg, rows, seq)
        + expert_products_flops(cfg, rows, seq, held_pairs)
    )
    # the decoded token: the same products for one token (its experts by their
    # expectation), one row against the window's keys on a sliding layer and
    # against seq + 1 keys on a full one
    cached = _pair_flops(cfg) * (
        layers_of(cfg, SLIDING) * min(cfg["sliding_window"], seq + 1)
        + layers_of(cfg, FULL) * (seq + 1)
    )
    decode = rows * (per_token + cached + expert_products_flops(cfg, 1, 1))
    heads_read = 2 * rows * 2 * cfg["hidden_size"] * cfg["vocab_size"]
    return prefill + decode + heads_read
