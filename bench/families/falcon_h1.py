"""Family ``falcon_h1``: a decoder whose every block runs a Mamba-2 (SSD) mixer
and grouped-query attention side by side on one normed input and sums them,
then a SwiGLU MLP, every product behind one of the published µP multipliers
(tiiuae/Falcon-H1-34B-Instruct).  Its checkpoint in the HuggingFace names of
its ``model_type``, and the operations and bytes of its forward as a judge runs
it.

The list's ORDER is the checkpoint (see ``families/bert.py``): embedding, the
layers in order (the input norm, the mixer's tensors, the attention's four
products, the second norm, the MLP's three), final norm, the untied head.
``ln_scale`` tensors (the norms' weights, the gated norm's, ``D``) are 1 + N(0,
std); everything else N(0, std): ``checkpoints._draw`` knows no other
distribution, so ``A_log`` near 0 is a rate of -1 and ``dt_bias`` near 0 a step
of about 0.69: a state that halves every token (PERF.md, question 23).

``INIT_STD`` 0.1, not the usual 0.02: the multipliers are made for weights of
another scale.  At 0.02 the stream is the embedding's 5.657 x 0.02 = 0.113 a
component and every branch adds under 3% of it, six layers deep, so a wrong
mixer, or the int8 control, would move the logits less than bf16's rounding of
the stream does and the cell's check would hold nothing.  At 0.1 each of the
three branches' outputs is of the stream's size (the readings are in the
configuration's ``assumed`` block and in PERF.md section 6).

Operations are counted for the MATHEMATICS: a multiply-add is two; matrix
products count; every layer at every slot of a bucket, padding included (the
program runs every layer at every slot: the last layer's keys, convolution tail
and state are the decoded token's).  ``ssd_flops`` / ``ssd_bytes`` are the work
of the PUBLISHED algorithm at the PUBLISHED chunk (``mamba_chunk_size`` 128)
over slots, whatever chunk or order the program's kernel takes: a slot and
layer, C Bᵀ over the chunk's positions a group (2 · chunk · N), the masked
scores times x (2 · chunk · d_ssm), the chunk's state and the state's output
(2 · N · d_ssm each); bytes xs and y once, B, C and dt once.
``forward_flops(cfg, rows, seq)`` is one judge dispatch: ``rows`` calls, each a
prefill of ``seq`` slots, two head reads and one decoded token through both
kinds of cache.
"""

INIT_STD = 0.1


def ssm_width(cfg: dict) -> int:
    """The mixer's width: ``mamba_d_ssm`` where given (``mamba_expand`` does
    not apply then)."""
    return cfg.get("mamba_d_ssm") or int(cfg["mamba_expand"] * cfg["hidden_size"])


def conv_width(cfg: dict) -> int:
    """Channels the convolution runs over: [x | B | C]."""
    return ssm_width(cfg) + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]


def in_proj_width(cfg: dict) -> int:
    """[z | x | B | C | dt]."""
    return ssm_width(cfg) + conv_width(cfg) + cfg["mamba_n_heads"]


def tensors(cfg: dict) -> list:
    h, hd, inner = cfg["hidden_size"], cfg["head_dim"], ssm_width(cfg)
    wide, narrow = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    heads, width = cfg["mamba_n_heads"], cfg["intermediate_size"]
    out = [("model.embed_tokens.weight", (cfg["vocab_size"], h), "normal")]
    for i in range(cfg["num_hidden_layers"]):
        base = f"model.layers.{i}"
        mix, att, ff = base + ".mamba", base + ".self_attn", base + ".feed_forward"
        out += [
            (f"{base}.input_layernorm.weight", (h,), "ln_scale"),
            (f"{mix}.in_proj.weight", (in_proj_width(cfg), h), "normal"),
            (f"{mix}.conv1d.weight", (conv_width(cfg), 1, cfg["mamba_d_conv"]), "normal"),
            (f"{mix}.conv1d.bias", (conv_width(cfg),), "normal"),
            (f"{mix}.A_log", (heads,), "normal"),
            (f"{mix}.D", (heads,), "ln_scale"),
            (f"{mix}.dt_bias", (heads,), "normal"),
            (f"{mix}.norm.weight", (inner,), "ln_scale"),
            (f"{mix}.out_proj.weight", (h, inner), "normal"),
            (f"{att}.q_proj.weight", (wide, h), "normal"),
            (f"{att}.k_proj.weight", (narrow, h), "normal"),
            (f"{att}.v_proj.weight", (narrow, h), "normal"),
            (f"{att}.o_proj.weight", (h, wide), "normal"),
            (f"{base}.pre_ff_layernorm.weight", (h,), "ln_scale"),
            (f"{ff}.gate_proj.weight", (width, h), "normal"),
            (f"{ff}.up_proj.weight", (width, h), "normal"),
            (f"{ff}.down_proj.weight", (h, width), "normal"),
        ]
    out += [
        ("model.final_layernorm.weight", (h,), "ln_scale"),
        ("lm_head.weight", (cfg["vocab_size"], h), "normal"),
    ]
    return out


# -- parameters a token multiplies by, a layer (matrix products only) ----------------------


def mlp_weights(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def ssm_weights(cfg: dict) -> int:
    """The input and output products: the convolution multiplies no matrix."""
    return cfg["hidden_size"] * (in_proj_width(cfg) + ssm_width(cfg))


def attention_weights(cfg: dict) -> int:
    hd = cfg["head_dim"]
    return 2 * cfg["hidden_size"] * (cfg["num_attention_heads"] + cfg["num_key_value_heads"]) * hd


def layer_weights(cfg: dict) -> int:
    return mlp_weights(cfg) + ssm_weights(cfg) + attention_weights(cfg)


def causal_pairs(seq: int) -> int:
    """(query, key <= query) pairs of one call of ``seq`` slots."""
    return seq * (seq + 1) // 2


def causal_attention_flops(cfg: dict, rows: int, seq: int) -> int:
    """q·k and probs·v over head_dim each, for the pairs the causal mask
    keeps, every query head, every layer."""
    per_pair = 2 * cfg["num_attention_heads"] * 2 * cfg["head_dim"]
    return cfg["num_hidden_layers"] * rows * causal_pairs(seq) * per_pair


def causal_attention_bytes(cfg: dict, rows: int, seq: int, itemsize: int = 2) -> int:
    """q read and the context written a query head, k and v read a KEY head."""
    per_token = 2 * (cfg["num_attention_heads"] + cfg["num_key_value_heads"]) * cfg["head_dim"]
    return cfg["num_hidden_layers"] * rows * seq * per_token * itemsize


def ssd_slot_flops(cfg: dict) -> int:
    """The published algorithm at the published chunk, a slot and layer."""
    chunk, n, inner = cfg["mamba_chunk_size"], cfg["mamba_d_state"], ssm_width(cfg)
    return 2 * chunk * n * cfg["mamba_n_groups"] + 2 * chunk * inner + 2 * (2 * n * inner)


def ssd_flops(cfg: dict, rows: int, seq: int) -> int:
    return cfg["num_hidden_layers"] * rows * seq * ssd_slot_flops(cfg)


def ssd_bytes(cfg: dict, rows: int, seq: int, itemsize: int = 2) -> int:
    """xs read and y written once, B and C once a group, dt (float32) once a
    head."""
    per_slot = (
        (2 * ssm_width(cfg) + 2 * cfg["mamba_n_groups"] * cfg["mamba_d_state"]) * itemsize
        + 4 * cfg["mamba_n_heads"]
    )
    return cfg["num_hidden_layers"] * rows * seq * per_slot


def forward_flops(cfg: dict, rows: int, seq: int, held_pairs=None) -> float:
    """One judge dispatch of ``rows`` calls in a bucket of ``seq`` slots (no
    experts: ``held_pairs`` is the readers' and counts nothing)."""
    layers = cfg["num_hidden_layers"]
    per_token = 2 * layers * layer_weights(cfg)
    prefill = (
        rows * seq * per_token + causal_attention_flops(cfg, rows, seq) + ssd_flops(cfg, rows, seq)
    )
    # the decoded token: the same products, one step of the recurrence (decay,
    # dt x (x) B, the state times C: 2 flops each a state element) and a row of
    # scores against seq + 1 cached keys, a layer
    step = 3 * 2 * ssm_width(cfg) * cfg["mamba_d_state"]
    cached = 2 * cfg["num_attention_heads"] * 2 * cfg["head_dim"] * (seq + 1)
    decode = rows * (per_token + layers * (step + cached))
    heads_read = 2 * rows * 2 * cfg["hidden_size"] * cfg["vocab_size"]
    return prefill + decode + heads_read
