"""Family ``phi4flash``: a decoder that feeds a decoder (microsoft/
Phi-4-mini-flash-reasoning, SambaY): Mamba layers and differential attention
over a window in the first half, one full-attention layer whose keys and values
every cross-attention layer of the second half reads, gated memory units on the
last Mamba layer's scan between them; LayerNorm with a bias, a SwiGLU MLP every
layer, a tied head.  Its checkpoint in the HuggingFace names of its
``model_type``, and the operations and bytes of its forward as a judge runs it.

Layer i of n = ``num_hidden_layers``, K = n / 2 + 1 (``kv_layer``): Mamba where
i is even and i < K, sliding attention where i is odd and i < K, full attention
at K, a memory unit where i is even and i > K, cross attention where i is odd
and i > K.  Every layer's mixer is named ``model.layers.i.attn``.

The list's ORDER is the checkpoint (see ``families/bert.py``): embedding, the
layers in order (two norms with their biases, the mixer's tensors, fc1, fc2),
final norm.  No ``lm_head``: ``tie_word_embeddings``.  ``ln_scale`` tensors
(the norms' weights, ``subln``, ``D``) are 1 + N(0, std); everything else
N(0, std), std 0.02: ``checkpoints._draw`` knows no other distribution, so
``A_log`` near 0 is a rate of -1 and ``dt_proj.bias`` near 0 a step of 0.69: a
state that halves every token (PERF.md, question 23).

Operations are counted for the MATHEMATICS and for the work the ANSWER needs:
a multiply-add is two; matrix products count, and the scan's recurrence as
written (``SCAN_FLOPS`` a position, channel and state).  A judge reads the
head at ``lens - 1`` and after one decoded token, and in this architecture a
layer behind K at a position feeds no other position: so layers 0 .. K - 1
and layer K's key and value products count at EVERY slot of a bucket, padding
included, and layer K's own attention, every layer behind it and the head at
the TWO positions read, whatever the program runs.  A count of all n layers at
every slot would read the split as a share of the peak over 100.  A sliding
layer's attention counts the pairs INSIDE THE BAND (min(window, t + 1) at
position t), every query head against its key head's keys (q.k over ``hd``,
probs.v over the pair's 2 hd value lanes).  Bytes are each array once at the
width the mathematics gives it (a query head is ``hd`` wide, whatever lanes a
kernel lays it in).  ``forward_flops(cfg, rows, seq)`` is one judge dispatch:
``rows`` calls, each a prefill of ``seq`` slots, two head reads and one decoded
token through the three kinds of cache.
"""

SCAN_FLOPS = 6  # dt·A, ·h, (dt x)·B, +, ·C, + : a position, channel and state


def kv_layer(cfg: dict) -> int:
    return cfg["num_hidden_layers"] // 2 + 1


def kind_of(cfg: dict, layer: int) -> str:
    """``mamba`` | ``sliding`` | ``full`` | ``memory`` | ``cross``."""
    top = kv_layer(cfg)
    if layer % cfg["mb_per_layer"] == 0:
        return "mamba" if layer < top else "memory"
    if layer < top:
        return "sliding"
    return "full" if layer == top else "cross"


def layers_of(cfg: dict, kind: str) -> int:
    return sum(kind_of(cfg, i) == kind for i in range(cfg["num_hidden_layers"]))


def head_dim(cfg: dict) -> int:
    return cfg["hidden_size"] // cfg["num_attention_heads"]


def d_inner(cfg: dict) -> int:
    return cfg["mamba_expand"] * cfg["hidden_size"]


def tensors(cfg: dict) -> list:
    h, hd, inner = cfg["hidden_size"], head_dim(cfg), d_inner(cfg)
    wide, narrow = cfg["num_attention_heads"] * hd, cfg["num_key_value_heads"] * hd
    n, rank, taps = cfg["mamba_d_state"], cfg["mamba_dt_rank"], cfg["mamba_d_conv"]
    out = [("model.embed_tokens.weight", (cfg["vocab_size"], h), "normal")]
    for i in range(cfg["num_hidden_layers"]):
        base, mix = f"model.layers.{i}", f"model.layers.{i}.attn"
        kind = kind_of(cfg, i)
        out += [
            (f"{base}.input_layernorm.weight", (h,), "ln_scale"),
            (f"{base}.input_layernorm.bias", (h,), "normal"),
            (f"{base}.post_attention_layernorm.weight", (h,), "ln_scale"),
            (f"{base}.post_attention_layernorm.bias", (h,), "normal"),
        ]
        if kind == "mamba":
            out += [
                (f"{mix}.in_proj.weight", (2 * inner, h), "normal"),
                (f"{mix}.conv1d.weight", (inner, 1, taps), "normal"),
                (f"{mix}.conv1d.bias", (inner,), "normal"),
                (f"{mix}.x_proj.weight", (rank + 2 * n, inner), "normal"),
                (f"{mix}.dt_proj.weight", (inner, rank), "normal"),
                (f"{mix}.dt_proj.bias", (inner,), "normal"),
                (f"{mix}.A_log", (inner, n), "normal"),
                (f"{mix}.D", (inner,), "ln_scale"),
                (f"{mix}.out_proj.weight", (h, inner), "normal"),
            ]
        elif kind == "memory":
            out += [
                (f"{mix}.in_proj.weight", (inner, h), "normal"),
                (f"{mix}.out_proj.weight", (h, inner), "normal"),
            ]
        else:
            fused, rows = ("Wq", wide) if kind == "cross" else ("Wqkv", wide + 2 * narrow)
            out += [
                (f"{mix}.{fused}.weight", (rows, h), "normal"),
                (f"{mix}.{fused}.bias", (rows,), "normal"),
                (f"{mix}.out_proj.weight", (h, wide), "normal"),
                (f"{mix}.out_proj.bias", (h,), "normal"),
                *[
                    (f"{mix}.inner_cross_attn.{name}", (hd,), "normal")
                    for name in ("lambda_q1", "lambda_k1", "lambda_q2", "lambda_k2")
                ],
                (f"{mix}.inner_cross_attn.subln.weight", (2 * hd,), "ln_scale"),
            ]
        out += [
            (f"{base}.mlp.fc1.weight", (2 * cfg["intermediate_size"], h), "normal"),
            (f"{base}.mlp.fc2.weight", (h, cfg["intermediate_size"]), "normal"),
        ]
    out += [
        ("model.final_layernorm.weight", (h,), "ln_scale"),
        ("model.final_layernorm.bias", (h,), "normal"),
    ]
    return out


# -- parameters a token multiplies by, a layer of each kind (matrix products only) ----------


def mlp_weights(cfg: dict) -> int:
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"]


def mamba_weights(cfg: dict) -> int:
    """in, x, dt and out: the convolution multiplies no matrix."""
    h, inner = cfg["hidden_size"], d_inner(cfg)
    n, rank = cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    return h * 2 * inner + inner * (rank + 2 * n) + rank * inner + inner * h


def memory_weights(cfg: dict) -> int:
    return 2 * cfg["hidden_size"] * d_inner(cfg)


def key_value_weights(cfg: dict) -> int:
    return 2 * cfg["hidden_size"] * cfg["num_key_value_heads"] * head_dim(cfg)


def query_out_weights(cfg: dict) -> int:
    return 2 * cfg["hidden_size"] * cfg["num_attention_heads"] * head_dim(cfg)


def causal_pairs(seq: int) -> int:
    """(query, key <= query) pairs of one call of ``seq`` slots."""
    return seq * (seq + 1) // 2


def band_pairs(cfg: dict, seq: int) -> int:
    """Pairs inside the band of a sliding layer: the window's keys, a query's
    own position among them."""
    k = min(cfg["sliding_window"], seq)
    return k * (k + 1) // 2 + (seq - k) * k


def _pair_flops(cfg: dict) -> int:
    """q.k over a head's dims and probs.v over the pair's value lanes, every
    query head."""
    return 2 * cfg["num_attention_heads"] * 3 * head_dim(cfg)


def window_attention_flops(cfg: dict, rows: int, seq: int) -> int:
    """The two products for the pairs INSIDE THE BAND, the sliding layers."""
    return layers_of(cfg, "sliding") * rows * band_pairs(cfg, seq) * _pair_flops(cfg)


def window_attention_bytes(cfg: dict, rows: int, seq: int, itemsize: int = 2) -> int:
    """q, k, v and the context, each once: a pair's context is 2 hd wide a
    query head."""
    hd = head_dim(cfg)
    width = 3 * cfg["num_attention_heads"] * hd + 2 * cfg["num_key_value_heads"] * hd
    return layers_of(cfg, "sliding") * rows * seq * width * itemsize


def selective_scan_flops(cfg: dict, rows: int, seq: int) -> int:
    """The recurrence as written, every Mamba layer."""
    return (
        layers_of(cfg, "mamba") * rows * seq * d_inner(cfg) * cfg["mamba_d_state"] * SCAN_FLOPS
    )


def selective_scan_bytes(cfg: dict, rows: int, seq: int, itemsize: int = 2) -> int:
    """xc and dt read, m written, a channel; B and C read, a state; each once."""
    width = 3 * d_inner(cfg) + 2 * cfg["mamba_d_state"]
    return layers_of(cfg, "mamba") * rows * seq * width * itemsize


def self_decoder_token_flops(cfg: dict) -> int:
    """Matrix products of one token through layers 0 .. K - 1 and layer K's
    key and value products."""
    mamba, sliding = layers_of(cfg, "mamba"), layers_of(cfg, "sliding")
    return 2 * (
        mamba * mamba_weights(cfg)
        + sliding * (query_out_weights(cfg) + key_value_weights(cfg))
        + (mamba + sliding) * mlp_weights(cfg)
        + key_value_weights(cfg)
    )


def cross_decoder_row_flops(cfg: dict, keys: int) -> int:
    """One position through layer K's own attention and every layer behind
    it: a row of scores against ``keys`` keys on the full layer and each cross
    layer."""
    attending = 1 + layers_of(cfg, "cross")
    memory = layers_of(cfg, "memory")
    return (
        2 * (
            attending * query_out_weights(cfg)
            + memory * memory_weights(cfg)
            + (attending + memory) * mlp_weights(cfg)
        )
        + attending * keys * _pair_flops(cfg)
    )


def forward_flops(cfg: dict, rows: int, seq: int, held_pairs=None) -> float:
    """One judge dispatch of ``rows`` calls in a bucket of ``seq`` slots (no
    experts: ``held_pairs`` is the readers' and counts nothing)."""
    per_token = self_decoder_token_flops(cfg)
    prefill = (
        rows * seq * per_token
        + window_attention_flops(cfg, rows, seq)
        + selective_scan_flops(cfg, rows, seq)
    )
    # the decoded token through the self decoder: its products, one scan step
    # a Mamba layer, one row against the window's keys a sliding layer
    decode = rows * (
        per_token
        + selective_scan_flops(cfg, 1, 1)
        + layers_of(cfg, "sliding") * min(cfg["sliding_window"], seq + 1) * _pair_flops(cfg)
    )
    # the two positions read: seq keys at the first, one more at the second
    behind = rows * (cross_decoder_row_flops(cfg, seq) + cross_decoder_row_flops(cfg, seq + 1))
    heads_read = 2 * rows * 2 * cfg["hidden_size"] * cfg["vocab_size"]
    return prefill + decode + behind + heads_read
