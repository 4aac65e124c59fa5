"""Family ``bert``: a HuggingFace ``BertModel`` state dict, and the
operations of its forward.

The list's ORDER is the checkpoint: ``checkpoints._draw`` keys each tensor's
generator by its position here.  ``kind`` is ``normal`` (weights, biases:
N(0, std)) or ``ln_scale`` (1 + N(0, std)); std is the family's ``INIT_STD``
where it gives one, else BERT's published ``initializer_range`` 0.02.
"""

import flops


def tensors(cfg: dict) -> list:
    """(name, shape, kind) for a BertModel state dict."""
    h, inter = cfg["hidden_size"], cfg["intermediate_size"]
    out = [
        ("embeddings.word_embeddings.weight", (cfg["vocab_size"], h), "normal"),
        (
            "embeddings.position_embeddings.weight",
            (cfg["max_position_embeddings"], h),
            "normal",
        ),
        (
            "embeddings.token_type_embeddings.weight",
            (cfg["type_vocab_size"], h),
            "normal",
        ),
        ("embeddings.LayerNorm.weight", (h,), "ln_scale"),
        ("embeddings.LayerNorm.bias", (h,), "normal"),
    ]
    for i in range(cfg["num_hidden_layers"]):
        base = f"encoder.layer.{i}"
        for name, shape in (
            ("attention.self.query", (h, h)),
            ("attention.self.key", (h, h)),
            ("attention.self.value", (h, h)),
            ("attention.output.dense", (h, h)),
            ("intermediate.dense", (inter, h)),
            ("output.dense", (h, inter)),
        ):
            out.append((f"{base}.{name}.weight", shape, "normal"))
            out.append((f"{base}.{name}.bias", (shape[0],), "normal"))
        for name in ("attention.output.LayerNorm", "output.LayerNorm"):
            out.append((f"{base}.{name}.weight", (h,), "ln_scale"))
            out.append((f"{base}.{name}.bias", (h,), "normal"))
    return out


def forward_flops(cfg: dict, rows: int, seq: int) -> int:
    return cfg["num_hidden_layers"] * flops.encoder_layer_flops(
        rows, seq, cfg["hidden_size"], cfg["intermediate_size"]
    )
