"""Family ``deberta-v2``: a ``DebertaV2ForSequenceClassification`` state dict
(v3 layout: shared position projections, relative embeddings with their
LayerNorm, context pooler, one-logit classifier), and the operations of its
forward.  The list's order is the checkpoint (see ``families/bert.py``)."""

import flops


def tensors(cfg: dict) -> list:
    h, inter = cfg["hidden_size"], cfg["intermediate_size"]
    rel = 2 * (cfg["position_buckets"] or cfg["max_relative_positions"])
    out = [
        ("deberta.embeddings.word_embeddings.weight", (cfg["vocab_size"], h), "normal"),
        ("deberta.embeddings.LayerNorm.weight", (h,), "ln_scale"),
        ("deberta.embeddings.LayerNorm.bias", (h,), "normal"),
        ("deberta.encoder.rel_embeddings.weight", (rel, h), "normal"),
        ("deberta.encoder.LayerNorm.weight", (h,), "ln_scale"),
        ("deberta.encoder.LayerNorm.bias", (h,), "normal"),
    ]
    for i in range(cfg["num_hidden_layers"]):
        base = f"deberta.encoder.layer.{i}"
        for name, shape in (
            ("attention.self.query_proj", (h, h)),
            ("attention.self.key_proj", (h, h)),
            ("attention.self.value_proj", (h, h)),
            ("attention.output.dense", (h, h)),
            ("intermediate.dense", (inter, h)),
            ("output.dense", (h, inter)),
        ):
            out.append((f"{base}.{name}.weight", shape, "normal"))
            out.append((f"{base}.{name}.bias", (shape[0],), "normal"))
        for name in ("attention.output.LayerNorm", "output.LayerNorm"):
            out.append((f"{base}.{name}.weight", (h,), "ln_scale"))
            out.append((f"{base}.{name}.bias", (h,), "normal"))
    out += [
        ("pooler.dense.weight", (h, h), "normal"),
        ("pooler.dense.bias", (h,), "normal"),
        ("classifier.weight", (1, h), "normal"),
        ("classifier.bias", (1,), "normal"),
    ]
    return out


def layer_flops(rows: int, seq: int, hidden: int, inter: int, span: int) -> int:
    """DeBERTa-v3 layer: the BERT layer plus disentangled attention's two
    extra score products (content-to-position and position-to-content, each
    rows x seq x 2*span x hidden) and the shared projections of the 2*span
    relative embeddings through the key and query matrices."""
    base = flops.encoder_layer_flops(rows, seq, hidden, inter)
    c2p_p2c = 2 * 2 * rows * seq * (2 * span) * hidden
    rel_proj = 2 * 2 * (2 * span) * hidden * hidden
    return base + c2p_p2c + rel_proj


def forward_flops(cfg: dict, rows: int, seq: int) -> int:
    span = min(cfg["position_buckets"], seq)
    head = 2 * rows * cfg["hidden_size"] * cfg["hidden_size"]  # pooler
    return head + cfg["num_hidden_layers"] * layer_flops(
        rows, seq, cfg["hidden_size"], cfg["intermediate_size"], span
    )
