"""Operations and bytes of the models' forward passes, as functions of shape.

Counted for the algorithm, not for an implementation: a multiply-add is two
operations; only matrix products are counted (LayerNorm, softmax, GELU and
the vote are a fraction of a percent at these widths).  Padding is counted
where it is computed: callers pass the SLOT shape (rows x sequence bucket) of
a dispatch, because the padded path runs the encoder over every slot.
"""

from __future__ import annotations


def encoder_layer_flops(rows: int, seq: int, hidden: int, inter: int) -> int:
    """One transformer encoder layer over ``rows`` sequences of ``seq``."""
    tokens = rows * seq
    projections = 4 * 2 * tokens * hidden * hidden  # q, k, v, output
    attention = 2 * 2 * rows * seq * seq * hidden  # q k^T and probs v
    mlp = 2 * 2 * tokens * hidden * inter
    return projections + attention + mlp


def bert_forward_flops(cfg: dict, rows: int, seq: int) -> int:
    return cfg["num_hidden_layers"] * encoder_layer_flops(
        rows, seq, cfg["hidden_size"], cfg["intermediate_size"]
    )


def deberta_layer_flops(rows: int, seq: int, hidden: int, inter: int, span: int) -> int:
    """DeBERTa-v3 layer: the BERT layer plus disentangled attention's two
    extra score products (content-to-position and position-to-content, each
    rows x seq x 2*span x hidden) and the shared projections of the 2*span
    relative embeddings through the key and query matrices."""
    base = encoder_layer_flops(rows, seq, hidden, inter)
    c2p_p2c = 2 * 2 * rows * seq * (2 * span) * hidden
    rel_proj = 2 * 2 * (2 * span) * hidden * hidden
    return base + c2p_p2c + rel_proj


def deberta_forward_flops(cfg: dict, rows: int, seq: int) -> int:
    span = min(cfg["position_buckets"], seq)
    head = 2 * rows * cfg["hidden_size"] * cfg["hidden_size"]  # pooler
    return head + cfg["num_hidden_layers"] * deberta_layer_flops(
        rows, seq, cfg["hidden_size"], cfg["intermediate_size"], span
    )


FORWARD = {"bert": bert_forward_flops, "deberta-v2": deberta_forward_flops}


def forward_flops(family: str, cfg: dict, rows: int, seq: int) -> int:
    return FORWARD[family](cfg, rows, seq)
