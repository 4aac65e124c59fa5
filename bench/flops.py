"""Operations and bytes of the models' forward passes, as functions of shape.

Counted for the algorithm, not for an implementation: a multiply-add is two
operations; only matrix products are counted (LayerNorm, softmax, GELU and
the vote are a fraction of a percent at these widths).  Padding is counted
where it is computed: callers pass the SLOT shape (rows x sequence bucket) of
a dispatch, because the padded path runs the encoder over every slot.

A family's own count is its file's ``forward_flops(cfg, rows, seq)``
(``bench/families/<family>.py``); what families share is here.
"""

from __future__ import annotations

import byname


def encoder_layer_flops(rows: int, seq: int, hidden: int, inter: int) -> int:
    """One transformer encoder layer over ``rows`` sequences of ``seq``."""
    tokens = rows * seq
    projections = 4 * 2 * tokens * hidden * hidden  # q, k, v, output
    attention = 2 * 2 * rows * seq * seq * hidden  # q k^T and probs v
    mlp = 2 * 2 * tokens * hidden * inter
    return projections + attention + mlp


def forward_flops(family: str, cfg: dict, rows: int, seq: int) -> int:
    return byname.module("families", family).forward_flops(cfg, rows, seq)
