"""kernel.head_norm_roofline.trinity: the head norms (and, on a sliding layer,
the turn) where the products left q and k, against their roofline.

A MEMORY roofline: q and k read and written once a layer (0.7 GB each way a
dispatch, 1.7 ms at the HBM's peak); the arithmetic is a few operations a
value and no matrix product, so the family counts none.  Over
``head_norm_turn``'s own events (``ops/head_norm.py``)."""

import trinity_scopes

KERNELS = ("head_norm_turn",)


def reduce(ctx):
    return trinity_scopes.roofline(ctx, KERNELS, "head_norm")
