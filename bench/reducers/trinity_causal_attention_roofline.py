"""kernel.causal_attention_roofline.trinity: the full layer's grouped-query
attention against its roofline: the CAUSAL half of 16,384 slots (134,225,920
pairs a call) x 48 heads x (128 + 128) x 2 = 9.9 TFLOP a dispatch against
0.35 GB; over ``causal_attention_blockwise``'s own events (six query heads
read one key head's blocks through the index map)."""

import trinity_scopes

KERNELS = ("causal_attention_blockwise",)


def reduce(ctx):
    return trinity_scopes.roofline(ctx, KERNELS, "causal_attention")
