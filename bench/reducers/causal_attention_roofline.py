"""kernel.causal_attention_roofline.judge: the causal blockwise attention
kernel against its roofline.

Compute-bound: a layer's causal scores and weighted values at 8192 tokens are
0.69 TFLOP a call (3.5 ms of the chip's arithmetic) against 0.34 GB of q, k, v
and the context (0.41 ms of its memory).  Operations and bytes are the
family's (``bench/families/glm4_moe_lite.py``: the CAUSAL half, every layer);
the time is the kernel's own events (``causal_attention_blockwise``, the
``jax.jit`` that holds the ``pallas_call``): the q/k assembly around it is in
``forward.share.projections.judge``.
"""

import byname
import judge_scopes

KERNELS = ("causal_attention_blockwise",)


def reduce(ctx):
    family = byname.module("families", ctx["config"]["family"])
    return judge_scopes.kernel_roofline(
        ctx, KERNELS, family.causal_attention_flops, family.causal_attention_bytes
    )
