"""kernel.causal_attention_roofline: the causal blockwise attention kernel
against its roofline, in every cell whose family counts it (the first and the
second judge's; the entry's ``workloads`` lists them).

Compute-bound: the first judge's causal scores and weighted values of a layer
at 8192 tokens are 0.69 TFLOP a call (3.5 ms of the chip's arithmetic) against
0.34 GB of q, k, v and the context (0.41 ms of its memory).  Operations and
bytes are the cell's own family's (``bench/families/<family>.py``
``causal_attention_flops`` / ``_bytes``: the CAUSAL half, every layer that attends);
the time is the kernel's own events (``causal_attention_blockwise``, the
``jax.jit`` that holds the ``pallas_call``): the q/k assembly around it is in
the cell's ``forward.share.projections.*``.
"""

import byname
import judge_scopes

KERNELS = ("causal_attention_blockwise",)


def reduce(ctx):
    family = byname.module("families", ctx["config"]["family"])
    return judge_scopes.kernel_roofline(
        ctx, KERNELS, family.causal_attention_flops, family.causal_attention_bytes
    )
