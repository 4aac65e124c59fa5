"""kernel.gated_delta_roofline.qnext: the chunked gated delta rule against its
roofline.

Memory-bound as the algorithm is counted: the recurrence is three products of
128 x 128 a position and a value head, 77 GFLOP a linear layer a dispatch of
3 x 8192 slots (0.39 ms of the chip's arithmetic) against 0.61 GB of q, k, v,
the output and the gates (0.74 ms of its memory).  Operations and bytes are
the family's (``bench/families/qwen3_next.py``: the RECURRENT form, whatever
the chunk; what a chunked form multiplies inside a chunk is the kernel's cost
and reads as distance from the roofline); the time is the kernel's own events
(``gated_delta_chunked``, the ``jax.jit`` that holds the ``pallas_call``):
the convolution, the gates and the running sum around it are in
``forward.share.linear_attention.qnext``.
"""

import judge_scopes
import qnext_scopes

KERNELS = ("gated_delta_chunked",)


def reduce(ctx):
    family = qnext_scopes.family_of(ctx)
    if not hasattr(family, "gated_delta_flops"):
        return None
    return judge_scopes.kernel_roofline(
        ctx, KERNELS, family.gated_delta_flops, family.gated_delta_bytes
    )
