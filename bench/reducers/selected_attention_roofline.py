"""kernel.selected_attention_roofline.glm5: attention over the keys a query
selected, against its roofline.

Compute-bound: the SELECTED pairs of a panel (14.7M a call of 8192 slots at
2048 keys a query, 43.7% of the causal ones) times 64 heads x 512 dims x 2 are
2.89 TFLOP a layer a dispatch (14.7 ms of the chip's arithmetic) against 3.2 GB
of q, k, v and the context (3.9 ms).  Operations and bytes are the family's
(``bench/families/glm_moe_dsa.py``: the mathematics, whatever the kernel
multiplies), so a kernel that multiplies every causal pair and masks reads
under 43.7 and none can read over 100.  The time is the kernel's own events
(``causal_attention_blockwise``, which this decoder hands a selection).
"""

import glm5_scopes

KERNELS = ("causal_attention_blockwise",)


def reduce(ctx):
    return glm5_scopes.roofline(ctx, KERNELS, "selected_attention")
