"""kernel.expert_products_roofline.judge: the routed experts' grouped products
against their roofline.

Compute-bound at a prefill: a sparse layer's three products over 98,304
(token, expert) pairs are 1.86 TFLOP a dispatch (9.4 ms) against 1.2 GB of
weights and 2.1 GB of rows (4.1 ms).  Operations and bytes are the family's
(4 experts a token, not the 64 held; padding rows of the tiled layout are the
kernel's cost and not the algorithm's); the time is the kernel's own events
(``grouped_expert_product``): the gathers into and out of the padded layout
are in ``forward.share.experts.judge``.
"""

import byname
import judge_scopes

KERNELS = ("grouped_expert_product",)


def reduce(ctx):
    family = byname.module("families", ctx["config"]["family"])
    return judge_scopes.kernel_roofline(
        ctx, KERNELS, family.expert_products_flops, family.expert_products_bytes
    )
