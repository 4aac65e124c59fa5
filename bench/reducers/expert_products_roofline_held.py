"""kernel.expert_products_roofline.qnext: the routed experts' grouped products
against their roofline, over the pairs that reached an expert HELD here.

Operations and bytes are the family's with ``held_pairs`` from the program's
counter (``judge.expert_pairs_here`` a dispatch: ``qnext_scopes.held_pairs``),
not ``num_experts_per_tok`` a token: a chip that holds a quarter of the
experts sees a quarter of the pairs only on average.  The time is the
kernel's own events (``grouped_expert_product``); the gathers into and out of
the padded layout are in ``forward.share.experts.qnext``.
"""

import judge_scopes
import qnext_scopes

KERNELS = ("grouped_expert_product",)


def reduce(ctx):
    family = qnext_scopes.family_of(ctx)
    flops = qnext_scopes.with_pairs(ctx, family.expert_products_flops)
    moved = qnext_scopes.with_pairs(ctx, family.expert_products_bytes)
    if flops is None:
        return None
    return judge_scopes.kernel_roofline(ctx, KERNELS, flops, moved)
