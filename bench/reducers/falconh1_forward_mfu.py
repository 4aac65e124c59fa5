"""forward.mfu.falconh1: model operations of the judge programs inside the trace
over their device time times the bf16 peak (``qnext_scopes.mfu``, which asks
the configuration's family): every layer's products at every slot (the MLP's
are 77% of them), the causal half of every layer's attention, the state-space
dual at the published chunk, the decoded token and the two head reads."""

import falconh1_scopes


def reduce(ctx):
    return falconh1_scopes.mfu(ctx)
