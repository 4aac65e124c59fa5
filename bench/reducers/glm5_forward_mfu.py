"""forward.mfu.glm5: model operations of the judge programs inside the trace
over their device time times the bf16 peak (``qnext_scopes.mfu``, which asks
the configuration's family): the indexer over the causal pairs, attention over
the SELECTED pairs, the experts from the counted pairs held here."""

import glm5_scopes
import qnext_scopes


def reduce(ctx):
    if not glm5_scopes.selects(ctx):  # a program without the selection
        return None
    return qnext_scopes.mfu(ctx)
