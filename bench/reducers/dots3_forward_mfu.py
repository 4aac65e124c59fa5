"""forward.mfu.dots3: model operations of the judge programs inside the trace
over their device time times the bf16 peak (``qnext_scopes.mfu``, which asks
the configuration's family): the indexer over the causal pairs of the full
layers, their attention over the SELECTED pairs, the sliding layers' over the
pairs INSIDE THE BAND, all at the published head widths, the experts from the
counted pairs held here."""

import dots3_scopes


def reduce(ctx):
    return dots3_scopes.mfu(ctx)
