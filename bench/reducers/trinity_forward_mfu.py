"""forward.mfu.trinity: model operations of the judge programs inside the trace
over their device time times the bf16 peak (``qnext_scopes.mfu``, which asks
the configuration's family): the sliding layers' attention over the pairs
INSIDE THE BAND, the full layer's over the causal half, every query head
against its key head's keys, the experts from the counted pairs held here."""

import trinity_scopes


def reduce(ctx):
    return trinity_scopes.mfu(ctx)
