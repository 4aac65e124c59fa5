"""forward.mfu.qnext: model operations of the judge programs inside the trace
over their device time times the bf16 peak; the experts' operations from the
counted pairs held here (``qnext_scopes.mfu``)."""

import qnext_scopes


def reduce(ctx):
    return qnext_scopes.mfu(ctx)
