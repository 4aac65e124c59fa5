"""forward.share.unscoped.qnext: per cent of the judge programs' device time under
the ``unscoped`` scopes (``qnext_scopes.GROUPS``)."""

import qnext_scopes


def reduce(ctx):
    return qnext_scopes.share(ctx, "unscoped")
