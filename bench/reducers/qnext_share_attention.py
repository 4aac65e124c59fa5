"""forward.share.attention.qnext: per cent of the judge programs' device time under
the ``attention`` scopes (``qnext_scopes.GROUPS``)."""

import qnext_scopes


def reduce(ctx):
    return qnext_scopes.share(ctx, "attention")
