"""forward.share.unscoped.falconh1: per cent of the judge programs' device time under
the ``unscoped`` scopes (``falconh1_scopes.GROUPS``)."""

import falconh1_scopes


def reduce(ctx):
    return falconh1_scopes.share(ctx, "unscoped")
