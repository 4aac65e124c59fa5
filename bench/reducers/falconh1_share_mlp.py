"""forward.share.mlp.falconh1: per cent of the judge programs' device time under
the ``mlp`` scopes (``falconh1_scopes.GROUPS``)."""

import falconh1_scopes


def reduce(ctx):
    return falconh1_scopes.share(ctx, "mlp")
