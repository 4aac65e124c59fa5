"""forward.share.linear_attention.qnext: per cent of the judge programs' device time under
the ``linear_attention`` scopes (``qnext_scopes.GROUPS``)."""

import qnext_scopes


def reduce(ctx):
    return qnext_scopes.share(ctx, "linear_attention")
