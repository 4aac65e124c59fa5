"""forward.share.full_attention.trinity: per cent of the judge programs' device time under
the ``full_attention`` scopes (``trinity_scopes.GROUPS``)."""

import trinity_scopes


def reduce(ctx):
    return trinity_scopes.share(ctx, "full_attention")
