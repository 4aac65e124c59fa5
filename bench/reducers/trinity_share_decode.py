"""forward.share.decode.trinity: per cent of the judge programs' device time under
the ``decode`` scopes (``trinity_scopes.GROUPS``)."""

import trinity_scopes


def reduce(ctx):
    return trinity_scopes.share(ctx, "decode")
