"""forward.share.decode.dots3: per cent of the judge programs' device time under
the ``decode`` scopes (``dots3_scopes.GROUPS``)."""

import dots3_scopes


def reduce(ctx):
    return dots3_scopes.share(ctx, "decode")
