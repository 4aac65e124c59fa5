"""forward.share.decode.phi4flash: per cent of the judge programs' device time under
the ``decode`` scopes (``phi4flash_scopes.GROUPS``)."""

import phi4flash_scopes


def reduce(ctx):
    return phi4flash_scopes.share(ctx, "decode")
