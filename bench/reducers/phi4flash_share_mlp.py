"""forward.share.mlp.phi4flash: per cent of the judge programs' device time under
the ``mlp`` scopes (``phi4flash_scopes.GROUPS``)."""

import phi4flash_scopes


def reduce(ctx):
    return phi4flash_scopes.share(ctx, "mlp")
