"""forward.share.cross_decoder.phi4flash: per cent of the judge programs' device time under
the ``cross_decoder`` scopes (``phi4flash_scopes.GROUPS``)."""

import phi4flash_scopes


def reduce(ctx):
    return phi4flash_scopes.share(ctx, "cross_decoder")
