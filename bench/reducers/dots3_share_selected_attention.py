"""forward.share.selected_attention.dots3: per cent of the judge programs' device time under
the ``selected_attention`` scopes (``dots3_scopes.GROUPS``)."""

import dots3_scopes


def reduce(ctx):
    return dots3_scopes.share(ctx, "selected_attention")
