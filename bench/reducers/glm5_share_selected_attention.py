"""forward.share.selected_attention.glm5: per cent of the judge programs' device time under
the ``selected_attention`` scopes (``glm5_scopes.GROUPS``)."""

import glm5_scopes


def reduce(ctx):
    return glm5_scopes.share(ctx, "selected_attention")
