"""forward.share.experts.glm5: per cent of the judge programs' device time under
the ``experts`` scopes (``glm5_scopes.GROUPS``)."""

import glm5_scopes


def reduce(ctx):
    return glm5_scopes.share(ctx, "experts")
