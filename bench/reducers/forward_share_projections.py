"""forward.share.projections.*: per cent of the model programs' device time under
the ``projections`` scopes (``scope_time.GROUPS``)."""

import scope_time


def reduce(ctx):
    return scope_time.share(ctx, "projections")
