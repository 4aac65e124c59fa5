"""forward.share.mlp.*: per cent of the model programs' device time under
the ``mlp`` scopes (``scope_time.GROUPS``)."""

import scope_time


def reduce(ctx):
    return scope_time.share(ctx, "mlp")
