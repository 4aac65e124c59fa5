"""forward.share.attention.*: per cent of the model programs' device time under
the ``attention`` scopes (``scope_time.GROUPS``)."""

import scope_time


def reduce(ctx):
    return scope_time.share(ctx, "attention")
