"""forward.share.unscoped.judge: per cent of the judge programs' device time under
the ``unscoped`` scopes (``judge_scopes.GROUPS``)."""

import judge_scopes


def reduce(ctx):
    return judge_scopes.share(ctx, "unscoped")
