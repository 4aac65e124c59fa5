"""forward.share.experts.judge: per cent of the judge programs' device time under
the ``experts`` scopes (``judge_scopes.GROUPS``)."""

import judge_scopes


def reduce(ctx):
    return judge_scopes.share(ctx, "experts")
