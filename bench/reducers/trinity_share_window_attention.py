"""forward.share.window_attention.trinity: per cent of the judge programs' device time under
the ``window_attention`` scopes (``trinity_scopes.GROUPS``)."""

import trinity_scopes


def reduce(ctx):
    return trinity_scopes.share(ctx, "window_attention")
