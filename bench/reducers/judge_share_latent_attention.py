"""forward.share.latent_attention.judge: per cent of the judge programs' device time under
the ``latent_attention`` scopes (``judge_scopes.GROUPS``)."""

import judge_scopes


def reduce(ctx):
    return judge_scopes.share(ctx, "latent_attention")
