"""device.idle_with_work_share: the device idle while a request was in
flight, per cent of the traced window (``scope_time.idle_with_work``)."""

import scope_time


def reduce(ctx):
    return scope_time.idle_with_work(ctx)
