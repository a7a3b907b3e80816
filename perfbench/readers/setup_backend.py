"""Seconds of set-up spent in the backend's compile call: the union, per
thread, of `compile.backend` spans that ended before the window began. On a
warm compile cache the time to get every executable back; on a cold one,
XLA."""

from perfbench import spans


def read(ctx, meta):
    return spans.setup_seconds(ctx, ("compile.backend",))
