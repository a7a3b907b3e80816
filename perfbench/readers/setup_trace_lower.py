"""Seconds of set-up spent tracing functions to jaxprs and lowering them to
MLIR: the union, per thread, of `compile.trace` and `compile.lower` spans
that ended before the window began. Python work that a warm compile cache
does not spare: fewer or smaller jits shorten it."""

from perfbench import spans


def read(ctx, meta):
    return spans.setup_seconds(ctx, ("compile.trace", "compile.lower"))
