"""1 minus the union of device-op intervals over the traced window, in %
(the busiest chips' mean on four). Never 0 by construction of a real trace;
None where no trace was taken."""


def read(ctx, meta):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.idle_share
