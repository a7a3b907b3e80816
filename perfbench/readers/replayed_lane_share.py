"""Sum of `replaying` over sum of `slots` of the window's `serve.decode`
spans, in %: decode lanes dispatched that could complete no token because
they rebuild the K/V of a request the page pool had preempted.
`decode_live_slots` counts such a lane as live."""

from perfbench import serve_spans


def read(ctx, meta):
    win = serve_spans.window(ctx)
    if win is None:
        return None
    slots = sum(serve_spans.attr(r, "slots") for r in win.decodes)
    if not slots:
        return None
    return 100.0 * sum(serve_spans.attr(r, "replaying") for r in win.decodes) / slots
