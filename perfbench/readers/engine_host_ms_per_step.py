"""Median, in ms, over the window's `serve.step` spans that dispatched a
decode step and ran no prefill (`decoded` 1, `admitted` 0, `chunks` 0), of
the span's duration less its `wait_ns`: the host's own work in a decode-only
engine step (lanes, page growth, the signature record, the bookkeeping of
the step before), without its wait for the device. It has to fit under the
device's step (`decode_step_ms`); where it does not, the device idles for
the difference every step."""

import statistics

from perfbench import serve_spans


def read(ctx, meta):
    win = serve_spans.window(ctx)
    if win is None or not win.decode_only:
        return None
    return 1e-6 * statistics.median(
        r[serve_spans.DUR] - serve_spans.attr(r, "wait_ns") for r in win.decode_only
    )
