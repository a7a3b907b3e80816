"""Summed duration of the window's `serve.step` spans that ran a prefill
(`admitted` + `chunks` > 0) over the window's length, in %: the TIME twin of
`prefill_step_share`, which counts steps. A step belongs to the window in
which it ended, whole."""

from perfbench import serve_spans


def read(ctx, meta):
    win = serve_spans.window(ctx)
    if win is None:
        return None
    return 100.0 * sum(r[serve_spans.DUR] for r in win.prefilling) / win.duration_ns
