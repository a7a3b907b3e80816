"""Sum of the `train.input_wait` spans inside the window's `train.pass`
over the pass's duration, in %: the share of the window in which the train
thread waited for its reader's next item (perfbench/spans.py says how the
window is found)."""

from perfbench import spans


def read(ctx, meta):
    win = spans.window(ctx)
    if win is None or not win.duration_ns:
        return None
    return 100.0 * win.child_ns(("train.input_wait",)) / win.duration_ns
