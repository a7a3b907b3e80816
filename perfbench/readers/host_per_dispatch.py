"""The window's `train.pass` span's self time over its `train.dispatch`
spans, in ms: the pass's duration less what its `train.input_wait`,
`train.handler`, `train.guard_poll`, `train.cost_fetch` and
`train.checkpoint` children cover (waiting for data, the caller, the device
or a save). `train.dispatch` stays in: stacking on the chip and enqueueing IS the
loop's work. The host work one dispatch costs, which bounds the step once
the device gets faster."""

from perfbench import spans


def read(ctx, meta):
    win = spans.window(ctx)
    if win is None:
        return None
    dispatches = win.count("train.dispatch")
    if not dispatches:
        return None
    own_ns = win.duration_ns - win.child_ns(spans.NOT_THE_LOOPS_OWN)
    return 1e-6 * own_ns / dispatches
