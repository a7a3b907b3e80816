"""Least time by shapes over device time of the kernels gru_seq_fwd and
gru_seq_bwd in the traced part of the window, in %: one forward and one
backward call per encoder direction per step, T x B x H from the
configuration (perfbench/rooflines.py says what each call must do)."""

from perfbench import rooflines, trace as trace_mod


def read(ctx, meta):
    if ctx.trace is None:
        return None
    ops = ctx.trace.ops()
    t_fwd, n_fwd = trace_mod.time_by_substring(ops, ("gru_seq_fwd",))
    t_bwd, n_bwd = trace_mod.time_by_substring(ops, ("gru_seq_bwd",))
    if not (n_fwd or n_bwd):
        return None
    c = ctx.cell.config
    t, b, h = int(c["src_len"]), int(ctx.facts["rows"]), int(c["hidden_dim"])
    least = 0.0
    for calls, backward in ((n_fwd, False), (n_bwd, True)):
        work = rooflines.gru_seq_work(t, b, h, backward, dtype_bytes=2)
        least += calls * rooflines.least_time(work["flops"], work["bytes"], ctx.peaks)[0]
    return 100.0 * least / (t_fwd + t_bwd)
