"""Least time by shapes over device time of the kernel paged_attention_decode
in the traced part of the window, in %, for a decoder of window and full
attention layers. Least time: for every traced decode step, one call a layer,
a full layer reading the K/V of each slot's whole context and a window layer
that of its last `sliding_window` positions, every slot in use counted, at
`pool_dtype`'s bytes an element (window_moe_counts.decode_attention_work:
the same work whatever implements the call, so a walk over more than the
window reads low), against the published peaks. Nothing to read where the
configuration has no window or the trace holds no such kernel."""

from perfbench import trace as trace_mod
from perfbench import window_moe_counts

KERNEL = "paged_attention_decode"


def read(ctx, meta):
    c = ctx.cell.config
    if ctx.trace is None or not ctx.facts.get("traced_contexts") or "sliding_window" not in c:
        return None
    seconds, events = trace_mod.time_by_substring(ctx.trace.ops(), (KERNEL,))
    if not events or not seconds:
        return None
    least = sum(
        window_moe_counts.decode_attention_least_time(c, [n + 1 for n in contexts], ctx.peaks)
        for contexts in ctx.facts["traced_contexts"] if contexts
    )
    return 100.0 * least / seconds
