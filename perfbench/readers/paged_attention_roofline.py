"""Least time by shapes over device time of the kernel paged_attention_decode
in the traced part of the window, in %. Least time: for every traced decode
step and layer, the bytes of the K/V pages each slot in use really reads and
the FLOPs of the attention itself (perfbench/rooflines.py), against the
published peaks; at decode the bytes bound it."""

from perfbench import rooflines, trace as trace_mod

KERNEL = "paged_attention_decode"


def read(ctx, meta):
    if ctx.trace is None or not ctx.facts.get("traced_contexts"):
        return None
    seconds, events = trace_mod.time_by_substring(ctx.trace.ops(), (KERNEL,))
    if not events:
        return None
    c = ctx.cell.config
    least = 0.0
    for contexts in ctx.facts["traced_contexts"]:
        if not contexts:
            continue
        work = rooflines.paged_attention_decode_work(
            [n + 1 for n in contexts], int(c["num_attention_heads"]),
            int(c["head_dim"]), int(c["session"]["page_size"]),
        )
        least += int(c["num_hidden_layers"]) * rooflines.least_time(
            work["flops"], work["bytes"], ctx.peaks
        )[0]
    return 100.0 * least / seconds
