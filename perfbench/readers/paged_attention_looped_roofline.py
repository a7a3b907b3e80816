"""Least time by shapes over device time of the kernel paged_attention_decode
in the traced part of the window, in %, for a model whose layers run several
times over a pool of the configuration's own type. Least time: for every
traced decode step, one call a cache layer (`total_ut_steps` x
`num_hidden_layers`), each reading the K/V pages every slot in use holds at
`pool_dtype`'s bytes an element (rooflines.paged_attention_decode_work, the
same work whatever implements the call), against the published peaks."""

from perfbench import rooflines, trace as trace_mod

KERNEL = "paged_attention_decode"
BYTES = {"bfloat16": 2, "float32": 4}


def read(ctx, meta):
    if ctx.trace is None or not ctx.facts.get("traced_contexts"):
        return None
    seconds, events = trace_mod.time_by_substring(ctx.trace.ops(), (KERNEL,))
    if not events:
        return None
    c = ctx.cell.config
    calls = int(c["total_ut_steps"]) * int(c["num_hidden_layers"])
    least = 0.0
    for contexts in ctx.facts["traced_contexts"]:
        if not contexts:
            continue
        work = rooflines.paged_attention_decode_work(
            [n + 1 for n in contexts], int(c["num_attention_heads"]),
            int(c["head_dim"]), int(c["session"]["page_size"]),
            dtype_bytes=BYTES[c["pool_dtype"]],
        )
        least += calls * rooflines.least_time(work["flops"], work["bytes"], ctx.peaks)[0]
    return 100.0 * least / seconds
