"""Pool bytes the slots a decode step runs hold over the context tokens
their attention reads, a mean over the program's `serve.decode` flight spans
that ENDED in the counted window: the span's `pages_full` of every full
layer and `pages_window` of every window layer (K and V, whole pages) over
its `context_tokens` (window_moe_counts.kv_bytes_held). Nothing to read where
the program's spans carry no such attributes (a program without window
layers' pages), the ring dropped them, or the builder gave no window."""

from perfbench import spans, window_moe_counts

SPAN = "serve.decode"


def read(ctx, meta):
    edges = ctx.facts.get("decode_window_ns")
    if not edges or "layer_types" not in ctx.cell.config:
        return None
    rows = spans.ring_rows()
    if rows is None:
        return None
    t0, t_end = edges
    ratios = []
    for r in rows:
        a = r[spans.ATTRS] or {}
        if (r[spans.NAME] != SPAN or not t0 < r[spans.START] + r[spans.DUR] <= t_end
                or not a.get("context_tokens") or "pages_window" not in a):
            continue
        held = window_moe_counts.kv_bytes_held(ctx.cell.config, a["pages_full"], a["pages_window"])
        ratios.append(held / a["context_tokens"])
    return sum(ratios) / len(ratios) if ratios else None
